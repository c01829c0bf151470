"""The arithmetic behind each metric file in ``metrics/``.

Every reader takes the run's context (``run.Context``) and returns a number,
or None when the run gave it nothing to read; the harness then leaves the
metric out of the result line.
"""
from __future__ import annotations

import numpy as np


def setup_s(ctx):
    return ctx.setup_s


def ttft_ms(ctx, q):
    if not ctx.latencies:
        return None
    return 1e3 * float(np.percentile(ctx.latencies, q))


def tok_s(ctx):
    """Prompt plus output tokens of the requests completed in the window,
    over the window's wall seconds (a cached prompt token counts: the
    user gets it)."""
    done = sum(r.prompt_len + len(r.tokens) for r in ctx.served)
    return done / ctx.window_s


def roofline(ctx, kernel):
    """Least time the chip could take for the kernel's work, over the
    kernel's device time, in percent.  ``kernel`` is any trace key: the
    paged pair's work comes from ``work.count``, another key's from its
    reference module's ``kernel_work``."""
    if ctx.trace is None or kernel not in ctx.work:
        return None
    t = ctx.trace["kernel_s"].get(kernel, 0.0)
    need = ctx.work[kernel].min_seconds
    if t <= 0 or need <= 0:
        return None
    return 100.0 * need / t


def mfu(ctx):
    """Model FLOPs of the tokens computed in the window (never the cached
    ones) over the traced window's seconds times the chip's peak."""
    if ctx.trace is None or ctx.work["model_flops"] <= 0:
        return None
    return 100.0 * ctx.work["model_flops"] / (ctx.trace["window_s"] * ctx.peak["bf16_flops"])


def idle_share(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
