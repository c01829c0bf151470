"""Operations and bytes the algorithm needs, from the requests served.

Counts follow what a request needs, not what the program happens to run:
padding rows, unused table entries and pages past a query's position are
not counted, and a sliding window caps the keys a query sees.  Shapes come
from the configuration's widths (``model.Widths``); token positions come
from each request's prompt, its cached prefix (alpha) and the prefill
pieces the scheduler splits its uncached part into.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Sequence, Tuple

KV_BYTES = 2           # bfloat16 pool


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0
    min_seconds: float = 0.0       # sum over calls of max(flops/peak, bytes/bw)

    def add(self, flops: float, nbytes: float, peak_flops: float, peak_bw: float):
        self.flops += flops
        self.bytes += nbytes
        self.min_seconds += max(flops / peak_flops, nbytes / peak_bw)


def pieces(segment_lengths: Sequence[int], chunk: int) -> List[int]:
    """Prefill piece sizes: each segment split into ``chunk``-token pieces,
    never across a segment boundary (the serving scheduler's rule)."""
    out = []
    for n in segment_lengths:
        if n <= 0:
            continue
        if chunk <= 0:
            out.append(n)
            continue
        out += [chunk] * (n // chunk) + ([n % chunk] if n % chunk else [])
    if chunk <= 0 and out:
        return [sum(out)]
    return out


def _keys(p: int, window: int) -> int:
    """Keys a query at absolute position p attends to."""
    return min(p + 1, window) if window else p + 1


def _sum_keys(lo: int, hi: int, window: int) -> int:
    """Sum of _keys(p) for p in [lo, hi)."""
    if hi <= lo:
        return 0
    if not window:
        return (lo + 1 + hi) * (hi - lo) // 2
    cut = min(max(window - 1, lo), hi)        # p < window-1 sees p+1 keys
    s = (lo + 1 + cut) * (cut - lo) // 2
    return s + (hi - cut) * window


def prefill_attention(w, q_start: int, n: int) -> Tuple[float, float]:
    """(flops, bytes) of one prefill piece's attention, all layers: n query
    rows at positions q_start.. q_start+n-1 against their visible keys."""
    flops = 4.0 * w.H * w.hd * _sum_keys(q_start, q_start + n, w.window)
    last = q_start + n - 1
    first_key = max(0, last - w.window + 1) if w.window else 0
    kv_tokens = last + 1 - first_key
    qo = 2 * n * w.H * w.hd * KV_BYTES
    kv = 2 * kv_tokens * w.KV * w.hd * KV_BYTES
    return w.L * flops, w.L * float(qo + kv)


def decode_attention(w, pos: int) -> Tuple[float, float]:
    """(flops, bytes) of one decode token's attention at absolute position
    pos, all layers."""
    keys = _keys(pos, w.window)
    flops = 4.0 * w.H * w.hd * keys
    nbytes = 2 * keys * w.KV * w.hd * KV_BYTES + 2 * w.H * w.hd * KV_BYTES
    return w.L * flops, w.L * float(nbytes)


def matmul_flops_per_token(w) -> float:
    """Dense matmul FLOPs of one token through every layer (no LM head)."""
    attn = w.D * w.H * w.hd + 2 * w.D * w.KV * w.hd + w.H * w.hd * w.D
    mlp = 3 * w.D * w.F
    return 2.0 * w.L * (attn + mlp)


def head_flops(w) -> float:
    return 2.0 * w.D * w.V


@dataclasses.dataclass
class Served:
    """What one served request needed: its prompt's segments in order
    (uncached passages, then the question), the tokens of the prompt that
    were cached (alpha), and how many tokens it decoded after the first."""
    segments: List[int]
    alpha: int
    decoded: int


def count(w, served: Iterable[Served], chunk: int, peak_flops: float,
          peak_bw: float) -> dict:
    """Work of a window's requests: the two paged kernels' (flops, bytes,
    least time) and the model's FLOPs."""
    pre, dec = Work(), Work()
    model = 0.0
    for r in served:
        pos = r.alpha
        ps = pieces(r.segments, chunk)
        for n in ps:
            f, b = prefill_attention(w, pos, n)
            pre.add(f, b, peak_flops, peak_bw)
            model += f + n * matmul_flops_per_token(w)
            pos += n
        model += head_flops(w) if ps else 0.0
        for _ in range(r.decoded):
            f, b = decode_attention(w, pos)
            dec.add(f, b, peak_flops, peak_bw)
            model += f + matmul_flops_per_token(w) + head_flops(w)
            pos += 1
    return {"paged_prefill": pre, "paged_decode": dec, "model_flops": model}
