"""Operations and bytes the algorithm needs, from the requests served.

Counts follow what a request needs, not what the program happens to run:
padding rows, unused table entries and pages past a query's position are
not counted, and a sliding window caps the keys a query sees.  Shapes come
from the widths of the configuration's reference module (``widths(conf)``:
``H``, ``KV``, ``hd``, ``D``, ``V``, each layer's ``windows`` and the
token's ``matmul_flops``); token positions come from each request's
prompt, its cached prefix (alpha) and the prefill pieces the scheduler
splits its uncached part into.
"""
from __future__ import annotations

import collections
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


def _by_window(w):
    """(window, number of layers with it), in order of first appearance."""
    return collections.Counter(w.windows).items()


def prefill_attention(w, q_start: int, n: int) -> Tuple[float, float]:
    """(flops, bytes) of one prefill piece's attention, all layers: n query
    rows at positions q_start.. q_start+n-1 against their visible keys."""
    flops = nbytes = 0.0
    last = q_start + n - 1
    for window, layers in _by_window(w):
        f = 4.0 * w.H * w.hd * _sum_keys(q_start, q_start + n, window)
        first_key = max(0, last - window + 1) if window else 0
        kv_tokens = last + 1 - first_key
        qo = 2 * n * w.H * w.hd * KV_BYTES
        kv = 2 * kv_tokens * w.KV * w.hd * KV_BYTES
        flops += layers * f
        nbytes += layers * float(qo + kv)
    return flops, nbytes


def decode_attention(w, pos: int) -> Tuple[float, float]:
    """(flops, bytes) of one decode token's attention at absolute position
    pos, all layers."""
    flops = nbytes = 0.0
    for window, layers in _by_window(w):
        keys = _keys(pos, window)
        f = 4.0 * w.H * w.hd * keys
        b = 2 * keys * w.KV * w.hd * KV_BYTES + 2 * w.H * w.hd * KV_BYTES
        flops += layers * f
        nbytes += layers * float(b)
    return flops, nbytes


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
            model += f + n * w.matmul_flops
            pos += n
        model += head_flops(w) if ps else 0.0
        for _ in range(r.decoded):
            f, b = decode_attention(w, pos)
            dec.add(f, b, peak_flops, peak_bw)
            model += f + w.matmul_flops + head_flops(w)
            pos += 1
    return {"paged_prefill": pre, "paged_decode": dec, "model_flops": model}
