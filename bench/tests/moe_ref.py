"""A reference module for a mixture-of-experts decoder, brought by a
configuration file (``"reference": "moe_ref.py"``) with no edit to the
harness: the shape a later configuration's own module takes.

Each layer is attention (windowed or full, as the configuration's
``layer_types`` say) and a routed feed-forward layer: ``E`` SwiGLU experts,
of which the router's top ``K`` logits pick each token's, weighted by the
softmax over those ``K`` logits.  The plain reference computes every expert
for every token and keeps the routed ones.  Weights follow the program's
layout: ``router`` (L, D, E), ``wg``/``wu`` (L, E, D, F), ``wd`` (L, E, F, D).
Everything but the feed-forward layer comes from ``model.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

import model
import work
from model import _fp8, _normal, _program_norm, _rms, base_key, mm

KERNELS = ("mlp",)            # the program's named scope of the feed-forward layer


@dataclasses.dataclass(frozen=True)
class Widths(model.Widths):
    E: int = 0                                  # experts
    K: int = 0                                  # experts per token
    layer_windows: Tuple[int, ...] = ()

    @property
    def windows(self) -> Tuple[int, ...]:
        return self.layer_windows

    @property
    def ffn_flops(self) -> float:
        """Feed-forward FLOPs of one token in one layer: the router and the
        K routed experts."""
        return 2.0 * (self.D * self.E + self.K * 3 * self.D * self.F)

    @property
    def matmul_flops(self) -> float:
        return self.L * (2.0 * self.attn_weights + self.ffn_flops)


def widths(conf: dict) -> Widths:
    dense = model.widths(conf)
    kinds = conf["layer_types"]
    windows = tuple(dense.window if k == "sliding_attention" else 0 for k in kinds)
    return Widths(**dense.__dict__, E=conf["num_local_experts"],
                  K=conf["num_experts_per_tok"], layer_windows=windows)


def program_fields(w: Widths) -> dict:
    """The program states a window pattern as ``global_every``: layer i is
    full where (i + 1) % global_every == 0.  A pattern it cannot state is
    refused."""
    every = w.windows.index(0) + 1 if 0 in w.windows else 0
    if tuple(w.window if every == 0 or (i + 1) % every else 0
             for i in range(w.L)) != w.windows:
        raise ValueError(f"the program cannot state the windows {w.windows}")
    return dict(model.program_fields(w), family="moe", moe_experts=w.E,
                moe_top_k=w.K, global_every=every)


def layer_weights(w: Widths, base, layer):
    """model.py's attention and norm weights; the experts and the router
    from a key of their own."""
    p = model.layer_weights(w, base, layer)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(base, 2), layer), 4)
    D, F, E = w.D, w.F, w.E
    p.update(router=_normal(ks[0], (D, E), D ** -0.5),
             wg=_normal(ks[1], (E, D, F), D ** -0.5),
             wu=_normal(ks[2], (E, D, F), D ** -0.5),
             wd=_normal(ks[3], (E, F, D), F ** -0.5 * (2 * w.L) ** -0.5))
    return p


def make_params(w: Widths, base):
    g = model.global_weights(w, base)
    blocks = jax.vmap(lambda i: layer_weights(w, base, i))(jnp.arange(w.L))
    blocks["ln1"] = _program_norm(blocks["ln1"])
    blocks["ln2"] = _program_norm(blocks["ln2"])
    out = {"embed": g["embed"], "final_norm": _program_norm(g["final_norm"]),
           "blocks": blocks}
    if not w.tied:
        out["lm_head"] = g["lm_head"]
    return out


def served_params(w: Widths, seed: int):
    return jax.jit(functools.partial(make_params, w))(base_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _ref_layer_weights(w: Widths, base, layer, fp8: bool):
    p = {k: v.astype(jnp.float32) for k, v in layer_weights(w, base, layer).items()}
    if fp8:         # one scale per output column, per expert
        p.update({k: _fp8(p[k], axis=-2)
                  for k in ("wq", "wk", "wv", "wo", "router", "wg", "wu", "wd")})
    return p


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _ref_layer(w: Widths, h, p, window: int, low: bool):
    h = model.attention(w, h, p, window, low)
    x = _rms(h, p["ln2"], w.eps)
    top, pick = jax.lax.top_k(mm(x, p["router"], low), w.K)
    gates = jax.nn.softmax(top, axis=-1)                               # (S, K)
    out = jnp.zeros_like(x)
    for e in range(w.E):
        gate = jnp.sum(jnp.where(pick == e, gates, 0.0), axis=-1)      # (S,)
        f = jax.nn.silu(mm(x, p["wg"][e], low)) * mm(x, p["wu"][e], low)
        out = out + gate[:, None] * mm(f, p["wd"][e], low)
    return h + out


reference_logits = functools.partial(model.reference_logits,
                                     weights=_ref_layer_weights, layer=_ref_layer)


def kernel_work(w: Widths, served, chunk: int, peak_flops: float, peak_bw: float) -> dict:
    """The feed-forward layers' work (scope ``mlp``): each prefill piece and
    each decoded token is one call, which reads the weights of the experts
    its tokens can reach and its tokens' activations in and out."""
    out = work.Work()

    def call(n):
        experts = min(w.E, n * w.K)
        weights = w.D * w.E + experts * 3 * w.D * w.F
        out.add(w.L * n * w.ffn_flops,
                w.L * float(2 * weights + 2 * 2 * n * w.D), peak_flops, peak_bw)

    for r in served:
        for n in work.pieces(r.segments, chunk):
            call(n)
        for _ in range(r.decoded):
            call(1)
    return {"mlp": out}
