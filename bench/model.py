"""Weights from the seed, and the plain reference the served tokens are
checked against: the default reference module of a configuration.

The weights belong to the benchmark: ``served_params`` makes them on the
device in one jitted call, in bfloat16 (the type they are served in), and
hands them to the program in its parameter layout.  The reference imports
nothing of the program.  It regenerates each layer's weights from the same
seed, layer by layer, and runs the decoder the configuration's source
describes (RMSNorm, rotary embeddings in the half-split convention,
grouped-query attention with an optional sliding window, SwiGLU) in float32
at ``HIGHEST`` matmul precision, one sequence at a time.

The control is the same reference computed in float8 (e4m3), the next
precision below the configuration's bfloat16: every matrix rounded with
one scale per output column, and every matmul's activation input, the
attention's included, rounded with one scale per row.

A configuration file may name another module under ``bench/`` as its
``"reference"``; the harness (``run.load_reference``) imports it by path in
place of this one.  Such a module provides what this one does:

* ``widths(conf)``: an object with at least ``L, D, V, H, KV, hd``, the
  per-layer attention ``windows`` (0 for full attention) and
  ``matmul_flops``, the matmul FLOPs one token needs through every layer
  (for experts, the routed ones and the router, never all of them);
* ``program_fields(w)``: the program's ``ModelConfig`` keyword arguments
  other than ``name`` and ``dtype``;
* ``make_params(w, key)`` and ``served_params(w, seed)``: the weights in
  the program's layout;
* ``reference_logits(w, seed, seqs, rows, fp8=False)``;
* optionally ``KERNELS``, further trace keys (kernel names or named-scope
  substrings) whose device time the trace reduction credits, and
  ``kernel_work(w, served, chunk, peak_flops, peak_bw)``, their work as
  ``{key: work.Work}``.

It imports the shared helpers (``base_key``, ``_fp8``, ``_rms``, ``_rope``,
``attention``, ``final_hidden``, ...) from here, and nothing of the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512          # reference attention: query rows per block
PAD = 512              # reference sequence lengths round up to this


@dataclasses.dataclass(frozen=True)
class Widths:
    L: int
    D: int
    F: int
    V: int
    H: int
    KV: int
    hd: int
    theta: float
    eps: float
    window: int
    bias: bool
    tied: bool

    @property
    def windows(self) -> Tuple[int, ...]:
        """Each layer's attention window (0: full attention)."""
        return (self.window,) * self.L

    @property
    def attn_weights(self) -> int:
        """Weights of one layer's q, k, v and output projections."""
        D, Hd, Kd = self.D, self.H * self.hd, self.KV * self.hd
        return D * Hd + 2 * D * Kd + Hd * D

    @property
    def matmul_flops(self) -> float:
        """Dense matmul FLOPs of one token through every layer (no LM head)."""
        return 2.0 * self.L * (self.attn_weights + 3 * self.D * self.F)


def widths(conf: dict) -> Widths:
    """The decoder's sizes from a configuration file (Hugging Face keys)."""
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    window = conf.get("sliding_window") or 0
    if not conf.get("use_sliding_window", True):
        window = 0
    return Widths(
        L=conf["num_hidden_layers"], D=D, F=conf["intermediate_size"],
        V=conf["vocab_size"], H=H, KV=conf["num_key_value_heads"],
        hd=conf.get("head_dim") or D // H, theta=float(conf["rope_theta"]),
        eps=float(conf["rms_norm_eps"]), window=int(window),
        bias=bool(conf["qkv_bias"]), tied=bool(conf["tie_word_embeddings"]))


def program_fields(w: Widths) -> dict:
    """The program's ``ModelConfig`` fields for this decoder."""
    return dict(
        family="dense", n_layers=w.L, d_model=w.D, n_heads=w.H, n_kv_heads=w.KV,
        d_ff=w.F, vocab_size=w.V, head_dim=w.hd, qkv_bias=w.bias, rope_theta=w.theta,
        norm_eps=w.eps, sliding_window=w.window, global_every=0 if w.window else 1,
        tie_embeddings=w.tied)


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, all of its bits counted."""
    k = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def _norm_weight(key, shape):
    g = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    return jnp.clip(g, 0.6, 1.4).astype(jnp.bfloat16)


def layer_weights(w: Widths, base: jax.Array, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights, bfloat16, published convention (a norm
    multiplies by its weight).  Matrices are (in, out)."""
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(base, 1), layer), 12)
    D, F, Hd, Kd = w.D, w.F, w.H * w.hd, w.KV * w.hd
    out_std = (2 * w.L) ** -0.5
    p = {
        "ln1": _norm_weight(ks[0], (D,)),
        "wq": _normal(ks[1], (D, Hd), D ** -0.5),
        "wk": _normal(ks[2], (D, Kd), D ** -0.5),
        "wv": _normal(ks[3], (D, Kd), D ** -0.5),
        "wo": _normal(ks[4], (Hd, D), Hd ** -0.5 * out_std),
        "ln2": _norm_weight(ks[5], (D,)),
        "wg": _normal(ks[6], (D, F), D ** -0.5),
        "wu": _normal(ks[7], (D, F), D ** -0.5),
        "wd": _normal(ks[8], (F, D), F ** -0.5 * out_std),
    }
    if w.bias:
        p["bq"] = _normal(ks[9], (Hd,), 0.1)
        p["bk"] = _normal(ks[10], (Kd,), 0.1)
        p["bv"] = _normal(ks[11], (Kd,), 0.1)
    return p


def global_weights(w: Widths, base: jax.Array) -> Dict[str, jax.Array]:
    ks = jax.random.split(jax.random.fold_in(base, 0), 3)
    p = {"embed": _normal(ks[0], (w.V, w.D), w.D ** -0.5 if w.tied else 1.0),
         "final_norm": _norm_weight(ks[1], (w.D,))}
    if not w.tied:
        p["lm_head"] = _normal(ks[2], (w.D, w.V), w.D ** -0.5)
    return p


def _program_norm(g):
    """The program's norms multiply by (1 + weight); g - 1 is exact in
    bfloat16 for g in [0.5, 2)."""
    return (g.astype(jnp.float32) - 1.0).astype(jnp.bfloat16)


def make_params(w: Widths, base: jax.Array):
    """Every weight in the program's layout, bfloat16 (jittable)."""
    g = global_weights(w, base)
    blocks = jax.vmap(lambda i: layer_weights(w, base, i))(jnp.arange(w.L))
    blocks["ln1"] = _program_norm(blocks["ln1"])
    blocks["ln2"] = _program_norm(blocks["ln2"])
    out = {"embed": g["embed"], "final_norm": _program_norm(g["final_norm"]),
           "blocks": blocks}
    if not w.tied:
        out["lm_head"] = g["lm_head"]
    return out


def served_params(w: Widths, seed: int):
    """Every weight, made on the default device in one jitted call."""
    return jax.jit(functools.partial(make_params, w))(base_key(seed))


# --------------------------------------------------------------------------
# reference forward (float32, HIGHEST)
# --------------------------------------------------------------------------

def _fp8(m, axis=0):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (a
    matrix's output columns, an activation's rows)."""
    s = jnp.max(jnp.abs(m), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (m / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _low(x, low: bool):
    """The control rounds every matmul's activation input to float8, one
    scale per row; the reference leaves it in float32."""
    return _fp8(x, axis=-1) if low else x


MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


@functools.partial(jax.jit, static_argnums=(0, 3))
def _ref_layer_weights(w: Widths, base, layer, fp8: bool):
    p = {k: v.astype(jnp.float32) for k, v in layer_weights(w, base, layer).items()}
    if fp8:
        p.update({k: _fp8(p[k]) for k in MATRICES})
    return p


@functools.partial(jax.jit, static_argnums=(0, 2))
def _ref_global_weights(w: Widths, base, fp8: bool):
    g = {k: v.astype(jnp.float32) for k, v in global_weights(w, base).items()}
    head = g["embed"].T if w.tied else g["lm_head"]
    if fp8:
        head = _fp8(head)
        g["embed"] = head.T if w.tied else _fp8(g["embed"].T).T
    return g["embed"], g["final_norm"], head


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mm(x, m, low: bool):
    """A float32 matmul at HIGHEST; the control rounds its input to float8."""
    return jnp.dot(_low(x, low), m, precision=HI)


def attention(w, h, p, window: int, low: bool):
    """h plus the attention sublayer of one layer over one sequence h: (S, D)
    float32, keys limited to the last ``window`` positions (0: all).  With
    ``low`` (the control) every matmul, attention included, takes float8
    inputs."""
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rms(h, p["ln1"], w.eps)
    q, k, v = mm(x, p["wq"], low), mm(x, p["wk"], low), mm(x, p["wv"], low)
    if w.bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _low(_rope(q.reshape(S, w.H, w.hd), pos, w.theta), low)
    k = _low(_rope(k.reshape(S, w.KV, w.hd), pos, w.theta), low)
    v = _low(v.reshape(S, w.KV, w.hd), low)
    R = w.H // w.KV
    q = q.reshape(S // Q_CHUNK, Q_CHUNK, w.KV, R, w.hd)

    def block(args):
        qc, lo = args
        s = jnp.einsum("qgrd,kgd->grqk", qc, k, precision=HI) * w.hd ** -0.5
        qp, kp = lo + jnp.arange(Q_CHUNK)[:, None], pos[None, :]
        live = kp <= qp
        if window:
            live &= kp > qp - window
        a = _low(jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1), low)
        return jnp.einsum("grqk,kgd->qgrd", a, v, precision=HI)

    o = jax.lax.map(block, (q, jnp.arange(0, S, Q_CHUNK)))
    o = o.reshape(S, w.H * w.hd)
    return h + mm(o, p["wo"], low)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _ref_layer(w: Widths, h, p, window: int, low: bool):
    """One decoder layer over one sequence h: (S, D) float32."""
    h = attention(w, h, p, window, low)
    x = _rms(h, p["ln2"], w.eps)
    f = jax.nn.silu(mm(x, p["wg"], low)) * mm(x, p["wu"], low)
    return h + mm(f, p["wd"], low)


def final_hidden(w, seed: int, seqs: Sequence[np.ndarray], fp8: bool = False,
                 weights=_ref_layer_weights, layer=_ref_layer):
    """(final-normed hidden rows per sequence, padded at the end to a
    multiple of PAD, which no earlier row can see; the LM head).  Layer by
    layer: each layer's weights are made once from the seed
    (``weights(w, base, i, fp8)``) and applied to every sequence
    (``layer(w, h, p, window, fp8)``).  A reference module with another
    layer passes its own two functions."""
    base = base_key(seed)
    embed, final_norm, head = _ref_global_weights(w, base, fp8)
    hs = []
    for t in seqs:
        n = -(-len(t) // PAD) * PAD
        ids = np.zeros(n, np.int32)
        ids[:len(t)] = t
        hs.append(embed[jnp.asarray(ids)])
    del embed
    for i, window in enumerate(w.windows):
        p = weights(w, base, i, fp8)
        hs = [layer(w, h, p, window, fp8) for h in hs]
        del p
    return [_rms(h, final_norm, w.eps) for h in hs], head


def reference_logits(w, seed: int, seqs: Sequence[np.ndarray],
                     rows: Sequence[np.ndarray], fp8: bool = False,
                     weights=_ref_layer_weights, layer=_ref_layer) -> List[np.ndarray]:
    """Logits of the plain model at the given rows of each token sequence:
    one (len(rows[i]), V) float32 array per sequence.  With ``fp8``, the
    control's: the same model computed in float8, the head's input rows
    rounded too.  ``weights`` and ``layer`` as in ``final_hidden``."""
    hs, head = final_hidden(w, seed, seqs, fp8, weights, layer)
    return [np.asarray(jnp.dot(_low(h[jnp.asarray(r)], fp8), head, precision=HI))
            for h, r in zip(hs, rows)]


def served_positions(prompt: np.ndarray, served: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
    """(input sequence, rows): the prompt followed by every served token
    but the last, and the rows whose logits chose each served token."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq, rows


def logit_gaps(logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """How far each chosen token's logit lies below the row's best."""
    lg = np.asarray(logits, np.float64)
    return lg.max(axis=1) - lg[np.arange(len(tokens)), np.asarray(tokens)]
