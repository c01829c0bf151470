"""A configuration's own reference module: what the default module gives
Qwen2 is pinned to what the harness gave it before modules could be named,
and a mixture-of-experts configuration with windowed and full layers is
added by new files and entries alone.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "traffic"), HERE]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import model  # noqa: E402
import moe_ref  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

SEED = 2**35 + 77
QWEN2 = run.load_json(os.path.join(BENCH, "configs", "qwen2-0.5b.json"))
SERVED = [work.Served([512, 384, 2048, 32], alpha=0, decoded=0),
          work.Served([1024, 640, 32], alpha=768, decoded=3),
          work.Served([32], alpha=4096, decoded=5)]
V5E = (197e12, 819e9)


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


# ---- qwen2 through the default module: as before, bit for bit -------------

def test_qwen2_program_config_is_unchanged():
    from repro.models.config import ModelConfig
    ref = run.load_reference(ROOT, QWEN2)
    assert os.path.samefile(ref.__file__, os.path.join(BENCH, "model.py"))
    w = ref.widths(QWEN2)
    want = ModelConfig(
        name="qwen2-0.5b", family="dense", n_layers=24, d_model=896, n_heads=14,
        n_kv_heads=2, d_ff=4864, vocab_size=151936, head_dim=64, qkv_bias=True,
        rope_theta=1000000.0, norm_eps=1e-06, sliding_window=0, global_every=1,
        tie_embeddings=True, dtype="bfloat16")
    assert run.program_config(QWEN2, ref, w) == want


def test_qwen2_trace_keys_are_the_paged_pair():
    assert run.trace_keys(run.load_reference(ROOT, QWEN2)) == ("paged_prefill", "paged_decode")


# (flops, bytes, least seconds) of the paged pair and the model's FLOPs over
# SERVED, chunk 512, v5e peaks, as the harness counted them before a
# configuration could bring its own windows and matmul FLOPs.
QWEN2_WORK = {
    0: ((628171407360.0, 742391808.0, 0.0032134024372063),
        (2413006848.0, 345403392.0, 0.0004217379633699634), 4005736878080.0),
    1024: ((366469447680.0, 560332800.0, 0.0018776156294614086),
           (704643072.0, 101351424.0, 0.00012375021245421245), 3742326554624.0),
}


@pytest.mark.parametrize("window", sorted(QWEN2_WORK))
def test_qwen2_work_counts_are_unchanged(window):
    w = dataclasses.replace(run.load_reference(ROOT, QWEN2).widths(QWEN2), window=window)
    out = work.count(w, SERVED, 512, *V5E)
    pre, dec, mf = QWEN2_WORK[window]
    assert (out["paged_prefill"].flops, out["paged_prefill"].bytes,
            out["paged_prefill"].min_seconds) == pre
    assert (out["paged_decode"].flops, out["paged_decode"].bytes,
            out["paged_decode"].min_seconds) == dec
    assert out["model_flops"] == mf


def test_default_weights_are_unchanged():
    ref = run.load_reference(ROOT, tiny.TINY_CONF)
    params = ref.served_params(ref.widths(tiny.TINY_CONF), SEED)
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(params),
                             key=lambda x: str(x[0])):
        h.update(str(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == "43f87428a4eafd7b065cf387a320fde4e2856786ff047e2ee7e3b08868f0c8b4"


# The reference's program (its jaxpr) with and without float8: the same
# operations give the same bits on any one machine, where the logits
# themselves differ in the last bits with the CPU's thread count.
REFERENCE_JAXPR = {
    False: "6931e482f27589a3efb2291c70bf3dd509e915204f83bc007611420b00e39db0",
    True: "786cbd33e403a430e1cddb025f4c0a3d5366b0b19e16b9c6b3b0142a5b1eb00e",
}


@pytest.mark.parametrize("fp8", [False, True])
def test_default_reference_is_unchanged(fp8):
    ref = run.load_reference(ROOT, tiny.TINY_CONF)
    w = ref.widths(tiny.TINY_CONF)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, w.V, n).astype(np.int32) for n in (70, 600)]
    rows = [np.arange(len(s)) for s in seqs]

    def logits():
        hs, head = ref.final_hidden(w, SEED, seqs, fp8)
        return [jnp.dot(ref._low(h[jnp.asarray(r)], fp8), head, precision=ref.HI)
                for h, r in zip(hs, rows)]
    text = str(jax.make_jaxpr(logits)())
    assert hashlib.sha256(text.encode()).hexdigest() == REFERENCE_JAXPR[fp8]
    got = ref.reference_logits(w, SEED, seqs, rows, fp8=fp8)
    assert all(np.array_equal(a, np.asarray(b)) for a, b in zip(got, logits()))


# ---- work counts over per-layer windows ----------------------------------

def test_mixed_windows_sum_the_single_window_counts():
    w = moe_ref.widths(tiny.TINY_MOE_CONF)
    assert w.windows == (96, 0)
    mixed = work.count(w, SERVED, 32, 1e12, 1e11)
    one = [work.count(dataclasses.replace(model.widths(tiny.TINY_CONF), L=1, window=win),
                      SERVED, 32, 1e12, 1e11) for win in w.windows]
    for k in ("paged_prefill", "paged_decode"):
        assert mixed[k].flops == sum(o[k].flops for o in one)
        assert mixed[k].bytes == sum(o[k].bytes for o in one)
    f, _ = work.prefill_attention(w, 100, 50)
    assert f == sum(work.prefill_attention(
        dataclasses.replace(model.widths(tiny.TINY_CONF), L=1, window=win), 100, 50)[0]
        for win in w.windows)


def test_moe_matmul_flops_count_the_routed_experts():
    w = moe_ref.widths(tiny.TINY_MOE_CONF)
    dense = model.widths(tiny.TINY_CONF)
    ffn = 2.0 * (w.D * w.E + w.K * 3 * w.D * w.F)
    assert w.matmul_flops == dense.matmul_flops - 2.0 * w.L * 3 * w.D * w.F + w.L * ffn


# ---- a mixture-of-experts configuration added by files alone --------------

def test_moe_program_follows_the_module(tmp_path):
    root = tiny.make_root(str(tmp_path / "co"), {}, tiny.TINY_MOE_CONF)
    ref = run.load_reference(root, tiny.TINY_MOE_CONF)
    assert os.path.samefile(ref.__file__, os.path.join(root, "bench", "moe_ref.py"))
    w = ref.widths(tiny.TINY_MOE_CONF)
    cfg = run.program_config(tiny.TINY_MOE_CONF, ref, w)
    assert (cfg.family, cfg.moe_experts, cfg.moe_top_k) == ("moe", 4, 2)
    assert cfg.layer_windows() == w.windows


def test_moe_reference_agrees_with_the_program_forward():
    from repro.models import model as M
    w = moe_ref.widths(tiny.TINY_MOE_CONF)
    cfg = run.program_config(tiny.TINY_MOE_CONF, moe_ref, w)
    params = moe_ref.served_params(w, SEED)
    toks = np.random.default_rng(0).integers(0, w.V, 150).astype(np.int32)
    prog = np.asarray(M.forward(cfg, params, {"tokens": jnp.asarray(toks)[None]}))[0]
    ref = moe_ref.reference_logits(w, SEED, [toks], [np.arange(len(toks))])[0]
    assert np.abs(prog - ref).max() < 0.1 * np.abs(ref).max()
    assert model.logit_gaps(ref, prog.argmax(1)).max() < 0.2


SINGLE = dict(tiny.TINY_MIX, loop="single", max_new_tokens=1, check_requests=6,
              targets="unique", passages=400)
MLP_READER = ('"""Feed-forward layer (scope mlp): least time for its work over its '
              'device time."""\nimport readers\n\n\ndef read(ctx):\n'
              '    return readers.roofline(ctx, "mlp")\n')


def moe_root(tmp_path):
    root = tiny.make_root(str(tmp_path / "co"), {"unique.single": SINGLE}, tiny.TINY_MOE_CONF)
    tiny.add_metric(root, tiny.MLP_ROOFLINE, MLP_READER)
    return root


def with_device_ops(read):
    """The CPU's trace has no device plane: give it one, whose single op
    spans the middle of the window and names every trace key, so that the
    reduction credits each key with time as it would on the chip."""
    def traced(path):
        dev, host = read(path)
        lo, hi = trace_reduce.window_of(host)
        keys = " ".join(("paged_prefill", "paged_decode") + moe_ref.KERNELS)
        dev["/device:TPU:0"] = [(lo + (hi - lo) // 4, hi - (hi - lo) // 4, f"fusion.1 {keys}")]
        return dev, host
    return traced


def test_moe_cell_added_by_files_alone(tmp_path, monkeypatch):
    """A configuration file naming its own reference module, a traffic file,
    a BENCHMARK.json entry and a reader for the module's trace key make a
    cell that runs and is correct, untraced and traced."""
    root = moe_root(tmp_path)
    res = run.execute(root, "tiny.unique.single", SEED, 1.0, False, require_tpu=False,
                      peaks=tiny.PEAKS)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms", "setup_s"}
    monkeypatch.setattr(trace_reduce, "read", with_device_ops(trace_reduce.read))
    res = run.execute(root, "tiny.unique.single", SEED, 1.0, True, require_tpu=False,
                      peaks=tiny.PEAKS)
    assert res["correct"]
    m = res["metrics"]
    assert {"mlp_roofline.single", "paged_prefill_roofline.single", "mfu.single"} <= set(m)
    assert m["mlp_roofline.single"]["value"] > 0


WRONG_ROUTER = '''"""The reference with a wrong router: each token's top expert alone."""
import dataclasses

import moe_ref
from moe_ref import (KERNELS, kernel_work, make_params, program_fields,  # noqa: F401
                     served_params, widths)


def reference_logits(w, seed, seqs, rows, fp8=False):
    return moe_ref.reference_logits(dataclasses.replace(w, K=1), seed, seqs, rows, fp8)
'''


def test_moe_wrong_router_is_not_correct(tmp_path):
    """The reference routes each token to one expert where the program
    routes it to two: the comparison finds them apart."""
    root = tiny.make_root(str(tmp_path / "co"), {"unique.single": SINGLE}, tiny.TINY_MOE_CONF)
    with open(os.path.join(root, "bench", "moe_wrong_router.py"), "w") as f:
        f.write(WRONG_ROUTER)
    tiny.dump(os.path.join(root, "bench", "configs", "tiny.json"),
              dict(tiny.TINY_MOE_CONF, reference="moe_wrong_router.py"))
    res = run.execute(root, "tiny.unique.single", SEED, 1.0, False, require_tpu=False,
                      peaks=tiny.PEAKS)
    assert res["correct"] is False and res["failed"] == 0


@pytest.fixture
def topology():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no described chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_rehearsal_compiles_a_module_of_its_own(tmp_path, capsys, topology):
    """The compile rehearsal takes the weights and the program's fields
    from the configuration's module, and finds its trace key in the
    compiled steps."""
    import rehearse
    root = tiny.make_root(str(tmp_path / "co"), {"unique.single": SINGLE}, tiny.TINY_MOE_CONF)
    cache = jax.config.jax_enable_compilation_cache
    try:
        rehearse.main([], root=root)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    lines = capsys.readouterr().out.splitlines()
    steps = [ln for ln in lines if ln.startswith(("prefill step", "decode step"))]
    assert len(steps) == 4 and all("'mlp'" in ln for ln in steps)
    assert any(ln.startswith("weights:") for ln in lines)
