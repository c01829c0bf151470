"""The harness end to end on the CPU at a tiny size: the weights, the
reference and its control, a cell added by files alone, faults planted in
the timed path, and the refusal to run without a TPU.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""
from __future__ import annotations

import os
import shutil
import subprocess
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
import run  # noqa: E402
import tiny  # noqa: E402

SEED = 2**35 + 77
SINGLE = dict(tiny.TINY_MIX, loop="single", max_new_tokens=1, check_requests=6)
UNIQUE_SINGLE = dict(SINGLE, targets="unique", passages=400)


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def execute(root, cell, **kw):
    return run.execute(root, cell, SEED, 1.0, False, require_tpu=False,
                       peaks=tiny.PEAKS, **kw)


# ---- weights and reference ------------------------------------------------

W = model.widths(tiny.TINY_CONF)


def test_served_weights_are_the_reference_weights():
    params = model.served_params(W, SEED)
    base = model.base_key(SEED)
    for layer in (0, W.L - 1):
        ref = model.layer_weights(W, base, layer)
        for k, v in ref.items():
            got = params["blocks"][k][layer]
            if k in ("ln1", "ln2"):
                got = (1.0 + got.astype(jnp.float32)).astype(jnp.bfloat16)
            assert np.array_equal(np.asarray(got), np.asarray(v)), k


def test_reference_agrees_with_the_program_forward():
    """The program's dense forward (bfloat16) and the reference pick the
    same tokens, with logits within bfloat16 rounding."""
    from repro.models import model as M
    cfg = run.program_config(tiny.TINY_CONF, model, W)
    params = model.served_params(W, SEED)
    toks = np.random.default_rng(0).integers(0, W.V, 150).astype(np.int32)
    prog = np.asarray(M.forward(cfg, params, {"tokens": jnp.asarray(toks)[None]}))[0]
    rows = np.arange(len(toks))
    ref = model.reference_logits(W, SEED, [toks], [rows])[0]
    assert np.abs(prog - ref).max() < 0.1 * np.abs(ref).max()
    assert (prog.argmax(1) == ref.argmax(1)).mean() > 0.9
    assert model.logit_gaps(ref, prog.argmax(1)).max() < 0.2


def test_control_lies_further_from_the_reference():
    """At this size too the float8 control's logits lie further from the
    reference's than the program's do."""
    from repro.models import model as M
    cfg = run.program_config(tiny.TINY_CONF, model, W)
    params = model.served_params(W, SEED)
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, W.V, n).astype(np.int32) for n in (90, 140, 200)]
    rows = [np.arange(len(s)) for s in seqs]
    ref = model.reference_logits(W, SEED, seqs, rows)
    low = model.reference_logits(W, SEED, seqs, rows, fp8=True)
    prog = [np.asarray(M.forward(cfg, params, {"tokens": jnp.asarray(s)[None]}))[0]
            for s in seqs]
    prog_err = max(np.abs(p - r).max() for p, r in zip(prog, ref))
    ctrl_err = max(np.abs(c - r).max() for c, r in zip(low, ref))
    assert ctrl_err > 3 * prog_err


@pytest.mark.parametrize("conf", [tiny.TINY_CONF, tiny.TINY_MOE_CONF], ids=["dense", "moe"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, conf):
    """A run of a cell whose program is correct: the float8 control, judged
    by the same comparison at the same rows, is not.  The control is the
    float8 reference of the configuration's own module."""
    root = tiny.make_root(str(tmp_path / "co"), {"unique.single": UNIQUE_SINGLE}, conf)
    res = execute(root, "tiny.unique.single", control=True)
    assert res["correct"]
    assert res["control"]["correct"] is False
    ctl, prog = res["control"]["checks"], res["checks"]
    assert ctl["logit_linf"]["value"] > 3 * prog["logit_linf"]["value"]


# ---- runs -------------------------------------------------------------------

def test_cell_added_by_files_alone(tmp_path):
    """A new configuration file, traffic file and BENCHMARK.json entry make
    a cell that runs, with no other file edited."""
    root = tiny.make_root(str(tmp_path / "co"), {"zipf.batch": tiny.TINY_MIX,
                                                  "unique.single": UNIQUE_SINGLE})
    res = execute(root, "tiny.zipf.batch")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tok_s", "setup_s"}
    assert list(res)[-1] == "checks"
    res = run.execute(root, "tiny.unique.single", SEED, 1.0, True,
                      require_tpu=False, peaks=tiny.PEAKS)
    assert res["correct"]
    m = res["metrics"]
    assert {"mfu.single", "device.idle_share.single"} <= set(m)
    assert "busy_s" in res["device"]


@pytest.mark.parametrize("where", ["prefill", "decode"])
def test_altered_token_is_not_correct(tmp_path, monkeypatch, where):
    """A token altered where the program produces it: the first token (out
    of prefill) or a decoded one."""
    from repro.serving import runtime
    V = W.V
    if where == "prefill":
        orig = runtime.ContinuousRuntime._first_token

        def first_token(self, st, res, t):
            res.first_token = (res.first_token + V // 2) % V
            return orig(self, st, res, t)
        monkeypatch.setattr(runtime.ContinuousRuntime, "_first_token", first_token)
        mixes, cell = {"unique.single": UNIQUE_SINGLE}, "tiny.unique.single"
    else:
        orig = runtime.ContinuousRuntime._on_decode_done

        def decode_done(self, payload):
            batch, toks = payload
            return orig(self, (batch, [(t + V // 2) % V for t in toks]))
        monkeypatch.setattr(runtime.ContinuousRuntime, "_on_decode_done", decode_done)
        mixes, cell = {"zipf.batch": tiny.TINY_MIX}, "tiny.zipf.batch"
    root = tiny.make_root(str(tmp_path / "co"), mixes)
    res = execute(root, cell)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_no_tpu_no_result(tmp_path):
    """Without a TPU, and in a directory holding only the benchmark's own
    files, the run exits non-zero and prints no result line."""
    only = tmp_path / "only"
    only.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), only)
    shutil.copytree(BENCH, only / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for where in (ROOT, str(only)):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.unique.single",
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=where, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
