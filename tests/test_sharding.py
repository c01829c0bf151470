"""Sharding + dry-run machinery on a small forced-device mesh.

Runs in a subprocess so the 8-device XLA flag never leaks into other tests
(the dry-run proper uses 512 devices via launch/dryrun.py).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get_reduced
    from repro.launch import specs as SP
    from repro.launch.specs import input_specs, shape_applicable
    from repro.launch.hlo_analysis import analyze

    SP.SHAPES = {
        "train_4k": dict(kind="train", seq=64, batch=8),
        "prefill_32k": dict(kind="prefill", seq=128, batch=8),
        "decode_32k": dict(kind="decode", seq=128, batch=8),
        "long_500k": dict(kind="decode", seq=256, batch=1),
    }
    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out = {}
    for arch in ["qwen2-0.5b", "mixtral-8x7b", "xlstm-1.3b", "hymba-1.5b"]:
        cfg = get_reduced(arch)
        for shape in SP.SHAPES:
            ok, _ = shape_applicable(cfg, shape)
            if not ok:
                continue
            with mesh:
                fn, args, donate, out_sh = input_specs(cfg, shape, mesh)
                c = jax.jit(fn, donate_argnums=donate,
                            out_shardings=out_sh).lower(*args).compile()
                t = analyze(c.as_text())
                out[f"{arch}/{shape}"] = dict(
                    flops=t.flops, coll=sum(t.coll.values()))
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_dryrun_machinery_small_mesh():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    assert len(res) == 15  # 4 archs x 4 shapes - qwen2's long_500k skip
    for k, v in res.items():
        assert v["flops"] > 0, k


def test_param_spec_divisibility_fallback():
    """Rules must replicate when dims don't divide the axis."""
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.sharding import param_spec

    class FakeMesh:
        shape = {"model": 16, "data": 16}
        axis_names = ("data", "model")

    cfg = get_config("mixtral-8x7b")

    class Leaf:
        def __init__(self, shape):
            self.shape = shape
            self.ndim = len(shape)

    # experts=8 not divisible by model=16 -> falls back to d_ff sharding
    s = param_spec(("blocks", "wg"), Leaf((32, 8, 4096, 14336)), cfg,
                   FakeMesh())
    assert s == P(None, None, None, "model")
    # attention fused head dim divisible -> column parallel
    s = param_spec(("blocks", "wq"), Leaf((32, 4096, 4096)), cfg, FakeMesh())
    assert s == P(None, None, "model")
    # odd dim -> replicate
    s = param_spec(("blocks", "wq"), Leaf((32, 4096, 100)), cfg, FakeMesh())
    assert s == P(None, None, None)


def test_long_context_applicability():
    from repro.configs import get_config
    from repro.launch.specs import supports_long_context
    expected = {
        "xlstm-1.3b": True, "hymba-1.5b": True, "gemma3-12b": True,
        "gemma2-27b": True, "mixtral-8x7b": True,
        "yi-34b": False, "phi3.5-moe-42b-a6.6b": False,
        "internvl2-1b": False, "musicgen-large": False, "qwen2-0.5b": False,
    }
    for arch, want in expected.items():
        assert supports_long_context(get_config(arch)) == want, arch
