"""Trip-count-aware HLO analyzer: exactness on scan fixtures (the roofline's
foundation — plain cost_analysis undercounts while bodies)."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.launch.hlo_analysis import analyze


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_single_matmul_flops():
    x = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    t = analyze(_compile(lambda a, b: a @ b, x, w).as_text())
    assert t.flops == pytest.approx(2 * 256 * 128 * 64, rel=0.01)


def test_scan_multiplies_trip_count():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)

    def f(x, ws):
        return lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

    t = analyze(_compile(f, x, ws).as_text())
    assert t.flops == pytest.approx(7 * 2 * 128 ** 3, rel=0.01)


def test_nested_scan():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 5, 64, 64), jnp.float32)

    def f(x, ws):
        def outer(c, wrow):
            return lax.scan(lambda c2, w: (c2 @ w, None), c, wrow)[0], None
        return lax.scan(outer, x, ws)[0]

    t = analyze(_compile(f, x, ws).as_text())
    assert t.flops == pytest.approx(15 * 2 * 64 ** 3, rel=0.01)


def test_undercount_vs_raw_cost_analysis():
    """Documents the undercount that motivates the analyzer."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def f(x, ws):
        return lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

    comp = _compile(f, x, ws)
    raw = comp.cost_analysis().get("flops", 0.0)
    ours = analyze(comp.as_text()).flops
    assert ours >= 9 * raw   # raw counts the body once


def test_dot_bytes_positive():
    x = jax.ShapeDtypeStruct((32, 32), jnp.bfloat16)
    t = analyze(_compile(lambda a: a @ a, x).as_text())
    assert t.dot_bytes >= 3 * 32 * 32 * 2
