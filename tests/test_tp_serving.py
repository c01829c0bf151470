"""Tensor-parallel serving parity.

The multidevice tests need >= 4 visible devices and therefore run in the CI
``multidevice`` lane, which exports
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` BEFORE any jax
import (jax locks the device count on first init — setting the flag inside
a test is too late, hence the skip guard instead of a fixture).

Parity claim under test: sharding params (Megatron col/row), the paged
pool's KV-head planes, and the decode kernels over a (1, tp) mesh never
changes greedy tokens OR per-tier hit attribution — mesh sizes 1, 2, 4 are
bit-identical to each other and to the single-device sequential engine.
"""
import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_reduced  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.sharding import (assert_tp_compatible,  # noqa: E402
                                   kv_heads_shardable)

multidevice = pytest.mark.multidevice
need4 = pytest.mark.skipif(
    jax.local_device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "set before jax import (CI multidevice lane)")

TINY = ["--requests", "4", "--docs", "8", "--doc-tokens", "10",
        "--top-k", "2", "--max-new-tokens", "2", "--rate", "100"]


def _run_main(monkeypatch, capsys, extra):
    monkeypatch.setattr("sys.argv", ["serve.py"] + TINY + extra)
    serve.main()
    return capsys.readouterr().out


# ---------------------------------------------------------------------------
# fast lane: the early mesh/model compatibility error needs NO devices
# ---------------------------------------------------------------------------

def test_tp_head_divisibility_errors_before_device_work(monkeypatch, capsys):
    """qwen2-reduced has 2 KV heads: --tp 4 would shard a KV head across
    devices.  serve.py must fail with a one-line SystemExit BEFORE any
    mesh/device-count check, so this runs (and fails identically) on a
    single-device machine."""
    monkeypatch.setattr("sys.argv", ["serve.py"] + TINY + ["--tp", "4"])
    with pytest.raises(SystemExit) as e:
        serve.main()
    msg = str(e.value)
    assert "shard a KV head" in msg and "--tp 4" in msg
    assert "[1, 2]" in msg          # suggests the clean tps


def test_kv_heads_shardable_table():
    qwen = get_reduced("qwen2-0.5b")      # H=4, KV=2
    llama = get_reduced("llama2-7b")      # H=4, KV=4
    assert [t for t in (1, 2, 4) if kv_heads_shardable(qwen, t)] == [1, 2]
    assert [t for t in (1, 2, 4) if kv_heads_shardable(llama, t)] == [1, 2, 4]
    assert_tp_compatible(llama, 4)        # no raise
    with pytest.raises(ValueError):
        assert_tp_compatible(qwen, 4)


# ---------------------------------------------------------------------------
# multidevice lane: real sharded engines on a forced-host-device mesh
# ---------------------------------------------------------------------------

@multidevice
@need4
def test_tp2_check_tokens(monkeypatch, capsys):
    """--tp 2 --check-tokens: the sharded continuous engine's greedy tokens
    match the single-device sequential engine bit-for-bit."""
    out = _run_main(monkeypatch, capsys, ["--tp", "2", "--check-tokens"])
    assert "tensor parallel: tp=2" in out
    assert "token check: all 4 requests identical" in out


@multidevice
@need4
def test_tp4_check_tokens_llama(monkeypatch, capsys):
    """--tp 4 needs 4-KV-head llama2-reduced (qwen2 tops out at tp=2)."""
    out = _run_main(monkeypatch, capsys,
                    ["--arch", "llama2-7b", "--tp", "4", "--check-tokens"])
    assert "tensor parallel: tp=4" in out
    assert "token check: all 4 requests identical" in out


@multidevice
@need4
def test_2d_fleet_replicas_x_tp(monkeypatch, capsys):
    """2D fleet: tp=2 WITHIN each replica x affinity routing ACROSS 2
    replicas; tokens still match the single sequential engine."""
    out = _run_main(monkeypatch, capsys,
                    ["--tp", "2", "--replicas", "2", "--check-tokens"])
    assert "continuous x2 (affinity)" in out
    assert "token check: all 4 requests identical" in out


@multidevice
@need4
def test_mesh_size_parity_tokens_and_tier_hits(monkeypatch):
    """Mesh sizes 1 / 2 / 4: identical greedy tokens AND identical per-tier
    hit attribution (gpu/host/disk hit tokens) — sharding must not change
    what the knowledge tree thinks it cached."""
    args = serve.build_parser().parse_args(
        TINY + ["--arch", "llama2-7b", "--requests", "6"])
    cfg, params, corpus, idx, wl, _ = serve.make_setup(args)
    runs = {}
    for tp in (1, 2, 4):
        monkeypatch.setattr(args, "tp", tp)
        rt = serve.make_runtimes(cfg, params, corpus, idx, args, 1)[0]
        res = sorted(rt.serve(wl, max_new_tokens=args.max_new_tokens),
                     key=lambda r: r.req_id)
        s = rt.tree.stats
        runs[tp] = ([list(r.tokens) for r in res],
                    {k: s[k] for k in ("hit_tokens_gpu", "hit_tokens_host",
                                       "hit_tokens_disk", "hits", "misses")})
    assert runs[1] == runs[2] == runs[4]


@multidevice
@need4
def test_tp2_chunk_reuse_tolerance(monkeypatch, capsys):
    """--tp 2 --reuse chunk: relocated-chunk reuse is approximate, so the
    sharded engine verifies against the sequential oracle through the
    tolerance comparator instead of bit-exactness."""
    out = _run_main(monkeypatch, capsys,
                    ["--tp", "2", "--attn", "paged", "--reuse", "chunk",
                     "--recompute-tokens", "8", "--block-size", "8",
                     "--check-tokens", "tol:5"])
    assert "tensor parallel: tp=2" in out
    assert "token check: all 4 requests within tol 5" in out


@multidevice
@need4
def test_tp_with_paged_disk_tiers(monkeypatch, capsys):
    """Sharded pool + tiny GPU tier: demotions/promotions run through
    PagedBackend's per-shard copies and tokens stay identical."""
    out = _run_main(monkeypatch, capsys,
                    ["--tp", "2", "--check-tokens",
                     "--gpu-cache-bytes", str(48 * 2**10),
                     "--disk-cache-bytes", str(8 * 2**20)])
    assert "token check: all 4 requests identical" in out


@multidevice
@need4
def test_replicas_own_their_devices():
    """--replicas 4 on 4 devices: replica i's params and paged pool live on
    device i alone (and on 2 devices at tp=2: replica i owns [2i, 2i+2))."""
    args = serve.build_parser().parse_args(TINY)
    cfg, params, corpus, idx, _, _ = serve.make_setup(args)
    devs = jax.devices()
    for tp, n in ((1, 4), (2, 2)):
        args.tp = tp
        rts = serve.make_runtimes(cfg, params, corpus, idx, args, n)
        for i, rt in enumerate(rts):
            own = set(devs[i * tp:(i + 1) * tp])
            placed = set().union(*(x.devices()
                                   for x in jax.tree.leaves(rt.params)))
            assert placed == own and rt.store.k.devices() == own
