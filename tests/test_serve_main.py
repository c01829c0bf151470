"""In-process e2e for the ``launch/serve.py`` driver's main() — the A/B
path (`--check-tokens`), the sequential engine, and multi-replica routing
were previously only exercised by hand; this drives the real argument
parser + drivers on a tiny config so CI catches flag/pipeline bitrot.
"""
import pytest

jax = pytest.importorskip("jax")

from repro.launch import serve  # noqa: E402

TINY = ["--requests", "4", "--docs", "8", "--doc-tokens", "10",
        "--top-k", "2", "--max-new-tokens", "2", "--rate", "100"]


def _run_main(monkeypatch, capsys, extra):
    monkeypatch.setattr("sys.argv", ["serve.py"] + TINY + extra)
    serve.main()
    return capsys.readouterr().out


def test_main_check_tokens_single_replica(monkeypatch, capsys):
    """Continuous vs sequential A/B on one replica: main() must run both
    engines and report identical greedy tokens."""
    out = _run_main(monkeypatch, capsys, ["--check-tokens"])
    assert "[continuous]" in out and "[sequential]" in out
    assert "token check: all 4 requests identical" in out


def test_main_chunk_reuse_tolerance(monkeypatch, capsys):
    """--reuse chunk --check-tokens tol:<eps>: the chunk-cache engine's
    approximate outputs verify against the sequential oracle through the
    tolerance comparator (docs/ARCHITECTURE.md §11)."""
    out = _run_main(monkeypatch, capsys,
                    ["--attn", "paged", "--reuse", "chunk",
                     "--recompute-tokens", "8", "--block-size", "8",
                     "--check-tokens", "tol:5"])
    assert "token check: all 4 requests within tol 5" in out


def test_main_check_tokens_two_replicas(monkeypatch, capsys):
    """--replicas 2 --routing affinity: routing never changes computation,
    so the fleet's tokens stay bit-identical to the single sequential
    engine, and the fleet report renders."""
    out = _run_main(monkeypatch, capsys,
                    ["--check-tokens", "--replicas", "2",
                     "--routing", "affinity"])
    assert "continuous x2 (affinity)" in out
    assert "token check: all 4 requests identical" in out
    assert "fleet: 2 replicas" in out
    assert "routed per replica" in out


def test_main_check_tokens_paged_attn(monkeypatch, capsys):
    """--attn paged: decode straight from the paged pool must keep greedy
    tokens bit-identical to the (dense) sequential engine."""
    out = _run_main(monkeypatch, capsys, ["--check-tokens", "--attn", "paged"])
    assert "token check: all 4 requests identical" in out


def test_main_check_tokens_paged_attn_three_replicas(monkeypatch, capsys):
    """--attn paged at N=3: every replica decodes through the kernel-backed
    paged path; the fleet's tokens still match the single dense sequential
    engine exactly."""
    out = _run_main(monkeypatch, capsys,
                    ["--check-tokens", "--attn", "paged", "--replicas", "3"])
    assert "continuous x3 (affinity)" in out
    assert "token check: all 4 requests identical" in out


def test_main_check_tokens_paged_prefill_chunked(monkeypatch, capsys):
    """--attn paged --prefill-chunk: chunked ragged prefill scatters KV
    straight into pool pages (no dense gather anywhere), and greedy tokens
    stay bit-identical to the dense sequential engine."""
    out = _run_main(monkeypatch, capsys,
                    ["--check-tokens", "--attn", "paged",
                     "--prefill-chunk", "6"])
    assert "token check: all 4 requests identical" in out


def test_main_check_tokens_paged_prefill_three_replicas(monkeypatch, capsys):
    """--attn paged --prefill-chunk at N=3: every replica prefills AND
    decodes through the paged kernels; the fleet still matches the single
    dense sequential engine exactly."""
    out = _run_main(monkeypatch, capsys,
                    ["--check-tokens", "--attn", "paged",
                     "--prefill-chunk", "6", "--replicas", "3"])
    assert "continuous x3 (affinity)" in out
    assert "token check: all 4 requests identical" in out


@pytest.mark.multidevice
@pytest.mark.skipif(
    jax.local_device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "set before jax import (CI multidevice lane)")
def test_main_check_tokens_paged_prefill_tp2(monkeypatch, capsys):
    """--tp 2 --attn paged --prefill-chunk: the sharded paged-prefill path
    (per-shard kernel dispatch over head-local pool planes) keeps greedy
    tokens bit-identical to the unsharded dense sequential engine."""
    out = _run_main(monkeypatch, capsys,
                    ["--check-tokens", "--attn", "paged",
                     "--prefill-chunk", "6", "--tp", "2"])
    assert "token check: all 4 requests identical" in out


def test_main_sequential_only(monkeypatch, capsys):
    out = _run_main(monkeypatch, capsys, ["--sequential"])
    assert "[sequential] served 4 requests" in out
    assert "[continuous]" not in out


FRONTDOOR = ["--requests", "16", "--max-batch", "2", "--frontdoor",
             "--tenants", "2", "--tenant-queries", "3"]


def test_main_frontdoor_check_tokens_single_replica(monkeypatch, capsys):
    """--frontdoor on repeat-heavy tenant traffic: the query cache absorbs
    repeats (trace longer than the in-flight window, so originals complete
    and populate the cache), and --check-tokens compares ONLY the admitted
    misses against the sequential engine — bit-identical."""
    out = _run_main(monkeypatch, capsys, FRONTDOOR + ["--check-tokens"])
    assert "[frontdoor x1" in out
    assert "hit_exact" in out                    # repeats actually hit
    assert "front door" in out and "SLO tenant0" in out
    assert "front-door miss requests identical" in out
    assert "excluded by construction" in out


def test_main_frontdoor_check_tokens_three_replicas(monkeypatch, capsys):
    """--frontdoor --replicas 3: misses fan out across the fleet through
    the affinity router and still match the single sequential engine."""
    out = _run_main(monkeypatch, capsys,
                    FRONTDOOR + ["--check-tokens", "--replicas", "3"])
    assert "frontdoor x3 (affinity)" in out
    assert "fleet: 3 replicas" in out
    assert "front-door miss requests identical" in out


def test_main_frontdoor_ignored_for_sequential(monkeypatch, capsys):
    out = _run_main(monkeypatch, capsys, ["--frontdoor", "--sequential"])
    assert "--frontdoor requires the continuous engine; ignored" in out
    assert "[sequential] served 4 requests" in out


def test_main_workload_knob_flags(monkeypatch, capsys):
    """PR 6 satellite: drift/zipf/phase/output-length knobs are plumbed
    through the CLI into make_workload."""
    out = _run_main(monkeypatch, capsys,
                    ["--sequential", "--zipf-s", "1.5", "--drift", "0.3",
                     "--n-phases", "4", "--output-len-mean", "2"])
    assert "[sequential] served 4 requests" in out


def test_main_returns_what_it_served(capsys):
    """main() hands back the config, results, runtimes and the oracle
    L-inf, and prints the L-inf beside the verdict."""
    out = serve.main(TINY + ["--check-tokens"])
    text = capsys.readouterr().out
    assert out.cfg.name == "qwen2-reduced"
    assert [r.req_id for r in out.results] == [0, 1, 2, 3]
    assert len(out.runtimes) == 1
    assert out.linf is not None and out.linf >= 0.0
    assert "first-token logit L-inf vs the sequential oracle" in text
    assert "4/4 requests with identical tokens" in text


def test_published_flag_selects_the_published_config():
    from repro.configs import get_config, get_reduced

    parse = serve.build_parser().parse_args
    assert serve.model_config(parse([])) == get_reduced("qwen2-0.5b")
    assert serve.model_config(parse(["--published"])) == \
        get_config("qwen2-0.5b")


def test_compile_cache_dir(monkeypatch):
    """The compile cache follows JAX_COMPILATION_CACHE_DIR when it is set
    (JAX reads it; nothing is overridden) and is the checkout's fixed
    .jax_cache otherwise."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert serve.setup_compile_cache() == "/elsewhere"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert serve.setup_compile_cache() == str(serve.REPO_ROOT
                                                  / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_replica_devices_wrap_and_refuse_oversized_tp():
    devs = jax.devices()
    assert serve.replica_devices(0, 1) == [devs[0]]
    assert serve.replica_devices(len(devs), 1) == [devs[0]]
    with pytest.raises(ValueError, match="visible"):
        serve.replica_devices(0, len(devs) + 1)
