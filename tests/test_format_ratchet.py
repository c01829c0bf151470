"""Freeze the ``ruff format`` burn-down manifest (pyproject.toml).

The ``[tool.ruff.format].exclude`` list grandfathers pre-formatter files
out of the blocking CI format gate.  It is a RATCHET: entries may only be
REMOVED (after ``ruff format <file>``), never quietly added — but this
container ships no ruff binary (offline image, see the blocker note in
pyproject.toml), so the gate itself cannot police additions here.  This
test does: the manifest is snapshotted below, and any NEW entry fails
tier-1 loudly with instructions, turning a one-line append into an
explicit, reviewable two-file change.

To legitimately grow the snapshot (a new file written in the repo's
hand-aligned house style while no ruff binary is available to verify it
clean): add the path to BOTH pyproject.toml and ``FROZEN`` below in the
same commit, and extend the blocker note in pyproject.toml.  To shrink
it (the goal): ``ruff format <file>``, then delete the entry from both.
"""
import pathlib
import tomllib

REPO = pathlib.Path(__file__).resolve().parent.parent

FROZEN = frozenset([
    "benchmarks/common.py",
    "benchmarks/fig13_overall.py",
    "benchmarks/fig_frontdoor.py",
    "benchmarks/perf_guard.py",
    "benchmarks/fig15_topk.py",
    "benchmarks/fig16_large_models.py",
    "benchmarks/fig17_policy.py",
    "benchmarks/fig18_reorder.py",
    "benchmarks/fig19_speculative.py",
    "benchmarks/fig2_prefill_scaling.py",
    "benchmarks/fig4_cache_hit.py",
    "benchmarks/fig5_retrieval_pattern.py",
    "benchmarks/fig_chunk_reuse.py",
    "benchmarks/fig_replica_routing.py",
    "benchmarks/fig_tp_scaling.py",
    "benchmarks/fig_tiered_cache.py",
    "benchmarks/kernel_bench.py",
    "benchmarks/run.py",
    "benchmarks/tab4_sched_time.py",
    "benchmarks/throughput_batching.py",
    "benchmarks/tpot_topk.py",
    "examples/policy_ablation.py",
    "examples/quickstart.py",
    "examples/rag_serving.py",
    "examples/train_tiny.py",
    "src/repro/configs/__init__.py",
    "src/repro/configs/gemma2_27b.py",
    "src/repro/configs/gemma3_12b.py",
    "src/repro/configs/hymba_1p5b.py",
    "src/repro/configs/internvl2_1b.py",
    "src/repro/configs/llama2_70b.py",
    "src/repro/configs/llama2_7b.py",
    "src/repro/configs/mistral_7b.py",
    "src/repro/configs/mixtral_8x7b.py",
    "src/repro/configs/musicgen_large.py",
    "src/repro/configs/phi35_moe_42b.py",
    "src/repro/configs/qwen2_0p5b.py",
    "src/repro/configs/xlstm_1p3b.py",
    "src/repro/configs/yi_34b.py",
    "src/repro/core/controller.py",
    "src/repro/core/fault_tolerance.py",
    "src/repro/core/iterative.py",
    "src/repro/core/knowledge_tree.py",
    "src/repro/core/profiler.py",
    "src/repro/core/reorder.py",
    "src/repro/core/speculative.py",
    "src/repro/kernels/ops.py",
    "src/repro/kernels/paged_attention.py",
    "src/repro/kernels/paged_prefill.py",
    "src/repro/kernels/prefix_attention.py",
    "src/repro/kernels/ref.py",
    "src/repro/kvcache/paged.py",
    "src/repro/launch/dryrun.py",
    "src/repro/launch/hlo_analysis.py",
    "src/repro/launch/mesh.py",
    "src/repro/launch/perf_probe.py",
    "src/repro/launch/serve.py",
    "src/repro/launch/sharding.py",
    "src/repro/launch/specs.py",
    "src/repro/launch/train.py",
    "src/repro/models/config.py",
    "src/repro/models/layers.py",
    "src/repro/models/model.py",
    "src/repro/retrieval/corpus.py",
    "src/repro/retrieval/traffic.py",
    "src/repro/retrieval/vectordb.py",
    "src/repro/serving/backend.py",
    "src/repro/serving/config.py",
    "src/repro/serving/engine.py",
    "src/repro/serving/frontdoor.py",
    "src/repro/serving/metrics.py",
    "src/repro/serving/router.py",
    "src/repro/serving/runtime.py",
    "src/repro/serving/scheduler.py",
    "src/repro/serving/simulator.py",
    "src/repro/training/checkpoint.py",
    "src/repro/training/data.py",
    "src/repro/training/optimizer.py",
    "src/repro/training/train_lib.py",
    "tests/test_arch_smoke.py",
    "tests/test_backend_protocol.py",
    "tests/test_chunk_reuse.py",
    "tests/test_chunked_prefill.py",
    "tests/test_engine_config.py",
    "tests/test_engine_e2e.py",
    "tests/test_fault_tolerance.py",
    "tests/test_format_ratchet.py",
    "tests/test_frontdoor.py",
    "tests/test_hlo_analysis.py",
    "tests/test_kernels.py",
    "tests/test_knowledge_tree.py",
    "tests/test_layers.py",
    "tests/test_model_equivalence.py",
    "tests/test_paged_decode.py",
    "tests/test_paged_pool.py",
    "tests/test_paged_prefill.py",
    "tests/test_perf_guard.py",
    "tests/test_reorder_properties.py",
    "tests/test_replica_router.py",
    "tests/test_retrieval.py",
    "tests/test_scheduling.py",
    "tests/test_serve_main.py",
    "tests/test_serving_metrics.py",
    "tests/test_serving_runtime.py",
    "tests/test_serving_scheduler.py",
    "tests/test_sharding.py",
    "tests/test_simulator.py",
    "tests/test_tiered_cache.py",
    "tests/test_tp_serving.py",
    "tests/test_tpot_topk.py",
    "tests/test_traffic.py",
    "tests/test_training.py",
])


def _manifest():
    with open(REPO / "pyproject.toml", "rb") as f:
        cfg = tomllib.load(f)
    return cfg["tool"]["ruff"]["format"]["exclude"]


def test_no_new_files_land_in_the_manifest():
    added = set(_manifest()) - FROZEN
    assert not added, (
        f"NEW file(s) added to the ruff-format burn-down manifest "
        f"([tool.ruff.format].exclude in pyproject.toml): {sorted(added)}.\n"
        f"The manifest is a ratchet — run `ruff format <file>` and keep the "
        f"file OUT of the exclude list. If that is genuinely impossible "
        f"(no ruff binary in the environment), freeze it explicitly: add "
        f"the path to FROZEN in tests/test_format_ratchet.py AND extend "
        f"the blocker note in pyproject.toml, in the same commit.")


def test_manifest_entries_exist():
    """Deleted/renamed files must leave the manifest — dead entries make
    the burn-down count lie."""
    stale = [p for p in _manifest() if not (REPO / p).is_file()]
    assert not stale, (f"manifest entries with no file on disk: {stale} — "
                      f"remove them from [tool.ruff.format].exclude")


def test_manifest_has_no_duplicates():
    m = _manifest()
    dupes = {p for p in m if m.count(p) > 1}
    assert not dupes, f"duplicate manifest entries: {sorted(dupes)}"


def test_manifest_only_shrinks_against_snapshot():
    """Entries removed from pyproject (reformatted files — the goal!) should
    also be pruned from FROZEN so the snapshot tracks reality."""
    gone = FROZEN - set(_manifest())
    assert not gone, (
        f"FROZEN lists entries no longer in pyproject.toml: {sorted(gone)} "
        f"— prune them from tests/test_format_ratchet.py (ratchet "
        f"progress, keep the snapshot honest)")
