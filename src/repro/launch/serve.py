"""End-to-end RAG serving driver: builds a corpus + vector index,
instantiates a model, and serves a batched Poisson workload through the full
RAGCache pipeline (staged retrieval -> knowledge tree -> prefix prefill ->
decode), printing TTFT/TPOT percentiles and cache statistics.

Default mode is the continuous-batching runtime (iteration-level scheduling,
paged batched decode, retrieval/prefill overlap — ``serving.runtime``);
``--sequential`` serves through the old one-request-at-a-time ``RAGServer``
for A/B comparison, and ``--check-tokens`` runs BOTH and asserts the greedy
tokens are identical.  ``--reuse chunk`` switches the runtime from
prefix-only KV reuse to the per-doc chunk cache (docs/ARCHITECTURE.md §11):
cached docs are reused at ANY position with the first ``--recompute-tokens``
rows of each relocated chunk recomputed.  Relocated reuse is approximate,
so verify it with ``--check-tokens tol:<eps>`` (first-token logit L-inf
tolerance) instead of the default bit-exact mode.

``--replicas N`` serves through N independent continuous runtimes behind a
``ReplicaRouter`` (doc-affinity by default; ``--routing`` picks the policy
for A/B sweeps).  Routing never changes computation — a request's greedy
tokens are a pure function of (docs, question) — so ``--check-tokens``
stays bit-identical to the single sequential engine at any replica count.

``--tp N`` makes each continuous runtime span N devices: params are
sharded by the Megatron column/row rules (launch/sharding.py), the paged
pool's KV-head plane is sharded over the mesh's model axis, and the paged
decode kernel dispatches per shard with head-local block tables
(shard_map).  Tensor parallelism never changes greedy tokens either, so
``--check-tokens`` holds at tp x replicas (2D fleet).  On CPU, expose
devices with XLA_FLAGS=--xla_force_host_platform_device_count=N.

``--frontdoor`` puts the front-door request layer ahead of the router
(serving/frontdoor.py): a query-level cache (exact token-hash + cosine
similarity hits, TTL + LRU bounded), per-tenant SLO-aware admission
(degrade top-k, then shed), and an optional fleet autoscaler
(``--autoscale``) that grows/shrinks the router's active set within
[--autoscale-min, --replicas], warming joining replicas from their disk
tier.  ``--tenants N`` swaps the workload for the multi-tenant traffic
model (retrieval/traffic.py: canonical query pools, per-tenant Zipf +
SLOs, diurnal + Markov-modulated burst arrivals).  With --frontdoor,
``--check-tokens`` compares the front-door *misses* (with any top-k
degradation applied identically to both engines); hits are served from
cache and shed requests never execute, so both are excluded by
construction.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --requests 12 --docs 50 --top-k 2 [--policy lru] [--no-reorder] \
        [--published] [--sequential] [--check-tokens] \
        [--replicas N --routing {affinity,round_robin,least_loaded}] \
        [--gpu-cache-bytes N --host-cache-bytes N \
         --disk-cache-bytes N --disk-cache-dir DIR]

By default the arch's reduced config is served (CPU-sized: 2 layers, narrow
widths); ``--published`` serves its published config (configs/*: every
layer at full width, random weights from ``--seed``), which is what runs on
the chip.  Replica ``i`` owns devices ``[i*tp, (i+1)*tp)`` (wrapping when
the fleet has more replicas than devices).  SSM/hybrid families always use
the sequential engine (recurrent state cannot be paged per-block).

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import pathlib
import time
from typing import List, Optional

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch.mesh import make_serving_mesh
from repro.launch.sharding import (assert_tp_compatible,
                                   serving_param_shardings, spec_summary)
from repro.models import model as M
from repro.retrieval.corpus import make_corpus, make_workload
from repro.retrieval.traffic import make_default_workload
from repro.retrieval.vectordb import IVFIndex
from repro.serving.config import (EngineConfig, FleetConfig, FrontDoorConfig)
from repro.serving.engine import RAGServer
from repro.serving.frontdoor import (TenantSLO, attach_answers,
                                     frontdoor_partition, make_frontdoor)
from repro.serving.metrics import FleetMetrics
from repro.serving.router import (ROUTING_POLICIES, ReplicaRouter,
                                  partition_requests)
from repro.serving.runtime import ContinuousRuntime


REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    other directory is set here; otherwise the cache goes to ``.jax_cache``
    at the root of the checkout — a fixed path, since the path is part of
    what a cache hit needs.  Call before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--published", action="store_true",
                    help="serve the arch's published config (every layer at "
                         "full width; random weights from --seed) instead "
                         "of the reduced CPU-sized one")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--docs", type=int, default=50)
    ap.add_argument("--doc-tokens", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--mode", default="rag", choices=["rag", "cag"],
                    help="workload mode (docs/ARCHITECTURE.md §12): 'rag' "
                         "runs staged retrieval per request; 'cag' "
                         "(cache-augmented generation) pre-inserts the FULL "
                         "corpus KV into the knowledge tree's disk tier at "
                         "startup and serves with zero retrieval stages — "
                         "docs resolve as tier hits promoted through the "
                         "PGDSF cascade.  Needs --disk-cache-bytes sized "
                         "for the whole corpus (0 = auto-size it)")
    ap.add_argument("--policy", default="pgdsf",
                    choices=["pgdsf", "gdsf", "lru", "lfu"])
    ap.add_argument("--gpu-cache-bytes", type=int, default=64 * 2**20,
                    help="knowledge-tree GPU tier budget (bytes)")
    ap.add_argument("--host-cache-bytes", type=int, default=512 * 2**20,
                    help="knowledge-tree host tier budget (bytes)")
    ap.add_argument("--disk-cache-bytes", type=int, default=0,
                    help="mmap'd disk tier budget below host memory "
                         "(0 = disabled); demotion cascades GPU->host->disk "
                         "under one PGDSF clock cascade")
    ap.add_argument("--disk-cache-dir", default=None,
                    help="directory for the disk tier's mmap segment files "
                         "(default: a fresh temp dir)")
    ap.add_argument("--no-reorder", action="store_true")
    ap.add_argument("--no-spec", action="store_true")
    ap.add_argument("--max-new-tokens", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode-batch slots (continuous mode)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="independent continuous-runtime replicas behind "
                         "the doc-affinity router (each owns its own "
                         "knowledge tree / paged store / scheduler)")
    ap.add_argument("--routing", default="affinity",
                    choices=list(ROUTING_POLICIES),
                    help="replica routing policy (A/B-able; routing never "
                         "changes computation, so --check-tokens holds at "
                         "any replica count)")
    ap.add_argument("--max-queue-skew", type=int, default=4,
                    help="affinity escape hatch: max allowed max-min "
                         "per-replica queue-depth skew before a request "
                         "escapes to the least-loaded replica")
    ap.add_argument("--max-shadow-paths", type=int, default=4096,
                    help="bound on the router's shadow ledger of "
                         "per-replica routed doc-set paths (affinity "
                         "routing state, evicted LRU)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="tokens per prefill chunk (0 = unchunked); applies "
                         "to BOTH engines so --check-tokens compares "
                         "identically chunked computations")
    ap.add_argument("--max-prefill-bs", type=int, default=4,
                    help="row slots of the ragged paged-prefill batch "
                         "(continuous paged mode; jit retraces per "
                         "power-of-two chunk bucket above this floor)")
    ap.add_argument("--max-prefill-tokens", type=int, default=0,
                    help="ragged prefill-batch token budget per engine "
                         "iteration (0 = one request per iteration; "
                         "continuous mode)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-KV block size in tokens (continuous mode)")
    ap.add_argument("--reuse", default="prefix",
                    choices=["prefix", "chunk"],
                    help="KV-reuse discipline (docs/ARCHITECTURE.md §11): "
                         "'prefix' reuses the longest cached doc-sequence "
                         "prefix (bit-identical); 'chunk' caches each doc "
                         "ONCE and reuses it at any position, recomputing "
                         "--recompute-tokens boundary rows per relocated "
                         "chunk (approximate — verify with "
                         "--check-tokens tol:<eps>; requires the paged "
                         "engine).  The sequential engine ignores this "
                         "(it stays the exact oracle)")
    ap.add_argument("--recompute-tokens", type=int, default=16,
                    help="boundary tokens recomputed per relocated chunk "
                         "(--reuse chunk); rounds UP to the block size so "
                         "the reused tail stays page-aligned, and clamps "
                         "to the chunk length (>= doc length degenerates "
                         "to an exact full recompute)")
    ap.add_argument("--attn", default="auto",
                    choices=["dense", "paged", "auto"],
                    help="continuous-mode attention engine for BOTH prefill "
                         "and decode: 'paged' computes straight against the "
                         "pool's page arrays (Pallas kernels on TPU, "
                         "per-page jnp online softmax on CPU) — prefill "
                         "scatters new KV into pages in place and decode "
                         "reads O(live tokens) per iteration, no dense KV "
                         "gather anywhere in steady state; 'dense' "
                         "re-materializes the full (L, B, S, KV, hd) "
                         "context every iteration (A/B baseline); 'auto' "
                         "= paged.  Greedy tokens are bit-identical across "
                         "modes; the sequential engine is always dense")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate (req/s)")
    # workload shape (single- and multi-tenant)
    ap.add_argument("--zipf-s", type=float, default=1.2,
                    help="Zipf doc-popularity skew of the workload")
    ap.add_argument("--drift", type=float, default=0.0,
                    help="fraction of popularity ranks reshuffled per "
                         "workload phase (non-stationary traffic; 0 = "
                         "stationary)")
    ap.add_argument("--n-phases", type=int, default=8,
                    help="workload phases for --drift")
    ap.add_argument("--output-len-mean", type=int, default=1,
                    help="mean decode length (1 = MMLU-like; ~6 = "
                         "NaturalQuestions-like)")
    # multi-tenant traffic model (retrieval/traffic.py)
    ap.add_argument("--tenants", type=int, default=0,
                    help="generate the workload from N tenants with "
                         "per-tenant Zipf skew, canonical query pools "
                         "(repeats -> front-door hits) and SLOs "
                         "(0 = single-tenant make_workload)")
    ap.add_argument("--slo-ttft-ms", type=float, default=500.0,
                    help="base per-tenant TTFT SLO target (tenant i gets "
                         "base * (1 + 0.5 i)); also the default SLO for "
                         "single-tenant --frontdoor runs")
    ap.add_argument("--tenant-queries", type=int, default=16,
                    help="canonical query pool size per tenant (smaller = "
                         "more repeats = higher front-door hit rate)")
    ap.add_argument("--diurnal-amplitude", type=float, default=0.0,
                    help="sinusoidal arrival-rate modulation depth (0..1)")
    ap.add_argument("--burst-rate-mult", type=float, default=1.0,
                    help="Markov-modulated burst-state rate multiplier "
                         "(1 = bursts off)")
    # front-door request layer (serving/frontdoor.py)
    ap.add_argument("--frontdoor", action="store_true",
                    help="serve through the front-door layer: query-level "
                         "cache (exact + similarity) -> per-tenant SLO "
                         "admission -> autoscaler -> replica router; "
                         "cache hits never reach an engine")
    ap.add_argument("--frontdoor-ttl", type=float, default=60.0,
                    help="query-cache TTL in seconds (entries expire TTL "
                         "after insertion regardless of use)")
    ap.add_argument("--frontdoor-sim-threshold", type=float, default=0.98,
                    help="cosine threshold for similarity hits against "
                         "cached query vectors (>= 1.0 disables the "
                         "similarity probe)")
    ap.add_argument("--frontdoor-capacity", type=int, default=512,
                    help="query-cache LRU capacity bound (entries)")
    ap.add_argument("--autoscale", action="store_true",
                    help="enable the fleet autoscaler: replicas in "
                         "[--autoscale-min, --replicas] against backlog "
                         "signals; scale-ups warm the joining replica's "
                         "tree from its disk tier")
    ap.add_argument("--autoscale-min", type=int, default=1,
                    help="autoscaler floor (active replicas never below)")
    ap.add_argument("--scale-up-backlog", type=float, default=8.0,
                    help="backlog per active replica above which the "
                         "fleet grows")
    ap.add_argument("--scale-down-backlog", type=float, default=2.0,
                    help="backlog per active replica below which the "
                         "fleet shrinks")
    ap.add_argument("--autoscale-cooldown", type=float, default=2.0,
                    help="seconds between autoscale events")
    ap.add_argument("--search-scale", type=float, default=1.0,
                    help="scale staged-search stage durations (emulate "
                         "paper-scale 78-446 ms searches on a tiny corpus)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree per replica: shard params "
                         "(Megatron col/row), paged-pool KV-head planes and "
                         "decode kernels over a (1, tp) device mesh.  "
                         "Requires tp visible devices (on CPU: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N).  Greedy tokens stay bit-identical to --tp 1, "
                         "so --check-tokens holds at any tp; composes with "
                         "--replicas into a 2D fleet (tp within a replica, "
                         "affinity routing across replicas)")
    ap.add_argument("--sequential", action="store_true",
                    help="serve through the old one-at-a-time RAGServer")
    ap.add_argument("--check-tokens", nargs="?", const="exact", default=None,
                    metavar="MODE",
                    help="run both engines and verify outputs.  Bare flag "
                         "or 'exact': greedy tokens must be bit-identical. "
                         "'tol:<eps>': tokens must match OR the first-token "
                         "logits must agree within L-inf <= eps — the "
                         "verification mode for --reuse chunk, whose "
                         "relocated chunks are approximate by construction")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def model_config(args):
    """The served ModelConfig: published (--published) or reduced."""
    return (get_config if args.published else get_reduced)(args.arch)


def replica_devices(i: int, tp: int) -> list:
    """Replica ``i``'s chips: ``tp`` consecutive devices, wrapping around
    when the fleet has more replicas than devices (CPU tests)."""
    devs = jax.devices()
    if tp > len(devs):
        raise ValueError(
            f"--tp {tp} needs {tp} devices but only {len(devs)} are visible; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{tp} (before the first jax import)")
    return [devs[(i * tp + j) % len(devs)] for j in range(tp)]


def init_params(cfg, seed: int, tp: int = 1):
    """Random weights from ``seed``, created where replica 0 serves them:
    on the default device, or (tp > 1) already sharded over replica 0's
    mesh — a model too large for one chip is never gathered onto one.
    Threefry keys are partitionable, so the values do not depend on tp."""
    fn = functools.partial(M.init_params, cfg)
    key = jax.random.PRNGKey(seed)
    if tp == 1:
        return jax.jit(fn)(key)
    mesh = make_serving_mesh(tp, replica_devices(0, tp))
    shardings = serving_param_shardings(cfg, jax.eval_shape(fn, key), mesh)
    return jax.jit(fn, out_shardings=shardings)(key)


def make_setup(args):
    """Build (cfg, params, corpus, idx, workload, tenants).  ``tenants`` is
    the TenantSpec list when --tenants > 0 (multi-tenant traffic model),
    else None (single-tenant stationary make_workload)."""
    cfg = model_config(args)
    params = init_params(cfg, args.seed, args.tp)
    corpus = make_corpus(args.docs, mean_doc_tokens=args.doc_tokens,
                         vocab=cfg.vocab_size, seed=args.seed)
    idx = IVFIndex(corpus.doc_vectors, n_clusters=min(16, args.docs),
                   nprobe=8)
    if args.tenants > 0:
        tenants, wl = make_default_workload(
            corpus, n_tenants=args.tenants, n_requests=args.requests,
            rate=args.rate, slo_ttft_ms=args.slo_ttft_ms,
            zipf_s=args.zipf_s, n_queries=args.tenant_queries,
            seed=args.seed + 1, drift=args.drift, n_phases=args.n_phases,
            diurnal_amplitude=args.diurnal_amplitude,
            burst_rate_mult=args.burst_rate_mult, vocab=cfg.vocab_size,
            question_tokens=8, output_len_mean=args.output_len_mean)
        return cfg, params, corpus, idx, wl, tenants
    wl = make_workload(corpus, n_requests=args.requests, rate=args.rate,
                       question_tokens=8, vocab=cfg.vocab_size,
                       zipf_s=args.zipf_s, seed=args.seed + 1,
                       drift=args.drift, n_phases=args.n_phases,
                       output_len_mean=args.output_len_mean)
    return cfg, params, corpus, idx, wl, None


def tier_hit_line(tree) -> str:
    s = tree.stats
    return (f"tier hits (tokens): gpu {s['hit_tokens_gpu']} / "
            f"host {s['hit_tokens_host']} / disk {s['hit_tokens_disk']}  "
            f"(spilled {s['spill_bytes']} B, fetched {s['fetch_bytes']} B)")


def parse_check_mode(value):
    """--check-tokens MODE -> ("exact", 0.0) or ("tol", eps).

    'exact' (or the bare flag) keeps the bit-identical contract; 'tol:<eps>'
    accepts token divergence when the first-token logits agree within
    L-inf <= eps — the only honest check for --reuse chunk, whose relocated
    chunks keep their original RoPE rotations (approximate by design)."""
    if value is None or value == "exact":
        return "exact", 0.0
    if isinstance(value, str) and value.startswith("tol:"):
        try:
            eps = float(value[len("tol:"):])
        except ValueError:
            raise SystemExit(f"--check-tokens: bad tolerance {value!r}")
        if eps < 0 or not np.isfinite(eps):
            raise SystemExit(f"--check-tokens: tolerance must be a finite "
                             f"non-negative number, got {value!r}")
        return "tol", eps
    raise SystemExit(f"--check-tokens: unknown mode {value!r} "
                     f"(use 'exact' or 'tol:<eps>')")


def _linf(a, b) -> Optional[float]:
    """First-token logit L-inf of two results; None unless both carry
    logits."""
    if a.first_logits is None or b.first_logits is None:
        return None
    return float(np.max(np.abs(np.asarray(a.first_logits, np.float64)
                               - np.asarray(b.first_logits, np.float64))))


def first_logit_linf(pairs) -> Optional[float]:
    """Largest first-token logit L-inf over (continuous, oracle) result
    pairs that both carry logits; None when no pair does."""
    d = [x for x in (_linf(a, b) for a, b in pairs) if x is not None]
    return max(d) if d else None


def token_mismatches(pairs, mode, eps):
    """Compare (continuous, sequential) result pairs under a check mode.

    exact: greedy tokens must be bit-identical.  tol: tokens may diverge iff
    both sides carry first-token logits within L-inf <= eps.  Returns the
    offending (req_id, tokens_a, tokens_b[, linf]) tuples."""
    bad = []
    for a, b in pairs:
        if list(a.tokens) == list(b.tokens):
            continue
        linf = _linf(a, b) if mode == "tol" else None
        if linf is not None:
            if linf <= eps:
                continue
            bad.append((a.req_id, list(a.tokens), list(b.tokens), linf))
        else:
            bad.append((a.req_id, list(a.tokens), list(b.tokens)))
    return bad


def serve_sequential(cfg, params, corpus, idx, wl, args, econf=None):
    # The sequential engine is the single-device token oracle: it takes the
    # same EngineConfig but deliberately ignores config.mesh, so
    # --check-tokens compares sharded continuous vs unsharded sequential.
    econf = econf if econf is not None else EngineConfig.from_args(args)
    params = jax.device_put(params, jax.devices()[0])
    srv = RAGServer(cfg, params, corpus, idx, config=econf)
    _print_preload(srv)
    t0 = time.time()
    results = srv.serve(wl, max_new_tokens=args.max_new_tokens)
    wall = time.time() - t0
    results = sorted(results, key=lambda r: r.req_id)
    print(f"\n[sequential] served {len(results)} requests in {wall:.1f}s "
          f"(incl. jit compiles)")
    print(f"{'req':>4} {'docs':>12} {'alpha':>6} {'beta':>5} "
          f"{'ttft_ms':>8}  tokens")
    for r in results:
        print(f"{r.req_id:>4} {str(r.docs):>12} {r.alpha:>6} {r.beta:>5} "
              f"{r.ttft * 1000:>8.1f}  {r.tokens}")
    ttfts = np.asarray([r.ttft for r in results])
    print(f"mean TTFT {ttfts.mean() * 1e3:.1f} ms  "
          f"(search+transfer+prefill summed serially)")
    print(f"doc hit rate: {srv.controller.doc_hit_rate:.2%}")
    print(tier_hit_line(srv.tree))
    print(f"tree stats: {srv.tree.stats}")
    return results


def _print_preload(engine, n_replicas: int = 1) -> None:
    """One-line CAG corpus-preload summary (docs/ARCHITECTURE.md §12)."""
    ps = getattr(engine, "preload_stats", None)
    if ps:
        per = f" per replica x{n_replicas}" if n_replicas > 1 else ""
        print(f"[cag] preloaded {ps['docs']} docs / {ps['tokens']} tokens "
              f"({ps['bytes']} B) into the disk tier in "
              f"{ps['seconds']:.2f}s{per}")


def make_runtimes(cfg, params, corpus, idx, args, n, econf=None):
    """``n`` continuous runtimes, replica ``i`` placed on
    ``replica_devices(i, tp)``: its params and paged pool live there."""
    econf = econf if econf is not None else EngineConfig.from_args(args)
    return [ContinuousRuntime(cfg, params, corpus, idx, config=econf,
                              devices=replica_devices(i, econf.mesh.tp))
            for i in range(n)]


def serve_continuous(cfg, params, corpus, idx, wl, args, econf=None,
                     fleet_conf=None):
    n = max(1, args.replicas)
    fleet_conf = (fleet_conf if fleet_conf is not None
                  else FleetConfig.from_args(args))
    rts = make_runtimes(cfg, params, corpus, idx, args, n, econf=econf)
    _print_preload(rts[0], n)
    router = ReplicaRouter(rts, config=fleet_conf)
    # partition the trace in arrival order by the request's retrieved docs
    # (deterministic, equal to the runtime's final staged-search result);
    # the in-flight window models per-replica backlog draining while the
    # trace arrives (each replica decodes max_batch requests concurrently)
    shares = partition_requests(
        router, wl,
        docs_of=lambda r: idx.search(r.query_vec, args.top_k),
        doc_tokens_of=lambda docs: [int(corpus.doc_lengths[d])
                                    for d in docs],
        context_of=lambda r, docs, toks: sum(toks) + len(r.question_tokens),
        window=2 * args.max_batch * n)
    t0 = time.time()
    results = []
    for rt, share in zip(rts, shares):
        if share:
            results.extend(rt.serve(share,
                                    max_new_tokens=args.max_new_tokens))
    wall = time.time() - t0
    results.sort(key=lambda r: r.req_id)
    label = "continuous" if n == 1 else f"continuous x{n} ({args.routing})"
    print(f"\n[{label}] served {len(results)} requests in {wall:.1f}s "
          f"wall (incl. jit compiles)")
    print(f"{'req':>4} {'docs':>12} {'alpha':>6} {'beta':>5} "
          f"{'ttft_ms':>8} {'spec':>5}  tokens")
    for r in results:
        print(f"{r.req_id:>4} {str(r.docs):>12} {r.alpha:>6} {r.beta:>5} "
              f"{r.ttft * 1000:>8.1f} {'hit' if r.speculative_hit else '':>5}"
              f"  {r.tokens}")
    print()
    if n == 1:
        print(rts[0].metrics.format_report())
        print(tier_hit_line(rts[0].tree))
        print(f"tree stats: {rts[0].tree.stats}")
    else:
        fleet = FleetMetrics(router.stats())
        for i, rt in enumerate(rts):
            fleet.add_replica(f"replica{i}", rt.metrics)
        print(fleet.format_report())
        for i, rt in enumerate(rts):
            print(f"replica{i} {tier_hit_line(rt.tree)}")
    return results, rts


def build_frontdoor(args, tenants, fdc=None):
    """Assemble the FrontDoor policy stack from CLI flags (via
    FrontDoorConfig).  The SAME constructor path the simulator benchmarks
    use (make_frontdoor), so every driver assembles the identical policy
    objects."""
    fdc = fdc if fdc is not None else FrontDoorConfig.from_args(args)
    slos = {}
    if tenants:
        slos = {t.name: TenantSLO(ttft_target=t.slo_ttft_ms / 1e3,
                                  min_top_k=t.min_top_k) for t in tenants}
    n = max(1, args.replicas)
    return make_frontdoor(
        capacity=fdc.capacity, ttl=fdc.ttl,
        sim_threshold=fdc.sim_threshold, slos=slos,
        default_slo_ttft=fdc.slo_ttft_ms / 1e3, top_k=args.top_k,
        min_replicas=min(max(1, fdc.autoscale_min), n), max_replicas=n,
        autoscale=fdc.autoscale,
        scale_up_backlog=fdc.scale_up_backlog,
        scale_down_backlog=fdc.scale_down_backlog,
        cooldown=fdc.cooldown)


def serve_frontdoor(cfg, params, corpus, idx, wl, tenants, args, econf=None,
                    fleet_conf=None, fdc=None):
    """Serve through front door -> router -> N continuous runtimes.

    Returns (miss_results, part): engine results for admitted misses (the
    --check-tokens comparison set; hits are served from cache and shed
    requests never execute, so both are excluded by construction)."""
    n = max(1, args.replicas)
    fleet_conf = (fleet_conf if fleet_conf is not None
                  else FleetConfig.from_args(args))
    rts = make_runtimes(cfg, params, corpus, idx, args, n, econf=econf)
    _print_preload(rts[0], n)
    router = ReplicaRouter(rts, config=fleet_conf)
    fd = build_frontdoor(args, tenants, fdc=fdc)
    part = frontdoor_partition(
        fd, router, wl,
        docs_of=lambda r: idx.search(r.query_vec,
                                     r.top_k if r.top_k > 0 else args.top_k),
        doc_tokens_of=lambda docs: [int(corpus.doc_lengths[d])
                                    for d in docs],
        context_of=lambda r, docs, toks: sum(toks) + len(r.question_tokens),
        window=2 * args.max_batch * n)
    t0 = time.time()
    results = []
    for rt, share in zip(rts, part.shares):
        if share:
            results.extend(rt.serve(share,
                                    max_new_tokens=args.max_new_tokens))
    wall = time.time() - t0
    results.sort(key=lambda r: r.req_id)
    # answers only exist after serving: fill the cache entries (hits share
    # the entry object, so the cached answer reaches them too)
    attach_answers(part, {r.req_id: r.tokens for r in results})
    label = f"frontdoor x{n} ({args.routing})"
    print(f"\n[{label}] {len(wl)} requests -> {len(part.hits)} cache hits, "
          f"{len(part.shed)} shed, {len(results)} engine-served in "
          f"{wall:.1f}s wall (incl. jit compiles)")
    for r, dec in part.hits:
        src = dec.entry.answer if dec.entry is not None else []
        print(f"{r.req_id:>4} {dec.kind:<11} <- req {dec.entry.source_req_id}"
              f"  tokens {src}")
    fleet = FleetMetrics(router.stats(), fd.stats())
    for i, rt in enumerate(rts):
        fleet.add_replica(f"replica{i}", rt.metrics)
    print(fleet.format_report())
    if part.warmed:
        for i, b in sorted(part.warmed.items()):
            print(f"scale-up warmed replica{i}: {b} B from disk tier")
    return results, part, rts


@dataclasses.dataclass
class ServeOutcome:
    """What ``main`` served: the config, the engine results by req_id, the
    continuous runtimes that produced them (empty when only the sequential
    engine ran), and the first-token logit L-inf against the sequential
    oracle (--check-tokens only)."""
    cfg: object
    results: list
    runtimes: List[ContinuousRuntime]
    linf: Optional[float] = None


def check_tokens(cont, seq, args, noun: str, note: str) -> float:
    """--check-tokens: compare continuous results against the sequential
    oracle's, print the verdict and the first-token logit L-inf, and exit
    non-zero on a mismatch.  Returns the L-inf."""
    mode, eps = parse_check_mode(args.check_tokens)
    seq_by_id = {r.req_id: r for r in seq}
    pairs = [(a, seq_by_id[a.req_id]) for a in cont]
    linf = first_logit_linf(pairs)
    same = sum(list(a.tokens) == list(b.tokens) for a, b in pairs)
    print(f"{same}/{len(pairs)} requests with identical tokens; first-token "
          f"logit L-inf vs the sequential oracle: max {linf}")
    mismatches = token_mismatches(pairs, mode, eps)
    if mismatches:
        raise SystemExit(f"token mismatch: {mismatches}")
    what = "identical" if mode == "exact" else f"within tol {eps:g}"
    print(f"\ntoken check: all {len(cont)} {noun} {what} ({note})")
    return linf


def main(argv=None) -> ServeOutcome:
    setup_compile_cache()
    args = build_parser().parse_args(argv)
    # the config dataclasses are built ONCE from argparse here and threaded
    # through every constructor below — config= is the SOLE constructor
    # API; loose kwargs raise TypeError (serving/config.py,
    # docs/ARCHITECTURE.md §10)
    econf = EngineConfig.from_args(args)
    fleet_conf = FleetConfig.from_args(args)
    fdc = FrontDoorConfig.from_args(args)
    if econf.mesh.tp > 1:
        # validate head divisibility BEFORE any device work or device-count
        # check, so a bad --arch/--tp pair fails fast on any machine
        try:
            assert_tp_compatible(model_config(args), econf.mesh.tp)
            replica_devices(0, econf.mesh.tp)
        except ValueError as e:
            raise SystemExit(f"--tp {econf.mesh.tp}: {e}")
    cfg, params, corpus, idx, wl, tenants = make_setup(args)
    print(f"model={cfg.name} family={cfg.family} layers={cfg.n_layers} "
          f"d_model={cfg.d_model}")
    if args.mode == "cag" and econf.disk_cache_bytes == 0 \
            and cfg.family not in ("ssm", "hybrid"):
        # auto-size the disk tier to hold the whole corpus KV exactly
        kv_bytes = max(1, 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd
                       * np.dtype(cfg.jdtype).itemsize)
        need = int(corpus.doc_lengths.sum()) * kv_bytes
        econf = dataclasses.replace(econf, disk_cache_bytes=need)
        print(f"[cag] --disk-cache-bytes 0 -> auto-sized to {need} B "
              f"({len(corpus.doc_lengths)} docs, {kv_bytes} B/token)")
    if econf.mesh.tp > 1:
        print(f"tensor parallel: tp={econf.mesh.tp} over a "
              f"(1, {econf.mesh.tp}) mesh "
              f"({jax.local_device_count()} devices visible)")
        smesh = make_serving_mesh(econf.mesh.tp,
                                  replica_devices(0, econf.mesh.tp))
        print(spec_summary(cfg, smesh, params))

    recurrent = cfg.family in ("ssm", "hybrid")
    if recurrent and not args.sequential:
        print("note: recurrent-state family -> sequential engine")
    if args.replicas > 1 and (recurrent or args.sequential):
        print("note: --replicas applies to the continuous engine only; "
              "the sequential A/B side stays a single engine")
    if recurrent and args.check_tokens:
        print("note: --check-tokens unavailable for recurrent families "
              "(no continuous engine to compare against); NOT checked")
    if args.frontdoor and (recurrent or args.sequential):
        print("note: --frontdoor requires the continuous engine; ignored")
    if econf.mesh.tp > 1 and (recurrent or args.sequential):
        print("note: --tp applies to the continuous engine only; the "
              "sequential engine is the single-device token oracle")
    if args.frontdoor and not recurrent and not args.sequential:
        miss_results, part, rts = serve_frontdoor(
            cfg, params, corpus, idx, wl, tenants, args, econf=econf,
            fleet_conf=fleet_conf, fdc=fdc)
        out = ServeOutcome(cfg, miss_results, rts)
        if args.check_tokens:
            # compare ONLY admitted misses (the requests an engine actually
            # served, with the front door's top_k rewrites applied); hits
            # are answered from cache and shed requests never execute
            seq = serve_sequential(cfg, params, corpus, idx,
                                   list(part.misses), args, econf=econf)
            out.linf = check_tokens(
                miss_results, seq, args, "front-door miss requests",
                f"continuous vs sequential; {len(part.hits)} hits + "
                f"{len(part.shed)} shed excluded by construction")
        return out
    checked = bool(args.check_tokens) and not recurrent
    if (args.sequential or recurrent) and not checked:
        seq = serve_sequential(cfg, params, corpus, idx, wl, args,
                               econf=econf)
        return ServeOutcome(cfg, sorted(seq, key=lambda r: r.req_id), [])
    cont, rts = serve_continuous(cfg, params, corpus, idx, wl, args,
                                 econf=econf, fleet_conf=fleet_conf)
    out = ServeOutcome(cfg, cont, rts)
    if checked:
        seq = serve_sequential(cfg, params, corpus, idx, wl, args,
                               econf=econf)
        out.linf = check_tokens(cont, seq, args, "requests",
                                "continuous vs sequential")
    return out


if __name__ == "__main__":
    main()
