"""Continuous-batching async serving runtime (real execution).

This is the event-loop engine the paper's serving numbers assume and the
sequential ``RAGServer`` lacks: iteration-level scheduling over many
concurrent requests, with

  * staged vector search running OFF the engine's critical path — each
    request's search stages are events on the runtime clock, and the
    ``SpeculativeController``'s per-stage decisions actually launch and
    terminate speculative prefills that overlap the remaining search
    (paper §5.3, Algorithm 2);
  * one engine iteration at a time: a *chunked, ragged-batched* prefill
    iteration — continuations of in-flight chunked prefills plus newly
    admitted jobs picked by the cache-aware ``ReorderQueue``, packed up to
    ``max_prefill_tokens`` — or ONE batched decode step for every running
    request.  A prefill split into ``prefill_chunk``-token pieces carries
    its partial KV across iterations in the paged store, and stale
    speculation is cancelled *between* chunks (the partial KV is freed and
    the remaining chunk tokens are never computed);
  * batched decode through the ``PagedKVStore``: each running request owns a
    token-level slot mapping (position -> (block, slot)); EVERY cached
    knowledge-tree document segment of the hit prefix is REFCOUNT-SHARED
    into it — block-aligned or not, since the mapping absorbs unaligned
    tails.  With ``attn="paged"`` (the default via "auto") each iteration
    runs per-layer paged attention STRAIGHT from the pool's page arrays
    through run tables (kernels/paged_attention.py: Pallas kernel on TPU,
    per-page jnp online softmax on CPU) and appends the new token's KV in
    place at its (block, slot) — nothing materializes the dense
    (L, B, S, KV, hd) context.  ``attn="dense"`` keeps the old slot-map
    gather + token scatter as an A/B baseline; greedy tokens are
    bit-identical across modes;
  * admission control and preemption by paged-block / tree-pin budget via
    the shared ``ContinuousBatchScheduler`` (the same policy object the
    discrete-event simulator executes) — the pin budget counts promote
    tokens too, so a hit path parked on host/disk cannot over-admit;
  * an optional mmap'd DISK tier below the host copies
    (``--disk-cache-bytes``): the knowledge tree demotes GPU -> host ->
    disk under one PGDSF clock cascade, and disk reads for a matched
    prefix are prefetched into host memory DURING the remaining retrieval
    stages (host-side I/O overlaps the accelerator exactly like the staged
    search), so the engine-critical promote stays a host->GPU copy.  See
    docs/ARCHITECTURE.md §2.

Clock semantics: the runtime keeps a virtual clock (seconds).  Engine
iterations advance it by their *measured* wall time (real JAX compute;
prefill shapes still jit-compile on first occurrence — NOTE that chunked
prefill multiplies unique (prefix_len, piece) shapes, so on this CPU-tiny
setup small chunk sizes are compile-dominated and chunked-mode latency
numbers include those compiles, like every prefill here; a production
deployment would bucket prefix lengths); retrieval stages
advance their own per-request lanes by max(measured stage wall time,
analytic stage cost) — search runs on host CPUs concurrently with the
accelerator, which is the paper's testbed overlap model.  TTFT is therefore
max(search_end, prefill_end) - arrival, NOT the serial sum the sequential
engine reports.

Families: attention-only (dense / moe / vlm).  SSM and hybrid recurrent
state cannot be paged per-block; serve those through the sequential engine.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.controller import RAGController, effective_recompute
from repro.core.knowledge_tree import (CacheBackend, EvictionError,
                                       KnowledgeTree)
from repro.core.profiler import CostProfiler
from repro.core.speculative import SpecState, SpeculativeController
from repro.kvcache.paged import (DiskSegmentStore, OutOfBlocks, PagedKVStore,
                                 PagedSegment, gather_slots, make_disk_store,
                                 max_write_runs, run_starts, scatter_slots)
from repro.launch.mesh import make_serving_mesh
from repro.launch.sharding import (assert_tp_compatible, pool_kv_spec,
                                   serving_param_shardings)
from repro.serving.config import (EngineConfig, MeshConfig,
                                  reject_legacy_kwargs)
from repro.models import layers as L
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.retrieval.corpus import Corpus, Request
from repro.serving.metrics import ServingMetrics
from repro.serving.scheduler import (DECODE, PREEMPT, PREFILL,
                                     ContinuousBatchScheduler, PagedAdmission,
                                     SchedulerConfig, prefill_piece_sizes)

WAITING, RUNNING, FINISHED = "waiting", "running", "finished"


class PagedBackend(CacheBackend):
    """Tree payloads are PagedSegments in the shared device store; the host
    tier holds dense numpy copies; the optional disk tier holds one mmap
    file per node (``DiskSegmentStore``). Transfer seconds are measured.

    The store may be placed on one chip or KV-head-sharded over a TP mesh;
    both hops then copy per device: demote (``swap_out``) pulls each
    device's head slice once and reassembles the dense host copy, and
    promote (``load``) hands the store host numpy, which it copies straight
    to where the pool lives (``PagedKVStore._place_segment``) — never a
    full replica on one device that the pool write would then reshard.

    Each hop runs inside a ``rt.tree.*`` span of ``metrics``, which also
    times it."""

    def __init__(self, store: PagedKVStore,
                 disk: Optional[DiskSegmentStore] = None,
                 metrics: Optional[ServingMetrics] = None):
        self.store = store
        self.disk = disk
        self.metrics = metrics if metrics is not None else ServingMetrics()

    def swap_out(self, node):
        with self.metrics.span("rt.tree.demote") as sp:
            k, v = jax.device_get(self.store.gather(node.payload_gpu))
            node.payload_host = {"k": np.asarray(k), "v": np.asarray(v)}
        return sp.seconds

    def load(self, node):
        with self.metrics.span("rt.tree.promote") as sp:
            try:
                node.payload_gpu = self.store.put(node.payload_host["k"],
                                                  node.payload_host["v"])
            except OutOfBlocks as e:
                raise EvictionError(str(e))   # promote() degrades to recompute
            jax.block_until_ready(self.store.k)
        return sp.seconds

    def spill(self, node):
        with self.metrics.span("rt.tree.spill") as sp:
            node.payload_disk = self.disk.write(node.payload_host["k"],
                                                node.payload_host["v"])
        return sp.seconds

    def fetch(self, node):
        with self.metrics.span("rt.tree.fetch") as sp:
            k, v = self.disk.read(node.payload_disk)
            node.payload_host = {"k": k, "v": v}
        return sp.seconds

    def free_gpu(self, node):
        if node.payload_gpu is not None:
            self.store.free(node.payload_gpu)
        node.payload_gpu = None

    def free_disk(self, node):
        if node.payload_disk is not None:
            self.disk.delete(node.payload_disk)
        node.payload_disk = None


@dataclasses.dataclass
class _PrefillResult:
    docs: Tuple[int, ...]
    cache: Optional[dict]           # dense full-sequence cache (L, 1, T, ...)
                                    # — None in paged-prefill mode
    first_token: int
    total_len: int
    alpha: int
    beta: int
    hit_docs: int
    hit_tier_tokens: Tuple[int, int, int]   # alpha split by (gpu, host, disk)
    speculative: bool
    started: float
    # paged-prefill mode: the computed KV already lives in the pool — the
    # result holds the page coordinates, not a dense copy.  hit_runs are
    # (blocks, n_tokens) snapshots of the shared (incref'd) prefix nodes;
    # pg_segs are the request-owned segments (uncached docs + question), in
    # sequence order.  Both lists are emptied when _paginate consumes them
    # (ownership transfers to the decode table) or _free_paged_kv drops them.
    hit_runs: List[Tuple[List[int], int]] = dataclasses.field(
        default_factory=list)
    pg_segs: List[PagedSegment] = dataclasses.field(default_factory=list)
    # ordered sequence layout: ("run"|"seg", index into hit_runs/pg_segs,
    # absolute start position).  Prefix mode is runs-then-segs; chunk mode
    # (--reuse chunk) interleaves shared runs and computed segments.
    layout: List[Tuple[str, int, int]] = dataclasses.field(
        default_factory=list)
    exact: bool = True              # False once a relocated chunk was reused
    first_logits: Optional[np.ndarray] = None   # (V,) at the first token


@dataclasses.dataclass
class _ChunkState:
    """Engine-side state of an in-flight chunked prefill: plan, execution
    cursor over the to-be-computed segments, remaining piece sizes, and the
    partial KV paged into the store between iterations."""
    plan: object                    # RequestPlan
    segs: List[np.ndarray]          # token arrays: uncached docs + question
    doc_bounds: List[Tuple[int, int]]  # (abs_start, length) per uncached doc
    pieces: List[int]               # remaining piece sizes (shared splitter)
    total: int                      # beta tokens in all pieces at start
    seg_idx: int = 0
    seg_off: int = 0
    plen: int = 0                   # absolute tokens prefixed so far
    prefix_hit: Optional[dict] = None  # dense cached-prefix KV (alpha tokens)
    partial_seg: Optional[object] = None  # PagedSegment of computed tokens
                                          # (dense mode only)
    cache: Optional[dict] = None    # dense full-seq cache, set when the
                                    # last piece completes (commit/paginate)
    logits: Optional[object] = None
    # paged-prefill mode: no dense KV at all.  hit_runs snapshot the shared
    # (pinned + incref'd) prefix nodes' pages; pg_segs hold one (initially
    # empty) segment per to-compute segment in ``segs`` — the step
    # writes each chunk's KV straight into their freshly allocated pages.
    hit_runs: List[Tuple[List[int], int]] = dataclasses.field(
        default_factory=list)
    pg_segs: List[PagedSegment] = dataclasses.field(default_factory=list)
    # paged mode: ordered layout of the full sequence (see _PrefillResult).
    # seg_abs[i] is the absolute start position of compute segment i —
    # chunk mode scatters compute segments between shared runs, so the
    # cursor's q_start is seg_abs[seg_idx] + seg_off, not a running prefix.
    layout: List[Tuple[str, int, int]] = dataclasses.field(
        default_factory=list)
    seg_abs: List[int] = dataclasses.field(default_factory=list)
    miss_segs: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Job:
    req: "_ReqRun"
    docs: Tuple[int, ...]
    speculative: bool
    enqueued: float
    cancelled: bool = False
    started: float = -1.0
    cs: Optional[_ChunkState] = None


@dataclasses.dataclass
class _ReqRun:
    r: Request
    tl: object                      # RequestTimeline
    spec: SpecState
    state: str = WAITING
    final_docs: Optional[Tuple[int, ...]] = None
    jobs: List[_Job] = dataclasses.field(default_factory=list)
    results: Dict[Tuple[int, ...], _PrefillResult] = dataclasses.field(
        default_factory=dict)
    start_by_docs: Dict[Tuple[int, ...], float] = dataclasses.field(
        default_factory=dict)
    # decode state: token-level slot mapping — position p of the request's
    # sequence lives at (pos_blk[p], pos_slot[p]) in the paged store
    pos_blk: List[int] = dataclasses.field(default_factory=list)
    pos_slot: List[int] = dataclasses.field(default_factory=list)
    owned_blocks: List[int] = dataclasses.field(default_factory=list)
    length: int = 0
    last_tok: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    remaining: int = 0
    exact: bool = True
    first_logits: Optional[np.ndarray] = None


@dataclasses.dataclass
class RuntimeResult:
    req_id: int
    tokens: List[int]
    ttft: float
    docs: Tuple[int, ...]
    alpha: int
    beta: int
    speculative_hit: bool
    # chunk-cache mode: False when a relocated chunk was reused (outputs are
    # approximate — verify with --check-tokens tol:<eps>); prefix mode and
    # full recomputes stay True (bit-identical contract holds).
    exact: bool = True
    first_logits: Optional[np.ndarray] = None   # (V,) logits at first token


class ContinuousRuntime:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        corpus: Corpus,
        index,
        *,
        config: Optional[EngineConfig] = None,
        n_blocks: Optional[int] = None,
        reorder_window: int = 32,
        profiler: Optional[CostProfiler] = None,
        devices: Optional[Sequence] = None,
        **legacy,
    ):
        # ``config=`` is the SOLE constructor API (serving/config.py): one
        # frozen EngineConfig carries the whole knob surface, and any
        # pre-PR 7 loose kwarg raises a TypeError naming the config field
        # that replaced it.  ``n_blocks`` / ``reorder_window`` /
        # ``profiler`` / ``devices`` stay explicit kwargs: they take
        # test-only shapes or live objects that don't belong in a
        # CLI-round-trip config.  ``devices``: the chips this replica owns
        # (``mesh.tp`` of them; None = the first ``tp`` visible devices).
        reject_legacy_kwargs("ContinuousRuntime", legacy, EngineConfig)
        config = config if config is not None else EngineConfig()
        gpu_cache_bytes = config.gpu_cache_bytes
        host_cache_bytes = config.host_cache_bytes
        disk_cache_bytes = config.disk_cache_bytes
        disk_cache_dir = config.disk_cache_dir
        policy = config.policy
        top_k = config.top_k
        reorder = config.reorder
        speculative = config.speculative
        max_batch = config.max_batch
        max_prefill_bs = config.max_prefill_bs
        prefill_chunk = config.prefill_chunk
        max_prefill_tokens = config.max_prefill_tokens
        block_size = config.block_size
        attn = config.attn
        attn_impl = config.attn_impl
        reuse = config.reuse
        recompute_tokens = config.recompute_tokens
        search_time_scale = config.search_time_scale
        mesh = config.mesh
        if cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                "recurrent-state families cannot be paged per-block; "
                "use the sequential RAGServer for ssm/hybrid")
        if attn not in ("dense", "paged", "auto"):
            raise ValueError(f"unknown attn mode {attn!r}")
        # "auto" resolves to the paged engine: Pallas kernel on TPU, the
        # pure-jnp per-page path elsewhere (kernels/ops.py dispatch).  The
        # dense gather survives only as the explicit --attn dense baseline.
        self.attn = "paged" if attn == "auto" else attn
        self.attn_impl = attn_impl
        if reuse not in ("prefix", "chunk"):
            raise ValueError(f"unknown reuse mode {reuse!r}")
        if reuse == "chunk" and self.attn != "paged":
            # relocated reuse needs per-run absolute positions in the run
            # table (boundary rows attend at their NEW positions over pages
            # cached elsewhere) — the dense gather has no such contract
            raise ValueError("--reuse chunk requires the paged engine "
                             "(--attn paged/auto)")
        self.reuse = reuse
        self.recompute_tokens = int(recompute_tokens)
        self.mode = config.mode
        if self.mode == "cag" and disk_cache_bytes <= 0:
            raise ValueError(
                "mode='cag' preloads the whole corpus KV into the disk tier "
                "and needs disk_cache_bytes > 0 sized for the corpus")
        self.cfg = cfg
        self.corpus = corpus
        self.index = index
        self.top_k = top_k
        self.search_time_scale = search_time_scale
        # ---- placement: one chip, or tp chips (tensor parallelism) -------
        # Params shard per launch/sharding.py::serving_param_shardings
        # (Megatron column rules; the two row matrices replicate — see the
        # deterministic-TP note there); the pool's (L, n_blocks, KV, block,
        # hd) planes shard whole KV heads over the "model" axis; block
        # tables / slot mappings / run tables stay replicated (they are
        # head-independent), so every scheduler/tree decision is identical
        # at any tp.  Model code traces under layers.tp_deterministic so
        # row-parallel contractions gather instead of all-reducing — the
        # bit-identical --check-tokens contract across mesh sizes.
        self.mesh_cfg = mesh or MeshConfig()
        self._mesh = None
        self._kv_sharding = None          # set only under TP
        pool_sharding = None              # None = the default device
        if self.mesh_cfg.tp > 1:
            assert_tp_compatible(cfg, self.mesh_cfg.tp)
            self._mesh = make_serving_mesh(self.mesh_cfg.tp, devices)
            params = jax.device_put(
                params, serving_param_shardings(cfg, params, self._mesh))
            self._kv_sharding = NamedSharding(self._mesh, pool_kv_spec())
            pool_sharding = self._kv_sharding
        elif devices is not None:
            pool_sharding = SingleDeviceSharding(devices[0])
            params = jax.device_put(params, pool_sharding)
        # CAG preloads compute each doc's KV through the single-device dense
        # prefill (bit-identical to the sequential oracle by construction);
        # the pool re-places host copies on promote, so the preloaded tier
        # bytes work at any tp.  Only CAG keeps this unsharded copy.
        self._preload_params = None
        if self.mode == "cag":
            one = devices[0] if devices is not None else jax.devices()[0]
            self._preload_params = jax.device_put(params, one)
        self.params = params
        kv_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd
                    * jnp.dtype(cfg.jdtype).itemsize)
        if n_blocks is None:
            n_blocks = int(np.clip(
                gpu_cache_bytes // (block_size * kv_bytes) + 64, 128, 4096))
        self.store = PagedKVStore(cfg.n_layers, n_blocks, block_size,
                                  cfg.n_kv_heads, cfg.hd,
                                  dtype=cfg.jdtype, device=True,
                                  sharding=pool_sharding)
        self._scratch_block = self.store.pool.alloc(1)[0]  # dummy-row sink
        self.disk = make_disk_store(disk_cache_dir, disk_cache_bytes)
        self.metrics = ServingMetrics()
        self.tree = KnowledgeTree(
            gpu_cache_bytes, host_cache_bytes,
            disk_cache_bytes if self.disk is not None else 0,
            policy=policy,
            profiler=profiler or CostProfiler.from_fn(
                lambda a, b: 1e-4 * b + 2e-8 * b * (a + b),
                (0, 64, 256, 1024), (1, 32, 128, 512, 1024)),
            backend=PagedBackend(self.store, self.disk, self.metrics),
            bytes_per_token=max(kv_bytes, 1),
        )
        self.controller = RAGController(self.tree)
        self.spec_ctl = SpeculativeController(max_prefill_bs,
                                              enabled=speculative)
        self.max_new_tokens = 4       # refined per serve()
        self.admission = PagedAdmission(self.store.pool, self.tree,
                                        decode_reserve=self.max_new_tokens)
        self.sched: ContinuousBatchScheduler[_Job] = ContinuousBatchScheduler(
            SchedulerConfig(max_batch=max_batch,
                            max_prefill_bs=max_prefill_bs,
                            reorder=reorder, reorder_window=reorder_window,
                            prefill_chunk=prefill_chunk,
                            max_prefill_tokens=max_prefill_tokens),
            viable=self._job_viable, admit=self._job_admissible)
        self.metrics.prefill_token_budget = max_prefill_tokens
        self._partial_jobs: List[_Job] = []   # jobs with live chunk state

        # The jitted steps are named functions, so each program carries its
        # name into the HLO and the profiler trace (``jit(rt_prefill_step)``).
        # No step or scope name may contain a kernel's name (``paged_prefill``
        # / ``paged_decode``): a trace reader credits a kernel with every op
        # whose text holds that name.
        def rt_dense_prefill(p, toks, pc, pl):
            return M.prefill(cfg, p, {"tokens": toks}, prefix_cache=pc,
                             prefix_len=pl)

        self._prefill_fn = jax.jit(rt_dense_prefill, static_argnames=("pl",))
        # paged-prefill step: ragged chunk rows computed straight against
        # the (donated) pool planes — jit retraces per (B, Sq) bucket, like
        # the dense prefill retraces per (prefix_len, piece) shape
        _impl, _tp_mesh = attn_impl, self._mesh
        # the most segments one prefill row crosses, which bounds the pages
        # it writes: a chunked splitter keeps each piece inside a segment;
        # an unchunked prefix plan walks every uncached doc, then the question
        self._row_segments = (1 if prefill_chunk > 0 or reuse == "chunk"
                              else top_k + 1)
        _segments = self._row_segments

        def rt_prefill_step(p, toks, tb, cn, sts, qs, ql, wb, ws, kp, vp):
            return M.paged_prefill_step(cfg, p, toks, kp, vp, tb, cn, sts, qs,
                                        ql, wb, ws, attn_impl=_impl,
                                        mesh=_tp_mesh, max_segments=_segments)

        self._paged_prefill_fn = jax.jit(rt_prefill_step,
                                         donate_argnums=(9, 10),
                                         **self._decode_jit_kw())
        self._decode_fn = None        # built in serve() once n_slots is known
        self.prefill_shapes = set()   # (rows, chunk bucket, table) run
        self._n_slots = 0
        self._n_tbl = 0               # run-table width (paged mode)
        # event loop
        self.now = 0.0
        self._events: List = []
        self._seq = itertools.count()
        self.engine_busy = False
        self.running: List[_ReqRun] = []   # decode-stage requests, FIFO
        self._force_decode = False         # progress guard after a
                                           # pagination failure (see below)
        self._all: List[_ReqRun] = []
        # CAG startup (docs/ARCHITECTURE.md §12): pre-insert the FULL corpus
        # KV into the disk tier.  Each doc's KV is computed at position 0
        # with no prefix — exactly what the engine computes for a doc served
        # first — so preloaded states are bit-identical to RAG-computed ones
        # and --check-tokens holds unchanged.
        self.preload_stats: Optional[dict] = None
        if self.mode == "cag":
            self.preload_stats = self.controller.preload_corpus(
                range(len(corpus.doc_lengths)), corpus.doc_lengths,
                self._corpus_payload)

    def _corpus_payload(self, doc_id: int, n_tokens: int) -> dict:
        """Host-layout (L, 1, T, KV, hd) {k, v} KV of one corpus doc,
        computed standalone through the dense prefill on pre-shard params."""
        toks = jnp.asarray(self.corpus.doc_tokens[doc_id])[None]
        _, cache = self._prefill_fn(self._preload_params, toks, None, 0)
        return {"k": np.asarray(cache["k"]), "v": np.asarray(cache["v"])}

    # ------------------------------------------------------------------
    # scheduler callbacks
    # ------------------------------------------------------------------

    def _job_viable(self, job: _Job) -> bool:
        return not job.cancelled and job.req.state == WAITING

    def _job_ctx_beta(self, job: _Job) -> Tuple[int, int, int]:
        """(context, beta, promote) token counts for one job: full sequence,
        to-be-computed tokens, and hit-prefix tokens NOT resident in GPU —
        a pinned path on host/disk still consumes GPU pin budget when the
        prefill promotes it (the admission check must see that)."""
        ctx = (sum(int(self.corpus.doc_lengths[d]) for d in job.docs)
               + len(job.req.r.question_tokens))
        if self.reuse == "chunk":
            cached = promote = 0
            for i, node in enumerate(self.tree.match_chunks(job.docs)):
                if node is None:
                    continue
                n_tok = int(self.corpus.doc_lengths[job.docs[i]])
                if node.exact_ctx and \
                        node.src_prefix == tuple(job.docs[:i]):
                    reused = n_tok
                else:
                    r = effective_recompute(self.recompute_tokens, n_tok,
                                            self.store.block_size)
                    reused = n_tok - r     # 0 when r covers the whole chunk
                cached += reused
                if reused and not node.in_gpu:
                    promote += node.n_tokens   # the whole node promotes
            return ctx, max(ctx - cached, 1), promote
        hit = self.tree.match_prefix(job.docs)
        cached = sum(n.n_tokens for n in hit)
        promote = sum(n.n_tokens for n in hit if not n.in_gpu)
        return ctx, max(ctx - cached, 1), promote

    def _job_admissible(self, job: _Job) -> bool:
        ctx, beta, promote = self._job_ctx_beta(job)
        return self.admission.admissible(ctx, beta, promote)

    def _job_lens(self, job: _Job) -> Tuple[int, int]:
        ctx, beta, _ = self._job_ctx_beta(job)
        return ctx - beta, beta

    # ------------------------------------------------------------------
    # event plumbing
    # ------------------------------------------------------------------

    def _push(self, t: float, kind: str, payload) -> None:
        heapq.heappush(self._events, (t, next(self._seq), kind, payload))

    # ------------------------------------------------------------------
    # serve
    # ------------------------------------------------------------------

    def serve(self, requests: Sequence[Request],
              max_new_tokens: int = 4) -> List[RuntimeResult]:
        # the runtime's entry span: its time less its children's is the
        # event loop's own host work
        with self.metrics.span("rt.serve"):
            self.max_new_tokens = max_new_tokens
            self.admission.decode_reserve = max_new_tokens
            max_doc = int(max(self.corpus.doc_lengths))
            max_q = max((len(r.question_tokens) for r in requests), default=8)
            max_ctx = self.top_k * max_doc + max_q + max_new_tokens
            n_slots = self.store.pool.blocks_for_tokens(max_ctx) + 1
            if n_slots > self.store.pool.n_blocks - 1:
                raise ValueError(
                    f"paged pool too small: a worst-case request needs "
                    f"{n_slots - 1} blocks but the pool has "
                    f"{self.store.pool.n_blocks - 1} usable; raise n_blocks "
                    f"or lower top_k/doc length")
            if n_slots != self._n_slots or self._decode_fn is None:
                self._n_slots = n_slots
                # paged mode reads runs, not a contiguous span: every
                # segment of the slot mapping (<= top_k shared docs + 1
                # private) may end mid-block, wasting at most one table
                # entry each.  Chunk mode splits a relocated doc into
                # boundary seg + shared tail, so up to 2 entries per doc go
                # to waste instead of 1.
                per_doc = 2 if self.reuse == "chunk" else 1
                self._n_tbl = n_slots + per_doc * self.top_k + 1
                self._build_decode_fn()
            first = len(self._all)
            for r in requests:
                self._push(max(r.arrival, self.now), "arrival", r)
            while self._events:
                self.now, _, kind, payload = heapq.heappop(self._events)
                getattr(self, f"_on_{kind}")(payload)
            unserved = [st.r.req_id for st in self._all[first:]
                        if st.state != FINISHED]
            if unserved:
                raise RuntimeError(
                    f"requests {unserved} were never served (admission-"
                    f"starved to the end of the event loop — pool or tree "
                    f"budget too small for the workload)")
            out = []
            for st in self._all[first:]:
                out.append(RuntimeResult(
                    req_id=st.r.req_id, tokens=list(st.tokens),
                    ttft=st.tl.ttft, docs=st.final_docs or (),
                    alpha=st.tl.alpha, beta=st.tl.beta,
                    speculative_hit=st.tl.speculative_hit,
                    exact=st.exact, first_logits=st.first_logits))
            out.sort(key=lambda x: x.req_id)
            return out

    # ------------------------------------------------------------------
    # arrivals & staged retrieval (host-CPU lanes, one per request)
    # ------------------------------------------------------------------

    def _on_arrival(self, r: Request) -> None:
        tl = self.metrics.timeline(next(self._seq), self.now)
        tl.req_id = r.req_id
        tl.search_start = self.now
        st = _ReqRun(r=r, tl=tl, spec=SpecState(r.req_id),
                     remaining=self.max_new_tokens)
        self._all.append(st)
        # per-request top_k override (Request.top_k > 0): the front door's
        # SLO admission degrades requests by lowering retrieval depth; both
        # engines honor it so degraded misses stay bit-identical under
        # --check-tokens.  Degradation only ever LOWERS top_k, so the
        # serve()-time max_ctx sizing (self.top_k) stays an upper bound.
        k = min(r.top_k, self.top_k) if r.top_k > 0 else self.top_k
        if self.mode == "cag":
            # ZERO retrieval stages (docs/ARCHITECTURE.md §12): the corpus
            # KV is already resident, so doc resolution is one synchronous
            # deterministic index probe, the retrieval/prefill-overlap
            # machinery degenerates (no stage events, no speculative
            # prefills, search_time identically 0), and the single final
            # job enters the scheduler at arrival.
            with self.metrics.span("rt.retrieval", req_id=r.req_id):
                docs = tuple(int(d) for d in self.index.search(r.query_vec, k))
            st.tl.search_end = self.now
            st.final_docs = docs
            job = _Job(req=st, docs=docs, speculative=False,
                       enqueued=self.now)
            st.jobs.append(job)
            self._submit(job)
            self._prefetch_disk(docs)
            st.tl.queue_enter = self.now
            self._engine_kick()
            return
        # materialize stages, measuring the real scan cost of each stage;
        # the per-request search lane advances by max(measured, analytic)
        t = self.now
        with self.metrics.span("rt.retrieval", req_id=r.req_id):
            it = iter(self.index.staged_search(r.query_vec, k))
            while True:
                t0 = time.perf_counter()
                try:
                    stage = next(it)
                except StopIteration:
                    break
                wall = time.perf_counter() - t0
                t += max(wall, stage.seconds) * self.search_time_scale
                self._push(t, "stage", (st, stage))

    def _on_stage(self, payload) -> None:
        st, stage = payload
        self.metrics.retrieval_stages += 1
        docs = tuple(stage.topk)
        if stage.is_final:
            st.tl.search_end = self.now
            st.final_docs = docs
        action, d = self.spec_ctl.on_stage(
            st.spec, docs, self.sched.pool_size(), is_final=stage.is_final)
        if action in ("terminate_and_launch", "terminate"):
            for job in st.jobs:
                if not job.cancelled and job.docs != docs:
                    job.cancelled = True
        if action in ("launch", "terminate_and_launch"):
            job = _Job(req=st, docs=d, speculative=not stage.is_final,
                       enqueued=self.now)
            st.jobs.append(job)
            self._submit(job)
            self._prefetch_disk(d)
            if not stage.is_final:
                self.metrics.spec_prefills += 1
        if stage.is_final:
            if st.tl.queue_enter < 0:
                st.tl.queue_enter = self.now
            self._maybe_finalize(st)
        self._engine_kick()

    def _prefetch_disk(self, docs: Tuple[int, ...]) -> None:
        """Overlap disk reads with the remaining retrieval stages (the same
        trick speculative prefill plays with compute, §5.3): as soon as a
        stage's top-k is known, stage any disk-only node of the matched
        prefix into host memory.  Disk I/O runs on host CPUs concurrently
        with the accelerator, so — like the staged search itself — it does
        not advance the engine clock; the later engine-critical promote
        becomes a pure host->GPU copy."""
        if self.disk is None:
            return
        if self.reuse == "chunk":
            hit = [n for n in self.tree.match_chunks(docs) if n is not None]
        else:
            hit = self.tree.match_prefix(docs)
        pinned = set(hit)   # staging node k must not re-spill node k-1
        for n in hit:
            if n.in_disk and not n.in_host and not n.in_gpu:
                before = self.tree.stats["fetch_bytes"]
                self.tree.fetch_to_host(n, pinned=pinned)
                moved = self.tree.stats["fetch_bytes"] - before
                if moved:
                    self.metrics.disk_prefetches += 1
                    self.metrics.disk_prefetch_bytes += moved

    def _submit(self, job: _Job) -> None:
        """Queue ``job`` with the scheduler at its current (cached, compute)
        token counts."""
        with self.metrics.span("rt.schedule", req_id=job.req.r.req_id):
            cached, compute = self._job_lens(job)
            self.sched.submit(job, cached, compute)

    def _maybe_finalize(self, st: _ReqRun) -> None:
        """Search done: if a prefill for the final docs already completed,
        the speculation paid off — emit the first token now."""
        if st.tl.first_token >= 0 or st.state != WAITING:
            return
        res = st.results.get(st.final_docs)
        if res is not None:
            self._first_token(st, res, max(self.now, st.tl.search_end))

    # ------------------------------------------------------------------
    # engine loop
    # ------------------------------------------------------------------

    def _engine_kick(self) -> None:
        while not self.engine_busy:
            with self.metrics.span("rt.schedule"):
                self._sweep_stale_partials()
                self.admission.invalidate()   # fresh snapshot per kick
                # after a pagination failed on shared-block pressure, run
                # one decode iteration first so running requests make
                # progress toward releasing their tables (livelock guard)
                force = self._force_decode and bool(self.running)
                self._force_decode = False
                act = None if force else self.sched.next_action(
                    len(self.running), refresh=self._job_lens)
            if force:
                self._start_decode()
                return
            if act.kind == PREFILL:
                self._start_prefill_batch(act.chunks)
                return
            if act.kind == DECODE:
                self._start_decode()
                return
            if act.kind == PREEMPT:
                self._preempt_one()
                continue               # resources freed; re-evaluate
            return                     # IDLE

    def _sweep_stale_partials(self) -> None:
        """Chunk-boundary cancellation: a kick only runs between engine
        iterations, so any in-flight chunked prefill whose job went stale
        (terminated speculation, finished request) is aborted HERE — partial
        KV freed, hit nodes unpinned, remaining chunk tokens never computed
        (Alg. 2 "terminate after the current iteration", at chunk grain)."""
        for job in [j for j in self._partial_jobs
                    if not self._job_viable(j)]:
            self._abort_chunked(job)

    def _preempt_one(self) -> None:
        """Free the youngest running request and send it back to prefill
        (vLLM-style recompute preemption)."""
        victim = max(self.running, key=lambda s: s.tl.first_token)
        self.running.remove(victim)
        self._release_table(victim)
        victim.state = WAITING
        victim.tokens = []
        victim.remaining = self.max_new_tokens
        stale_res = victim.results.pop(victim.final_docs, None)
        if stale_res is not None:
            self._free_paged_kv(stale_res)
        victim.tl.first_token = -1.0    # recompute re-emits the first token
        victim.tl.token_times = []
        victim.tl.preemptions += 1
        self.metrics.preemptions += 1
        job = _Job(req=victim, docs=victim.final_docs, speculative=False,
                   enqueued=self.now)
        victim.jobs.append(job)
        self._submit(job)

    # ---- chunked + batched prefill -------------------------------------

    def _start_prefill_batch(self, chunks) -> None:
        """One engine iteration: execute the next chunk of every job the
        scheduler packed (ragged — chunk sizes differ per job).  Real
        compute is measured and billed as one iteration on the virtual
        clock; commit / first-token decisions happen at the completion
        event, so retrieval stages landing mid-iteration cancel at the
        chunk boundary, never mid-chunk."""
        self.engine_busy = True
        t0 = time.perf_counter()
        outcomes = []                  # (job, finished)
        executed = 0
        rows = []                      # paged mode: packed chunk rows
        for ch in chunks:
            job = ch.item
            if not self._job_viable(job):
                # went stale in this very event-loop instant; nothing ran
                if job.cs is not None:
                    self._abort_chunked(job)
                else:
                    self.sched.abort_prefill(job)
                continue
            rid = job.req.r.req_id
            if job.cs is None:
                with self.metrics.span("rt.plan", req_id=rid):
                    self._begin_chunked(job)
            if self.attn == "paged":
                with self.metrics.span("rt.prefill.pack", req_id=rid):
                    row = self._prep_paged_chunk(job)
                if row is None:
                    # OutOfBlocks: abort and requeue in place
                    self._abort_chunked(job, requeue=True)
                    continue
                rows.append(row)
                executed += row[-1]
            else:
                n = self._run_chunk(job)
                if n < 0:
                    continue           # paged partial hit OutOfBlocks: job
                                       # was aborted + requeued in-place
                executed += n
            outcomes.append((job, not job.cs.pieces))
        if rows:
            # ONE ragged batched call for every packed chunk — the batch is
            # row-independent (padding rows fully masked), so tokens are
            # identical whatever shares the iteration
            self._run_paged_rows(rows)
        dt = time.perf_counter() - t0
        if outcomes:
            # all-stale batches (every chunk went stale in this event-loop
            # instant) executed nothing: don't record a phantom iteration
            self.metrics.record_iteration("prefill", 1)
            self.metrics.record_prefill_batch(len(outcomes), executed)
        self._push(self.now + dt, "prefill_batch_done", outcomes)

    def _begin_chunked(self, job: _Job) -> None:
        """First chunk: plan the request, promote the hit prefix, and build
        the execution cursor.  Piece sizes come from the shared splitter so
        runtime, simulator and sequential engine chunk identically."""
        st = job.req
        job.started = self.now
        st.start_by_docs.setdefault(job.docs, self.now)
        doc_tokens = [int(self.corpus.doc_lengths[d]) for d in job.docs]
        if self.reuse == "chunk":
            plan = self.controller.plan_chunks(
                job.docs, doc_tokens, len(st.r.question_tokens),
                recompute_tokens=self.recompute_tokens,
                block_size=self.store.block_size)
        else:
            plan = self.controller.plan(job.docs, doc_tokens,
                                        len(st.r.question_tokens))
        self.controller.promote(plan)   # host->device pull
        if plan.chunks is not None:
            self._begin_chunk_layout(job, plan)
            return
        segs = [np.asarray(self.corpus.doc_tokens[job.docs[i]])
                for i in range(len(plan.hit_nodes), len(job.docs))]
        bounds, start = [], plan.alpha
        for s in segs:
            bounds.append((start, len(s)))
            start += len(s)
        segs.append(np.asarray(st.r.question_tokens))
        pieces = prefill_piece_sizes([len(s) for s in segs],
                                     self.sched.config.prefill_chunk)
        if not pieces:
            raise ValueError(
                f"request {st.r.req_id}: nothing to prefill (empty question "
                f"and fully cached documents) — no logits can be produced")
        if self.attn == "paged":
            # no dense gather of the hit prefix: snapshot its page runs and
            # refcount-share them (the nodes are also pinned until commit,
            # so the pages can be read in place for the whole prefill)
            hit_runs, layout, plen = [], [], 0
            for node in plan.hit_nodes:
                seg = node.payload_gpu
                self.store.share(seg)
                layout.append(("run", len(hit_runs), plen))
                hit_runs.append((list(seg.blocks), seg.n_tokens))
                plen += seg.n_tokens
            seg_abs, pos = [], plen
            for i, s in enumerate(segs):
                seg_abs.append(pos)
                layout.append(("seg", i, pos))
                pos += len(s)
            job.cs = _ChunkState(plan=plan, segs=segs, doc_bounds=bounds,
                                 pieces=pieces, total=sum(pieces), plen=plen)
            job.cs.hit_runs = hit_runs
            job.cs.layout = layout
            job.cs.seg_abs = seg_abs
            job.cs.pg_segs = [PagedSegment(self.store, [], 0) for _ in segs]
        else:
            prefix_hit, plen = self._assemble_prefix(plan.hit_nodes)
            job.cs = _ChunkState(plan=plan, segs=segs, doc_bounds=bounds,
                                 pieces=pieces, total=sum(pieces),
                                 plen=plen, prefix_hit=prefix_hit)
        self._partial_jobs.append(job)

    def _begin_chunk_layout(self, job: _Job, plan) -> None:
        """Chunk-cache twin of the prefix begin path (--reuse chunk): the
        request's sequence is an ORDERED INTERLEAVING of shared cached runs
        and to-compute segments.  Per doc position (ChunkItem):

          * exact — share the node's pages whole, like a prefix hit;
          * reloc — an owned boundary segment of ``recompute`` tokens (the
            doc head, recomputed at its NEW absolute position over the true
            preceding context) followed by the node's page-aligned TAIL
            pages, refcount-shared in place (stale RoPE — approximate);
          * miss — an owned segment computing the whole doc.

        The question is the final owned segment.  Compute segments sit at
        scattered absolute offsets, so each records its start (seg_abs)."""
        st = job.req
        bs = self.store.block_size
        segs: List[np.ndarray] = []
        seg_abs: List[int] = []
        layout: List[Tuple[str, int, int]] = []
        hit_runs: List[Tuple[List[int], int]] = []
        miss_segs: List[int] = []
        pos = 0
        for it in plan.chunks:
            if it.kind == "exact":
                seg = it.node.payload_gpu
                self.store.share(seg)
                layout.append(("run", len(hit_runs), pos))
                hit_runs.append((list(seg.blocks), seg.n_tokens))
                self.metrics.exact_chunk_hits += 1
            elif it.kind == "reloc":
                toks = np.asarray(self.corpus.doc_tokens[it.doc_id])
                segs.append(toks[:it.recompute])
                seg_abs.append(pos)
                layout.append(("seg", len(segs) - 1, pos))
                # recompute is page-aligned (effective_recompute), so the
                # reused tail starts at slot 0 of a block — the run-table /
                # decode-run contract every shared run must satisfy
                tail = list(it.node.payload_gpu.blocks[it.recompute // bs:])
                self.store.share_blocks(tail)
                layout.append(("run", len(hit_runs), pos + it.recompute))
                hit_runs.append((tail, it.n_tokens - it.recompute))
                self.metrics.reloc_chunk_hits += 1
                self.metrics.reloc_recompute_tokens += it.recompute
            else:
                segs.append(np.asarray(self.corpus.doc_tokens[it.doc_id]))
                seg_abs.append(pos)
                layout.append(("seg", len(segs) - 1, pos))
                miss_segs.append(len(segs) - 1)
            pos += it.n_tokens
        segs.append(np.asarray(st.r.question_tokens))
        seg_abs.append(pos)
        layout.append(("seg", len(segs) - 1, pos))
        seg_lens = [len(s) for s in segs]
        chunk = self.sched.config.prefill_chunk
        if chunk > 0:
            pieces = prefill_piece_sizes(seg_lens, chunk)
        else:
            # one piece per segment even unchunked: a piece's query rows are
            # CONSECUTIVE absolute positions (kernel q_start contract), and
            # here compute segments are separated by shared runs
            pieces = [int(n) for n in seg_lens if n > 0]
        if not pieces:
            raise ValueError(
                f"request {st.r.req_id}: nothing to prefill (empty question "
                f"and fully cached documents) — no logits can be produced")
        job.cs = _ChunkState(plan=plan, segs=segs, doc_bounds=[],
                             pieces=pieces, total=sum(pieces),
                             plen=plan.alpha)
        job.cs.hit_runs = hit_runs
        job.cs.layout = layout
        job.cs.seg_abs = seg_abs
        job.cs.miss_segs = miss_segs
        job.cs.pg_segs = [PagedSegment(self.store, [], 0) for _ in segs]
        self._partial_jobs.append(job)

    def _chunk_prefix(self, cs: _ChunkState) -> Tuple[Optional[dict], int]:
        """KV prefix for the next piece: the dense cached-prefix alone
        (first iteration), plus the partial KV gathered back out of the
        paged store on continuation iterations."""
        if cs.partial_seg is None:
            return cs.prefix_hit, cs.plen
        k, v = self.store.gather(cs.partial_seg)
        if cs.prefix_hit is None:
            return {"k": k, "v": v}, cs.plen
        return {"k": jnp.concatenate([cs.prefix_hit["k"], k], axis=2),
                "v": jnp.concatenate([cs.prefix_hit["v"], v], axis=2)}, cs.plen

    def _run_chunk(self, job: _Job) -> int:
        """Execute the next piece of ``job``'s prefill.  A piece never spans
        a segment boundary when chunking is enabled; with chunking disabled
        the single piece walks every segment (legacy one-iteration prefill).
        Returns tokens computed, or -1 if paging the partial KV failed and
        the job was aborted + requeued."""
        cs = job.cs
        n = cs.pieces.pop(0)
        multi_iter = bool(cs.pieces) or cs.partial_seg is not None
        prefix, plen = self._chunk_prefix(cs)
        plen0, left = plen, n
        logits = cache = None
        while left > 0:
            seg = cs.segs[cs.seg_idx]
            take = min(left, len(seg) - cs.seg_off)
            toks = jnp.asarray(seg[cs.seg_off:cs.seg_off + take])[None]
            with self._trace_ctx():
                logits, cache = self._prefill_fn(self.params, toks,
                                                 prefix, plen)
            prefix, plen = cache, plen + take
            cs.seg_off += take
            left -= take
            while cs.seg_idx < len(cs.segs) and \
                    cs.seg_off >= len(cs.segs[cs.seg_idx]):
                cs.seg_idx += 1
                cs.seg_off = 0
        jax.block_until_ready(logits)
        cs.plen = plen
        cs.logits = logits
        if not cs.pieces or not multi_iter:
            # final piece (or legacy single-iteration prefill): the carried
            # cache is the full sequence — keep it dense for commit/paginate
            cs.cache = cache
        else:
            # page the newly computed KV into the store so the only live
            # copy of the partial prefill is paged (cancellation frees it)
            k = cache["k"][:, :, plen0:plen]
            v = cache["v"][:, :, plen0:plen]
            nb = self.store.pool.blocks_for_tokens(plen - plen0)
            if not self._reclaim_blocks(nb):
                self._abort_chunked(job, requeue=True)
                return -1
            try:
                if cs.partial_seg is None:
                    cs.partial_seg = self.store.put(k, v)
                else:
                    self.store.append(cs.partial_seg, k, v)
            except OutOfBlocks:
                self._abort_chunked(job, requeue=True)
                return -1
        return n

    # ---- paged ragged prefill (no dense KV at any point) ---------------

    def _prep_paged_chunk(self, job: _Job):
        """Allocate pages for the next piece of ``job`` and build its row of
        the ragged batch: chunk tokens, their (block, slot) write coords,
        and the run table covering cached prefix + everything computed so
        far INCLUDING this chunk (causal masking over absolute positions
        keeps row i from seeing slots past it).  Returns None if the pool
        cannot hold the piece; the caller then aborts and requeues the job."""
        cs = job.cs
        n = cs.pieces.pop(0)
        while cs.seg_idx < len(cs.segs) and \
                cs.seg_off >= len(cs.segs[cs.seg_idx]):
            cs.seg_idx += 1          # skip empty segments before anchoring
            cs.seg_off = 0
        # the piece's rows are consecutive absolute positions anchored at
        # the cursor's segment (chunk mode scatters compute segments between
        # shared runs, so a running prefix length is NOT the position)
        q_start = cs.seg_abs[cs.seg_idx] + cs.seg_off
        toks = np.zeros(n, np.int32)
        wblk = np.full(n, self._scratch_block, np.int32)
        wslot = np.zeros(n, np.int32)
        off, left = 0, n
        while left > 0:
            seg = cs.segs[cs.seg_idx]
            pg = cs.pg_segs[cs.seg_idx]
            take = min(left, len(seg) - cs.seg_off)
            need = (self.store.pool.blocks_for_tokens(pg.n_tokens + take)
                    - len(pg.blocks))
            if need > 0 and not self._reclaim_blocks(need):
                return None
            try:
                blk, slot = self.store.extend_alloc(pg, take)
            except OutOfBlocks:
                return None
            toks[off:off + take] = seg[cs.seg_off:cs.seg_off + take]
            wblk[off:off + take] = blk
            wslot[off:off + take] = slot
            cs.seg_off += take
            off += take
            left -= take
            while cs.seg_idx < len(cs.segs) and \
                    cs.seg_off >= len(cs.segs[cs.seg_idx]):
                cs.seg_idx += 1
                cs.seg_off = 0
        cs.plen += n
        tables, counts, starts = self._paged_chunk_row(cs)
        return (job, toks, wblk, wslot, q_start, tables, counts, starts, n)

    def _paged_chunk_row(self, cs: _ChunkState):
        """Run-table row over the ordered sequence layout, same contract as
        decode (kernels/paged_attention.py): every entry starts at slot 0 of
        a fresh block, so runs are exactly the per-block spans.  Each entry
        carries its TRUE absolute start — with chunk-mode interleaving, a
        shared run can sit PAST a partially filled compute segment, and
        causal masking over absolute positions (not table order) is what
        keeps those later keys invisible to this piece's rows."""
        T = self._n_tbl
        bs = self.store.block_size
        tables = np.full(T, self._scratch_block, np.int32)
        counts = np.zeros(T, np.int32)
        starts = np.zeros(T, np.int32)
        j = 0
        for kind, idx, abs0 in cs.layout:
            if kind == "run":
                blocks, ntok = cs.hit_runs[idx]
            else:
                pg = cs.pg_segs[idx]
                blocks, ntok = pg.blocks, pg.n_tokens
            for bi, blk in enumerate(blocks):
                c = min(bs, ntok - bi * bs)
                if c <= 0:
                    break
                tables[j] = blk
                counts[j] = c
                starts[j] = abs0 + bi * bs
                j += 1
        assert j <= T, (j, T)
        return tables, counts, starts

    def _run_paged_rows(self, rows) -> None:
        """Execute one ragged batched paged-prefill iteration.  Rows pad to
        ``max_prefill_bs`` and chunk lengths to a power-of-two bucket (>= 8)
        to bound jit retraces; padding rows/tokens write into the scratch
        block and are fully masked (q_len), so every real row's output —
        and therefore every token — is independent of what shares the
        batch."""
        it = len(self.metrics.iterations)
        with self.metrics.span("rt.prefill.pack", it=it):
            B = max(self.sched.config.max_prefill_bs, len(rows))
            Sq = max(8, 1 << (max(r[-1] for r in rows) - 1).bit_length())
            T = self._n_tbl
            toks = np.zeros((B, Sq), np.int32)
            wblk = np.full((B, Sq), self._scratch_block, np.int32)
            wslot = np.zeros((B, Sq), np.int32)
            tables = np.full((B, T), self._scratch_block, np.int32)
            counts = np.zeros((B, T), np.int32)
            starts = np.zeros((B, T), np.int32)
            q_start = np.zeros((B,), np.int32)
            q_len = np.zeros((B,), np.int32)
            self.prefill_shapes.add((B, Sq, T))
            n_runs = max_write_runs(Sq, self.store.block_size,
                                    self._row_segments)
            for i, (job, t, wb, ws, qs, tb, cn, st_, n) in enumerate(rows):
                # the step writes n_runs pages a row and would drop the rest
                runs = int(run_starts(wb, ws, True).sum())
                assert runs <= n_runs, (
                    f"prefill row of {n} tokens writes {runs} pages; the "
                    f"{Sq}-row step writes at most {n_runs}")
                toks[i, :n] = t
                wblk[i, :n] = wb
                wslot[i, :n] = ws
                tables[i] = tb
                counts[i] = cn
                starts[i] = st_
                q_start[i] = qs
                q_len[i] = n
        with self.metrics.span("rt.prefill.launch", it=it), self._trace_ctx():
            logits, self.store.k, self.store.v = self._paged_prefill_fn(
                self.params, jnp.asarray(toks), jnp.asarray(tables),
                jnp.asarray(counts), jnp.asarray(starts),
                jnp.asarray(q_start), jnp.asarray(q_len),
                jnp.asarray(wblk), jnp.asarray(wslot),
                self.store.k, self.store.v)
        with self.metrics.span("rt.prefill.wait", it=it):
            logits = jax.block_until_ready(logits)
        for i, row in enumerate(rows):
            row[0].cs.logits = logits[i:i + 1]       # (1, 1, V)

    def _on_prefill_batch_done(self, payload) -> None:
        self.engine_busy = False
        for job, finished in payload:
            st = job.req
            cs = job.cs
            if cs is None:
                continue               # aborted mid-iteration (requeue path)
            stale = job.cancelled or st.state != WAITING
            if not finished:
                if stale:
                    self._abort_chunked(job)
                else:
                    self.sched.note_chunk_done(job, cs.pieces)
                continue
            # prefill complete
            self.sched.note_chunk_done(job, [])
            pg_segs, hit_runs = cs.pg_segs, cs.hit_runs
            if not stale:
                # ownership of the paged state moves to the result BEFORE
                # _drop_chunk_state (which frees whatever is still attached)
                cs.pg_segs, cs.hit_runs = [], []
            self._drop_chunk_state(job)
            if stale:
                for n in cs.plan.hit_nodes:   # unpin without committing
                    n.pinned = False
                self.metrics.wasted_prefills += 1
                continue
            rid = st.r.req_id
            with self.metrics.span("rt.first_token", req_id=rid):
                first_token = int(jnp.argmax(cs.logits[0, -1]))
                first_logits = np.asarray(cs.logits[0, -1])
            res = _PrefillResult(
                docs=job.docs, cache=cs.cache,
                first_token=first_token,
                total_len=cs.plen,
                alpha=cs.plan.alpha, beta=cs.plan.beta,
                hit_docs=cs.plan.hit_docs,
                hit_tier_tokens=cs.plan.hit_tier_tokens,
                speculative=job.speculative, started=job.started,
                hit_runs=hit_runs, pg_segs=pg_segs,
                layout=list(cs.layout), exact=cs.plan.exact,
                first_logits=first_logits)
            with self.metrics.span("rt.commit", req_id=rid):
                if cs.plan.chunks is not None:
                    self._commit_paged_chunks(
                        cs.plan, [pg_segs[i] for i in cs.miss_segs])
                elif self.attn == "paged":
                    self._commit_paged(cs.plan, pg_segs[:len(cs.doc_bounds)])
                else:
                    payloads = [(start, length, cs.cache)
                                for start, length in cs.doc_bounds]
                    self._commit_payloads(cs.plan, payloads)
            st.results[job.docs] = res
            if st.final_docs is not None and job.docs == st.final_docs:
                self._first_token(st, res, max(self.now, st.tl.search_end))
        self._engine_kick()

    def _drop_chunk_state(self, job: _Job) -> None:
        cs = job.cs
        if cs is not None:
            if cs.partial_seg is not None:
                self.store.free(cs.partial_seg)
                cs.partial_seg = None
            self._free_paged_kv(cs)
        job.cs = None
        if job in self._partial_jobs:
            self._partial_jobs.remove(job)

    def _free_paged_kv(self, holder) -> None:
        """Drop a _ChunkState's or _PrefillResult's paged KV references:
        release the shared hit runs (one incref each) and free the owned
        segments.  No-op once ownership has transferred (lists emptied)."""
        for blocks, _ in holder.hit_runs:
            self.store.release(blocks)
        holder.hit_runs = []
        for pg in holder.pg_segs:
            if pg.blocks:
                self.store.free(pg)
        holder.pg_segs = []

    def _abort_chunked(self, job: _Job, requeue: bool = False) -> None:
        """Mid-prefill cancellation: free the partial KV, unpin the hit
        prefix, and account the chunk tokens that were never computed."""
        cs = job.cs
        saved = sum(cs.pieces) if cs is not None else 0
        if cs is not None:
            for n in cs.plan.hit_nodes:
                n.pinned = False
        self._drop_chunk_state(job)
        self.sched.abort_prefill(job)
        if not requeue:
            # a requeued job recomputes everything later — only genuine
            # cancellations (stale speculation / finished request) save work
            self.metrics.record_chunk_cancel(saved)
        if requeue:
            # paged-pool pressure, not staleness: recompute later — force a
            # decode iteration first so running requests free blocks
            job.cancelled = True
            self._force_decode = True
            redo = _Job(req=job.req, docs=job.docs,
                        speculative=job.speculative, enqueued=self.now)
            job.req.jobs.append(redo)
            self._submit(redo)

    def _commit_payloads(self, plan, payloads) -> None:
        """Page the new per-doc KV segments into the store and insert them
        into the knowledge tree; stop caching at the first doc the pool
        cannot hold (graceful §8-style truncation)."""
        segs = []
        for (start, length, cache) in payloads:
            k = cache["k"][:, :, start:start + length]
            v = cache["v"][:, :, start:start + length]
            if not self._reclaim_blocks(self.store.pool.blocks_for_tokens(length)):
                break
            try:
                segs.append(self.store.put(k, v))
            except OutOfBlocks:
                break
        inserted = self.controller.commit(
            plan, segs, max_docs=len(plan.hit_nodes) + len(segs))
        # free every segment the tree did not take: the tail when insert
        # stopped early, and duplicates when a concurrent chunked prefill
        # committed the same doc path first (the tree keeps the incumbent)
        kept = {id(n.payload_gpu) for n in inserted}
        for seg in segs:
            if id(seg) not in kept:
                self.store.free(seg)

    def _commit_paged(self, plan, doc_segs) -> None:
        """Paged twin of ``_commit_payloads``: the per-doc KV already lives
        in pool blocks (the prefill step wrote it in place), so
        committing is pure refcounting — share each segment to mint the
        tree's independent reference, then drop it again for every segment
        the tree declined (duplicate doc path or insert stopped early)."""
        for seg in doc_segs:
            self.store.share(seg)
        inserted = self.controller.commit(
            plan, list(doc_segs), max_docs=len(plan.hit_nodes) + len(doc_segs))
        kept = {id(n.payload_gpu) for n in inserted}
        for seg in doc_segs:
            if id(seg) not in kept:
                self.store.release(seg.blocks)

    def _commit_paged_chunks(self, plan, doc_segs) -> None:
        """Chunk-mode commit (--reuse chunk): only MISS docs enter the flat
        chunk cache — the canonical entry for an exact/reloc hit is the node
        already resident, and relocated boundary segments stay request-
        private (their KV is position-specific).  Pure refcounting like
        ``_commit_paged``; declined segments return their extra ref."""
        for seg in doc_segs:
            self.store.share(seg)
        inserted = self.controller.commit_chunks(plan, list(doc_segs))
        kept = {id(n.payload_gpu) for n in inserted}
        for seg in doc_segs:
            if id(seg) not in kept:
                self.store.release(seg.blocks)

    def _reclaim_blocks(self, needed: int) -> bool:
        """Evict unpinned tree leaves (PGDSF order, shared Alg. 1 loop)
        until the pool has ``needed`` free blocks."""
        try:
            self.tree.evict_gpu_until(
                lambda: self.store.pool.free_blocks >= needed)
            return True
        except EvictionError:
            return False

    def _assemble_prefix(self, nodes) -> Tuple[Optional[dict], int]:
        if not nodes:
            return None, 0
        ks, vs = [], []
        for n in nodes:
            k, v = self.store.gather(n.payload_gpu)
            ks.append(k)
            vs.append(v)
        k = jnp.concatenate(ks, axis=2)
        return {"k": k, "v": jnp.concatenate(vs, axis=2)}, int(k.shape[2])

    # ---- first token & decode admission --------------------------------

    def _first_token(self, st: _ReqRun, res: _PrefillResult, t: float) -> None:
        tl = st.tl
        tl.first_token = t
        tl.prefill_end = t
        tl.alpha, tl.beta = res.alpha, res.beta
        tl.hit_docs = res.hit_docs
        (tl.hit_tokens_gpu, tl.hit_tokens_host,
         tl.hit_tokens_disk) = res.hit_tier_tokens
        tl.n_docs = len(res.docs)
        tl.docs = res.docs
        tl.speculative_hit = res.speculative or res.started < tl.search_end
        start = st.start_by_docs.get(res.docs)
        if start is not None:
            tl.final_prefill_start = start
        st.tokens = [res.first_token]
        st.exact = res.exact
        st.first_logits = res.first_logits
        st.remaining = self.max_new_tokens - 1
        for job in st.jobs:            # any other pending work is now moot
            if not job.cancelled and job.docs != res.docs:
                job.cancelled = True
        if st.remaining <= 0:
            self._finish(st, t)
            return
        if not self._paginate(st, res):
            # pool pressure raced us between admission and join: retry later
            self._requeue_after_pagination_failure(st)
            return
        st.state = RUNNING
        st.last_tok = res.first_token
        self.running.append(st)

    def _requeue_after_pagination_failure(self, st: _ReqRun) -> None:
        res = st.results.pop(st.final_docs, None)
        if res is not None:
            self._free_paged_kv(res)
        st.tokens = []
        st.tl.first_token = -1.0       # not actually servable yet
        self._force_decode = True      # guarantee decode progress before
                                       # this job can be re-popped
        job = _Job(req=st, docs=st.final_docs, speculative=False,
                   enqueued=self.now)
        st.jobs.append(job)
        self._submit(job)

    def _paginate(self, st: _ReqRun, res: _PrefillResult) -> bool:
        """Build the request's decode slot mapping: refcount-share EVERY
        complete GPU-resident knowledge-tree prefix node — block-aligned or
        not; the token-level (block, slot) mapping absorbs unaligned doc
        tails, so a 20-token doc in 16-token blocks shares both its blocks
        and the next doc's tokens simply start in a fresh block — and copy
        the rest (uncached docs + question) into private blocks with decode
        reserve."""
        if self.attn == "paged" and (res.pg_segs or res.hit_runs):
            return self._paginate_paged(st, res)
        bs = self.store.block_size
        pos_blk: List[int] = []
        pos_slot: List[int] = []
        shared: List[int] = []
        offset = 0
        for node in self.tree.match_prefix(res.docs):
            seg = node.payload_gpu
            if (seg is None or not node.in_gpu
                    or seg.n_tokens != node.n_tokens):
                break
            self.store.share(seg)
            for i in range(seg.n_tokens):
                pos_blk.append(seg.blocks[i // bs])
                pos_slot.append(i % bs)
            shared.extend(seg.blocks)
            offset += seg.n_tokens
        rest = res.total_len - offset
        k = res.cache["k"][:, :, offset:res.total_len]
        v = res.cache["v"][:, :, offset:res.total_len]
        need = self.store.pool.blocks_for_tokens(rest + st.remaining)
        if not self._reclaim_blocks(need):
            self.store.release(shared)
            return False
        try:
            priv = self.store.put(k, v, reserve_tokens=st.remaining)
        except OutOfBlocks:
            self.store.release(shared)
            return False
        for i in range(rest + st.remaining):
            pos_blk.append(priv.blocks[i // bs])
            pos_slot.append(i % bs)
        st.pos_blk, st.pos_slot = pos_blk, pos_slot
        st.owned_blocks = shared + priv.blocks
        st.length = res.total_len
        self.metrics.blocks_shared += len(shared)
        self.metrics.blocks_copied += len(priv.blocks)
        return True

    def _paginate_paged(self, st: _ReqRun, res: _PrefillResult) -> bool:
        """Paged twin of ``_paginate``: every token already sits in a pool
        block — the cached prefix in the shared hit runs, the rest in the
        result's owned segments — so building the decode slot mapping is
        pure bookkeeping plus one allocation-only extension of the question
        segment for the decode reserve.  On success the result's references
        transfer wholesale to ``st.owned_blocks`` (lists emptied); on
        failure ``res`` is left untouched for the requeue path to free."""
        bs = self.store.block_size
        qseg = res.pg_segs[-1]
        need = (self.store.pool.blocks_for_tokens(qseg.n_tokens + st.remaining)
                - len(qseg.blocks))
        if need > 0 and not self._reclaim_blocks(need):
            return False
        try:
            self.store.extend_alloc(qseg, st.remaining)
        except OutOfBlocks:
            return False
        pos_blk: List[int] = []
        pos_slot: List[int] = []
        shared: List[int] = []
        owned: List[int] = []
        # walk the ordered layout — prefix mode is runs-then-segs, chunk
        # mode interleaves them; either way entries appear in absolute
        # position order, so appending yields the position->slot mapping
        for kind, idx, _ in res.layout:
            if kind == "run":
                blocks, n_tokens = res.hit_runs[idx]
                for i in range(n_tokens):
                    pos_blk.append(blocks[i // bs])
                    pos_slot.append(i % bs)
                shared.extend(blocks)
            else:
                pg = res.pg_segs[idx]
                for i in range(pg.n_tokens):
                    pos_blk.append(pg.blocks[i // bs])
                    pos_slot.append(i % bs)
                owned.extend(pg.blocks)
        st.pos_blk, st.pos_slot = pos_blk, pos_slot
        st.owned_blocks = shared + owned
        st.length = res.total_len
        self.metrics.blocks_shared += len(shared)
        self.metrics.blocks_copied += len(owned)
        res.pg_segs, res.hit_runs = [], []    # ownership moved to the table
        return True

    def _release_table(self, st: _ReqRun) -> None:
        if st.owned_blocks:
            self.store.release(st.owned_blocks)
        st.pos_blk, st.pos_slot, st.owned_blocks = [], [], []
        st.length = 0

    # ---- batched decode ------------------------------------------------

    def _build_decode_fn(self) -> None:
        if self.attn == "paged":
            self._build_paged_decode_fn()
        else:
            self._build_dense_decode_fn()

    def _trace_ctx(self):
        """Context for every call that may TRACE model code: under TP,
        layers.tp_deterministic makes row-parallel contractions gather
        their activations instead of lowering to a partial-sum all-reduce
        (the one mesh-size-dependent float reduction).  jit caches the
        traced computation, so wrapping the calls — not just the first —
        is belt-and-braces for new shape signatures."""
        return (L.tp_deterministic(self._mesh) if self._mesh is not None
                else contextlib.nullcontext())

    def _decode_jit_kw(self) -> dict:
        """Under TP, pin the decode step's output shardings: tokens come
        back replicated (the host event loop reads them), and the pool
        planes keep the pool's own KV-head sharding so the (8, 9) donation
        reuses the sharded buffers in place instead of silently copying."""
        if self._kv_sharding is None:
            return {}
        rep = NamedSharding(self._mesh, PartitionSpec())
        return {"out_shardings": (rep, self._kv_sharding,
                                  self._kv_sharding)}

    def _build_paged_decode_fn(self) -> None:
        """Decode attention straight from the pool's page arrays: per-layer
        paged attention through run tables (kernels/ops.py dispatch — Pallas
        on TPU, per-page jnp online softmax on CPU), new-token KV appended
        in place at its (block, slot).  Nothing here scales with the dense
        max-context span S — the steady-state iteration touches live pages
        only."""
        cfg = self.cfg
        impl = self.attn_impl
        tp_mesh = self._mesh

        def rt_decode_step(params, toks, tables, counts, starts, pos,
                           write_blk, write_slot, k_pages, v_pages):
            logits, k_pages, v_pages = M.paged_decode_step(
                cfg, params, toks, k_pages, v_pages, tables, counts, starts,
                write_blk, write_slot, pos, attn_impl=impl, mesh=tp_mesh)
            return jnp.argmax(logits[:, -1], axis=-1), k_pages, v_pages

        self._decode_fn = jax.jit(rt_decode_step, donate_argnums=(8, 9),
                                  **self._decode_jit_kw())
        # warm up the single decode shape (dummy rows decode token 0 into
        # the scratch block, exactly like a padding row in _start_decode)
        args = self._paged_decode_args([])
        with self._trace_ctx():
            _, self.store.k, self.store.v = self._decode_fn(
                self.params, *args, self.store.k, self.store.v)
        jax.block_until_ready(self.store.k)

    def compiled_steps(self) -> Dict[str, str]:
        """HLO text of the paged programs this runtime has run: ``decode``
        and one ``prefill BxSq`` per ragged-batch bucket — what a caller
        inspects to see what executes (e.g. that the Pallas kernels are
        in it).  Recompiling hits JAX's compile caches."""
        if self.attn != "paged":
            return {}
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        out = {}
        with self._trace_ctx():
            if self._decode_fn is not None:
                out["decode"] = self._decode_fn.lower(
                    self.params, *self._paged_decode_args([]),
                    self.store.k, self.store.v).compile().as_text()
            for B, Sq, T in sorted(self.prefill_shapes):
                out[f"prefill {B}x{Sq}"] = self._paged_prefill_fn.lower(
                    self.params, i32(B, Sq), i32(B, T), i32(B, T),
                    i32(B, T), i32(B), i32(B), i32(B, Sq), i32(B, Sq),
                    self.store.k, self.store.v).compile().as_text()
        return out

    def _paged_decode_args(self, batch):
        """Pack the run tables for one paged decode iteration.  Contract
        (kernels/paged_attention.py): the slot mapping is a list of runs,
        each starting at slot 0 of its block — run boundaries are exactly
        the positions with pos_slot == 0."""
        B = self.sched.config.max_batch
        T = self._n_tbl
        toks = np.zeros((B, 1), np.int32)
        tables = np.full((B, T), self._scratch_block, np.int32)
        counts = np.zeros((B, T), np.int32)
        starts = np.zeros((B, T), np.int32)
        pos = np.ones((B,), np.int32)
        write_blk = np.full((B,), self._scratch_block, np.int32)
        write_slot = np.zeros((B,), np.int32)
        counts[:, 0] = 1               # dummy rows attend their scratch write
        for i, st in enumerate(batch):
            n = st.length + 1          # incl. the token decoded this step
            blk = np.asarray(st.pos_blk[:n], np.int32)
            slot = np.asarray(st.pos_slot[:n], np.int32)
            run = np.flatnonzero(slot == 0)
            assert len(run) <= T, (len(run), T)
            counts[i] = 0
            tables[i, :len(run)] = blk[run]
            counts[i, :len(run)] = np.diff(np.append(run, n))
            starts[i, :len(run)] = run
            pos[i] = n
            toks[i, 0] = st.last_tok
            write_blk[i] = st.pos_blk[st.length]
            write_slot[i] = st.pos_slot[st.length]
        return (jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(counts),
                jnp.asarray(starts), jnp.asarray(pos),
                jnp.asarray(write_blk), jnp.asarray(write_slot))

    def _build_dense_decode_fn(self) -> None:
        cfg = self.cfg
        B = self.sched.config.max_batch
        S = self._n_slots * self.store.block_size   # max token positions

        def rt_dense_decode_step(params, toks, blk_map, slot_map, lengths,
                                 k_pages, v_pages):
            # token-level slot mapping (vLLM-style slot_mapping): position p
            # of request b lives at (blk_map[b, p], slot_map[b, p]), so the
            # gathered dense sequence is hole-free even when shared tree
            # segments end mid-block — sharing needs no block alignment
            k = gather_slots(k_pages, blk_map, slot_map)  # (L, B, S, KV, hd)
            v = gather_slots(v_pages, blk_map, slot_map)
            logits, new = M.decode_step(cfg, params, toks,
                                        {"k": k, "v": v}, lengths + 1)
            bidx = jnp.arange(B)
            newk = new["k"][:, bidx, lengths]          # (L, B, KV, hd)
            newv = new["v"][:, bidx, lengths]
            blk = blk_map[bidx, lengths]
            slot = slot_map[bidx, lengths]
            k_pages = scatter_slots(k_pages, blk, slot, newk)
            v_pages = scatter_slots(v_pages, blk, slot, newv)
            return jnp.argmax(logits[:, -1], axis=-1), k_pages, v_pages

        self._decode_fn = jax.jit(rt_dense_decode_step, donate_argnums=(5, 6),
                                  **self._decode_jit_kw())
        # warm up the single decode shape so its compile never lands on the
        # serving clock (all dummy rows write into the scratch block)
        toks = jnp.zeros((B, 1), jnp.int32)
        blk_map = jnp.full((B, S), self._scratch_block, jnp.int32)
        slot_map = jnp.zeros((B, S), jnp.int32)
        lengths = jnp.zeros((B,), jnp.int32)
        with self._trace_ctx():
            _, self.store.k, self.store.v = self._decode_fn(
                self.params, toks, blk_map, slot_map, lengths,
                self.store.k, self.store.v)
        jax.block_until_ready(self.store.k)

    def _start_decode(self) -> None:
        batch = self.running[:self.sched.config.max_batch]
        self.engine_busy = True
        self.metrics.record_iteration("decode", len(batch))
        t0 = time.perf_counter()
        it = len(self.metrics.iterations)
        with self.metrics.span("rt.decode.pack", it=it):
            args = (self._paged_decode_args(batch) if self.attn == "paged"
                    else self._dense_decode_args(batch))
        with self.metrics.span("rt.decode.launch", it=it), self._trace_ctx():
            next_toks, self.store.k, self.store.v = self._decode_fn(
                self.params, *args, self.store.k, self.store.v)
        with self.metrics.span("rt.decode.wait", it=it):
            next_toks = np.asarray(jax.block_until_ready(next_toks))
        dt = time.perf_counter() - t0
        self._push(self.now + dt, "decode_done",
                   (batch, [int(t) for t in next_toks[:len(batch)]]))

    def _dense_decode_args(self, batch):
        B = self.sched.config.max_batch
        S = self._n_slots * self.store.block_size
        toks = np.zeros((B, 1), np.int32)
        blk_map = np.full((B, S), self._scratch_block, np.int32)
        slot_map = np.zeros((B, S), np.int32)
        lengths = np.zeros((B,), np.int32)
        for i, st in enumerate(batch):
            toks[i, 0] = st.last_tok
            blk_map[i, :len(st.pos_blk)] = st.pos_blk
            slot_map[i, :len(st.pos_slot)] = st.pos_slot
            lengths[i] = st.length
        return (jnp.asarray(toks), jnp.asarray(blk_map),
                jnp.asarray(slot_map), jnp.asarray(lengths))

    def _on_decode_done(self, payload) -> None:
        batch, toks = payload
        self.engine_busy = False
        for st, tok in zip(batch, toks):
            if st.state != RUNNING:     # preempted meanwhile
                continue
            st.tokens.append(tok)
            st.last_tok = tok
            st.length += 1
            st.remaining -= 1
            st.tl.token_times.append(self.now)
            if st.remaining <= 0:
                self.running.remove(st)
                self._release_table(st)
                self._finish(st, self.now)
        self._engine_kick()

    def _finish(self, st: _ReqRun, t: float) -> None:
        st.state = FINISHED
        st.tl.finish = t
        st.tl.tokens = list(st.tokens)
        for job in st.jobs:
            job.cancelled = True
        # drop the prefill results (incl. wasted speculations) — the paged
        # store/tree is the only KV owner after a request completes; paged
        # results still hold refcounts that must be returned to the pool
        for res in st.results.values():
            self._free_paged_kv(res)
        st.results = {}
        st.jobs = []
