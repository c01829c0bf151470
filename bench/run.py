"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration, whose
file under ``bench/configs`` holds the model's sizes and its serving
deployment, and a traffic mix, ``bench/traffic/<traffic>.json``, which
holds the corpus, the request stream, the loop and the serving settings.
The configuration's reference module, ``bench/<"reference">`` or
``bench/model.py`` where the file names none, gives its widths, its
weights, the program's model fields, the plain reference and any further
trace keys with their work (``model.py`` says what it provides).  Each
metric is read by ``bench/metrics/<metric>.py``.

Set-up makes the weights on the device from the seed, builds the corpus,
the index and the continuous runtime through its public constructor, warms
every program the window can run and fills the knowledge tree with the
cell's own traffic.  The window then drives ``ContinuousRuntime.serve``:

* ``single``: one client in a closed loop, each request served alone and
  timed on the host clock (its time to first token);
* ``batch``: fixed groups of requests queued at once and drained, timed as
  a whole.

After the window the served tokens of a sample of requests are compared
with the plain float32 reference.  With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are read from the
trace, the program's counters and the work counts (``work.py``).

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is the JSON
result.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """time.monotonic() at which this process started (Linux /proc)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.monotonic() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.monotonic()


T_PROCESS = _process_start()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "traffic")]

import numpy as np  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
KERNELS = ("paged_prefill", "paged_decode")      # trace keys of every configuration


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict                     # configuration file
    mix: dict                      # traffic mix file
    metrics: List[str]             # metric names this run reports


def load_cell(root: str, name: str, trace: bool) -> Cell:
    """The cell and what it reports, found by name under ``root``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = load_json(os.path.join(root, conf_entry["file"]))
    mix = load_json(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))

    def here(m):
        return name in m.get("workloads", [name])

    e2e = [m["name"] for m in bench["end_to_end"] if here(m)]
    if trace:
        names = [m["name"] for m in bench["per_layer"]
                 if here(m) and m["moves"] in e2e]
    else:
        names = e2e
    return Cell(name, int(w["chips"]), conf, mix, names)


def _import(path: str, name: str):
    """The module at ``path``, registered as ``name`` (a dataclass needs its
    module in ``sys.modules`` while the class is made)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, metric: str):
    return _import(os.path.join(root, "bench", "metrics", f"{metric}.py"),
                   f"metric_{metric}").read


def load_reference(root: str, conf: dict):
    """The configuration's reference module: the file under ``bench/`` that
    its ``"reference"`` names, ``model.py`` where it names none."""
    name = conf.get("reference", "model.py")
    rel = os.path.normpath(name)
    if os.path.isabs(rel) or rel.startswith("..") or not rel.endswith(".py"):
        raise SystemExit(f"reference {name!r} is not a .py file under bench/")
    return _import(os.path.join(root, "bench", rel),
                   "reference_" + rel[:-3].replace(os.sep, "_").replace(".", "_"))


def trace_keys(ref) -> tuple:
    """The keys whose device time the trace reduction credits: the paged
    kernels and the reference module's own ``KERNELS``."""
    return KERNELS + tuple(getattr(ref, "KERNELS", ()))


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    ``armed`` (the measured window)."""

    def __init__(self, jax):
        self.armed = False
        self.compiles = 0
        self.cache_loads = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            if self.armed:
                self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1


@dataclasses.dataclass
class ServedRequest:
    req_id: int
    prompt: np.ndarray             # token ids: passages in order, question
    prompt_len: int
    tokens: List[int]
    alpha: int
    beta: int
    segments: List[int]            # uncached passage lengths, question length
    first_logits: np.ndarray       # (V,) the program's logits at the first token


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    setup_s: float
    window_s: float
    latencies: List[float]
    served: List[ServedRequest]
    trace: Optional[dict]
    work: Optional[dict]
    peak: dict


def program_config(conf: dict, ref, w):
    from repro.models.config import ModelConfig
    return ModelConfig(name=conf["name"], dtype=conf["torch_dtype"],
                       **ref.program_fields(w))


def kv_bytes_per_token(w) -> int:
    return 2 * w.L * w.KV * w.hd * 2


def bucket(n: int) -> int:
    """The runtime's prefill row bucket: a power of two, at least 8."""
    return max(8, 1 << (n - 1).bit_length())


def expected_prefill_rows(mix: dict) -> set:
    """Row buckets the mix's prefill pieces can fall in."""
    from generate import passage_lengths
    import work
    chunk = mix["engine"]["prefill_chunk"]
    sizes = {mix["question_tokens"]}
    for n in set(passage_lengths(mix).tolist()):
        sizes |= set(work.pieces([n], chunk))
    return {bucket(s) for s in sizes}


class Run:
    """One run of a cell: set-up, window, metrics, correctness."""

    def __init__(self, cell: Cell, ref, seed: int, seconds: float, peak: dict, jax):
        from generate import Traffic, make_passages
        from repro.retrieval.corpus import Corpus
        from repro.retrieval.vectordb import IVFIndex
        from repro.serving.config import EngineConfig
        from repro.serving.runtime import ContinuousRuntime

        self.cell, self.seed, self.seconds, self.peak = cell, seed, seconds, peak
        self.jax = jax
        conf, mix = cell.conf, cell.mix
        eng = mix["engine"]
        self.w = w = ref.widths(conf)
        self.params = ref.served_params(w, seed)
        jax.block_until_ready(self.params)
        self.passages = make_passages(mix, w.V, seed)
        self.traffic = Traffic(mix, self.passages, w.V, seed)
        self.stream = self.traffic.stream()
        corpus = Corpus(self.passages.vectors, self.passages.tokens,
                        self.passages.lengths)
        index = IVFIndex(corpus.doc_vectors, n_clusters=min(16, len(corpus.doc_lengths)),
                         nprobe=8)
        tiers = conf["serving"]
        block = eng["block_size"]
        n_blocks = tiers["pool_bytes"] // (block * kv_bytes_per_token(w))
        self.rt = ContinuousRuntime(
            program_config(conf, ref, w), self.params, corpus, index,
            config=EngineConfig(
                gpu_cache_bytes=tiers["device_tier_bytes"],
                host_cache_bytes=tiers["host_tier_bytes"],
                top_k=mix["top_k"], max_batch=eng["max_batch"],
                max_prefill_bs=eng["max_prefill_bs"],
                prefill_chunk=eng["prefill_chunk"],
                max_prefill_tokens=eng["max_prefill_tokens"],
                block_size=block),
            n_blocks=int(n_blocks))
        self._next_id = 0

    # ---- requests ------------------------------------------------------

    def request(self, q, max_new: int):
        from repro.retrieval.corpus import Request
        r = Request(req_id=self._next_id, arrival=0.0, query_vec=q.vector,
                    question_tokens=q.question, target_doc=q.target,
                    output_len=max_new)
        self._next_id += 1
        return r

    def served(self, res, question) -> ServedRequest:
        lens = self.passages.lengths
        prompt = np.concatenate([self.passages.tokens[d] for d in res.docs]
                                + [question]).astype(np.int32)
        segs, acc = [], 0
        for d in res.docs:
            if acc >= res.alpha:
                segs.append(int(lens[d]))
            acc += int(lens[d])
        segs.append(len(question))
        return ServedRequest(res.req_id, prompt, len(prompt), list(res.tokens),
                             res.alpha, res.beta, segs,
                             np.asarray(res.first_logits, np.float32))

    # ---- set-up ----------------------------------------------------------

    def warm_tier_moves(self) -> None:
        """Compile the tier movers' device ops for every passage size:
        a demotion gathers a passage's pages and copies them out, a
        promotion copies them back into the pool."""
        import jax.numpy as jnp
        st, w = self.rt.store, self.w
        block = st.block_size
        for nb in range(1, self.cell.mix["max_tokens"] // block + 1):
            z = np.zeros((w.L, 1, nb * block, w.KV, w.hd), jnp.bfloat16)
            seg = st.put(z, z)
            k, v = self.jax.device_get(st.gather(seg))
            st.free(seg)
        self.jax.block_until_ready(st.k)

    def fill_count(self) -> int:
        """Requests that fill both tiers once over, at the mix's mean
        prompt length."""
        mix, tiers = self.cell.mix, self.cell.conf["serving"]
        lens = self.passages.lengths
        prompt = mix["top_k"] * float(np.mean(lens)) + mix["question_tokens"]
        cap = tiers["device_tier_bytes"] + tiers["host_tier_bytes"]
        return math.ceil(mix["fill_factor"] * cap / (prompt * kv_bytes_per_token(self.w)))

    def setup(self) -> None:
        self.warm_tier_moves()
        n = self.fill_count()
        fill = [self.request(q, 1) for q in self.traffic.fill(n)]
        t0 = time.perf_counter()
        res = self.rt.serve(fill, 1)
        share = sum(r.alpha for r in res) / max(1, sum(r.alpha + r.beta for r in res))
        log(f"fill: {n} requests in {time.perf_counter() - t0:.3f} s, "
            f"hit token share {share:.4f}")
        rows = {s[1] for s in self.rt.prefill_shapes}
        missing = expected_prefill_rows(self.cell.mix) - rows
        if missing:
            log(f"fill: prefill row buckets not yet compiled: {sorted(missing)}")

    # ---- window ----------------------------------------------------------

    def window(self, counter: CompileCounter, trace: bool) -> None:
        jax, mix, rt = self.jax, self.cell.mix, self.rt
        loop, max_new = mix["loop"], mix["max_new_tokens"]
        group = mix["group"] if loop == "batch" else 1
        self.questions = {}
        self.latencies, self.drains, results = [], [], []
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        counter.armed = True
        self.attempted = 0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                reqs = [self.request(next(self.stream), max_new) for _ in range(group)]
                for r in reqs:
                    self.questions[r.req_id] = r.question_tokens
                self.attempted += len(reqs)
                s = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench.serve {loop}"):
                    out = rt.serve(reqs, max_new)
                e = time.perf_counter()
                (self.latencies if loop == "single" else self.drains).append(e - s)
                results += out
                if e - t0 >= self.seconds:
                    break
        self.window_s = e - t0
        counter.armed = False
        if trace:
            jax.profiler.stop_trace()
        self.served_reqs = [self.served(res, self.questions[res.req_id])
                            for res in results if res.tokens]
        self.failed = self.attempted - len(self.served_reqs)

    # ---- correctness -----------------------------------------------------

    def sample(self) -> List[ServedRequest]:
        """Requests the reference checks: drawn from the seed, with the
        longest prompt among them."""
        n = self.cell.mix["check_requests"]
        reqs = self.served_reqs
        longest = max(range(len(reqs)), key=lambda i: reqs[i].prompt_len)
        rng = np.random.default_rng([self.seed, 7])
        rest = [i for i in range(len(reqs)) if i != longest]
        pick = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
        return [reqs[i] for i in [longest] + sorted(pick.tolist())]

    def free_program(self) -> None:
        del self.rt, self.params
        gc.collect()


def judge(ref: List[np.ndarray], tokens: List[List[int]],
          first_logits: List[np.ndarray]) -> dict:
    """The readings taken against the reference: the widest gap by which a
    served token's logit lies below the reference's best, and the widest
    distance of a first-token logit from the reference's (L-infinity over
    the vocabulary)."""
    import model
    gaps = np.concatenate([model.logit_gaps(r, t) for r, t in zip(ref, tokens)])
    linf = max(float(np.abs(f.astype(np.float64) - r[0]).max())
               for f, r in zip(first_logits, ref))
    return {"logit_gap": float(gaps.max()), "logit_linf": linf}


def checks_of(readings: dict, limits: dict, failed: int) -> dict:
    """Each reading that the configuration gives a limit, beside it; a
    reading with no limit yet is logged and compares nothing."""
    for k in readings.keys() - limits.keys():
        log(f"reading {k}: {readings[k]} (no limit set)")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in readings.items() if k in limits}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return checks


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def compare(reference, w, seed: int, sample: List[ServedRequest],
            control: bool = False) -> dict:
    """The program's served tokens and first-token logits judged against the
    float32 reference (the configuration's ``reference`` module); with
    ``control``, the float8 control judged the same way in the program's
    place: its first choice at every row where the program chose a token,
    and its logits at the first."""
    import model
    seqs, rows = zip(*(model.served_positions(r.prompt, r.tokens) for r in sample))
    ref = reference.reference_logits(w, seed, seqs, rows)
    out = {"program": judge(ref, [r.tokens for r in sample],
                            [r.first_logits for r in sample]),
           "tokens": sum(len(r.tokens) for r in sample)}
    out["tokens_off_reference"] = int(sum(
        (model.logit_gaps(g, r.tokens) > 0).sum() for g, r in zip(ref, sample)))
    if control:
        low = reference.reference_logits(w, seed, seqs, rows, fp8=True)
        out["control"] = judge(ref, [lg.argmax(1) for lg in low], [lg[0] for lg in low])
    return out


def device_info(devices, chips: int, trace: Optional[dict]) -> dict:
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    info = {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": int(peak)}
    if trace is not None:
        info["busy_s"] = trace["busy_s"]
        info["window_s"] = trace["window_s"]
    return info


def execute(root: str, workload: str, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, peaks: Optional[dict] = None,
            control: bool = False) -> dict:
    """Run the cell and return the result object (the last stdout line)."""
    cell = load_cell(root, workload, trace)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise SystemExit(f"needs {cell.chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    table = peaks if peaks is not None else load_json(os.path.join(BENCH, "peaks.json"))
    kind = devices[0].device_kind
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    ref = load_reference(root, cell.conf)
    counter = CompileCounter(jax)
    run = Run(cell, ref, seed, seconds, table[kind], jax)
    run.setup()
    setup_s = time.monotonic() - T_PROCESS
    log(f"setup: {setup_s:.3f} s, {counter.compile_s:.3f} s of it compiling")
    run.window(counter, trace)
    served = run.served_reqs
    log(f"window: {run.window_s:.6f} s; requests attempted {run.attempted}, "
        f"completed {len(served)}")
    log(f"window: {counter.compiles} compiles and {counter.cache_loads} "
        f"compile-cache loads inside the window")
    half = len(served) // 2
    for part, rs in (("first", served[:half]), ("second", served[half:])):
        a = sum(r.alpha for r in rs)
        t = sum(r.alpha + r.beta for r in rs)
        log(f"window: hit token share, {part} half: {a / t if t else 0.0:.4f}")
    if run.drains:
        log("window: group drains (s): " + " ".join(f"{d:.4f}" for d in run.drains))
    tr = work = None
    if trace:
        import trace_reduce as trace_mod
        import work as work_mod
        dev_ops, host = trace_mod.read(trace_mod.latest_xplane(TRACE_DIR))
        tr = trace_mod.reduce(dev_ops, host, trace_keys(ref))
        log(f"trace: busy {tr['busy_s']:.6f} s of {tr['window_s']:.6f} s; kernels "
            + ", ".join(f"{k} {v:.6f} s" for k, v in tr["kernel_s"].items()))
        needs = [work_mod.Served(r.segments, r.alpha, max(0, len(r.tokens) - 1))
                 for r in served]
        args = (run.w, needs, cell.mix["engine"]["prefill_chunk"],
                run.peak["bf16_flops"], run.peak["hbm_bytes_per_s"])
        work = work_mod.count(*args)
        if hasattr(ref, "kernel_work"):
            work.update(ref.kernel_work(*args))
    dev = device_info(devices, cell.chips, tr)
    log(f"device: peak_bytes_in_use {dev['memory_peak_bytes']}")
    ctx = Context(setup_s, run.window_s, run.latencies, served, tr, work, run.peak)
    metrics = {}
    units = {m["name"]: m["unit"] for m in _all_metrics(root)}
    for name in cell.metrics:
        v = load_reader(root, name)(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}
    sample = run.sample()
    w = run.w
    run.free_program()
    t0 = time.perf_counter()
    limits = {k: float(v) for k, v in cell.conf["check"].items()}
    cmp = compare(ref, w, seed, sample, control)
    log(f"reference: {len(sample)} requests, {cmp['tokens']} served tokens, "
        f"{cmp['tokens_off_reference']} not the reference's first choice, "
        f"{time.perf_counter() - t0:.3f} s")
    checks = checks_of(cmp["program"], limits, run.failed)
    result = {"correct": passes(checks), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": dev}
    if tr is not None:
        result["breakdown"] = tr["breakdown"]
    if control:
        ctl = checks_of(cmp["control"], limits, run.failed)
        result["control"] = {"correct": passes(ctl), "checks": ctl,
                             "readings": cmp["control"], "program_readings": cmp["program"]}
        for k, c in ctl.items():
            log(f"control {k}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def _all_metrics(root: str):
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    return bench["end_to_end"] + bench["per_layer"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = execute(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
