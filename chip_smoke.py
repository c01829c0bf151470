"""Smoke test of the RAG serving path on TPU.

One chip (no arguments): serve full-width qwen2-0.5b (its published config,
random weights from a seed) through ``repro.launch.serve``'s continuous
runtime, with prefill in pieces of up to 32 tokens batched into ragged
chunks of several sizes, and check every request against the sequential
``RAGServer`` oracle (``--check-tokens``).  The paged prefill and decode
programs the runtime ran must contain the compiled Pallas kernels
(``tpu_custom_call`` and the kernel's name in the HLO).

Four chips (``--four-chips``), and nothing else: (a) four one-chip qwen2-0.5b
replicas behind the doc-affinity router, each placed on its own chip,
checked against the oracle; (b) full-width llama2-7b at ``--tp 2`` and then
at ``--tp 4``, compared by tokens.

    python chip_smoke.py
    python chip_smoke.py --four-chips

Everything runs in this one process and it starts no other.  JAX's compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache``
in the checkout.  Any failure exits non-zero without printing a result;
on success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402

from repro.launch import serve  # noqa: E402

# One chip: greedy tokens must equal the oracle's (on v5e they do, at a
# first-token logit L-inf of 0.068).  Four chips: tokens must match, or the
# first-token logits agree within L-inf 0.5 = 16 bf16 ulps at the top
# logits' magnitude (printed per phase; [4, 8) has ulp 2**-5).  On v5e, tp=4
# and tp=2 logits differ by up to 0.247 and one request in four diverged
# at its fourth token (README "Running on a TPU").
CHECK = "exact"
FLEET_CHECK = "tol:0.5"
REQUESTS = ["--docs", "16", "--doc-tokens", "32", "--top-k", "2"]
REQUESTS += ["--max-new-tokens", "4", "--seed", "0"]
REQUESTS += ["--prefill-chunk", "32", "--max-prefill-tokens", "96"]
QWEN = ["--arch", "qwen2-0.5b", "--published", "--requests", "8"] + REQUESTS


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache hits skip)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


def serve_phase(name: str, argv: list, clock: CompileClock):
    """Run ``serve.main(argv)`` and print what it served."""
    print(f"\n=== {name}: serve.py {' '.join(argv)}", flush=True)
    c0, t0 = clock.seconds, time.perf_counter()
    out = serve.main(argv)
    wall = time.perf_counter() - t0
    cfg = out.cfg
    tokens = sum(len(r.tokens) for r in out.results)
    print(
        f"[{name}] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} hd={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype}"
    )
    print(
        f"[{name}] served {len(out.results)} requests, {tokens} tokens in "
        f"{wall:.1f}s wall, {clock.seconds - c0:.1f}s of it compiling"
    )
    top = max(float(abs(r.first_logits).max()) for r in out.results)
    print(f"[{name}] max |first-token logit| {top}")
    if out.linf is not None:
        print(f"[{name}] check passed: first-token logit L-inf {out.linf}")
    return out


def check_kernels(name: str, runtimes: list) -> None:
    """Every paged program a runtime ran must hold its Pallas kernel."""
    for i, rt in enumerate(runtimes):
        programs = rt.compiled_steps()
        if "decode" not in programs or len(programs) < 2:
            fail(f"{name}: replica {i} ran no paged programs: {list(programs)}")
        for prog, text in sorted(programs.items()):
            kernel = "paged_decode" if prog == "decode" else "paged_prefill"
            if "tpu_custom_call" not in text or kernel not in text:
                fail(f"{name}: {prog} program holds no {kernel} kernel")
            print(f"[{name}] replica {i} {prog}: {kernel} tpu_custom_call")


def one_chip(clock: CompileClock) -> None:
    out = serve_phase("qwen2-0.5b", QWEN + ["--check-tokens", CHECK], clock)
    check_kernels("qwen2-0.5b", out.runtimes)


def placement(rt) -> tuple:
    """(devices holding any param, devices holding the pool) of a runtime."""
    leaves = jax.tree.leaves(rt.params)
    return set().union(*(x.devices() for x in leaves)), rt.store.k.devices()


def four_chips(clock: CompileClock) -> None:
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, found {len(devs)}")
    argv = QWEN + ["--replicas", "4", "--check-tokens", FLEET_CHECK]
    out = serve_phase("replicas", argv, clock)
    for i, rt in enumerate(out.runtimes):
        params, pool = placement(rt)
        where = f"params on {sorted(map(str, params))}"
        print(f"[replicas] replica {i}: {where}, pool on {sorted(map(str, pool))}")
        if params != {devs[i]} or pool != {devs[i]}:
            fail(f"replica {i} is not placed on {devs[i]} alone")
    del out
    gc.collect()

    llama = ["--arch", "llama2-7b", "--published", "--requests", "4"] + REQUESTS
    runs = {}
    for tp in (2, 4):
        out = serve_phase(f"llama2-7b tp={tp}", llama + ["--tp", str(tp)], clock)
        runs[tp] = {r.req_id: r for r in out.results}
        del out
        gc.collect()  # release the tp=2 replica before tp=4 places its own
    mode, eps = serve.parse_check_mode(FLEET_CHECK)
    pairs = [(runs[4][i], runs[2][i]) for i in sorted(runs[4])]
    same = sum(list(a.tokens) == list(b.tokens) for a, b in pairs)
    print(
        f"[llama2-7b] tp=4 vs tp=2: {same}/{len(pairs)} requests with identical "
        f"tokens; first-token logit L-inf {serve.first_logit_linf(pairs)}"
    )
    bad = serve.token_mismatches(pairs, mode, eps)
    if bad:
        fail(f"llama2-7b tp=4 vs tp=2 token mismatch: {bad}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true", help="run only the four-chip phases"
    )
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (device 0 is {dev.platform})")
    cache = serve.setup_compile_cache()
    count = len(jax.devices())
    print(f"device_kind={dev.device_kind} count={count} compile cache={cache}")
    clock = CompileClock()
    (four_chips if args.four_chips else one_chip)(clock)
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"{d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    n = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache {cache}: {n} entries")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": count}
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
