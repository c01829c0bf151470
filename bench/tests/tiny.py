"""A CPU-sized copy of the benchmark: the real harness files, a tiny
configuration and a tiny mix, laid out as a checkout."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_CONF = {
    "name": "tiny", "source": "test", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "sliding_window": 96, "tie_word_embeddings": False, "qkv_bias": True,
    "torch_dtype": "bfloat16", "reduced": [],
    "serving": {"pool_bytes": 200 * 16 * 256, "device_tier_bytes": 100000,
                "host_tier_bytes": 100000},
    "check": {"logit_gap": 0.05, "logit_linf": 0.05},
}

# Four experts of which each token takes two, the first layer windowed
# and the second full; the reference module is this directory's moe_ref.py.
TINY_MOE_CONF = dict(
    TINY_CONF, name="tiny-moe", reference="moe_ref.py", num_local_experts=4,
    num_experts_per_tok=2, layer_types=["sliding_attention", "full_attention"])

TINY_MIX = {
    "loop": "batch", "passages": 60, "median_tokens": 48, "sigma": 0.4,
    "min_tokens": 16, "max_tokens": 96, "token_multiple": 16, "top_k": 2,
    "question_tokens": 8, "fill_factor": 1.2, "targets": "zipf", "zipf_s": 1.0,
    "shuffle_block": 4, "max_new_tokens": 3, "group": 4, "check_requests": 3,
    "engine": {"block_size": 16, "prefill_chunk": 32, "max_prefill_tokens": 0,
               "max_prefill_bs": 1, "max_batch": 4},
}

PEAKS = {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
                 "source": "test"}}


def dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


TOK_S = {"name": "tok_s", "unit": "tok/s", "better": "higher", "bound": 0.1,
         "source": "host_clock"}


def make_root(tmp: str, mixes: dict, conf: dict = TINY_CONF) -> str:
    """A checkout under ``tmp`` whose BENCHMARK.json has one tiny cell per
    mix (named ``tiny.<mix>``), with the real metric readers and the
    default reference module, and the configuration's own reference module
    where it names one in this directory.  The batch cells' throughput
    metric is added as a later cell would add it: a reader file and an
    entry."""
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(tmp, "bench", "metrics"))
    shutil.copy(os.path.join(BENCH, "model.py"), os.path.join(tmp, "bench"))
    if "reference" in conf:
        shutil.copy(os.path.join(HERE, conf["reference"]), os.path.join(tmp, "bench"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    dump(os.path.join(tmp, "bench", "configs", "tiny.json"), conf)
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = []
    for name, mix in mixes.items():
        dump(os.path.join(tmp, "bench", "traffic", f"{name}.json"), mix)
        bench["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                   "traffic": name, "chips": 1, "why": "test"})
    cells = [w["name"] for w in bench["workloads"]]
    single = [c for c, m in zip(cells, mixes.values()) if m["loop"] == "single"]
    batch = [c for c in cells if c not in single]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = single
    if batch:
        bench["end_to_end"].append(dict(TOK_S, workloads=batch))
        with open(os.path.join(tmp, "bench", "metrics", "tok_s.py"), "w") as f:
            f.write('"""Served tokens per second."""\n'
                    "from readers import tok_s as read  # noqa: F401\n")
    dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp


MLP_ROOFLINE = {"name": "mlp_roofline.single", "unit": "%", "better": "higher",
                "source": "device_trace", "layer": "model step", "moves": "ttft_p95_ms"}


def add_metric(root: str, entry: dict, reader: str) -> None:
    """A per-layer metric added as a later PR adds one: a reader file and
    an entry, for every cell."""
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    cells = [w["name"] for w in bench["workloads"]]
    bench["per_layer"].append(dict(entry, workloads=cells))
    dump(path, bench)
    with open(os.path.join(root, "bench", "metrics", f"{entry['name']}.py"), "w") as f:
        f.write(reader)
