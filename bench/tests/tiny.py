"""A benchmark root of tiny cells for CPU tests: the real harness, drivers
and readers on a two-layer model, found by name like any cell."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = {"name": "tiny", "hidden_size": 128, "intermediate_size": 256,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
          "rope_theta": 10000.0, "tie_word_embeddings": False}
MIXES = {
    "tserve": {"kind": "serve",
               "engine": {"mode": "disagg", "slots": 4, "cache_len": 64,
                          "streams": 4, "compress": "int8",
                          "queue_limit": 64},
               "arrivals": {"process": "poisson", "rate_per_s": 20.0},
               "prompt_len": {"values": [16, 32]},
               "output_len": {"values": [4, 8]},
               "check": {"requests": 4}, "trace_seconds": 0.5,
               "drain_seconds": 30},
    "tbatch": {"kind": "serve",
               "engine": {"mode": "mono", "slots": 4, "cache_len": 64,
                          "queue_limit": 64},
               "arrivals": {"process": "closed", "clients": 4,
                            "requests_per_client": 50},
               "prompt_len": {"values": [16, 32]},
               "output_len": {"values": [4, 8]},
               "check": {"requests": 4}, "trace_seconds": 0.5},
}
CHIPS = {"tserve": 1, "tbatch": 1}
# limits for the tiny model on the CPU, where the program computes in
# float32 and bfloat16 much as the reference does
LIMITS = {"tserve": {"served_gap": 0.05}, "tbatch": {"served_gap": 0.05}}


def make_root(path: str) -> str:
    """Write BENCHMARK.json and the tiny cells' files under `path`, with the
    real metric readers beside them."""
    os.makedirs(os.path.join(path, "bench"), exist_ok=True)
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(path, "bench", d), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(path, "bench", "metrics"),
                    dirs_exist_ok=True)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": f"{m}.tiny", "config": "tiny",
                          "traffic": m, "chips": CHIPS[m], "why": "test"}
                         for m in MIXES]
    real = {"serve_disagg_int8": "tserve", "serve_batch_mono": "tbatch"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"{real[w.split('.')[0]]}.tiny"
                              for w in m["workloads"]]
    _dump(os.path.join(path, "BENCHMARK.json"), spec)
    _dump(os.path.join(path, "bench", "configs", "tiny.json"), CONFIG)
    for name, mix in MIXES.items():
        _dump(os.path.join(path, "bench", "traffic", f"{name}.json"), mix)
        _dump(os.path.join(path, "bench", "cells", f"{name}.tiny.json"),
              {"checks": {k: {"limit": v}
                          for k, v in LIMITS[name].items()}})
    return path


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
