"""The harness finds cells, configurations, mixes and metric readers by
name; the traffic generators are deterministic in the seed; and off the
chip `bench/run.py` exits non-zero without a result."""
import json
import os
import subprocess
import sys

import numpy as np

from bench import harness, traffic
from bench.tests import tiny
from bench.tests.conftest import REPO

MIX = {"kind": "serve", "arrivals": {"process": "poisson", "rate_per_s": 3.0},
       "prompt_len": {"values": [256, 512, 1024], "shares": [0.3, 0.4, 0.3]},
       "output_len": {"values": [16, 32, 64, 256],
                      "shares": [0.3, 0.3, 0.25, 0.15]}}


def test_new_files_found_by_name(tmp_path):
    """A configuration, a mix, a metric and a cell added as files and one
    BENCHMARK.json entry each: no harness file names them."""
    root = tiny.make_root(str(tmp_path))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "other", "source": "test",
                            "file": "bench/configs/other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "newmix.other", "config": "other",
                              "traffic": "newmix", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "device", "moves": "itl_p95_ms",
                              "workloads": ["newmix.other"]})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("newmix.other")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    json.dump({**tiny.CONFIG, "name": "other", "hidden_size": 256},
              open(os.path.join(root, "bench/configs/other.json"), "w"))
    json.dump(MIX, open(os.path.join(root, "bench/traffic/newmix.json"),
                        "w"))
    with open(os.path.join(root, "bench/metrics/new_metric.py"), "w") as f:
        f.write("def read(r):\n    return 7.0\n")
    cell = harness.load_cell(root, "newmix.other")
    assert cell.config["hidden_size"] == 256
    assert cell.mix["arrivals"]["rate_per_s"] == 3.0
    assert harness.end_to_end_names(cell) == ["itl_p95_ms", "setup_s"]
    names = [m["name"] for m in harness.per_layer_entries(cell)]
    assert names == ["new_metric"]
    assert harness.reader(root, "new_metric")(None) == 7.0
    assert harness.driver_for(cell).__module__ == "bench.drive_serve"


def test_every_metric_has_a_reader():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for m in spec["per_layer"]:
        assert callable(harness.reader(REPO, m["name"])), m["name"]
    for w in spec["workloads"]:
        cell = harness.load_cell(REPO, w["name"])
        assert harness.per_layer_entries(cell), w["name"]
        assert "setup_s" in harness.end_to_end_names(cell)


def test_open_loop_same_work_for_every_seed():
    big = 2**31 + 12345
    a = traffic.open_loop(MIX, big, 40.0)
    assert a == traffic.open_loop(MIX, big, 40.0)
    b = traffic.open_loop(MIX, big + 1, 40.0)
    assert a != b
    assert len(a) == len(b)
    assert sorted(x[1] for x in a) == sorted(x[1] for x in b)
    assert sorted(x[2] for x in a) == sorted(x[2] for x in b)
    assert all(0.0 <= d < 40.0 for d, _, _ in a)
    assert sorted(d for d, _, _ in a) == [d for d, _, _ in a]
    shares = {v: sum(x[1] == v for x in a) / len(a) for v in (256, 512, 1024)}
    assert abs(shares[512] - 0.4) < 0.02


def test_closed_loop_and_tokens_deterministic():
    mix = dict(MIX, arrivals={"process": "closed", "clients": 4,
                              "requests_per_client": 5})
    assert traffic.closed_loop(mix, 9) == traffic.closed_loop(mix, 9)
    assert traffic.closed_loop(mix, 9) != traffic.closed_loop(mix, 10)
    p1 = traffic.prompt_tokens(9, [3, 5], 100)
    p2 = traffic.prompt_tokens(9, [3, 5], 100)
    assert all(np.array_equal(x, y) for x, y in zip(p1, p2))


def test_run_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = spec["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "5", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


def test_bench_json_keys_and_names():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in spec["workloads"]}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= 1
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(
            REPO, "bench", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            REPO, "bench", "cells", w["name"] + ".json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
