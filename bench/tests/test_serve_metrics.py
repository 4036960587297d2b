"""The serving drivers' end-to-end metrics: the gaps between tokens are
read by one statistic in each kind of gap, and a window reports exactly
the end-to-end metrics that BENCHMARK.json names for its cell."""
import json
import math
import os
import time

import numpy as np
import pytest

from bench import drive_serve, harness, system
from bench.tests.conftest import REPO
from bench.weights import dims_of

PLAIN, STALL = 0.017, 0.250     # one decode step; one admission stall
SHARES = (0.045, 0.055, 0.065)  # of gaps that hold an admission


def _stamps(stall_share, n=4000, requests=20):
    """Token stamps of `requests` requests whose `n` gaps are all PLAIN but
    `stall_share` of them, spread evenly, which are STALL."""
    gaps = np.full(n, PLAIN)
    gaps[np.linspace(0, n - 1, round(stall_share * n)).round().astype(int)] \
        = STALL
    return [np.concatenate([[0.0], np.cumsum(g)]).tolist()
            for g in np.split(gaps, requests)]


@pytest.mark.parametrize("name, steady", [("itl_p99_ms", STALL),
                                          ("itl_p50_ms", PLAIN),
                                          ("itl_p95_ms", None)])
def test_itl_stats_one_kind_of_gap_each(name, steady):
    """With 4.5-6.5% of gaps stalled, the p99 stays on the stall and the
    median on the plain step, where the p95 jumps from one to the other."""
    reads = []
    for share in SHARES:
        metrics, notes = drive_serve.itl_stats(_stamps(share), math.inf)
        assert set(metrics) == {"itl_p99_ms", "itl_p50_ms"}
        reads.append({**metrics, **notes}[name])
    if steady is None:
        assert max(reads) / min(reads) > 5, reads
    else:
        assert max(reads) / min(reads) < 1.1, reads
        assert reads[0] == pytest.approx(1e3 * steady, rel=0.1)


class FakeEngine:
    """Stands in for `ServingEngine` under the drivers: every step adds one
    token to each request it holds, until the request's `max_new`."""

    def __init__(self):
        self.batcher = self
        self._outputs: dict = {}
        self.results: dict = {}
        self._left: dict = {}

    # the hooks that `drive_serve.Probe` wraps
    def _prefill_fn(self, params, toks):
        return None

    def _on_decode_start(self, rid):
        return None

    def _decode_tick(self, slots):
        return None

    def submit(self, prompt, max_new):
        rid = len(self._outputs) + len(self.results)
        self._outputs[rid], self._left[rid] = [], int(max_new)
        return rid

    def active(self) -> int:
        return len(self._left)

    def step(self) -> int:
        time.sleep(0.001)
        for rid in list(self._left):
            self._outputs[rid].append(1)
            self._left[rid] -= 1
            if not self._left[rid]:
                del self._left[rid]
                self.results[rid] = np.asarray(self._outputs.pop(rid))
        return 1


def _serve_cells():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    return [w["name"] for w in spec["workloads"]
            if harness.load_cell(REPO, w["name"]).mix["kind"] == "serve"]


@pytest.mark.parametrize("workload", _serve_cells())
def test_window_reports_the_cells_end_to_end_names(workload):
    cell = harness.load_cell(REPO, workload)
    cell.seed, cell.seconds, cell.t_start = 2**31 + 11, 0.3, time.time()
    eng = FakeEngine()
    probe = drive_serve.Probe(eng)
    try:
        win = drive_serve.window(cell, dims_of(cell.config),
                                 system.mesh_of({}), eng, probe,
                                 harness.CompileCounter())
    finally:
        probe.close()
    assert sorted(win["metrics"]) == sorted(harness.end_to_end_names(cell))
    assert all(math.isfinite(v) and v > 0 for v in win["metrics"].values())
