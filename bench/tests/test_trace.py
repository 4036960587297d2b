"""The trace reduction, on a small trace recorded on a TPU v5 lite (four
decode steps of qwen2.5-14b-pp4 at 32 slots, PR 12) and on made-up
intervals."""
import os

import pytest

from bench import readers, trace as T, work
from bench.weights import dims_of, load_config
from bench.tests.conftest import REPO

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "decode_14b_pp4.xplane.pb")
PEAK = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tr():
    return T.load(DATA, whole=True)


def test_programs_and_busy(tr):
    assert len(tr.devices) == 1
    names = [m.name.split("(")[0] for m in tr.devices[0].modules]
    assert names.count("jit_body") == 4 and names.count("jit__argmax") == 4
    busy = T.busy_s(tr)
    # four decode steps of ~24.65 ms and four argmax programs
    assert busy == pytest.approx(
        sum(m.dur for m in tr.devices[0].modules) / 1e9)
    assert 0.095 < busy < 0.105
    assert busy < tr.window_s


def test_leaf_ops_and_kernels(tr):
    ops = tr.devices[0].ops
    assert not any(T.op_kind(e.name) == "while" for e in ops)
    kinds = [work.kernel_of(e.name) for e in ops]
    # rmsnorm: two per layer and the final one, 12 layers, four steps
    assert kinds.count("rmsnorm") == 4 * (2 * 12 + 1)
    r = readers.Reading(trace=tr, dims=dims_of(load_config(
        f"{REPO}/bench/configs/qwen2.5-14b-pp4.json")), peak=PEAK, chips=1)
    share = readers.roofline_pct(r, ("rmsnorm",))
    assert 0 < share <= 100
    assert readers.roofline_pct(r, ("flash_fwd",)) is None
    idle = readers.idle_pct(r)
    assert 0 < idle < 10


def test_breakdown(tr):
    b = T.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(s, float) and s > 0 for _, s in b["device_ops"])
    top = sum(s for _, s in b["device_ops"])
    assert top <= T.busy_s(tr) + 1e-9


def test_intervals():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]
    ev = [T.Event("p", 0, 10), T.Event("a", 1, 3), T.Event("b", 4, 9),
          T.Event("c", 11, 12)]
    assert [e.name for e in T._leaves(ev)] == ["a", "b", "c"]


def test_collectives_and_kinds():
    t = ("%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
         "replica_groups={}")
    assert T.op_kind(t) == "all-reduce-start" and T.is_collective(t)
    t = "%cp.1 = (f32[4]{0}, f32[4]{0}) collective-permute-start(f32[4] %y)"
    assert T.is_collective(t)
    assert not T.is_collective("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %y)")


def test_annotation_arguments():
    assert T._named("bench.prefill#tokens=512#", {}) == (
        "bench.prefill", {"tokens": "512"})


def test_launched_modules_are_the_first_after_each_span():
    mods = [T.Event("jit_decode", 0, 40), T.Event("jit_prefill", 55, 85),
            T.Event("jit_codec", 90, 95), T.Event("jit_prefill", 130, 150)]
    host = [T.Event("bench.prefill", 50, 52),
            T.Event("bench.prefill", 120, 121)]
    tr = T.Trace(window=(0, 200), devices=[T.Device("d", mods, [], [])],
                 host=host)
    r = readers.Reading(trace=tr, dims=None, peak=PEAK, chips=1)
    # each prefill span launched the program that starts after it, however
    # long after the host span itself ended
    assert readers.launched_modules_s(r, "bench.prefill") == (30 + 20) / 1e9
    assert readers.launched_modules_s(r, "bench.none") == 0.0
