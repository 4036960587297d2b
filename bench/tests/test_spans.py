"""The readers of the program's own spans and named programs
(`bench/spans.py`), on the committed trace and on made-up intervals."""
import os

import pytest

from bench import readers, spans as S, trace as T
from bench.weights import dims_of, load_config
from bench.tests.conftest import REPO

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "decode_14b_pp4.xplane.pb")
PEAK = {"flops": 197e12, "hbm_bytes_per_s": 819e9}

# the readers' numbers on the committed fixture, as they read before the
# program's own spans were read
FIXTURE_COUNTS = {"decode_tokens": 128, "decode_keys": 128 * 400}
FIXTURE_METRICS = {
    "kvship_ms_per_req": None, "mfu.prefill.serve_disagg": None,
    "flash_fwd_roofline.serve_disagg": None,
    "int8_codec_roofline.serve_disagg": None,
    "idle.serve_disagg": 7.061788032242633,
    "mfu.serve_batch": 5.029645369450941,
    "idle.serve_batch": 7.061788032242633}
FIXTURE_BREAKDOWN = {
    "device_ops": [
        ["fusion:dynamic-slice_bitcast_fusion.4", 0.009822518],
        ["copy:copy.273", 0.009800043], ["copy:copy.272", 0.009795322],
        ["fusion:bitcast_dynamic-update-slice_fusion.4", 0.009719902],
        ["fusion:bitcast_add_fusion.8", 0.009089139],
        ["fusion:fusion.152", 0.009048734],
        ["fusion:fusion.153", 0.009048653],
        ["fusion:convert_bitcast_fusion.2", 0.008237735],
        ["fusion:bitcast_dynamic-update-slice_fusion.5", 0.004777415],
        ["fusion:fusion.147", 0.004360568]],
    "idle_gaps": [
        ["$dispatch.py:424 _device_put_sharding_impl", 0.002794833],
        ["$tree_util.py:74 tree_flatten", 0.002399991],
        ["$<unknown> append", 0.002329681],
        ["$array.py:631 _value", 1.2144e-05]]}
SPAN_METRICS = (
    "admit_ms_per_req.serve_disagg", "kv_to_host_ms_per_req.serve_disagg",
    "kvship_codec_ms_per_req.serve_disagg",
    "cache_insert_ms_per_req.serve_disagg", "idle_in_admit.serve_disagg",
    "decode_step_ms.serve_disagg", "decode_step_ms.serve_batch",
    "step_host_ms.serve_batch")


def test_fixture_reads_as_before_and_has_no_program_spans(tmp_path,
                                                          monkeypatch):
    from bench import harness
    tr = T.load(DATA, whole=True)
    assert S.load(DATA) == (None, [])
    monkeypatch.setattr(S, "ROOT", str(tmp_path))
    monkeypatch.setattr(S, "_last", (None, []))
    assert T.busy_s(tr) == pytest.approx(0.099187724, rel=1e-12)
    assert tr.window_s == pytest.approx(0.106724373, rel=1e-12)
    r = readers.Reading(trace=tr, dims=dims_of(load_config(
        f"{REPO}/bench/configs/qwen2.5-14b-pp4.json")), peak=PEAK, chips=1,
        counts=FIXTURE_COUNTS)
    for name, want in FIXTURE_METRICS.items():
        got = harness.reader(REPO, name)(r)
        assert got == (None if want is None else pytest.approx(
            want, rel=1e-12)), name
    assert T.breakdown(tr) == FIXTURE_BREAKDOWN
    # the decode program there predates its name, and no span was written:
    # every reader of the program's spans and names finds nothing
    assert S.of(r) == []
    for name in SPAN_METRICS:
        assert harness.reader(REPO, name)(r) is None, name


def _ms(name, start, end):
    return T.Event(name, start * 1e6, end * 1e6)


WINDOW = (100e6, 1000e6)


def _made_up(monkeypatch) -> readers.Reading:
    """A window from 100 to 1000 ms.  Steps B (300-600) and C (600-900)
    lie inside it; step A and the last admission straddle its edges.  The
    spans stand in for what `of` would have read from the trace file."""
    spans = [
        _ms("serve.step", 50, 300), _ms("serve.decode_sync", 60, 150),
        _ms("serve.step", 300, 600), _ms("serve.decode_sync", 310, 400),
        _ms("serve.admit", 420, 580), _ms("serve.kv_to_host", 430, 470),
        _ms("kvship.ship", 470, 560), _ms("kvship.codec", 480, 500),
        _ms("kvship.codec", 510, 540), _ms("serve.cache_insert", 560, 575),
        _ms("serve.step", 600, 900), _ms("serve.decode_sync", 600, 700),
        _ms("serve.admit", 720, 860), _ms("kvship.ship", 730, 850),
        _ms("kvship.codec", 740, 790), _ms("kvship.codec", 800, 810),
        _ms("serve.cache_insert", 850, 858),
        _ms("serve.step", 900, 1100), _ms("serve.admit", 950, 1050),
        _ms("kvship.ship", 960, 1040), _ms("kvship.codec", 970, 990)]
    monkeypatch.setattr(S, "_last", (WINDOW, spans))
    # programs as `trace.load` leaves them: clipped to the window
    d0 = [_ms("jit_serve_decode(1)", 100, 420),
          _ms("jit_serve_decode(1)", 600, 760),
          _ms("jit_serve_prefill(2)", 880, 1000)]
    d1 = [_ms("jit_other(3)", 100, 200), _ms("jit_serve_decode(1)", 200, 300),
          _ms("jit_other(3)", 300, 1000)]
    tr = T.Trace(window=WINDOW,
                 devices=[T.Device("d0", d0, [], []),
                          T.Device("d1", d1, [], [])],
                 host=[])
    return readers.Reading(trace=tr, dims=None, peak=PEAK, chips=2)


def test_span_readers_leave_out_spans_cut_by_the_window(monkeypatch):
    r = _made_up(monkeypatch)
    assert [e.start / 1e6 for e in S.inside(r, "serve.step")] == [300, 600]
    # admissions 160 and 140 ms; the one at the right edge is left out
    assert S.mean_ms(r, "serve.admit") == pytest.approx(150)
    assert S.mean_ms(r, "serve.kv_to_host") == pytest.approx(40)
    assert S.mean_ms(r, "serve.cache_insert") == pytest.approx(11.5)
    assert S.mean_ms(r, "serve.first_token") is None


def test_child_sums_per_parent(monkeypatch):
    r = _made_up(monkeypatch)
    # codec 20 + 30 in the first ship, 50 + 10 in the second; the third
    # ship straddles the edge, so its codec span counts for nothing
    assert S.child_ms_per_parent(r, "kvship.ship", "kvship.codec") == \
        pytest.approx(55)
    # steps of 300 ms, holding decode syncs of 90 and 100 ms
    assert S.child_ms_per_parent(r, "serve.step", "serve.decode_sync") == \
        pytest.approx(95)
    assert S.child_ms_per_parent(r, "serve.none", "kvship.codec") is None


def test_idle_under_a_span_averages_over_the_devices(monkeypatch):
    r = _made_up(monkeypatch)
    # device 0 is idle 420-600 and 760-880: under admissions 420-580 and
    # 760-860, 260 ms; device 1 is never idle; window 900 ms
    assert S.idle_under_pct(r, "serve.admit") == pytest.approx(
        100 * 260 / 2 / 900)
    assert S.idle_under_pct(r, "serve.none") is None


def test_program_time_by_name(monkeypatch):
    r = _made_up(monkeypatch)
    # 160 ms on device 0 and 100 on device 1; the run clipped at the
    # window's start is left out
    assert S.mean_program_ms(r, "jit_serve_decode") == pytest.approx(130)
    assert S.mean_program_ms(r, "jit_serve_prefill") is None


def test_span_metrics_on_made_up_trace(monkeypatch):
    from bench import harness
    r = _made_up(monkeypatch)
    want = {"admit_ms_per_req.serve_disagg": 150,
            "kv_to_host_ms_per_req.serve_disagg": 40,
            "kvship_codec_ms_per_req.serve_disagg": 55,
            "cache_insert_ms_per_req.serve_disagg": 11.5,
            "idle_in_admit.serve_disagg": 100 * 260 / 2 / 900,
            "decode_step_ms.serve_disagg": 130,
            "decode_step_ms.serve_batch": 130,
            "step_host_ms.serve_batch": 300 - 95}
    assert set(want) == set(SPAN_METRICS)
    for name, v in want.items():
        assert harness.reader(REPO, name)(r) == pytest.approx(v), name


def _record(path, rid):
    import jax
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(path):
        with TraceAnnotation("serve.step", decoding=2):
            with TraceAnnotation(T.WINDOW_SPAN):
                with TraceAnnotation("serve.admit", rid=rid, tokens=16):
                    pass
        with TraceAnnotation("serve.admit", rid=rid + 1, tokens=8):
            pass


def test_of_reads_the_trace_file_of_the_window_unclipped(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(S, "_last", (None, []))
    base = tmp_path / "bench_out" / "trace"
    _record(str(base / "other"), 7)
    _record(str(base / "cell"), 4)
    path = T.find_xplane(str(base / "cell"))
    # the newer trace file is not the one whose window is read
    os.utime(T.find_xplane(str(base / "other")), (2e9, 2e9))
    tr = T.load(path)
    assert not any(e.name.startswith(S.PREFIXES) for e in tr.host)
    r = readers.Reading(trace=tr, dims=None, peak=None, chips=1)
    step, admit = sorted(S.of(r, root=str(tmp_path)), key=lambda e: e.start)
    assert (step.name, admit.name) == ("serve.step", "serve.admit")
    assert int(admit.stats["rid"]) == 4 and int(step.stats["decoding"]) == 2
    # the step began before the window and is kept whole
    assert step.start < tr.window[0] and step.end > tr.window[1]
    # the admission after the window is in the file but does not overlap
    rids = lambda evs: sorted(int(e.stats["rid"]) for e in evs
                              if e.name == "serve.admit")
    assert rids(S.load(path)[1]) == [4, 5] and rids(S.of(r)) == [4]
