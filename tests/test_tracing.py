"""The serving engine's profiler spans and program names.

A small disaggregated int8 engine runs under `jax.profiler.trace`; its
`.xplane.pb` is read back with `ProfileData`.  The spans must all be there,
nest as the engine's calls nest, carry the engine's own numbers as stats,
and change no token; the prefill and decode programs carry stable names.
"""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (CommConfig, RunConfig, ShapeConfig, TrainConfig,
                           get_config, smoke_config)
from repro.core import telemetry as tel
from repro.core.path import (WAN_LONDON_POZNAN, WAN_POZNAN_GDANSK, Hop,
                             WidePath)

SPANS = ("serve.step", "serve.decode_sync", "serve.admit", "serve.prefill",
         "serve.kv_to_host", "kvship.ship", "kvship.codec",
         "serve.cache_insert", "serve.first_token")
ADMIT_PARTS = ("serve.prefill", "serve.kv_to_host", "kvship.ship",
               "serve.cache_insert", "serve.first_token")


@pytest.fixture(scope="module")
def rc_mesh():
    from repro.launch.mesh import make_local_mesh
    cfg = smoke_config(get_config("llama3.2-3b"))
    rc = RunConfig(model=cfg, shape=ShapeConfig("d", 64, 3, "decode"),
                   comm=CommConfig(), train=TrainConfig())
    return rc, make_local_mesh()


def _int8_two_hop() -> WidePath:
    comm = CommConfig(streams=4, chunk_mb=0.001, compress="int8")
    hops = (Hop(name="hop0-lon-poz", link=WAN_LONDON_POZNAN, comm=comm),
            Hop(name="hop1-poz-gda", link=WAN_POZNAN_GDANSK, comm=comm))
    return WidePath(axis="pod", comm=comm, name="kvship").with_hops(hops)


def _requests(cfg):
    rng = np.random.default_rng(11)
    return [(rng.integers(1, cfg.vocab_size, size=pl), mn)
            for pl, mn in [(8, 3), (16, 2), (8, 4), (12, 2)]]


def _serve(rc, mesh, mode, trace_dir=None):
    """Run the requests to completion; returns (engine, {rid: prompt
    length}, spans or None)."""
    from repro.runtime.serving import ServingEngine
    eng = ServingEngine(rc, mesh, mode=mode, seed=0,
                        path=_int8_two_hop() if mode == "disagg" else None)
    lens = {}
    for prompt, mnew in _requests(rc.model):
        lens[eng.submit(prompt, mnew)] = len(prompt)
    if trace_dir is None:
        eng.run_to_completion()
        return eng, lens, None
    with jax.profiler.trace(trace_dir):
        eng.run_to_completion()
    return eng, lens, _read_spans(trace_dir)


def _read_spans(trace_dir) -> list:
    """(name, start, end, stats) of the host events named `serve.*` or
    `kvship.*`, in start order."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "kvship.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def _children(spans, parent, name):
    return [s for s in _named(spans, name) if _inside(s, parent)]


@pytest.fixture(scope="module")
def disagg(rc_mesh, tmp_path_factory):
    rc, mesh = rc_mesh
    return _serve(rc, mesh, "disagg", str(tmp_path_factory.mktemp("tr")))


def test_every_span_present(disagg):
    _, _, spans = disagg
    assert {s[0] for s in spans} == set(SPANS)


def test_spans_nest_as_the_engine_calls(disagg):
    _, _, spans = disagg
    for codec in _named(spans, "kvship.codec"):
        assert any(_inside(codec, p) for p in _named(spans, "kvship.ship"))
    for name in ADMIT_PARTS:
        for s in _named(spans, name):
            parents = [a for a in _named(spans, "serve.admit")
                       if _inside(s, a)]
            assert len(parents) == 1, name
            if name == "kvship.ship":
                assert s[3]["rid"] == parents[0][3]["rid"]
    for name in ("serve.admit", "serve.decode_sync"):
        for s in _named(spans, name):
            assert any(_inside(s, p) for p in _named(spans, "serve.step"))
    # a step's decoding stat is the rows its decode sync read
    for step in _named(spans, "serve.step"):
        syncs = _children(spans, step, "serve.decode_sync")
        assert [s[3]["rows"] for s in syncs] == (
            [step[3]["decoding"]] if step[3]["decoding"] else [])


def test_one_admit_per_request_with_rid_and_length(disagg):
    eng, lens, spans = disagg
    admits = _named(spans, "serve.admit")
    assert {a[3]["rid"]: a[3]["tokens"] for a in admits} == lens
    assert len(admits) == len(lens) == len(eng.results)
    for a in admits:
        (pre,) = _children(spans, a, "serve.prefill")
        assert pre[3]["tokens"] == a[3]["tokens"]
        (to_host,) = _children(spans, a, "serve.kv_to_host")
        (insert,) = _children(spans, a, "serve.cache_insert")
        # the KV that left the device lands in the cache at the same size,
        # and each hop's codec spans carry every byte of it
        assert to_host[3]["bytes"] == insert[3]["bytes"] > 0
        codecs = _children(spans, a, "kvship.codec")
        hop_in = sum(c[3]["bytes"] for c in codecs) // 2
        assert hop_in == to_host[3]["bytes"]


def test_codec_spans_per_ship_are_chunks_times_hops(disagg):
    eng, lens, spans = disagg
    # one frozen plan per prompt length: KV leaves are (layers, length, ..)
    plans = {p.shapes[0][1]: p for p in eng._ship_plans.values()}
    ships = _named(spans, "kvship.ship")
    assert len(ships) == len(lens) and len(plans) == len(set(lens.values()))
    for ship in ships:
        plan = plans[lens[ship[3]["rid"]]]
        codecs = _children(spans, ship, "kvship.codec")
        assert ship[3]["chunks"] == len(plan.chunks)
        assert ship[3]["hops"] == plan.n_hops == 2
        assert len(codecs) == len(plan.chunks) * plan.n_hops
        assert sum(c[3]["wire"] for c in codecs) == plan.wire_bytes_total


def test_mono_engine_emits_no_kvship_span(rc_mesh, tmp_path):
    rc, mesh = rc_mesh
    _, lens, spans = _serve(rc, mesh, "mono", str(tmp_path))
    names = {s[0] for s in spans}
    assert not any(n.startswith("kvship.") for n in names)
    assert len(_named(spans, "serve.admit")) == len(lens)
    assert set(SPANS) - names == {"kvship.ship", "kvship.codec"}


def test_tokens_identical_with_profiler_on_and_off(rc_mesh, disagg):
    rc, mesh = rc_mesh
    traced, _, _ = disagg
    plain, _, _ = _serve(rc, mesh, "disagg")
    assert sorted(traced.results) == sorted(plain.results)
    for rid in plain.results:
        np.testing.assert_array_equal(traced.results[rid], plain.results[rid])


def test_programs_are_named(disagg):
    eng, _, _ = disagg
    toks = jnp.zeros((1, 8), jnp.int32)
    prefill = eng._prefill_fn.lower(eng.server.params, toks).as_text()
    assert "@jit_serve_prefill" in prefill
    decode = eng.server.bundle.fn.lower(
        eng.server.params, eng.cache, jnp.asarray(eng._pos),
        jnp.asarray(eng._tok)).as_text()
    assert "@jit_serve_decode" in decode


def test_span_needs_no_profiler():
    with tel.span("serve.step", decoding=3) as s:
        with tel.span("serve.first_token"):
            pass
    assert isinstance(s, jax.profiler.TraceAnnotation)
