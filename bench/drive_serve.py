"""Serving cells: `ServingEngine.step()` driven by an open-loop schedule
(arrivals due at fixed times, whatever the engine does) or a closed loop
(each client sends its next request when the last one finishes).

Set-up draws the weights on the device, builds the engine as
`launch/serve.py` does, and warms every prompt length of the mix.  The
window then runs for `--seconds`; every token is stamped when the engine
step that made it returns (the step ends in the host's argmax sync).  After
the window the engine is freed and the reference scores a sample of the
finished requests (`bench/reference.served_gaps`).
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import reference, system, traffic
from bench.harness import (CompileCounter, Outcome, TracedTail, memory_peak,
                           span)
from bench.weights import make_params


class Probe:
    """Host spans around the engine's calls into its layers, and the token
    stamps.  Spans: `bench.prefill` (the prefill call, with the prompt
    length), `bench.ship_kv`, `bench.admit` (prefill, ship, cache insert and
    first token), `bench.decode` (the batched decode step and its argmax
    sync).  The spans wrap the engine's attributes from outside and add no
    synchronisation of their own; a hook the engine no longer has is an
    error, not a silently missing span."""

    HOOKS = ("_prefill_fn", "_on_decode_start", "_decode_tick")

    def __init__(self, eng):
        import repro.runtime.serving as rs
        missing = [h for h in self.HOOKS if not hasattr(eng, h)]
        if not hasattr(rs, "ship_kv"):
            missing.append("repro.runtime.serving.ship_kv")
        if missing:
            raise AttributeError(f"the serving engine has no {missing}: "
                                 "the benchmark's spans need updating")
        self.eng = eng
        self.rs = rs
        self._ship = rs.ship_kv
        self._prefill = eng._prefill_fn
        self._admit = eng._on_decode_start
        self._decode = eng._decode_tick

        def prefill(params, toks):
            with span("bench.prefill", tokens=int(toks.shape[1])):
                return self._prefill(params, toks)

        def ship(*a, **k):
            with span("bench.ship_kv"):
                return self._ship(*a, **k)

        def admit(rid):
            with span("bench.admit"):
                return self._admit(rid)

        def decode(slots):
            with span("bench.decode"):
                return self._decode(slots)

        rs.ship_kv = ship
        eng._prefill_fn = prefill
        eng._on_decode_start = admit
        eng._decode_tick = decode
        self.live: dict = {}       # rid -> tokens seen so far
        self.stamps: dict = {}     # rid -> [seconds of each token]
        self.counting = False
        self.decode_tokens = 0
        self.decode_keys = 0
        self.prompt_len: dict = {}

    def close(self):
        self.rs.ship_kv = self._ship

    def watch(self, rid: int, prompt_len: int):
        self.live[rid] = 0
        self.stamps[rid] = []
        self.prompt_len[rid] = prompt_len

    def collect(self, t: float) -> list:
        """Stamp the tokens the last step made; returns rids finished."""
        done = []
        outs, res = self.eng._outputs, self.eng.results
        for rid in list(self.live):
            toks = res.get(rid)
            finished = toks is not None
            n = len(toks) if finished else len(outs.get(rid, ()))
            seen = self.live[rid]
            if n > seen:
                self.stamps[rid] += [t] * (n - seen)
                if self.counting:
                    for i in range(max(seen, 1), n):
                        self.decode_tokens += 1
                        self.decode_keys += self.prompt_len[rid] + i
                self.live[rid] = n
            if finished:
                del self.live[rid]
                done.append(rid)
        return done


def _p(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else \
        float("nan")


def itl_stats(stamps, end: float) -> tuple:
    """Gaps between consecutive tokens of each request, both stamped by the
    window's close `end`: (end-to-end metrics, notes), in ms.

    Gaps are of two kinds: one decode step, and a decode step that every
    active slot waits through while a prompt is admitted (prefill, KV
    shipping, cache insert).  The median sits among the plain steps and,
    while over 1% of gaps hold an admission (5-6% in the disagg mix), the
    99th percentile among the admissions, however long they take.  The
    95th percentile there falls on the border between the two kinds and
    swings with the arrival order, so it is only a note, beside the mean of
    the worst 5% of gaps."""
    gaps = []
    for ts in stamps:
        gaps += list(np.diff([t for t in ts if t <= end]))
    worst = sorted(gaps)[len(gaps) - math.ceil(len(gaps) / 20):]
    return ({"itl_p50_ms": 1e3 * _p(gaps, 50),
             "itl_p99_ms": 1e3 * _p(gaps, 99)},
            {"itl_p95_ms": 1e3 * _p(gaps, 95),
             "itl_tail5_mean_ms": 1e3 * float(np.mean(worst)) if worst
             else float("nan"),
             "itl_gaps": len(gaps)})


def _warm(eng, mix, dm, seed):
    """One request of every prompt length: compiles prefill at each length,
    the KV codec at each chunk length, the cache insert and decode."""
    lens = sorted(set(mix["prompt_len"]["values"]))
    toks = traffic.prompt_tokens(seed + 7919, lens, dm.vocab)
    for _ in range(2):
        for t in toks:
            eng.submit(t, 2)
        eng.run_to_completion()


def setup(cell, dm):
    """Weights on the device, the engine as `launch/serve.py` builds it,
    its layer spans, and every prompt length of the mix warmed."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    e = cell.mix["engine"]
    mesh = system.mesh_of({})
    with jax.set_mesh(mesh):
        params = make_params(dm, cell.seed, NamedSharding(mesh, P()))
        eng = system.serving_engine(
            dm, mesh, params, mode=e["mode"], slots=e["slots"],
            cache_len=e["cache_len"], streams=e.get("streams", 16),
            compress=e.get("compress", "none"),
            queue_limit=e.get("queue_limit", 4096))
        del params
        probe = Probe(eng)
        _warm(eng, cell.mix, dm, cell.seed)
    return mesh, eng, probe


def window(cell, dm, mesh, eng, probe, counter, mix=None) -> dict:
    import jax
    mix = mix or cell.mix
    loop = (_open_loop if mix["arrivals"]["process"] == "poisson"
            else _closed_loop)
    with jax.set_mesh(mesh):
        return loop(eng, probe, mix, dm, cell.seed, float(cell.seconds),
                    cell, counter)


def run(cell, dm, devices, *, control_modes=()) -> Outcome:
    counter = CompileCounter()
    mesh, eng, probe = setup(cell, dm)
    try:
        win = window(cell, dm, mesh, eng, probe, counter)
    finally:
        probe.close()
    peak = memory_peak(devices)
    finished = {rid: np.asarray(eng.results[rid]) for rid in win["rids"]
                if rid in eng.results}
    prompts = win["prompts"]
    del eng, probe
    gc.collect()
    checks, extra = _check(dm, cell.mix, cell.seed, finished, prompts,
                           control_modes)
    notes = {"setup_s": win["setup_s"], "window_s": win["window_s"],
             "window_compiles": win["compiles"],
             "generator_late_s_p95": win.get("late_p95"),
             "backlog_at_close": win.get("backlog"),
             **win.get("notes", {}),
             "sampled_requests": extra["n"],
             "sampled_tokens": extra["tokens"], **extra.get("control", {})}
    return Outcome(metrics=win["metrics"], checks=checks,
                   attempted=win["attempted"], failed=win["failed"],
                   memory_peak_bytes=peak, counts=win["counts"],
                   trace_dir=win.get("trace_dir", ""), notes=notes)


def _open_loop(eng, probe, mix, dm, seed, seconds, cell, counter) -> dict:
    sched = traffic.open_loop(mix, seed, seconds)
    prompts = traffic.prompt_tokens(seed, [s for _, s, _ in sched], dm.vocab)
    rid_of: list = [None] * len(sched)
    sent_at: list = [None] * len(sched)
    tr = TracedTail(cell)
    c0 = counter.total()
    t_wall = time.time()
    t0 = time.perf_counter()
    setup_s = t_wall - cell.t_start
    i = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        tr.tick(now)
        probe.counting = tr.on
        while i < len(sched) and sched[i][0] <= now:
            rid = eng.submit(prompts[i], sched[i][2])
            rid_of[i], sent_at[i] = rid, now
            if rid is not None:
                probe.watch(rid, sched[i][1])
            i += 1
        if eng.batcher.active() > 0:
            with span("bench.step"):
                eng.step()
            probe.collect(time.perf_counter() - t0)
        else:
            nxt = sched[i][0] if i < len(sched) else seconds
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    end = time.perf_counter() - t0
    tr.stop()
    probe.counting = False
    compiles = counter.total() - c0
    backlog = sum(1 for k in range(i)
                  if rid_of[k] is None or not probe.stamps[rid_of[k]])
    # every request due in the window gets its first token, however late
    due = [(k, rid_of[k]) for k in range(i) if rid_of[k] is not None]
    limit = time.perf_counter() + float(mix.get("drain_seconds", 120))
    while (any(not probe.stamps[r] for _, r in due)
           and time.perf_counter() < limit and eng.batcher.active() > 0):
        eng.step()
        probe.collect(time.perf_counter() - t0)
    ttft = [probe.stamps[r][0] - sched[k][0] for k, r in due
            if probe.stamps[r]]
    itl, itl_notes = itl_stats([probe.stamps[r] for _, r in due], end)
    failed = (sum(r is None for r in rid_of[:i])
              + sum(not probe.stamps[r] for _, r in due))
    late = [sent_at[k] - sched[k][0] for k in range(i)]
    return {"metrics": {"setup_s": setup_s, **itl},
            "notes": {"ttft_p50_ms": 1e3 * _p(ttft, 50),
                      "ttft_p95_ms": 1e3 * _p(ttft, 95), **itl_notes},
            "attempted": i, "failed": failed, "setup_s": setup_s,
            "window_s": end, "compiles": compiles,
            "late_p95": _p(late, 95), "backlog": backlog,
            "rids": [r for _, r in due],
            "prompts": {r: prompts[k] for k, r in due},
            "counts": {"decode_tokens": probe.decode_tokens,
                       "decode_keys": probe.decode_keys},
            "trace_dir": tr.dir}


def _closed_loop(eng, probe, mix, dm, seed, seconds, cell, counter) -> dict:
    plans = traffic.closed_loop(mix, seed)
    flat = [p for pl in plans for p in pl]
    toks = traffic.prompt_tokens(seed, [s for s, _ in flat], dm.vocab)
    per = len(plans[0])
    nxt = [0] * len(plans)
    client_of: dict = {}
    prompts: dict = {}
    attempted = failed = 0

    def send(c):
        nonlocal attempted, failed
        k = nxt[c]
        if k >= per:
            return
        nxt[c] += 1
        s, n = plans[c][k]
        attempted += 1
        rid = eng.submit(toks[c * per + k], n)
        if rid is None:
            failed += 1
            return
        client_of[rid] = c
        prompts[rid] = toks[c * per + k]
        probe.watch(rid, s)

    for c in range(len(plans)):
        send(c)
    # every client's first request is decoding before the window opens
    while any(not probe.stamps[r] for r in list(client_of)):
        eng.step()
        for r in probe.collect(-1.0):
            send(client_of[r])
    tr = TracedTail(cell)
    c0 = counter.total()
    t_wall = time.time()
    t0 = time.perf_counter()
    setup_s = t_wall - cell.t_start
    attempted = failed = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        tr.tick(now)
        probe.counting = tr.on
        with span("bench.step"):
            eng.step()
        for r in probe.collect(time.perf_counter() - t0):
            send(client_of[r])
    end = time.perf_counter() - t0
    tr.stop()
    probe.counting = False
    tokens = sum(sum(1 for t in ts if t > 0) for ts in probe.stamps.values())
    return {"metrics": {"setup_s": setup_s,
                        "serve_tokens_per_s": tokens / end},
            "attempted": attempted, "failed": failed, "setup_s": setup_s,
            "window_s": end, "compiles": counter.total() - c0,
            "rids": sorted(r for r in client_of if r in eng.results),
            "prompts": prompts,
            "counts": {"decode_tokens": probe.decode_tokens,
                       "decode_keys": probe.decode_keys},
            "trace_dir": tr.dir}


def sample(finished: dict, n: int, seed: int) -> list:
    """The longest finished request and n-1 others drawn from the seed."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r]), -r))
    rest = [r for r in rids if r != longest]
    r = traffic.rng(seed, 4)
    pick = list(r.choice(rest, size=min(n - 1, len(rest)), replace=False)) \
        if rest else []
    return [longest] + sorted(int(x) for x in pick)


def _check(dm, mix, seed, finished, prompts, control_modes) -> tuple:
    """Scores the sample with the float32 reference: the widest gap by which
    a served token's logit lies below the reference's best at its position.
    With `control_modes`, also the gap of the token each lower-precision
    control puts first at the same positions."""
    import jax
    chk = mix["check"]
    rids = sample(finished, int(chk["requests"]), seed)
    if not rids:
        return {"served_gap": float("inf")}, {"n": 0, "tokens": 0}
    T = max(mix["prompt_len"]["values"]) + max(mix["output_len"]["values"])
    n_max = max(mix["output_len"]["values"])
    params = make_params(dm, seed)
    worst = {"f32": -1.0, **{m: -1.0 for m in control_modes}}
    n_tok = 0
    for rid in rids:
        p, s = prompts[rid], finished[rid]
        seq = np.zeros(T, np.int32)
        seq[:len(p)] = p
        seq[len(p):len(p) + len(s)] = s
        served = np.full(n_max, -1, np.int32)
        served[:len(s)] = s
        n_tok += len(s)
        for mode in ("f32",) + tuple(control_modes):
            g_srv, g_ctl = reference.served_gaps(
                params, jax.numpy.asarray(seq), len(p),
                jax.numpy.asarray(served), dm=dm, mode=mode)
            if mode == "f32":
                worst["f32"] = max(worst["f32"], float(np.max(g_srv)))
            else:
                worst[mode] = max(worst[mode], float(np.max(g_ctl)))
    del params
    extra = {"n": len(rids), "tokens": n_tok,
             "control": {f"control_gap_{m}": worst[m] for m in control_modes}}
    return {"served_gap": worst["f32"]}, extra
