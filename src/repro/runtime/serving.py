"""ServingEngine: disaggregated prefill/decode with continuous batching.

Glues the three serving pieces together with *real* model work:

* `core.serving.ContinuousBatcher` — the slot scheduler (virtual step clock,
  deterministic event timeline);
* `core.kvship` — the prefilled KV cache crossing the WAN as chunked leaves
  over a `WidePath` (``mode="disagg"``), with exact per-hop wire bytes under
  ``serve/req{rid}/kv`` telemetry keys;
* `runtime.serve_loop.Server` — the decode StepBundle, driven here with
  per-sequence ``(B,)`` positions so every slot sits at its own depth.

Engine semantics: one engine step == one batcher step == one decode token
per occupied slot.  Prefill and KV-ship execute synchronously at their
transition step (the batcher runs with ``ship_steps=0``), so a monolithic
engine (``mode="mono"``) and a disaggregated one replay the *same* schedule
— the parity test asserts their tokens are bit-identical, because decode is
row-local and the ``none`` codec ships bytes unchanged.  Modeled WAN
seconds still land in telemetry via the shipper.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunConfig
from repro.core import telemetry as tel
from repro.core.kvship import KVShipPlan, ShipError, plan_kv_ship, ship_kv
from repro.core.path import WidePath
from repro.core.serving import ContinuousBatcher
from repro.runtime.serve_loop import Server


class ServingEngine:
    """Continuous-batching serving with optional prefill/decode split.

    Parameters
    ----------
    rc: run config; ``rc.shape.global_batch`` is the decode slot count and
        ``rc.shape.seq_len`` the decode cache length.
    mesh: decode-site mesh (prefill runs on the same process here; the
        disaggregation is in the KV bytes crossing `path`).
    mode: ``"mono"`` (prefill feeds decode in-memory) or ``"disagg"``
        (prefill KV is shipped over `path` before decode may start).
    path: the WAN `WidePath` KV caches cross when ``mode="disagg"``.
    route / topo: the :class:`~repro.core.topology.Route` the path was
        compiled from plus its topology — with these, each real KV ship
        runs under the route's `LinkProfile` fault schedules (reship on a
        failed hop through `retry`, reroute over `topo` after
        `max_reships`); a :class:`~repro.core.kvship.ShipError` (no route
        left) degrades the engine to in-memory KV handoff (collocated
        mono fallback, ``stats()["degraded"]``).
    deadline_steps / membership / prefill_site / decode_site / log: passed
        to the batcher — per-request SLOs with shedding, serve failover
        off evicted sites, incidents into `log`.
    """

    def __init__(self, rc: RunConfig, mesh, *, mode: str = "mono",
                 path: Optional[WidePath] = None, params=None, seed: int = 0,
                 queue_limit: int = 64, step_s: float = 1e-2,
                 route=None, topo=None, retry=None, max_reships: int = 2,
                 ship_timeout_s: float = 30.0, deadline_steps=None,
                 shed: bool = True, membership=None,
                 prefill_site: Optional[str] = None,
                 decode_site: Optional[str] = None, log=None):
        if mode not in ("mono", "disagg"):
            raise ValueError(f"mode must be 'mono' or 'disagg', got {mode!r}")
        if mode == "disagg" and path is None:
            raise ValueError(f"mode='disagg' needs a WidePath to ship KV "
                             f"over, got path={path!r}")
        if rc.model.encoder_layers:
            raise ValueError(
                f"ServingEngine is decoder-only; {rc.model.name!r} has "
                f"{rc.model.encoder_layers} encoder layers")
        self.rc = rc
        self.mode = mode
        self.path = path
        self.route = route
        self.topo = topo
        self.retry = retry
        self.max_reships = int(max_reships)
        self.ship_timeout_s = float(ship_timeout_s)
        self.log = log
        self._degraded = False
        self.server = Server(rc, mesh, params=params, seed=seed)
        self.model = self.server.bundle.model
        self.max_slots = rc.shape.global_batch
        self.max_len = rc.shape.seq_len
        self.batcher = ContinuousBatcher(
            self.max_slots, queue_limit, prefill_steps=1, ship_steps=0,
            step_s=step_s, deadline_steps=deadline_steps, shed=shed,
            log=log, membership=membership, prefill_site=prefill_site,
            decode_site=decode_site)
        self.cache = self.server.init_cache()
        self._pos = np.zeros(self.max_slots, np.int32)
        self._tok = np.zeros((self.max_slots, 1), np.int32)
        self._decoding: dict[int, int] = {}     # slot -> rid
        self._prompts: dict[int, np.ndarray] = {}
        self._outputs: dict[int, list] = {}
        self.results: dict[int, np.ndarray] = {}   # rid -> generated tokens
        self._n_events = 0
        self._ship_plans: dict[tuple, KVShipPlan] = {}

        def serve_prefill(p, toks):
            return self.model.prefill(p, {"tokens": toks})

        # a named function, so its program runs as `jit_serve_prefill`
        self._prefill_fn = jax.jit(serve_prefill)

    # -- request intake -----------------------------------------------------
    def submit(self, prompt_tokens: np.ndarray, max_new: int,
               deadline_steps: Optional[int] = None) -> Optional[int]:
        """Admit one request (or None when admission control rejects or
        sheds it)."""
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        S_p = prompt.shape[0]
        w = self.rc.model.sliding_window
        if S_p + max_new > self.max_len or (w and S_p > w):
            raise ValueError(
                f"prompt_len={S_p} + max_new={max_new} exceeds the decode "
                f"cache (max_len={self.max_len}, window={w})")
        rid = self.batcher.submit(S_p, max_new, deadline_steps=deadline_steps)
        if rid is not None:
            self._prompts[rid] = prompt
        return rid

    # -- engine step --------------------------------------------------------
    def step(self) -> int:
        """One engine step: batcher transition + the real work it implies."""
        pre = dict(self._decoding)   # slots decoding before this step
        with tel.span("serve.step", decoding=len(pre)):
            self.batcher.step_once()
            tl = self.batcher.timeline()
            events = tl[self._n_events:]
            self._n_events = len(tl)
            if pre:
                self._decode_tick(pre)   # batcher rule (3): pre-existing slots
            for kind, tag, _step in events:
                rid = int(tag[3:])
                if kind == "decode":
                    self._on_decode_start(rid)
                elif kind == "complete":
                    self._on_complete(rid)
                elif kind == "timeout":
                    self._on_abort(rid, keep_prompt=False)
                elif kind == "requeue":
                    self._on_abort(rid, keep_prompt=True)
                elif kind in ("shed", "reject"):
                    self._prompts.pop(rid, None)
        return len(events)

    def run_to_completion(self, max_steps: int = 100_000) -> dict:
        """Step until every submitted request is terminal; returns stats."""
        steps = 0
        while self.batcher.active() > 0:
            if steps >= max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps: "
                    f"{self.batcher.active()} request(s) still live")
            self.step()
            steps += 1
        return self.batcher.stats()

    # -- internals ----------------------------------------------------------
    def _decode_tick(self, slots: dict) -> None:
        """One real batched decode step; only `slots` rows advance."""
        bundle = self.server.bundle
        logits, self.cache = bundle.fn(
            self.server.params, self.cache, jnp.asarray(self._pos),
            jnp.asarray(self._tok))
        with tel.span("serve.decode_sync", rows=len(slots)):
            toks = np.asarray(jnp.argmax(logits[:, -1:, :], axis=-1))[:, 0]
        for slot, rid in slots.items():
            self._outputs[rid].append(int(toks[slot]))
            self._pos[slot] += 1
            self._tok[slot, 0] = toks[slot]

    def _on_decode_start(self, rid: int) -> None:
        """Prefill the request's prompt, ship its KV if disaggregated, land
        it in the decode cache, and bank the first token."""
        slot = self.batcher.slot_of(rid)
        prompt = self._prompts[rid]
        S_p = prompt.shape[0]
        with tel.span("serve.admit", rid=rid, tokens=S_p):
            with tel.span("serve.prefill", tokens=S_p):
                logits, pcache = self._prefill_fn(self.server.params,
                                                  prompt[None, :])
            with tel.span("serve.kv_to_host",
                          bytes=pcache["k"].nbytes + pcache["v"].nbytes):
                kv = {n: np.asarray(pcache[n][:, 0]) for n in ("k", "v")}
            if self.mode == "disagg" and not self._degraded:
                geom = tuple(sorted((n, tuple(a.shape))
                                    for n, a in kv.items()))
                if geom not in self._ship_plans:
                    self._ship_plans[geom] = plan_kv_ship(kv, self.path)
                try:
                    kv, res = ship_kv(kv, self._ship_plans[geom], rid,
                                      step=self.batcher.now(),
                                      route=self.route, retry=self.retry,
                                      max_reships=self.max_reships,
                                      topo=self.topo, log=self.log,
                                      timeout_s=self.ship_timeout_s)
                    self.batcher.note_ship(rid, reships=res.reships,
                                           reroutes=res.reroutes)
                except ShipError as e:
                    # no surviving route: hand the KV over in memory from
                    # here on (collocated mono fallback) and flag it
                    self._degraded = True
                    self.batcher.degrade(reason=str(e))
            with tel.span("serve.cache_insert",
                          bytes=sum(a.nbytes for a in kv.values())):
                cache = dict(self.cache)
                for n, leaf in kv.items():
                    cache[n] = self.cache[n].at[:, slot, :S_p].set(
                        jnp.asarray(leaf).astype(self.cache[n].dtype))
                self.cache = cache
            with tel.span("serve.first_token"):
                first = int(np.asarray(jnp.argmax(logits[0, -1])))
            self._pos[slot] = S_p
            self._tok[slot, 0] = first
            self._outputs[rid] = [first]
            self._decoding[slot] = rid

    def _on_complete(self, rid: int) -> None:
        slot = None
        for s, r in self._decoding.items():
            if r == rid:
                slot = s
                break
        if slot is not None:
            del self._decoding[slot]
        self.results[rid] = np.asarray(self._outputs.pop(rid), np.int64)

    def _on_abort(self, rid: int, *, keep_prompt: bool) -> None:
        """A request left the pipeline without completing: `timeout` drops
        it for good, `requeue` (serve failover) keeps the prompt so the
        re-queued request prefills again from scratch."""
        slot = None
        for s, r in self._decoding.items():
            if r == rid:
                slot = s
                break
        if slot is not None:
            del self._decoding[slot]
        self._outputs.pop(rid, None)
        if not keep_prompt:
            self._prompts.pop(rid, None)
