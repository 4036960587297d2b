#!/usr/bin/env python3
"""Smoke test on the TPU: serving and training at qwen1.5-0.5b's widths.

    python3 chip_smoke.py               # one chip: serving, training, kernels
    python3 chip_smoke.py --four-chips  # 2x2 host: two-site training sync

Drives the system through the entry points a user calls, `ServingEngine`
and `Trainer`, built as `launch/serve.py` and `launch/train.py` build them,
at the published widths of qwen1.5-0.5b (24 layers, d_model 1024) with
random weights from `--seed`.  One process holds the chip(s).  Lines
starting with "[smoke]" are smoke numbers (wall time on a shared host, one
short run), not benchmark numbers.  The last line of stdout is one JSON
object, {"ok": true, "device": {...}}.  A phase that fails raises, and the
script then exits non-zero without that line; so it does when JAX finds no
TPU, or when the repository's `src/` is not next to this file.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import (CommConfig, RunConfig, ShapeConfig,  # noqa: E402
                           TrainConfig, get_config)
from repro.core.path import WAN_LONDON_POZNAN, WidePath  # noqa: E402
from repro.data import DataConfig, make_pipeline  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.runtime import ServingEngine, Trainer  # noqa: E402

ARCH = "qwen1.5-0.5b"
SLOTS, CACHE_LEN = 8, 2048                     # serving: decode slots, cache
PROMPT_LENS = (64, 256, 512)                   # one prefill compile each
N_REQUESTS, NEW_TOKENS = 12, (32, 64)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 5
FOUR_CHIP_BATCH = 16                           # 4 rows per chip
# ring-int8 vs psum-none: both start from the same weights and batches;
# int8 gradient noise may move the loss only this far in five steps
LOSS_TOL = 0.05


class CompileClock:
    """Seconds spent in XLA compilation; a persistent-cache hit counts only
    its retrieval.  (Tracing and lowering, which the cache cannot save,
    are left out.)"""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def smoke(phase: str, **fields) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    fields["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(f"[smoke] {phase} " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def assert_kernels(lowered, what: str) -> None:
    """The compiled program still calls the Pallas kernels: none was
    replaced by jnp or run in interpret mode."""
    n = lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"')
    if n == 0:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled "
                             f"program")
    smoke(f"kernels {what}", tpu_custom_calls=n)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_requests(seed: int, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, vocab, size=int(rng.choice(PROMPT_LENS))),
             int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1)))
            for _ in range(N_REQUESTS)]


def serve(cfg, mesh, clock, requests, mode: str, compress: str,
          seed: int) -> list:
    """One engine, as launch/serve.py builds it; the request set runs twice
    (the first run pays compilation).  Returns each request's tokens."""
    rc = RunConfig(model=cfg, shape=ShapeConfig("serve", CACHE_LEN, SLOTS,
                                                "decode"),
                   comm=CommConfig(), train=TrainConfig())
    path = (WidePath(axis="pod", comm=CommConfig(streams=16,
                                                 compress=compress),
                     link=WAN_LONDON_POZNAN, name="kvship")
            if mode == "disagg" else None)
    eng = ServingEngine(rc, mesh, mode=mode, path=path, seed=seed)
    runs = []
    for _ in range(2):
        c0, t0 = clock.total, time.perf_counter()
        rids = [eng.submit(p, n) for p, n in requests]
        stats = eng.run_to_completion()
        wall = time.perf_counter() - t0
        if None in rids or stats["degraded"]:
            raise AssertionError(f"{mode}/{compress}: rejected or degraded "
                                 f"(rids {rids}, stats {stats})")
        toks = [eng.results[r] for r in rids]
        for (_, n), t in zip(requests, toks):
            if len(t) != n:
                raise AssertionError(f"{mode}/{compress}: a request made "
                                     f"{len(t)} of {n} tokens")
        runs.append((toks, wall, clock.total - c0))
    (toks, wall0, comp0), (toks1, wall1, comp1) = runs
    if any(not np.array_equal(a, b) for a, b in zip(toks, toks1)):
        raise AssertionError(f"{mode}/{compress}: the repeat run decoded "
                             f"other tokens")
    n_tok = sum(len(t) for t in toks)
    smoke(f"serve mode={mode} compress={compress}", requests=len(toks),
          tokens=n_tok, first_run_s=round(wall0, 3),
          first_run_compile_s=round(comp0, 3), steady_s=round(wall1, 3),
          steady_compile_s=round(comp1, 3),
          steady_tok_per_s=round(n_tok / wall1, 1))
    if mode == "mono":
        plen = len(requests[-1][0])
        assert_kernels(eng._prefill_fn.lower(
            eng.server.params, jnp.zeros((1, plen), jnp.int32)),
            f"prefill_1x{plen}")
    del eng
    gc.collect()          # the engine's jitted prefill closes over it
    return toks


def serving_phase(cfg, clock, seed: int) -> None:
    mesh = make_local_mesh(data=len(jax.devices()), model=1)
    requests = make_requests(seed, cfg.vocab_size)
    with jax.set_mesh(mesh):
        mono = serve(cfg, mesh, clock, requests, "mono", "none", seed)
        none = serve(cfg, mesh, clock, requests, "disagg", "none", seed)
        int8 = serve(cfg, mesh, clock, requests, "disagg", "int8", seed)
    if any(not np.array_equal(a, b) for a, b in zip(mono, none)):
        raise AssertionError("mono and disagg/none decoded different tokens")
    same = sum(int(np.array_equal(a, b)) for a, b in zip(mono, int8))
    smoke("serve parity", mono_eq_disagg_none=True,
          int8_requests_equal_to_mono=f"{same}/{len(mono)}")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train(cfg, mesh, clock, comm: CommConfig, batch: int, seed: int,
          label: str) -> Trainer:
    """Trainer for TRAIN_STEPS steps, as launch/train.py builds it."""
    rc = RunConfig(
        model=cfg, shape=ShapeConfig("train", TRAIN_SEQ, batch, "train"),
        comm=comm,
        train=TrainConfig(total_steps=TRAIN_STEPS,
                          warmup_steps=max(TRAIN_STEPS // 10, 1)))
    data = make_pipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ, global_batch=batch,
        seed=seed))
    c0 = clock.total
    trainer = Trainer(rc, mesh)
    trainer.init_or_restore(seed)
    hist = trainer.run(data, TRAIN_STEPS, log_every=0)
    data.close()
    losses = [h["loss"] for h in hist]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{label}: non-finite loss {losses}")
    steady = float(np.median([h["time_s"] for h in hist[1:]]))
    smoke(f"train {label}", batch=f"{batch}x{TRAIN_SEQ}",
          compile_s=round(clock.total - c0, 3),
          first_step_s=round(hist[0]["time_s"], 3),
          steady_step_s=round(steady, 4),
          steady_tok_per_s=round(batch * TRAIN_SEQ / steady, 1),
          losses=[round(x, 4) for x in losses])
    return trainer


def training_phase(cfg, clock, seed: int) -> None:
    mesh = make_local_mesh(data=len(jax.devices()), model=1)
    with jax.set_mesh(mesh):
        trainer = train(cfg, mesh, clock, CommConfig(), TRAIN_BATCH, seed,
                        "one-chip")
        first = trainer.history[0]["loss"]
        if abs(first - math.log(cfg.vocab_size)) > 1.0:
            raise AssertionError(f"step-1 loss {first} is not within 1.0 of "
                                 f"ln(vocab) = {math.log(cfg.vocab_size)}")
        batch = trainer._place_batch(np.zeros((TRAIN_BATCH, TRAIN_SEQ + 1),
                                              np.int32))
        assert_kernels(trainer.bundle.fn.lower(trainer.state, batch),
                       "train_step")


def flash_check(seed: int) -> None:
    """One on-chip flash attention call against the jnp reference at the
    widest serving prefill shape."""
    B, S, H, D = 1, max(PROMPT_LENS), 16, 64
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               .astype(jnp.bfloat16)
               for kk in jax.random.split(jax.random.PRNGKey(seed), 3))
    got = np.asarray(ops.flash_attention(q, k, v, causal=True), np.float32)
    want = np.asarray(ref.flash_attention_ref(q, k, v, causal=True),
                      np.float32)
    smoke("kernels flash_vs_ref", shape=f"{B}x{S}x{H}x{D}",
          max_abs_err=float(np.max(np.abs(got - want))))
    # bf16 outputs: the tolerance of tests/test_kernels.py
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def codec_check(seed: int) -> None:
    """The int8 wire codec on the chip against the jnp reference at a KV
    chunk's flat shape: bit-identical codes, scales and decoded values."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (24 * 512 * 16 * 64,))
    q, s = ops.quant_int8(x)
    qr, sr = ref.quant_int8_ref(x)
    y = ops.dequant_int8(q, s)
    yr = ref.dequant_int8_ref(qr, sr)
    diff = {n: int(jnp.sum(a != b))
            for n, a, b in (("codes", q, qr), ("scales", s, sr),
                            ("decoded", y, yr))}
    smoke("kernels int8_codec_vs_ref", n=x.shape[0],
          **{f"{n}_differing": d for n, d in diff.items()})
    if any(diff.values()):
        raise AssertionError(f"int8 codec differs from ref: {diff}")


def four_chip_phase(cfg, clock, seed: int) -> None:
    """Two sites of two chips: the pod axis is the slow link.  Ring with an
    int8 wire against the plain psum, from the same weights and data."""
    mesh = make_local_mesh(data=2, model=1, pod=2)
    losses = {}
    with jax.set_mesh(mesh):
        for algo, compress in (("ring", "int8"), ("psum", "none")):
            comm = CommConfig(mode="hierarchical", algo=algo,
                              compress=compress)
            trainer = train(cfg, mesh, clock, comm, FOUR_CHIP_BATCH, seed,
                            f"pod2xdata2 {algo}/{compress}")
            losses[algo] = [h["loss"] for h in trainer.history]
            devs = set()
            for leaf in jax.tree.leaves(trainer.state["params"]):
                devs |= leaf.sharding.device_set
            batch = trainer._place_batch(np.zeros(
                (FOUR_CHIP_BATCH, TRAIN_SEQ + 1), np.int32))["tokens"]
            shards = {s.device: s.index for s in batch.addressable_shards}
            if len(devs) != 4 or len(set(map(str, shards.values()))) != 4:
                raise AssertionError(f"params span {len(devs)} devices; "
                                     f"batch shards {shards}")
            del trainer
            gc.collect()
    gap = max(abs(a - b) for a, b in zip(losses["ring"], losses["psum"]))
    smoke("train ring_int8_vs_psum_none", max_loss_gap=round(gap, 6),
          tolerance=LOSS_TOL)
    if gap > LOSS_TOL:
        raise AssertionError(f"ring/int8 and psum/none losses differ by "
                             f"{gap} > {LOSS_TOL}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the two-site training sync on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    need = 4 if args.four_chips else 1
    if len(jax.devices()) < need:
        sys.exit(f"chip_smoke: needs {need} chips, JAX sees "
                 f"{len(jax.devices())}")
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    cfg = get_config(ARCH)
    smoke("setup", arch=ARCH, layers=cfg.num_layers, d_model=cfg.d_model,
          device_kind=dev.device_kind, chips=len(jax.devices()),
          compile_cache=cache_dir)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(cfg, clock, args.seed)
    else:
        serving_phase(cfg, clock, args.seed)
        training_phase(cfg, clock, args.seed)
        flash_check(args.seed)
        codec_check(args.seed)
    smoke("total", wall_s=round(time.perf_counter() - t0, 3),
          compile_s=round(clock.total, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
