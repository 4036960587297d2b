"""Run one tiny cell on the CPU with the timed path broken by a planted
fault (or not), past the harness's look for a chip; print the result line.

    python3 -m bench.tests.run_tiny <root> <cell> <seed> <seconds> [fault]

Faults: token_altered (a served token altered where the engine banks it),
state_unchanged (the decode step hands back its cache unchanged).
"""
from __future__ import annotations

import json
import sys
import time

from bench import harness, system  # noqa: F401  (puts src/ on the path)


def plant(fault: str) -> None:
    import repro.runtime.serving as rs
    from repro.models.transformer import Transformer
    if fault == "token_altered":
        orig = rs.ServingEngine._decode_tick

        def tick(self, slots):
            orig(self, slots)
            for rid in slots.values():
                out = self._outputs[rid]
                out[-1] = (out[-1] + 1) % self.rc.model.vocab_size
        rs.ServingEngine._decode_tick = tick
    elif fault == "state_unchanged":
        orig = Transformer.decode_step

        def decode_step(self, params, cache, pos, tokens, **k):
            logits, _ = orig(self, params, cache, pos, tokens, **k)
            return logits, cache
        Transformer.decode_step = decode_step
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")


def main(argv):
    root, name, seed, seconds = argv[0], argv[1], int(argv[2]), float(argv[3])
    fault = argv[4] if len(argv) > 4 else ""
    cell = harness.load_cell(root, name)
    cell.seed, cell.seconds, cell.t_start = seed, seconds, time.time()
    devs = harness.init_jax(root, cell.entry["chips"], require_tpu=False)
    plant(fault)
    res = harness.run_cell(cell, devs[:cell.entry["chips"]],
                           harness.driver_for(cell))
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
