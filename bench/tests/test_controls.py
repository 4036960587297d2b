"""The controls: the reference put in the program's place and computed in
fp8, the next precision below the bfloat16 the configurations state, has
to come out not correct against the same limits as the program.  Tiny
cells on the CPU; the cells' own readings, at their own sizes on the chip,
are in PERF.md."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, traffic
from bench.tests import tiny
from bench.weights import dims_of, make_params

DM = dims_of(tiny.CONFIG)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_fails(seed):
    p = traffic.prompt_tokens(seed, [32], DM.vocab)[0]
    params = make_params(DM, seed)
    # served tokens: the reference's own greedy continuation, so the f32
    # reading is exactly 0 and the gap is the control's alone
    seq = np.zeros(40, np.int32)
    seq[:32] = p
    for i in range(8):
        x = reference.hidden(params, jnp.asarray(seq[None, :32 + i]), DM,
                             "f32")[0, -1]
        seq[32 + i] = int(jnp.argmax(reference.logits(params, x, DM)))
    served = jnp.asarray(seq[32:40])
    g_srv, g_ctl = reference.served_gaps(params, jnp.asarray(seq), 32,
                                         served, dm=DM, mode="fp8")
    limit = tiny.LIMITS["tserve"]["served_gap"]
    assert float(jnp.max(g_srv)) == pytest.approx(0.0, abs=1e-5)
    assert float(jnp.max(g_ctl)) > limit


def test_judge_needs_every_limit():
    cell = harness.Cell(root="", name="x", entry={}, spec={}, config={},
                        mix={}, limits={"checks": {"a": {"limit": 1.0}}})
    assert harness.judge(cell, {"a": 0.5})[0]
    assert not harness.judge(cell, {"a": 1.5})[0]
    assert not harness.judge(cell, {"a": 0.5, "b": 0.0})[0]
    assert not harness.judge(cell, {"a": float("nan")})[0]
    assert not harness.judge(cell, {})[0]
