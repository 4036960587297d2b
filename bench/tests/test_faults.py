"""The comparison that decides `correct` fails when the timed path is
broken underneath: each fault a cell can have is planted in the program,
the harness runs the rest of a run past its look for a chip, and `correct`
comes out false; the unbroken run comes out true.  Tiny cells on the CPU.
"""
import pytest

from bench.tests.conftest import run_tiny

SEED = 2**31 + 77


def test_serving_sound(tiny_root):
    r = run_tiny(tiny_root, "tserve.tiny", SEED, 1.5)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_serving_faults_fail(tiny_root, fault):
    r = run_tiny(tiny_root, "tserve.tiny", SEED, 1.5, fault)
    assert not r["correct"], r["checks"]
