"""Shared fixtures.

NOTE: XLA_FLAGS / device counts are NOT set here (smoke tests must see the
real single CPU device).  Multi-device tests run in subprocesses via
`multidev` below.
"""
from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import zlib

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- hypothesis degradation ---------------------------------------------------
# When hypothesis is missing (clean env), property tests fall back to a small
# deterministic engine instead of skipping.  Test modules use:
#     try: from hypothesis import given, ...
#     except ImportError: from conftest import given, st
#
# The fallback supports the strategy kinds our suites actually use
# (sampled_from / integers / floats / booleans).  Each test runs a fixed
# number of examples: the two boundary corners first, then samples from an
# RNG seeded by the test name, so failures replay bit-identically.
class _Strategy:
    """Deterministic stand-in for a hypothesis strategy."""

    def __init__(self, boundaries, sample):
        self.boundaries = list(boundaries)
        self._sample = sample

    def sample(self, rng):
        return self._sample(rng)


class _St:
    @staticmethod
    def sampled_from(elements):
        xs = list(elements)
        return _Strategy([xs[0], xs[-1]], lambda rng: rng.choice(xs))

    @staticmethod
    def integers(min_value, max_value):
        return _Strategy([min_value, max_value],
                         lambda rng: rng.randint(min_value, max_value))

    @staticmethod
    def floats(min_value, max_value):
        return _Strategy([min_value, max_value],
                         lambda rng: rng.uniform(min_value, max_value))

    @staticmethod
    def booleans():
        return _Strategy([False, True], lambda rng: rng.random() < 0.5)

    def __getattr__(self, name):
        # Unknown strategy kind: given() sees the non-_Strategy value and
        # degrades that one test to a reasoned skip.
        return lambda *a, **kw: None


st = _St()

_RANDOM_EXAMPLES = 4  # per test, after the two boundary corners


def given(*args, **kwargs):
    """Stand-in @given: runs boundary + seeded random examples."""

    def deco(fn):
        if args or not kwargs or any(
                not isinstance(s, _Strategy) for s in kwargs.values()):
            return pytest.mark.skip(
                reason="hypothesis not installed; strategy not covered by "
                       "the deterministic fallback engine")(fn)
        names = list(kwargs)

        def runner():
            rng = random.Random(zlib.crc32(fn.__name__.encode()))
            cases = [{n: kwargs[n].boundaries[pick] for n in names}
                     for pick in (0, -1)]
            cases += [{n: kwargs[n].sample(rng) for n in names}
                      for _ in range(_RANDOM_EXAMPLES)]
            seen = set()
            for case in cases:
                key = repr(sorted(case.items()))
                if key in seen:
                    continue
                seen.add(key)
                try:
                    fn(**case)
                except Exception as e:
                    raise AssertionError(
                        f"falsifying example: {fn.__name__}({case!r})") from e

        # Deliberately NOT functools.wraps: __wrapped__ would make pytest
        # introspect fn's signature and demand fixtures for B/S/....
        runner.__name__ = fn.__name__
        runner.__doc__ = fn.__doc__
        runner.__module__ = fn.__module__
        return runner

    return deco


@pytest.fixture(scope="session")
def multidev():
    """Run a snippet under N fake CPU devices; returns parsed RESULT json."""

    def run(script: str, ndev: int = 8, timeout: int = 900) -> dict:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        env["JAX_PLATFORMS"] = "cpu"     # fake devices; never take a chip
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             text=True, capture_output=True, timeout=timeout,
                             cwd=REPO)
        for line in out.stdout.splitlines():
            if line.startswith("RESULT:"):
                return json.loads(line[len("RESULT:"):])
        raise AssertionError(
            f"no RESULT line (rc={out.returncode}):\n"
            f"STDOUT:\n{out.stdout[-3000:]}\nSTDERR:\n{out.stderr[-3000:]}")

    return run
