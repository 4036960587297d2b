"""mpwlint's own test coverage.

One bad/good fixture twin per rule (R1..R5): the bad snippet must fire and
the good twin must stay silent — deleting any rule's implementation breaks
its bad-fixture test.  Layer 2 (S1..S4) is pinned by running the real
verifier against the live planners, plus seeded-violation twins.  The
end-to-end test asserts `src/` is clean with an empty baseline, gating the
pass in tier-1 forever.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.mpwlint.engine import lint_paths  # noqa: E402
from tools.mpwlint.findings import (Finding, is_suppressed,  # noqa: E402
                                    load_baseline, suppressed_rules,
                                    write_baseline)
from tools.mpwlint.rules import RULES, audit_mpw_verbs, build_context  # noqa: E402
from tools.mpwlint import semantic  # noqa: E402


def run_rule(rule_id: str, source: str, relpath: str = "src/repro/core/x.py"):
    ctx = build_context(relpath, textwrap.dedent(source))
    findings = RULES[rule_id](ctx)
    return [f for f in findings if not is_suppressed(f, ctx.lines)]


# -- R1: traced purity --------------------------------------------------------

R1_BAD = """
    import time, jax

    @jax.jit
    def step(x):
        t0 = time.perf_counter()
        return x + t0
"""

R1_GOOD = """
    import time, jax

    @jax.jit
    def step(x):
        return x * 2

    def host_timer():
        return time.perf_counter()
"""


def test_r1_fires_on_host_call_under_jit():
    found = run_rule("R1", R1_BAD)
    assert any(f.rule == "R1" and "time.perf_counter" in f.message
               for f in found)


def test_r1_silent_on_pure_jit_and_host_code():
    assert run_rule("R1", R1_GOOD) == []


def test_r1_fires_on_self_mutation_in_custom_vjp():
    src = """
        import jax

        @jax.custom_vjp
        def hook(self, x):
            self.count += 1
            return x
    """
    found = run_rule("R1", src)
    assert any("self.count" in f.message for f in found)


def test_r1_fires_on_scanned_function():
    src = """
        import jax

        def body(carry, x):
            open("/tmp/log").write("hi")
            return carry, x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
    """
    found = run_rule("R1", src)
    assert any("open" in f.message for f in found)


def test_r1_fires_on_partial_jit_decorator():
    src = """
        import random, jax
        from functools import partial

        @partial(jax.jit, static_argnums=0)
        def step(n, x):
            return x + random.random()
    """
    assert any("random.random" in f.message for f in run_rule("R1", src))


# -- R2: lock discipline ------------------------------------------------------

R2_BAD = """
    import threading

    class Mirror:
        def __init__(self):
            self._lock = threading.Lock()
            self.total = 0
            self._thread = threading.Thread(target=self._run)

        def _run(self):
            self.total += 1

        def reset(self):
            self.total = 0
"""

R2_GOOD = """
    import threading

    class Mirror:
        def __init__(self):
            self._lock = threading.Lock()
            self.total = 0
            self._thread = threading.Thread(target=self._run)

        def _run(self):
            with self._lock:
                self.total += 1

        def reset(self):
            with self._lock:
                self.total = 0
"""


def test_r2_fires_on_unguarded_shared_write():
    found = run_rule("R2", R2_BAD)
    assert any(f.rule == "R2" and "Mirror.total" in f.message for f in found)


def test_r2_silent_when_writes_are_lock_guarded():
    assert run_rule("R2", R2_GOOD) == []


def test_r2_ignores_modules_without_threads_or_locks():
    src = R2_BAD.replace("import threading", "").replace(
        "self._lock = threading.Lock()", "pass").replace(
        "self._thread = threading.Thread(target=self._run)", "pass")
    assert run_rule("R2", src) == []


def test_r2_single_writer_attrs_are_fine():
    src = """
        import threading

        class Worker:
            def __init__(self):
                self.t = None

            def start(self):
                self.t = threading.Thread(target=print)
    """
    # `t` is written in __init__ + one method: that IS two methods, so the
    # post-construction write must be guarded
    assert any("Worker.t" in f.message for f in run_rule("R2", src))
    solo = """
        import threading

        class Worker:
            def start(self):
                self.t = threading.Thread(target=print)
    """
    assert run_rule("R2", solo) == []


# -- R3: typed errors ---------------------------------------------------------

def test_r3_fires_on_bare_assert():
    found = run_rule("R3", "def f(n):\n    assert n > 0\n")
    assert any("bare `assert`" in f.message for f in found)


def test_r3_silent_on_typed_raise():
    src = """
        def f(n):
            if n <= 0:
                raise ValueError(f"n must be > 0, got {n}")
    """
    assert run_rule("R3", src) == []


def test_r3_fires_on_constant_valueerror_in_core():
    src = 'def f(n):\n    raise ValueError("bad value")\n'
    found = run_rule("R3", src, relpath="src/repro/core/x.py")
    assert any("constant message" in f.message for f in found)
    # outside core/ the constant-message check does not apply
    assert run_rule("R3", src, relpath="src/repro/runtime/x.py") == []


# -- R4: telemetry keys -------------------------------------------------------

def test_r4_fires_on_off_grammar_key():
    src = 'def f(tel, key, i):\n    tel.record(f"{key}/leg{i}", 1.0)\n'
    found = run_rule("R4", src)
    assert any("{}/leg{}" in f.message for f in found)


def test_r4_silent_on_documented_grammar():
    src = """
        def f(tel, key, i, leg):
            tel.record(f"{key}/hop{i}:{leg}", 1.0)
            tel.note_plan(f"{key}/bkt{i}", payload_bytes=0)
            tel.record(f"{key}/intra", 1.0)
            tel.record(f"{key}/wan", 1.0)
            tel.record(key, 1.0)
            tel.record("ckpt:interpod", 1.0)
            g(tel_key=f"{key}/bkt{i}")
    """
    assert run_rule("R4", src) == []


def test_r4_checks_tel_key_kwarg():
    src = 'def f(g, key):\n    g(tel_key=f"{key}-oops")\n'
    assert any(f.rule == "R4" for f in run_rule("R4", src))


def test_r4_fires_on_misspelled_incident_kind():
    src = 'def f(log, step, rid):\n    log.add(step, "resheep", f"req{rid}")\n'
    found = run_rule("R4", src)
    assert any("resheep" in f.message for f in found)


def test_r4_silent_on_vocabulary_incident_kinds():
    src = """
        def f(log, step, rid):
            log.add(step, "reship", f"req{rid}")
            log.add(step, "reroute", f"req{rid}")
            log.add(step, "serve_failover", "decode:a->b")
            log.add(step, "degrade", "serve")
            log.add(step, "timeout", f"req{rid}")
            log.add(step, "shed", f"req{rid}")
    """
    assert run_rule("R4", src) == []


def test_r4_ignores_set_add_and_dynamic_kinds():
    src = """
        def f(log, seen, step, kind, rid):
            seen.add(rid)
            log.add(step, kind, f"req{rid}")
    """
    assert run_rule("R4", src) == []


def test_r4_mpw_verb_audit_fires_on_undocumented_verb(tmp_path):
    (tmp_path / "src/repro/core").mkdir(parents=True)
    (tmp_path / "docs").mkdir()
    (tmp_path / "src/repro/core/api.py").write_text(textwrap.dedent("""
        class MPW:
            def Send(self, x):
                return x

            def Mystery(self, x):
                return x
    """))
    (tmp_path / "docs/api.md").write_text("| `Send(x)` | ships x |\n")
    found = audit_mpw_verbs(tmp_path)
    assert [f for f in found if "Mystery" in f.message]
    assert not [f for f in found if "`Send`" in f.message]


def test_r4_mpw_verb_audit_clean_on_this_repo():
    assert audit_mpw_verbs(REPO) == []


# -- R5: core determinism -----------------------------------------------------

def test_r5_fires_on_wall_clock_in_core():
    src = "import time\n\ndef f():\n    return time.monotonic()\n"
    found = run_rule("R5", src, relpath="src/repro/core/x.py")
    assert any("wall-clock" in f.message for f in found)


def test_r5_fires_on_unseeded_rng_in_core():
    src = ("import numpy as np\n\ndef f():\n"
           "    return np.random.default_rng().random()\n")
    found = run_rule("R5", src, relpath="src/repro/core/x.py")
    assert any("RNG" in f.message for f in found)


def test_r5_silent_on_seeded_rng_and_outside_core():
    seeded = ("import numpy as np\n\ndef f(seed):\n"
              "    return np.random.default_rng(seed).random()\n")
    assert run_rule("R5", seeded, relpath="src/repro/core/x.py") == []
    clock = "import time\n\ndef f():\n    return time.monotonic()\n"
    assert run_rule("R5", clock, relpath="src/repro/runtime/x.py") == []


# -- R6: retry loops route through RetryPolicy --------------------------------

R6_BAD = """
    import time

    def fetch(sock):
        while True:
            try:
                return sock.recv()
            except OSError:
                time.sleep(0.5)
                continue
"""

R6_GOOD = """
    from repro.core.retry import RetryPolicy, RetryState

    def fetch(sock):
        retry = RetryState(RetryPolicy(max_attempts=3))
        while True:
            try:
                return sock.recv()
            except OSError:
                if retry.next_delay_s() is None:
                    raise
                continue
"""


def test_r6_fires_on_continue_from_except_in_while():
    found = run_rule("R6", R6_BAD)
    assert any(f.rule == "R6" and "except" in f.message for f in found)


def test_r6_fires_on_sleep_backoff_in_retry_loop():
    found = run_rule("R6", R6_BAD)
    assert any("time.sleep" in f.message for f in found)


def test_r6_silent_when_routed_through_retrypolicy():
    assert run_rule("R6", R6_GOOD) == []


def test_r6_silent_outside_src_and_on_plain_loops():
    # same hand-rolled loop outside src/ (tests, tools) is not our business
    assert run_rule("R6", R6_BAD, relpath="tools/x.py") == []
    # a while loop whose continue is plain control flow, not error-swallowing
    plain = """
        def drain(q):
            while q:
                item = q.pop()
                if item is None:
                    continue
                handle(item)
    """
    assert run_rule("R6", plain) == []
    # a sleep in a poll loop with no try/except is pacing, not retry
    poll = """
        import time

        def wait_for(flag):
            while not flag():
                time.sleep(0.1)
    """
    assert run_rule("R6", poll) == []


def test_r6_continue_in_nested_for_does_not_blame_the_while():
    # the `continue` targets the inner for-loop, which has no try around it
    src = """
        def pump(jobs):
            while jobs:
                try:
                    jobs = refresh(jobs)
                except KeyError:
                    jobs = []
                for j in jobs:
                    if j.done:
                        continue
                    run(j)
    """
    assert run_rule("R6", src) == []


# -- suppressions and baseline ------------------------------------------------

def test_inline_suppression_silences_one_rule():
    src = ("import time\n\ndef f():\n"
           "    return time.monotonic()    # mpwlint: disable=R5\n")
    assert run_rule("R5", src, relpath="src/repro/core/x.py") == []
    assert suppressed_rules("x = 1  # mpwlint: disable=R1,R5") == {"R1", "R5"}
    # a suppression for a different rule does not silence this one
    other = ("import time\n\ndef f():\n"
             "    return time.monotonic()    # mpwlint: disable=R1\n")
    assert run_rule("R5", other, relpath="src/repro/core/x.py") != []


def test_baseline_roundtrip_waives_known_findings(tmp_path):
    f = Finding("R5", "src/repro/core/x.py", 3, "wall-clock read", "fix it")
    baseline = tmp_path / "baseline.json"
    write_baseline(baseline, [f])
    keys = load_baseline(baseline)
    assert f.key in keys
    moved = Finding("R5", "src/repro/core/x.py", 99, "wall-clock read", "")
    assert moved.key in keys        # line moves don't invalidate the waiver
    assert Finding("R5", "src/repro/core/x.py", 3, "other", "").key not in keys


# -- Layer 2: semantic verifier ----------------------------------------------

def test_semantic_chunk_coverage_clean():
    assert semantic.check_chunk_coverage() == []
    assert semantic.check_file_chunk_coverage() == []


def test_semantic_wire_bound_clean():
    assert semantic.check_wire_bound() == []


def test_semantic_routes_clean():
    assert semantic.check_route_soundness() == []


def test_semantic_buckets_clean():
    assert semantic.check_bucket_contracts() == []


def test_semantic_wire_bound_catches_violation(monkeypatch):
    from repro.core import ring as real_ring
    monkeypatch.setattr(real_ring, "wire_bytes_per_pod",
                        lambda payload, world, algo="psum", compress="none":
                        float(payload) * 2.0 * max(1, world))
    assert semantic.check_wire_bound() != []


def test_semantic_routes_catch_dead_hop(monkeypatch):
    # a topology that ignores fail_link() must be caught as a dead hop
    from repro.core import topology as topo_mod
    monkeypatch.setattr(topo_mod.Topology, "fail_link",
                        lambda self, a, b, bidirectional=True: None)
    findings = semantic.check_route_soundness()
    assert any("dead hop" in f.message for f in findings)


# -- end to end ---------------------------------------------------------------

def test_src_is_clean_ast_rules():
    """Layer 1 over the real src/ tree: zero findings, empty baseline."""
    findings = lint_paths(["src"], REPO)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert load_baseline(REPO / "tools/mpwlint/baseline.json") == set()


def test_cli_end_to_end_json_exit_codes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "tools.mpwlint", "src", "--format=json",
         "--no-semantic"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout)
    assert report["count"] == 0

    bad = tmp_path / "bad.py"
    bad.write_text("def f(n):\n    assert n > 0\n")
    out = subprocess.run(
        [sys.executable, "-m", "tools.mpwlint", str(bad), "--format=json",
         "--no-semantic"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert out.returncode == 1
    report = json.loads(out.stdout)
    assert report["count"] == 1
    assert report["findings"][0]["rule"] == "R3"


@pytest.mark.slow
def test_cli_full_run_including_semantic():
    out = subprocess.run(
        [sys.executable, "-m", "tools.mpwlint", "src"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout
