import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


def run_tiny(root, cell, seed, seconds, fault="", devices=1, timeout=600):
    """Result line of one tiny cell run on the CPU in a child process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-m", "bench.tests.run_tiny", root,
                        cell, str(seed), str(seconds)] + ([fault] if fault
                                                          else []),
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])
