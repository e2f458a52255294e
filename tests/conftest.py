"""Shared fixtures + the multi-device subprocess harness.  NOTE: no

XLA_FLAGS here — smoke tests and benches must see 1 device (only
launch/dryrun.py forces 512 host devices, and the multi-device tests
spawn subprocesses that set their own flags)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, SRC)

def run_py(code: str, devices: int = 8) -> str:
    """Run ``code`` in a fresh interpreter with ``devices`` forced host
    devices (the count must be fixed before jax initialises)."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


@pytest.fixture(scope="session", autouse=True)
def _checked_store():
    """``REPRO_CHECKED_STORE=1`` runs the whole session with every
    ``StateStore`` operation sanitized (key shape vs the KeySchema,
    write-after-publish, read-before-write) — see
    repro.analysis.checked_store.  smoke.sh runs the store/transport
    shards under the flag; any suite must stay green with it on."""
    if os.environ.get("REPRO_CHECKED_STORE") != "1":
        yield None
        return
    from repro.analysis.checked_store import StoreSanitizer
    with StoreSanitizer() as sanitizer:
        yield sanitizer
