"""Multi-host test harness: relaunch a test module on N forced devices.

Device count locks at first jax init, so a test that needs a real multi-
device mesh (shard_map collectives crossing >1 device) cannot run in the
main pytest process.  The pattern (generalizing tests/test_moe_shardmap.py):

* the OUTER test — collected in the normal suite — calls
  :func:`relaunch_in_worker` on its own file with a ``-k`` selector,
* the WORKER tests — named so the selector picks them up — are skipped in
  the main process (:func:`in_worker` is False there) and run for real in
  the subprocess, where ``XLA_FLAGS=--xla_force_host_platform_device_count``
  was exported before python started.

CI also runs the worker selection directly as its own job step (exporting
``REPRO_MULTIHOST_ACTIVE=1`` and the XLA flag), so multi-device failures
surface with full pytest reporting, not just a subprocess returncode.
"""

from __future__ import annotations

import os
import subprocess
import sys

ENV_FLAG = "REPRO_MULTIHOST_ACTIVE"


def in_worker() -> bool:
    """True inside the forced-device subprocess (or the CI multihost step)."""
    return bool(os.environ.get(ENV_FLAG))


def relaunch_in_worker(test_file: str, n_devices: int = 8,
                       select: str | None = None,
                       timeout: int = 540) -> subprocess.CompletedProcess:
    """Re-run ``test_file`` under pytest with ``n_devices`` forced host
    devices; returns the completed process (caller asserts on returncode)."""
    env = dict(os.environ)
    env[ENV_FLAG] = "1"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices}")
    env["JAX_PLATFORMS"] = "cpu"     # forced host devices; never the chip
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "pytest", "-x", "-q", test_file]
    if select:
        cmd += ["-k", select]
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, cwd=os.path.dirname(src))
