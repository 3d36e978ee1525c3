"""Benchmark utilities: wall-clock timing + subprocess multi-device runs.

The container has ONE real CPU device; collective-strategy benchmarks run
in subprocesses with --xla_force_host_platform_device_count (host devices
talk over memcpy, so *relative* strategy overheads -- message count,
fusion, per-chunk dispatch -- are visible even without a fabric). The
alpha-beta ICI model (core/comm_model.py) supplies derived v5e columns
next to each measured row.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:  # allow running without PYTHONPATH=src
    sys.path.insert(0, SRC)

# One timing implementation for benchmarks AND the measured planner
# (repro.core.planner owns it; the planner cannot import benchmarks/).
from repro.core.planner import time_fn  # noqa: E402,F401


def run_devices_subprocess(code: str, devices: int, timeout: int = 900) -> str:
    env = dict(os.environ)
    # a CPU rehearsal: the child never reaches for an accelerator, which
    # a parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=timeout, cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{out.stderr[-3000:]}")
    return out.stdout


def csv_row(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.1f},{derived}"
