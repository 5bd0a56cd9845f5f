"""Shared benchmark helpers: timing, CSV output, subprocess devices."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time
from typing import Callable, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
ARTIFACTS = os.path.join(REPO, "artifacts")


def time_fn(fn: Callable, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time per call in microseconds."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


def run_with_devices(code: str, devices: int, timeout: int = 900) -> str:
    """Run ``code`` in a child on ``devices`` forced CPU host devices.

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``), so it never
    competes for an accelerator this process may hold; whatever it times is
    a host-device time, and this function says so on stdout.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    print(f"# host-device run: {devices} forced CPU devices, not accelerator times")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-3000:]}")
    return proc.stdout
