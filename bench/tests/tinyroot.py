"""A copy of the benchmark at CPU-test sizes, in a temporary directory."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

#: the cells' configurations cut to sizes the CPU tests can hold: 4,096
#: stencil rows (two kernel row tiles), 8,192 random rows over 4 ranks
TINY = {"stencil2d-1024": {"grid_side": 64}, "er-s19-ef16": {"scale": 13}}


def make(dst: Path) -> Path:
    """``dst`` holding ``BENCHMARK.json`` and ``bench/`` with tiny configs."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", ".jax_cache", "__pycache__"))
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        path = dst / entry["file"]
        cfg = json.loads(path.read_text())
        cfg.update(TINY[entry["name"]])
        path.write_text(json.dumps(cfg))
    return dst
