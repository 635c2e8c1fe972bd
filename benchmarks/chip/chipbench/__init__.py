"""Chip benchmark of the serving engine: one cell (configuration x traffic
mix) per run, driven from ``BENCHMARK.json`` at the repo root.

Everything that measures lives here and imports nothing from ``src/``
except the system under test: traffic generation (``traffic``), the
window arithmetic (``window``), the reduction of a profiler trace
(``tracereduce``), operation and byte counts with the peak table
(``cost``), and the comparison that decides ``correct`` (``correct``).
"""

import importlib.util
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def load_file(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark directory (a
    metric's reader, a configuration's model), loaded by path under a name
    of its own so it cannot collide with another module."""
    key = f"chipbench_{kind}.{name.replace('-', '_').replace('.', '_')}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH_DIR / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]
