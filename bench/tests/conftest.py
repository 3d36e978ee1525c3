"""The benchmark's own tests run on the CPU, on four virtual devices:

    PYTHONPATH=src python -m pytest bench/tests -q
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
BENCH = Path(__file__).resolve().parents[1]
for path in (str(BENCH), str(BENCH.parent / "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
