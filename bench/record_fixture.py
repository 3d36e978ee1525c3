#!/usr/bin/env python3
"""Record the trace fixture of the reduction's tests on four chips: a
traced run of the ``fft2_16k_p4`` cell at 1024^2, kept as
``bench/testdata/fft2_1024_p4.xplane.pb``.

    python bench/record_fixture.py
"""

import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
FIXTURE = BENCH / "testdata" / "fft2_1024_p4.xplane.pb"


def main() -> int:
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import run

    config = dict(run.resolve("fft2_16k_p4").config, shape=[1024, 1024])
    keep = BENCH / "testdata" / "_recording"
    keep.mkdir(parents=True, exist_ok=True)
    os.environ["BENCH_KEEP_TRACE"] = str(keep)
    try:
        result = run.run_cell("fft2_16k_p4", 1, 3.0, True, config=config)
        shutil.move(str(keep / "fft2_16k_p4-1.xplane.pb"), FIXTURE)
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
