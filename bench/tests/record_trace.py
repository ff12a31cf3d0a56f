"""Record the small trace the reduction's tests read: a smoke-size cell,
traced for one cadence period on one TPU chip, and the result line that
run printed.

    python bench/tests/record_trace.py <out.xplane.pb> <out.result.json>
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main() -> int:
    t0 = time.perf_counter()
    import jax

    import harness
    import smoke

    if jax.default_backend() != "tpu":
        sys.exit("record_trace: needs a TPU")
    cell = smoke.smoke_cell("qwen1.5-0.5b.soi1024-exact-every2")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    res = harness.run(cell, 5, 0.0, True, jax.devices()[:1], t0, peaks)
    path = sorted(glob.glob(os.path.join(harness.trace_dir(), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    shutil.copy(path, sys.argv[1])
    shutil.rmtree(harness.trace_dir(), ignore_errors=True)
    with open(sys.argv[2], "w") as f:
        json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
