"""Record the trace the attention kernel's reader is tested on: the smoke
cell of ``record_trace.py`` widened to head dim 64 (hidden 256 on 4
heads) and run at 256 tokens, where attention takes the flash kernel on
the chip; traced for one cadence period on one TPU chip, with the result
line that run printed. Record it from an empty compile cache: the
cache's key leaves op metadata out.

    python bench/tests/record_kernel_trace.py <out.xplane.pb> <out.result.json>
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

import smoke  # noqa: E402

NAME = "qwen1.5-0.5b.soi1024-exact-every2"
#: the widths and length at which the kernel engages: head dim 64, and a
#: sequence that is a whole number of its 128-wide blocks
HEAD_DIM, HEADS, SEQ = 64, 4, 256


def kernel_cell():
    cell = smoke.smoke_cell(NAME)
    c = cell.config
    c.update(hidden_size=HEADS * HEAD_DIM, num_attention_heads=HEADS,
             num_key_value_heads=HEADS)
    c["program"].update(d_model=HEADS * HEAD_DIM, n_heads=HEADS,
                        n_kv_heads=HEADS, head_dim=HEAD_DIM)
    cell.traffic.update(seq=SEQ)
    return cell


def main() -> int:
    t0 = time.perf_counter()
    import jax

    import harness

    if jax.default_backend() != "tpu":
        sys.exit("record_kernel_trace: needs a TPU")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    res = harness.run(kernel_cell(), 5, 0.0, True, jax.devices()[:1], t0,
                      peaks)
    path = sorted(glob.glob(os.path.join(harness.trace_dir(), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    shutil.copy(path, sys.argv[1])
    shutil.rmtree(harness.trace_dir(), ignore_errors=True)
    with open(sys.argv[2], "w") as f:
        json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
