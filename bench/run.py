"""K-FAC training benchmark on TPU: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints, last on standard output, one JSON line: ``correct``,
``attempted`` (steps in the window), ``failed`` (steps whose loss was not
finite), ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics read from a profiler trace of the window),
``device``, and ``check``: each number compared with the reference beside
its limit, which also close standard error. Exits nonzero, with no
result, when JAX finds no TPU, fewer chips than the cell asks for, or a
device missing from ``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def prepare(workload: str, chips=None):
    """Environment, cell and devices: exits unless JAX runs on enough
    TPU chips of a kind ``bench/peaks.json`` lists."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit("bench: the program (src/repro) is not in this checkout")
    # one fixed cache inside the checkout: only a cell's first run compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # no eviction: a cell's programs outgrow a capped cache
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

    import harness

    cell = harness.load_cell(workload, chips=chips)
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"bench: needs a TPU; JAX runs on "
                 f"{jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < cell.chips:
        sys.exit(f"bench: {cell.name} needs {cell.chips} chips, JAX "
                 f"finds {len(devices)}")
    devices = devices[:cell.chips]
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        sys.exit(f"bench: no peaks for device kind {kind!r} in "
                 f"bench/peaks.json")
    return harness, cell, devices, peaks[kind]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness, cell, devices, peaks = prepare(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T_START, peaks, log=log)
    if args.trace:
        shutil.rmtree(harness.trace_dir(), ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
