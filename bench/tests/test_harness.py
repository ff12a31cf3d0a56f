"""The harness on the CPU at smoke size: everything a run does but the
look for a chip. An honest run comes out correct; the control (the
reference one precision step lower in the program's place) and each
fault planted under the timed path come out not correct."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

import check
import harness
import smoke

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ONE_CHIP = ("qwen2-0.5b.soi128-exact-every10",
            "qwen1.5-0.5b.soi1024-exact-every2")
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    # the CPU keeps no allocator statistics
    monkeypatch.setattr(harness, "peak_bytes", lambda devices: 1)


def _run(name, faults=(), seed=2 ** 31 + 3):
    cell = smoke.smoke_cell(name)
    return harness.run(cell, seed, 0.5, False, jax.devices()[:1],
                       time.perf_counter(), PEAKS, faults=faults,
                       log=lambda m: None)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_honest_run_is_correct(name):
    res = _run(name)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}
    assert set(res["check"]) == set(check.NAMES)
    json.dumps(res)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(name):
    cell = smoke.smoke_cell(name)
    ctl = harness.Reference(cell, "control").numbers(11, keep=True)
    ref = harness.Reference(cell).numbers(11, ctl.pop("inverses"))
    ok, table = check.verdict(check.compare(ctl, ref), cell.limits)
    assert not ok, table


@pytest.mark.parametrize("fault", ["half_batch", "unchanged"])
@pytest.mark.parametrize("name", ONE_CHIP)
def test_fault_is_not_correct(name, fault):
    assert not _run(name, faults=(fault,))["correct"]


_FOUR = """
import sys, time, json
sys.path[:0] = {path!r}
import jax, harness, smoke
harness.peak_bytes = lambda d: 1
cell = smoke.smoke_cell("qwen2-0.5b.dp4-soi1024-exact-every2", chips=4)
for faults in ((), ("exchange",)):
    r = harness.run(cell, 7, 0.5, False, jax.devices()[:4],
                    time.perf_counter(), {{"bf16_flops_per_s": 1.0,
                    "hbm_bytes_per_s": 1.0}}, faults=faults,
                    log=lambda m: None)
    print(json.dumps([list(faults), r["correct"]]))
"""


def test_four_chip_cell_and_its_exchange_fault():
    """The data-parallel --dist-inv path that a four-chip cell would run
    (not in BENCHMARK.json yet), on four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR.format(path=sys.path[:3])],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = [json.loads(x) for x in out.stdout.splitlines()[-2:]]
    assert got == [[[], True], [["exchange"], False]]


def test_cli_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run_py = os.path.join(os.path.dirname(HERE), "run.py")
    args = ["--workload", ONE_CHIP[0], "--seed", "1", "--seconds", "1",
            "--trace", "0"]
    out = subprocess.run([sys.executable, run_py] + args, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and not out.stdout.strip()
