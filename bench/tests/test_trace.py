"""The reduction from trace to metrics: interval arithmetic by hand, and a
small trace recorded on one TPU v5e chip (``data/smoke.xplane.pb.gz``,
made by ``record_trace.py``, with the result line that run printed)."""

import gzip
import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_subtract():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == \
        [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.length(tr.subtract([(0, 1)], [(0, 1)])) == 0


def _raw():
    # device 0: a train program [1, 5) with a matmul [1, 3) and an
    # all-reduce [2.5, 4.5); device 1 idle in [1, 2)
    dev0 = {"modules": [("jit_train_step(7)", 1.0, 5.0)],
            "ops": [("fusion.1", 1.0, 3.0), ("all-reduce.2", 2.5, 4.5)]}
    dev1 = {"modules": [("jit_train_step(7)", 2.0, 5.0)],
            "ops": [("fusion.1", 2.0, 5.0)]}
    host = [("bench:window", 0.0, 6.0), ("bench:step", 0.5, 1.5),
            ("bench:batch", 5.0, 6.0)]
    return tr.Raw({0: dev0, 1: dev1}, host)


def test_reduce_by_hand():
    s = tr.reduce(_raw())
    assert s.window_s == 6.0
    # busy: device 0 [1, 4.5) = 3.5, device 1 [2, 5) = 3
    assert s.busy_s == pytest.approx(3.25)
    assert s.module_s(r"^jit_train_step\b") == pytest.approx(3.5)
    assert s.module_s(r"^jit_stats_step\b") is None
    # all-reduce alone on device 0 in [3, 4.5)
    assert s.collective_exposed_s == pytest.approx(0.75)
    assert s.collective_s == pytest.approx(1.0)
    assert s.top_ops[0] == ["jit_train_step/fusion.1", 2.5]
    gaps = dict(s.idle_gaps)
    # a gap goes to the innermost span open where it begins: device 0
    # idle [0, 1) and [4.5, 6) in the window only; device 1 idle [0, 2)
    # in the window and [5, 6) in bench:batch; halved over 2 devices
    assert gaps == pytest.approx({"bench:window": 2.25,
                                  "bench:batch": 0.5})
    assert sum(gaps.values()) == pytest.approx(6.0 - 3.25)


def test_chip_trace_reads_as_the_chip_run_did(tmp_path):
    """The metrics the chip run printed for this trace come out again."""
    import harness

    path = tmp_path / "smoke.xplane.pb"
    with gzip.open(os.path.join(DATA, "smoke.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with open(os.path.join(DATA, "smoke.result.json")) as f:
        res = json.load(f)
    s = tr.reduce(tr.load(str(path)))
    assert s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.window_s == pytest.approx(res["device"]["window_s"])
    assert s.busy_s == pytest.approx(res["device"]["busy_s"])
    import smoke

    # the cell the trace was recorded from (record_trace.py; recorded
    # on the composed-inverse path, whose refresh has the same module
    # name and sizes)
    cell = smoke.smoke_cell("qwen1.5-0.5b.soi1024-exact-every2")
    win = harness.Window(steps=2, tokens=2 * 4 * 32, seconds=0.0, losses=[],
                         stats_calls=1, inv_calls=1, compiles=0)
    reading = harness.make_reading(cell, 1, {"bf16_flops_per_s": 197e12,
                                             "hbm_bytes_per_s": 819e9},
                                   win, 0.0, 0, trace=s)
    for m in cell.per_layer:
        mod = harness.load_module(os.path.join(
            os.path.dirname(os.path.dirname(DATA)), "metrics",
            m["name"] + ".py"))
        assert mod.read(reading) == pytest.approx(
            res["metrics"][m["name"]]["value"]), m["name"]
    # one cadence period: two train steps, one statistics pass and one
    # refresh, each its own program
    assert s.module_count(r"^jit_train_step\b") == 2
    assert s.module_count(r"^jit_stats_step\b") == 1
    assert s.module_count(r"^jit__lambda\b") == 1
    assert s.collective_s == 0
    assert all(not n.split("/")[-1].startswith("%while")
               for n, _ in s.top_ops)
