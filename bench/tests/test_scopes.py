"""Device time by named scope (``scopes.py``): the name-stack rule and the
decoding by hand, the readers on the trace recorded before the program
named its scopes (``data/smoke.xplane.pb.gz``: they read nothing), and
on one recorded on one TPU v5e chip after (``data/smoke_scoped.xplane.
pb.gz``, made by ``record_trace.py``, with the result line that run
printed)."""

import gzip
import json
import os

import pytest

import harness
import scopes
import smoke
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(DATA)), "metrics")
#: the readers of the named scopes and of the sync span
NEW = ("attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
       "wu_ms_per_step", "fpbp_other_ms_per_step", "su_gram_ms_per_refresh",
       "sync_idle_ms_per_step")
PARTS = ("attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
         "wu_ms_per_step", "fpbp_other_ms_per_step")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("stack, scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn/dot_general:",
     "attn"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/mul:", "mlp"),
    ("jit(train_step)/transpose(jvp(head))/exp:", "head"),
    ("jit(stats_step)/jvp()/while/body/closed_call/attn/soi_gram/"
     "...tib,...tic->...ibc/dot_general:", "soi_gram"),
    ("jit(<lambda>)/inv/jit(inv)/lu:", "inv"),
    ("jit(train_step)/wu/...iajc,...jcd->...iajd/dot_general:", "wu"),
    ("jit(train_step)/jit(inv)/lu:", None),
    ("jit(train_step)/jvp(jit(take_along_axis))/gather:", None),
    ("state.kfac.inverses['layers/attn/wq']['A_inv']:", None),
    ("jit(train_step)/attn/ge;jit(train_step)/jvp()", "attn"),
    ("", None),
])
def test_scope_of(stack, scope):
    assert scopes.scope_of(stack) == scope


def _xspace(path):
    """A trace by hand: the window [1, 5) s on the host; on the device a
    train program with attention [0.5, 1.5), WU [2, 3), an unscoped
    copy [3, 4) and a loop that encloses them, and a refresh [4, 6)."""
    space = scopes._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "bench:window"
    line = host.lines.add(name="main", timestamp_ns=1_000_000_000)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=4 * 10 ** 12)
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "program_id")):
        dev.stat_metadata.add(key=key).value.name = name
    ops = {10: ("jit_train_step(7)", None, None),
           11: ("jit__lambda(8)", None, None),
           20: ("%fusion.1 = f32[] fusion()", 7,
                "jit(train_step)/jvp()/while/body/closed_call/attn/"
                "dot_general:"),
           21: ("%fusion.2 = f32[] fusion()", 7,
                "jit(train_step)/wu/mul:"),
           22: ("%copy.3 = f32[] copy()", 7, None),
           23: ("%while.4 = f32[] while()", 7,
                "jit(train_step)/jvp()/while:"),
           24: ("%fusion.5 = f32[] fusion()", 8,
                "jit(<lambda>)/inv/jit(inv)/lu:")}
    for key, (name, pid, stack) in ops.items():
        md = dev.event_metadata.add(key=key).value
        md.name = name
        if pid is not None:
            md.stats.add(metadata_id=2, uint64_value=pid)
        if stack is not None:
            md.stats.add(metadata_id=1, str_value=stack)
    s = 10 ** 12
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    mods.events.add(metadata_id=10, offset_ps=s // 2, duration_ps=7 * s // 2)
    mods.events.add(metadata_id=11, offset_ps=4 * s, duration_ps=2 * s)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for key, a, b in ((20, 0.5, 1.5), (21, 2, 3), (22, 3, 4), (23, 0.5, 4),
                      (24, 4, 6)):
        line.events.add(metadata_id=key, offset_ps=int(a * s),
                        duration_ps=int((b - a) * s))
    with gzip.open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_load_by_hand(tmp_path):
    path = str(tmp_path / "hand.xplane.pb.gz")
    _xspace(path)
    got = scopes.load(path)
    # attention clipped to the window: [1, 1.5); the loop and the copy
    # are charged to nothing; the refresh clipped to [4, 5)
    assert got == {"jit_train_step": {"attn": pytest.approx(0.5),
                                      "wu": pytest.approx(1.0)},
                   "jit__lambda": {"inv": pytest.approx(1.0)}}


def _reading(trace_file, tmp_path, name):
    path = tmp_path / (name + ".xplane.pb")
    with gzip.open(os.path.join(DATA, trace_file)) as f:
        path.write_bytes(f.read())
    cell = smoke.smoke_cell("qwen1.5-0.5b.soi1024-exact-every2")
    # one cadence period: two train steps, one statistics pass, one
    # refresh (record_trace.py)
    win = harness.Window(steps=2, tokens=2 * 4 * 32, seconds=0.0, losses=[],
                         stats_calls=1, inv_calls=1, compiles=0)
    r = harness.make_reading(cell, 1, PEAKS, win, 0.0, 0,
                             trace=tr.reduce(tr.load(str(path))))
    r.trace_path = str(path)
    return r


def _read(name, reading):
    return harness.load_module(os.path.join(METRICS, name + ".py")).read(
        reading)


def test_new_readers_read_nothing_on_a_trace_without_scopes(tmp_path):
    r = _reading("smoke.xplane.pb.gz", tmp_path, "smoke")
    assert scopes.split(r) is None
    for name in NEW:
        assert _read(name, r) is None, name
    # without a trace at all, or with one that does not decode
    r.__dict__.pop("_scopes")
    r.trace_path = str(tmp_path / "missing.xplane.pb")
    assert _read("attn_ms_per_step", r) is None
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\xff" * 64)
    r.__dict__.pop("_scopes")
    r.trace_path = str(bad)
    assert _read("attn_ms_per_step", r) is None


def test_scoped_chip_trace_reads_as_the_chip_run_did(tmp_path):
    """Every per-layer metric the chip run printed for this trace comes
    out again; the new ones read something, and the train program's
    parts add up to it."""
    r = _reading("smoke_scoped.xplane.pb.gz", tmp_path, "smoke_scoped")
    with open(os.path.join(DATA, "smoke_scoped.result.json")) as f:
        res = json.load(f)
    assert r.trace.window_s == pytest.approx(res["device"]["window_s"])
    for m in r.cell.per_layer:
        v = _read(m["name"], r)
        assert v is not None, m["name"]
        assert v == pytest.approx(res["metrics"][m["name"]]["value"]), \
            m["name"]
    parts = sum(_read(name, r) for name in PARTS)
    assert parts == pytest.approx(_read("fpbp_wu_ms_per_step", r))
    assert all(_read(name, r) > 0 for name in NEW)
    # the refresh program keeps its name; its work is under "inv"
    assert r.trace.module_count(r"^jit__lambda\b") == 1
    assert scopes.split(r)["jit__lambda"]["inv"] > 0
