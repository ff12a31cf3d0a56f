"""repro.obs telemetry spine: registry semantics, batched device taps
(one device_get per drain; tapped steps bitwise-identical), span
nesting + Chrome-trace schema, exporter round-trips, and the --obs CLI
surfaces on both launchers.

The multidevice-marked test rides the same subprocess pattern as
``test_dist_solve_multidev``: tap drains must behave identically when
the tapped metrics are produced on a >1-device mesh.
"""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import (
    NULL,
    Counter,
    Histogram,
    JsonlWriter,
    MetricsRegistry,
    Observability,
    TapBuffer,
    Tracer,
    console_summary,
    from_args,
    prometheus_text,
    with_taps,
)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value() == 3.5          # failed inc left no trace


def test_counter_label_isolation():
    c = Counter("req_total")
    c.inc(reason="eos")
    c.inc(3, reason="length")
    c.inc(reason="eos")
    assert c.value(reason="eos") == 2
    assert c.value(reason="length") == 3
    assert c.value(reason="nope") == 0
    rows = {tuple(sorted(r["labels"].items())): r["value"]
            for r in c._sample_rows()}
    assert rows[(("reason", "eos"),)] == 2


def test_gauge_last_write_wins():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(4)
    g.set(2)
    g.inc()
    assert g.value() == 3
    assert g.value(shard="a") is None
    g.set(9, shard="a")
    assert g.value(shard="a") == 9
    assert g.value() == 3            # labelless series untouched


def test_histogram_bucket_edges_le_semantics():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (1.0, 1.5, 2.0, 4.0, 5.0, 100.0):
        h.observe(v)
    row = h._sample_rows()[0]
    # cumulative le semantics: le=1 covers {1.0}; le=2 adds {1.5, 2.0};
    # le=4 adds {4.0}; +Inf adds {5.0, 100.0}
    assert row["buckets"]["1.0"] == 1
    assert row["buckets"]["2.0"] == 3
    assert row["buckets"]["4.0"] == 4
    assert row["buckets"]["+Inf"] == 6
    assert row["count"] == 6
    assert row["sum"] == pytest.approx(113.5)


def test_histogram_quantile_and_empty():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
    assert math.isnan(h.quantile(0.5))
    for _ in range(10):
        h.observe(1.5)
    q = h.quantile(0.5)
    assert 1.0 <= q <= 2.0           # interpolated inside its bucket
    h2 = Histogram("big", buckets=(1.0,))
    h2.observe(50.0)                 # +Inf bucket -> last finite edge
    assert h2.quantile(0.99) == 1.0


def test_registry_get_or_create_and_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("n")
    assert reg.counter("n") is a     # idempotent handle
    with pytest.raises(TypeError):
        reg.gauge("n")
    reg.histogram("h", buckets=(1, 2))
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1, 2, 3))
    assert "n" in reg and len(reg) == 2
    assert reg.names() == ["h", "n"]


def test_registered_but_untouched_counter_exports_zero():
    reg = MetricsRegistry()
    reg.counter("quiet_total", "never incremented")
    snap = reg.snapshot()
    assert snap[0]["samples"] == [{"labels": {}, "value": 0.0}]
    assert "quiet_total 0" in prometheus_text(reg)


# ---------------------------------------------------------------------------
# device taps
# ---------------------------------------------------------------------------

def test_tapbuffer_single_device_get_per_drain(monkeypatch):
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    buf = TapBuffer()
    expect = {}
    for step in range(5):
        m = {"loss": jnp.asarray(step * 1.5),
             "gnorm": jnp.asarray(step + 0.25),
             "aux": jnp.asarray(step, jnp.int32)}
        expect[step] = {k: float(v) for k, v in m.items()}
        buf.push(step, m)
    calls.clear()                    # float() above also syncs; ignore
    assert len(buf) == 5
    rows = buf.drain()
    assert len(calls) == 1           # ONE batched transfer for 15 scalars
    assert len(buf) == 0 and buf.n_drains == 1
    assert dict(rows) == expect
    assert buf.drain() == [] and buf.n_drains == 1   # empty: no sync


def test_tapbuffer_clear_drops_without_reading(monkeypatch):
    def boom(x):
        raise AssertionError("clear must not touch the device")

    buf = TapBuffer()
    buf.push(0, {"m": jnp.asarray(1.0)})
    monkeypatch.setattr(jax, "device_get", boom)
    buf.clear()
    assert len(buf) == 0
    assert buf.drain() == []         # nothing buffered -> no device_get


def test_with_taps_bitwise_parity():
    def step(state, batch):
        w = state["w"] + batch.sum(axis=0)
        return {"w": w, "t": state["t"] + 1}, {"loss": (w * w).sum()}

    taps = {"w_norm": lambda st, m: jnp.sqrt((st["w"] ** 2).sum()),
            "loss_sq": lambda st, m: m["loss"] ** 2}
    base = jax.jit(step)
    tapped = jax.jit(with_taps(step, taps))
    state0 = {"w": jnp.arange(8, dtype=jnp.float32) / 7.0,
              "t": jnp.asarray(0, jnp.int32)}
    batch = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    s_base, m_base = base(state0, batch)
    s_tap, m_tap = tapped(state0, batch)
    for a, b in zip(jax.tree.leaves(s_base), jax.tree.leaves(s_tap)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m_tap["loss"]) == float(m_base["loss"])
    assert set(m_tap) == {"loss", "w_norm", "loss_sq"}
    assert float(m_tap["w_norm"]) == pytest.approx(
        float(jnp.sqrt((s_base["w"] ** 2).sum())))


def test_with_taps_collision_raises():
    def step(state, batch):
        return state, {"loss": jnp.asarray(0.0)}

    tapped = with_taps(step, {"loss": lambda st, m: m["loss"]})
    with pytest.raises(ValueError, match="collides"):
        tapped({}, jnp.zeros(1))


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_schema():
    tr = Tracer()
    with tr.span("outer", args={"step": 1}):
        with tr.span("inner"):
            pass
    tr.instant("marker", args={"k": 2})
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["inner", "outer", "marker"]
    inner, outer, marker = evs
    for e in (inner, outer):
        assert e["ph"] == "X"
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
    assert marker["ph"] == "i"
    # nesting: inner's [ts, ts+dur] lies inside outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    json.dumps(doc)                  # serializable as-is


def test_span_fence_blocks_and_cat_defaults():
    tr = Tracer()
    x = jnp.ones((64, 64))
    with tr.span("dispatch_only"):
        y = x @ x
    with tr.span("fenced", fence=lambda: y):
        y = y @ x
    evs = tr.to_chrome()["traceEvents"]
    assert evs[0]["cat"] == "dispatch"
    assert evs[1]["cat"] == "compute"


def test_span_error_recorded_and_reraised():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom", fence=lambda: 1 / 0):   # fence skipped
            raise RuntimeError("inner failure")
    ev = tr.to_chrome()["traceEvents"][0]
    assert ev["name"] == "boom"
    assert ev["args"]["error"] == "RuntimeError"


def test_tracer_bounded_and_disabled():
    tr = Tracer(max_events=2)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 2 and tr.n_dropped == 3
    assert tr.to_chrome()["otherData"]["n_dropped"] == 3
    off = Tracer(enabled=False)
    with off.span("x"):
        pass
    off.instant("y")
    assert len(off) == 0


def test_tracer_save_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("a"):
        pass
    p = tr.save(str(tmp_path / "trace.json"))
    doc = json.load(open(p))
    assert doc["traceEvents"][0]["name"] == "a"


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_jsonl_rotation_roundtrip(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with JsonlWriter(path, max_bytes=200) as w:
        for i in range(12):
            w.write({"kind": "step", "i": i})
    assert os.path.exists(path + ".1")
    got = []
    for p in (path + ".1", path):
        got += [json.loads(line)["i"] for line in open(p)]
    # single-generation rotation: the tail of the stream is intact and
    # in order (older overwritten generations may be gone)
    assert got == sorted(got)
    assert got[-1] == 11
    w2 = JsonlWriter(path)           # reopen appends, not truncates
    w2.write({"kind": "late", "i": 12})
    w2.close()
    assert json.loads(open(path).readlines()[-1])["i"] == 12


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(3, mode="paged")
    h = reg.histogram("lat_s", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = prometheus_text(reg)
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert 'req_total{mode="paged"} 3' in text
    assert '# TYPE lat_s histogram' in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert "lat_s_count 3" in text
    assert "lat_s_sum" in text


def test_console_summary_renders():
    reg = MetricsRegistry()
    reg.counter("n_total").inc(7)
    reg.histogram("t_s", buckets=(1.0, 2.0)).observe(1.5, phase="wu")
    out = console_summary(reg, title="t")
    assert "== t ==" in out
    assert "n_total" in out and "7" in out
    assert 'phase="wu"' in out and "p99=" in out


# ---------------------------------------------------------------------------
# facade
# ---------------------------------------------------------------------------

def test_null_obs_is_inert(tmp_path):
    assert not NULL.enabled
    c = NULL.counter("x_total")      # handles still work (never exported)
    c.inc()
    with NULL.span("s"):
        pass
    NULL.event("e", a=1)
    NULL.write({"kind": "r"})
    assert NULL.flush() == {}
    assert len(NULL.tracer) == 0
    assert list(tmp_path.iterdir()) == []


def test_observability_flush_writes_all_artifacts(tmp_path):
    o = Observability(out_dir=str(tmp_path / "obs"))
    o.counter("a_total").inc()
    with o.span("s"):
        pass
    o.event("ev", x=1)
    paths = o.flush(summary={"kind": "run_summary", "n": 3})
    o.close()
    assert set(paths) == {"jsonl", "prom", "trace"}
    lines = [json.loads(l) for l in open(paths["jsonl"])]
    assert lines[0]["kind"] == "ev"
    assert lines[-1] == {**lines[-1], "kind": "run_summary",
                         "schema": 1, "n": 3}
    assert "a_total 1" in open(paths["prom"]).read()
    names = [e["name"] for e in
             json.load(open(paths["trace"]))["traceEvents"]]
    assert names == ["s", "ev"]


def test_from_args():
    class A:
        obs = False
        obs_dir = None

    assert from_args(A()) is NULL
    a = A()
    a.obs = True
    o = from_args(a)
    assert o.enabled and o.out_dir is None
    b = A()
    b.obs_dir = "/tmp/nonexistent-not-created-until-init"


# ---------------------------------------------------------------------------
# train loop integration: batched drain + full per-step history
# ---------------------------------------------------------------------------

class _ToyProgram:
    def init_state(self, mesh):
        return {"w": jnp.zeros((4,))}

    def make_step(self, mesh):
        @jax.jit
        def step(state, batch):
            s = jnp.sum(batch["tokens"][:, 0]).astype(jnp.float32)
            return {"w": state["w"] + s}, {"loss": s, "aux": s * 2}
        return step

    def state_sharding(self, mesh):
        return lambda key: None


def _run_loop(tmp_path, obs=None, total=12, log_every=5):
    from repro.data import SyntheticTokens
    from repro.runtime import LoopConfig, TrainLoop

    ds = SyntheticTokens(vocab=97, seq_len=8, global_batch=4, seed=3)
    loop = TrainLoop(
        LoopConfig(total_steps=total, ckpt_dir=str(tmp_path / "ck"),
                   ckpt_every=50, log_every=log_every),
        _ToyProgram(), ds, obs=obs)
    return loop, loop.run()


def test_loop_history_records_every_step(tmp_path):
    loop, summary = _run_loop(tmp_path)
    # the old loop sampled the history at log_every cadence; now every
    # step's scalars are retained, formatting alone is throttled
    assert [h["step"] for h in summary["history"]] == list(range(12))
    assert all({"loss", "aux"} <= set(h) for h in summary["history"])
    # drains happen once per log window (+ the tail), not per step
    assert 1 <= loop._taps.n_drains <= 4


def test_loop_obs_on_matches_off(tmp_path):
    _, off = _run_loop(tmp_path / "a", obs=None)
    obs = Observability(out_dir=str(tmp_path / "obsout"))
    loop, on = _run_loop(tmp_path / "b", obs=obs)
    assert [h["loss"] for h in on["history"]] == \
        [h["loss"] for h in off["history"]]
    assert obs.counter("train_steps_total").value() == 12
    # every step row also landed in the JSONL stream
    paths = obs.flush()
    obs.close()
    rows = [json.loads(l) for l in open(paths["jsonl"])]
    assert sum(r["kind"] == "train_step" for r in rows) == 12


# ---------------------------------------------------------------------------
# CLI smokes
# ---------------------------------------------------------------------------

def _prom_names(path):
    names = set()
    for line in open(path):
        if line.startswith("#") or not line.strip():
            continue
        name = line.split("{")[0].split(" ")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                name = name[: -len(suffix)]
        names.add(name)
    return names


def test_train_cli_obs_smoke(tmp_path):
    from repro.launch.train import main

    obs_dir = tmp_path / "obs"
    summary = main([
        "--arch", "qwen1.5-0.5b", "--smoke", "--steps", "4",
        "--batch", "2", "--seq", "16", "--smw",
        "--ckpt-dir", str(tmp_path / "ck"),
        "--obs-dir", str(obs_dir)])
    assert summary["steps"] == 4
    assert len(summary["history"]) == 4      # every step recorded
    names = _prom_names(obs_dir / "metrics.prom")
    need = {"train_steps_total", "train_step_wall_s", "train_loss", "solve_smw_drift", "solve_smw_fallback_total",
            "runtime_remesh_total"}
    assert need <= names, f"missing {need - names}"
    doc = json.load(open(obs_dir / "trace.json"))
    assert any(e["name"].startswith("phase:")
               for e in doc["traceEvents"])
    kinds = [json.loads(l)["kind"]
             for l in open(obs_dir / "events.jsonl")]
    assert kinds.count("train_step") == 4
    assert "train_summary" in kinds


def test_serve_cli_obs_smoke(tmp_path):
    from repro.launch.serve import main

    obs_dir = tmp_path / "obs"
    summary, done = main([
        "--arch", "qwen2-0.5b", "--smoke", "--paged", "--prefix-cache",
        "--requests", "6", "--max-slots", "2", "--prompt-len", "16",
        "--gen", "6", "--kv-blocks", "6",
        "--obs-dir", str(obs_dir)])
    assert summary["schema"] == 1
    assert summary["kind"] == "serve_summary"
    assert "scheduler" in summary and "resident_bytes" in summary
    names = _prom_names(obs_dir / "metrics.prom")
    need = {"serve_ttft_s", "serve_tpot_s", "serve_queue_depth",
            "serve_slot_occupancy", "serve_free_blocks",
            "serve_prefix_hits_total", "serve_preemptions_total",
            "serve_requests_total"}
    assert need <= names, f"missing {need - names}"
    rows = [json.loads(l) for l in open(obs_dir / "events.jsonl")]
    fin = [r for r in rows if r["kind"] == "request_finished"]
    assert len(fin) == 6
    assert rows[-1]["kind"] == "serve_summary"
    assert rows[-1]["schema"] == 1
    doc = json.load(open(obs_dir / "trace.json"))
    assert any(e["name"] == "decode_chunk" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# multidevice: tap drain over a sharded step (subprocess pattern)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_tap_drain_multidevice_parity():
    """Tapped metrics produced by a sharded program drain to the same
    host floats a per-metric blocking readback would give, and the
    tapped step's (sharded) state is bitwise the untapped one."""
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data"))

    def step(state, batch):
        w = state + batch.sum(axis=0)
        return w, {"loss": (w * w).sum(), "mean": w.mean()}

    tapped = jax.jit(
        with_taps(step, {"norm": lambda st, m: jnp.sqrt(
            (st * st).sum())}))
    base = jax.jit(step)
    state = jax.device_put(jnp.arange(8, dtype=jnp.float32), sh)
    batch = jax.device_put(
        jnp.ones((2, 8), jnp.float32), jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, "data")))

    buf = TapBuffer()
    s_t = state
    s_b = state
    expect = []
    for i in range(3):
        s_b, m_b = base(s_b, batch)
        s_t, m_t = tapped(s_t, batch)
        expect.append({k: float(v) for k, v in m_b.items()})
        buf.push(i, m_t)
    np.testing.assert_array_equal(np.asarray(s_b), np.asarray(s_t))
    rows = buf.drain()
    assert buf.n_drains == 1
    for (tag, m), e, i in zip(rows, expect, range(3)):
        assert tag == i
        assert m["loss"] == e["loss"] and m["mean"] == e["mean"]
        assert m["norm"] == pytest.approx(math.sqrt(m["loss"]))


def test_multidevice_subprocess_smoke(multidev_runner):
    res = multidev_runner(["-m", "multidevice", "tests/test_obs.py"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "1 passed" in res.stdout
