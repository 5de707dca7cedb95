"""Observability stack: MetricsRegistry, FlightRecorder, Perfetto export.

Load-bearing properties:
  1. determinism -- two same-seed device burns with the recorder on emit
     byte-identical event streams (sim-time timestamps, wall durs off);
  2. histogram fidelity -- log2-bucket percentile estimates land within a
     factor of two of exact numpy percentiles by construction;
  3. export schema -- the emitted document is well-formed Chrome
     trace_event JSON (metadata rows, int tids, monotone per-track ts,
     async spans carrying cat + id/id2) and the CLI summarizer reads it;
  4. registry-backed attributes -- the legacy counter reads on the
     resolver are views over registry cells (one source of truth);
  5. the jit guard -- a recorder call reached under jax tracing fails
     loudly; a DISABLED recorder stays inert everywhere, including jit.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from accord_tpu.obs import export
from accord_tpu.obs.metrics import (GLOSSARY, CounterDict, Histogram,
                                    MetricsRegistry)
from accord_tpu.obs.trace import REC, FlightRecorder


@pytest.fixture(autouse=True)
def _recorder_reset():
    """Every test leaves the process-global recorder disabled and empty."""
    yield
    REC.enabled = False
    REC.wall = False
    REC.clear()


# -- recorded burn fixture ----------------------------------------------------

def _record_burn(seed: int = 7, ops: int = 40):
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.sim.burn import run_burn
    from accord_tpu.sim.cluster import ClusterConfig

    resolvers = []

    def factory():
        r = BatchDepsResolver(num_buckets=128, initial_cap=128,
                              max_dispatch=64)
        resolvers.append(r)
        return r

    cfg = ClusterConfig(num_nodes=3, rf=3, deps_resolver_factory=factory,
                        deps_batch_window_ms=4.0, device_latency_ms=10.0)
    REC.clear()
    REC.configure(capacity=1 << 16, wall=False)
    REC.enabled = True
    try:
        report = run_burn(seed, ops=ops, key_count=8, zipf_theta=0.99,
                          max_keys_per_txn=3, concurrency=8,
                          write_ratio=0.7, config=cfg)
    finally:
        REC.enabled = False
    events = REC.events()
    dropped = REC.dropped
    REC.clear()
    assert report.acked + report.failed == ops
    return report, events, dropped, resolvers


@pytest.fixture(scope="module")
def recorded():
    return _record_burn(), _record_burn()


def test_same_seed_traces_byte_identical(recorded):
    (_, e1, d1, _), (_, e2, d2, _) = recorded
    assert d1 == 0 and d2 == 0, "ring overflowed; capacity too small"
    assert len(e1) > 500, "trace suspiciously small for a 40-op burn"
    assert json.dumps(e1, sort_keys=True) == json.dumps(e2, sort_keys=True)


def test_trace_vocabulary_present(recorded):
    _, events, _, _ = recorded[0]
    names = {ev["name"] for ev in events}
    # txn lifecycle, device pipeline, sim network: all tracks populated
    for expect in ("coordinate", "preaccepted", "accepted", "stable",
                   "applied", "dispatch", "window", "stage_host",
                   "preaccept", "encode", "decode", "send", "deliver"):
        assert expect in names, f"no {expect!r} events recorded"


def test_txn_spans_balance(recorded):
    report, events, _, _ = recorded[0]
    begins = sum(1 for e in events
                 if e.get("ph") == "b" and e.get("cat") == "txn")
    ends = sum(1 for e in events
               if e.get("ph") == "e" and e.get("cat") == "txn")
    assert begins == report.acked + report.failed
    assert ends == begins, "coordinations left open at burn end"


def test_registry_latency_histograms(recorded):
    report, _, _, _ = recorded[0]
    snap = report.registry.snapshot()
    for name in ("txn.commit_latency_us", "txn.apply_latency_us"):
        h = snap[name]
        assert h["count"] == report.acked
        assert 0 < h["p50"] <= h["p95"] <= h["p99"] <= h["max"]
    assert snap["txn.started"] >= report.acked


def test_resolver_snapshot_is_registry_backed(recorded):
    _, _, _, resolvers = recorded[0]
    assert resolvers, "device factory never ran"
    for r in resolvers:
        snap = r.snapshot()
        # the legacy attribute reads are descriptor views over the same
        # registry cells the snapshot serializes
        assert snap["resolver.dispatches"] == r.dispatches
        assert snap["resolver.subjects"] == r.subjects
        for b in ("stage", "decode", "outside"):
            name = f"resolver.drained_{b}_s"
            assert snap[name] == r.metrics.timer(name).total >= 0.0
        assert snap["resolver.upload_bytes"] == r.upload_bytes
        # and nothing escapes the documented vocabulary
        unknown = set(snap) - set(GLOSSARY)
        assert not unknown, f"undocumented metrics: {sorted(unknown)}"


# -- export schema ------------------------------------------------------------

def test_export_schema(recorded):
    _, events, _, _ = recorded[0]
    doc = export.to_chrome_trace(events)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    pids = {e["pid"] for e in evs}
    named = {e["pid"] for e in evs if e.get("ph") == "M"
             and e["name"] == "process_name"}
    assert named == pids, "every node process must be named"
    last_ts: dict = {}
    for e in evs:
        assert isinstance(e["tid"], int), "string tids must be numbered"
        if e.get("ph") == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last_ts.get(key, 0), "per-track ts not monotone"
        last_ts[key] = e["ts"]
        if e["ph"] == "X":
            assert "dur" in e
        elif e["ph"] in ("b", "e"):
            assert "cat" in e and ("id" in e or "id2" in e)
        elif e["ph"] == "i":
            assert e["s"] == "t"
        elif e["ph"] == "f":
            assert e["bp"] == "e"


def test_export_summarize_and_cli(tmp_path, capsys, recorded):
    _, events, _, _ = recorded[0]
    path = tmp_path / "trace.json"
    doc = export.write_trace(str(path), events)
    summary = export.summarize(doc)
    # every device window closes (harvest fired for every dispatch) and
    # every coordination closes (applied-quorum or failure)
    assert summary["unclosed_async"] == 0
    assert summary["spans"]["window"]["count"] > 0
    assert summary["instants"]["send"] == summary["instants"]["deliver"]
    assert export.main(["--summarize", str(path)]) == 0
    out = capsys.readouterr().out
    assert "window" in out and "coordinate" in out


# -- histogram fidelity -------------------------------------------------------

def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=3.0, sigma=1.5, size=5000)
    h = Histogram("t")
    for v in samples:
        h.observe(float(v))
    assert h.count == 5000
    assert h.mean == pytest.approx(float(np.mean(samples)))
    for p in (50, 95, 99):
        exact = float(np.percentile(samples, p))
        est = h.percentile(p)
        assert exact / 2 <= est <= exact * 2, \
            f"p{p}: est {est} vs exact {exact}"
    assert h.percentile(100) == h.max


def test_histogram_zeros_and_merge():
    a = Histogram("a")
    for v in (0.0, 0.0, 5.0, 9.0):
        a.observe(v)
    assert a.percentile(25) == 0.0
    b = Histogram("b")
    for v in (100.0, 200.0):
        b.observe(v)
    a.merge_from(b)
    assert a.count == 6
    assert a.max == 200.0
    assert a.percentile(99) <= 200.0


# -- registry -----------------------------------------------------------------

def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError):
        reg.timer("x")


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(2)
    b.counter("c").inc(3)
    a.timer("t").add(0.5)
    b.timer("t").add(0.25)
    b.gauge("g").set(7.0)
    a.merge_from(b)
    assert a.counter("c").value == 5
    assert a.timer("t").total == pytest.approx(0.75)
    assert a.gauge("g").value == 7.0


def test_counterdict_view():
    reg = MetricsRegistry()
    d = CounterDict(reg, "up", ("full", "ts"))
    d["full"] += 10
    d["ts"] = 3
    assert d == {"full": 10, "ts": 3}
    assert reg.counter("up.full").value == 10
    assert sorted(d) == ["full", "ts"]
    assert d.get("missing", 42) == 42


def test_descriptors_write_through():
    from accord_tpu.ops.resolver import BatchDepsResolver
    r = BatchDepsResolver(num_buckets=128, initial_cap=128)
    r.dispatches += 3
    r.preaccept_s += 0.5
    assert r.metrics.counter("resolver.dispatches").value == 3
    assert r.dispatches == 3
    assert r.metrics.timer("resolver.preaccept_s").total == \
        pytest.approx(0.5)


# -- recorder mechanics -------------------------------------------------------

def test_ring_bounded_and_disabled_noop():
    rec = FlightRecorder(capacity=16)
    for i in range(100):
        rec.instant(0, "t", "x", i)
    assert len(rec) == 0, "disabled recorder must not record"
    rec.enabled = True
    for i in range(100):
        rec.instant(0, "t", "x", i)
    assert len(rec) == 16
    assert rec.dropped == 84
    assert rec.events()[0]["ts"] == 84  # oldest dropped
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_wall_flag_gates_durations():
    rec = FlightRecorder()
    rec.enabled = True
    rec.complete(0, "t", "x", 10, dur=5.5)
    assert rec.events()[0]["dur"] == 0, "wall off: dur must stay 0"
    rec.configure(wall=True)
    rec.complete(0, "t", "x", 20, dur=5.5)
    assert rec.events()[1]["dur"] == 5.5


def test_recorder_rejects_jit_traced_calls():
    import jax
    import jax.numpy as jnp

    REC.configure(capacity=256)
    REC.enabled = True

    @jax.jit
    def bad(x):
        REC.instant(0, "t", "inside-jit", 0)
        return x + 1

    with pytest.raises(RuntimeError, match="jax tracing"):
        bad(jnp.int32(1))

    # disabled, the same call is a no-op even under tracing
    REC.enabled = False
    REC.clear()

    @jax.jit
    def fine(x):
        REC.instant(0, "t", "inside-jit", 0)
        return x * 2

    assert int(fine(jnp.int32(2))) == 4
    assert len(REC) == 0


# -- node / maelstrom integration ---------------------------------------------

def test_node_shutdown_emits_snapshot():
    from accord_tpu.maelstrom.runner import Runner

    r = Runner(seed=3)
    stats = r.run_random_workload(ops=12)
    assert stats["txn_ok"] > 0 and stats["errors"] == 0
    assert stats["txn_ok"] == r.metrics.counter("maelstrom.txn_ok").value
    r.shutdown()
    lines = [ln for ln in getattr(r, "log_lines", [])
             if ln.startswith("metrics shutdown ")]
    assert len(lines) == len(r.nodes), "every node emits a final snapshot"
    started = 0
    for ln in lines:
        snap = json.loads(ln.split(" ", 3)[3])
        assert snap, "empty metrics snapshot"
        started += snap.get("txn.started", 0)
    assert started >= stats["txn_ok"], \
        "coordinations started across nodes must cover every acked txn"


def test_readme_documents_every_metric():
    with open("README.md") as f:
        readme = f.read()
    missing = [name for name in GLOSSARY if name not in readme]
    assert not missing, f"README glossary missing: {missing}"
