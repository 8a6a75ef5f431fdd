"""Telemetry: tracer, metrics registry, drift detection, and the
disabled-path invariance guarantees.

Runs on 1-device meshes (degenerate topology); the 8-device acceptance leg
(nested train-step spans in the Perfetto trace, poisoned-table drift +
ingest repair, hot-path overhead guard) is tests/checks/telemetry_check.py.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, runtime, telemetry
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh
from subproc import run_check


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts (and leaves the process) disabled and empty."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _mesh_topo():
    mesh = make_mesh((1, 1), ("node", "local"))
    return mesh, Topology(1, 1)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_records_nothing_and_allocates_no_context():
    assert not telemetry.enabled()
    ctx = telemetry.span("x", cat="test", plan="p")
    assert ctx is telemetry.span("y")  # shared null context, no allocation
    with ctx:
        pass
    assert telemetry.begin("x") is None
    telemetry.end(None)
    telemetry.emit("x", 0.0, 1.0)
    telemetry.instant("x")
    telemetry.observe_plan(Topology(1, 1), "allreduce", "float32", 64,
                           "pip_mcoll", 1e-3)
    assert telemetry.spans() == []
    assert telemetry.plan_observations() == []
    assert not telemetry.should_sample("k", every=1)


def test_span_and_begin_end_record_tagged_windows():
    telemetry.enable()
    with telemetry.span("build/allreduce", cat="build", plan="pip_mcoll"):
        pass
    tok = telemetry.begin("allreduce[pip_mcoll]", cat="comm",
                          track="comm:allreduce#1", bucket=0)
    telemetry.end(tok)
    s1, s2 = telemetry.spans()
    assert s1.name == "build/allreduce" and s1.track == "main"
    assert dict(s1.args)["plan"] == "pip_mcoll"
    assert s2.track == "comm:allreduce#1" and s2.duration >= 0.0
    assert s2.start >= s1.start


def test_ring_buffer_bounds_and_drop_counter():
    telemetry.enable(capacity=8)
    try:
        for i in range(20):
            telemetry.instant(f"s{i}")
        assert len(telemetry.spans()) == 8
        assert telemetry.spans_dropped() == 12
        assert [s.name for s in telemetry.spans()][0] == "s12"
    finally:
        telemetry.enable(capacity=65536)


def test_export_chrome_trace_tracks_and_events(tmp_path):
    telemetry.enable()
    with telemetry.span("train/step", cat="train"):
        with telemetry.span("train/fwd", cat="train"):
            pass
        tok = telemetry.begin("bucket0[pip_pipeline]", cat="bucket",
                              track="bucket:0")
        telemetry.end(tok)
    out = tmp_path / "trace.json"
    trace = telemetry.export_chrome_trace(out)
    assert json.loads(out.read_text()) == trace
    meta = {e["args"]["name"]: e["tid"] for e in trace["traceEvents"]
            if e["ph"] == "M"}
    assert meta["main"] == 0 and "bucket:0" in meta
    evs = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"train/step", "train/fwd", "bucket0[pip_pipeline]"}
    step, fwd = evs["train/step"], evs["train/fwd"]
    assert fwd["tid"] == 0 and evs["bucket0[pip_pipeline]"]["tid"] != 0
    # nesting by time containment on the exported microsecond timeline
    assert step["ts"] <= fwd["ts"]
    assert fwd["ts"] + fwd["dur"] <= step["ts"] + step["dur"] + 1e-3
    assert trace["otherData"]["spans_dropped"] == 0


def test_plan_tags_schema():
    tags = telemetry.plan_tags("allreduce", "pip_pipeline", chunks=4,
                               codec="int8_block", group="node", nbytes=5000)
    assert tags == {"collective": "allreduce", "algo": "pip_pipeline",
                    "chunks": 4, "codec": "int8_block", "group": "node",
                    "size_bucket": 8192}
    assert "size_bucket" not in telemetry.plan_tags("broadcast", "binomial")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_histogram_quantiles_and_summary():
    h = telemetry.Histogram("t")
    for v in (1e-3, 2e-3, 3e-3, 4e-3, 100e-3):
        h.observe(v)
    assert h.count == 5 and np.isclose(h.mean, 0.022)
    assert h.vmin == 1e-3 and h.vmax == 100e-3
    assert 1e-3 <= h.quantile(0.5) <= 4e-3
    assert h.quantile(0.99) <= 100e-3
    assert h.quantile(0.0) == 1e-3  # clamped to observed min
    s = h.summary()
    assert s["count"] == 5 and s["p99"] >= s["p50"]
    assert telemetry.Histogram("e").quantile(0.5) == 0.0
    assert telemetry.Histogram("e").summary() == {"count": 0}


def test_registry_counters_always_on_and_reset():
    assert not telemetry.enabled()
    telemetry.counter("x.hits").inc()
    telemetry.counter("x.hits").inc(2)
    telemetry.histogram("x.lat").observe(1e-3)
    d = telemetry.registry().to_dict()
    assert d["counters"]["x.hits"] == 3
    assert d["histograms"]["x.lat"]["count"] == 1
    telemetry.reset()
    assert telemetry.registry().to_dict() == {"counters": {},
                                              "histograms": {}}


# ---------------------------------------------------------------------------
# plan observations + drift detection
# ---------------------------------------------------------------------------


def _observe(topo, plan="pip_mcoll", seconds=(1e-3, 2e-3, 3e-3),
             synced=True, coll="allreduce", nbytes=4096):
    for s in seconds:
        telemetry.observe_plan(topo, coll, "float32", nbytes, plan, s,
                               synced=synced)


def test_observe_plan_median_keeps_sync_and_dispatch_separate():
    telemetry.enable()
    topo = Topology(4, 2)
    _observe(topo, seconds=(1e-3, 2e-3, 3e-3), synced=True)
    _observe(topo, seconds=(1e-6,), synced=False)
    (obs,) = telemetry.plan_observations()
    assert obs.median(synced=True) == 2e-3
    assert obs.median(synced=False) == 1e-6
    reg = telemetry.registry().to_dict()["histograms"]
    assert reg["plan.allreduce.pip_mcoll.sync_seconds"]["count"] == 3
    assert reg["plan.allreduce.pip_mcoll.dispatch_seconds"]["count"] == 1


def test_drift_report_flags_table_divergence_both_directions():
    telemetry.enable()
    topo = Topology(4, 2)
    sel = autotune.Selector(table=autotune.TuningTable())
    # in-band row: table within 1.5x of the observed 2ms median
    _observe(topo, plan="pip_mcoll", seconds=(2e-3,) * 3)
    sel.table.record(topo, "allreduce", "float32", 4096, "pip_mcoll", 1.5e-3)
    # poisoned-fast row: table claims 1000x faster than observed
    _observe(topo, plan="ring", seconds=(2e-3,) * 3)
    sel.table.record(topo, "allreduce", "float32", 4096, "ring", 2e-6)
    # poisoned-slow row: table claims 1000x slower than observed
    _observe(topo, plan="recursive_doubling", seconds=(2e-3,) * 3)
    sel.table.record(topo, "allreduce", "float32", 4096,
                     "recursive_doubling", 2.0)
    rows = {r.plan: r for r in telemetry.drift_report(selector=sel)}
    assert not rows["pip_mcoll"].flagged
    assert rows["ring"].flagged and rows["ring"].drift_vs_table > 0
    assert rows["recursive_doubling"].flagged
    assert rows["recursive_doubling"].drift_vs_table < 0
    # worst-first ordering and the flagged-only view agree
    report = telemetry.drift_report(selector=sel)
    assert abs(report[0].drift_vs_table) >= abs(report[-1].drift_vs_table)
    assert {r.plan for r in telemetry.drifted_plans(selector=sel)} == \
        {"ring", "recursive_doubling"}


def test_drift_report_without_table_entry_reports_model_only():
    telemetry.enable()
    topo = Topology(4, 2)
    _observe(topo, plan="pip_mcoll", seconds=(2e-3,) * 3)
    (row,) = telemetry.drift_report(selector=autotune.Selector(
        table=autotune.TuningTable()))
    assert row.table_s is None and row.drift_vs_table is None
    assert not row.flagged  # no table promise -> nothing to flag
    assert row.model_s is not None and row.drift_vs_model is not None


def test_drift_report_min_samples_gate():
    telemetry.enable()
    topo = Topology(4, 2)
    _observe(topo, seconds=(2e-3,))
    sel = autotune.Selector(table=autotune.TuningTable())
    assert telemetry.drift_report(selector=sel, min_samples=2) == []
    assert len(telemetry.drift_report(selector=sel, min_samples=1)) == 1


def test_selector_ingest_folds_observed_medians_into_table():
    telemetry.enable()
    topo = Topology(4, 2)
    _observe(topo, plan="pip_mcoll", seconds=(1e-3, 2e-3, 3e-3))
    _observe(topo, plan="ring", seconds=(5e-3,))
    sel = autotune.Selector(table=autotune.TuningTable())
    gen0 = sel.table.generation
    assert sel.ingest(telemetry, min_samples=2) == 1  # ring gated out
    entry = sel.table.lookup(topo, "allreduce", "float32", 4096)
    assert entry == {"pip_mcoll": 2e-3}
    assert sel.table.generation > gen0
    assert sel.ingest(telemetry, min_samples=1) == 2  # both qualify now
    assert sel.table.lookup(topo, "allreduce", "float32",
                            4096)["ring"] == 5e-3


def test_should_sample_is_deterministic_one_in_n():
    telemetry.enable()
    hits = [telemetry.should_sample("k", every=4) for _ in range(8)]
    assert hits == [True, False, False, False, True, False, False, False]


# ---------------------------------------------------------------------------
# disabled-path invariance: telemetry must never change results or caching
# ---------------------------------------------------------------------------


def _run_all(comm, topo):
    outs = {}
    for name in runtime.collectives():
        x = runtime.example_input(name, topo, 256)
        outs[name] = np.asarray(comm.invoke(name, x))
    return outs


def test_outputs_and_exec_cache_keys_invariant_under_telemetry():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    runtime.clear_cache()
    base = _run_all(comm, topo)
    keys_off = set(runtime._EXEC_CACHE)
    telemetry.enable()
    runtime.clear_cache()
    traced = _run_all(comm, topo)
    keys_on = set(runtime._EXEC_CACHE)
    assert keys_on == keys_off, "telemetry state leaked into cache keys"
    for name, out in base.items():
        np.testing.assert_array_equal(out, traced[name], err_msg=name)
    assert len(telemetry.spans()) > 0  # it did actually trace


def test_persistent_op_bitwise_invariant_and_sampled_probe_gated():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    x = jnp.arange(64, dtype=jnp.float32).reshape(1, 64)
    op = comm.allreduce_init(x, algo="pip_mcoll")
    off = np.asarray(op.start(x).wait())
    telemetry.enable()
    on = np.asarray(op.start(x).wait())
    np.testing.assert_array_equal(off, on)
    # the start->wait window landed as a comm span with plan tags
    comm_spans = [s for s in telemetry.spans() if s.cat == "comm"]
    assert comm_spans and dict(comm_spans[-1].args)["algo"] == "pip_mcoll"
    (obs,) = [o for o in telemetry.plan_observations()
              if o.collective == "allreduce"]
    assert len(obs.samples) == 1  # blocking wait -> one synced sample


def test_snapshot_unifies_observables_when_disabled():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    runtime.clear_cache()
    comm.allreduce(jnp.ones((1, 16), jnp.float32))
    snap = telemetry.snapshot()
    assert snap["enabled"] is False
    assert snap["tracer"]["spans"] == 0
    assert snap["cache"]["exec_misses"] >= 1
    assert snap["selection"]["total"] >= 1
    assert isinstance(snap["live_persistent_ops"], int)
    assert snap["plans"] == []


def test_cache_stats_reset_zeroes_in_place():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    runtime.clear_cache()
    comm.allreduce(jnp.ones((1, 16), jnp.float32))
    s = runtime.cache_stats()
    assert s.exec_misses >= 1
    s.reset()
    assert runtime.cache_stats().exec_misses == 0
    assert runtime.cache_stats().exec_hits == 0


# ---------------------------------------------------------------------------
# 8-device acceptance leg (subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_telemetry_acceptance_8dev():
    """Nested train-step spans in the exported trace, poisoned-table drift
    flagged + repaired by Selector.ingest, hot-path overhead < 2%."""
    out = run_check("telemetry_check.py", 8, 4, 2)
    assert "telemetry_check N=4 P=2: OK" in out
