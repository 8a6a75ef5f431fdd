"""Telemetry: spans on the profiler's clock, metrics registry, drift
detection, named scopes, and the disabled-path invariance guarantees.

Spans are read back from one CPU ``jax.profiler`` capture per module
(:func:`capture`). Runs on 1-device meshes (degenerate topology); the
8-device acceptance leg (nested train-step spans in the profiler trace,
poisoned-table drift + ingest repair, hot-path overhead guard) is
tests/checks/telemetry_check.py.
"""
import contextlib
import gc
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, runtime, telemetry
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh
from subproc import run_check

#: span names the program emits outside the train/, comm/ and serve/
#: families
PROGRAM_SPANS = ("sync_wait", "gc")


def _is_program_span(name):
    return name in PROGRAM_SPANS or name.startswith(
        ("train/", "comm/", "serve/"))


def _tiny_overlapped_step(mesh, topo):
    from repro.configs import reduced_config
    from repro.models import decoder
    from repro.models.decoder import RunFlags
    from repro.optim import adamw
    from repro.train import manual_step
    from repro.train.step import TrainConfig
    cfg = reduced_config("smollm-360m")
    ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10,
                             schedule="constant", grad_clip=1e9)
    tcfg = TrainConfig(optimizer=ocfg, flags=RunFlags(remat="none"))
    key = jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab)}
    params = decoder.init(key, cfg)
    opt = adamw.init(params, ocfg)
    # one layer's gradient per bucket: two chunk programs
    step = manual_step.make_overlapped_train_step(
        cfg, tcfg, mesh, topo, algo="pip_mcoll", bucket_bytes=1 << 18,
        segmented=True)
    return step, params, opt, batch


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One profiler capture of everything the span tests read: an
    overlapped train step and a blocking persistent wait inside a
    harness-style ``dispatch`` span, a forced collection with telemetry on
    (``t/gc_on``) and off (``t/gc_off``), and the same persistent op with
    telemetry disabled (``t/off``). Returns (events, step): events as
    (name, start_ns, end_ns, stats) from every host plane."""
    from jax.profiler import ProfileData
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    x = jnp.arange(64, dtype=jnp.float32).reshape(1, 64)
    op = comm.allreduce_init(x, algo="pip_mcoll")
    step, params, opt, batch = _tiny_overlapped_step(mesh, topo)
    params, opt, m = step(params, opt, batch, step=0)  # compile outside
    jax.block_until_ready(m["loss"])
    op.start(x).wait()
    out = tmp_path_factory.mktemp("trace")
    telemetry.reset()
    try:
        with jax.profiler.trace(str(out)):
            telemetry.enable()
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt, m = step(params, opt, batch, step=7)
                op.start(x, bucket=99, step=7).wait(block=True)
            jax.block_until_ready(m["loss"])
            with jax.profiler.TraceAnnotation("t/gc_on"):
                gc.collect()
            telemetry.disable()
            with jax.profiler.TraceAnnotation("t/gc_off"):
                gc.collect()
            with jax.profiler.TraceAnnotation("t/off"):
                with telemetry.span("comm/never"):
                    op.start(x).wait(block=True)
                    telemetry.instant("comm/never_either")
    finally:
        telemetry.disable()
        telemetry.reset()
    (path,) = out.glob("**/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    keep = _is_program_span(e.name) or e.name.startswith(
                        "comm/never")
                    events.append((e.name, e.start_ns, e.end_ns,
                                   dict(e.stats) if keep else {}))
    return events, step


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(events, outer):
    """Events that lie inside the (single) event named ``outer``."""
    ((_, lo, hi, _),) = _named(events, outer)
    return [e for e in events if lo <= e[1] and e[2] <= hi]


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
    ctx = telemetry.span("x", plan="p")
    assert ctx is telemetry.span("y")  # shared null context, no allocation
    with ctx:
        pass
    telemetry.instant("x")
    telemetry.observe_plan(Topology(1, 1), "allreduce", "float32", 64,
                           "pip_mcoll", 1e-3)
    assert telemetry.plan_observations() == []
    assert not telemetry.should_sample("k", every=1)
    assert telemetry._gc_hook not in gc.callbacks


def test_enabled_span_is_a_tagged_profiler_annotation():
    telemetry.enable()
    ann = telemetry.span("comm/start", collective="allreduce", bucket=2)
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    assert ann is not telemetry.span("comm/start")
    assert telemetry._gc_hook in gc.callbacks
    telemetry.disable()
    assert telemetry._gc_hook not in gc.callbacks


def test_program_spans_nest_inside_dispatch_with_tags(capture):
    events, step = capture
    inner = _inside(events, "dispatch")
    names = [e[0] for e in inner]
    for name in ("train/step", "train/ensure_ops", "train/fwd",
                 "train/head_bwd", "train/embed_bwd", "train/apply",
                 "comm/start", "sync_wait"):
        assert name in names, (name, sorted(set(names)))
    # every span of the step carries its number
    for name, _, _, stats in inner:
        if name.startswith("train/"):
            assert stats.get("step") == 7, (name, stats)
    ks = sorted(st["k"] for n, _, _, st in inner if n == "train/chunk_bwd")
    assert ks == list(range(len(step.bounds))) and len(ks) >= 2
    # one comm/start per gradient bucket and the metrics vector, tagged
    # with its plan, its bucket and the step; the waits do not block
    starts = [st for n, _, _, st in inner if n == "comm/start"
              and st.get("step") == 7 and st.get("bucket") != 99]
    assert len(starts) == len(step.grad_sync.slices) + 1
    assert {st["collective"] for st in starts} == {"allreduce"}
    assert {st["algo"] for st in starts} == {"pip_mcoll"}
    assert sorted(str(st["bucket"]) for st in starts) == sorted(
        [str(i) for i in range(len(step.grad_sync.slices))] + ["metrics"])
    assert len([n for n, *_ in inner if n == "comm/wait"]) == len(starts)
    # the blocking wait of the persistent op is the one sync_wait
    (sw,) = [st for n, _, _, st in inner if n == "sync_wait"]
    assert sw["bucket"] == 99 and sw["step"] == 7
    # nesting: the step's stage spans lie inside train/step
    (_, lo, hi, _), = [e for e in inner if e[0] == "train/step"]
    for n, s, e, _ in inner:
        if n.startswith("train/") and n != "train/step":
            if n != "train/ensure_ops":
                assert lo <= s and e <= hi, n


def test_gc_span_on_forced_collect_and_not_after_disable(capture):
    events, _ = capture
    on = [e for e in _inside(events, "t/gc_on") if e[0] == "gc"]
    assert on and all(e[3].get("generation") == 2 for e in on)
    assert not [e for e in _inside(events, "t/gc_off") if e[0] == "gc"]


def test_disabled_telemetry_traces_no_program_span(capture):
    events, _ = capture
    inner = _inside(events, "t/off")
    assert any(n == "t/off" for n, *_ in inner)
    assert not [n for n, *_ in inner if _is_program_span(n)
                or n.startswith("comm/never")]


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
    # it did observe: one dispatch sample per collective
    assert len(telemetry.plan_observations()) == len(base)


def test_persistent_op_bitwise_invariant_and_sampled_probe_gated():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    x = jnp.arange(64, dtype=jnp.float32).reshape(1, 64)
    op = comm.allreduce_init(x, algo="pip_mcoll")
    off = np.asarray(op.start(x).wait())
    telemetry.enable()
    on = np.asarray(op.start(x, bucket=0).wait())
    np.testing.assert_array_equal(off, on)
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
    assert "tracer" not in snap
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


# ---------------------------------------------------------------------------
# named scopes: op_name metadata only
# ---------------------------------------------------------------------------


def _compiled_text(which):
    """Compiled HLO of the single-jit train step or of one overlapped-step
    program, at the tiny config on a (1, 1) mesh, without its metadata:
    each instruction's ``metadata={...}`` and the source-location tables
    that follow the computations (``FileNames`` on)."""
    mesh, topo = _mesh_topo()
    step, params, opt, batch = _tiny_overlapped_step(mesh, topo)
    if which == "train_step":
        from repro.train.step import train_step
        lowered = jax.jit(lambda p, o, b: train_step(
            p, o, b, step.cfg, step.tcfg)).lower(params, opt, batch)
    else:
        step._build(params, batch)
        outs = jax.eval_shape(step._fwd_c, params, batch)
        K = len(step.bounds)
        lowered = (step._fwd_c.lower(params, batch) if which == "fwd" else
                   step._head_bwd_c.lower(params, outs[K], outs[K + 1],
                                          batch))
    text = lowered.compile().as_text()
    return text, re.sub(r", metadata=\{[^}]*\}", "",
                        text.split("\nFileNames\n")[0])


@pytest.mark.parametrize("which,scopes", [
    ("train_step", ("embed", "layers", "attn", "mlp", "head", "xent",
                    "adamw")),
    ("fwd", ("embed", "layers", "attn", "mlp")),
    ("head_bwd", ("head", "xent")),
])
def test_named_scopes_change_only_metadata(which, scopes, monkeypatch):
    raw, scoped = _compiled_text(which)
    names = set(re.findall(r'op_name="([^"]*)"', raw))
    for scope in scopes:
        assert any(re.search(rf"(^|/|\(){scope}(/|\))", n) for n in names), \
            scope
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        _, plain = _compiled_text(which)
    assert "metadata=" not in scoped
    assert scoped == plain


def test_cached_executables_keep_this_builds_scopes(tmp_path):
    """The persistent compilation cache keys on op metadata (set by
    importing ``repro``): a build that differs only in its named scopes
    compiles its own executable, and never loads one whose metadata name
    another build's scopes."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    import repro  # noqa: F401
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        for scope in ("adamw", None):
            def step(x):
                with (jax.named_scope(scope) if scope
                      else contextlib.nullcontext()):
                    return x * 2.0 + 1.0
            jax.jit(step).lower(jnp.ones(8)).compile()
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert len(list(tmp_path.glob("jit_step-*"))) == 2
