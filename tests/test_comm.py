"""Communicator API: blocking methods, persistent nonblocking ops, the
plan-spec normalization point, the memoized per-(mesh, topo) communicator,
comm.split() sub-communicators, and the repo-wide grep enforcing that the
retired free-function shims (runtime.collective, mcoll.collective_fn) stay
gone.

Runs on 1-device meshes (degenerate topology) — multi-device behavior is
covered by tests/test_conformance.py and the subprocess checks.
"""
import pathlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm as comm_mod
from repro.core import mcoll, runtime
from repro.core.comm import Communicator, PersistentOp, PlanSpec
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh

REPO = pathlib.Path(__file__).resolve().parent.parent


def _mesh_topo(node="node", local="local"):
    mesh = make_mesh((1, 1), (node, local))
    return mesh, Topology(1, 1, node_axis=node, local_axis=local)


# ---------------------------------------------------------------------------
# blocking methods
# ---------------------------------------------------------------------------


def test_methods_cover_every_collective():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    for name in runtime.collectives():
        assert callable(getattr(comm, name)), name
        assert callable(getattr(comm, f"{name}_init")), name
        x = runtime.example_input(name, topo, 64)
        out = comm.invoke(name, x)
        assert np.isfinite(np.asarray(out, np.float64)).all()


def test_method_matches_runtime_backend_bitwise():
    """A Communicator method and the runtime backend entry are one code
    path — identical results, shared exec-cache entry."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    runtime.clear_cache()
    z = jnp.ones((1, 64), jnp.float32)
    a = comm.allreduce(z, algo="pip_mcoll")
    b = runtime.run(mesh, topo, "allreduce", "pip_mcoll", z)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = runtime.cache_stats()
    assert s.exec_misses == 1 and s.exec_hits == 1, s


def test_unknown_collective_rejected():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    with pytest.raises(ValueError, match="unknown collective"):
        comm.invoke("gossip", jnp.arange(4.0))


def test_kwargs_validated_at_plan_construction():
    """An unsupported knob fails with a clear ValueError when the plan is
    constructed — never a TypeError mid-trace."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    z = jnp.ones((1, 16), jnp.float32)
    with pytest.raises(ValueError, match="unsupported kwargs"):
        comm.allreduce(z, algo="xla", radix=3)
    with pytest.raises(ValueError, match="unknown algorithm"):
        comm.allreduce(z, algo="does_not_exist")
    with pytest.raises(ValueError, match="does not support chunking"):
        comm.allreduce(z, algo="xla", chunks=2)
    with pytest.raises(ValueError, match="does not support compression"):
        comm.allreduce(z, algo="xla", codec="int8_block")
    # a knob pinned in the spec AND passed again as an extra kwarg is a
    # contradiction the resolver refuses (internal API: methods make this
    # unreachable by construction)
    with pytest.raises(ValueError, match="duplicate plan knobs"):
        comm._resolve(PlanSpec("allreduce", "pip_pipeline", chunks=2), z,
                      {"chunks": 3})


def test_plan_resolution_method():
    """comm.plan exposes the selector's (algo, chunks, codec) plan for
    shard-body consumers (MoE) on this communicator's topology."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    sel = comm.plan("allreduce", 1 << 20)
    assert sel.algo in mcoll.algorithms("allreduce")
    assert sel.chunks >= 1 and sel.codec == "none"


def test_instance_selector_drives_auto_resolution():
    """A Communicator constructed with its own selector resolves auto
    plans (blocking AND persistent) through IT, not the process default —
    its calibration data is actually consulted."""
    from repro.core import autotune
    mesh, topo = _mesh_topo()
    custom = autotune.Selector()
    comm = Communicator(mesh, topo, selector=custom)
    z = jnp.ones((1, 64), jnp.float32)
    default_before = autotune.default_selector().stats.total
    comm.allreduce(z)                   # algo="auto" -> custom selector
    comm.allreduce_init(z)              # persistent init resolves too
    assert custom.stats.total == 2, custom.stats
    assert autotune.default_selector().stats.total == default_before
    # a measured entry recorded into the custom table wins its resolution
    custom.table.record(topo, "allreduce", "float32", 256, "xla", 1e-9)
    algo, _ = runtime.resolve_algo(topo, "allreduce", "auto", z,
                                   selector=custom)
    assert algo == "xla"
    op = comm.allreduce_init(z)
    assert op.algo == "xla", op.plan


# ---------------------------------------------------------------------------
# the memoized communicator
# ---------------------------------------------------------------------------


def test_communicator_memoized_per_mesh_topo():
    mesh, topo = _mesh_topo()
    c1 = comm_mod.communicator(mesh, topo)
    c2 = comm_mod.communicator(mesh, topo)
    assert c1 is c2
    mesh2, topo2 = _mesh_topo("n2", "l2")
    assert comm_mod.communicator(mesh2, topo2) is not c1


# ---------------------------------------------------------------------------
# comm.split(): sub-communicator edge cases (1-device; multi-device group
# semantics live in tests/test_conformance.py)
# ---------------------------------------------------------------------------


def test_split_memoized_and_shares_selector():
    """Repeated splits of one spec return the SAME child (so persistent
    ops and plan caches are shared), and children share the parent's
    selector so calibration merges into one table."""
    mesh, _ = _mesh_topo()
    root = Communicator(mesh)
    g1 = root.split(axes="local")
    g2 = root.split(axes="local")
    assert g1 is g2
    assert g1.selector is root.selector
    assert g1.topo.group == "local" and g1.topo.world == 1
    assert root.split(axes="node") is not g1


def test_split_world1_and_size1_axes_run_collectives():
    """Degenerate groups (size-1 axis -> world-1 child) still run every
    collective: the identity semantics, not an error."""
    mesh, _ = _mesh_topo()
    root = Communicator(mesh)
    g = root.split(axes="local")
    z = jnp.ones((1, 16), jnp.float32)
    np.testing.assert_array_equal(np.asarray(g.allreduce(z)),
                                  np.asarray(z))
    for name in runtime.collectives():
        x = runtime.example_input(name, g.topo, 64)
        out = g.invoke(name, x)
        assert np.isfinite(np.asarray(out, np.float64)).all()


def test_single_axis_group_topology_dedupes_axes():
    """A single-axis group names the same mesh axis at both topology
    levels; ``active_axes`` must still name it once — a repeated axis in
    the collective tuple is a trace-time ppermute error on real meshes."""
    topo = Topology(1, 4, node_axis="tp", local_axis="tp")
    assert topo.active_axes == ("tp",)
    assert Topology(1, 1, node_axis="tp", local_axis="tp").active_axes \
        == ("tp",)


def test_split_of_split_composes():
    mesh, _ = _mesh_topo()
    root = Communicator(mesh)
    gg = root.split(axes=("node", "local")).split(axes="local")
    assert gg.topo.world == 1 and gg.topo.group == "local"
    z = jnp.ones((1, 8), jnp.float32)
    np.testing.assert_array_equal(np.asarray(gg.allreduce(z)),
                                  np.asarray(z))


def test_split_exec_cache_shared_between_identical_children():
    """Two identically-specced splits (memo hit) reuse one exec-cache
    entry — the group topology is the cache key, not the child object."""
    mesh, _ = _mesh_topo()
    root = Communicator(mesh)
    runtime.clear_cache()
    z = jnp.ones((1, 32), jnp.float32)
    root.split(axes="local").allreduce(z, algo="pip_mcoll")
    root.split(axes="local").allreduce(z, algo="pip_mcoll")
    s = runtime.cache_stats()
    assert s.exec_misses == 1 and s.exec_hits == 1, s


def test_split_group_namespaces_tuning_keys():
    """A child's tuning rows carry the group tag: the same NxP shape tuned
    as a group never aliases the ungrouped table rows (an 8-way TP group
    and an 8-way flat world calibrate independently)."""
    from repro.core import autotune
    mesh, topo = _mesh_topo()
    root = Communicator(mesh, topo)
    g = root.split(axes="local")
    assert autotune.topo_key(g.topo) != autotune.topo_key(topo)
    assert autotune.topo_key(g.topo).endswith("/g:local")
    root.selector.table.record(g.topo, "allreduce", "float32", 1 << 10,
                               "xla", 1e-9)
    assert root.selector.table.lookup(topo, "allreduce", "float32",
                                      1 << 10) is None
    sel = g.plan("allreduce", 1 << 10)
    assert sel.algo == "xla"


def test_split_calibration_table_roundtrip_with_group_keys(tmp_path):
    """Group-keyed rows survive a save/load cycle and keep resolving."""
    from repro.core import autotune
    mesh, _ = _mesh_topo()
    root = Communicator(mesh)
    g = root.split(axes="local")
    root.selector.table.record(g.topo, "allreduce", "float32", 1 << 10,
                               "xla", 1e-9)
    path = tmp_path / "table.json"
    root.selector.table.save(path)
    loaded = autotune.TuningTable.load(path)
    hit = loaded.lookup(g.topo, "allreduce", "float32", 1 << 10)
    assert hit == {"xla": 1e-9}


def test_split_validation():
    mesh, _ = _mesh_topo()
    root = Communicator(mesh)
    with pytest.raises(ValueError, match="exactly one of"):
        root.split()
    with pytest.raises(ValueError, match="exactly one of"):
        root.split(axes="local", color=[0])
    with pytest.raises(ValueError, match="key= only"):
        root.split(axes="local", key=[0])
    with pytest.raises(ValueError, match="not in mesh axes"):
        root.split(axes="tp")
    with pytest.raises(ValueError, match="one entry per parent rank"):
        root.split(color=[0, 1])


def test_split_color_groups():
    mesh, _ = _mesh_topo()
    root = Communicator(mesh)
    groups = root.split(color=[7])
    assert set(groups) == {7}
    g = groups[7]
    assert g.topo.world == 1 and g.topo.group == "color7"
    z = jnp.ones((1, 8), jnp.float32)
    np.testing.assert_array_equal(np.asarray(g.allreduce(z)), np.asarray(z))


def test_unscoped_root_requires_split():
    """A mesh without the node/local axes yields an unscoped root:
    split(axes=...) works, collectives raise with a pointer to it."""
    mesh = make_mesh((1,), ("tp",))
    root = Communicator(mesh)
    assert root.topo is None
    with pytest.raises(ValueError, match=r"split\(axes=\.\.\.\)"):
        root.allreduce(jnp.ones((1, 8), jnp.float32))
    with pytest.raises(ValueError, match=r"split\(axes=\.\.\.\)"):
        root.plan("allreduce", 1 << 10)
    g = root.split(axes="tp")
    assert g.topo is not None and g.topo.world == 1
    z = jnp.ones((1, 8), jnp.float32)
    np.testing.assert_array_equal(np.asarray(g.allreduce(z)), np.asarray(z))


# ---------------------------------------------------------------------------
# persistent ops (1-device semantics; multi-device in conformance/checks)
# ---------------------------------------------------------------------------


def test_persistent_op_properties_and_call():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    z = jnp.ones((1, 64), jnp.float32)
    op = comm.allreduce_init(z, algo="pip_pipeline", chunks=2)
    assert isinstance(op, PersistentOp)
    assert (op.algo, op.chunks, op.codec) == ("pip_pipeline", 2, "none")
    assert op.plan == "pip_pipeline#c2"
    assert op.shape == (1, 64) and op.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(op(z)),  # __call__ sugar
                                  np.asarray(comm.allreduce(
                                      z, algo="pip_pipeline", chunks=2)))


def test_persistent_init_needs_an_operand_spec():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    with pytest.raises(ValueError, match="shape"):
        comm.allreduce_init()
    op = comm.allreduce_init(shape=(1, 8), dtype=jnp.float32,
                             algo="pip_mcoll")
    out = op.start(jnp.ones((1, 8), jnp.float32)).wait()
    np.testing.assert_array_equal(np.asarray(out), np.ones((1, 8)))


def test_persistent_init_resolves_auto_once():
    """auto resolves at init; the op then carries a concrete plan."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    z = jnp.ones((1, 1 << 18), jnp.float32)
    op = comm.allreduce_init(z)  # algo="auto"
    assert op.algo != "auto" and op.algo in mcoll.algorithms("allreduce")
    algo, kw = runtime.resolve_algo(topo, "allreduce", "auto", z)
    assert op.algo == algo and op.chunks == kw.get("chunks", 1)


def test_persistent_depth_validation():
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    with pytest.raises(ValueError, match="depth"):
        comm.allreduce_init(shape=(1, 8), dtype=jnp.float32,
                            algo="pip_mcoll", depth=0)


def test_persistent_donate_is_a_distinct_program():
    """donate=True compiles a separate executable (input aliasing differs)
    but produces identical results."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    runtime.clear_cache()
    z = jnp.ones((1, 32), jnp.float32)
    op = comm.allreduce_init(z, algo="pip_mcoll")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU may ignore donation
        opd = comm.allreduce_init(z, algo="pip_mcoll", donate=True)
        want = np.asarray(op.start(z).wait())
        got = np.asarray(opd.start(jnp.ones((1, 32), jnp.float32)).wait())
    np.testing.assert_array_equal(got, want)
    assert runtime.cache_stats().exec_misses == 2


def test_persistent_carry_roundtrip_and_arg_pairing():
    """A carry op's wait() returns (result, new_state) matching the
    runtime's carry-threaded program, and start() enforces the carry-arg
    pairing both ways (carry op without state / plain op with state)."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, 96)), jnp.float32)
    e0 = jnp.asarray(rng.standard_normal((1, 96)), jnp.float32)
    op = comm.allreduce_init(x, algo="pip_mcoll", codec="int8_block",
                             carry=True)
    assert op.carry and op.codec == "int8_block"
    y, e1 = op.start(x, carry=e0).wait()
    fn = runtime.build(mesh, topo, "allreduce", "pip_mcoll", carry=True,
                       codec="int8_block")
    ry, re1 = fn(x, e0)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ry))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(re1))
    # threading the returned state back in is a valid (and the intended)
    # next start; the op stays reusable
    y2, _ = op.start(x, carry=e1).wait()
    assert np.isfinite(np.asarray(y2)).all()
    with pytest.raises(ValueError, match="requires carry=state"):
        op.start(x)
    plain = comm.allreduce_init(x, algo="pip_mcoll")
    with pytest.raises(ValueError, match="does not take a carry"):
        plain.start(x, carry=e0)
    with pytest.raises(ValueError, match="carry"):
        op.start(x, carry=jnp.zeros((1, 8), jnp.float32))  # wrong spec


def test_persistent_carry_needs_err_capable_algorithm():
    """carry=True is the error-feedback hookup: only algorithms with an
    err state operand (the pip family) compile it; xla does not."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    assert runtime.supports_carry("allreduce", "pip_mcoll")
    assert runtime.supports_carry("allreduce", "pip_pipeline")
    assert not runtime.supports_carry("allreduce", "xla")
    with pytest.raises(ValueError, match="carry"):
        comm.allreduce_init(shape=(1, 8), dtype=jnp.float32, algo="xla",
                            carry=True)
    with pytest.raises(ValueError, match="only supported on allreduce"):
        PlanSpec("broadcast", carry=True)


def test_persistent_release_semantics():
    """release() retires the op from the live-op count (idempotently) and
    makes any further start() raise; re-init of the same spec is an
    exec-cache hit, not a recompile."""
    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)
    runtime.clear_cache()
    z = jnp.ones((1, 48), jnp.float32)
    base = comm_mod.live_persistent_ops()
    op = comm.allreduce_init(z, algo="pip_mcoll")
    assert comm_mod.live_persistent_ops() == base + 1
    assert not op.released
    op.release()
    assert op.released
    assert comm_mod.live_persistent_ops() == base
    op.release()  # idempotent: no double-decrement
    assert comm_mod.live_persistent_ops() == base
    with pytest.raises(RuntimeError, match="released"):
        op.start(z)
    misses = runtime.cache_stats().exec_misses
    op2 = comm.allreduce_init(z, algo="pip_mcoll")
    assert runtime.cache_stats().exec_misses == misses  # cache hit
    np.testing.assert_array_equal(np.asarray(op2(z)), np.asarray(z))
    op2.release()


def test_overlapped_sync_releases_ops_on_plan_rebind():
    """Rebind hygiene across budget-schedule plan crossings: every rebuild
    of OverlappedGradSync's bucket ops releases the ops it replaces, so the
    process-wide live-op count stays flat however many times the schedule
    crosses a plan boundary. (The resolver is monkeypatched to alternate
    plans deterministically — on a world-1 topology the real cost model
    resolves every budget to the same lossless plan, which would make the
    crossing a no-op; the 8-device flatness check with the real resolver
    lives in tests/checks/manual_step_check.py.)"""
    from repro.train import manual_step

    mesh, topo = _mesh_topo()
    comm = Communicator(mesh, topo)

    def fake_resolve(topo_, nbytes, dtype, algo, chunks, codec, budget):
        if budget > 0.0:
            return "pip_mcoll", {"codec": "int8_block"}
        return "pip_mcoll", {}

    orig = manual_step._resolve_plan
    manual_step._resolve_plan = fake_resolve
    try:
        sched = lambda step: 0.05 if (step // 2) % 2 else 0.0
        gs = manual_step.OverlappedGradSync(
            comm, [(0, 32), (32, 96)], metric_len=4, algo="pip_mcoll",
            error_budget=sched)
        gs.ensure_ops(0)
        base = comm_mod.live_persistent_ops()
        assert gs.plans() == ["pip_mcoll", "pip_mcoll"]
        assert [op.carry for op in gs._ops] == [False, False]
        payloads = [jnp.ones((1, n), jnp.float32) for _, n in gs.slices]
        mvec = jnp.zeros((1, 4), jnp.float32)
        for step in range(12):
            gs.ensure_ops(step)
            # every crossing rebuilds, none leaks: live count never grows
            assert comm_mod.live_persistent_ops() == base
            synced, _ = gs.sync(payloads, mvec, overlap=bool(step % 2))
            assert all(np.isfinite(np.asarray(s)).all() for s in synced)
        assert gs.rebuilds == 5  # budget crossed a plan boundary 5 times
        assert gs.plans() == ["pip_mcoll@int8_block"] * 2
        assert all(op.carry for op in gs._ops)
        assert all(e is not None for e in gs.errs)
    finally:
        manual_step._resolve_plan = orig


# ---------------------------------------------------------------------------
# regression grep: the retired free-function shims stay retired
# ---------------------------------------------------------------------------


def test_retired_shims_have_no_call_sites():
    """Like the PR-1 shard_map grep: runtime.collective and
    mcoll.collective_fn were deleted after the Communicator migration —
    no code anywhere in the repo may reference them again (new call sites
    go through repro.core.comm.Communicator or runtime.build)."""
    pattern = re.compile(
        r"runtime\.collective\s*\(|"
        r"from\s+repro\.core\.runtime\s+import\s+.*\bcollective\b|"
        r"\bcollective_fn\b")
    allowed = {pathlib.Path(__file__).resolve()}
    offenders = []
    for sub in ("src", "tests", "benchmarks", "examples"):
        for path in sorted((REPO / sub).rglob("*.py")):
            if path.resolve() in allowed:
                continue
            for i, line in enumerate(path.read_text().splitlines(), 1):
                if pattern.search(line):
                    offenders.append(
                        f"{path.relative_to(REPO)}:{i}: {line.strip()}")
    assert not offenders, (
        "references to retired shims (runtime.collective / "
        "mcoll.collective_fn); use Communicator methods or runtime.build:\n"
        + "\n".join(offenders))
    assert not hasattr(runtime, "collective")
    assert not hasattr(mcoll, "collective_fn")


# ---------------------------------------------------------------------------
# PlanSpec normalization (unit level; cache-entry assertions live in
# test_runtime.py::test_exec_cache_kwargs_normalization_single_entry)
# ---------------------------------------------------------------------------


def test_plan_spec_kwargs_drop_unpinned_knobs():
    assert PlanSpec("allreduce").kwargs() == {}
    assert PlanSpec("allreduce", chunks=None, codec=None).kwargs() == {}
    assert PlanSpec("allreduce", chunks=4).kwargs() == {"chunks": 4}
    assert PlanSpec("allreduce", codec="none").kwargs() == {"codec": "none"}
    assert PlanSpec("allreduce", chunk_bytes=1024).kwargs() == \
        {"chunk_bytes": 1024}


def test_plan_spec_normalized_resolution_is_single_plan():
    """Every spelling of the default plan resolves to identical normalized
    kwargs — the exec-cache key material."""
    topo = Topology(1, 1)
    z = jnp.ones((1, 64), jnp.float32)
    resolved = set()
    for spec in (PlanSpec("allreduce", "pip_pipeline"),
                 PlanSpec("allreduce", "pip_pipeline", chunks=1),
                 PlanSpec("allreduce", "pip_pipeline", chunks=None),
                 PlanSpec("allreduce", "pip_pipeline", codec="none"),
                 PlanSpec("allreduce", "pip_pipeline", codec=None)):
        algo, kw = runtime.resolve_algo(topo, spec.collective, spec.algo, z,
                                        spec.kwargs())
        resolved.add((algo, tuple(sorted(kw.items()))))
    assert len(resolved) == 1, resolved
