"""Collective runtime: the single ``jax.shard_map``/``check_vma`` path,
build/exec cache behavior, and the no-direct-shard_map regression grep.

Cache tests run in-process on 1-device meshes (a (1, 1) node x local mesh
is a valid degenerate topology), keeping device-count containment intact.
All cache tests drive the runtime through the Communicator (the supported
surface, via ``_coll``); the ``runtime.collective`` deprecation shim has
its own tests in test_comm.py.
"""
import inspect
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import comm as comm_mod
from repro.core import runtime
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"


def _coll(mesh, topo, name, algo, x, **kw):
    return comm_mod.communicator(mesh, topo).invoke(name, x, algo=algo, **kw)


# ---------------------------------------------------------------------------
# shard_map: the one installed path (jax.shard_map with check_vma)
# ---------------------------------------------------------------------------


def test_compat_picks_installed_impl():
    """``runtime.sharded`` calls the installed ``jax.shard_map`` directly;
    no other spelling (``jax.experimental.shard_map``) is resolved."""
    assert callable(jax.shard_map)
    src = inspect.getsource(runtime.sharded)
    assert "jax.shard_map(" in src
    assert "experimental" not in src


def test_compat_kwarg_spelling_matches_impl():
    """The installed ``jax.shard_map`` takes ``check_vma`` (not the old
    ``check_rep``), and ``sharded(check=...)`` is passed through as it."""
    params = inspect.signature(jax.shard_map).parameters
    assert "check_vma" in params and "check_rep" not in params
    assert "check_vma=check" in inspect.getsource(runtime.sharded)


def test_compat_shard_map_executes():
    mesh = make_mesh((1,), ("d",))
    fn = runtime.sharded(lambda x: x * 2, mesh, in_specs=(P("d"),),
                         out_specs=P("d"))
    np.testing.assert_array_equal(np.asarray(fn(jnp.arange(4.0))),
                                  np.arange(4.0) * 2)
    # check=True is check_vma: a varying output declared replicated is
    # refused at trace time; check=False lets it through
    replicated = dict(in_specs=(P("d"),), out_specs=P())
    fn2 = runtime.sharded(lambda x: x + 1, mesh, **replicated)
    np.testing.assert_array_equal(np.asarray(fn2(jnp.zeros(2))), np.ones(2))
    with pytest.raises(ValueError, match="replication"):
        runtime.sharded(lambda x: x + 1, mesh, check=True,
                        **replicated)(jnp.zeros(2))


# ---------------------------------------------------------------------------
# runtime: build cache + compiled-callable (exec) cache
# ---------------------------------------------------------------------------


def _mesh_topo(node="node", local="local"):
    mesh = make_mesh((1, 1), (node, local))
    return mesh, Topology(1, 1, node_axis=node, local_axis=local)


def test_build_cache_identity_and_invalidation():
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    f1 = runtime.build(mesh, topo, "allgather", "xla")
    f2 = runtime.build(mesh, topo, "allgather", "xla")
    assert f1 is f2, "identical key must return the identical callable"
    f3 = runtime.build(mesh, topo, "allgather", "pip_mcoll")
    assert f3 is not f1, "algo change must build fresh"
    f4 = runtime.build(mesh, topo, "allgather", "xla", stacked=False)
    assert f4 is not f1, "kwarg change must build fresh"
    s = runtime.cache_stats()
    assert s.build_hits == 1 and s.build_misses == 3


def test_exec_cache_hit_on_identical_key():
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    x = jnp.arange(4.0)
    out1 = _coll(mesh, topo, "allgather", "xla", x)
    out2 = _coll(mesh, topo, "allgather", "xla", x)
    s = runtime.cache_stats()
    assert s.exec_misses == 1 and s.exec_hits == 1
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1)[0], np.asarray(x))


def test_exec_cache_fresh_on_shape_dtype_algo_mesh_change():
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    _coll(mesh, topo, "allgather", "xla", jnp.arange(4.0))
    _coll(mesh, topo, "allgather", "xla", jnp.arange(8.0))
    assert runtime.cache_stats().exec_misses == 2, "shape change re-compiles"
    _coll(mesh, topo, "allgather", "xla",
                       jnp.arange(4, dtype=jnp.int32))
    assert runtime.cache_stats().exec_misses == 3, "dtype change re-compiles"
    _coll(mesh, topo, "allgather", "pip_mcoll", jnp.arange(4.0))
    assert runtime.cache_stats().exec_misses == 4, "algo change re-compiles"
    mesh2, topo2 = _mesh_topo("n2", "l2")
    _coll(mesh2, topo2, "allgather", "xla", jnp.arange(4.0))
    assert runtime.cache_stats().exec_misses == 5, "mesh change re-compiles"
    assert runtime.cache_stats().exec_hits == 0


def test_collective_correct_through_cache():
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.arange(6.0).reshape(1, 6)
    for _ in range(2):  # second pass: every call a cache hit, same results
        out = _coll(mesh, topo, "allreduce", "pip_mcoll", z)
        np.testing.assert_allclose(np.asarray(out), np.asarray(z))
    assert runtime.cache_stats().exec_hits == 1


def test_unknown_collective_rejected():
    mesh, topo = _mesh_topo()
    with pytest.raises(ValueError):
        runtime.build(mesh, topo, "gossip", "xla")


def test_build_rejects_auto():
    """auto needs an operand (size/dtype drive selection) — build has none."""
    mesh, topo = _mesh_topo()
    with pytest.raises(ValueError):
        runtime.build(mesh, topo, "allgather", "auto")


# ---------------------------------------------------------------------------
# chunked plans in the exec cache
# ---------------------------------------------------------------------------


def test_exec_cache_chunked_plans_do_not_collide():
    """The same algorithm at different chunk counts compiles different
    programs — the exec-cache key must separate them."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.ones((1, 64), jnp.float32)
    _coll(mesh, topo, "allreduce", "pip_pipeline", z, chunks=1)
    _coll(mesh, topo, "allreduce", "pip_pipeline", z, chunks=2)
    assert runtime.cache_stats().exec_misses == 2, "chunk change re-compiles"
    _coll(mesh, topo, "allreduce", "pip_pipeline", z, chunks=2)
    s = runtime.cache_stats()
    assert s.exec_hits == 1 and s.exec_misses == 2, s


def test_exec_cache_default_chunks_normalized():
    """Omitting ``chunks`` on a chunk-capable algorithm is the same plan as
    ``chunks=1`` — one cache entry, not two."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.ones((1, 64), jnp.float32)
    _coll(mesh, topo, "allreduce", "pip_pipeline", z)
    _coll(mesh, topo, "allreduce", "pip_pipeline", z, chunks=1)
    s = runtime.cache_stats()
    assert s.exec_hits == 1 and s.exec_misses == 1, s


def test_exec_cache_kwargs_normalization_single_entry():
    """The PlanSpec normalization point: ``chunks=None``, ``chunks=1``,
    ``codec=None``, ``codec="none"`` and the bare call are ONE plan — a
    single exec-cache entry through every call-path spelling (the kwargs
    drift that used to risk distinct entries per spelling)."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.ones((1, 64), jnp.float32)
    comm = comm_mod.communicator(mesh, topo)
    comm.allreduce(z, algo="pip_pipeline")
    comm.allreduce(z, algo="pip_pipeline", chunks=1)
    comm.allreduce(z, algo="pip_pipeline", chunks=None)
    comm.allreduce(z, algo="pip_pipeline", codec=None)
    comm.allreduce(z, algo="pip_pipeline", codec="none")
    comm.allreduce(z, algo="pip_pipeline", chunks=None, codec=None)
    s = runtime.cache_stats()
    assert s.exec_misses == 1 and s.exec_hits == 5, s
    # the persistent path of the same plan shares the build cache but pins
    # the operand sharding, so it compiles exactly one more executable —
    # and every later init of the spec is a hit
    op = comm.allreduce_init(z, algo="pip_pipeline", chunks=None, codec=None)
    op2 = comm.allreduce_init(z, algo="pip_pipeline", chunks=1,
                              codec="none")
    s = runtime.cache_stats()
    assert s.exec_misses == 2 and s.exec_hits == 6, s


def test_plan_spec_validates_at_construction():
    """PlanSpec rejects bad knobs before any trace happens."""
    with pytest.raises(ValueError, match="unknown collective"):
        comm_mod.PlanSpec("gossip")
    with pytest.raises(ValueError, match="chunks"):
        comm_mod.PlanSpec("allreduce", chunks=0)
    with pytest.raises(ValueError, match="chunk_bytes"):
        comm_mod.PlanSpec("allreduce", chunk_bytes=0)
    with pytest.raises(ValueError, match="error_budget"):
        comm_mod.PlanSpec("allreduce", error_budget=-0.5)
    with pytest.raises(TypeError, match="schedule"):
        comm_mod.PlanSpec("allreduce", error_budget=lambda s: 0.0)


def test_auto_and_explicit_chunked_callers_share_entries():
    """auto resolves to an (algo, chunks) plan whose exec-cache entry is
    the one an explicit caller of the same plan uses."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.ones((1, 1 << 20), jnp.float32)  # bandwidth regime
    algo, kw = runtime.resolve_algo(topo, "allreduce", "auto", z)
    _coll(mesh, topo, "allreduce", algo, z, **kw)  # explicit
    _coll(mesh, topo, "allreduce", "auto", z)      # auto: hit
    s = runtime.cache_stats()
    assert s.exec_misses == 1 and s.exec_hits == 1, s


def test_chunk_bytes_converts_to_chunks_plan():
    """chunk_bytes is sugar for chunks=ceil(payload/chunk_bytes) and shares
    the cache entry with the equivalent explicit chunks."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.ones((1, 1024), jnp.float32)  # payload 4096 B
    algo, kw = runtime.resolve_algo(topo, "allreduce", "pip_pipeline", z,
                                    {"chunk_bytes": 1024})
    assert algo == "pip_pipeline" and kw == {"chunks": 4, "codec": "none"}, kw
    _coll(mesh, topo, "allreduce", "pip_pipeline", z,
                       chunk_bytes=1024)
    _coll(mesh, topo, "allreduce", "pip_pipeline", z, chunks=4)
    s = runtime.cache_stats()
    assert s.exec_misses == 1 and s.exec_hits == 1, s


def test_chunks_on_non_capable_algo_rejected_clearly():
    """chunks/chunk_bytes with an algorithm that has no pipelined form must
    be a clear resolution-time error, not a TypeError mid-trace."""
    mesh, topo = _mesh_topo()
    z = jnp.ones((1, 64), jnp.float32)
    with pytest.raises(ValueError, match="does not support chunking"):
        _coll(mesh, topo, "allreduce", "xla", z, chunks=2)
    with pytest.raises(ValueError, match="does not support chunking"):
        _coll(mesh, topo, "allreduce", "xla", z, chunk_bytes=64)


def test_calibrate_records_chunked_plans(tmp_path):
    """Calibration measures chunk-count variants for the pipelined
    algorithms and records them under plan keys the selector decodes."""
    from repro.core import autotune as at
    mesh, topo = _mesh_topo()
    sel = at.Selector()
    rows = runtime.calibrate(mesh, topo, names=("allreduce",),
                             sizes=(1 << 20,), iters=1, selector=sel)
    assert any(r.algo == "pip_pipeline" and r.chunks > 1 for r in rows), \
        "no chunked plan measured at a bandwidth-regime size"
    measured = sel.table.lookup(topo, "allreduce", "float32", 1 << 20)
    assert any(at.decode_plan(k)[1] > 1 for k in measured), measured
    s = sel.choose("allreduce", topo, 1 << 20)
    assert s.source == "measured" and s.chunks >= 1


# ---------------------------------------------------------------------------
# codec plans in the exec cache
# ---------------------------------------------------------------------------


def test_exec_cache_codec_plans_do_not_collide():
    """The same algorithm with different codecs compiles different
    programs — the exec-cache key must separate them."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.ones((1, 64), jnp.float32)
    _coll(mesh, topo, "allreduce", "pip_mcoll", z)
    _coll(mesh, topo, "allreduce", "pip_mcoll", z,
                       codec="int8_block")
    assert runtime.cache_stats().exec_misses == 2, "codec change re-compiles"
    _coll(mesh, topo, "allreduce", "pip_mcoll", z,
                       codec="int8_block")
    s = runtime.cache_stats()
    assert s.exec_hits == 1 and s.exec_misses == 2, s


def test_exec_cache_default_codec_normalized():
    """Omitting ``codec`` on a codec-capable algorithm is the same plan as
    ``codec="none"`` — one cache entry, not two; and a zero-budget auto
    resolution shares it too."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    z = jnp.ones((1, 64), jnp.float32)
    _coll(mesh, topo, "allreduce", "pip_mcoll", z)
    _coll(mesh, topo, "allreduce", "pip_mcoll", z, codec="none")
    s = runtime.cache_stats()
    assert s.exec_hits == 1 and s.exec_misses == 1, s


def test_codec_on_non_capable_algo_rejected_clearly():
    mesh, topo = _mesh_topo()
    z = jnp.ones((1, 64), jnp.float32)
    with pytest.raises(ValueError, match="does not support compression"):
        _coll(mesh, topo, "allreduce", "xla", z,
                           codec="int8_block")
    with pytest.raises(ValueError, match="unknown codec"):
        _coll(mesh, topo, "allreduce", "pip_mcoll", z,
                           codec="zstd")


def test_auto_honors_pinned_codec_at_every_size():
    """algo="auto" with a pinned lossy codec must carry the pin into the
    resolved plan even when the selector's lossless winner is not
    codec-capable (small sizes) — never silently drop it."""
    topo = Topology(4, 2, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    for elems in (16, 1 << 20):
        x = jnp.ones((8, elems), jnp.float32)
        algo, kw = runtime.resolve_algo(topo, "allreduce", "auto", x,
                                        {"codec": "int8_block"})
        assert kw.get("codec") == "int8_block", (elems, algo, kw)
        from repro.core import mcoll
        assert mcoll.supports_codec("allreduce", algo), (elems, algo)


def test_auto_rejects_bad_codec_pins():
    """Invalid codec names and codec pins on non-capable algorithms fail
    at resolution; a pin under auto lands on a codec-capable algorithm
    (every collective has one since compressed broadcast/scatter)."""
    topo = Topology(4, 2)
    x = jnp.ones((8, 64), jnp.float32)
    with pytest.raises(ValueError, match="unknown codec"):
        runtime.resolve_algo(topo, "allreduce", "auto", x, {"codec": "zstd"})
    xb = jnp.ones((64,), jnp.float32)
    with pytest.raises(ValueError, match="does not support compression"):
        runtime.resolve_algo(topo, "broadcast", "binomial", xb,
                             {"codec": "int8_block"})
    algo, kw = runtime.resolve_algo(topo, "broadcast", "auto", xb,
                                    {"codec": "int8_block"})
    from repro.core import mcoll
    assert mcoll.supports_codec("broadcast", algo)
    assert kw.get("codec") == "int8_block"


def test_resolve_auto_zero_budget_is_lossless():
    """auto with the default error_budget resolves every collective to a
    lossless plan (codec absent or "none" in the normalized kwargs)."""
    topo = Topology(1, 1)
    for coll in runtime.collectives():
        x = runtime.example_input(coll, topo, 1 << 22)
        algo, kw = runtime.resolve_algo(topo, coll, "auto", x)
        assert kw.get("codec", "none") == "none", (coll, algo, kw)


def test_calibrate_records_codec_plans(tmp_path):
    """Calibration measures codec variants and records them under plan
    keys; a zero-budget selector ignores them, a budgeted one may use
    them."""
    from repro.core import autotune as at
    mesh, topo = _mesh_topo()
    sel = at.Selector()
    rows = runtime.calibrate(mesh, topo, names=("allreduce",),
                             sizes=(1 << 16,), iters=1, selector=sel)
    assert any(r.codec != "none" for r in rows), "no codec plan measured"
    measured = sel.table.lookup(topo, "allreduce", "float32", 1 << 16)
    assert any(at.decode_plan(k)[2] != "none" for k in measured), measured
    assert sel.choose("allreduce", topo, 1 << 16).codec == "none"
    s = sel.choose("allreduce", topo, 1 << 16, error_budget=1.0)
    assert s.source == "measured"


def test_calibrate_codecs_restrictable():
    """codecs=() keeps a calibration sweep lossless-only."""
    from repro.core import autotune as at
    mesh, topo = _mesh_topo()
    sel = at.Selector()
    rows = runtime.calibrate(mesh, topo, names=("allreduce",),
                             sizes=(256,), iters=1, selector=sel,
                             codecs=())
    assert rows and all(r.codec == "none" for r in rows)


# ---------------------------------------------------------------------------
# LRU bounds: shape-diverse traffic cannot grow the caches without limit
# ---------------------------------------------------------------------------


def test_exec_cache_lru_bounded_and_counts_evictions():
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    old = runtime.set_cache_limits()
    runtime.set_cache_limits(max_exec=2)
    try:
        for n in (4, 8, 16):  # 3 distinct shapes through a 2-entry cache
            _coll(mesh, topo, "allgather", "xla",
                               jnp.arange(float(n)))
        s = runtime.cache_stats()
        assert s.exec_misses == 3 and s.exec_evictions == 1
        # oldest entry (n=4) was evicted -> re-miss; newest still hits
        _coll(mesh, topo, "allgather", "xla", jnp.arange(16.0))
        assert runtime.cache_stats().exec_hits == 1
        _coll(mesh, topo, "allgather", "xla", jnp.arange(4.0))
        assert runtime.cache_stats().exec_misses == 4
    finally:
        runtime.set_cache_limits(**{f"max_{k}": v for k, v in old.items()})


def test_exec_cache_lru_recency_order():
    """A hit refreshes recency: the least-recently-USED entry is evicted,
    not the least-recently-inserted."""
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    old = runtime.set_cache_limits()
    runtime.set_cache_limits(max_exec=2)
    try:
        _coll(mesh, topo, "allgather", "xla", jnp.arange(4.0))
        _coll(mesh, topo, "allgather", "xla", jnp.arange(8.0))
        _coll(mesh, topo, "allgather", "xla", jnp.arange(4.0))
        # inserting a third evicts n=8 (LRU), keeping the refreshed n=4
        _coll(mesh, topo, "allgather", "xla", jnp.arange(16.0))
        _coll(mesh, topo, "allgather", "xla", jnp.arange(4.0))
        s = runtime.cache_stats()
        assert s.exec_hits == 2 and s.exec_misses == 3, s
    finally:
        runtime.set_cache_limits(**{f"max_{k}": v for k, v in old.items()})


def test_build_cache_lru_bounded():
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    old = runtime.set_cache_limits()
    runtime.set_cache_limits(max_build=2)
    try:
        for algo in ("xla", "pip_mcoll", "ring"):
            runtime.build(mesh, topo, "allgather", algo)
        s = runtime.cache_stats()
        assert s.build_misses == 3 and s.build_evictions == 1
        runtime.build(mesh, topo, "allgather", "xla")  # evicted -> rebuild
        assert runtime.cache_stats().build_misses == 4
    finally:
        runtime.set_cache_limits(**{f"max_{k}": v for k, v in old.items()})


def test_shrinking_limit_evicts_immediately():
    mesh, topo = _mesh_topo()
    runtime.clear_cache()
    old = runtime.set_cache_limits()
    try:
        for n in (4, 8, 16):
            _coll(mesh, topo, "allgather", "xla",
                               jnp.arange(float(n)))
        assert runtime.cache_stats().exec_evictions == 0
        runtime.set_cache_limits(max_exec=1)
        assert runtime.cache_stats().exec_evictions == 2
    finally:
        runtime.set_cache_limits(**{f"max_{k}": v for k, v in old.items()})


# ---------------------------------------------------------------------------
# regression: runtime.sharded is the only code touching the raw API
# ---------------------------------------------------------------------------


def test_no_direct_shard_map_outside_compat():
    """``jax.shard_map`` is named only in ``core/runtime.py``, and called
    there exactly once (``runtime.sharded``)."""
    pattern = re.compile(
        r"jax\.shard_map|jax\.sharding\.shard_map"
        r"|experimental\.shard_map|experimental import shard_map")
    home = SRC / "repro" / "core" / "runtime.py"
    offenders = []
    for path in sorted(SRC.rglob("*.py")):
        if path == home:
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if pattern.search(line):
                offenders.append(f"{path.relative_to(SRC)}:{i}: {line.strip()}")
    assert not offenders, (
        "direct shard_map references outside core/runtime.py:\n"
        + "\n".join(offenders))
    assert home.read_text().count("jax.shard_map(") == 1
