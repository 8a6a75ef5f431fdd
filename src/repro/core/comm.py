"""Communicator: the object API for collectives — blocking methods plus
persistent, nonblocking ops.

PiP-MColl's multi-object design wins by letting several communication
objects make progress concurrently instead of serializing on one blocking
call; MPI evolved the same way with persistent collectives
(``MPI_Allreduce_init`` / ``MPI_Start`` / ``MPI_Wait`` in MPI Advance) and
with binding collectives to a long-lived communicator object instead of
re-deriving topology per call. This module is that shape on JAX:

  * :class:`Communicator` owns ``(mesh, topo, selector)`` and fronts the
    runtime's build/exec caches (``repro.core.runtime`` is the cache
    backend). One method per collective — ``comm.allreduce(x, algo="auto",
    chunks=..., codec=..., error_budget=...)`` — replaces the old
    stringly-typed free function; kwargs are validated when the plan is
    constructed, not mid-trace.
  * ``comm.split(axes=...)`` makes **groups first-class** (the
    ``MPI_Comm_split`` analog): it returns a child Communicator scoped to
    a sub-topology over the named mesh axes — its collectives run
    independently per group (SPMD: one child object serves every group
    along the orthogonal axes), its tuning table rows are namespaced by
    the group tag, and its plan/exec/persistent caches key on the group
    topology so siblings of identical shape share compiled entries.
    ``split(color=..., key=...)`` handles irregular groups by building a
    sub-mesh per color.
  * :class:`PlanSpec` normalizes the plan knobs exactly once (``chunks=None``
    == ``chunks=1`` == omitted; ``codec=None`` == ``codec="none"`` ==
    omitted; ``chunk_bytes`` folds into ``chunks``), so every call path of
    one plan shares a single exec-cache entry.
  * ``op = comm.allreduce_init(...)`` returns a :class:`PersistentOp`:
    the ``(algo, chunks, codec)`` plan is resolved and the executable
    AOT-compiled exactly once at init; every ``op.start(x)`` reuses it and
    returns a :class:`CollHandle` immediately (JAX async dispatch), so
    ``handle.wait()`` composes into software pipelining — start bucket i's
    allreduce, do other work, then wait. ``depth`` bounds outstanding
    starts (``depth>=2`` = double buffering); ``donate=True`` donates the
    operand buffer on backends that support aliasing.

:func:`communicator` (the per-(mesh, topo) memo below) is the canonical
entry point for hot loops that cannot keep a handle around.
"""
from __future__ import annotations

import dataclasses
import math
import time as _time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune, runtime
from repro.core import telemetry as _tm
from repro.core.topology import Topology


def _default_topo(mesh) -> Optional[Topology]:
    """``Topology.from_mesh`` when the mesh carries the default node/local
    axes; ``None`` (an unscoped root) otherwise."""
    try:
        return Topology.from_mesh(mesh)
    except (KeyError, ValueError):
        return None


# ---------------------------------------------------------------------------
# plan spec: one normalization point for every call path
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """The caller's plan request for one collective invocation, validated
    and normalized at construction.

    Normalization rules (the single place they live):
      * ``chunks=None`` means "unpinned" and is dropped — the resolver
        fills the default (1) or the selector's chunk count, so ``None``,
        ``1`` and "omitted" share one exec-cache entry;
      * ``codec=None`` likewise drops (resolver default ``"none"``);
      * ``chunk_bytes`` is size-relative sugar the resolver converts to a
        concrete ``chunks`` against the operand;
      * ``error_budget`` must be a non-negative float here — schedule
        callables live one level up (the persistent gradient-sync op).
    """

    collective: str
    algo: str = "auto"
    chunks: Optional[int] = None
    chunk_bytes: Optional[int] = None
    codec: Optional[str] = None
    error_budget: float = 0.0
    stacked: bool = True
    #: carry-threaded persistent program: start(x, carry=state) ->
    #: wait() -> (result, new_state). Only meaningful for persistent ops
    #: on carry-capable algorithms (error-feedback allreduce).
    carry: bool = False

    def __post_init__(self):
        if self.collective not in runtime.collectives():
            raise ValueError(f"unknown collective {self.collective!r}; "
                             f"one of {runtime.collectives()}")
        if self.carry and self.collective != "allreduce":
            raise ValueError(
                f"carry state threading is only supported on allreduce "
                f"(error-feedback reductions), not {self.collective!r}")
        if self.chunks is not None and int(self.chunks) < 1:
            raise ValueError(f"chunks must be >= 1, got {self.chunks}")
        if self.chunk_bytes is not None and int(self.chunk_bytes) < 1:
            raise ValueError(
                f"chunk_bytes must be >= 1, got {self.chunk_bytes}")
        if callable(self.error_budget):
            raise TypeError(
                "error_budget schedules (callables) are only accepted by "
                "the persistent gradient-sync op "
                "(train.manual_step.make_overlapped_train_step); "
                "per-call plans need a float")
        if float(self.error_budget) < 0.0:
            raise ValueError(
                f"error_budget must be >= 0, got {self.error_budget}")

    def kwargs(self) -> Dict[str, Any]:
        """The normalized knob dict handed to the resolver (``None`` knobs
        dropped so unpinned and default-pinned calls share cache keys)."""
        kw: Dict[str, Any] = {}
        if self.chunks is not None:
            kw["chunks"] = int(self.chunks)
        if self.chunk_bytes is not None:
            kw["chunk_bytes"] = int(self.chunk_bytes)
        if self.codec is not None:
            kw["codec"] = str(self.codec)
        return kw


class _Proto:
    """Shape/dtype stand-in for plan resolution without a live array."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = jnp.dtype(dtype)

    @property
    def nbytes(self) -> int:
        return int(math.prod(self.shape)) * self.dtype.itemsize


# ---------------------------------------------------------------------------
# persistent nonblocking ops
# ---------------------------------------------------------------------------


class CollHandle:
    """One in-flight persistent-op invocation. ``wait()`` yields the result
    exactly once; a second ``wait`` is a misuse error (like MPI requests,
    which are invalidated by completion)."""

    __slots__ = ("_op", "_value", "_done", "_tags", "_t0")

    def __init__(self, op: "PersistentOp", value, tags=None, t0=0.0):
        self._op = op
        self._value = value
        self._done = False
        self._tags = tags
        self._t0 = t0

    @property
    def done(self) -> bool:
        """True once this handle has been waited on."""
        return self._done

    def wait(self, block: bool = True):
        """Complete the operation and return its result.

        ``block=True`` (default, MPI_Wait semantics) blocks until the
        result is materialized; ``block=False`` returns the async-dispatch
        future immediately — downstream JAX ops compose with it either
        way, so software pipelining just interleaves ``start``/``wait``.
        """
        if self._done:
            raise RuntimeError(
                f"double wait on a {self._op.collective} handle: each "
                f"start(x) yields one result")
        self._done = True
        self._op._inflight -= 1
        if self._tags is None:  # telemetry was off at start()
            if block:
                jax.block_until_ready(self._value)
            return self._value
        # a blocking wait is the host blocked on the collective
        # (``sync_wait``), and a synced wall-clock sample for the drift
        # detector (the result is materialized — no extra device sync)
        with _tm.span("sync_wait" if block else "comm/wait", **self._tags):
            if block:
                jax.block_until_ready(self._value)
        if block:
            op = self._op
            _tm.observe_plan(op.comm.topo, op.collective, str(op.dtype),
                             op._msg_nbytes, op.plan,
                             _time.perf_counter() - self._t0, synced=True)
        return self._value


#: count of live (initialised, not yet released) persistent ops — the
#: rebind-hygiene observable: re-resolving a plan must release the old op,
#: so repeated plan crossings keep this flat instead of growing it
_LIVE_OPS = 0


def live_persistent_ops() -> int:
    """Number of :class:`PersistentOp` objects initialised and not yet
    :meth:`~PersistentOp.release`\\ d (process-wide)."""
    return _LIVE_OPS


class PersistentOp:
    """A persistent collective: plan resolved and executable compiled once
    at init (``comm.<collective>_init``), reused by every ``start``.

    ``start(x) -> CollHandle`` dispatches asynchronously and returns
    immediately; ``handle.wait() -> result`` completes it. At most
    ``depth`` starts may be outstanding (un-waited) at once — ``depth=1``
    is strict request/complete pairing, ``depth>=2`` enables double
    buffering (start bucket i+1 before waiting bucket i).

    ``carry=True`` builds the carry-threaded variant: ``start(x,
    carry=state)`` takes a second operand with the payload's spec and
    ``handle.wait()`` returns ``(result, new_state)`` — per-bucket
    error-feedback residuals riding the persistent compressed allreduce.

    Owners that re-resolve plans must :meth:`release` the op they replace
    (``MPI_Request_free`` analog): release drops the compiled-callable
    reference (the donated-buffer pin with ``donate=True``) and makes any
    later ``start`` a clear error. The compiled executable itself stays in
    the runtime's LRU exec cache, so releasing and re-initialising an
    identical spec never recompiles.
    """

    def __init__(self, comm: "Communicator", collective: str,
                 shape: Tuple[int, ...], dtype, algo: str,
                 kw: Dict[str, Any], *, stacked: bool = True,
                 depth: int = 1, donate: bool = False,
                 carry: bool = False):
        if int(depth) < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.comm = comm
        self.collective = collective
        self.shape = tuple(int(s) for s in shape)
        self.dtype = jnp.dtype(dtype)
        self.algo = algo
        self.kw = dict(kw)
        self.stacked = bool(stacked)
        self.depth = int(depth)
        self.donate = bool(donate)
        self.carry = bool(carry)
        self.starts = 0
        self._inflight = 0
        self._released = False
        total = int(math.prod(self.shape)) * self.dtype.itemsize
        # per-process message bytes in the cost model's convention
        # (mirrors runtime._message_bytes) — the drift detector's size key
        self._msg_nbytes = (max(1, total) if collective == "broadcast"
                            else max(1, total // comm.topo.world))
        global _LIVE_OPS
        self._compiled, self._in_sharding = runtime.compile_persistent(
            comm.mesh, comm.topo, collective, algo, self.shape, self.dtype,
            stacked=stacked, donate=donate, carry=self.carry, **self.kw)
        _tm.counter("comm.persistent_inits").inc()
        _LIVE_OPS += 1

    def _tags(self) -> Dict[str, Any]:
        return _tm.plan_tags(self.collective, self.algo, self.chunks,
                             self.codec, self.comm.topo.group or "",
                             nbytes=self._msg_nbytes)

    @property
    def chunks(self) -> int:
        return int(self.kw.get("chunks", 1))

    @property
    def codec(self) -> str:
        return str(self.kw.get("codec", "none"))

    @property
    def plan(self) -> str:
        """The resolved plan key (``algo#cN@codec``, defaults omitted)."""
        return autotune.encode_plan(self.algo, self.chunks, self.codec)

    @property
    def inflight(self) -> int:
        return self._inflight

    @property
    def released(self) -> bool:
        return self._released

    def release(self) -> None:
        """Free this op (``MPI_Request_free``): drop the compiled-callable
        reference and retire it from the live-op count. Idempotent; any
        ``start`` after release raises. The compiled executable stays in
        the runtime exec cache (re-init of the same spec is a cache hit)."""
        global _LIVE_OPS
        if self._released:
            return
        self._released = True
        self._compiled = None
        _LIVE_OPS -= 1
        _tm.counter("comm.persistent_releases").inc()

    def _check_operand(self, x, what: str = "operand"):
        if not isinstance(x, jax.Array):
            x = jnp.asarray(x)
        if tuple(x.shape) != self.shape or x.dtype != self.dtype:
            raise ValueError(
                f"persistent {self.collective} op compiled for "
                f"{self.shape}/{self.dtype}, got {what} {tuple(x.shape)}/"
                f"{x.dtype}; init a new op for a new operand spec")
        if getattr(x, "sharding", None) != self._in_sharding:
            x = runtime.to_sharding(x, self._in_sharding)
        return x

    def start(self, x, carry=None, **tags) -> CollHandle:
        """Dispatch one invocation of the compiled plan on ``x`` and return
        its handle immediately (no recompile, no cache lookup). A carry op
        additionally takes ``carry=state`` (same spec as ``x``) and its
        handle's ``wait()`` returns ``(result, new_state)``. ``tags``
        (a bucket index, a step number) join the plan's on this call's
        ``comm/start`` and wait spans when telemetry is enabled."""
        if not _tm.enabled():
            return CollHandle(self, self._dispatch(x, carry))
        tags = dict(self._tags(), **tags)
        t0 = _time.perf_counter()
        with _tm.span("comm/start", **tags):
            return CollHandle(self, self._dispatch(x, carry), tags, t0)

    def _dispatch(self, x, carry):
        if self._released:
            raise RuntimeError(
                f"start() on a released {self.collective} persistent op; "
                f"init a new op (release() retired this one)")
        if self._inflight >= self.depth:
            raise RuntimeError(
                f"{self.collective} persistent op already has "
                f"{self._inflight} outstanding start(s) at depth="
                f"{self.depth}; wait() the previous handle first, or init "
                f"with depth>=2 for double buffering")
        if self.carry != (carry is not None):
            raise ValueError(
                f"{self.collective} persistent op was compiled with "
                f"carry={self.carry}; start() "
                + ("requires carry=state" if self.carry
                   else "does not take a carry operand"))
        x = self._check_operand(x)
        if self.carry:
            carry = self._check_operand(carry, what="carry")
        self._inflight += 1
        self.starts += 1
        if self.carry:
            return self._compiled(x, carry)
        return self._compiled(x)

    def __call__(self, x, carry=None):
        """Blocking convenience: ``start(x).wait()``."""
        return self.start(x, carry=carry).wait()


# ---------------------------------------------------------------------------
# the communicator
# ---------------------------------------------------------------------------


class Communicator:
    """A long-lived collective context bound to ``(mesh, topo)``.

    Owns the selector handle and fronts the runtime's build/exec caches;
    exposes one blocking method per collective plus ``*_init`` constructors
    for persistent nonblocking ops, and :meth:`split` for sub-communicators
    over a subset of the mesh. Construct once per (mesh, topology) and
    reuse — or use :func:`communicator` for the process-wide memo.

    A Communicator built on a mesh whose axes don't map onto the default
    node/local topology (e.g. a 3-axis MoE mesh) is an **unscoped root**:
    ``split(axes=...)`` works, collective methods raise until scoped.
    """

    def __init__(self, mesh, topo: Optional[Topology] = None, *,
                 selector: Optional[autotune.Selector] = None):
        self.mesh = mesh
        if topo is None:
            topo = _default_topo(mesh)
        self.topo = topo
        self.selector = (selector if selector is not None
                         else autotune.default_selector())
        self._groups: Dict[tuple, "Communicator"] = {}

    def __repr__(self) -> str:
        if self.topo is None:
            return (f"Communicator(unscoped root, "
                    f"mesh axes={tuple(self.mesh.axis_names)})")
        grp = f", group={self.topo.group!r}" if self.topo.group else ""
        return (f"Communicator({self.topo.n_nodes}x{self.topo.n_local}, "
                f"axes={self.topo.axes}{grp})")

    def _require_topo(self) -> Topology:
        if self.topo is None:
            raise ValueError(
                "this Communicator is an unscoped root — mesh axes "
                f"{tuple(self.mesh.axis_names)} do not map onto the default "
                "node/local topology; call split(axes=...) to scope it to a "
                "group before running collectives")
        return self.topo

    # -- sub-communicators --------------------------------------------------

    def split(self, axes=None, *, color=None, key=None,
              group: Optional[str] = None):
        """The ``MPI_Comm_split`` analog: derive child communicator(s)
        scoped to a subset of this communicator's processes.

        Two forms:

        ``split(axes=...)`` — regular (mesh-aligned) groups. ``axes`` is
        one mesh axis name or a ``(node_axis, local_axis)`` pair; the child
        shares this mesh and runs every group along the orthogonal axes in
        one SPMD program, so a single child object serves all siblings.
        Its :class:`~repro.core.topology.Topology` is derived with
        :meth:`Topology.subset` (link classes inherited from the parent
        where the axis matches), its tuning-table rows carry the group tag
        (``group=`` overrides the default ``"x".join(axes)``), and because
        children are memoized here, repeated splits of the same spec share
        plan/exec/persistent caches.

        ``split(color=..., key=...)`` — irregular groups. ``color`` is a
        sequence of ``world`` ints (one per parent rank, parent flat device
        order); ranks with equal color form a group, ordered by
        ``(key[rank], rank)`` (``key`` defaults to parent rank). Returns
        ``{color: Communicator}``, each on its own ``(1, group_size)``
        sub-mesh. Use this for groups that don't align with mesh axes.

        Splitting a child again (split-of-split) composes naturally.
        """
        if (axes is None) == (color is None):
            raise ValueError("split() takes exactly one of axes= or color=")
        if axes is not None:
            if key is not None:
                raise ValueError("key= only applies to color splits")
            ax = (axes,) if isinstance(axes, str) else tuple(axes)
            gk = ("axes", ax, group)
            hit = self._groups.get(gk)
            if hit is None:
                topo = Topology.subset(self.mesh, ax, parent=self.topo,
                                       group=group)
                hit = self._groups[gk] = Communicator(
                    self.mesh, topo, selector=self.selector)
            return hit
        return self._split_color(color, key, group)

    def _split_color(self, color, key, group: Optional[str]
                     ) -> Dict[Any, "Communicator"]:
        devices = list(np.asarray(self.mesh.devices).flat)
        world = len(devices)
        color = tuple(int(c) for c in color)
        if len(color) != world:
            raise ValueError(
                f"color needs one entry per parent rank: got {len(color)} "
                f"for world {world}")
        key = (tuple(range(world)) if key is None
               else tuple(int(k) for k in key))
        if len(key) != world:
            raise ValueError(
                f"key needs one entry per parent rank: got {len(key)} "
                f"for world {world}")
        gk = ("color", color, key, group)
        hit = self._groups.get(gk)
        if hit is None:
            hit = {}
            for c in sorted(set(color)):
                ranks = sorted((r for r in range(world) if color[r] == c),
                               key=lambda r: (key[r], r))
                sub = jax.sharding.Mesh(
                    np.asarray([devices[r] for r in ranks]).reshape(
                        1, len(ranks)),
                    ("node", "local"))
                tag = group if group is not None else f"color{c}"
                topo = dataclasses.replace(Topology.from_mesh(sub),
                                           group=tag)
                hit[c] = Communicator(sub, topo, selector=self.selector)
            self._groups[gk] = hit
        return dict(hit)

    # -- plan resolution ----------------------------------------------------

    def plan(self, collective: str, nbytes: int, dtype: str = "float32",
             error_budget: float = 0.0) -> autotune.Selection:
        """The selector's ``(algo, chunks, codec)`` plan for one payload
        size on this communicator's topology (consumers that execute inside
        their own shard_map bodies — MoE dispatch/combine, the fused train
        step — resolve here and run the mcoll algorithm themselves)."""
        return self.selector.choose(collective, self._require_topo(),
                                    int(nbytes), dtype=dtype,
                                    error_budget=float(error_budget))

    def _resolve(self, spec: PlanSpec, proto, extra: Dict[str, Any]
                 ) -> Tuple[str, Dict[str, Any]]:
        kw = spec.kwargs()
        overlap = set(kw) & set(extra)
        if overlap:
            raise ValueError(f"duplicate plan knobs {sorted(overlap)}")
        kw.update(extra)
        topo = self._require_topo()
        with _tm.span("comm/plan_resolve", collective=spec.collective,
                      requested=spec.algo):
            return runtime.resolve_algo(topo, spec.collective, spec.algo,
                                        proto, kw,
                                        error_budget=spec.error_budget,
                                        selector=self.selector)

    # -- blocking methods ---------------------------------------------------

    def _call(self, name: str, x, *, algo: str = "auto",
              chunks: Optional[int] = None,
              chunk_bytes: Optional[int] = None,
              codec: Optional[str] = None, error_budget: float = 0.0,
              stacked: bool = True, **kw):
        spec = PlanSpec(name, algo, chunks, chunk_bytes, codec,
                        error_budget, stacked)
        x = runtime.global_operand(self.mesh, name, x)
        algo_r, kw_r = self._resolve(spec, x, kw)
        return runtime.run_resolved(self.mesh, self._require_topo(), name,
                                    algo_r, x, stacked=stacked, **kw_r)

    def allreduce(self, x, **knobs):
        """Sum-allreduce: in ``(world, m, ...)`` sharded dim0, out the
        reduced payload stacked per device. Knobs: ``algo`` (default
        "auto"), ``chunks``/``chunk_bytes``, ``codec``, ``error_budget``,
        plus algorithm-specific kwargs (``radix``, ``inter``, ...)."""
        return self._call("allreduce", x, **knobs)

    def reduce_scatter(self, x, **knobs):
        """Reduce-scatter: in ``(world, world*s, ...)`` sharded dim0, out
        each device's reduced shard (global ``(world*s, ...)``)."""
        return self._call("reduce_scatter", x, **knobs)

    def allgather(self, x, *, stacked: bool = True, **knobs):
        """Allgather: in ``(world*m, ...)`` sharded dim0; out stacked
        ``(world, world*m, ...)`` (row d = device d's full copy) or the
        replicated gather with ``stacked=False``."""
        return self._call("allgather", x, stacked=stacked, **knobs)

    def alltoall(self, x, **knobs):
        """All-to-all: in ``(world, world, s...)`` sharded dim0, out the
        transposed exchange."""
        return self._call("alltoall", x, **knobs)

    def broadcast(self, x, **knobs):
        """Broadcast from ``root`` (default 0): in ``(m, ...)`` replicated,
        out stacked ``(world, m, ...)``."""
        return self._call("broadcast", x, **knobs)

    def scatter(self, x, **knobs):
        """Scatter from ``root`` (default 0): in ``(world*m, ...)``
        replicated, out each device's shard."""
        return self._call("scatter", x, **knobs)

    def invoke(self, name: str, x, **knobs):
        """Name-indexed dispatch to the blocking methods (parametrized
        sweeps); new call sites should prefer the per-collective
        methods."""
        method = getattr(self, name, None)
        if name not in runtime.collectives() or method is None:
            raise ValueError(f"unknown collective {name!r}; "
                             f"one of {runtime.collectives()}")
        return method(x, **knobs)

    # -- persistent nonblocking ops -----------------------------------------

    def persistent(self, name: str, x=None, *, shape=None, dtype=None,
                   algo: str = "auto", chunks: Optional[int] = None,
                   chunk_bytes: Optional[int] = None,
                   codec: Optional[str] = None, error_budget: float = 0.0,
                   stacked: bool = True, depth: int = 1,
                   donate: bool = False, carry: bool = False,
                   **kw) -> PersistentOp:
        """Init a :class:`PersistentOp` for ``name`` on a fixed operand
        spec — pass an example operand ``x`` (array or ShapeDtypeStruct) or
        explicit ``shape=``/``dtype=``. The ``(algo, chunks, codec)`` plan
        is resolved and the executable compiled here, once.

        ``carry=True`` (allreduce only) threads a per-op state operand:
        ``op.start(x, carry=state)``; ``handle.wait()`` returns
        ``(result, new_state)`` — the error-feedback hookup for
        compressed gradient sync. The resolved algorithm must accept an
        ``err`` state (the pip family does; ``xla``/``flat_rd`` do not)."""
        if x is not None:
            shape = tuple(x.shape)
            dtype = x.dtype
        if shape is None or dtype is None:
            raise ValueError("persistent op needs an example operand x or "
                             "explicit shape= and dtype=")
        spec = PlanSpec(name, algo, chunks, chunk_bytes, codec,
                        error_budget, stacked, carry)
        proto = _Proto(shape, dtype)
        algo_r, kw_r = self._resolve(spec, proto, kw)
        return PersistentOp(self, name, proto.shape, proto.dtype, algo_r,
                            kw_r, stacked=stacked, depth=depth,
                            donate=donate, carry=carry)

    def allreduce_init(self, x=None, **knobs) -> PersistentOp:
        return self.persistent("allreduce", x, **knobs)

    def reduce_scatter_init(self, x=None, **knobs) -> PersistentOp:
        return self.persistent("reduce_scatter", x, **knobs)

    def allgather_init(self, x=None, **knobs) -> PersistentOp:
        return self.persistent("allgather", x, **knobs)

    def alltoall_init(self, x=None, **knobs) -> PersistentOp:
        return self.persistent("alltoall", x, **knobs)

    def broadcast_init(self, x=None, **knobs) -> PersistentOp:
        return self.persistent("broadcast", x, **knobs)

    def scatter_init(self, x=None, **knobs) -> PersistentOp:
        return self.persistent("scatter", x, **knobs)

    def split_lattice(self) -> Tuple["Communicator", ...]:
        """Every mesh-aligned split child of this communicator: one per
        single active (size > 1) axis, plus the full multi-axis group when
        more than one axis is active — e.g. a 2x4 mesh yields the
        ``("node",)``, ``("local",)`` and ``("node", "local")`` children.
        Children are the same memoized objects :meth:`split` returns."""
        topo = self._require_topo()
        axes = tuple(topo.active_axes)
        combos = [(a,) for a in axes]
        if len(axes) > 1:
            combos.append(tuple(axes))
        return tuple(self.split(axes=c) for c in combos)

    # -- calibration / observability passthroughs ---------------------------

    def calibrate(self, include_splits: bool = False, **kw):
        """Timed plan sweeps into this communicator's selector table
        (see ``runtime.calibrate``).

        ``include_splits=True`` additionally walks :meth:`split_lattice`
        and calibrates every mesh-aligned split child, so each group
        topology lands measured ``/g:``-keyed tuning rows *before* first
        use — a fresh ``comm.split(axes=...)`` then resolves
        ``algo="auto"`` from measurement instead of the cost-model prior.
        All rows land in the shared selector table; ``path=`` (when given)
        is saved once, after the whole lattice.

        Under a multi-controller runtime every process runs the same sweeps
        (SPMD — the timed programs are cross-process collectives), then the
        per-process tables are folded into rank 0's
        (``distributed.backend.merge_tuning_table``) so ``path=`` is
        written exactly once, by rank 0, with every rank's rows."""
        from repro.distributed import backend as _dist
        kw.setdefault("selector", self.selector)
        path = kw.pop("path", None)
        rows = list(runtime.calibrate(self.mesh, self._require_topo(), **kw))
        if include_splits:
            for child in self.split_lattice():
                rows.extend(runtime.calibrate(child.mesh, child.topo, **kw))
        if _dist.is_multiprocess():
            _dist.merge_tuning_table(self.selector.table)
        if path is not None and _dist.process_rank() == 0:
            self.selector.table.save(path)
        _dist.barrier("comm.calibrate/saved")
        return rows

    def cache_stats(self) -> "runtime.CacheStats":
        return runtime.cache_stats()

    def selection_stats(self) -> autotune.SelectionStats:
        return self.selector.stats


# ---------------------------------------------------------------------------
# process-wide memo
# ---------------------------------------------------------------------------


_COMMS: Dict[tuple, Communicator] = {}


def communicator(mesh, topo: Optional[Topology] = None) -> Communicator:
    """The memoized per-(mesh, topo) Communicator: repeated lookups from
    hot loops share one object per context instead of re-deriving it per
    call — and, because :meth:`Communicator.split` memoizes its children,
    per split spec too. On a mesh without the default node/local axes this
    returns the unscoped root (``split(axes=...)`` to scope it)."""
    t = topo if topo is not None else _default_topo(mesh)
    key = (mesh, t)
    hit = _COMMS.get(key)
    if hit is None:
        hit = _COMMS[key] = Communicator(mesh, t)
    return hit
