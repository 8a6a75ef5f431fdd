"""Collective runtime: the single entry point for building and invoking
shard_map'd collectives.

This layer owns, for the whole codebase:

  1. **one shard_map call site** — all shard_map construction flows
     through :func:`sharded` (the only place in ``src/`` that names
     ``jax.shard_map``), so a JAX API move is absorbed in one place;
  2. **wiring** — the per-collective ``body`` / ``in_specs`` / ``out_specs``
     conventions live in the declarative :data:`_WIRING` table instead of
     being re-derived at every call site;
  3. **caching** — mirroring how mpi4jax funnels every MPI primitive through
     one token-threaded dispatch layer, repeated invocations from
     training / serving / benchmark loops reuse both the built callable
     (keyed on mesh + collective + algo + kwargs) and the AOT-compiled
     executable (additionally keyed on input shape/dtype). Both caches are
     LRU-bounded (:func:`set_cache_limits`) so shape-diverse serving
     traffic cannot grow them without limit; evictions are counted in
     :class:`CacheStats`.
  4. **algorithm selection** — ``algo="auto"`` resolves through the
     selection subsystem (``repro.core.autotune``: cost-model priors +
     measured calibration) at exec-cache time, keyed on the *resolved*
     algorithm so auto and explicit callers share cache entries. The
     resolution is a full ``(algo, chunks, codec)`` plan (tuning-table key
     ``algo#cN@codec``): the chunk count and codec are normalized into the
     kwargs (and therefore the exec-cache key), ``chunk_bytes=<b>`` is
     accepted as a size-relative way to pin the chunking, and
     ``error_budget=<eps>`` gates which error-bounded codecs
     (``repro.core.compress``) auto may pick (0.0 = lossless only).

Since the Communicator API landed (``repro.core.comm``), this module is the
**cache backend**: construction, compilation and plan resolution live here;
the supported user-facing surface is ``comm.Communicator`` (one method per
collective, persistent nonblocking ops, ``comm.split`` sub-communicators).

Public API:

  * :func:`run` — execute a collective through the compiled-callable cache
    (the backend entry point ``Communicator`` methods call); ``algo="auto"``
    picks the algorithm per (topology, collective, dtype, size).
  * :func:`build` — get the cached jitted callable for a collective key.
  * :func:`compile_persistent` — AOT-compile one plan for a fixed
    shape/dtype with a pinned input sharding (the ``PersistentOp`` backend;
    entries share the exec cache, so re-initialising an op is a hit).
  * :func:`sharded` — the one shard_map wrapper for custom bodies (MoE
    expert-parallel dispatch, the manual train step, ad-hoc checks).
  * :func:`calibrate` — timed sweeps feeding the selector's tuning table.
  * :func:`cache_stats` / :func:`selection_stats` / :func:`clear_cache` —
    observe / reset the caches and the selector.
"""
from __future__ import annotations

import dataclasses
import inspect
import time as _time
from collections import OrderedDict
from functools import lru_cache, partial
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import autotune
from repro.core import compress as _codecs
from repro.core import mcoll as _mcoll
from repro.core import telemetry as _tm
from repro.core.topology import Topology

AUTO = "auto"

# ---------------------------------------------------------------------------
# the one shard_map call site
# ---------------------------------------------------------------------------


def sharded(body: Callable, mesh, in_specs: Any, out_specs: Any,
            check: bool = False) -> Callable:
    """Wrap ``body`` with ``jax.shard_map`` over ``mesh``; ``check`` is its
    ``check_vma`` (verify the varying-manual-axes typing of the outputs).

    This is the supported way to shard_map a custom body anywhere in the
    codebase; it keeps the direct JAX-API reference in this one function.
    """
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# ---------------------------------------------------------------------------
# declarative wiring table: collective -> shard_map conventions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Wiring:
    """How one collective maps global arrays onto per-device bodies.

    in_mode:    "shard"     input dim0 sharded over the flat (node, local)
                            axis tuple,
                "replicate" input replicated,
                "row"       input dim0 sharded, each device's shard is one
                            leading row (the body consumes ``x[0]``).
    out_mode:   "stack"     per-device results stacked along a new dim0
                            (row d = device d's result),
                "shard"     output dim0 sharded,
                "replicate" output replicated.
    take_row0:  body consumes ``x[0]`` rather than ``x``.
    stackable:  honors ``stacked=False`` by switching out_mode to
                "replicate" (allgather's replicated-output variant).
    """

    in_mode: str
    out_mode: str
    take_row0: bool = False
    stackable: bool = False


_WIRING: Dict[str, Wiring] = {
    "allgather": Wiring("shard", "stack", stackable=True),
    "scatter": Wiring("replicate", "shard"),
    "broadcast": Wiring("replicate", "stack"),
    "allreduce": Wiring("row", "stack", take_row0=True),
    "reduce_scatter": Wiring("row", "shard", take_row0=True),
    "alltoall": Wiring("row", "stack", take_row0=True),
}


def _in_spec(mode: str, ax) -> P:
    return {"shard": P(ax), "replicate": P(None), "row": P(ax, None)}[mode]


def _out_spec(mode: str, ax) -> P:
    return {"stack": P(ax, None), "shard": P(ax), "replicate": P(None)}[mode]


def collectives() -> Tuple[str, ...]:
    return tuple(sorted(_WIRING))


def algorithms(collective: str):
    """Algorithm names registered for ``collective`` (see core.mcoll)."""
    return _mcoll.algorithms(collective)


# ---------------------------------------------------------------------------
# caches (LRU-bounded)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    build_hits: int = 0
    build_misses: int = 0
    build_evictions: int = 0
    exec_hits: int = 0
    exec_misses: int = 0
    exec_evictions: int = 0

    @property
    def exec_hit_rate(self) -> float:
        total = self.exec_hits + self.exec_misses
        return self.exec_hits / total if total else 0.0

    def reset(self) -> None:
        """Zero every counter in place (handles stay live) — per-phase
        assertions in checks start from a clean baseline instead of
        subtracting process-lifetime totals by hand."""
        self.build_hits = self.build_misses = self.build_evictions = 0
        self.exec_hits = self.exec_misses = self.exec_evictions = 0


_DEFAULT_MAX_BUILD = 256
_DEFAULT_MAX_EXEC = 1024

_BUILD_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_EXEC_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_LIMITS = {"build": _DEFAULT_MAX_BUILD, "exec": _DEFAULT_MAX_EXEC}
_STATS = CacheStats()


def cache_stats() -> CacheStats:
    return _STATS


def selection_stats() -> autotune.SelectionStats:
    """Selection counters of the default selector (the one ``algo="auto"``
    resolves through) — lives next to cache_stats for observability."""
    return autotune.default_selector().stats


def set_cache_limits(max_build: Optional[int] = None,
                     max_exec: Optional[int] = None) -> Dict[str, int]:
    """Set LRU bounds (entries) for the build/exec caches; None leaves a
    bound unchanged. Returns the active limits. Shrinking evicts oldest
    entries immediately (counted in CacheStats)."""
    if max_build is not None:
        _LIMITS["build"] = int(max_build)
    if max_exec is not None:
        _LIMITS["exec"] = int(max_exec)
    _evict(_BUILD_CACHE, "build")
    _evict(_EXEC_CACHE, "exec")
    return dict(_LIMITS)


def _evict(cache: "OrderedDict", which: str) -> None:
    limit = max(1, _LIMITS[which])
    while len(cache) > limit:
        cache.popitem(last=False)
        if which == "build":
            _STATS.build_evictions += 1
        else:
            _STATS.exec_evictions += 1


def clear_cache() -> None:
    _BUILD_CACHE.clear()
    _EXEC_CACHE.clear()
    _STATS.reset()  # in place, so handles from cache_stats() stay live


def _kw_key(kw: Dict[str, Any]) -> tuple:
    return tuple(sorted(kw.items()))


def _span_tags(topo: Topology, collective: str, algo: str,
               kw: Dict[str, Any], nbytes: Optional[int] = None
               ) -> Dict[str, Any]:
    """Telemetry tag dict for one resolved plan at a runtime boundary."""
    return _tm.plan_tags(collective, algo, int(kw.get("chunks", 1)),
                         str(kw.get("codec", "none")), topo.group or "",
                         nbytes=nbytes)


# ---------------------------------------------------------------------------
# algorithm resolution (algo="auto")
# ---------------------------------------------------------------------------


def _message_bytes(collective: str, topo: Topology, x) -> int:
    """Per-process message size in the cost model's conventions, from the
    *global* runtime operand: broadcast's operand is the per-process payload
    itself; every other collective's operand carries all ``world`` shards."""
    if collective == "broadcast":
        return max(1, int(x.nbytes))
    return max(1, int(x.nbytes) // topo.world)


@lru_cache(maxsize=None)  # one small frozenset per algorithm function
def _accepted_params(fn: Callable) -> frozenset:
    return frozenset(inspect.signature(fn).parameters)


def _filter_kwargs(fn: Callable, kw: Dict[str, Any]) -> Dict[str, Any]:
    """Keep only kwargs the algorithm function accepts (an auto-resolved
    algorithm must not choke on another algorithm's tuning knobs)."""
    if not kw:
        return kw
    params = _accepted_params(fn)
    return {k: v for k, v in kw.items() if k in params}


def resolve_algo(topo: Topology, collective: str, algo: str, x,
                 kw: Optional[Dict[str, Any]] = None,
                 error_budget: float = 0.0,
                 selector: Optional[autotune.Selector] = None
                 ) -> Tuple[str, Dict[str, Any]]:
    """Resolve ``algo`` ("auto" -> selector (algo, chunks, codec) plan)
    for operand ``x``.

    Returns (resolved_algo, normalized_kwargs). Explicit algorithm names
    pass through untouched; chunk and codec knobs are normalized either
    way so exec-cache keys are shared between auto and explicit callers of
    the same plan:

      * ``chunk_bytes=<b>`` converts to ``chunks=ceil(payload/b)`` against
        the per-process payload of ``x`` (so one knob serves every size);
      * a chunk-capable algorithm always carries an explicit ``chunks``
        entry (default 1), and a codec-capable one an explicit ``codec``
        entry (default "none"), so the default knobs and "no kwarg" are
        one cache key;
      * ``algo="auto"`` fills ``chunks``/``codec`` from the selector's
        plan unless the caller pinned them; ``error_budget`` (also
        accepted inside ``kw``) gates which codecs the selector may pick
        (0.0 = lossless only); ``selector`` overrides the process-wide
        default (a Communicator passes its own).
    """
    kw = dict(kw or {})
    budget = kw.pop("error_budget", None)
    if budget is None:
        budget = error_budget
    nbytes = _message_bytes(collective, topo, x)
    cb = kw.pop("chunk_bytes", None)
    if cb:
        kw.setdefault("chunks", max(1, -(-nbytes // int(cb))))
    if algo != AUTO:
        try:
            fn = _mcoll.algorithm(collective, algo)
        except KeyError:
            raise ValueError(
                f"unknown algorithm {algo!r} for {collective}; one of "
                f"{_mcoll.algorithms(collective)}") from None
        if _mcoll.supports_chunks(collective, algo):
            kw["chunks"] = int(kw.get("chunks", 1))
        elif "chunks" in kw:
            # fail clearly at resolution time, not as an opaque TypeError
            # deep inside trace (the auto path filters this instead)
            raise ValueError(
                f"{collective}/{algo} does not support chunking; "
                f"chunk-capable algorithms: "
                f"{sorted(_mcoll.CHUNKED[collective]) or 'none'}")
        if _mcoll.supports_codec(collective, algo):
            cdd = str(kw.get("codec", _codecs.NONE))
            _codecs.codec(cdd)  # validate the name at resolution time
            if cdd != _codecs.NONE and not _codecs.admissible(
                    cdd, collective,
                    max(float(budget), _codecs.meta(cdd).error_bound),
                    jnp.issubdtype(x.dtype, jnp.integer)):
                # fail at resolution time with the domain reason, not as a
                # trace-time error deep inside the algorithm body
                raise ValueError(
                    f"codec {cdd!r} is not admissible for {collective} on "
                    f"dtype {x.dtype} (lossy codecs never touch integer "
                    f"payloads; integer-only codecs need integer payloads "
                    f"on non-reducing collectives)")
            kw["codec"] = cdd
        elif kw.get("codec", _codecs.NONE) != _codecs.NONE:
            raise ValueError(
                f"{collective}/{algo} does not support compression; "
                f"codec-capable algorithms: "
                f"{sorted(_mcoll.COMPRESSED[collective]) or 'none'}")
        else:
            kw.pop("codec", None)
        # plan-time kwarg validation: an unsupported knob must be a clear
        # resolution error, not a TypeError deep inside trace
        bad = set(kw) - _accepted_params(fn)
        if bad:
            raise ValueError(
                f"{collective}/{algo} got unsupported kwargs "
                f"{sorted(bad)}; accepted: "
                f"{sorted(_accepted_params(fn) - {'x', 'y', 'z', 'topo'})}")
        return algo, kw
    pinned_codec = kw.get("codec")
    if pinned_codec is not None:
        pinned_codec = str(pinned_codec)
        _codecs.codec(pinned_codec)  # validate the name before selection
        if pinned_codec != _codecs.NONE:
            if not any(_mcoll.supports_codec(collective, a)
                       for a in autotune.candidates(collective, topo)):
                raise ValueError(
                    f"{collective} has no codec-capable algorithm; "
                    f"codec={pinned_codec!r} cannot be honored")
            # pinning a lossy codec IS an accuracy contract: selection
            # must admit it even when no explicit budget was given
            budget = max(float(budget),
                         _codecs.meta(pinned_codec).error_bound)
            if not _codecs.admissible(pinned_codec, collective,
                                      float(budget),
                                      jnp.issubdtype(x.dtype, jnp.integer)):
                raise ValueError(
                    f"codec {pinned_codec!r} is not admissible for "
                    f"{collective} on dtype {x.dtype} (lossy codecs never "
                    f"touch integer payloads; integer-only codecs need "
                    f"integer payloads on non-reducing collectives)")
    sel = (selector if selector is not None
           else autotune.default_selector()).choose(
        collective, topo, nbytes, dtype=str(x.dtype),
        error_budget=float(budget))
    algo, chunks = sel.algo, sel.chunks
    if pinned_codec not in (None, _codecs.NONE) and \
            not _mcoll.supports_codec(collective, algo):
        # the selector's winner cannot carry the pinned codec (e.g. a
        # latency-regime algorithm): honor the pin by taking the cheapest
        # codec-capable plan instead of silently dropping the knob
        from repro.core import costmodel
        net = costmodel.net_for(topo)
        cnet = costmodel.codec_net(net, topo, pinned_codec)
        best = None
        for a in autotune.candidates(collective, topo):
            if not _mcoll.supports_codec(collective, a):
                continue
            try:
                c = (costmodel.optimal_chunks(collective, a, topo, nbytes,
                                              cnet)
                     if _mcoll.supports_chunks(collective, a) else 1)
                t = costmodel.plan_cost(collective, a, topo, nbytes, net,
                                        chunks=c, codec=pinned_codec).time
            except ValueError:  # implemented but not modeled (cf. choose)
                t, c = float("inf"), 1
            if best is None or t < best[0]:
                best = (t, a, c)
        # the capability pre-check above guarantees >=1 codec-capable
        # candidate, so best is always set (unmodeled ones rank last)
        _, algo, chunks = best
    kw = _filter_kwargs(_mcoll.algorithm(collective, algo), kw)
    if _mcoll.supports_chunks(collective, algo):
        kw["chunks"] = int(kw.get("chunks", chunks or 1))
    if _mcoll.supports_codec(collective, algo):
        kw["codec"] = str(kw.get("codec", sel.codec or _codecs.NONE))
    return algo, kw


# ---------------------------------------------------------------------------
# construction + compiled-callable cache
# ---------------------------------------------------------------------------


def supports_carry(collective: str, algo: str) -> bool:
    """Whether ``(collective, algo)`` can run as a carry-threaded persistent
    program: the algorithm must accept an ``err`` state operand (the
    error-feedback carry of the compressed reductions)."""
    try:
        fn = _mcoll.algorithm(collective, algo)
    except KeyError:
        return False
    return "err" in _accepted_params(fn)


def _construct(mesh, topo: Topology, collective: str, algo: str,
               stacked: bool, jit: bool, donate: bool,
               carry: bool = False, **kw) -> Callable:
    wiring = _WIRING[collective]
    fn = partial(_mcoll.algorithm(collective, algo), topo=topo, **kw)
    # shard over ALL mesh axes, not just the topology's: operands stay
    # global (dim0 spans every device of the mesh) while the algorithm
    # communicates only over topo's axes — so a sub-communicator group
    # (topo covering a subset of the mesh) runs independently per group
    # and out row d is device d's within-group result. For a topology
    # covering the whole mesh this is the same spec as before.
    ax = tuple(mesh.axis_names)
    out_mode = wiring.out_mode
    if wiring.stackable and not stacked:
        out_mode = "replicate"
    take_row0, stack_out = wiring.take_row0, out_mode == "stack"

    if carry:
        # carry-threaded variant: a second state operand rides the same
        # wiring as the payload (error-feedback residuals live at
        # device-dependent offsets, so both are "row"-sharded) and a fresh
        # state comes back next to the result — op.start(x, carry=e) ->
        # (y, new_e). Only algorithms that accept err can be built this way.
        if not (take_row0 and stack_out):
            raise ValueError(
                f"carry operand needs row-in/stack-out wiring; "
                f"{collective} is {wiring.in_mode}/{wiring.out_mode}")
        if not supports_carry(collective, algo):
            raise ValueError(
                f"{collective}/{algo} does not thread a carry (no err "
                f"state operand); carry-capable allreduce algorithms: "
                f"{[a for a in _mcoll.algorithms(collective) if supports_carry(collective, a)]}")

        def body_carry(x, e):
            y, ne = fn(x[0], err=e[0])
            return y[None], ne[None]

        # the program's name: its HLO module, and so the profiler's
        # ``XLA Modules`` line, reads ``jit_<collective>.<algo>``
        body_carry.__name__ = f"{collective}.{algo}"
        spec = _in_spec(wiring.in_mode, ax)
        mapped = sharded(body_carry, mesh, in_specs=(spec, spec),
                         out_specs=(_out_spec(out_mode, ax),) * 2,
                         check=False)
        if not jit:
            return mapped
        return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())

    def body(x):
        y = fn(x[0] if take_row0 else x)
        return y[None] if stack_out else y

    body.__name__ = f"{collective}.{algo}"  # as body_carry's
    mapped = sharded(body, mesh, in_specs=(_in_spec(wiring.in_mode, ax),),
                     out_specs=_out_spec(out_mode, ax), check=False)
    if not jit:
        return mapped
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def build(mesh, topo: Topology, collective: str, algo: str, *,
          stacked: bool = True, jit: bool = True, donate: bool = False,
          carry: bool = False, **kw) -> Callable:
    """Build (or fetch from cache) the jitted shard_map'd callable for one
    collective key. Identical keys return the identical callable object, so
    jit's trace cache is shared across call sites.

    Key: (mesh axes/shape/devices, collective, algo, stacked, jit, donate,
    kwargs). Input shape/dtype enter at :func:`run` time via jit's own
    trace cache (and explicitly in the exec cache). ``donate=True`` donates
    the operand buffer to the computation (persistent double-buffered ops
    on backends that support aliasing).

    Input/output conventions (global arrays; D = mesh devices, G =
    ``topo.world`` — equal for a root communicator, G < D for a
    sub-communicator group, where every device's result is computed within
    its own group):
      allgather:      in (D*m, ...) sharded dim0 -> out (D, G*m, ...)
                      stacked (row d = device d's group copy) or
                      (G*m, ...) replicated when G == D.
      scatter:        in (G*m, ...) replicated   -> out (D*m, ...) sharded
                      (device d's shard = its within-group scatter share).
      broadcast:      in (m, ...) replicated     -> out (D, m, ...) stacked.
      allreduce:      in (D, m, ...) sharded dim0 -> out (D, m, ...)
                      stacked (row d = device d's group-reduced vector).
      reduce_scatter: in (D, G*s, ...) sharded dim0 -> out (D*s, ...)
                      sharded.
      alltoall:       in (D, G, s...) sharded dim0 -> out (D, G, s...)
                      sharded.
    """
    if collective not in _WIRING:
        raise ValueError(f"unknown collective {collective!r}; "
                         f"one of {collectives()}")
    if algo == AUTO:
        raise ValueError("algo='auto' resolves per input size/dtype; call "
                         "Communicator methods (or resolve_algo first)")
    # Mesh hashes/compares by axis names + device assignment, so it keys
    # the cache directly (no per-call O(n_devices) key construction). The
    # fused-codec switch changes the traced program, so it's part of the key
    # (the conformance A/B under compress.jnp_reference_paths must not hit
    # a program built with fusion on, and vice versa).
    key = (mesh, topo, collective, algo, stacked, jit, donate, carry,
           _kw_key(kw), _codecs.fused_enabled())
    hit = _BUILD_CACHE.get(key)
    if hit is not None:
        _STATS.build_hits += 1
        _BUILD_CACHE.move_to_end(key)
        return hit
    _STATS.build_misses += 1
    built = _construct(mesh, topo, collective, algo, stacked, jit, donate,
                       carry, **kw)
    _BUILD_CACHE[key] = built
    _evict(_BUILD_CACHE, "build")
    return built


def run(mesh, topo: Topology, name: str, algo: str, x, *,
        stacked: bool = True, error_budget: float = 0.0, **kw):
    """Execute collective ``name`` with ``algo`` on ``x`` over ``mesh``
    through the compiled-callable cache (the ``Communicator`` backend).

    The AOT-compiled executable is cached on (mesh, collective, algo, input
    shape/dtype, kwargs), so every invocation after the first with an
    identical key skips trace, lowering and compilation entirely.

    ``algo="auto"`` resolves through the selection subsystem (measured
    tuning table when calibrated, cost-model prior otherwise) before the
    cache lookup — the key carries the *resolved* plan (algorithm + chunk
    count + codec), so auto and explicit callers share compiled
    executables. ``error_budget`` lets auto pick an error-bounded codec
    plan (``core.compress``); the default 0.0 keeps resolution lossless.
    An explicit ``codec=`` kwarg pins the codec on the codec-capable
    algorithms instead.
    """
    if name not in _WIRING:  # before selector resolution, for the friendly
        raise ValueError(f"unknown collective {name!r}; "  # error either way
                         f"one of {collectives()}")
    x = global_operand(mesh, name, x)
    algo, kw = resolve_algo(topo, name, algo, x, kw,
                            error_budget=error_budget)
    return run_resolved(mesh, topo, name, algo, x, stacked=stacked, **kw)


def run_resolved(mesh, topo: Topology, name: str, algo: str, x, *,
                 stacked: bool = True, **kw):
    """Execute an already-resolved plan through the exec cache — the fast
    path for callers that ran :func:`resolve_algo` themselves (Communicator
    methods resolve once with their own selector, then come here)."""
    key = (mesh, topo, name, algo, stacked, _kw_key(kw),
           (tuple(x.shape), str(x.dtype)), _codecs.fused_enabled())
    if not _tm.enabled():  # one global read; the disabled path adds nothing
        return _exec(key, mesh, topo, name, algo, x, stacked, kw)(x)
    nbytes = _message_bytes(name, topo, x)
    t0 = _time.perf_counter()
    with _tm.span("comm/start",
                  **_span_tags(topo, name, algo, kw, nbytes=nbytes)):
        out = _exec(key, mesh, topo, name, algo, x, stacked, kw)(x)
    # dispatch wall-clock only (async: the device may still be running)
    _tm.observe_plan(topo, name, str(x.dtype), nbytes,
                     autotune.encode_plan(algo, int(kw.get("chunks", 1)),
                                          str(kw.get("codec", "none"))),
                     _time.perf_counter() - t0, synced=False)
    return out


def _exec(key, mesh, topo: Topology, name: str, algo: str, x,
          stacked: bool, kw: Dict[str, Any]):
    """The exec cache's compiled callable for ``key``, compiled from
    ``x``'s spec on a miss (inside a ``comm/compile`` span)."""
    compiled = _EXEC_CACHE.get(key)
    if compiled is not None:
        _STATS.exec_hits += 1
        _EXEC_CACHE.move_to_end(key)
        return compiled
    _STATS.exec_misses += 1
    with _tm.span("comm/compile", **(_span_tags(topo, name, algo, kw)
                                      if _tm.enabled() else {})):
        jitted = build(mesh, topo, name, algo, stacked=stacked, jit=True,
                       **kw)
        compiled = jitted.lower(x).compile()
    _EXEC_CACHE[key] = compiled
    _evict(_EXEC_CACHE, "exec")
    return compiled


def input_sharding(mesh, topo: Topology, collective: str) -> NamedSharding:
    """The canonical operand sharding for one collective's wiring — what
    persistent ops compile against (and reshard stray operands to)."""
    if collective not in _WIRING:
        raise ValueError(f"unknown collective {collective!r}; "
                         f"one of {collectives()}")
    del topo  # operands are global over the whole mesh (cf. _construct)
    return NamedSharding(mesh, _in_spec(_WIRING[collective].in_mode,
                                        tuple(mesh.axis_names)))


def _dist_backend():
    from repro.distributed import backend as _dist  # lazy: core stays
    return _dist                                    # importable standalone


def to_sharding(x, sharding):
    """Commit ``x`` to ``sharding`` as a (possibly cross-process) global.

    Single-process this is exactly ``device_put`` — bit-identical to the
    historical behavior, including the exec-cache interaction. Under a
    multi-controller runtime a host value becomes a global array with each
    process contributing its own shards, and an existing non-addressable
    global on the wrong sharding is resharded through a jitted identity
    (``device_put`` cannot move shards it does not own).
    """
    dist = _dist_backend()
    if not dist.is_multiprocess():
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        if x.sharding == sharding:
            return x
        return jax.jit(lambda v: v, out_shardings=sharding)(x)
    return dist.global_array(np.asarray(x), sharding)


def global_operand(mesh, collective, x):
    """Canonicalize one collective operand for ``mesh``.

    Single-process: plain ``jnp.asarray`` (uncommitted, so the exec cache
    keeps mixing committed/uncommitted operands exactly as before). Under a
    multi-controller runtime every operand is committed to the collective's
    canonical :func:`input_sharding` so compiled executables always see one
    layout — each process passes the same full logical value.
    """
    dist = _dist_backend()
    if not dist.is_multiprocess():
        return jnp.asarray(x)
    return to_sharding(x, input_sharding(mesh, None, collective))


def compile_persistent(mesh, topo: Topology, name: str, algo: str,
                       shape: Tuple[int, ...], dtype, *,
                       stacked: bool = True, donate: bool = False,
                       carry: bool = False,
                       **kw) -> Tuple[Callable, NamedSharding]:
    """AOT-compile one resolved plan for a fixed operand shape/dtype with
    the collective's canonical input sharding pinned (``PersistentOp``
    backend). Returns ``(compiled, in_sharding)``.

    ``carry=True`` compiles the carry-threaded program variant: the
    executable takes ``(x, carry)`` — both with the payload's shape, dtype
    and sharding — and returns ``(result, new_carry)``. This is how
    per-bucket error-feedback state rides a persistent compressed
    allreduce (``op.start(x, carry=err)`` -> ``handle.wait()`` ->
    ``(y, new_err)``); only algorithms with an ``err`` state operand
    support it (:func:`supports_carry`).

    Entries live in the same LRU exec cache as :func:`run`, keyed with the
    pinned sharding (a blocking call compiled against a host-local operand
    layout is a different executable) — re-initialising a persistent op
    with an identical spec is an exec-cache hit, never a recompile.
    """
    if algo == AUTO:
        raise ValueError("compile_persistent needs a resolved plan; call "
                         "resolve_algo first (Communicator.persistent "
                         "does this)")
    sharding = input_sharding(mesh, topo, name)
    key = (mesh, topo, name, algo, stacked, _kw_key(kw),
           (tuple(shape), str(jnp.dtype(dtype))),
           ("persistent", donate, carry), _codecs.fused_enabled())
    compiled = _EXEC_CACHE.get(key)
    if compiled is not None:
        _STATS.exec_hits += 1
        _EXEC_CACHE.move_to_end(key)
        return compiled, sharding
    _STATS.exec_misses += 1
    with _tm.span("comm/compile", persistent=True,
                  **(_span_tags(topo, name, algo, kw)
                     if _tm.enabled() else {})):
        jitted = build(mesh, topo, name, algo, stacked=stacked, jit=True,
                       donate=donate, carry=carry, **kw)
        proto = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                     sharding=sharding)
        compiled = (jitted.lower(proto, proto).compile() if carry
                    else jitted.lower(proto).compile())
    _EXEC_CACHE[key] = compiled
    _evict(_EXEC_CACHE, "exec")
    return compiled, sharding


# ---------------------------------------------------------------------------
# calibration: measured sweeps -> the selector's tuning table
# ---------------------------------------------------------------------------


def example_input(collective: str, topo: Topology, nbytes: int,
                  dtype=jnp.float32, devices: Optional[int] = None):
    """A global operand for ``collective`` sized so the per-process message
    is ``nbytes`` (the cost model's size convention).

    ``devices`` is the total mesh device count ``D`` the operand's sharded
    dim0 spans (see :func:`build`'s conventions); it defaults to
    ``topo.world`` and must be passed for sub-communicator topologies,
    where the group size ``G = topo.world`` is smaller than the mesh."""
    G = topo.world
    D = int(devices) if devices is not None else G
    itemsize = jnp.dtype(dtype).itemsize
    elems = max(1, nbytes // itemsize)
    if collective == "allgather":
        return jnp.arange(D * elems, dtype=dtype)
    if collective == "scatter":
        return jnp.arange(G * elems, dtype=dtype)
    if collective == "broadcast":
        return jnp.arange(elems, dtype=dtype)
    if collective == "allreduce":
        return (jnp.arange(D * elems, dtype=dtype) % 13).reshape(D, elems)
    if collective == "reduce_scatter":
        s = max(1, elems // G)
        return (jnp.arange(D * G * s, dtype=dtype) % 11).reshape(D, G * s)
    if collective == "alltoall":
        s = max(1, elems // G)
        return jnp.arange(D * G * s, dtype=dtype).reshape(D, G, s)
    raise ValueError(collective)


@dataclasses.dataclass(frozen=True)
class CalibrationRow:
    collective: str
    algo: str
    nbytes: int
    dtype: str
    seconds: float
    chunks: int = 1
    codec: str = "none"
    #: sub-communicator group tag ("" = the root topology); split-lattice
    #: sweeps (Communicator.calibrate(include_splits=True)) fill this
    group: str = ""


def calibrate(mesh, topo: Topology,
              names: Optional[Iterable[str]] = None,
              sizes: Iterable[int] = (256, 4096, 65536),
              dtype=jnp.float32, iters: int = 10,
              selector: Optional[autotune.Selector] = None,
              codecs: Optional[Tuple[str, ...]] = None,
              path=None) -> List[CalibrationRow]:
    """Timed sweeps of every candidate plan x size, through the same
    compiled-callable path hot loops use, recorded into the selector's
    tuning table (and saved to ``path`` as JSON when given).

    Plans cover every feasible algorithm, chunk-count variants for the
    pipelined ones, and codec variants for the codec-capable ones
    (``codecs=()`` restricts to lossless plans). After calibration,
    ``algo="auto"`` on this (topology, collective, dtype, size bucket)
    resolves from measurement instead of the cost-model prior — codec
    entries still gated by the caller's ``error_budget`` at choose time.
    Calibrate with the same topology link metadata consumers use (e.g. both
    via ``Topology.from_mesh``) — the tuning-table key includes the links.
    """
    sel = selector or autotune.default_selector()
    rows: List[CalibrationRow] = []
    n_dev = int(np.asarray(mesh.devices).size)
    for name in (tuple(names) if names else collectives()):
        for nbytes in sizes:
            x = example_input(name, topo, int(nbytes), dtype,
                              devices=n_dev)
            for algo, chunks, codec in autotune.plans(
                    name, topo, int(nbytes), codecs=codecs,
                    dtype=str(jnp.dtype(dtype))):
                kw = {}
                if _mcoll.supports_chunks(name, algo):
                    kw["chunks"] = chunks
                if codec != _codecs.NONE:
                    kw["codec"] = codec
                plan = autotune.encode_plan(algo, chunks, codec)
                with _tm.span("comm/calibrate", plan=plan,
                              **(_span_tags(topo, name, algo, kw,
                                            nbytes=int(nbytes))
                                 if _tm.enabled() else {})):
                    jax.block_until_ready(
                        run(mesh, topo, name, algo, x, **kw))  # compile
                    samples = []
                    for _ in range(max(1, iters)):
                        t0 = _time.perf_counter()
                        jax.block_until_ready(
                            run(mesh, topo, name, algo, x, **kw))
                        samples.append(_time.perf_counter() - t0)
                sec = float(np.median(samples))
                if _tm.enabled():
                    # blocked loops are the highest-quality drift evidence
                    for s in samples:
                        _tm.observe_plan(topo, name, str(jnp.dtype(dtype)),
                                         int(nbytes), plan, s, synced=True)
                sel.table.record(topo, name, str(jnp.dtype(dtype)),
                                 int(nbytes), plan, sec)
                rows.append(CalibrationRow(name, algo, int(nbytes),
                                           str(jnp.dtype(dtype)), sec,
                                           chunks, codec,
                                           group=topo.group or ""))
    if path is not None:
        sel.table.save(path)
    return rows
