"""Manual-collective training step: the paper's collectives wired into the
DP gradient-sync path.

Where PiP-MColl fits in training: the per-step *small-message* syncs are
latency-bound at scale — global grad-norm scalars, MoE router load stats,
metric reductions — while the gradient payload itself is the bandwidth-bound
large-message case the paper's segmented transfers target. This module
builds a shard_map'd step in which

  - gradients are synced **bucketed** by default: the whole grad tree is
    flattened into fixed-size buckets (``bucket_bytes``, default 4 MiB) and
    each bucket runs one pipelined allreduce. Bucketing turns many
    per-tensor latency-bound syncs into few large transfers sized where the
    chunked pipeline (``pip_pipeline`` + per-bucket chunk count from the
    selection subsystem) overlaps intra- and inter-node stages,
  - the plan per payload is resolved through the selection subsystem
    (``algo="auto"``, the default) — or pinned explicitly via ``algo=`` /
    ``chunks=`` / ``codec=``,
  - ``error_budget`` opts the gradient sync into error-bounded compression
    (``core.compress``): the selector may pick any codec whose stated
    relative-error bound fits the budget (``0.0`` = lossless plans only),
    and the compressed allreduce threads **error-feedback state** per
    bucket so the accumulated update tracks the true gradient sum,
  - scalar metrics and the loss always sync lossless (small-message regime
    — the paper's headline case — and reported numbers must be exact).

Two step shapes are built here:

  * :func:`make_manual_train_step` — the **fused barrier-style** step: one
    jitted shard_map computing backward, per-bucket allreduce, and the
    optimizer update in a single program (gradient sync happens at the end
    of backprop, every bucket serialized inside one computation). Supports
    error-feedback compressed sync.
  * :func:`make_overlapped_train_step` — the **persistent nonblocking**
    step (the Communicator API's overlap shape): each bucket rides a
    persistent ``comm.allreduce_init`` op (plan resolved + compiled once,
    reused every step). With ``segmented="auto"`` (default, decoder
    family) backprop itself is split into **layer-wise VJP segments**
    aligned to bucket boundaries: the head/chunk/embed backward programs
    run newest-to-oldest and ``op.start(bucket_i)`` is issued *between*
    segment executions, so bucket i's allreduce overlaps bucket i+1's
    backward **compute** — the PiP-MColl overlap shape — instead of only
    its dispatch (the monolithic fallback, one backward program emitting
    all buckets). Compressed buckets thread per-bucket error-feedback
    residuals through **carry ops** (``op.start(x, carry=err)``;
    ``handle.wait() -> (y, new_err)``), matching the fused step's EF
    semantics. The barrier variant of the same decomposition
    (``overlap=False``) waits out each bucket before starting the next —
    the two are bit-identical (same compiled programs, different host
    scheduling), which the check asserts; the benchmark artifact reports
    the step-time delta. ``error_budget`` may be a **schedule**
    ``callable(step) -> float``: the per-bucket codec plan is re-resolved
    only when the budget crosses a plan boundary (old ops released, new
    ops built via the exec cache, so returning to a previous plan never
    recompiles).

The pjit path (train.step) remains the default for the dry-run; this path
is validated against it on multi-device CPU meshes in
tests/checks/manual_step_check.py (same loss/grads to fp32 tolerance, the
bucketed path bit-exact against the unbucketed one, and the compressed
variant still descending).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import autotune, costmodel, mcoll, runtime
from repro.core import compress as codecs
from repro.core import telemetry as _tm
from repro.core.comm import Communicator, communicator
from repro.core.topology import Topology
from repro.optim import adamw
from repro.train.step import TrainConfig, loss_fn

#: default gradient bucket size — large enough that the pipelined allreduce
#: is the modeled winner, small enough to bound the peak fused buffer
DEFAULT_BUCKET_BYTES = 4 << 20


def _comm_topo(mesh, topo) -> Tuple[Communicator, Topology]:
    """Both step builders accept either a :class:`Topology` or a
    :class:`Communicator` (e.g. a ``comm.split(axes=...)`` group child) in
    the ``topo`` slot — the communicator's group then defines the
    data-parallel domain: the batch is sharded and gradients are mean-
    reduced over its axes only, and its tuning rows (group-tagged) drive
    plan selection."""
    if isinstance(topo, Communicator):
        comm = topo
        if comm.mesh is not mesh:
            raise ValueError("the group communicator's mesh must be the "
                             "step's mesh")
        if comm.topo is None:
            raise ValueError("unscoped root communicator: split(axes=...) "
                             "to scope the gradient sync to a group")
        return comm, comm.topo
    return communicator(mesh, topo), topo


def _resolve_plan(topo: Topology, nbytes: int, dtype, algo: str,
                  chunks: Optional[int], codec: Optional[str],
                  error_budget: float) -> Tuple[str, dict]:
    """(algorithm, kwargs) plan for one allreduce payload, resolved at
    trace time (shapes are static, so selection is a Python-level decision
    baked into the jitted step).

    ``algo="auto"`` takes the selector's full (algo, chunks, codec) plan
    under the error budget. A pinned ``algo`` with ``codec=None`` and a
    positive budget still picks the cheapest admissible codec for that
    algorithm via the cost model (so ``algo="pip_mcoll"`` + budget works
    like auto's codec dimension, just with the algorithm fixed)."""
    net = costmodel.net_for(topo)
    name, c, cd = algo, chunks, codec
    if name == "auto":
        sel = autotune.default_selector().choose(
            "allreduce", topo, nbytes, net=net, dtype=str(dtype),
            error_budget=error_budget)
        name = sel.algo
        if c is None:
            c = sel.chunks
        if cd is None:
            cd = sel.codec
    elif cd is None and error_budget > 0.0 and \
            mcoll.supports_codec("allreduce", name):
        cands = codecs.for_budget(error_budget)
        if cands:
            cd = min(cands,
                     key=lambda k: costmodel.plan_cost(
                         "allreduce", name, topo, nbytes, net,
                         chunks=c or 1, codec=k).time)
        # else: no codec admissible under this budget — stay lossless
        # rather than letting min() raise on the empty sequence
    kw = {}
    if c and mcoll.supports_chunks("allreduce", name):
        kw["chunks"] = int(c)
    if cd and cd != codecs.NONE and mcoll.supports_codec("allreduce", name):
        kw["codec"] = cd
    return name, kw


def _make_sync(topo: Topology, algo: str, chunks: Optional[int] = None):
    """Lossless mean-allreduce for one payload (metrics, loss, and the
    unbucketed gradient path)."""

    def sync_mean(v):
        g = jnp.asarray(v, jnp.float32).reshape(-1)
        name, kw = _resolve_plan(topo, g.size * g.dtype.itemsize, g.dtype,
                                 algo, chunks, None, 0.0)
        out = mcoll.algorithm("allreduce", name)(g, topo, **kw) / topo.world
        return out.reshape(jnp.shape(v))

    return sync_mean


def _make_grad_sync(topo: Topology, algo: str, chunks: Optional[int],
                    codec: Optional[str], error_budget: float):
    """Mean-allreduce with error-feedback threading for gradient buckets:
    ``sync(x, err) -> (mean, new_err)``. When the resolved plan is
    lossless (or carries no feedback state), ``err`` passes through."""

    def sync(v, err):
        g = jnp.asarray(v, jnp.float32).reshape(-1)
        name, kw = _resolve_plan(topo, g.size * g.dtype.itemsize, g.dtype,
                                 algo, chunks, codec, error_budget)
        fn = mcoll.algorithm("allreduce", name)
        if kw.get("codec") and err is not None:
            out, err = fn(g, topo, err=err, **kw)
        else:
            out = fn(g, topo, **kw)
        return (out / topo.world).reshape(jnp.shape(v)), err

    return sync


def bucket_slices(total: int, bucket_elems: int) -> List[Tuple[int, int]]:
    """(start, length) windows covering [0, total) in fixed-size buckets
    (the last bucket carries the remainder)."""
    if total <= 0:
        return []
    b = max(1, int(bucket_elems))
    return [(s, min(b, total - s)) for s in range(0, total, b)]


def sync_tree_bucketed(grads, sync_fn, bucket_bytes: int, err_state=None):
    """Flatten a gradient tree into fp32 buckets of ``bucket_bytes``, run
    ``sync_fn(bucket, err) -> (synced, new_err)`` once per bucket, and
    restore the tree structure. Returns ``(synced_tree, new_err_state)``.

    One allreduce per bucket instead of one per tensor: small tensors stop
    paying per-collective latency, and every bucket is large enough for the
    pipelined algorithms to win. Elementwise reductions make the result
    bit-identical to syncing each leaf with the same algorithm.
    ``err_state`` is a tuple of per-bucket error-feedback buffers (from
    :func:`init_error_state`) or empty for lossless sync.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads, err_state
    flat = (jnp.concatenate(
        [jnp.asarray(l, jnp.float32).reshape(-1) for l in leaves])
        if len(leaves) > 1
        else jnp.asarray(leaves[0], jnp.float32).reshape(-1))
    bucket_elems = max(1, int(bucket_bytes) // 4)  # fp32 wire dtype
    slices = bucket_slices(flat.size, bucket_elems)
    errs = list(err_state) if err_state else [None] * len(slices)
    assert len(errs) == len(slices), \
        f"error state has {len(errs)} buckets, payload needs {len(slices)}"
    synced, new_errs = [], []
    for (start, n), e in zip(slices, errs):
        y, e2 = sync_fn(lax.dynamic_slice_in_dim(flat, start, n, axis=0), e)
        synced.append(y)
        new_errs.append(e2)
    flat = jnp.concatenate(synced) if len(synced) > 1 else synced[0]
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(jnp.shape(l)))
        off += l.size
    new_state = tuple(e for e in new_errs if e is not None)
    return jax.tree_util.tree_unflatten(treedef, out), new_state


def make_manual_train_step(cfg, tcfg: TrainConfig, mesh, topo,
                           algo: str = "auto",
                           error_budget: float = 0.0,
                           bucketed: bool = True,
                           bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                           chunks: Optional[int] = None,
                           codec: Optional[str] = None):
    """Data-parallel over the topology's active axes (node=slow/pod axis,
    local=fast axis). Params replicated; batch sharded over those axes.
    ``topo`` may be a :class:`Topology` or a group :class:`Communicator`
    (``comm.split(axes=...)``) — the group then scopes the sync.

    ``algo`` names an allreduce algorithm from core.mcoll, or "auto"
    (default) to let the selection subsystem pick an (algorithm, chunks,
    codec) plan per payload size. ``error_budget`` admits error-bounded
    codecs into the gradient-sync plan (``0.0`` = lossless; loss/metric
    syncs stay lossless regardless), with error feedback threaded per
    bucket. ``bucketed`` (default) flattens the grad tree into
    ``bucket_bytes`` buckets with one pipelined allreduce each — bit-exact
    with the per-tensor path for the same lossless plan; ``chunks`` /
    ``codec`` pin those knobs instead of the selector's plan. Error
    feedback requires the bucketed path (its state is per bucket); the
    unbucketed path compresses statelessly."""
    _, topo = _comm_topo(mesh, topo)
    sync_mean = _make_sync(topo, algo, chunks)
    grad_sync = _make_grad_sync(topo, algo, chunks, codec, error_budget)

    def bucket_sync(v, e):
        # error buffers are DEVICE state: globally (world, n) sharded over
        # the mesh axes, (1, n) per device inside the shard_map (residuals
        # live at device-dependent offsets, so a replicated spec would lie
        # about the invariant and lose every shard but device 0's on
        # materialization)
        if e is None:
            return grad_sync(v, None)
        y, e2 = grad_sync(v, e[0])
        return y, e2[None]

    def step(params, opt_state, err_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg, tcfg, None, None)

        if bucketed:
            grads, err_state = sync_tree_bucketed(grads, bucket_sync,
                                                  bucket_bytes, err_state)
        else:
            grads = jax.tree.map(lambda g: grad_sync(g, None)[0], grads)
        loss = sync_mean(loss.reshape(1))[0]

        new_params, new_opt, om = adamw.update(params, grads, opt_state,
                                               tcfg.optimizer)
        metrics = dict(metrics, **om, loss=loss)
        metrics = {k: (sync_mean(jnp.asarray(v, jnp.float32).reshape(1))[0]
                       if jnp.asarray(v).ndim == 0 else v)
                   for k, v in metrics.items()}
        return new_params, new_opt, err_state, metrics

    ax = topo.active_axes
    err_spec = P(ax) if error_budget > 0.0 else P()
    mapped = runtime.sharded(
        step, mesh,
        in_specs=(P(), P(), err_spec, P(ax)),
        out_specs=(P(), P(), err_spec, P()),
        check=False)
    return jax.jit(mapped, donate_argnums=(0, 1, 2))


def init_error_state(params, error_budget: float = 0.0,
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                     topo: Optional[Topology] = None):
    """Per-bucket error-feedback buffers for the compressed gradient sync:
    a tuple of zero fp32 ``(world, bucket_len)`` arrays (row d = device
    d's residuals; sharded over the mesh axes by the step) matching
    :func:`bucket_slices` over the flattened parameter count. Empty (no
    state) when the budget is 0 — lossless sync carries nothing between
    steps."""
    if error_budget <= 0.0:
        return ()
    if isinstance(topo, Communicator):
        topo = topo.topo
    if topo is None:
        raise ValueError("init_error_state needs the topology when "
                         "error_budget > 0 (error feedback is per-device "
                         "state, shaped (world, bucket_len))")
    total = sum(int(jnp.size(l)) for l in jax.tree.leaves(params))
    bucket_elems = max(1, int(bucket_bytes) // 4)
    return tuple(jnp.zeros((topo.world, n), jnp.float32)
                 for _, n in bucket_slices(total, bucket_elems))


# ---------------------------------------------------------------------------
# overlapped gradient sync: persistent nonblocking per-bucket allreduce
# ---------------------------------------------------------------------------


class OverlappedGradSync:
    """Per-bucket persistent allreduce ops for the overlapped step.

    Holds one ``PersistentOp`` per gradient bucket plus one for the packed
    scalar-metrics vector (always lossless). ``error_budget`` is a float or
    a schedule ``callable(step) -> float``; plans are re-resolved per step
    but ops are **rebuilt only when a bucket's resolved plan changes**
    (budget crossing a plan boundary) — the old ops are :meth:`released
    <repro.core.comm.PersistentOp.release>` first (rebind hygiene: with
    ``donate=True`` a dropped-but-unreleased op would pin its donated
    buffers), and rebuilding goes through the runtime exec cache, so
    flipping back to an earlier plan is a cache hit, not a recompile.
    ``rebuilds`` counts those transitions.

    Buckets whose resolved plan carries a codec ride **carry ops**
    (``start(x, carry=err) -> handle; wait() -> (y, new_err)``): per-bucket
    error-feedback residuals thread through the persistent op exactly like
    the fused step's ``err_state``, updated on :meth:`wait`. Lossless
    buckets use plain ops (bit-identical to the fused lossless sync path's
    reduction). ``errs`` holds the live per-bucket state (``None`` for
    lossless buckets); it resets to zeros when a plan change rebuilds an
    op.
    """

    def __init__(self, comm, slices: List[Tuple[int, int]], metric_len: int,
                 algo: str = "auto", chunks: Optional[int] = None,
                 codec: Optional[str] = None, error_budget=0.0,
                 donate: bool = False):
        self.comm = comm
        self.slices = list(slices)
        self.metric_len = int(metric_len)
        self.algo, self.chunks, self.codec = algo, chunks, codec
        self.error_budget = error_budget
        self.donate = bool(donate)
        self.rebuilds = 0
        self._plans: Optional[List[Tuple[str, dict]]] = None
        self._last_budget: Optional[float] = None
        self._ops: List = []
        self.errs: List = []
        self._metric_op = None
        self._step = 0  # the step ensure_ops last saw: the spans' step tag

    def budget_at(self, step: int) -> float:
        if callable(self.error_budget):
            return float(self.error_budget(int(step)))
        return float(self.error_budget)

    def plans(self) -> List[str]:
        """Current per-bucket plan keys (``algo#cN@codec``)."""
        return [op.plan for op in self._ops]

    def _resolve(self, budget: float) -> List[Tuple[str, dict]]:
        topo = self.comm.topo
        return [_resolve_plan(topo, n * 4, jnp.float32, self.algo,
                              self.chunks, self.codec, budget)
                for _, n in self.slices]

    def ensure_ops(self, step: int) -> None:
        """Re-resolve the per-bucket plan for this step's budget; rebuild
        the persistent ops only when a plan actually changed. Plans are a
        pure function of the budget value here, so an unchanged budget
        (always, for a float knob) skips the cost-model walk entirely."""
        self._step = int(step)
        with _tm.span("train/ensure_ops", step=self._step):
            self._ensure_ops(self.budget_at(step))

    def _ensure_ops(self, budget: float) -> None:
        if self._plans is not None and budget == self._last_budget:
            return
        self._last_budget = budget
        plans = self._resolve(budget)
        if plans == self._plans:
            return
        for op in self._ops:
            op.release()
        world = self.comm.topo.world
        self._ops = [
            self.comm.allreduce_init(
                shape=(world, n), dtype=jnp.float32, algo=name,
                chunks=kw.get("chunks"), codec=kw.get("codec"),
                donate=self.donate,
                carry=bool(kw.get("codec"))
                and runtime.supports_carry("allreduce", name))
            for (_, n), (name, kw) in zip(self.slices, plans)]
        self.errs = [jnp.zeros(op.shape, jnp.float32) if op.carry else None
                     for op in self._ops]
        if self._metric_op is None:
            # scalar metrics always sync lossless, with the same pinned
            # algorithm family as the gradient plan (budget 0)
            mname, mkw = _resolve_plan(self.comm.topo, self.metric_len * 4,
                                       jnp.float32, self.algo, self.chunks,
                                       None, 0.0)
            self._metric_op = self.comm.allreduce_init(
                shape=(world, self.metric_len), dtype=jnp.float32,
                algo=mname, chunks=mkw.get("chunks"))
        if self._plans is not None:
            self.rebuilds += 1
            _tm.counter("train.bucket_rebuilds").inc()
            if _tm.enabled():
                _tm.instant("train/bucket_rebuild", step=self._step,
                            budget=budget,
                            plans=",".join(op.plan for op in self._ops))
        self._plans = plans

    # -- per-bucket start/wait (the segmented step interleaves these with
    # its backward-segment programs) ----------------------------------------

    def start(self, i: int, payload):
        """Start bucket ``i``'s persistent allreduce (threading its EF
        carry when the plan compresses); returns the handle."""
        # errs[i] is None for a lossless bucket, whose op takes no carry
        return self._ops[i].start(payload, carry=self.errs[i], bucket=i,
                                  step=self._step)

    def wait(self, i: int, handle, block: bool = False):
        """Complete bucket ``i``: returns the reduced payload and absorbs
        the new error-feedback state for carry buckets."""
        op = self._ops[i]
        if op.carry:
            y, new_err = handle.wait(block=block)
            self.errs[i] = new_err
            if _tm.should_sample(f"ef:{id(self)}:{i}"):
                self._observe_ef(op, y, new_err)
            return y
        return handle.wait(block=block)

    @staticmethod
    def _observe_ef(op, y, new_err) -> None:
        """Sampled codec-quality probe (telemetry on, 1-in-N waits): the
        achieved-vs-bound relative error straight off the error-feedback
        carry, plus the achieved wire ratio on the reduced payload. The
        only telemetry site that materializes device values — which is why
        it hides behind ``should_sample``."""
        amax_y = float(jnp.max(jnp.abs(y)))
        amax_e = float(jnp.max(jnp.abs(new_err)))
        rel = amax_e / (amax_y + 1e-30)
        _tm.observe_ef_error(op.codec, rel,
                             codecs.meta(op.codec).error_bound)
        _tm.observe_codec_ratio(
            op.codec, codecs.codec(op.codec).achieved_ratio(y))

    def run(self, i: int, payload):
        """Barrier-style bucket ``i``: start and block out the wait."""
        return self.wait(i, self.start(i, payload), block=True)

    def start_metric(self, mvec):
        return self._metric_op.start(mvec, bucket="metrics",
                                     step=self._step)

    def sync(self, buckets, mvec, overlap: bool = True):
        """Allreduce every bucket + the metrics vector.

        ``overlap=True``: start everything, then wait — bucket i's
        communication overlaps bucket i+1's dispatch/execution (software
        pipelining under async dispatch). ``overlap=False``: the
        barrier-style reference — each bucket fully completes before the
        next starts. Same ops either way, so results are bit-identical.
        """
        if overlap:
            handles = [self.start(i, b) for i, b in enumerate(buckets)]
            mh = self.start_metric(mvec)
            synced = [self.wait(i, h, block=False)
                      for i, h in enumerate(handles)]
            return synced, mh.wait(block=False)
        synced = [self.run(i, b) for i, b in enumerate(buckets)]
        return synced, self.start_metric(mvec).wait(block=True)


class _OverlappedStep:
    """Callable train step built by :func:`make_overlapped_train_step`.

    Lazily builds its compiled backward/apply programs from the first
    (params, batch) it sees (payload shapes and the metric-key set are
    static from there on).

    Two decompositions (``.mode`` after the first call):

    * ``"monolithic"`` — one backward program emitting every bucket, then
      all per-bucket allreduces. Only the *dispatch* of the allreduces
      overlaps (bucket i's comm vs bucket i+1's dispatch).
    * ``"segmented"`` — backprop is split into layer-wise VJP segments
      aligned to bucket boundaries: a forward program records the hidden
      state at each segment boundary, the head/chunk/embed backward
      programs run newest-to-oldest, and bucket i's persistent allreduce
      **starts between segment executions** — its communication overlaps
      bucket i+1's backward *compute*, the PiP-MColl overlap shape (DDP-
      style gradient bucketing). Available for the decoder family
      (``params`` = embed/groups/final_norm/lm_head, no frontend embeds,
      ``microbatches == 1``); grads match the monolithic decomposition to
      fp32 tolerance but are **not** bitwise against it (segment-shaped
      XLA programs reduce in a different order) — bitwise identity holds
      between the overlap/barrier twins of the *same* decomposition.
    """

    def __init__(self, cfg, tcfg: TrainConfig, mesh, topo,
                 algo: str, error_budget, bucket_bytes: int,
                 chunks: Optional[int], codec: Optional[str],
                 overlap: bool, donate: bool, segmented="auto"):
        self.cfg, self.tcfg = cfg, tcfg
        self.comm, self.topo = _comm_topo(mesh, topo)
        self.mesh = mesh
        self.overlap = bool(overlap)
        self._knobs = (algo, chunks, codec)
        self._budget = error_budget
        self.bucket_bytes = int(bucket_bytes)
        self.donate = bool(donate)
        self.segmented = segmented
        self.mode: Optional[str] = None
        self.grad_sync: Optional[OverlappedGradSync] = None
        self._backward_c = None
        self._apply_c = None
        self._auto_step = 0
        # segmented-mode programs
        self._fwd_c = None
        self._head_bwd_c = None
        self._chunk_bwd_c: List = []
        self._embed_bwd_c = None
        self.bounds: List[Tuple[int, int]] = []

    # -- lazy build ---------------------------------------------------------

    def _segment_support(self, params, batch) -> Optional[str]:
        """None when the segmented decomposition applies, else the reason
        it does not (the decomposition mirrors decoder.forward exactly)."""
        if getattr(self.cfg, "family", None) == "encdec":
            return "encoder-decoder family"
        if self.tcfg.microbatches != 1:
            return "microbatch gradient accumulation"
        if not (isinstance(params, dict)
                and set(params) == {"embed", "groups", "final_norm",
                                    "lm_head"}):
            return "non-decoder parameter tree"
        if isinstance(batch, dict) and batch.get("embeds") is not None:
            return "frontend embeds in the batch"
        return None

    def _build(self, params, batch):
        why_not = self._segment_support(params, batch)
        if self.segmented and why_not is None:
            self.mode = "segmented"
            return self._build_segmented(params, batch)
        if self.segmented is True:
            raise ValueError(
                f"segmented=True but the segmented backward does not "
                f"apply here: {why_not}")
        self.mode = "monolithic"
        return self._build_monolithic(params, batch)

    def _build_monolithic(self, params, batch):
        cfg, tcfg, topo = self.cfg, self.tcfg, self.topo
        leaves = jax.tree.leaves(params)
        treedef = jax.tree.structure(params)
        leaf_meta = [(jnp.shape(l), int(jnp.size(l))) for l in leaves]
        total = sum(s for _, s in leaf_meta)
        slices = bucket_slices(total, max(1, self.bucket_bytes // 4))
        _, metric_avals = jax.eval_shape(
            lambda p, b: loss_fn(p, b, cfg, tcfg, None, None), params, batch)
        mkeys = sorted(k for k, v in metric_avals.items() if not v.shape)
        world, ax = topo.world, topo.active_axes

        def backward(params, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, cfg, tcfg, None, None)
            ls = jax.tree.leaves(grads)
            flat = (jnp.concatenate(
                [jnp.asarray(l, jnp.float32).reshape(-1) for l in ls])
                if len(ls) > 1
                else jnp.asarray(ls[0], jnp.float32).reshape(-1))
            segs = tuple(lax.dynamic_slice_in_dim(flat, s, n, axis=0)
                         for s, n in slices)
            mvec = jnp.stack(
                [jnp.asarray(loss, jnp.float32)]
                + [jnp.asarray(metrics[k], jnp.float32) for k in mkeys])
            return tuple(g[None] for g in segs) + (mvec[None],)

        self._backward_c = jax.jit(runtime.sharded(
            backward, self.mesh, in_specs=(P(), P(ax)),
            out_specs=(P(ax, None),) * (len(slices) + 1), check=False))

        def apply(params, opt_state, *synced):
            buckets, mvec = synced[:-1], synced[-1]
            parts = [b[0] / world for b in buckets]
            flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            out, off = [], 0
            for shape, size in leaf_meta:
                out.append(flat[off:off + size].reshape(shape))
                off += size
            grads = jax.tree_util.tree_unflatten(treedef, out)
            new_params, new_opt, om = adamw.update(params, grads, opt_state,
                                                   tcfg.optimizer)
            mv = mvec[0] / world
            metrics = {k: mv[i + 1] for i, k in enumerate(mkeys)}
            metrics = dict(metrics, **om, loss=mv[0])
            return new_params, new_opt, metrics

        mapped = runtime.sharded(
            apply, self.mesh,
            in_specs=(P(), P()) + (P(ax, None),) * (len(slices) + 1),
            out_specs=(P(), P(), P()), check=False)
        self._apply_c = jax.jit(mapped, donate_argnums=(0, 1))

        algo, chunks, codec = self._knobs
        self.grad_sync = OverlappedGradSync(
            self.comm, slices, len(mkeys) + 1, algo=algo, chunks=chunks,
            codec=codec, error_budget=self._budget, donate=self.donate)

    def _build_segmented(self, params, batch):
        from repro.models import decoder
        from repro.train.step import cross_entropy

        cfg, tcfg, topo = self.cfg, self.tcfg, self.topo
        flags = tcfg.flags
        moe_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
        world, ax = topo.world, topo.active_axes

        # segment boundaries: whole pattern cycles, sized so one chunk's
        # group grads fill ~bucket_bytes (fp32 wire dtype)
        gleaves = jax.tree.leaves(params["groups"])
        gdef = jax.tree.structure(params["groups"])
        nc = int(jnp.shape(gleaves[0])[0])
        cycle_elems = sum(int(jnp.size(l)) // nc for l in gleaves)
        seg = min(nc, max(1, (self.bucket_bytes // 4) // max(1, cycle_elems)))
        bounds = [(lo, min(lo + seg, nc)) for lo in range(0, nc, seg)]
        self.bounds = bounds
        K = len(bounds)

        # per-chunk flat layout: the group leaves sliced to the chunk's
        # cycle window, flattened in tree-leaf order
        def chunk_meta(lo, hi):
            metas = []
            for l in gleaves:
                shape = ((hi - lo),) + tuple(jnp.shape(l)[1:])
                metas.append((shape, int(jnp.size(l)) // nc * (hi - lo)))
            return metas

        head_meta = [(jnp.shape(params["final_norm"]["scale"]),
                      int(jnp.size(params["final_norm"]["scale"]))),
                     (jnp.shape(params["lm_head"]),
                      int(jnp.size(params["lm_head"])))]
        embed_shape = jnp.shape(params["embed"])
        sizes = ([sum(s for _, s in head_meta)]
                 + [sum(s for _, s in chunk_meta(lo, hi))
                    for lo, hi in reversed(bounds)]
                 + [int(jnp.size(params["embed"]))])
        mkeys = ["aux", "ce", "tokens"]  # loss_fn's scalar metrics, sorted

        def _flat32(leaves_):
            parts = [jnp.asarray(l, jnp.float32).reshape(-1) for l in leaves_]
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

        # (1) forward: record the hidden state entering every segment
        def fwd(params, batch):
            h = decoder.embed_apply(params, batch["tokens"], cfg)
            hs, aux = [], jnp.zeros((), jnp.float32)
            for lo, hi in bounds:
                hs.append(h)
                h, a = decoder.segment_apply(params, h, cfg, lo, hi,
                                             flags=flags)
                aux = aux + jnp.asarray(a, jnp.float32)
            return tuple(hs) + (h, aux[None])

        self._fwd_c = jax.jit(runtime.sharded(
            fwd, self.mesh, in_specs=(P(), P(ax)),
            out_specs=(P(ax),) * (K + 1) + (P(ax),), check=False))

        # (2) head backward: loss + (final_norm, lm_head) bucket + trunk
        # cotangent + the packed metrics vector
        def head_bwd(params, h_out, aux, batch):
            hp = {"final_norm": params["final_norm"],
                  "lm_head": params["lm_head"]}

            def head_loss(hp_, h_):
                logits = decoder.head_apply(hp_, h_, cfg, flags=flags)
                return cross_entropy(logits, batch["labels"], tcfg.z_loss)

            ce, vjp, n = jax.vjp(head_loss, hp, h_out, has_aux=True)
            dhp, dh = vjp(jnp.ones((), ce.dtype))
            a = aux[0]
            loss = jnp.asarray(ce, jnp.float32) + moe_w * a
            metrics = {"aux": a, "ce": ce, "tokens": n}
            mvec = jnp.stack(
                [loss] + [jnp.asarray(metrics[k], jnp.float32)
                          for k in mkeys])
            return _flat32(jax.tree.leaves(dhp))[None], dh, mvec[None]

        self._head_bwd_c = jax.jit(runtime.sharded(
            head_bwd, self.mesh, in_specs=(P(), P(ax), P(ax), P(ax)),
            out_specs=(P(ax, None), P(ax), P(ax, None)), check=False))

        # (3) one backward program per segment: VJP of that cycle window,
        # emitting its grad bucket + the cotangent for the segment below
        def make_chunk_bwd(lo, hi):
            def chunk_bwd(params, h_in, dh):
                def seg(p, h_):
                    return decoder.segment_apply(p, h_, cfg, lo, hi,
                                                 flags=flags)

                (_, aux_k), vjp_k = jax.vjp(seg, params, h_in)
                dp, dh_in = vjp_k((dh, jnp.asarray(moe_w, aux_k.dtype)))
                gg = jax.tree.map(
                    lambda g: lax.slice_in_dim(g, lo, hi, axis=0),
                    dp["groups"])
                return _flat32(jax.tree.leaves(gg))[None], dh_in

            return jax.jit(runtime.sharded(
                chunk_bwd, self.mesh, in_specs=(P(), P(ax), P(ax)),
                out_specs=(P(ax, None), P(ax)), check=False))

        self._chunk_bwd_c = [make_chunk_bwd(lo, hi) for lo, hi in bounds]

        # (4) embedding backward: the final (oldest) bucket
        def embed_bwd(params, batch, dh0):
            _, vjp_e = jax.vjp(
                lambda p: decoder.embed_apply(p, batch["tokens"], cfg),
                params)
            de = vjp_e(dh0)[0]["embed"]
            return jnp.asarray(de, jnp.float32).reshape(-1)[None]

        self._embed_bwd_c = jax.jit(runtime.sharded(
            embed_bwd, self.mesh, in_specs=(P(), P(ax), P(ax)),
            out_specs=P(ax, None), check=False))

        # (5) apply: reassemble the param-tree grads from the synced
        # buckets (start order: head, chunk_{K-1}..chunk_0, embed)
        cmetas = [chunk_meta(lo, hi) for lo, hi in bounds]

        def unflatten(flat, metas):
            out, off = [], 0
            for shape, size in metas:
                out.append(lax.slice_in_dim(flat, off, off + size,
                                            axis=0).reshape(shape))
                off += size
            return out

        def apply(params, opt_state, *synced):
            buckets, mvec = synced[:-1], synced[-1]
            head = buckets[0][0] / world
            chunks_fwd = [buckets[1 + j][0] / world
                          for j in range(K)][::-1]
            emb = buckets[1 + K][0] / world
            scale_g, lm_g = unflatten(head, head_meta)
            gtrees = [jax.tree_util.tree_unflatten(gdef, unflatten(f, m))
                      for f, m in zip(chunks_fwd, cmetas)]
            ggroups = (jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *gtrees)
                if K > 1 else gtrees[0])
            grads = {"embed": emb.reshape(embed_shape),
                     "final_norm": {"scale": scale_g},
                     "groups": ggroups, "lm_head": lm_g}
            new_params, new_opt, om = adamw.update(params, grads, opt_state,
                                                   tcfg.optimizer)
            mv = mvec[0] / world
            metrics = {k: mv[i + 1] for i, k in enumerate(mkeys)}
            metrics = dict(metrics, **om, loss=mv[0])
            return new_params, new_opt, metrics

        mapped = runtime.sharded(
            apply, self.mesh,
            in_specs=(P(), P()) + (P(ax, None),) * (len(sizes) + 1),
            out_specs=(P(), P(), P()), check=False)
        self._apply_c = jax.jit(mapped, donate_argnums=(0, 1))

        algo, chunks, codec = self._knobs
        self.grad_sync = OverlappedGradSync(
            self.comm, [(0, n) for n in sizes], len(mkeys) + 1, algo=algo,
            chunks=chunks, codec=codec, error_budget=self._budget,
            donate=self.donate)

    # -- the step -----------------------------------------------------------

    def _segmented_step(self, params, opt_state, batch, n: int):
        """Backward newest-to-oldest, starting bucket i's allreduce before
        computing segment i+1's backward — under async dispatch bucket i's
        communication runs while the next segment's VJP executes. The
        barrier twin blocks out each bucket before touching the next
        segment (same compiled programs, so the two are bit-identical).
        ``n`` is the step's number, which its spans carry."""
        gs, K = self.grad_sync, len(self.bounds)
        with _tm.span("train/step", step=n, mode="segmented",
                      overlap=self.overlap):
            with _tm.span("train/fwd", step=n):
                outs = self._fwd_c(params, batch)
            hs, h_out, aux = outs[:K], outs[K], outs[K + 1]
            with _tm.span("train/head_bwd", step=n):
                head_flat, dh, mvec = self._head_bwd_c(params, h_out, aux,
                                                       batch)
            if self.overlap:
                handles = [gs.start(0, head_flat)]
                mh = gs.start_metric(mvec)
                for j, k in enumerate(range(K - 1, -1, -1)):
                    with _tm.span("train/chunk_bwd", step=n, k=k):
                        bflat, dh = self._chunk_bwd_c[k](params, hs[k], dh)
                    handles.append(gs.start(1 + j, bflat))
                with _tm.span("train/embed_bwd", step=n):
                    eflat = self._embed_bwd_c(params, batch, dh)
                handles.append(gs.start(K + 1, eflat))
                synced = [gs.wait(i, h, block=False)
                          for i, h in enumerate(handles)]
                mvec_s = mh.wait(block=False)
            else:
                synced = [gs.run(0, head_flat)]
                mvec_s = gs.start_metric(mvec).wait(block=True)
                for j, k in enumerate(range(K - 1, -1, -1)):
                    with _tm.span("train/chunk_bwd", step=n, k=k):
                        bflat, dh = self._chunk_bwd_c[k](params, hs[k], dh)
                    synced.append(gs.run(1 + j, bflat))
                with _tm.span("train/embed_bwd", step=n):
                    eflat = self._embed_bwd_c(params, batch, dh)
                synced.append(gs.run(K + 1, eflat))
            with _tm.span("train/apply", step=n):
                return self._apply_c(params, opt_state, *synced, mvec_s)

    def __call__(self, params, opt_state, batch, step: Optional[int] = None):
        """One train step. ``step`` feeds the error-budget schedule (when a
        callable was given); defaults to an internal counter. Returns
        ``(new_params, new_opt_state, metrics)``."""
        if self.mode is None:
            self._build(params, batch)
        if step is None:
            step = self._auto_step
        self._auto_step = int(step) + 1
        self.grad_sync.ensure_ops(int(step))
        if self.mode == "segmented":
            return self._segmented_step(params, opt_state, batch, int(step))
        with _tm.span("train/step", step=int(step), mode="monolithic",
                      overlap=self.overlap):
            with _tm.span("train/backward", step=int(step)):
                outs = self._backward_c(params, batch)
            synced, mvec = self.grad_sync.sync(outs[:-1], outs[-1],
                                               overlap=self.overlap)
            with _tm.span("train/apply", step=int(step)):
                return self._apply_c(params, opt_state, *synced, mvec)


def make_overlapped_train_step(cfg, tcfg: TrainConfig, mesh, topo,
                               algo: str = "auto", error_budget=0.0,
                               bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                               chunks: Optional[int] = None,
                               codec: Optional[str] = None,
                               overlap: bool = True,
                               donate: bool = False,
                               segmented="auto") -> _OverlappedStep:
    """Bucketed DP train step with **persistent nonblocking** gradient sync
    (the Communicator overlap shape; see the module docstring).

    Same data-parallel semantics as :func:`make_manual_train_step`
    (bucketed, algo/chunks/codec knobs, loss+scalar-metric sync lossless,
    ``topo`` may be a Topology or a group Communicator from
    ``comm.split``), including error feedback: compressed buckets thread
    per-bucket EF residuals through **carry ops** (``start(x, carry=err)``)
    exactly like the fused step's ``err_state``, so the two paths no
    longer diverge semantically. ``error_budget`` may additionally be a
    schedule ``callable(step) -> float`` (codec plan re-resolved only at
    plan boundaries; ops released and rebuilt through the exec cache).

    ``segmented`` selects the backward decomposition: ``"auto"`` (default)
    uses layer-wise VJP segments when the model supports it — bucket i's
    allreduce then overlaps bucket i+1's backward *compute*, not just its
    dispatch — falling back to the monolithic backward otherwise;
    ``True`` requires it (raises when unsupported); ``False`` pins the
    monolithic shape. The returned step is ``step(params, opt_state,
    batch, step=None) -> (params, opt_state, metrics)``; ``.mode`` names
    the decomposition chosen and ``.grad_sync`` exposes the persistent ops
    (plan keys, rebuild count, EF state) for tests/benchmarks.
    ``overlap=False`` builds the barrier-style variant of the same
    decomposition — bit-identical results, no pipelining.
    """
    return _OverlappedStep(cfg, tcfg, mesh, topo, algo, error_budget,
                           bucket_bytes, chunks, codec, overlap, donate,
                           segmented=segmented)
