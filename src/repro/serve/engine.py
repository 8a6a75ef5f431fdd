"""Batched serving engine: continuous prefill+decode over a request queue.

Serving loop structure (vLLM-style, reduced):
  - requests arrive with a prompt (token array) and max_new_tokens,
  - the engine packs up to `max_batch` active sequences into one fixed
    KV-cache block (padded slots are masked),
  - one prefill pass per admitted request fills its cache rows,
  - a single fused decode step advances every active sequence each tick;
    finished sequences (EOS or budget) free their slot for the next queue
    entry (continuous batching).

Token-level sync across DP replicas (multi-host) is a small-message
collective — the paper's regime. When the engine is given a mesh/topology
it binds a ``Communicator`` (``repro.core.comm``) — and, with
``sync_axes=...``, scopes the sync to a sub-communicator
(``comm.split(axes=sync_axes)``, e.g. the DP group of a DPxTP mesh) — and
syncs each tick's sampled tokens through a **persistent broadcast op**: the
tick payload
shape is fixed at ``(max_batch,)``, so the ``(algo, chunks, codec)`` plan
is resolved and the executable compiled once on the first tick
(``comm.broadcast_init``), and every later tick is a bare
``op.start(...).wait()`` — no cache lookups on the serving hot path. The
algorithm comes from the selection subsystem (``algo="auto"``: cost-model
prior until a calibration table is loaded, measured table after — the op
re-resolves when the tuning table mutates, tracked by generation). The
engine exposes ``sync_error_budget`` — the subsystem-wide accuracy knob —
on that plan resolution (integer token payloads always resolve lossless;
see ``Engine.__init__``)."""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.comm import Communicator, PersistentOp
from repro.core.topology import Topology
from repro.models import decoder
from repro.models.decoder import RunFlags

#: sync-plan rebinds (tuning-table generation changes) tolerated silently;
#: past this, one rate-limited warning names the storm so the flat
#: ``live_persistent_ops()`` assertion has a diagnostic to point at
REBIND_WARN_THRESHOLD = 3


@dataclasses.dataclass
class Request:
    prompt: np.ndarray              # (T,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    out_tokens: Optional[List[int]] = None


class Engine:
    def __init__(self, params, cfg, max_batch: int = 8, max_len: int = 256,
                 flags: RunFlags = RunFlags(), greedy: bool = True,
                 mesh=None, topo: Optional[Topology] = None,
                 sync_axes=None, sync_algo: str = "auto",
                 sync_error_budget: float = 0.0):
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.flags = flags
        # DP replica token sync: algorithm resolved per tick payload by the
        # selection subsystem (sync_algo="auto"), or pinned explicitly.
        # sync_error_budget is the engine's accuracy knob on that plan: it
        # flows into the selector's codec gating (core.compress); integer
        # token payloads resolve lossless for any budget (lossy codecs are
        # inadmissible on integers), but the knob is part of the engine API
        # so float-payload syncs (logit / hidden-state replication) inherit
        # the budget semantics.
        # sync_axes scopes the tick sync to a sub-communicator —
        # ``comm.split(axes=sync_axes)`` — e.g. sync_axes="node" broadcasts
        # within each DP replica group while TP shards stay independent.
        # Calibration for the sync plan then belongs on ``self.sync_comm``
        # (the group's tuning rows are namespaced by the group tag).
        self.mesh = mesh
        # Communicator(mesh, None) derives the default node/local topology
        # when the mesh has those axes, and is an *unscoped root* (topo
        # None) otherwise — split(axes=...) still works on it, so
        # sync_axes= remains the way to serve on e.g. a 3-axis MoE mesh.
        self.comm = (Communicator(mesh, topo) if mesh is not None else None)
        self.topo = self.comm.topo if self.comm is not None else topo
        self.sync_comm = (self.comm.split(axes=sync_axes)
                          if self.comm is not None and sync_axes is not None
                          else self.comm)
        if mesh is not None and (self.sync_comm is None
                                 or self.sync_comm.topo is None):
            # fail at construction, not on the first mid-serving tick: an
            # unscoped root would slip past _sync_tokens' world-1 guard and
            # blow up inside broadcast_init with a live batch in flight
            raise ValueError(
                f"engine tick-sync needs a scoped communicator: mesh axes "
                f"{tuple(mesh.axis_names)} do not map onto the default "
                f"node/local topology. Pass sync_axes=<axis or (axis, "
                f"axis)> so the engine scopes the sync via comm.split("
                f"axes=...), or pass an explicit topo=.")
        self.sync_algo = sync_algo
        self.sync_error_budget = float(sync_error_budget)
        # lazily bound on the first real sync (a world-1 engine never pays
        # for plan resolution or compilation — see _sync_tokens); rebound
        # when the selector's tuning table mutates, so a calibration table
        # loaded mid-serving still flips auto to the measured plan
        self._sync_op: Optional[PersistentOp] = None
        self._sync_gen: int = -1
        # per-engine observability: tick latency histogram (host-side,
        # timed around the whole decode+sync tick — no extra device sync),
        # slot-occupancy accumulator, and the sync-plan rebind counter
        # behind Engine.metrics(). Always on: one perf_counter pair and a
        # histogram bump per tick is noise next to a decode step.
        self._tick_hist = telemetry.Histogram("serve.tick_seconds")
        self._ticks = 0
        self._occupied_slot_ticks = 0
        self.rebinds = 0
        self._rebind_warned = False
        self.caches = decoder.init_cache(cfg, max_batch, max_len)
        self.lengths = np.zeros(max_batch, np.int32)
        self.active: List[Optional[Request]] = [None] * max_batch

        def prefill(params, caches, tokens):
            logits, _, new_c = decoder.forward(params, tokens, cfg,
                                               flags=flags, caches=caches)
            return logits[:, -1:], new_c

        def decode(params, caches, tokens, index):
            logits, _, new_c = decoder.forward(params, tokens, cfg,
                                               flags=flags, caches=caches,
                                               cache_index=index)
            return logits, new_c

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(1,))

    def _sync_tokens(self, nxt: np.ndarray) -> np.ndarray:
        """Cross-replica agreement on each slot's next token (greedy decode
        is deterministic, but sampled decode diverges across hosts without
        this). Small-message broadcast — the paper's latency-bound regime —
        through a persistent op: plan + executable fixed on the first tick,
        every later tick a bare start/wait."""
        if self.mesh is None or (self.sync_comm.topo is not None
                                 and self.sync_comm.topo.world == 1):
            return nxt  # nothing to reconcile; skip the per-token dispatch
        arr = jnp.asarray(nxt, jnp.int32)
        gen = self.sync_comm.selector.table.generation
        if self._sync_op is None or gen != self._sync_gen:
            # (re)resolve the plan: first tick, or the tuning table changed
            # (e.g. a calibration table loaded mid-serving) — re-init is an
            # exec-cache hit when the resolved plan is unchanged. Release
            # the op being replaced (rebind hygiene: an orphaned op would
            # linger in the live-op count and pin donated buffers).
            if self._sync_op is not None:
                self._sync_op.release()
                # a *re*bind (not the first bind): a storm of these —
                # e.g. a budget schedule oscillating the tuning table
                # every tick — used to be completely silent
                self.rebinds += 1
                telemetry.counter("serve.plan_rebinds").inc()
                if (self.rebinds > REBIND_WARN_THRESHOLD
                        and not self._rebind_warned):
                    self._rebind_warned = True
                    warnings.warn(
                        f"engine sync-plan rebind storm: {self.rebinds} "
                        f"rebinds over {self._ticks} ticks (tuning-table "
                        f"generation now {gen}); something is mutating the "
                        f"selector table every few ticks — each rebind "
                        f"releases and re-inits the persistent sync op "
                        f"(exec-cache hits, but plan resolution per tick). "
                        f"See Engine.metrics()['plan_rebinds'].",
                        RuntimeWarning, stacklevel=3)
            self._sync_op = self.sync_comm.broadcast_init(
                arr, algo=self.sync_algo,
                error_budget=self.sync_error_budget)
            self._sync_gen = gen
        return np.asarray(self._sync_op.start(arr).wait(block=False)[0])

    # NOTE: slot-at-a-time prefill keeps the demo simple; the fused decode
    # step is the performance-relevant path.
    def _admit(self, req: Request, slot: int):
        T = len(req.prompt)
        assert T < self.max_len
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        # run prefill on a single-row cache view, then write it back
        # (cache leaves are (n_cycles, batch, ...): batch is dim 1)
        row = jax.tree.map(lambda c: c[:, slot:slot + 1], self.caches)
        last_logits, row = self._prefill(self.params, row, tokens)
        self.caches = jax.tree.map(
            lambda c, r: c.at[:, slot:slot + 1].set(r), self.caches, row)
        self.lengths[slot] = T
        req.out_tokens = [int(last_logits[0, 0].argmax())]
        self.active[slot] = req

    def run(self, requests: List[Request], max_ticks: int = 10000
            ) -> List[Request]:
        queue = list(requests)
        done: List[Request] = []
        ticks = 0
        while (queue or any(self.active)) and ticks < max_ticks:
            ticks += 1
            t_tick = time.perf_counter()
            with telemetry.span("serve/tick", tick=ticks):
                # admit
                for slot in range(self.max_batch):
                    if self.active[slot] is None and queue:
                        self._admit(queue.pop(0), slot)
                # fused decode tick: every active slot advances one token,
                # each at its OWN cache index (a (B,) vector): slot b's new
                # KV row lands at lengths[b] and its attention masks to
                # lengths[b]+1. A uniform max index would jump a freshly
                # admitted short row past its true length, leaving
                # uninitialized KV it then attends over (mixed-length
                # admission corruption).
                toks = np.zeros((self.max_batch, 1), np.int32)
                for slot, req in enumerate(self.active):
                    if req is not None:
                        toks[slot, 0] = req.out_tokens[-1]
                logits, self.caches = self._decode(
                    self.params, self.caches, jnp.asarray(toks),
                    jnp.asarray(self.lengths, jnp.int32))
                nxt = self._sync_tokens(np.asarray(logits[:, 0].argmax(-1)))
                for slot, req in enumerate(self.active):
                    if req is None:
                        continue
                    req.out_tokens.append(int(nxt[slot]))
                    self.lengths[slot] += 1
                    if (len(req.out_tokens) >= req.max_new_tokens or
                            (req.eos_id is not None
                             and req.out_tokens[-1] == req.eos_id)):
                        done.append(req)
                        self.active[slot] = None
            dt = time.perf_counter() - t_tick
            active_n = sum(r is not None for r in self.active)
            self._ticks += 1
            self._occupied_slot_ticks += active_n
            self._tick_hist.observe(dt)
        done.extend([r for r in self.active if r is not None])
        return done

    def metrics(self) -> dict:
        """Per-engine serving metrics: tick-latency distribution (p50/p99
        seconds over every decode+sync tick this engine has run), mean slot
        occupancy (active slots / max_batch, post-retire), and the
        sync-plan rebind count (see ``REBIND_WARN_THRESHOLD``)."""
        h = self._tick_hist
        return {
            "ticks": self._ticks,
            "tick_p50_s": h.quantile(0.50),
            "tick_p99_s": h.quantile(0.99),
            "tick_mean_s": h.mean,
            "slot_occupancy": (self._occupied_slot_ticks
                               / (self._ticks * self.max_batch)
                               if self._ticks else 0.0),
            "plan_rebinds": self.rebinds,
            "sync_starts": (self._sync_op.starts
                            if self._sync_op is not None else 0),
        }
