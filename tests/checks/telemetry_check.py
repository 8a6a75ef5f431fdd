"""Telemetry acceptance on a real (N, P) CPU mesh.

Usage: telemetry_check.py N P   (run under XLA_FLAGS device_count = N*P)

Asserts:
  1. a segmented-overlapped train step run with telemetry on, under a
     ``jax.profiler`` trace, puts every backward stage (fwd, head_bwd,
     per-chunk chunk_bwd, embed_bwd, apply) in the profiler's trace as a
     span nested inside the enclosing train/step span, with one
     ``comm/start`` span per bucket allreduce (and the metrics vector),
     tagged with its plan and bucket, inside the same window (the overlap
     timeline on the device's clock);
  2. the drift detector flags a poisoned tuning-table row (a fake-fast
     entry that hijacks selection) and ``Selector.ingest`` repairs the
     table from the observed medians so ``choose`` recovers;
  3. the telemetry hooks cost < 2% on the persistent-op hot path when the
     tracer is disabled (stripped-replica baseline, min-of-medians);
  4. ``snapshot()`` unifies cache/selection/live-op observables non-trivially.
"""
import glob
import sys
import tempfile

N, P = int(sys.argv[1]), int(sys.argv[2])

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune, runtime, telemetry
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh

mesh = make_mesh((N, P), ("node", "local"))
topo = Topology.from_mesh(mesh)
comm = Communicator(mesh, topo)
telemetry.enable()

# --- 1. segmented-overlapped train step -> nested spans -------------------
from repro.configs import reduced_config
from repro.models import decoder
from repro.models.decoder import RunFlags
from repro.optim import adamw
from repro.train import manual_step
from repro.train.step import TrainConfig

M = N * P
cfg = reduced_config("smollm-360m")
ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10,
                         schedule="constant", grad_clip=1e9)
tcfg = TrainConfig(optimizer=ocfg, flags=RunFlags(remat="none"))
key = jax.random.PRNGKey(0)
batch = {"tokens": jax.random.randint(key, (max(M, 2), 32), 0, cfg.vocab),
         "labels": jax.random.randint(jax.random.PRNGKey(1),
                                      (max(M, 2), 32), 0, cfg.vocab)}
params = decoder.init(key, cfg)
opt = adamw.init(params, ocfg)
step = manual_step.make_overlapped_train_step(
    cfg, tcfg, mesh, topo, algo="pip_pipeline", bucket_bytes=256 << 10,
    overlap=True, segmented=True)
for _ in range(2):  # compile + settle shardings outside the traced window
    params, opt, m = step(params, opt, batch)
    jax.block_until_ready((params, m["loss"]))
telemetry.reset()
with tempfile.TemporaryDirectory() as out:
    # the trace covers exactly one steady-state step
    with jax.profiler.trace(out):
        params, opt, m = step(params, opt, batch)
        jax.block_until_ready((params, m["loss"]))
    (xplane,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    profile = jax.profiler.ProfileData.from_file(xplane)

by_name = {}
for plane in profile.planes:
    if plane.name.startswith("/host:"):
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train/", "comm/")):
                    by_name.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats)))
spans = [s for v in by_name.values() for s in v]
((lo, hi, _),) = by_name["train/step"]
n_chunks = len(step.bounds)
assert sorted(st["k"] for *_, st in by_name["train/chunk_bwd"]) == \
    list(range(n_chunks))
stage_names = ["train/fwd", "train/head_bwd", "train/chunk_bwd",
               "train/embed_bwd", "train/apply"]
for name in stage_names:
    for s, e, _ in by_name[name]:
        assert lo <= s and e <= hi, (name, s, e, lo, hi)
# one comm/start per bucket allreduce and the metrics vector, each inside
# the step window: the bucket starts interleave with the backward stages
n_buckets = len(step.grad_sync.slices)
starts = by_name["comm/start"]
assert len(starts) == n_buckets + 1, (len(starts), n_buckets)
assert sorted(str(st["bucket"]) for *_, st in starts) == sorted(
    [str(i) for i in range(n_buckets)] + ["metrics"])
for s, e, st in starts:
    assert lo <= s and e <= hi, st
    assert st["collective"] == "allreduce" and st["algo"], st

# --- 2. drift detector flags a poisoned row; ingest repairs it ------------
telemetry.reset()
nbytes = 4096
comm.calibrate(names=("allreduce",), sizes=(nbytes,), iters=4)
sel = comm.selector
good = sel.choose("allreduce", topo, nbytes)
good_plan = autotune.encode_plan(good.algo, good.chunks, good.codec)
entry = sel.table.lookup(topo, "allreduce", "float32", nbytes)
# victim must be a lossless plan: choose() under the default zero error
# budget never admits codec plans, poisoned or not
victim = sorted(p for p in entry
                if p != good_plan
                and autotune.decode_plan(p)[2] == "none")[0]
# poison: a fake-fast table row hijacks selection toward the victim plan
sel.table.record(topo, "allreduce", "float32", nbytes, victim, 1e-9)
hijacked = sel.choose("allreduce", topo, nbytes)
assert autotune.encode_plan(hijacked.algo, hijacked.chunks,
                            hijacked.codec) == victim, hijacked
flagged = telemetry.drifted_plans(selector=sel)
assert any(r.plan == victim and r.collective == "allreduce"
           for r in flagged), flagged
victim_row = next(r for r in flagged if r.plan == victim)
assert victim_row.table_s == 1e-9 and victim_row.drift_vs_table > 0.5
# ingest folds the observed medians back in: the poisoned row is repaired
# and selection recovers without re-running calibration
n_ingested = sel.ingest(min_samples=2)
assert n_ingested >= len(entry), n_ingested
repaired = sel.choose("allreduce", topo, nbytes)
assert autotune.encode_plan(repaired.algo, repaired.chunks,
                            repaired.codec) == good_plan, repaired
assert not any(r.plan == victim
               for r in telemetry.drifted_plans(selector=sel))

# --- 3. disabled-path overhead guard: the telemetry hooks left in the
# persistent-op hot path (the span tags a caller passes, an enabled() read
# and a _dispatch frame in start, a None-tags check in wait) must cost
# < 2% of a start/wait round trip when telemetry is off.
#
# Measured in two parts because an end-to-end A/B subtraction cannot
# resolve 2% here: an A/A control (timing the SAME function in both slots
# of a pairwise-interleaved loop) shows a +-2-3% noise floor on this
# 8-thread-device CPU target, i.e. the round trip's run-to-run variance
# swamps the quantity under test. So:
#   (a) the precise bound times the exact instructions the disabled path
#       adds, amortized over a tight loop (deterministic to ~ns), against
#       the measured round trip — this is the <2% assertion;
#   (b) an interleaved end-to-end A/B keeps a loose sanity bound (<15%,
#       above the noise floor) so a gross regression — e.g. an always-on
#       perf_counter or observe_plan landing in the disabled path — still
#       fails the check even if it hides from the enumerated-hook loop.
telemetry.disable()
import time as _time

op = comm.allreduce_init(shape=(M, 1 << 14), dtype=jnp.float32,
                         algo="pip_pipeline")
xb = jnp.ones((M, 1 << 14), jnp.float32)
op.start(xb).wait()  # warm the executable


def instrumented_once():
    op.start(xb).wait(block=True)


def stripped_once():
    # start()+wait(block=True) minus the telemetry lines — the baseline a
    # hypothetical hook-free build would run
    x2 = op._check_operand(xb)
    op._inflight += 1
    op.starts += 1
    v = op._compiled(x2)
    op._inflight -= 1
    jax.block_until_ready(v)


def dispatch_frame(x, carry):
    # stands for the PersistentOp._dispatch call that start() makes
    return x


def hook_lines_once(x, carry=None, **tags):
    # exactly what telemetry adds to a disabled start/wait round trip: the
    # caller's span tags packed into **tags (OverlappedGradSync passes
    # bucket= and step=), the enabled() read in start, the _dispatch call
    # frame, the handle's (tags, t0) defaults, and the None-tags check in
    # wait
    if telemetry.enabled():
        raise AssertionError("telemetry must be disabled here")
    v = dispatch_frame(x, carry)
    handle_tags, t0 = None, 0.0
    if handle_tags is not None:
        raise AssertionError
    return v


HOOK_REPS = 200_000
t0 = _time.perf_counter()
for i in range(HOOK_REPS):
    hook_lines_once(xb, bucket=i, step=i)
hook_s = (_time.perf_counter() - t0) / HOOK_REPS

# round trip: block-averaged so per-call scheduling noise amortizes
RT_BLOCK, rt = 50, []
for _ in range(6):
    t0 = _time.perf_counter()
    for _ in range(RT_BLOCK):
        instrumented_once()
    rt.append((_time.perf_counter() - t0) / RT_BLOCK)
rt_s = sorted(rt)[len(rt) // 2]

overhead = hook_s / rt_s
assert overhead < 0.02, \
    f"disabled-telemetry dispatch overhead {overhead:.2%} " \
    f"(hooks {hook_s * 1e9:.0f}ns vs round trip {rt_s * 1e6:.1f}us)"

# (b) end-to-end sanity: interleaved A/B with a bound above the measured
# noise floor
inst_s, strip_s = [], []
for r in range(200):
    t0 = _time.perf_counter()
    (stripped_once if r % 2 else instrumented_once)()
    t1 = _time.perf_counter()
    (instrumented_once if r % 2 else stripped_once)()
    t2 = _time.perf_counter()
    (strip_s if r % 2 else inst_s).append(t1 - t0)
    (inst_s if r % 2 else strip_s).append(t2 - t1)
inst_med = sorted(inst_s)[len(inst_s) // 2]
strip_med = sorted(strip_s)[len(strip_s) // 2]
e2e = (inst_med - strip_med) / strip_med
assert e2e < 0.15, \
    f"end-to-end disabled-telemetry overhead {e2e:.2%} " \
    f"({inst_med * 1e6:.1f}us vs {strip_med * 1e6:.1f}us) — far above " \
    f"the hook-level bound; something heavy runs on the disabled path"
telemetry.enable()

# --- 4. unified snapshot --------------------------------------------------
snap = telemetry.snapshot()
assert snap["enabled"]
assert snap["cache"]["exec_hits"] > 0
assert snap["selection"]["total"] > 0 and snap["selection"]["by_choice"]
assert any(p["collective"] == "allreduce" and p["samples"] >= 2
           for p in snap["plans"])
assert snap["histograms"], "registry never observed a latency"

print(f"telemetry_check N={N} P={P}: OK spans={len(spans)} "
      f"buckets={n_buckets} chunks={n_chunks} victim={victim} "
      f"ingested={n_ingested} good={good_plan}")
