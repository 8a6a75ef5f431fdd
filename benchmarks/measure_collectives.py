"""Wall-clock the real shard_map collective implementations on 8 CPU host
devices (launched by benchmarks/run.py with XLA_FLAGS set). CPU collective
timing does not model ICI, but the ROUND-COUNT ordering (pip_mcoll fewer
rounds than flat algorithms) shows up in dispatch overhead, and correctness
of every algorithm is asserted on the way.

All invocations go through the Communicator API (repro.core.comm) backed
by the runtime's compiled-callable cache: the first call per (collective,
algo, shape) key compiles, every timed call is a cache hit, so
re-trace/re-jit overhead is excluded from the measured numbers. Hit/miss
totals are emitted as a measured/ row for run.py.

Modes:
  (default)             measured rows for allgather/allreduce, every
                        explicit algorithm plus algo="auto" (result
                        asserted identical to the explicit runs), a chunk
                        sweep of the pipelined allreduce, and compressed
                        rows per codec (wall-clock + achieved error vs the
                        codec's stated bound).
  --calibrate OUT.json  run comm.calibrate over all six collectives
                        (chunked and codec plans included), persist the
                        tuning table + latency rows + a model-vs-measured
                        crossover comparison + the pipeline-crossover
                        table + a compression section (achieved ratio /
                        error, crossover vs lossless) as JSON
                        (the BENCH_collectives artifact).
  --overlap [OUT.json]  persistent-op overlap leg: barrier-style vs
                        overlapped bucketed allreduce (one persistent op,
                        depth=1 start/wait pairs vs depth=K windowed
                        starts), the init-vs-start amortization curve, and
                        the four-leg **train-step** matrix ({monolithic,
                        backward-segmented} x {barrier, overlapped}) with
                        paired-difference deltas and the >=8-device
                        non-regression gate. With OUT.json, merges an
                        "overlap" section into the artifact
                        (results/BENCH_collectives.json).
  --codec-kernels [OUT.json]
                        codec-kernel microbench: fused Pallas codec
                        lowerings vs the jnp reference path per fused
                        codec (wall-clock both jitted, analytic HBM
                        traffic per stage, roofline seconds at v5e's
                        HBM bandwidth),
                        asserting the fused encode pass moves <= half the
                        jnp path's bytes; with OUT.json, merges a
                        "codec_kernels" section into the artifact and
                        writes results/BENCH_codec_kernels.json.

The mesh factors the ambient device count into (node, local) — run.py
forces 8 host devices (4x2); the CI conformance matrix runs the overlap
leg at {1, 2, 8}.

Under the multi-process launcher (``python -m repro.distributed.launch
--processes K --devices M -- benchmarks/measure_collectives.py
--calibrate OUT``) the mesh is ``(K processes, M devices)`` with the node
axis on the process boundary (host_ipc inter / host_cpu intra links); only
``--calibrate`` is supported there — every rank runs the SPMD sweeps,
rank 0 merges the tables and writes one artifact stamped
``backend="multiprocess"`` / ``process_count=K``.
"""
import argparse
import contextlib
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (artifact as artifact_schema, autotune, compress,
                        costmodel, mcoll, runtime, telemetry)
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.distributed import backend as dist_backend
from repro.launch import cache as compile_cache
from repro.launch.mesh import make_mesh, make_process_mesh

# must run before the first device query: under the repro.distributed
# launcher this joins the multi-controller runtime (no-op otherwise)
BACKEND = dist_backend.auto_initialize()
compile_cache.enable()

DC = jax.device_count()
if BACKEND.multiprocess:
    # node axis == process boundary, so derive_link splits host_ipc (inter)
    # from host_cpu (intra) — the hierarchy the calibration is measuring
    mesh = make_process_mesh()
    N, P = mesh.devices.shape
else:
    P = 2 if DC % 2 == 0 else 1
    N = DC // P
    mesh = make_mesh((N, P), ("node", "local"))
topo = Topology.from_mesh(mesh)
comm = Communicator(mesh, topo)

# cross-process gloo runs are far slower per dispatch than in-process host
# devices; trim the sweep so the multiprocess calibrate leg stays tractable
CAL_SIZES = (256, 4096) if BACKEND.multiprocess else (256, 4096, 65536)
CAL_ITERS = 3 if BACKEND.multiprocess else 10


def bench(fn, x, n=20):
    out = jax.block_until_ready(fn(x))  # compile (exec-cache miss)
    t0 = time.time()
    for _ in range(n):                  # timed calls are all cache hits
        out = jax.block_until_ready(fn(x))
    return (time.time() - t0) / n * 1e6, out


def measure_mode():
    for nbytes in (256, 65536):
        m = nbytes // 4 // (N * P)
        x = jnp.arange(N * P * max(m, 1), dtype=jnp.float32)
        ag_out = None
        for algo in mcoll.algorithms("allgather"):
            if algo not in autotune.candidates("allgather", topo):
                continue
            fn = lambda a, _algo=algo: comm.allgather(a, algo=_algo,
                                                      stacked=True)
            us, out = bench(fn, x)
            ok = bool((np.asarray(out)[0] == np.asarray(x)).all())
            assert ok, algo
            ag_out = np.asarray(out)
            print(f"measured/allgather/{algo}/{nbytes}B,{us:.1f},8cpu-dev ok")
        # algo="auto": resolved through the selector, result must match
        resolved, _ = runtime.resolve_algo(topo, "allgather", "auto", x)
        fn = lambda a: comm.allgather(a, stacked=True)
        us, out = bench(fn, x)
        np.testing.assert_array_equal(np.asarray(out), ag_out)
        print(f"measured/allgather/auto/{nbytes}B,{us:.1f},"
              f"resolved={resolved}")
        for algo in mcoll.algorithms("allreduce"):
            if algo not in autotune.candidates("allreduce", topo):
                continue
            z = jnp.ones((N * P, max(m, 1)), jnp.float32)
            fn = lambda a, _algo=algo: comm.allreduce(a, algo=_algo)
            us, out = bench(fn, z)
            print(f"measured/allreduce/{algo}/{nbytes}B,{us:.1f},8cpu-dev ok")
        z = jnp.ones((N * P, max(m, 1)), jnp.float32)
        resolved, _ = runtime.resolve_algo(topo, "allreduce", "auto", z)
        us, out = bench(lambda a: comm.allreduce(a), z)
        np.testing.assert_allclose(np.asarray(out)[0],
                                   np.full(max(m, 1), N * P, np.float32))
        print(f"measured/allreduce/auto/{nbytes}B,{us:.1f},"
              f"resolved={resolved}")

    # pipelined allreduce chunk sweep at the largest size: wall-clock per
    # chunk count, results asserted identical to chunks=1
    m = 65536 // 4 // (N * P)
    z = jnp.ones((N * P, m), jnp.float32)
    base = None
    for c in (1, 2, 4, 8):
        us, out = bench(lambda a, _c=c: comm.allreduce(
            a, algo="pip_pipeline", chunks=_c), z)
        if base is None:
            base = np.asarray(out)
        else:
            np.testing.assert_allclose(np.asarray(out), base, rtol=1e-6)
        print(f"measured/allreduce/pip_pipeline_c{c}/65536B,{us:.1f},"
              f"8cpu-dev ok")

    # compressed allreduce per codec at the largest size: wall-clock +
    # achieved relative error vs the exact sum (the accuracy side of the
    # wire-ratio trade, asserted against the codec's stated bound)
    zr = (jax.random.normal(jax.random.PRNGKey(0), (N * P, m)) * 0.01)
    exact = np.asarray(zr).sum(0)
    A = float(np.abs(np.asarray(zr)).max())
    denom = np.abs(exact).max() + 1e-12
    for cd in compress.lossy():
        us, out = bench(lambda a, _cd=cd: comm.allreduce(
            a, algo="pip_mcoll", codec=_cd), zr)
        err = float(np.abs(np.asarray(out)[0] - exact).max())
        tol = compress.collective_tolerance(cd, "allreduce", N * P, A)
        assert err <= tol + 1e-7, (cd, err, tol)
        print(f"measured/allreduce/pip_mcoll@{cd}/65536B,{us:.1f},"
              f"rel_err={err / denom:.5f} "
              f"ratio={compress.meta(cd).wire_ratio:.2f}x")

    stats = runtime.cache_stats()
    assert stats.exec_hits > 0 and stats.exec_misses > 0, stats
    print(f"measured/runtime_cache,0.0,exec_hits={stats.exec_hits} "
          f"exec_misses={stats.exec_misses} "
          f"hit_rate={stats.exec_hit_rate:.3f}")
    sstats = runtime.selection_stats()
    print(f"measured/selection,0.0,prior={sstats.prior} "
          f"measured={sstats.measured}")


def calibrate_mode(out_path: str):
    sel = comm.selector
    # multiprocess trims codec plans too (the compression section below
    # still measures every lossy codec end to end on the same mesh)
    rows = comm.calibrate(sizes=CAL_SIZES, iters=CAL_ITERS,
                          codecs=(() if BACKEND.multiprocess else None))
    for r in rows:
        plan = autotune.encode_plan(r.algo, r.chunks, r.codec)
        print(f"calibrate/{r.collective}/{plan}/{r.nbytes}B,"
              f"{r.seconds * 1e6:.1f},measured")
    # model-vs-measured: where does the measured winner disagree with the
    # cost-model prior on this mesh?
    prior_sel = autotune.Selector()  # empty table -> prior only
    comparison = []
    agree = 0
    for name in runtime.collectives():
        for nbytes in CAL_SIZES:
            measured = sel.choose(name, topo, nbytes)
            prior = prior_sel.choose(name, topo, nbytes)
            match = measured.algo == prior.algo
            agree += match
            # per-plan signed relative error (measured - model) / model:
            # every measured plan at this (collective, size), not just the
            # crossover verdict — the drift detector's offline counterpart
            per_plan = []
            entry = sel.table.lookup(topo, name, "float32", nbytes) or {}
            for plan_key in sorted(entry):
                meas_s = entry[plan_key]
                model_s = autotune.predicted_seconds(name, plan_key, topo,
                                                     nbytes)
                per_plan.append({
                    "plan": plan_key,
                    "measured_us": meas_s * 1e6,
                    "model_us": (model_s * 1e6
                                 if model_s and model_s > 0.0 else None),
                    "signed_rel_err": ((meas_s - model_s) / model_s
                                       if model_s and model_s > 0.0
                                       else None),
                })
            comparison.append({
                "collective": name, "nbytes": nbytes,
                "measured_algo": measured.algo,
                "measured_us": measured.seconds * 1e6,
                "prior_algo": prior.algo,
                "prior_us": prior.seconds * 1e6,
                "agree": match,
                "per_plan": per_plan,
            })
            print(f"calibrate/crossover/{name}/{nbytes}B,0.0,"
                  f"measured={measured.algo} prior={prior.algo} "
                  f"agree={match}")
    total = len(comparison)
    print(f"calibrate/model_vs_measured,0.0,agree={agree}/{total}")
    # pipeline crossover: per pipelined pair, modeled unchunked vs
    # optimally-chunked latency across a size sweep (where does chunking
    # start to win?) plus the measured per-plan medians at the calibrated
    # sizes, so the artifact shows model and measurement side by side
    net = costmodel.net_for(topo)
    pipeline_rows = []
    for coll in runtime.collectives():
        for algo in sorted(mcoll.CHUNKED[coll]):
            fn = costmodel.COST_FNS[coll]
            xover = costmodel.pipeline_crossover_bytes(coll, algo, topo, net)
            model_sweep = []
            for nbytes in (256, 4096, 65536, 1 << 20, 1 << 24):
                c = costmodel.optimal_chunks(coll, algo, topo, nbytes, net)
                model_sweep.append({
                    "nbytes": nbytes, "chunks": c,
                    "unchunked_us": fn(algo, topo, nbytes, net,
                                       chunks=1).time * 1e6,
                    "chunked_us": fn(algo, topo, nbytes, net,
                                     chunks=c).time * 1e6,
                })
            measured = {}
            for nbytes in CAL_SIZES:
                entry = sel.table.lookup(topo, coll, "float32", nbytes) or {}
                plans = {k: v * 1e6 for k, v in entry.items()
                         if autotune.decode_plan(k)[0] == algo}
                if plans:
                    measured[str(nbytes)] = plans
            pipeline_rows.append({
                "collective": coll, "algo": algo,
                "model_crossover_bytes": xover,
                "model_sweep": model_sweep,
                "measured_us_by_plan": measured,
            })
            print(f"calibrate/pipeline/{coll}/{algo},0.0,"
                  f"model_crossover={xover}")
    # compression: per codec — declared + achieved wire ratio, achieved
    # error on a measured compressed allreduce (vs its stated bound), the
    # same-algo modeled crossover vs lossless, and the budget-selection
    # crossover (smallest size where auto under that codec's budget goes
    # lossy on this topology)
    compression_rows = []
    m = 65536 // 4 // (N * P)
    zr = (jax.random.normal(jax.random.PRNGKey(0), (N * P, m)) * 0.01)
    exact = np.asarray(zr).sum(0)
    A = float(np.abs(np.asarray(zr)).max())
    sweep_sizes = tuple(2 ** i for i in range(6, 25))
    for cd in compress.lossy():
        c = compress.codec(cd)
        sample = jax.random.normal(jax.random.PRNGKey(1), (1, m))
        achieved_ratio = 4.0 * m / c.wire_bytes(c.encode(sample))
        out = comm.allreduce(zr, algo="pip_mcoll", codec=cd)
        err = float(np.abs(dist_backend.to_host(out)[0] - exact).max())
        bound_abs = compress.collective_tolerance(cd, "allreduce", N * P, A)
        xover_model = costmodel.compressed_crossover_bytes(
            "allreduce", "pip_pipeline", topo, net, cd, sizes=sweep_sizes)
        budget = c.meta.error_bound
        prior_only = autotune.Selector()
        xover_budget = next(
            (s for s in sweep_sizes
             if prior_only.choose("allreduce", topo, s,
                                  error_budget=budget).codec != "none"),
            None)
        compression_rows.append({
            "codec": cd,
            "declared_ratio": c.meta.wire_ratio,
            "achieved_ratio": achieved_ratio,
            "stated_rel_bound": c.meta.error_bound,
            "achieved_abs_error": err,
            "bound_abs_tolerance": bound_abs,
            "model_crossover_vs_lossless_bytes": xover_model,
            "budget_selection_crossover_bytes": xover_budget,
        })
        print(f"calibrate/compression/{cd},0.0,"
              f"ratio={achieved_ratio:.2f}x err={err:.2e} "
              f"bound={bound_abs:.2e} model_crossover={xover_model} "
              f"budget_crossover={xover_budget}")
    artifact = dist_backend.stamp_artifact({
        "topology": autotune.topo_key(topo),
        "sizes": list(CAL_SIZES),
        "table": sel.table.to_json(),
        "latency_rows": [r.__dict__ for r in rows],
        "model_vs_measured": comparison,
        "pipeline_crossover": pipeline_rows,
        "compression": compression_rows,
    })
    # refuse to write a malformed artifact: every section + row key this
    # mode is responsible for must be present (schema in core.artifact)
    artifact_schema.validate(artifact,
                             sections=artifact_schema.CALIBRATE_SECTIONS)
    # comm.calibrate() already folded every rank's rows into rank 0's
    # table, so rank 0 writes the single merged artifact
    if BACKEND.process_index == 0:
        path = pathlib.Path(out_path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(artifact, indent=1, sort_keys=True))
        print(f"calibrate/artifact,0.0,{path}")
    dist_backend.barrier("calibrate_mode/done")


def overlap_mode(out_path=None):
    """Persistent-op overlap leg (the Communicator API's headline claim).

    Three measurements, all deterministic-plan:
      1. bucketed allreduce microbench — one persistent op over a stream of
         K equal buckets: barrier-style (depth=1, wait each start before
         the next) vs overlapped (depth=K, start the whole window then
         wait), i.e. MPI_Start/Wait pairing vs software pipelining;
      2. init-vs-start amortization — one-time plan+compile cost vs the
         per-start cost it buys, amortized over n starts;
      3. train-step delta — four make_overlapped_train_step legs on the
         reduced config: {monolithic, backward-segmented} x {barrier,
         overlapped}, timed in interleaved rounds so paired per-round
         differences cancel drift. The monolithic pair isolates allreduce
         *dispatch* pipelining (one backward program, sync after); the
         segmented pair overlaps bucket i's allreduce with bucket i+1's
         backward *compute*. Twins of one decomposition are bit-identical
         by construction (asserted). delta_ms = the segmented-overlapped
         step vs the monolithic barrier baseline (the end-to-end win); at
         >= 8 devices the leg asserts delta_ms >= 0 and delta_ms >
         dispatch-only overlap (the CI gate).
    """
    M = N * P
    n = (256 << 10) // 4  # 256 KiB per bucket
    K = 8
    algo = "pip_pipeline"
    reps = 5
    buckets = [(jnp.arange(M * n, dtype=jnp.float32) % 7 + b).reshape(M, n)
               for b in range(K)]

    op_b = comm.allreduce_init(shape=(M, n), dtype=jnp.float32, algo=algo,
                               depth=1)
    op_o = comm.allreduce_init(shape=(M, n), dtype=jnp.float32, algo=algo,
                               depth=K)
    # warm both paths (shared compiled executable; asserted identical)
    ref = np.asarray(op_b.start(buckets[0]).wait())
    np.testing.assert_array_equal(
        np.asarray(op_o.start(buckets[0]).wait()), ref)

    def barrier_pass():
        outs = []
        for b in buckets:
            outs.append(op_b.start(b).wait(block=True))
        return outs

    def overlapped_pass():
        handles = [op_o.start(b) for b in buckets]
        outs = [h.wait(block=False) for h in handles]
        jax.block_until_ready(outs)
        return outs

    barrier_pass(), overlapped_pass()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        ob = barrier_pass()
    barrier_us = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for _ in range(reps):
        oo = overlapped_pass()
    overlapped_us = (time.perf_counter() - t0) / reps * 1e6
    for a, b in zip(ob, oo):  # bit-identical across scheduling styles
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    speedup = barrier_us / max(overlapped_us, 1e-9)
    print(f"overlap/microbench/barrier/{K}x{n * 4}B,{barrier_us:.1f},"
          f"plan={op_b.plan}")
    print(f"overlap/microbench/overlapped/{K}x{n * 4}B,{overlapped_us:.1f},"
          f"speedup={speedup:.2f}x")

    # init-vs-start amortization: persistent init pays plan resolution +
    # compile once; a start is a bare dispatch. A fresh shape forces a true
    # cold init (exec-cache miss).
    n2 = n + 16
    xc = jnp.ones((M, n2), jnp.float32)
    t0 = time.perf_counter()
    op_c = comm.allreduce_init(shape=(M, n2), dtype=jnp.float32, algo=algo)
    init_us = (time.perf_counter() - t0) * 1e6
    op_c.start(xc).wait()  # first dispatch warms the executable
    samples = []
    for _ in range(20):
        t0 = time.perf_counter()
        op_c.start(xc).wait(block=True)
        samples.append(time.perf_counter() - t0)
    start_us = float(np.median(samples)) * 1e6
    amortization = [
        {"starts": k, "amortized_us_per_start": (init_us + k * start_us) / k}
        for k in (1, 2, 4, 8, 16, 32, 64)]
    print(f"overlap/amortization,0.0,init_us={init_us:.1f} "
          f"start_us={start_us:.1f} "
          f"breakeven_starts={max(1, int(init_us / max(start_us, 1e-9)))}")

    # train-step leg: barrier vs overlapped bucketed gradient sync on the
    # reduced config (identical compiled programs, scheduling differs)
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
    batch = {"tokens": jax.random.randint(key, (max(M, 2), 32), 0,
                                          cfg.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(1),
                                          (max(M, 2), 32), 0, cfg.vocab)}
    # four legs, two decompositions x two schedules:
    #   mono_barrier / mono_overlap — ONE backward program emitting every
    #     bucket, so overlap=True can only pipeline allreduce *dispatch*
    #     (the PR-5 measurement; its headline number);
    #   seg_barrier / segmented — backward-segmented decomposition, where
    #     bucket i's allreduce is in flight while bucket i+1's backward
    #     segment COMPUTES.
    # Twins of one decomposition run identical compiled programs (only host
    # scheduling differs) -> their trained params must be bit-identical.
    legs = (("mono_barrier", False, False), ("mono_overlap", True, False),
            ("seg_barrier", False, True), ("segmented", True, True))
    states, n_buckets, n_segments = {}, {}, 0
    for label, ov, seg in legs:
        params = decoder.init(key, cfg)
        opt = adamw.init(params, ocfg)
        step = manual_step.make_overlapped_train_step(
            cfg, tcfg, mesh, topo, algo=algo, bucket_bytes=256 << 10,
            overlap=ov, segmented=seg)
        # two warm steps: the first compiles, the second settles the
        # donated-param shardings (a step whose apply re-lays-out params
        # triggers one more compile of the consumers on the NEXT call —
        # that must not land in the timed window)
        for _ in range(2):
            params, opt, m = step(params, opt, batch)
            jax.block_until_ready((params, m["loss"]))
        states[label] = [step, params, opt]
        n_buckets[label] = len(step.grad_sync.slices)
        if seg:
            n_segments = len(step.bounds)
    # interleaved rounds: one timed step per leg per round, so slow drift
    # (CPU frequency, co-tenants) hits every leg alike and the PAIRED
    # per-round differences cancel it — the gated metrics are medians of
    # those paired differences, not differences of medians
    reps_t = 10
    samples = {label: [] for label, _, _ in legs}
    for _ in range(reps_t):
        for label, _, _ in legs:
            slot = states[label]
            step_l, params, opt = slot
            t0 = time.perf_counter()
            params, opt, m = step_l(params, opt, batch)
            jax.block_until_ready((params, m["loss"]))
            samples[label].append((time.perf_counter() - t0) * 1e3)
            slot[1], slot[2] = params, opt
    step_times = {k: float(np.median(v)) for k, v in samples.items()}
    for label, _, _ in legs:
        print(f"overlap/train_step/{label},"
              f"{step_times[label] * 1e3:.1f},"
              f"buckets={n_buckets[label]}")
    for a, b in (("mono_barrier", "mono_overlap"),
                 ("seg_barrier", "segmented")):
        diff = max(jax.tree.leaves(jax.tree.map(
            lambda x, y: float(jnp.abs(x.astype(jnp.float32)
                                       - y.astype(jnp.float32)).max()),
            states[a][1], states[b][1])))
        assert diff == 0.0, f"{a} vs {b} twins diverged: {diff}"

    def paired(a, b):
        return float(np.median([x - y for x, y in
                                zip(samples[a], samples[b])]))

    # dispatch_overlap: what overlap=True buys the monolithic decomposition
    # (allreduce dispatch pipelining only — the PR-5 measurement).
    # compute_overlap: what overlap=True buys the segmented decomposition
    # over its own barrier twin. On host-CPU devices compute and
    # communication share the same cores, so this is ~0 there; on real
    # accelerators it is the backward-compute window the per-bucket
    # allreduces hide under. delta: the end-to-end headline — the
    # segmented-overlapped step vs the monolithic barrier baseline.
    dispatch_overlap = paired("mono_barrier", "mono_overlap")
    compute_overlap = paired("seg_barrier", "segmented")
    delta = paired("mono_barrier", "segmented")
    print(f"overlap/train_step/dispatch_overlap,0.0,"
          f"{dispatch_overlap:+.2f}ms ({step_times['mono_barrier']:.1f}ms "
          f"-> {step_times['mono_overlap']:.1f}ms)")
    print(f"overlap/train_step/compute_overlap,0.0,"
          f"{compute_overlap:+.2f}ms ({step_times['seg_barrier']:.1f}ms "
          f"-> {step_times['segmented']:.1f}ms)")
    print(f"overlap/train_step/delta,0.0,{delta:+.2f}ms "
          f"segments={n_segments} "
          f"({step_times['mono_barrier']:.1f}ms -> "
          f"{step_times['segmented']:.1f}ms)")
    if M >= 8:
        # CI non-regression gate (8-device leg): the segmented-overlapped
        # step must not lose to the monolithic barrier baseline, and must
        # buy strictly more than dispatch-only pipelining did
        assert delta >= 0.0, \
            f"segmented step regressed vs monolithic barrier: {delta:+.2f}ms"
        assert delta > dispatch_overlap, \
            (f"segmented win ({delta:+.2f}ms) did not beat dispatch-only "
             f"overlap ({dispatch_overlap:+.2f}ms)")

    section = {
        "devices": M, "topology": autotune.topo_key(topo),
        "microbench": {
            "buckets": K, "bucket_bytes": n * 4, "plan": op_b.plan,
            "barrier_us": barrier_us, "overlapped_us": overlapped_us,
            "speedup": speedup,
        },
        "amortization": {"init_us": init_us, "start_us": start_us,
                         "curve": amortization},
        "train_step": {
            "buckets": n_buckets["segmented"],
            "mono_buckets": n_buckets["mono_barrier"],
            "segments": n_segments,
            "mono_barrier_ms": step_times["mono_barrier"],
            "mono_overlap_ms": step_times["mono_overlap"],
            "seg_barrier_ms": step_times["seg_barrier"],
            "segmented_ms": step_times["segmented"],
            "dispatch_overlap_ms": dispatch_overlap,
            "compute_overlap_ms": compute_overlap,
            "delta_ms": delta,
        },
    }
    if out_path:
        path = pathlib.Path(out_path)
        data = json.loads(path.read_text()) if path.exists() else {}
        data["overlap"] = section
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(data, indent=1, sort_keys=True))
        print(f"overlap/artifact,0.0,{path}")


def codec_kernel_mode(out_path=None):
    """Codec-kernel microbench: fused Pallas lowerings vs jnp reference.

    For every fused codec (compress.fused_codecs()), wall-clock the two
    fused entry points against the jnp reference path (both jitted, timed
    iterations are executable-cache hits; the jnp variant is traced under
    compress.jnp_reference_paths() so its compiled program never routes a
    kernel), then report the ANALYTIC memory traffic per stage
    (kernels.codec.memory_traffic — the HBM passes each path makes) and
    the roofline seconds those bytes cost at the HBM bandwidth of the TPU
    it runs on (``roofline.terms.PEAKS``; an unknown kind raises), or of a
    modeled v5e on the CPU, which has no peaks row. On CPU the fused
    kernels run in interpret mode, so wall-clock favors jnp — the traffic
    model is the TPU-relevant number, and the acceptance bar (fused moves
    <= half the jnp bytes on at least one codec) is asserted here.

    Also re-measures zlib_sim's entropy-backed wire ratio (satellite: the
    ratio is measured, not assumed). With OUT_JSON, merges a
    ``codec_kernels`` section into the artifact and writes the standalone
    results/BENCH_codec_kernels.json next to it.
    """
    from repro.kernels import codec as ckern
    from repro.roofline import terms

    kind = (jax.devices()[0].device_kind if jax.default_backend() == "tpu"
            else terms.V5E)
    hbm_bw = terms.peaks(kind).hbm_bw
    S, W = 8, 8
    L = 16 * compress.BLOCK          # 4096 elems/slice, 32 KiB wire payload
    n_elems = S * L
    key = jax.random.PRNGKey(7)
    x2d = jax.random.normal(key, (S, L), jnp.float32) * 0.01
    err = jnp.zeros_like(x2d)
    rows = []
    for name in compress.fused_codecs():
        cd = compress.codec(name)
        # fused path: traced with the toggle on (the default)
        f_ef = jax.jit(lambda x, e, _c=cd: _c.encode_with_feedback(x, e))
        us_f_ef, (comp_f, _) = bench(lambda a: f_ef(a, err), x2d, n=3)
        f_dr = jax.jit(lambda c, _c=cd: _c.decode_reduce(c, L))
        us_f_dr, out_f = bench(lambda c: f_dr(c), comp_f, n=3)
        # jnp reference: traced (compiled) with the toggle off, so the
        # cached executable stays the jnp program after the toggle returns
        with compress.jnp_reference_paths():
            j_ef = jax.jit(lambda x, e, _c=cd: _c.encode_with_feedback(x, e))
            us_j_ef, (comp_j, _) = bench(lambda a: j_ef(a, err), x2d, n=3)
            j_dr = jax.jit(lambda c, _c=cd: _c.decode_reduce(c, L))
            us_j_dr, out_j = bench(lambda c: j_dr(c), comp_j, n=3)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_j),
                                   rtol=1e-6, atol=1e-5 * W)
        wb_per_elem = cd.wire_bytes(comp_f) / float(n_elems)
        traffic = ckern.memory_traffic(
            wb_per_elem, n_elems, W=W,
            amax_pass=ckern.lowering(name).amax_pass)
        row = {"codec": name, "elems": n_elems,
               "wire_bytes_per_elem": wb_per_elem,
               "wall_us": {"encode_feedback": {"fused": us_f_ef,
                                               "jnp": us_j_ef},
                           "decode_reduce": {"fused": us_f_dr,
                                             "jnp": us_j_dr}},
               "traffic": traffic,
               "roofline_s": {
                   stage: {path: traffic[stage][f"{path}_bytes"] / hbm_bw
                           for path in ("jnp", "fused")}
                   for stage in traffic}}
        rows.append(row)
        for stage in ("encode_feedback", "decode_reduce"):
            t = traffic[stage]
            frac = t["fused_bytes"] / t["jnp_bytes"]
            print(f"codec_kernel/{name}/{stage},"
                  f"{row['wall_us'][stage]['fused']:.1f},"
                  f"jnp_us={row['wall_us'][stage]['jnp']:.1f} "
                  f"fused_bytes={t['fused_bytes']:.0f} "
                  f"jnp_bytes={t['jnp_bytes']:.0f} "
                  f"traffic_frac={frac:.3f} "
                  f"roofline_fused_us="
                  f"{row['roofline_s'][stage]['fused'] * 1e6:.2f}")
    # acceptance: fused moves <= half the jnp bytes on >= 1 codec (it holds
    # for all of them on the encode side; assert the weakest form here)
    halved = [r["codec"] for r in rows
              if r["traffic"]["encode_feedback"]["fused_bytes"]
              <= 0.5 * r["traffic"]["encode_feedback"]["jnp_bytes"]]
    assert halved, rows
    print(f"codec_kernel/traffic_halved,0.0,{' '.join(halved)}")
    # zlib_sim: the wire ratio is measured (byte-entropy stage), not assumed
    zl = compress.codec("zlib_sim")
    ids = (np.arange(4096, dtype=np.int64) * 2654435761) % 50257
    sample = jnp.asarray(ids, jnp.float32).reshape(1, -1)
    measured = 4.0 * sample.size / zl.wire_bytes(zl.encode(sample))
    zlib_row = {"codec": "zlib_sim", "meta_ratio": zl.meta.wire_ratio,
                "measured_ratio": float(measured)}
    print(f"codec_kernel/zlib_sim/measured_ratio,0.0,"
          f"meta={zl.meta.wire_ratio:.2f}x measured={measured:.2f}x")
    section = {"devices": int(DC), "block": compress.BLOCK,
               "slices": S, "world": W, "elems_per_slice": L,
               "fused_codecs": list(compress.fused_codecs()),
               "rows": rows, "traffic_halved": halved,
               "zlib_sim": zlib_row, "roofline_device_kind": kind,
               "note": "wall_us on CPU runs the kernels in interpret mode; "
                       "traffic/roofline_s are the analytic HBM passes"}
    if out_path:
        path = pathlib.Path(out_path)
        data = json.loads(path.read_text()) if path.exists() else {}
        data["codec_kernels"] = section
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(data, indent=1, sort_keys=True))
        solo = path.parent / "BENCH_codec_kernels.json"
        solo.write_text(json.dumps(section, indent=1, sort_keys=True))
        print(f"codec_kernel/artifact,0.0,{path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--calibrate", metavar="OUT_JSON", default=None,
                    help="run the calibration sweep and write the tuning "
                         "table artifact instead of the measure rows")
    ap.add_argument("--overlap", metavar="OUT_JSON", nargs="?", const="",
                    default=None,
                    help="run the persistent-op overlap leg (barrier vs "
                         "overlapped bucketed sync + amortization curve); "
                         "with OUT_JSON, merge an 'overlap' section into "
                         "the artifact")
    ap.add_argument("--codec-kernels", metavar="OUT_JSON", nargs="?",
                    const="", default=None,
                    help="run the codec-kernel microbench (fused Pallas "
                         "lowerings vs jnp reference: wall-clock, analytic "
                         "memory traffic, roofline seconds); with OUT_JSON, "
                         "merge a 'codec_kernels' section into the artifact")
    ap.add_argument("--trace", metavar="OUT_DIR", default=None,
                    help="enable telemetry and take a jax.profiler trace of "
                         "the whole run into OUT_DIR (open it in Perfetto "
                         "or XProf; orthogonal to the mode flags)")
    args = ap.parse_args()
    if BACKEND.multiprocess and not args.calibrate:
        raise SystemExit(
            "multi-process runs support --calibrate only; the measure/"
            "overlap/codec-kernel legs are single-process benchmarks "
            "(run them without the repro.distributed launcher)")
    with contextlib.ExitStack() as stack:
        if args.trace:
            telemetry.enable()
            stack.callback(telemetry.disable)
            stack.enter_context(jax.profiler.trace(args.trace))
        if args.calibrate:
            calibrate_mode(args.calibrate)
        elif args.overlap is not None:
            overlap_mode(args.overlap or None)
        elif args.codec_kernels is not None:
            codec_kernel_mode(args.codec_kernels or None)
        else:
            measure_mode()
    if args.trace:
        print(f"trace/artifact,0.0,{args.trace}")
