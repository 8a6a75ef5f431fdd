"""Benchmark harness — one function per paper table/figure plus framework
benches. Prints ``name,us_per_call,derived`` CSV rows.

  fig1_scatter    paper Figure 1: MPI_Scatter small messages, 128x18
  fig2_allgather  paper Figure 2: MPI_Allgather 16..512B, 128x18
  tpu_hierarchy   the TPU-native adaptation: pod-level hierarchical gains
  measured_rounds wall-clock of the real shard_map collectives on 8 CPU
                  devices (subprocess; relative ordering, not TPU time);
                  runs through repro.core.runtime's compiled-callable
                  cache and reports its hit/miss totals
  autotune_table  algorithm crossover tables for all six collectives
                  (model priors + measured comparison when calibrated)
  roofline_summary aggregates results/dryrun.jsonl (if present)

``python benchmarks/run.py calibrate`` runs the measured calibration sweep
plus the persistent-op overlap leg and the codec-kernel microbench on the
8-CPU-device mesh, persisting the selection subsystem's tuning table, an
``overlap`` section (barrier vs overlapped bucketed sync, init/start
amortization curve, train-step delta), and a ``codec_kernels`` section
(fused Pallas codec lowerings vs jnp reference: wall-clock, analytic HBM
traffic, roofline seconds) to ``results/BENCH_collectives.json`` (the CI
perf artifact; the codec section is also written standalone as
``results/BENCH_codec_kernels.json``).

The paper's absolute numbers come from an OPA cluster; figures here are the
alpha-beta model (core/costmodel.py) instantiated with the paper's cluster
constants — EXPERIMENTS.md compares the modeled speedups against the
paper's measured claims.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from repro.core import autotune, costmodel
from repro.core.topology import Topology

REPO = pathlib.Path(__file__).resolve().parent.parent
ROWS = []


def emit(name: str, us: float, derived: str = ""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.3f},{derived}")


def fig1_scatter():
    """Paper Fig.1: scatter small messages on 128 nodes x 18 ppn."""
    topo = Topology(128, 18)
    lib_nets = {"openmpi": costmodel.paper_cluster_openmpi(),
                "mvapich2": costmodel.paper_cluster_cma(),
                "intelmpi": costmodel.paper_cluster_posix_shmem()}
    for m in (16, 32, 64, 128, 256, 512):
        pip = costmodel.scatter_cost("pip_mcoll", topo, m,
                                     costmodel.paper_cluster_pip())
        emit(f"fig1/pip_mcoll/{m}B", pip.us(),
             f"rounds={pip.inter_rounds}")
        best = None
        for lib, net in lib_nets.items():
            c = costmodel.scatter_cost("binomial", topo, m, net)
            emit(f"fig1/{lib}/{m}B", c.us(), f"rounds={c.inter_rounds}")
            best = min(best or c.time, c.time)
        emit(f"fig1/speedup_vs_best/{m}B", 0.0,
             f"{best / pip.time:.2f}x")


def fig2_allgather():
    """Paper Fig.2: allgather 16..512B on 128x18 (paper: up to 4.6x)."""
    topo = Topology(128, 18)
    lib_nets = {"openmpi": costmodel.paper_cluster_openmpi(),
                "mvapich2": costmodel.paper_cluster_cma(),
                "intelmpi": costmodel.paper_cluster_posix_shmem(),
                "pip_mpich": costmodel.paper_cluster_pip_mpich()}
    for m in (16, 32, 64, 128, 256, 512):
        pip = costmodel.allgather_cost("pip_mcoll", topo, m,
                                       costmodel.paper_cluster_pip())
        emit(f"fig2/pip_mcoll/{m}B", pip.us(), f"rounds={pip.inter_rounds}")
        best_flat = None
        best_hier = None
        for lib, net in lib_nets.items():
            algo = "bruck" if lib == "pip_mpich" else "recursive_doubling"
            c = costmodel.allgather_cost(algo, topo, m, net)
            emit(f"fig2/{lib}/{m}B", c.us(), f"rounds={c.inter_rounds}")
            best_flat = min(best_flat or c.time, c.time)
            h = costmodel.allgather_cost("single_leader", topo, m, net)
            best_hier = min(best_hier or h.time, h.time)
        emit(f"fig2/speedup_bracket/{m}B", 0.0,
             f"[{best_hier / pip.time:.2f}x..{best_flat / pip.time:.2f}x]"
             f" paper_claim=4.6x@64B")


def tpu_hierarchy():
    """Beyond-paper: the adaptation on TPU v5e meshes."""
    for name, topo, net in (
            ("pod16x16_ici", Topology(16, 16), costmodel.tpu_v5e_pod()),
            ("dcn2x256", Topology(2, 256), costmodel.tpu_v5e_multipod()),
            ("dcn32x256", Topology(32, 256), costmodel.tpu_v5e_multipod())):
        for m in (256, 4096, 1 << 16):
            pip = costmodel.allgather_cost("pip_mcoll", topo, m, net)
            sl = costmodel.allgather_cost("single_leader", topo, m, net)
            emit(f"tpu/{name}/allgather/{m}B/pip_mcoll", pip.us(),
                 f"rounds={pip.inter_rounds}")
            emit(f"tpu/{name}/allgather/{m}B/single_leader", sl.us(),
                 f"speedup={sl.time / pip.time:.2f}x")


def _bench_subprocess(extra_args, prefix: str, timeout: int) -> None:
    """Run measure_collectives.py on 8 forced CPU host devices (subprocess
    so this process keeps 1 device and stays off any accelerator) and
    re-emit its ``prefix``-tagged CSV rows. A failed subprocess fails this
    run after its ERROR row."""
    script = REPO / "benchmarks" / "measure_collectives.py"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = f"{REPO / 'src'}:{env.get('PYTHONPATH', '')}"
    out = subprocess.run([sys.executable, str(script), *extra_args],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    if out.returncode != 0:
        emit(f"{prefix}ERROR", 0.0, out.stderr[-200:].replace(",", ";"))
        raise SystemExit(1)
    for line in out.stdout.splitlines():
        if line.startswith(prefix):
            parts = line.split(",")
            emit(parts[0], float(parts[1]), ",".join(parts[2:]))


def _bench_multiprocess(extra_args, prefix: str, timeout: int,
                        processes: int, devices: int) -> None:
    """Run measure_collectives.py under the repro.distributed launcher:
    ``processes`` coordinated jax.distributed workers with ``devices``
    forced CPU host devices each. The launcher re-prints rank 0's stdout,
    so row re-emission works exactly like :func:`_bench_subprocess`;
    failures are fatal (the calibrate leg is a CI gate)."""
    script = REPO / "benchmarks" / "measure_collectives.py"
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO / 'src'}:{env.get('PYTHONPATH', '')}"
    out = subprocess.run(
        [sys.executable, "-m", "repro.distributed.launch",
         "--processes", str(processes), "--devices", str(devices),
         "--timeout", str(timeout), "--", str(script), *extra_args],
        env=env, capture_output=True, text=True, timeout=timeout + 120)
    if out.returncode != 0:
        emit(f"{prefix}ERROR", 0.0,
             (out.stderr or out.stdout)[-400:].replace(",", ";"))
        raise SystemExit(1)
    for line in out.stdout.splitlines():
        if line.startswith(prefix):
            parts = line.split(",")
            emit(parts[0], float(parts[1]), ",".join(parts[2:]))


def measured_rounds():
    """Wall-clock the real shard_map algorithms (8 CPU host devices,
    subprocess so this process keeps 1 device). CPU timings demonstrate
    round-count ordering only — derived column has modeled TPU time.
    The subprocess drives every call through repro.core.runtime, so timed
    iterations are compiled-callable cache hits (no re-trace in the
    numbers); the measured/runtime_cache row carries the hit/miss totals."""
    _bench_subprocess([], "measured/", timeout=900)


def autotune_table():
    """Model-prior crossover tables for all six collectives (algorithm AND
    chunk-count plans), plus (when a calibration artifact exists) the
    measured-vs-model comparison and the measured pipeline crossovers."""
    from repro.core import mcoll
    topo = Topology(16, 16, node_link="tpu_v5e_ici", local_link="tpu_v5e_ici")
    net = costmodel.net_for(topo)
    selector = autotune.Selector()
    for coll in sorted(costmodel.COST_FNS):
        table = selector.crossover_table(coll, topo)
        crossovers = []
        prev = None
        for size in sorted(table):
            plan = autotune.encode_plan(table[size].algo,
                                        table[size].chunks,
                                        table[size].codec)
            if plan != prev:
                crossovers.append(f"{size}B->{plan}")
                prev = plan
        emit(f"autotune/{coll}/16x16", 0.0, " ".join(crossovers))
    # modeled pipelining crossover per chunk-capable pair: the size where
    # the optimally-chunked variant starts beating chunks=1
    for coll in sorted(costmodel.COST_FNS):
        for algo in sorted(mcoll.CHUNKED[coll]):
            xo = costmodel.pipeline_crossover_bytes(coll, algo, topo, net)
            emit(f"autotune/pipeline_crossover/{coll}/{algo}/16x16", 0.0,
                 f"model_crossover={xo}B" if xo else "no-crossover")
    # modeled codec crossovers (compression axis): per codec-capable pair
    # and codec, the size where the compressed plan beats lossless
    from repro.core import compress
    for coll in sorted(costmodel.COST_FNS):
        for algo in sorted(mcoll.COMPRESSED[coll]):
            for cd in compress.lossy():
                xo = costmodel.compressed_crossover_bytes(coll, algo, topo,
                                                          net, cd)
                emit(f"autotune/codec_crossover/{coll}/{algo}@{cd}/16x16",
                     0.0, f"model_crossover={xo}B" if xo else "no-crossover")
    art = REPO / "results" / "BENCH_collectives.json"
    if art.exists():
        data = json.loads(art.read_text())
        agree = sum(1 for c in data.get("model_vs_measured", ())
                    if c["agree"])
        total = len(data.get("model_vs_measured", ()))
        emit("autotune/model_vs_measured", 0.0,
             f"agree={agree}/{total} topo={data.get('topology')}")
        for c in data.get("model_vs_measured", ()):
            if not c["agree"]:
                emit(f"autotune/disagree/{c['collective']}/{c['nbytes']}B",
                     c["measured_us"],
                     f"measured={c['measured_algo']} "
                     f"prior={c['prior_algo']}")
        for row in data.get("pipeline_crossover", ()):
            emit(f"autotune/measured_pipeline/{row['collective']}/"
                 f"{row['algo']}", 0.0,
                 f"model_crossover={row['model_crossover_bytes']}B "
                 f"measured_sizes={sorted(row['measured_us_by_plan'])}")
        for row in data.get("compression", ()):
            emit(f"autotune/compression/{row['codec']}", 0.0,
                 f"ratio={row['achieved_ratio']:.2f}x "
                 f"err={row['achieved_abs_error']:.2e} "
                 f"bound={row['bound_abs_tolerance']:.2e} "
                 f"crossover={row['model_crossover_vs_lossless_bytes']}B "
                 f"budget_crossover="
                 f"{row['budget_selection_crossover_bytes']}B")


def calibrate_collectives(processes: int = 1, devices: int = 4):
    """Run the measured calibration sweep and persist the tuning-table
    artifact to results/BENCH_collectives.json for CI upload +
    autotune_table. Default: the 8-CPU-device single-process mesh
    (subprocess, like measured_rounds); ``processes > 1`` runs it under
    the repro.distributed launcher instead — a real multi-controller
    ``(processes, devices)`` mesh with the node axis on the process
    boundary, rank 0 writing the merged artifact."""
    out_json = REPO / "results" / "BENCH_collectives.json"
    if processes > 1:
        _bench_multiprocess(["--calibrate", str(out_json)], "calibrate/",
                            timeout=3000, processes=processes,
                            devices=devices)
    else:
        _bench_subprocess(["--calibrate", str(out_json)], "calibrate/",
                          timeout=1800)


def overlap_collectives():
    """Run the persistent-op overlap leg (barrier vs overlapped bucketed
    sync, init/start amortization, train-step delta) on the 8-CPU-device
    mesh and merge its ``overlap`` section into the calibration artifact
    (run AFTER calibrate_collectives — the calibrate mode rewrites the
    file)."""
    out_json = REPO / "results" / "BENCH_collectives.json"
    _bench_subprocess(["--overlap", str(out_json)], "overlap/",
                      timeout=1800)


def codec_kernel_collectives():
    """Run the codec-kernel microbench (fused Pallas codec lowerings vs jnp
    reference: wall-clock, analytic HBM traffic, roofline seconds) on the
    8-CPU-device mesh and merge its ``codec_kernels`` section into the
    calibration artifact (run AFTER calibrate_collectives — the calibrate
    mode rewrites the file). Also writes results/BENCH_codec_kernels.json
    as a standalone artifact."""
    out_json = REPO / "results" / "BENCH_collectives.json"
    _bench_subprocess(["--codec-kernels", str(out_json)], "codec_kernel/",
                      timeout=1800)


def roofline_summary():
    path = REPO / "results" / "dryrun_opt.jsonl"
    if not path.exists():
        path = REPO / "results" / "dryrun.jsonl"
    if not path.exists():
        emit("roofline/NOT_RUN", 0.0, "run repro.launch.dryrun --all first")
        return
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    ok = [r for r in recs if r.get("status") == "ok"
          and not r.get("multi_pod")]
    for r in ok:
        ro = r["roofline"]
        emit(f"roofline/{r['arch']}/{r['shape']}",
             ro["step_lower_bound_s"] * 1e6,
             f"bottleneck={ro['bottleneck']};frac="
             f"{ro['roofline_fraction']:.3f};useful="
             f"{ro['useful_ratio']:.3f}")


def main() -> None:
    print("name,us_per_call,derived")
    argv = sys.argv[1:]

    def _flag(name: str, default: int) -> int:
        return int(argv[argv.index(name) + 1]) if name in argv else default

    if "calibrate" in argv:
        # CI smoke: measured calibration sweep + persistent-op overlap leg
        # + codec-kernel microbench -> BENCH_collectives.json (table,
        # crossovers, overlap + codec_kernels sections).
        # ``calibrate --processes K [--devices M]`` runs the sweep on a
        # K-process multi-controller mesh (M CPU devices per process);
        # the overlap/codec legs stay single-process and merge into the
        # same artifact, preserving its backend/process_count stamp.
        calibrate_collectives(processes=_flag("--processes", 1),
                              devices=_flag("--devices", 4))
        overlap_collectives()
        codec_kernel_collectives()
        # the three modes above each rewrite/merge the artifact; validate
        # the final shape so a mode silently dropping a section fails HERE
        from repro.core import artifact as artifact_schema
        artifact_schema.validate_file(
            REPO / "results" / "BENCH_collectives.json")
        emit("calibrate/artifact_schema", 0.0, "all sections validated")
        autotune_table()
        return
    fig1_scatter()
    fig2_allgather()
    tpu_hierarchy()
    autotune_table()
    measured_rounds()
    roofline_summary()


if __name__ == "__main__":
    main()
