"""PiP-MColl in action: run every collective algorithm on a simulated
(4 nodes x 2 locals) cluster, verify identical results, and print the cost
model's predicted latency on the paper's cluster vs TPU v5e.

  PYTHONPATH=src python examples/collectives_demo.py
(This example forces 8 host devices; run it standalone, not from a session
that already initialized jax.)
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune, compress, costmodel, mcoll
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh

N, P = 4, 2
mesh = make_mesh((N, P), ("node", "local"))
topo = Topology(N, P)
comm = Communicator(mesh, topo)
x = jnp.arange(N * P * 4, dtype=jnp.float32)

print(f"== allgather on {N}x{P} devices (Communicator API, cached) ==")
for algo in mcoll.algorithms("allgather"):
    out = np.asarray(comm.allgather(x, algo=algo, stacked=True))
    ok = all((out[d] == np.asarray(x)).all() for d in range(N * P))
    print(f"  {algo:20s} correct={ok}")
    assert ok
    comm.allgather(x, algo=algo, stacked=True)
stats = comm.cache_stats()
print(f"  runtime cache: {stats.exec_hits} hits / "
      f"{stats.exec_misses} compiles")

print(f"\n== persistent nonblocking allreduce (init once, start/wait) ==")
zp = (jnp.arange(N * P * 16, dtype=jnp.float32) % 9).reshape(N * P, 16)
blocking = np.asarray(comm.allreduce(zp, algo="pip_mcoll"))
op = comm.allreduce_init(zp, algo="pip_mcoll", depth=2)
misses0 = comm.cache_stats().exec_misses
h1 = op.start(zp)            # returns immediately (async dispatch)
h2 = op.start(zp)            # double-buffered: 2nd start before 1st wait
outs = [np.asarray(h1.wait()), np.asarray(h2.wait())]
for o in outs:
    np.testing.assert_array_equal(o, blocking)
assert comm.cache_stats().exec_misses == misses0, "start must not compile"
print(f"  plan={op.plan} starts={op.starts} "
      f"compiles_after_init=0 bitwise==blocking=True")

print("\n== modeled small-message latency, paper cluster (128x18) ==")
big = Topology(128, 18)
for m in (64, 256, 1024):
    pip = costmodel.allgather_cost("pip_mcoll", big, m,
                                   costmodel.paper_cluster_pip())
    rd = costmodel.allgather_cost("recursive_doubling", big, m,
                                  costmodel.paper_cluster_cma())
    print(f"  {m:5d}B  pip_mcoll {pip.us():9.1f}us  "
          f"({pip.inter_rounds} inter rounds)   flat-RD {rd.us():9.1f}us "
          f"({rd.inter_rounds} rounds)  speedup {rd.time / pip.time:.1f}x")

print("\n== modeled on TPU v5e pod (16 x 16 chips, hierarchical axes) ==")
pod = Topology(16, 16)
for m in (256, 4096, 1 << 20):
    pip = costmodel.allgather_cost("pip_mcoll", pod, m,
                                   costmodel.tpu_v5e_pod())
    sl = costmodel.allgather_cost("single_leader", pod, m,
                                  costmodel.tpu_v5e_pod())
    print(f"  {m:8d}B  pip_mcoll {pip.us():9.1f}us  single-leader "
          f"{sl.us():9.1f}us  speedup {sl.time / pip.time:.2f}x")

print("\n== chunked pipelining: pip_pipeline allreduce (runtime, chunks=) ==")
z = (jnp.arange(N * P * 12, dtype=jnp.float32) % 13).reshape(N * P, 12)
expect = np.asarray(z).sum(0)
for c in (1, 2, 4):
    out = np.asarray(comm.allreduce(z, algo="pip_pipeline", chunks=c))
    assert all((out[d] == expect).all() for d in range(N * P))
    print(f"  chunks={c} correct=True")
net = costmodel.tpu_v5e_pod()
for m in (4096, 1 << 20, 1 << 24):
    c = costmodel.optimal_chunks("allreduce", "pip_pipeline", pod, m, net)
    t1 = costmodel.allreduce_cost("pip_pipeline", pod, m, net, chunks=1)
    tc = costmodel.allreduce_cost("pip_pipeline", pod, m, net, chunks=c)
    print(f"  modeled {m:8d}B  c*={c:3d}  unchunked {t1.us():9.1f}us  "
          f"chunked {tc.us():9.1f}us  win {t1.time / tc.time:.2f}x")
xo = costmodel.pipeline_crossover_bytes("allreduce", "pip_pipeline", pod, net)
print(f"  modeled pipelining crossover: {xo}B")

print("\n== error-bounded compressed collectives (codec=) ==")
zr = (jax.random.normal(jax.random.PRNGKey(0), (N * P, 2048)) * 0.01)
exact = np.asarray(zr).sum(0)
A = float(np.abs(np.asarray(zr)).max())
for cd in compress.lossy():
    out = np.asarray(comm.allreduce(zr, algo="pip_mcoll", codec=cd))
    err = np.abs(out[0] - exact).max()
    tol = compress.collective_tolerance(cd, "allreduce", N * P, A)
    assert err <= tol + 1e-7, (cd, err, tol)
    m = compress.meta(cd)
    print(f"  {cd:11s} ratio={m.wire_ratio:4.1f}x stated_bound="
          f"{m.error_bound:.4f}  achieved_err={err:.2e} (tol {tol:.2e})")

print("\n== codec selection under an error budget (16x16 DCN pod) ==")
dcn = Topology(16, 16, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
sel = autotune.Selector()
print(f"  {'size':>10s}  " + "  ".join(f"budget={b:<7g}"
                                       for b in (0.0, 0.004, 0.07, 1.0)))
for size in (256, 65536, 1 << 20, 1 << 24):
    plans = []
    for b in (0.0, 0.004, 0.07, 1.0):
        s = sel.choose("allreduce", dcn, size, error_budget=b)
        plans.append(autotune.encode_plan(s.algo, s.chunks, s.codec))
    print(f"  {size:>9d}B  " + "  ".join(f"{p:<14s}" for p in plans))
zero = sel.choose("allreduce", dcn, 1 << 24, error_budget=0.0)
assert zero.codec == "none", "error_budget=0.0 must stay lossless"
print("collectives_demo OK")
