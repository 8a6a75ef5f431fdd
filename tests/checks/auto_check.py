"""algo="auto" end-to-end on a real (N, P) CPU mesh, via the Communicator.

Usage: auto_check.py N P   (run under XLA_FLAGS device_count = N*P)

Asserts, for all six collectives:
  1. Communicator methods with algo="auto" resolve through the selector
     (prior source before calibration) and return bit-identical results to
     every explicit algorithm;
  2. after comm.calibrate, auto resolves from the measured table and
     still returns correct results;
  3. auto and explicit callers share exec-cache entries (auto re-invocation
     is a cache hit, not a fresh compile), and a persistent op initialised
     for the same plan shares the compiled-executable path (repeated start
     never compiles).
"""
import sys

N, P = int(sys.argv[1]), int(sys.argv[2])

import jax
import numpy as np

from repro.core import autotune, runtime
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh

mesh = make_mesh((N, P), ("node", "local"))
topo = Topology.from_mesh(mesh)
assert topo.link_names == ("host_cpu", "host_cpu"), topo.link_names
comm = Communicator(mesh, topo)

checks = 0

# --- 1. auto == every explicit algorithm, prior-sourced -------------------
for name in runtime.collectives():
    for nbytes in (64, 4096):
        x = runtime.example_input(name, topo, nbytes)
        outs = {}
        for algo in autotune.candidates(name, topo):
            outs[algo] = np.asarray(comm.invoke(name, x, algo=algo))
        ref_algo = sorted(outs)[0]
        for algo, out in outs.items():
            if name == "allreduce":  # reduction order: fp tolerance
                np.testing.assert_allclose(out, outs[ref_algo], rtol=1e-6)
            else:
                np.testing.assert_array_equal(out, outs[ref_algo],
                                              err_msg=f"{name}/{algo}")
        comm.selection_stats().reset()
        auto_out = np.asarray(comm.invoke(name, x))
        assert comm.selection_stats().total == 1
        np.testing.assert_allclose(auto_out, outs[ref_algo], rtol=1e-6)
        checks += 1
assert comm.selection_stats().measured == 0, "no calibration yet"

# --- 3. auto shares the exec cache with explicit callers ------------------
runtime.clear_cache()
x = runtime.example_input("allgather", topo, 64)
resolved, _ = runtime.resolve_algo(topo, "allgather", "auto", x)
comm.allgather(x, algo=resolved)   # miss (explicit)
comm.allgather(x)                  # hit (auto)
s = comm.cache_stats()
assert s.exec_misses == 1 and s.exec_hits == 1, s
checks += 1

# --- 3b. persistent op: compile once at init, never at start --------------
op = comm.allgather_init(x, algo=resolved)
comm.cache_stats().reset()
for _ in range(4):
    out_p = np.asarray(op.start(x).wait())
assert comm.cache_stats().exec_misses == 0, "start must never compile"
np.testing.assert_array_equal(out_p, np.asarray(comm.allgather(x)))
op2 = comm.allgather_init(x, algo=resolved)  # same spec: exec-cache hit
assert comm.cache_stats().exec_misses == 0, "re-init must be a hit"
checks += 1

# --- 2. calibration flips resolution to the measured table ----------------
comm.calibrate(sizes=(64, 4096), iters=3)
for name in runtime.collectives():
    x = runtime.example_input(name, topo, 64)
    comm.selection_stats().reset()
    out = np.asarray(comm.invoke(name, x))
    assert comm.selection_stats().measured == 1, name
    assert np.isfinite(out.astype(np.float64)).all()
    checks += 1
sel = autotune.default_selector()
s = sel.choose("allgather", topo, 64)
assert s.source == "measured", s

print(f"auto_check N={N} P={P}: {checks} checks OK")
