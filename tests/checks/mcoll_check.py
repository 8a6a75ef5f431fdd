"""Exhaustive correctness check of every mcoll algorithm on a (N, P) mesh.

Usage: mcoll_check.py N P   (run under XLA_FLAGS device_count = N*P)
Asserts every collective x algorithm x root/radix variant matches the pure
numpy oracle on every device. Exit 0 = all good.
"""
import sys

N, P = int(sys.argv[1]), int(sys.argv[2])

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import Topology
from repro.core import mcoll, runtime
from repro.launch.mesh import make_mesh

M = N * P
mesh = make_mesh((N, P), ("node", "local"))
topo = Topology(N, P)
checks = 0


def ag_oracle(x):
    return np.array(x)


def check_allgather():
    global checks
    m = 3
    x = jnp.arange(M * m, dtype=jnp.float32)
    for algo in mcoll.algorithms("allgather"):
        if algo == "recursive_doubling" and (M & (M - 1)):
            continue
        fn = runtime.build(mesh, topo, "allgather", algo, stacked=True)
        out = np.array(fn(x))
        assert out.shape == (M, M * m)
        for d in range(M):
            np.testing.assert_array_equal(out[d], np.array(x), err_msg=f"{algo} d={d}")
        checks += 1
    for radix in range(2, P + 2):
        fn = runtime.build(mesh, topo, "allgather", "pip_mcoll",
                                 stacked=True, radix=radix)
        out = np.array(fn(x))
        for d in range(M):
            np.testing.assert_array_equal(out[d], np.array(x))
        checks += 1
    # 2-D payloads and other dtypes
    x2 = jnp.arange(M * 2 * 4, dtype=jnp.bfloat16).reshape(M * 2, 4)
    fn = runtime.build(mesh, topo, "allgather", "pip_mcoll", stacked=True)
    out = np.array(fn(x2).astype(jnp.float32))
    for d in range(M):
        np.testing.assert_array_equal(out[d], np.array(x2.astype(jnp.float32)))
    checks += 1


def check_scatter():
    global checks
    m = 2
    x = jnp.arange(M * m, dtype=jnp.float32)
    for algo in mcoll.algorithms("scatter"):
        roots = [0, M // 2, M - 1] if algo != "linear" else [0]
        for root in roots:
            fn = runtime.build(mesh, topo, "scatter", algo, root=root)
            np.testing.assert_array_equal(np.array(fn(x)), np.array(x),
                                          err_msg=f"{algo} root={root}")
            checks += 1
    for radix in range(2, P + 2):
        fn = runtime.build(mesh, topo, "scatter", "pip_mcoll",
                                 radix=radix, root=1)
        np.testing.assert_array_equal(np.array(fn(x)), np.array(x))
        checks += 1


def check_broadcast():
    global checks
    y = jnp.arange(5, dtype=jnp.float32) + 7
    for algo in mcoll.algorithms("broadcast"):
        for root in [0, M - 1]:
            fn = runtime.build(mesh, topo, "broadcast", algo, root=root)
            out = np.array(fn(y))
            for d in range(M):
                np.testing.assert_array_equal(out[d], np.array(y))
            checks += 1


def check_allreduce():
    global checks
    z = (jnp.arange(M * 7, dtype=jnp.float32) % 13).reshape(M, 7)
    expect = np.array(z).sum(0)
    for algo in mcoll.algorithms("allreduce"):
        fn = runtime.build(mesh, topo, "allreduce", algo)
        out = np.array(fn(z))
        for d in range(M):
            np.testing.assert_allclose(out[d], expect, rtol=1e-6)
        checks += 1
    fn = runtime.build(mesh, topo, "allreduce", "pip_mcoll",
                             inter="recursive_doubling")
    out = np.array(fn(z))
    for d in range(M):
        np.testing.assert_allclose(out[d], expect, rtol=1e-6)
    checks += 1


def check_reduce_scatter_alltoall():
    global checks
    s = 2
    w = (jnp.arange(M * M * s, dtype=jnp.float32) % 11).reshape(M, M * s)
    expect = np.array(w).sum(0)
    for algo in mcoll.algorithms("reduce_scatter"):
        fn = runtime.build(mesh, topo, "reduce_scatter", algo)
        np.testing.assert_allclose(np.array(fn(w)).reshape(-1), expect,
                                   rtol=1e-6)
        checks += 1
    a = jnp.arange(M * M * s, dtype=jnp.float32).reshape(M, M, s)
    expect_t = np.array(a).transpose(1, 0, 2)
    for algo in mcoll.algorithms("alltoall"):
        fn = runtime.build(mesh, topo, "alltoall", algo)
        np.testing.assert_array_equal(np.array(fn(a)), expect_t)
        checks += 1


def check_chunked():
    """Pipelined variants with chunk counts that do not divide the payload
    (remainder segments must round-trip exactly on this topology)."""
    global checks
    m = 5
    x = jnp.arange(M * m, dtype=jnp.float32)
    y = jnp.arange(m, dtype=jnp.float32) + 3
    z = (jnp.arange(M * m, dtype=jnp.float32) % 13).reshape(M, m)
    a = jnp.arange(M * M * m, dtype=jnp.float32).reshape(M, M, m)
    for c in (2, 3):
        fn = runtime.build(mesh, topo, "allgather", "ring_pipeline",
                                 stacked=True, chunks=c)
        out = np.array(fn(x))
        for d in range(M):
            np.testing.assert_array_equal(out[d], np.array(x))
        fn = runtime.build(mesh, topo, "scatter", "pip_mcoll",
                                 root=M - 1, chunks=c)
        np.testing.assert_array_equal(np.array(fn(x)), np.array(x))
        fn = runtime.build(mesh, topo, "broadcast", "pip_mcoll",
                                 root=1, chunks=c)
        out = np.array(fn(y))
        for d in range(M):
            np.testing.assert_array_equal(out[d], np.array(y))
        fn = runtime.build(mesh, topo, "allreduce", "pip_pipeline",
                                 chunks=c)
        out = np.array(fn(z))
        for d in range(M):
            np.testing.assert_allclose(out[d], np.array(z).sum(0), rtol=1e-6)
        fn = runtime.build(mesh, topo, "alltoall", "pip_pipeline",
                                 chunks=c)
        np.testing.assert_array_equal(np.array(fn(a)),
                                      np.array(a).transpose(1, 0, 2))
        checks += 5


check_allgather()
check_scatter()
check_broadcast()
check_allreduce()
check_reduce_scatter_alltoall()
check_chunked()
print(f"mcoll_check N={N} P={P}: {checks} checks OK")
