"""Compressed collective subsystem on a (N x P) mesh: every lossy codec's
allreduce vs the exact psum, within the codec's stated bound; the
error_budget=auto path; and error-feedback convergence over steps."""
import sys
N, P = int(sys.argv[1]), int(sys.argv[2])

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as Pt

from repro.core import compress, mcoll, runtime
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh

mesh = make_mesh((N, P), ("node", "local"))
topo = Topology.from_mesh(mesh)
comm = Communicator(mesh, topo)
M = N * P
n = 1000  # non-multiple of world*block on purpose
x = (jax.random.normal(jax.random.PRNGKey(0), (M, n)) * 0.01)
want = np.asarray(x).sum(0)
A = float(np.abs(np.asarray(x)).max())

# 1. every lossy codec, through the Communicator's compiled-callable cache,
# on both the plain and the pipelined compressed allreduce — blocking and
# persistent-nonblocking execution of one plan must agree bitwise
for codec in compress.lossy():
    tol = compress.collective_tolerance(codec, "allreduce", M, A) + 1e-7
    for algo, kw in (("pip_mcoll", {}), ("pip_pipeline", {"chunks": 3})):
        got = np.asarray(comm.allreduce(x, algo=algo, codec=codec, **kw))
        err = max(np.abs(got[d] - want).max() for d in range(M))
        assert err <= tol, (codec, algo, err, tol)
        op = comm.allreduce_init(x, algo=algo, codec=codec, **kw)
        np.testing.assert_array_equal(np.asarray(op.start(x).wait()), got)

# 2. error_budget resolution: auto under a budget conforms to the loosest
# admissible codec's bound; zero budget must reproduce the exact sum
got = np.asarray(comm.allreduce(x, error_budget=0.05))
tol = compress.collective_tolerance("int8_block", "allreduce", M, A) + 1e-7
assert np.abs(got[0] - want).max() <= tol
exact = np.asarray(comm.allreduce(x, error_budget=0.0))
np.testing.assert_allclose(exact[0], want, atol=1e-5 * max(A, 1.0))

# 3. error feedback: accumulated compressed sums track the true accumulated
# sum to within ~one step's residual (no drift), unlike feedback-free
def body(xs, es):
    out, e2 = mcoll.pip_mcoll_allreduce(xs[0], topo, codec="int8_block",
                                        err=es[0])
    return out[None], e2[None]

fn = jax.jit(runtime.sharded(
    body, mesh,
    in_specs=(Pt(("node", "local"), None), Pt(("node", "local"), None)),
    out_specs=(Pt(("node", "local"), None), Pt(("node", "local"), None)),
    check=False))
err_state = jnp.zeros((M, n), jnp.float32)
zeros = jnp.zeros((M, n), jnp.float32)
acc_fb = np.zeros(n)
acc_nofb = np.zeros(n)
T = 20
for _ in range(T):
    out, err_state = fn(x, err_state)
    acc_fb += np.asarray(out)[0]
    out2, _ = fn(x, zeros)
    acc_nofb += np.asarray(out2)[0]
lag_fb = np.abs(acc_fb - want * T).max()
lag_nofb = np.abs(acc_nofb - want * T).max()
assert lag_fb <= lag_nofb + 1e-9, (lag_fb, lag_nofb)
assert lag_fb <= compress.collective_tolerance("int8_block", "allreduce",
                                               M, A) * 4, lag_fb

rel = np.abs(acc_fb / T - want).max() / (np.abs(want).max() + 1e-9)
print(f"compressed_allreduce N={N} P={P}: OK rel_err={rel:.4f} "
      f"fb_lag={lag_fb:.2e} nofb_lag={lag_nofb:.2e}")
