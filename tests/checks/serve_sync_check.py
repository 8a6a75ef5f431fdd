"""Serving-engine DP token sync through the Communicator's persistent
broadcast op on a real multi-device mesh.

Usage: serve_sync_check.py N P   (run under XLA_FLAGS device_count = N*P)

Asserts the mesh-attached engine produces the same tokens as the sync-free
reference, resolves its per-tick broadcast through the selector
(algo="auto"), and compiles the persistent sync op exactly once — every
later tick is a bare start/wait (no cache lookups, no recompiles).
"""
import sys

N, P = int(sys.argv[1]), int(sys.argv[2])

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.core import runtime
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh
from repro.models import decoder
from repro.serve.engine import Engine, Request

cfg = reduced_config("smollm-360m")
params = decoder.init(jax.random.PRNGKey(0), cfg)
prompt = np.arange(5, dtype=np.int32) + 2

ref = Engine(params, cfg, max_batch=1, max_len=32)
want = ref.run([Request(prompt=prompt.copy(), max_new_tokens=4)])[0]

mesh = make_mesh((N, P), ("node", "local"))
topo = Topology.from_mesh(mesh)
runtime.clear_cache()
runtime.selection_stats().reset()
eng = Engine(params, cfg, max_batch=1, max_len=32, mesh=mesh, topo=topo)
assert eng.sync_algo == "auto"
got = eng.run([Request(prompt=prompt.copy(), max_new_tokens=4)])[0]

assert got.out_tokens == want.out_tokens, (got.out_tokens, want.out_tokens)
assert runtime.selection_stats().total > 0, "sync never hit the selector"
s = runtime.cache_stats()
# persistent sync op: exactly one compile for the whole run, zero repeat
# lookups — every decode tick after the first is a bare start/wait
assert s.exec_misses == 1, s
assert eng._sync_op is not None and eng._sync_op.starts >= 3, \
    (eng._sync_op and eng._sync_op.starts)

# a calibration table loaded mid-serving must re-resolve the sync plan
# (the persistent op is rebound on tuning-table generation bumps) — and
# the engine still produces the reference tokens from the measured plan
op_before = eng._sync_op
# calibrate at the tick payload's exact key: (1,) int32 -> 4-byte bucket
eng.comm.calibrate(names=("broadcast",), sizes=(4,), iters=1,
                   dtype=jnp.int32)
got2 = eng.run([Request(prompt=prompt.copy(), max_new_tokens=4)])[0]
assert got2.out_tokens == want.out_tokens, got2.out_tokens
assert eng._sync_op is not op_before, "sync op never re-resolved"
assert runtime.selection_stats().measured > 0, "measured plan never used"

# group-scoped sync: the tick broadcast runs on the DP ("node") group
# child (comm.split(axes="node")) — TP shards stay independent — and
# still reproduces the reference tokens; calibration for the group plan
# lands on the child's namespaced tuning rows
geng = Engine(params, cfg, max_batch=1, max_len=32, mesh=mesh,
              sync_axes="node")
assert geng.sync_comm is not geng.comm
assert geng.sync_comm.topo.group == "node"
assert geng.sync_comm.topo.world == N
assert geng.sync_comm.selector is geng.comm.selector
got3 = geng.run([Request(prompt=prompt.copy(), max_new_tokens=4)])[0]
assert got3.out_tokens == want.out_tokens, got3.out_tokens
if N > 1:
    assert geng._sync_op is not None and geng._sync_op.starts >= 3
    gop = geng._sync_op
    geng.sync_comm.calibrate(names=("broadcast",), sizes=(4,), iters=1,
                             dtype=jnp.int32)
    got4 = geng.run([Request(prompt=prompt.copy(), max_new_tokens=4)])[0]
    assert got4.out_tokens == want.out_tokens, got4.out_tokens
    assert geng._sync_op is not gop, "group sync op never re-resolved"

# --- Engine.metrics(): tick-latency distribution + occupancy + rebinds ----
m = eng.metrics()
assert m["ticks"] >= 6, m  # two 4-token runs, 3 decode ticks each
assert m["tick_p50_s"] > 0.0 and m["tick_p99_s"] > 0.0, m
assert m["tick_p99_s"] >= m["tick_p50_s"] >= 0.0, m
assert 0.0 < m["slot_occupancy"] <= 1.0, m
assert m["plan_rebinds"] == 1, m  # the mid-serving calibration rebind
assert m["sync_starts"] >= 3, m

# --- rebind storm: a tuning table mutating every run must trip ONE
# rate-limited warning once rebinds pass REBIND_WARN_THRESHOLD ------------
import warnings

from repro.serve.engine import REBIND_WARN_THRESHOLD

with warnings.catch_warnings(record=True) as rec:
    warnings.simplefilter("always")
    for _ in range(REBIND_WARN_THRESHOLD + 2):
        eng.comm.selector.table.generation += 1  # simulate table churn
        eng.run([Request(prompt=prompt.copy(), max_new_tokens=2)])
storm = [w for w in rec if "rebind storm" in str(w.message)]
assert len(storm) == 1, [str(w.message) for w in rec]
assert eng.metrics()["plan_rebinds"] > REBIND_WARN_THRESHOLD

print(f"serve_sync_check N={N} P={P}: OK tokens={got.out_tokens} "
      f"sync_starts={op_before.starts} exec_misses={s.exec_misses} "
      f"recal_plan={eng._sync_op.plan} group={geng.sync_comm.topo.group} "
      f"tick_p50_s={m['tick_p50_s']:.2e} rebinds="
      f"{eng.metrics()['plan_rebinds']}")
