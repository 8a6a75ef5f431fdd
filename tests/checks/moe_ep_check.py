"""MoE expert-parallel shard_map path vs the single-device local oracle."""
import sys
DP, TP = int(sys.argv[1]), int(sys.argv[2])

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.launch.mesh import make_mesh
from repro.layers import moe
from repro.sharding.rules import Rules

cfg = reduced_config("qwen3-moe-235b-a22b")
# give the reduced config a TP-divisible expert count & generous capacity so
# the EP path drops nothing (exactness vs oracle requires no drops)
cfg = dataclasses.replace(
    cfg, moe=dataclasses.replace(cfg.moe, n_experts=max(8, TP),
                                 capacity_factor=8.0))
key = jax.random.PRNGKey(0)
p = moe.init(key, cfg)
B, S = DP * 2, 16
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                      jnp.float32).astype(jnp.bfloat16)

y_ref, aux_ref = moe._moe_local(p, x.reshape(-1, cfg.d_model), cfg)
y_ref = y_ref.reshape(B, S, cfg.d_model)

mesh = make_mesh((DP, TP), ("data", "model"))
rules = Rules(batch=("data",), fsdp=(), tp="model")
with mesh:
    y_ep, aux_vec = moe.apply(p, x, cfg, rules=rules, mesh=mesh)

np.testing.assert_allclose(np.asarray(y_ep, np.float32),
                           np.asarray(y_ref, np.float32), rtol=6e-2,
                           atol=6e-2)
# aux loss agrees on average (per-slice estimate vs global)
assert abs(float(aux_vec.mean()) - float(aux_ref)) < 0.5

# compressed combine path: under an error budget the combine all-to-all may
# run through an error-bounded codec; the result must stay within the bf16
# oracle tolerance plus the codec's bound on the combine payload scale
with mesh:
    y_c, _ = moe.apply(p, x, cfg, rules=rules, mesh=mesh, error_budget=0.07)
scale = float(np.abs(np.asarray(y_ref, np.float32)).max())
np.testing.assert_allclose(np.asarray(y_c, np.float32),
                           np.asarray(y_ref, np.float32), rtol=6e-2,
                           atol=6e-2 + 0.07 * scale)
print(f"moe_ep_check DP={DP} TP={TP}: OK (compressed combine "
      f"max_diff={np.abs(np.asarray(y_c, np.float32) - np.asarray(y_ep, np.float32)).max():.3e})")
