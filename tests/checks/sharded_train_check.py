"""Full pjit train step on a small (data x model) mesh with the production
sharding rules: params FSDP+TP sharded, batch data-sharded; loss finite and
matches the single-logical-device value."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.launch import specs
from repro.launch.mesh import make_mesh
from repro.models import decoder
from repro.models.decoder import RunFlags
from repro.optim import adamw
from repro.sharding.rules import Rules
from repro.train.step import TrainConfig, train_step
from repro.configs.base import ShapeConfig

cfg = reduced_config("yi-34b")
mesh = make_mesh((4, 2), ("data", "model"))
rules = Rules(batch=("data",), fsdp=("data",), tp="model")
ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=5,
                         schedule="constant")
tcfg = TrainConfig(optimizer=ocfg, flags=RunFlags(remat="none"))
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")

with mesh:
    jitted, (p_sds, o_sds, b_sds) = specs.build_cell(cfg, shape, mesh, rules,
                                                     tcfg=tcfg)
    # materialize real values with the same shardings
    params = decoder.init(jax.random.PRNGKey(0), cfg, mesh=mesh, rules=rules)
    params = jax.tree.map(lambda v, s: jax.device_put(v, s.sharding), params,
                          p_sds)
    opt = adamw.init(params, ocfg)
    opt = jax.tree.map(lambda v, s: jax.device_put(v, s.sharding), opt, o_sds)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
             "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
    batch = jax.tree.map(lambda v, s: jax.device_put(v, s.sharding), batch,
                         b_sds)
    new_p, new_o, metrics = jitted(params, opt, batch)
    sharded_loss = float(metrics["loss"])

# single-device reference
params1 = decoder.init(jax.random.PRNGKey(0), cfg)
opt1 = adamw.init(params1, ocfg)
batch1 = {k: jnp.asarray(np.asarray(v)) for k, v in batch.items()}
_, _, m1 = jax.jit(lambda p, o, b: train_step(p, o, b, cfg, tcfg))(
    params1, opt1, batch1)
ref_loss = float(m1["loss"])
assert np.isfinite(sharded_loss)
np.testing.assert_allclose(sharded_loss, ref_loss, rtol=2e-2)
print(f"sharded_train_check: OK loss={sharded_loss:.4f} ref={ref_loss:.4f}")
