"""Compile a cell's training step for a described TPU v5e, with no chip,
and print what the compiler says of its memory.

  JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py --workload <name>

Only the single-jit step is one program that can be compiled whole this
way; the overlapped step is many programs and is rehearsed end to end on
forced CPU devices instead (see PERF.md). Prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmarks.chip import cells, harness, weights
    from repro.optim import adamw
    from repro.train.step import TrainConfig, train_step
    from repro.models.decoder import RunFlags

    jax.config.update("jax_enable_compilation_cache", False)
    cell = cells.resolve(args.workload)
    cfg, tr = cell.config, cell.traffic
    if tr["step"] != "single_jit":
        raise SystemExit("only the single-jit step compiles as one program")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    mcfg = harness.model_config(cfg)
    ocfg = harness.optimizer(tr)
    tcfg = TrainConfig(optimizer=ocfg, z_loss=float(tr["z_loss"]),
                       flags=RunFlags(remat=tr["remat"]))
    key = jax.random.key(0)
    p = jax.eval_shape(lambda k: weights.to_program(weights.init(k, cfg)),
                       key)
    o = jax.eval_shape(lambda q: adamw.init(q, ocfg), p)
    rows = tr["batch_per_chip"]
    b = {k: jax.ShapeDtypeStruct((rows, tr["seq_len"]), "int32")
         for k in ("tokens", "labels")}
    on = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    step = jax.jit(lambda p_, o_, b_: train_step(p_, o_, b_, mcfg, tcfg),
                   donate_argnums=(0, 1))
    ma = step.lower(on(p), on(o), on(b)).compile().memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {f: int(getattr(ma, f)) for f in fields}
    out["layers"] = cfg["num_hidden_layers"]
    out["peak_estimate_bytes"] = (out["argument_size_in_bytes"]
                                  + out["output_size_in_bytes"]
                                  - out["alias_size_in_bytes"]
                                  + out["temp_size_in_bytes"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
