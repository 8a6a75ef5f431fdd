"""Small-mesh dry-run smoke: exercise the full build_cell -> lower ->
compile -> roofline pipeline on an 8-device (4 data x 2 model) mesh for one
arch per family and every shape kind. Validates the deliverable-(e)
machinery end to end without the 512-device cost."""
import jax

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch import specs
from repro.launch.mesh import make_mesh
from repro.models.decoder import RunFlags
from repro.roofline import hlo as H
from repro.sharding.rules import Rules
from repro.train.step import TrainConfig

mesh = make_mesh((4, 2), ("data", "model"))
rules = Rules(batch=("data",), fsdp=("data",), tp="model")
flags = RunFlags()

CELLS = [
    ("smollm-360m", ShapeConfig("t", 256, 8, "train")),
    ("qwen3-moe-235b-a22b", ShapeConfig("p", 512, 8, "prefill")),
    ("rwkv6-1.6b", ShapeConfig("d", 1024, 8, "decode")),
    ("seamless-m4t-large-v2", ShapeConfig("d", 512, 8, "decode")),
]
for arch, shape in CELLS:
    cfg = get_config(arch)
    with mesh:
        jitted, args = specs.build_cell(cfg, shape, mesh, rules,
                                        tcfg=TrainConfig(flags=flags),
                                        flags=flags)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    costs = H.analyze(compiled.as_text(), vmem_tile=(512, 1024,
                                                     cfg.head_dim))
    assert costs.flops > 0, arch
    assert costs.memory_bytes > 0, arch
    peak = (getattr(mem, "argument_size_in_bytes", 0) or 0) + \
        (getattr(mem, "temp_size_in_bytes", 0) or 0)
    assert peak > 0, arch
    print(f"dryrun_smoke {arch} {shape.kind}: flops/dev={costs.flops:.2e} "
          f"coll={costs.collective_bytes:.2e}B OK")
print("dryrun_smoke_check OK")
