"""Production mesh construction + default sharding rules per run shape.

Production target: TPU v5e, 16x16 = 256 chips per pod; multi-pod adds a
"pod" axis across DCN (2 pods = 512 chips for the dry-run; the axis scales
to O(100) pods — nothing in the sharding is pod-count-specific).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.roofline import terms
from repro.sharding.rules import Rules


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules,
    ``shard_map`` bodies and gathers here are written for GSPMD-propagated
    shardings, not for ``make_mesh``'s default ``Explicit`` axes."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_process_mesh(node_axis: str = "node", local_axis: str = "local"):
    """A ``(process_count, devices_per_process)`` mesh whose node axis is
    exactly the process boundary.

    Devices are ordered ``(process_index, id)`` so each mesh row is one
    process's devices — the layout ``Topology.from_mesh`` reads the
    intra/inter link split from (``derive_link`` classifies the node axis
    ``host_ipc`` and the local axis ``host_cpu`` on a multi-process CPU
    runtime). Requires every process to contribute the same device count.
    """
    import numpy as np
    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    nproc = jax.process_count()
    if len(devices) % nproc:
        raise ValueError(f"{len(devices)} devices do not divide evenly "
                         f"across {nproc} processes")
    arr = np.array(devices).reshape(nproc, -1)
    for row in arr:
        owners = {d.process_index for d in row}
        if len(owners) != 1:
            raise ValueError(f"uneven devices per process: mesh row spans "
                             f"processes {sorted(owners)}")
    return jax.sharding.Mesh(arr, (node_axis, local_axis))


HBM_BYTES = terms.peaks(terms.V5E).hbm_bytes  # the production mesh is v5e


def default_rules(mesh, kind: str, global_batch: int, seq_len: int,
                  param_bytes: float = 0.0) -> Rules:
    """Pick the parallelism layout for a run shape.

    train/prefill: batch over (pod, data), FSDP over data, TP over model.
    decode:        TP-resident weights (NO ZeRO-3: re-gathering params every
                   token is the latency killer the baseline sweep exposed)
                   whenever params/TP fit in HBM; batch over (pod, data);
                   long-context (batch too small) switches to context
                   parallelism — KV sequence over data.
    """
    axes = mesh.axis_names
    pod = ("pod",) if "pod" in axes else ()
    dp = pod + (("data",) if "data" in axes else ())
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tp_size = mesh.shape["model"] if "model" in axes else 1
    if kind == "decode":
        # keep weights resident if the TP shard fits alongside caches
        fsdp = () if (param_bytes and
                      param_bytes / tp_size < 0.75 * HBM_BYTES) else ("data",)
        if global_batch < dp_size:
            # context parallelism: shard the KV cache sequence over data
            return Rules(batch=pod if global_batch % max(
                [mesh.shape[a] for a in pod] + [1]) == 0 and pod else (),
                fsdp=fsdp, tp="model", seq="data")
        return Rules(batch=dp, fsdp=fsdp, tp="model", seq=None)
    return Rules(batch=dp, fsdp=("data",), tp="model", seq=None)
