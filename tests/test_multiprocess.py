"""Multi-process SPMD backend: single-process unit coverage of
repro.distributed (backend descriptors, launcher plumbing, process-aware
link derivation, cross-rank table merging) plus the spawned 2-process
conformance legs (subprocess-contained device counts)."""
import sys

import numpy as np
import pytest

from subproc import run_check

from repro.core import artifact, topology
from repro.core.autotune import TuningTable
from repro.core.topology import Topology, derive_link
from repro.distributed import backend as dist
from repro.distributed import launch


# -- backend descriptor (this pytest process is single-process) --------------


def test_single_process_backend():
    be = dist.current_backend()
    assert be.name == "single" and be.process_count == 1 \
        and be.process_index == 0 and not be.multiprocess
    assert dist.auto_initialize() == be  # no REPRO_DIST_* env -> no-op
    assert not dist.is_multiprocess()
    assert dist.process_rank() == 0 and dist.process_count() == 1
    dist.barrier("noop")  # must not require an initialized service
    assert dist.merge_tuning_table(TuningTable()) == 0


def test_to_host_and_stamp():
    x = np.arange(6.0).reshape(2, 3)
    np.testing.assert_array_equal(dist.to_host(x), x)
    data = dist.stamp_artifact({"topology": "1x1/host_cpu/host_cpu"})
    assert data["backend"] == "single" and data["process_count"] == 1


def test_stamped_fields_satisfy_artifact_schema():
    data = dist.stamp_artifact({})
    assert artifact.validate(data, sections=("backend", "process_count"))


# -- launcher plumbing -------------------------------------------------------


def test_worker_env_contract():
    env = launch._worker_env(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 --foo"},
        rank=1, processes=2, devices_per_process=4,
        coord="127.0.0.1:5555", scratch="/tmp/s")
    # the parent's forced device count is replaced, other flags survive
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "=8" not in env["XLA_FLAGS"] and "--foo" in env["XLA_FLAGS"]
    assert env[dist.ENV_PROCS] == "2" and env[dist.ENV_RANK] == "1"
    assert env[dist.ENV_COORD] == "127.0.0.1:5555"
    assert env[dist.ENV_SCRATCH] == "/tmp/s"
    assert str(launch.SRC) in env["PYTHONPATH"]


def test_fn_ref_forms():
    ref = launch._fn_ref("repro.core.runtime:collectives")
    assert ref == {"kind": "module", "module": "repro.core.runtime",
                   "name": "collectives"}
    assert callable(launch._resolve_fn(ref))
    with pytest.raises(ValueError, match="module:function"):
        launch._fn_ref("not-a-spec")
    with pytest.raises(ValueError, match="module-level"):
        launch._fn_ref(lambda: None)


def test_spawn_failure_carries_rank_tails():
    with pytest.raises(launch.LaunchError, match="rank 0"):
        launch.spawn([sys.executable, "-c",
                      "import sys; print('boom'); sys.exit(3)"],
                     processes=1, devices_per_process=1, timeout=60)


# -- process-aware link derivation (fake devices, no spawn needed) -----------


class _Dev:
    def __init__(self, platform, process_index, slice_index=None,
                 device_kind=None):
        self.platform = platform
        self.process_index = process_index
        self.device_kind = device_kind
        if slice_index is not None:
            self.slice_index = slice_index


class _FakeMesh:
    axis_names = ("node", "local")

    def __init__(self, rows):
        self.devices = np.array(rows, dtype=object)

    @property
    def shape(self):
        return {"node": self.devices.shape[0],
                "local": self.devices.shape[1]}


def _mesh(platform, procs, per_proc, device_kind=None):
    return _FakeMesh([[_Dev(platform, p, device_kind=device_kind)
                       for _ in range(per_proc)] for p in range(procs)])


def test_derive_link_splits_on_process_boundary():
    mesh = _mesh("cpu", 2, 4)
    assert derive_link(mesh, "node", "inter") == "host_ipc"
    assert derive_link(mesh, "local", "intra") == "host_cpu"
    topo = Topology.from_mesh(mesh)
    assert topo.link_names == ("host_ipc", "host_cpu")


def test_derive_link_single_process_cpu_stays_host_cpu():
    mesh = _mesh("cpu", 1, 4)
    assert derive_link(mesh, "node", "inter") == "host_cpu"
    assert derive_link(mesh, "local", "intra") == "host_cpu"


def test_derive_link_unknown_platform_warns_once():
    topology._FALLBACK_WARNED.discard("gpu")
    mesh = _mesh("gpu", 2, 2)
    with pytest.warns(RuntimeWarning, match="folklore"):
        assert derive_link(mesh, "node", "inter") == "host_ipc"
    # second call: already warned for this platform
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert derive_link(mesh, "local", "intra") == "host_cpu"


def test_derive_link_tpu_unchanged():
    mesh = _mesh("tpu", 2, 2, device_kind="TPU v5 lite")
    assert derive_link(mesh, "node", "inter") == "tpu_v5e_dcn"
    assert derive_link(mesh, "local", "intra") == "tpu_v5e_ici"


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", None])
def test_derive_link_unknown_tpu_kind_raises(kind):
    mesh = _mesh("tpu", 1, 2, device_kind=kind)
    with pytest.raises(ValueError, match="device kind"):
        derive_link(mesh, "local", "intra")


# -- cross-rank table merge semantics ----------------------------------------


def test_merge_reduce_max_keeps_slowest_rank():
    topo = Topology(2, 4, node_link="host_ipc", local_link="host_cpu")
    a, b = TuningTable(), TuningTable()
    a.record(topo, "allreduce", "float32", 4096, "pip_mcoll", 1e-4)
    b.record(topo, "allreduce", "float32", 4096, "pip_mcoll", 3e-4)
    b.record(topo, "allreduce", "float32", 4096, "ring", 2e-4)
    a.merge(b, reduce=max)
    entry = a.lookup(topo, "allreduce", "float32", 4096)
    assert entry["pip_mcoll"] == pytest.approx(3e-4)  # slowest rank wins
    assert entry["ring"] == pytest.approx(2e-4)       # new keys fold in
    # default merge keeps other-wins semantics
    c = TuningTable()
    c.record(topo, "allreduce", "float32", 4096, "pip_mcoll", 9e-4)
    a.merge(c)
    assert a.lookup(topo, "allreduce", "float32",
                    4096)["pip_mcoll"] == pytest.approx(9e-4)


# -- spawned multi-controller legs ------------------------------------------


@pytest.mark.parametrize("procs,dev", [
    pytest.param(2, 2, id="2x2"),
    pytest.param(2, 4, id="2x4", marks=pytest.mark.slow),
])
def test_multiprocess_conformance(procs, dev):
    out = run_check("multiproc_conformance_check.py", procs * dev,
                    procs, dev, timeout=1800)
    assert "MULTIPROC_CONFORMANCE_OK" in out
    assert f"topo={procs}x{dev}/host_ipc/host_cpu" in out
