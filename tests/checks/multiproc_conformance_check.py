"""Multi-process conformance: every collective x algorithm, bitwise vs the
single-process XLA reference — plus the calibrate-merge and data-pipeline
legs, amortizing one multi-controller spawn.

Usage (via tests/subproc.py): ``run_check(script, procs * dev, procs,
dev)``. This parent process sees ``procs * dev`` forced host devices and
computes the single-process reference outputs on a ``(procs, dev)`` mesh;
it then spawns ``procs`` coordinated ``jax.distributed`` workers with
``dev`` devices each (``repro.distributed.launch`` overrides the forced
device count per child) running :func:`worker` over the identical
operands. Operands are ``runtime.example_input``'s exact small integers,
so float reductions are order-independent-exact and parity is bitwise.

The worker leg also asserts the process-aware topology (node axis =
process boundary -> ``host_ipc`` inter / ``host_cpu`` intra links), runs a
mini ``comm.calibrate`` whose per-rank tables rank 0 merges and saves, and
returns this process's data-pipeline slice so the parent can check the
K-process global batch is bitwise the 1-process batch.
"""
import pathlib
import sys
import tempfile

import numpy as np

PAYLOAD_NBYTES = 4096


def _plans(runtime, mcoll, autotune, topo):
    for name in runtime.collectives():
        for algo in mcoll.algorithms(name):
            if algo in autotune.candidates(name, topo):
                yield name, algo


def worker(ref_path: str, procs: int, dev: int):
    from repro.distributed import backend as dist
    be = dist.auto_initialize()  # before any device access
    import jax
    from repro.core import autotune, mcoll, runtime
    from repro.core.comm import Communicator
    from repro.core.topology import Topology
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_process_mesh

    assert be.multiprocess and jax.process_count() == procs
    mesh = make_process_mesh()
    assert mesh.devices.shape == (procs, dev), mesh.devices.shape
    topo = Topology.from_mesh(mesh)
    # the tentpole's topology claim: the process boundary splits the link
    # classes, so intra and inter rows never alias in the tuning table
    assert topo.link_names == ("host_ipc", "host_cpu"), topo.link_names
    key = autotune.topo_key(topo)
    assert key == f"{procs}x{dev}/host_ipc/host_cpu", key

    comm = Communicator(mesh, topo)
    refs = np.load(ref_path)
    failures, checked = [], 0
    for name, algo in _plans(runtime, mcoll, autotune, topo):
        x = runtime.example_input(name, topo, PAYLOAD_NBYTES)
        out = getattr(comm, name)(x, algo=algo)
        got = dist.to_host(out)
        want = refs[f"{name}/{algo}"]
        if got.shape != want.shape or got.dtype != want.dtype \
                or not (got == want).all():
            failures.append(f"{name}/{algo}")
        checked += 1

    # calibrate-merge leg: every rank sweeps, rank 0 folds + saves once
    table_path = dist.scratch_dir() / "merged_table.json"
    rows = comm.calibrate(names=("allreduce",), sizes=(PAYLOAD_NBYTES,),
                          iters=2, codecs=(), path=str(table_path))
    assert rows, "calibrate produced no rows"

    # data-pipeline host sharding: this process generates only its slice
    ds = SyntheticLM(vocab=64, seq_len=32, global_batch=2 * procs, seed=3)
    assert ds.host_batch == 2 and ds.host_offset == 2 * be.process_index
    return {"rank": be.process_index, "topo_key": key, "checked": checked,
            "failures": failures, "table_path": str(table_path),
            "tokens": ds.batch(step=5)["tokens"]}


def main() -> None:
    procs, dev = int(sys.argv[1]), int(sys.argv[2])
    import jax
    from repro.core import autotune, mcoll, runtime
    from repro.core.autotune import TuningTable
    from repro.core.comm import Communicator
    from repro.core.topology import Topology
    from repro.launch.mesh import make_mesh

    assert jax.device_count() == procs * dev, jax.device_count()
    mesh = make_mesh((procs, dev), ("node", "local"))
    topo = Topology.from_mesh(mesh)
    assert topo.link_names == ("host_cpu", "host_cpu"), topo.link_names
    comm = Communicator(mesh, topo)
    refs = {}
    for name, algo in _plans(runtime, mcoll, autotune, topo):
        x = runtime.example_input(name, topo, PAYLOAD_NBYTES)
        refs[f"{name}/{algo}"] = np.asarray(getattr(comm, name)(x,
                                                                algo=algo))
    ref_path = pathlib.Path(tempfile.mkdtemp(prefix="mp_conf_")) / "ref.npz"
    np.savez(ref_path, **refs)

    from repro.distributed import launch
    results = launch.run(worker, str(ref_path), procs, dev,
                         processes=procs, devices_per_process=dev,
                         timeout=1500)
    results.sort(key=lambda r: r["rank"])
    assert [r["rank"] for r in results] == list(range(procs))
    for r in results:
        assert not r["failures"], \
            f"rank {r['rank']} bitwise mismatches: {r['failures']}"
        assert r["checked"] == len(refs), (r["checked"], len(refs))

    # merged tuning table: one file, rank 0's fold, keyed on the
    # process-aware topology with distinct intra/inter link classes
    table = TuningTable.load(results[0]["table_path"])
    key = results[0]["topo_key"]
    plans = table.entries[key]["allreduce"]["float32"]
    assert any(algos for algos in plans.values()), table.entries

    # K-process global batch == what a 1-process run generates (this parent
    # IS the 1-process run: jax.process_count() == 1 here)
    from repro.data.pipeline import SyntheticLM
    single = SyntheticLM(vocab=64, seq_len=32, global_batch=2 * procs,
                         seed=3).batch(step=5)["tokens"]
    stacked = np.concatenate([r["tokens"] for r in results])
    np.testing.assert_array_equal(stacked, single)

    print(f"MULTIPROC_CONFORMANCE_OK procs={procs} dev={dev} "
          f"plans={len(refs)} topo={key}")


if __name__ == "__main__":
    main()
