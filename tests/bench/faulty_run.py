"""Drive one small run of a cell through the harness, on the CPU, with a
fault planted under the timed path; print the result line.

  python tests/bench/faulty_run.py <workload> <fault|none>
  python tests/bench/faulty_run.py <workload> control

``control`` prints instead, for three seeds, the readings of the program
and of the fp8 control against the reference, one JSON line each.

The configuration keeps the cell's kind of model and step at a size a
test can hold; the cell's traffic and comparison are its own, with the
limits set at this size (data/small_cells.json). Run in a process whose
forced CPU device count is the cell's chips.
"""
import json
import pathlib
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SMALL = {"hidden_size": 256, "intermediate_size": 640,
         "num_attention_heads": 2, "head_dim": 128, "num_hidden_layers": 2,
         "vocab_size": 1024}


def small_cell(workload):
    """The cell as data/small_cells.json names it (its configuration,
    traffic and chips, with the limits at the small size), at the small
    size."""
    from benchmarks.chip import cells
    small = json.loads((pathlib.Path(__file__).resolve().parent / "data"
                        / "small_cells.json").read_text())[workload]
    entry = {"name": workload, "config": small["config"],
             "traffic": small["traffic"], "chips": small["chips"]}
    bench = dict(cells.load_benchmark(), workloads=[entry])
    cell = cells.resolve(workload, bench, limits=small["limits"])
    cfg = dict(cell.config, **SMALL)
    cfg["num_key_value_heads"] = (2 if cell.config["num_key_value_heads"]
                                  == cell.config["num_attention_heads"]
                                  else 1)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, batch_per_chip=2,
                        pool_batches=2, bucket_bytes=1 << 18)
    return cell


def main(workload, fault):
    from benchmarks.chip import calibrate, faults, harness
    cell = small_cell(workload)
    if fault == "control":
        calibrate.readings(cell, [5, 6, 7], control=True, require_tpu=False)
        return
    undo = []
    plant = None
    if fault != "none":
        def plant(tm):
            u = faults.plant(fault, tm)
            if u:
                undo.append(u)
    try:
        result = harness.run(cell, 2 ** 31 + 99, 0.3, False, T0,
                             require_tpu=False, plant=plant)
    finally:
        for u in undo:
            u()
    print(json.dumps(result))


if __name__ == "__main__":
    main(*sys.argv[1:3])
