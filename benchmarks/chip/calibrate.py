"""Readings that the limits of a cell's comparison are set from.

  python benchmarks/chip/calibrate.py --workload <name> --seeds 1 2 3 ... \
      [--control] [--faults half_batch no_exchange] [--upper-seeds N] \
      [--out FILE]

In one process, for every seed: the program's checked steps against the
reference (the lower readings), and with ``--control`` the reference
computed at fp8 in the program's place (the upper readings), and with
``--faults`` the program with each named fault planted (see
``faults.py``); ``--upper-seeds N`` takes the control and the faults on
the first N seeds only. Prints one JSON line per seed and reading; ``--out``
writes them all to a file. Runs on the chip; tests call :func:`readings`
on the CPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--upper-seeds", type=int, default=None)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".bench_out" / "jax_cache"))
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    from benchmarks.chip import cells
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rows = readings(cells.resolve(args.workload), args.seeds, args.control,
                    args.faults, upper_seeds=args.upper_seeds)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return 0


def readings(cell, seeds, control=False, faults=(), require_tpu=True,
             emit=print, upper_seeds=None):
    import jax
    import numpy as np
    from benchmarks.chip import check, data, faults as fault_lib
    from benchmarks.chip import harness, weights

    devices = harness.find_devices(cell.chips, require_tpu)
    cfg, tr = cell.config, cell.traffic
    out = []

    def record(seed, what, prog, ref, t):
        found = check.gaps(prog, ref)
        row = {"seed": seed, "reading": what, "seconds": round(t, 3),
               **{k: v[0] for k, v in found.items()},
               "at": {k: v[1] for k, v in found.items()},
               "loss": [float(x) for x in prog["loss"]]}
        out.append(row)
        emit(json.dumps(row), flush=True)

    tm = harness.Trainer(cell, devices)
    reference = harness.Reference(cell, tm.rows)
    fp8_reference = harness.Reference(cell, tm.rows, "fp8")
    built = tm.step, tm.put
    for n, seed in enumerate(seeds):
        upper = upper_seeds is None or n < upper_seeds
        kd = np.asarray(jax.random.key_data(weights.key_from_seed(seed)))
        stream = data.TokenStream(cfg["vocab_size"], tr["seq_len"], seed,
                                  tr.get("data"))
        checked = [stream.batch(s, tm.rows)
                   for s in range(int(tr["check_steps"]))]
        t = time.perf_counter()
        ref = reference(kd, checked, devices[0])
        emit(json.dumps({"seed": seed, "reference_loss":
                         [float(x) for x in ref["loss"]],
                         "seconds": round(time.perf_counter() - t, 3)}))
        for what in ("program",) + (tuple(faults) if upper else ()):
            t = time.perf_counter()
            undo = None
            tm.step, tm.put = built
            if what != "program":
                undo = fault_lib.plant(what, tm)
            try:
                p, o, prog = harness.checked_steps(tm, kd, checked)
            finally:
                if undo:
                    undo()
            del p, o
            gc.collect()
            record(seed, what, prog, ref, time.perf_counter() - t)
        if control and upper:
            t = time.perf_counter()
            ctl = fp8_reference(kd, checked, devices[0])
            prog = {"loss": ctl["loss"], "grad_norm": ctl["grad_norm"][None],
                    "change_norm": ctl["change_norm"][None]}
            record(seed, "control_fp8", prog, ref, time.perf_counter() - t)
    return out


if __name__ == "__main__":
    sys.exit(main())
