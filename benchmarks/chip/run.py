"""Run one cell of the on-chip benchmark once.

  python benchmarks/chip/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, metrics and limits are found by
name from ``BENCHMARK.json`` (see ``cells.py``). Progress goes to stdout;
the last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``) and, last, ``checks``: each number the comparison with the
reference made, beside its limit. The same numbers are the last lines of
stderr. The run exits non-zero, printing no result, when JAX finds no TPU
or fewer chips than the cell asks for.

JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, and otherwise in ``.bench_out/jax_cache``
at the root of the checkout, so that only a cell's first run compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".bench_out" / "jax_cache"))
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    try:
        import jax
        import repro  # noqa: F401  (the system under test)
        from benchmarks.chip import cells, harness
    except ImportError as e:
        print(f"[bench] cannot import the harness or the program: {e}",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cell = cells.resolve(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"{k} {c['value']!r} limit {c['limit']!r} at {c['at']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
