"""Record a small chip trace for test_trace_chip.py.

  python tests/bench/record_trace.py <workload> OUT.xplane.pb

Runs the cell at the small size of faulty_run.py on its chips through the
harness, traced for a fraction of a second, and copies the profiler's
``.xplane.pb`` to OUT.
"""
import pathlib
import shutil
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"),
                str(pathlib.Path(__file__).resolve().parent)]


def main(workload, out):
    from benchmarks.chip import harness, trace
    from faulty_run import small_cell
    cell = small_cell(workload)
    cell.traffic["trace_seconds"] = 0.004
    result = harness.run(cell, 7, 0.004, True, T0)
    print(result)
    shutil.copy(trace.find_xplane(str(harness.TRACE_DIR)), out)


if __name__ == "__main__":
    main(*sys.argv[1:3])
