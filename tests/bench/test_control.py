"""The control: the reference computed at fp8, put in the program's place,
fails the cell's comparison on three seeds, while the program passes it.
At the small size of faulty_run.py on forced CPU devices, with the limits
set at that size; the readings at the cells' own sizes, on the chip, are
in PERF.md."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import check  # noqa: E402


@pytest.mark.parametrize("workload,chips", [("qwen1.5-4b.train", 1),
                                            ("smollm-360m.dp4", 4)])
def test_fp8_control_fails_and_program_passes(workload, chips):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "faulty_run.py"), workload, "control"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(l) for l in proc.stdout.splitlines()
            if l.startswith("{") and '"reading"' in l]
    assert sorted({r["reading"] for r in rows}) == ["control_fp8",
                                                    "program"]
    assert len(rows) == 6
    limits = json.loads((HERE / "data" / "small_cells.json").read_text())[
        workload]["limits"]
    for r in rows:
        found = {k: (r[k], r["at"][k]) for k in check.NUMBERS}
        ok, _ = check.decide(found, limits)
        assert ok is (r["reading"] == "program"), r
