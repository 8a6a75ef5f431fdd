"""A run with the timed path broken underneath reads ``correct`` false,
once for each fault a cell can have; the same run unbroken reads true.
Small sizes on forced CPU devices, one process per run."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SCRIPT = pathlib.Path(__file__).resolve().parent / "faulty_run.py"

CASES = [("qwen1.5-4b.train", 1, f) for f in
         ("none", "unchanged", "half_batch")] + [
        ("smollm-360m.dp4", 4, f) for f in
         ("none", "unchanged", "half_batch", "no_exchange")]


@pytest.mark.parametrize("workload,chips,fault", CASES)
def test_planted_fault_reads_incorrect(workload, chips, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, str(SCRIPT), workload, fault],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    checks = {k: (v["value"], v["limit"]) for k, v in
              result["checks"].items()}
    assert result["correct"] is (fault == "none"), checks
    assert result["attempted"] > 0 and result["failed"] == 0
