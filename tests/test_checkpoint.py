"""Fault tolerance: atomic checkpointing, failure injection + exact resume,
elastic re-shard restore."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.launch.mesh import make_mesh

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"a": {"w": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3)},
            "step": jnp.int32(7)}
    mgr.save(5, tree)
    like = jax.tree.map(jnp.zeros_like, tree)
    out = mgr.restore(5, like)
    np.testing.assert_array_equal(
        np.array(out["a"]["w"], np.float32),
        np.array(tree["a"]["w"], np.float32))
    assert int(out["step"]) == 7
    assert mgr.latest_step() == 5


def test_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.ones((2,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.all_steps() == [3, 4]


def test_atomic_commit_no_partial(tmp_path):
    """A .tmp dir left behind must never be picked up as a checkpoint."""
    mgr = CheckpointManager(str(tmp_path))
    (tmp_path / ".tmp_step_000000009").mkdir()
    assert mgr.latest_step() is None
    mgr.save(3, {"w": jnp.ones(2)})
    assert mgr.latest_step() == 3


def _run_train(args, check=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO / 'src'}:{env.get('PYTHONPATH', '')}"
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *args],
        capture_output=True, text=True, env=env, timeout=600, check=False)


@pytest.mark.slow
def test_failure_injection_and_exact_resume(tmp_path):
    """Kill training at step 7, resume from the step-5 checkpoint, and the
    final losses must be bitwise-identical to an uninterrupted run
    (deterministic data + state restore)."""
    common = ["--arch", "smollm-360m", "--reduced", "--steps", "12",
              "--batch", "2", "--seq", "32", "--ckpt-every", "5",
              "--log-every", "1", "--lr", "1e-3", "--ckpt-blocking"]
    # uninterrupted reference
    ref = _run_train(common + ["--ckpt-dir", str(tmp_path / "ref")])
    assert ref.returncode == 0, ref.stdout + ref.stderr
    # interrupted run
    crash = _run_train(common + ["--ckpt-dir", str(tmp_path / "ft"),
                                 "--die-at-step", "7"])
    assert crash.returncode == 42, crash.stdout + crash.stderr
    assert "injected failure" in crash.stdout
    resumed = _run_train(common + ["--ckpt-dir", str(tmp_path / "ft")])
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    assert "resumed from step 5" in resumed.stdout

    def losses(out):
        return {int(l.split()[2]): l.split()[4]
                for l in out.splitlines() if l.startswith("[train] step")}
    ref_l = losses(ref.stdout)
    res_l = losses(resumed.stdout)
    for step in (10, 11):
        assert ref_l[step] == res_l[step], (step, ref_l, res_l)


def test_elastic_reshard_restore(tmp_path):
    """Save unsharded, restore onto an explicit sharding target (the elastic
    path: same bytes, new topology/placement)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    mgr.save(1, tree)
    mesh = make_mesh((1,), ("data",))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    out = mgr.restore(1, jax.tree.map(jnp.zeros_like, tree),
                      shardings=shardings)
    np.testing.assert_array_equal(np.array(out["w"]), np.array(tree["w"]))
    assert out["w"].sharding == shardings["w"]
