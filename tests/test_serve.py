"""Serving engine: continuous batching semantics + data pipeline checks."""
import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import decoder
from repro.serve.engine import Engine, Request

from subproc import run_check


def test_engine_continuous_batching():
    cfg = reduced_config("smollm-360m")
    params = decoder.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, max_batch=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=(n,),
                                        dtype=np.int32), max_new_tokens=4)
            for n in (5, 9, 3, 12, 7)]  # 5 requests through 2 slots
    done = eng.run(reqs)
    assert len(done) == 5
    for r in done:
        assert len(r.out_tokens) >= 4
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)


def test_engine_greedy_matches_direct_decode():
    """Single request through the engine == manual prefill+decode."""
    cfg = reduced_config("qwen1.5-4b")
    params = decoder.init(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(6, dtype=np.int32) + 3
    eng = Engine(params, cfg, max_batch=1, max_len=32)
    out = eng.run([Request(prompt=prompt, max_new_tokens=4)])[0].out_tokens

    import jax.numpy as jnp
    caches = decoder.init_cache(cfg, 1, 32)
    logits, _, caches = decoder.forward(params, jnp.asarray(prompt)[None],
                                        cfg, caches=caches)
    toks = [int(logits[0, -1].argmax())]
    for i in range(3):
        step = len(prompt) + i
        logits, _, caches = decoder.forward(
            params, jnp.asarray([[toks[-1]]], jnp.int32), cfg,
            caches=caches, cache_index=step)
        toks.append(int(logits[0, 0].argmax()))
    assert out == toks, (out, toks)


def test_engine_degenerate_mesh_skips_sync_dispatch():
    """On a world-size-1 mesh there is nothing to reconcile: the engine
    must produce identical tokens WITHOUT dispatching a per-tick
    collective."""
    from repro.core import runtime
    from repro.core.topology import Topology

    cfg = reduced_config("smollm-360m")
    params = decoder.init(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(5, dtype=np.int32) + 2
    ref = Engine(params, cfg, max_batch=1, max_len=32)
    want = ref.run([Request(prompt=prompt.copy(), max_new_tokens=4)])[0]

    mesh = make_mesh((1, 1), ("node", "local"))
    topo = Topology.from_mesh(mesh)
    runtime.clear_cache()
    eng = Engine(params, cfg, max_batch=1, max_len=32, mesh=mesh, topo=topo)
    assert eng.sync_algo == "auto"
    got = eng.run([Request(prompt=prompt.copy(), max_new_tokens=4)])[0]
    assert got.out_tokens == want.out_tokens
    s = runtime.cache_stats()
    assert s.exec_misses == 0 and s.exec_hits == 0, s


def test_engine_mixed_length_admission_matches_solo_runs():
    """Regression for the decode-tick cache-index corruption: a short
    prompt admitted into a batch alongside a longer in-flight sequence
    must decode exactly as it would alone. The broken tick advanced every
    slot at the uniform max cache index, so a freshly admitted short row
    wrote its KV past its true length and attended over uninitialized
    cache — greedy outputs silently diverged from the solo run."""
    cfg = reduced_config("smollm-360m")
    params = decoder.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=(n,), dtype=np.int32)
               for n in (12, 3, 7)]  # mixed lengths through 2 slots

    def outputs(reqs, max_batch):
        eng = Engine(params, cfg, max_batch=max_batch, max_len=64)
        done = eng.run([Request(prompt=p.copy(), max_new_tokens=6)
                        for p in reqs])
        return {tuple(r.prompt.tolist()): r.out_tokens for r in done}

    solo = {}
    for p in prompts:
        solo.update(outputs([p], max_batch=1))
    batched = outputs(prompts, max_batch=2)
    assert batched == solo, {k: (batched[k], solo[k]) for k in solo
                             if batched[k] != solo[k]}


def test_engine_unscoped_root_mesh_raises_at_construction():
    """A mesh whose axes don't map onto the default node/local topology
    yields an unscoped root communicator; the engine must refuse it in
    __init__ (pointing at sync_axes=) rather than blowing up inside
    broadcast_init on the first multi-replica tick."""
    cfg = reduced_config("smollm-360m")
    params = decoder.init(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((1, 1, 1), ("dp", "tp", "ep"))
    with pytest.raises(ValueError, match=r"sync_axes"):
        Engine(params, cfg, max_batch=1, max_len=32, mesh=mesh)
    # the error's own guidance works: scoping the sync via sync_axes=
    eng = Engine(params, cfg, max_batch=1, max_len=32, mesh=mesh,
                 sync_axes="dp")
    assert eng.sync_comm.topo is not None
    assert eng.sync_comm.topo.world == 1


@pytest.mark.slow
def test_engine_token_sync_resolves_through_selector_2dev():
    """With a real 2-device mesh, every decode tick syncs tokens via the
    Communicator's persistent broadcast op (algo="auto"): same outputs as
    the sync-free engine, selection stats advance, one compile total."""
    out = run_check("serve_sync_check.py", 2, 1, 2)
    assert "serve_sync_check" in out and "OK" in out


@pytest.mark.slow
def test_engine_token_sync_and_metrics_8dev():
    """8-device leg: the same token-sync contract plus Engine.metrics()
    (non-zero tick p50/p99, occupancy, rebind count) and the rebind-storm
    warning, asserted inside the check."""
    out = run_check("serve_sync_check.py", 8, 4, 2)
    assert "serve_sync_check N=4 P=2: OK" in out


def test_data_determinism_and_structure():
    ds = SyntheticLM(vocab=64, seq_len=32, global_batch=4, seed=7)
    b1 = ds.batch(3)
    b2 = ds.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not (ds.batch(4)["tokens"] == b1["tokens"]).all()
    # next-token alignment
    full1 = np.concatenate([b1["tokens"], b1["labels"][:, -1:]], axis=1)
    np.testing.assert_array_equal(full1[:, 1:], b1["labels"])
    assert b1["tokens"].min() >= 0 and b1["tokens"].max() < 64


def test_data_prefetch_iterator():
    ds = SyntheticLM(vocab=64, seq_len=16, global_batch=2, seed=1)
    it = ds.iterator(start_step=5)
    first = next(it)
    np.testing.assert_array_equal(first["tokens"], ds.batch(5)["tokens"])
    second = next(it)
    np.testing.assert_array_equal(second["tokens"], ds.batch(6)["tokens"])
