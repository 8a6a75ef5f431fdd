"""The plain reference against the program computed in f32 on the CPU,
and the comparison's arithmetic by hand."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import check, data, weights  # noqa: E402


def tiny(qkv_bias=True, kv=2):
    return {"name": "tiny", "hidden_size": 64, "intermediate_size": 160,
            "num_attention_heads": 4, "num_key_value_heads": kv,
            "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
            "initializer_range": 0.2, "qkv_bias": qkv_bias}


@pytest.mark.parametrize("qkv_bias,kv", [(True, 4), (False, 2)])
def test_reference_matches_the_program_in_f32(qkv_bias, kv):
    """Same weights and batch, the program's loss_fn with f32 params and
    logits: the loss and every leaf's gradient agree to f32 rounding."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip import harness
    from benchmarks.chip.reference import dense_decoder
    from repro.models.decoder import RunFlags
    from repro.train.step import TrainConfig, loss_fn

    cfg = tiny(qkv_bias, kv)
    plain = weights.init(weights.key_from_seed(3), cfg, dtype=jnp.float32)
    # non-zero biases, so their path is exercised
    plain = {k: (v + 0.1 if k.startswith("layers.b") else v)
             for k, v in plain.items()}
    b = data.TokenStream(cfg["vocab_size"], 24, 5).batch(0, 2)
    tcfg = TrainConfig(z_loss=1e-4, flags=RunFlags(
        remat="none", logits_dtype="float32"))
    mcfg = harness.model_config(cfg)
    with jax.default_matmul_precision("highest"):
        (loss_p, _), g_p = jax.value_and_grad(loss_fn, has_aux=True)(
            weights.to_program(plain), {k: jnp.asarray(v)
                                        for k, v in b.items()}, mcfg, tcfg)
    loss_r, g_r = dense_decoder.loss_and_grads(
        plain, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]), cfg,
        "f32", 1e-4, 1)
    assert float(loss_r) == pytest.approx(float(loss_p), rel=1e-5)
    g_p = weights.from_program(g_p)
    for k in weights.names(cfg):
        np.testing.assert_allclose(np.asarray(g_r[k]), np.asarray(g_p[k]),
                                   rtol=2e-3, atol=2e-5 * float(
                                       np.abs(np.asarray(g_r[k])).max()),
                                   err_msg=k)


def test_fp8_product_rounds_and_f32_does_not():
    import jax.numpy as jnp
    from benchmarks.chip.reference import dense_decoder
    a = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)
    eye = jnp.eye(8)
    exact = dense_decoder.product("f32")("ij,jk->ik", a, eye)
    low = dense_decoder.product("fp8")("ij,jk->ik", a, eye)
    assert np.array_equal(np.asarray(exact), np.asarray(a))
    err = np.abs(np.asarray(low) - np.asarray(a)).max()
    assert 0 < err <= 2.0 ** -4


def test_gaps_and_limits_by_hand():
    names = ["a", "b", "c", "bias"]
    ref = {"names": names, "loss": [10.0, 9.0],
           "grad_norm": np.array([1.0, 2.0, 4.0, 1e-4]),
           "change_norm": np.array([1.0, 1.0, 1.0, 1.0])}
    prog = {"loss": [10.001, 9.0],
            # replica 1's leaf c reads 4.2: 0.2 over max(4, median 1.5)
            "grad_norm": np.array([[1.0, 2.0, 4.0, 1e-4],
                                   [1.0, 2.0, 4.2, 1e-4]]),
            # the bias moved double: left out, its gradient is < 1e-3 of
            # the median leaf's
            "change_norm": np.array([[1.0, 1.1, 1.0, 2.0],
                                     [1.0, 1.0, 1.0, 2.0]])}
    found = check.gaps(prog, ref)
    assert found["loss_gap"][0] == pytest.approx(1e-4)
    assert found["loss_gap"][1] == "step1"
    assert found["grad_gap"] == (pytest.approx(0.05), "c@1")
    assert found["change_gap"] == (pytest.approx(0.1), "b@0")
    lim = {k: {"limit": v} for k, v in
           (("loss_gap", 2e-4), ("grad_gap", 0.1), ("change_gap", 0.2))}
    ok, out = check.decide(found, lim)
    assert ok and out["grad_gap"]["limit"] == 0.1
    lim["change_gap"]["limit"] = 0.05
    assert not check.decide(found, lim)[0]
    prog["loss"] = [float("nan"), 9.0]
    assert not check.decide(check.gaps(prog, ref), lim)[0]


def test_large_seeds_make_the_same_inputs():
    import jax
    seed = 2 ** 31 + 12345
    k1 = jax.random.key_data(weights.key_from_seed(seed))
    k2 = jax.random.key_data(weights.key_from_seed(seed))
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    assert not np.array_equal(
        np.asarray(k1), np.asarray(jax.random.key_data(
            weights.key_from_seed(seed + 1))))
    a = data.TokenStream(1000, 16, seed).batch(3, 2)
    b = data.TokenStream(1000, 16, seed).batch(3, 2)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert not np.array_equal(a["tokens"][0], a["tokens"][1])
