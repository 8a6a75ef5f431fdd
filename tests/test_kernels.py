"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all asserting allclose against the pure-jnp ref.py oracles (interpret mode
executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 64, 4, 2, 16), (2, 128, 8, 8, 32), (3, 256, 6, 2, 64),
    (2, 512, 16, 4, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(B, S, H, KV, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S + hd), 3)
    q = _rand(ks[0], (B, 1, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    for cur in (1, S // 3, S):
        got = ops.flash_decode(q, k, v, jnp.int32(cur), chunk=64)
        want = ref.flash_decode(q, k, v, jnp.int32(cur))
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=tol, atol=tol)


@given(b=st.integers(1, 3), nk=st.integers(1, 4), g=st.integers(1, 4),
       hd=st.sampled_from([8, 16, 32]), cur_frac=st.floats(0.1, 1.0))
@settings(max_examples=20, deadline=None)
def test_flash_decode_property(b, nk, g, hd, cur_frac):
    S = 128
    KV = nk
    H = nk * g
    ks = jax.random.split(jax.random.PRNGKey(b * 7 + g), 3)
    q = _rand(ks[0], (b, 1, H, hd), jnp.float32)
    k = _rand(ks[1], (b, S, KV, hd), jnp.float32)
    v = _rand(ks[2], (b, S, KV, hd), jnp.float32)
    cur = max(1, int(S * cur_frac))
    got = ops.flash_decode(q, k, v, jnp.int32(cur), chunk=32)
    want = ref.flash_decode(q, k, v, jnp.int32(cur))
    np.testing.assert_allclose(np.array(got), np.array(want), rtol=3e-5,
                               atol=3e-5)


# ---------------------------------------------------------------------------
# rwkv6_wkv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (1, 32, 2, 8, 8), (2, 64, 4, 16, 32), (1, 128, 1, 32, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_wkv_matches_ref(B, T, H, hd, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(T + hd), 6)
    r = _rand(ks[0], (B, T, H, hd), dtype)
    k = _rand(ks[1], (B, T, H, hd), dtype)
    v = _rand(ks[2], (B, T, H, hd), dtype)
    w = jax.nn.sigmoid(_rand(ks[3], (B, T, H, hd), jnp.float32)) * 0.98
    u = _rand(ks[4], (H, hd), jnp.float32) * 0.1
    s0 = _rand(ks[5], (B, H, hd, hd), jnp.float32) * 0.1
    y_got, sT_got = ops.rwkv6_wkv(r, k, v, w, u, s0, chunk=chunk)
    y_want, sT_want = ref.rwkv6_wkv(r, k, v, w, u, s0)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.array(y_got), np.array(y_want), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.array(sT_got), np.array(sT_want), rtol=tol,
                               atol=tol)


@given(t_chunks=st.integers(1, 4), chunk=st.sampled_from([4, 16, 32]),
       hd=st.sampled_from([4, 8, 16]))
@settings(max_examples=15, deadline=None)
def test_rwkv6_chunking_invariance(t_chunks, chunk, hd):
    """Kernel result must not depend on the chunk size (state handoff)."""
    B, H = 1, 2
    T = t_chunks * 32
    ks = jax.random.split(jax.random.PRNGKey(hd + chunk), 6)
    r = _rand(ks[0], (B, T, H, hd), jnp.float32)
    k = _rand(ks[1], (B, T, H, hd), jnp.float32)
    v = _rand(ks[2], (B, T, H, hd), jnp.float32)
    w = jax.nn.sigmoid(_rand(ks[3], (B, T, H, hd), jnp.float32))
    u = _rand(ks[4], (H, hd), jnp.float32) * 0.1
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y1, s1 = ops.rwkv6_wkv(r, k, v, w, u, s0, chunk=chunk)
    y2, s2 = ref.rwkv6_wkv(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.array(y1), np.array(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.array(s1), np.array(s2), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# mamba_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,Di,N,chunk,dblk", [
    (1, 32, 16, 4, 8, 8), (2, 64, 64, 16, 32, 32), (1, 128, 32, 8, 128, 16),
])
def test_mamba_scan_matches_ref(B, T, Di, N, chunk, dblk):
    ks = jax.random.split(jax.random.PRNGKey(T + Di), 5)
    dt = jax.nn.softplus(_rand(ks[0], (B, T, Di), jnp.float32))
    A = -jnp.exp(_rand(ks[1], (Di, N), jnp.float32) * 0.5)
    Bm = _rand(ks[2], (B, T, N), jnp.float32)
    Cm = _rand(ks[3], (B, T, N), jnp.float32)
    x = _rand(ks[4], (B, T, Di), jnp.float32)
    y_got, h_got = ops.mamba_scan(dt, A, Bm, Cm, x, chunk=chunk, dblk=dblk)
    y_want, h_want = ref.mamba_scan(dt, A, Bm, Cm, x)
    np.testing.assert_allclose(np.array(y_got), np.array(y_want), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.array(h_got), np.array(h_want), rtol=2e-4,
                               atol=2e-4)


@given(chunk=st.sampled_from([4, 8, 32]), dblk=st.sampled_from([4, 16]))
@settings(max_examples=10, deadline=None)
def test_mamba_scan_block_invariance(chunk, dblk):
    B, T, Di, N = 1, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(chunk * 31 + dblk), 5)
    dt = jax.nn.softplus(_rand(ks[0], (B, T, Di), jnp.float32))
    A = -jnp.exp(_rand(ks[1], (Di, N), jnp.float32) * 0.5)
    Bm = _rand(ks[2], (B, T, N), jnp.float32)
    Cm = _rand(ks[3], (B, T, N), jnp.float32)
    x = _rand(ks[4], (B, T, Di), jnp.float32)
    y_got, h_got = ops.mamba_scan(dt, A, Bm, Cm, x, chunk=chunk, dblk=dblk)
    y_want, h_want = ref.mamba_scan(dt, A, Bm, Cm, x)
    np.testing.assert_allclose(np.array(y_got), np.array(y_want), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# staging kernels (the paper's shared-memory copy analogues)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,m", [(4, 8), (16, 32), (7, 5), (128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_shift_blocks(N, m, dtype):
    v = jnp.arange(N * m).reshape(N, m).astype(dtype)
    for shift in (0, 1, N // 2, N - 1):
        got = ops.shift_blocks(v, jnp.int32(shift))
        want = ref.shift_blocks(v, shift)
        np.testing.assert_array_equal(np.array(got), np.array(want))


@given(n=st.integers(2, 64), k=st.integers(1, 32), seed=st.integers(0, 99))
@settings(max_examples=25, deadline=None)
def test_pack_blocks_property(n, k, seed):
    m = 4
    src = jnp.arange(n * m, dtype=jnp.float32).reshape(n, m)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (k,), 0, n)
    got = ops.pack_blocks(src, idx)
    want = ref.pack_blocks(src, idx)
    np.testing.assert_array_equal(np.array(got), np.array(want))


# ---------------------------------------------------------------------------
# fused codec kernels (encode+error-feedback / decode+reduce, interpret mode
# on CPU — the same kernel bodies the compressed collectives route through)
# ---------------------------------------------------------------------------


from repro.core import compress  # noqa: E402  (kernel tests below need it)
from repro.kernels import codec as ckern  # noqa: E402

# (3, 46000) is 540 block rows: one full 512-row tile and a partial one
CODEC_SHAPES = [(1, 256), (3, 1000), (4, 64), (2, 2048), (3, 46000)]


def _codec_payload(S, L, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (S, L), jnp.float32)
    err = jax.random.normal(k2, (S, L), jnp.float32) * 0.01
    return x, err


def test_codec_lowerings_registered():
    names = ckern.fused_codec_names()
    assert "int8_block" in names and "int4_block" in names
    assert ("fp8_sim" in names) == hasattr(jnp, "float8_e4m3fn")
    # registry agreement: compress advertises exactly what's registered
    assert set(compress.fused_codecs()) == set(names)
    for n in names:
        lw = ckern.lowering(n)
        assert lw is not None and lw.name == n
    assert ckern.lowering("topk") is None


@pytest.mark.parametrize("S,L", CODEC_SHAPES)
@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_encode_feedback_matches_jnp(name, S, L):
    """Fused one-pass encode+error-feedback vs the jitted jnp reference:
    identical wire form (bitwise), residual to float tolerance."""
    x, err = _codec_payload(S, L, seed=S * 31 + L)
    cd = compress.codec(name)
    lw = ckern.lowering(name)
    with compress.jnp_reference_paths():
        comp_ref, res_ref = jax.jit(cd.encode_with_feedback)(x, err)
    comp_got, res_got = lw.encode_feedback(x, err)
    assert set(comp_got) == set(comp_ref)
    for leaf in comp_ref:
        np.testing.assert_array_equal(np.array(comp_ref[leaf]),
                                      np.array(comp_got[leaf]), err_msg=leaf)
    np.testing.assert_allclose(np.array(res_ref), np.array(res_got),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("S,L", CODEC_SHAPES)
@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_encode_residual_matches_jnp(name, S, L):
    x, _ = _codec_payload(S, L, seed=S + L)
    cd = compress.codec(name)
    lw = ckern.lowering(name)

    def jnp_ref(x2d):
        comp = cd.encode(x2d)
        return comp, x2d - cd.decode(comp, x2d.shape[-1])

    with compress.jnp_reference_paths():
        comp_ref, res_ref = jax.jit(jnp_ref)(x)
    comp_got, res_got = lw.encode_residual(x)
    for leaf in comp_ref:
        np.testing.assert_array_equal(np.array(comp_ref[leaf]),
                                      np.array(comp_got[leaf]), err_msg=leaf)
    np.testing.assert_allclose(np.array(res_ref), np.array(res_got),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("W", [1, 2, 8])
@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_decode_reduce_matches_jnp(name, W):
    """Register accumulation over the wire axis vs dequantize-then-sum
    (accumulation order differs, so float tolerance not bitwise)."""
    L = 777
    cd = compress.codec(name)
    xs = jax.random.normal(jax.random.PRNGKey(W), (W, L), jnp.float32)
    comp = cd.encode(xs)
    with compress.jnp_reference_paths():
        want = jax.jit(lambda c: cd.decode(c, L).sum(axis=0))(comp)
    got = ckern.lowering(name).decode_reduce(comp, L)
    assert got.shape == (L,)
    np.testing.assert_allclose(np.array(want), np.array(got),
                               rtol=1e-6, atol=1e-5 * W)


@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_decode_reduce_past_one_tile_matches_jnp(name):
    """547 block rows: a full 512-row tile and a partial edge tile."""
    W, L = 2, 547 * 256 - 100
    cd = compress.codec(name)
    xs = jax.random.normal(jax.random.PRNGKey(5), (W, L), jnp.float32)
    comp = cd.encode(xs)
    with compress.jnp_reference_paths():
        want = jax.jit(lambda c: cd.decode(c, L).sum(axis=0))(comp)
    got = ckern.lowering(name).decode_reduce(comp, L)
    assert got.shape == (L,)
    np.testing.assert_allclose(np.array(want), np.array(got),
                               rtol=1e-6, atol=1e-5 * W)


@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_fused_roundtrip_within_stated_bound(name):
    """decode(fused-encoded wire) honors the codec's stated error bound."""
    cd = compress.codec(name)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 512), jnp.float32)
    comp, res = ckern.lowering(name).encode_residual(x)
    back = cd.decode(comp, 512)
    bound = cd.meta.error_bound * float(jnp.max(jnp.abs(x))) + 1e-6
    assert float(jnp.max(jnp.abs(back - x))) <= bound
    # the residual IS the roundtrip error
    np.testing.assert_allclose(np.array(res), np.array(x - back),
                               rtol=0, atol=1e-6)


def test_codec_int4_wire_is_packed_two_per_byte():
    cd = compress.codec("int4_block")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 512), jnp.float32)
    comp, _ = ckern.lowering("int4_block").encode_residual(x)
    assert comp["q"].dtype == jnp.uint8
    assert comp["q"].shape == (2, 2, compress.BLOCK // 2)  # half the elems
    # measured wire bytes track the declared ~7.8x ratio
    ratio = x.size * 4 / cd.wire_bytes(comp)
    assert ratio >= 0.9 * cd.meta.wire_ratio


@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_error_feedback_converges_through_fused_path(name):
    """Carried residual keeps the accumulated signal within one step's
    quantization error of the true accumulation (Karimireddy)."""
    cd = compress.codec(name)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 640), jnp.float32)
    err = jnp.zeros_like(x)
    acc = jnp.zeros_like(x)
    step = jax.jit(cd.encode_with_feedback)
    for _ in range(50):
        comp, err = step(x, err)
        acc = acc + cd.decode(comp, 640)
    true = 50.0 * x
    # telescoping: acc + err == 50*x up to float roundoff...
    np.testing.assert_allclose(np.array(acc + err), np.array(true),
                               rtol=1e-4, atol=1e-3)
    # ...so the tracking error stays one step's quantization residual,
    # never accumulating over the 50 steps
    bound = cd.meta.error_bound * float(jnp.max(jnp.abs(x))) * 1.5 + 1e-3
    assert float(jnp.max(jnp.abs(acc - true))) <= bound


def test_codec_memory_traffic_fused_at_most_half():
    """The analytic pass accounting behind the cost model's fused pricing:
    encode+feedback moves <= half the jnp path's bytes for every one-pass
    fused codec. A codec with a slice-max pass (fp8_sim) still moves
    fewer, and exactly one more read of x + err than one pass would."""
    for name in ckern.fused_codec_names():
        m = compress.meta(name)
        amax = ckern.lowering(name).amax_pass
        n, b = 1 << 20, 4.0 / m.wire_ratio
        tr = ckern.memory_traffic(b, n, W=8, amax_pass=amax)
        enc = tr["encode_feedback"]
        if amax:
            one = ckern.memory_traffic(b, n, W=8)["encode_feedback"]
            assert enc["fused_bytes"] == one["fused_bytes"] + 8 * n, name
            assert enc["fused_bytes"] < enc["jnp_bytes"], (name, enc)
        else:
            assert enc["fused_bytes"] <= 0.5 * enc["jnp_bytes"], (name, enc)
        dec = tr["decode_reduce"]
        assert dec["fused_bytes"] < dec["jnp_bytes"], (name, dec)


def test_kernels_integrate_with_layers():
    """use_kernel paths wire correctly into the layers.

    Layer-level: kernel output must be EXACT vs the default path (same
    inputs). Model-level: one-ulp bf16 reassociation inside lax.scan can
    flip discrete MoE top-k routing for a few tokens (verified benign — both
    paths shift equally vs the unscanned reference), so end-to-end we assert
    greedy-token agreement instead of elementwise closeness."""
    from repro.configs import reduced_config
    from repro.layers import mamba, rwkv
    from repro.models import decoder

    # exactness at the layer level
    cfg_m = reduced_config("jamba-1.5-large-398b")
    pm = mamba.init(jax.random.PRNGKey(0), cfg_m)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, 32, cfg_m.d_model)).astype(jnp.bfloat16)
    y_ref, _ = mamba.apply(pm, x, cfg_m, use_kernel=False)
    y_ker, _ = mamba.apply(pm, x, cfg_m, use_kernel=True)
    np.testing.assert_array_equal(np.array(y_ref, np.float32),
                                  np.array(y_ker, np.float32))
    cfg_r = reduced_config("rwkv6-1.6b")
    pr = rwkv.init(jax.random.PRNGKey(0), cfg_r)
    xr = jax.random.normal(jax.random.PRNGKey(2),
                           (2, 32, cfg_r.d_model)).astype(jnp.bfloat16)
    y1, _, s1 = rwkv.time_mix(pr["tm"], xr, cfg_r, use_kernel=False)
    y2, _, s2 = rwkv.time_mix(pr["tm"], xr, cfg_r, use_kernel=True)
    np.testing.assert_array_equal(np.array(y1, np.float32),
                                  np.array(y2, np.float32))

    # wiring through the full models
    for arch in ("rwkv6-1.6b", "jamba-1.5-large-398b"):
        cfg = reduced_config(arch)
        params = decoder.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                    cfg.vocab)
        base, _, _ = decoder.forward(params, tokens, cfg)
        flags = decoder.RunFlags(use_rwkv_kernel=True, use_mamba_kernel=True)
        got, _, _ = decoder.forward(params, tokens, cfg, flags=flags)
        b = np.array(base, np.float32)
        g = np.array(got, np.float32)
        agree = (b.argmax(-1) == g.argmax(-1)).mean()
        assert agree > 0.9, (arch, agree)
