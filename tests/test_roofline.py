"""Roofline machinery: HLO parser units (synthetic HLO), trip-count
weighting on a real compiled scan, analytic model-FLOPs sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import SHAPES, get_config
from repro.roofline import hlo as H
from repro.roofline import terms as T


def test_shape_bytes():
    assert H.shape_bytes("f32[8,64]{1,0}") == 8 * 64 * 4
    assert H.shape_bytes("bf16[2,3]") == 12
    assert H.shape_bytes("(f32[4], s32[2])") == 24
    assert H.shape_bytes("pred[10]") == 10
    assert H.shape_bytes("token[]") == 0


SYNTH = """
HloModule m

%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} constant({...})
  %d = f32[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %d)
}

%cond.1 (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %lim = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %lim), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %a)
  %wl = (s32[], f32[8,8]) while(%t0), condition=%cond.1, body=%body.1
  %ar = f32[8,8]{1,0} all-reduce(%a), replica_groups={}, to_apply=%cond.1
  ROOT %r = f32[8,8]{1,0} get-tuple-element(%wl), index=1
}
"""


def test_synthetic_hlo_trip_weighting():
    costs = H.analyze(SYNTH)
    # dot inside a 10-trip loop: 2*8*8*8 * 10
    assert costs.flops == 2 * 8 * 8 * 8 * 10
    assert costs.collective_counts.get("all-reduce") == 1
    assert costs.collective_bytes == 8 * 8 * 4


def test_real_compiled_scan_weighting():
    """Compiled lax.scan: parser FLOPs must scale ~linearly with length."""
    w = jnp.ones((32, 32), jnp.float32)
    x = jnp.ones((4, 32), jnp.float32)

    def f(n):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.jit(lambda x: jax.lax.scan(body, x, None, length=n)[0])

    def flops(n):
        txt = f(n).lower(x).compile().as_text()
        return H.analyze(txt).flops

    f4, f16 = flops(4), flops(16)
    assert f4 > 0
    ratio = f16 / f4
    assert 3.0 < ratio < 5.0, (f4, f16)


def test_movement_chain_effective_bytes():
    txt = """
HloModule m

ENTRY %main (a: bf16[1024,64]) -> f32[1024,64] {
  %a = bf16[1024,64]{1,0} parameter(0)
  %c = f32[1024,64]{1,0} convert(%a)
  %cp = f32[1024,64]{1,0} copy(%c)
  %b = f32[64,64]{1,0} constant({...})
  ROOT %d = f32[1024,64]{1,0} dot(%cp, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    costs = H.analyze(txt)
    # dot reads the bf16-effective operand (1024*64*2) + const (64*64*4),
    # writes f32 out; converts/copies contribute nothing
    want = 1024 * 64 * 2 + 64 * 64 * 4 + 1024 * 64 * 4
    assert costs.memory_bytes == want, costs.memory_bytes


@given(arch=st.sampled_from(["yi-34b", "qwen3-moe-235b-a22b", "smollm-360m"]),
       shape=st.sampled_from(["train_4k", "prefill_32k", "decode_32k"]))
@settings(max_examples=12, deadline=None)
def test_model_flops_properties(arch, shape):
    cfg = get_config(arch)
    sc = SHAPES[shape]
    mf = T.model_flops(cfg, sc)
    mfa = T.model_flops_attn(cfg, sc)
    assert mf > 0 and mfa >= 0
    if shape == "train_4k":
        # train >= 3x prefill per token at equal token counts
        pf = T.model_flops(cfg, SHAPES["prefill_32k"])
        tokens_t = sc.global_batch * sc.seq_len
        tokens_p = SHAPES["prefill_32k"].global_batch * \
            SHAPES["prefill_32k"].seq_len
        np.testing.assert_allclose((mf / tokens_t) / (pf / tokens_p), 3.0,
                                   rtol=1e-6)


def test_terms_bottleneck_classification():
    t = T.compute_terms(1e12, 1e12, 1e9, 256, 6e14)
    assert t.bottleneck == "memory"  # 1e12B/819GBps >> 1e12F/197TFs
    t2 = T.compute_terms(1e14, 1e10, 1e9, 256, 6e16)
    assert t2.bottleneck == "compute"


def test_peaks_table_keyed_by_device_kind():
    pk = T.peaks("TPU v5 lite")
    assert (pk.flops_bf16, pk.hbm_bw, pk.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in pk.source
    assert T.peaks(T.V5E) is pk
    t = T.compute_terms(1e12, 0.0, 0.0, 1, 1e12)
    np.testing.assert_allclose(t.compute_s, 1e12 / 197e12)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite"])
def test_peaks_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        T.peaks(kind)
