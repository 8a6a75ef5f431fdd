"""Compile the fused codec kernels and a pip_mcoll allreduce for a TPU v5e
that is described, not attached (``v5e:2x2``): what the chip's compiler
refuses (unaligned tiles, scalar VMEM stores, VMEM overflow) fails here
with no chip. Nothing runs, so these tests say nothing about results or
times. The topology is described inside a fixture only: a module that
loaded the TPU library while pytest imports it would break the other
test workers."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compress, runtime
from repro.core.topology import Topology
from repro.kernels import codec as ckern
from repro.launch.mesh import make_mesh

BUCKET_ELEMS = (4 << 20) // 4       # one 4 MiB f32 gradient bucket
SLICES = 4
# fewer rows than one (32, 128) int8 vreg tile, and 540 rows: a full
# 512-row tile and a partial edge tile
EDGE_SHAPES = [(1, 37 * compress.BLOCK), (3, 46000)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_encode_feedback_compiles_for_v5e(name, one_chip):
    lw = ckern.lowering(name)
    x = jax.ShapeDtypeStruct((SLICES, BUCKET_ELEMS // SLICES), jnp.float32,
                             sharding=one_chip)
    _compile(lambda a, e: lw.encode(a, e, interpret=False), x, x)


@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_encode_residual_compiles_for_v5e(name, one_chip):
    lw = ckern.lowering(name)
    x = jax.ShapeDtypeStruct((SLICES, BUCKET_ELEMS // SLICES), jnp.float32,
                             sharding=one_chip)
    _compile(lambda a: lw.encode(a, interpret=False), x)


@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_decode_reduce_compiles_for_v5e(name, one_chip):
    lw = ckern.lowering(name)
    L = BUCKET_ELEMS // SLICES
    comp = jax.eval_shape(compress.codec(name).encode,
                          jax.ShapeDtypeStruct((SLICES, L), jnp.float32))
    _compile(lambda c: lw.decode(c, L, interpret=False),
             _sds(comp, one_chip))


@pytest.mark.parametrize("S,L", EDGE_SHAPES)
@pytest.mark.parametrize("name", ckern.fused_codec_names())
def test_codec_kernels_compile_for_v5e_at_edge_shapes(name, S, L,
                                                     one_chip):
    lw = ckern.lowering(name)
    x = jax.ShapeDtypeStruct((S, L), jnp.float32, sharding=one_chip)
    _compile(lambda a, e: lw.encode(a, e, interpret=False), x, x)
    comp = jax.eval_shape(compress.codec(name).encode,
                          jax.ShapeDtypeStruct((S, L), jnp.float32))
    _compile(lambda c: lw.decode(c, L, interpret=False),
             _sds(comp, one_chip))


@pytest.mark.parametrize("codec", [None, "int8_block"])
def test_pip_mcoll_allreduce_compiles_over_four_chips(codec, topo,
                                                      monkeypatch):
    """One allreduce program over a (2, 2) mesh of the described chips;
    the link classes come from the devices' ``device_kind``. The process's
    backend is the CPU, so the test steers the codec kernels to their
    compiled (not interpreted) form."""
    monkeypatch.setattr(ckern, "_interpret", lambda: False)
    mesh = make_mesh((2, 2), ("node", "local"), devices=topo.devices)
    t = Topology.from_mesh(mesh)
    assert t.link_names == ("tpu_v5e_ici", "tpu_v5e_ici")
    kw = {"codec": codec} if codec else {}
    fn = runtime.build(mesh, t, "allreduce", "pip_mcoll", **kw)
    x = jax.ShapeDtypeStruct(
        (t.world, BUCKET_ELEMS), jnp.float32,
        sharding=runtime.input_sharding(mesh, t, "allreduce"))
    text = fn.lower(x).compile().as_text()
    assert ("tpu_custom_call" in text) == bool(codec)
