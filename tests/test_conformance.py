"""Collective conformance suite: every registered (collective x algorithm)
pair against the ``xla_*`` reference, across dtypes, odd / non-power-of-two
payload shapes, and chunk counts.

Unlike the subprocess checks (tests/checks/*), this suite runs IN-PROCESS
on whatever devices the interpreter was started with, factoring
``jax.device_count()`` into a (node, local) mesh. Under the tier-1 run
that is the 1-device degenerate topology (cheap, still exercises every
algorithm's trace path and the chunking/padding arithmetic); CI runs the
same suite under a device-count matrix
(``XLA_FLAGS=--xla_force_host_platform_device_count={1,2,8}``) so the
multi-device routing is conformance-tested per count. The exhaustive
dtype/shape/chunk sweeps are marked ``slow`` so the matrix can split fast
and slow legs; the ``comm.split()`` group leg (every collective x
algorithm over the mesh split each way, bitwise against the reference
restricted to the group) selects with ``-k group``.

Property sweeps use ``_hypothesis_compat``: full property search with
hypothesis installed, a fixed deterministic replay without it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import autotune, compress, costmodel, mcoll, runtime
from repro.core.comm import Communicator
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# mesh from the ambient device count (the CI matrix sets XLA_FLAGS)
# ---------------------------------------------------------------------------

DC = jax.device_count()
P = 2 if DC % 2 == 0 else 1
N = DC // P
M = N * P
mesh = make_mesh((N, P), ("node", "local"))
topo = Topology(N, P)
COMM = Communicator(mesh, topo)

PAIRS = [(coll, algo) for coll in runtime.collectives()
         for algo in mcoll.algorithms(coll)]
CHUNKED_PAIRS = [(coll, algo) for coll, algo in PAIRS
                 if mcoll.supports_chunks(coll, algo)]
CODEC_PAIRS = [(coll, algo) for coll, algo in PAIRS
               if mcoll.supports_codec(coll, algo)]
# every (collective x codec) pair, through each codec-capable algorithm
CODEC_TRIPLES = [(coll, algo, cd) for coll, algo in CODEC_PAIRS
                 for cd in compress.lossy()]
DTYPES = ("float32", "bfloat16", "int32")

# reference algorithm per collective: the vendor lowering ("linear" is
# scatter's vendor-equivalent masked select)
REF = {coll: ("xla" if "xla" in mcoll.algorithms(coll) else "linear")
       for coll in runtime.collectives()}


def _operand(coll: str, m: int, dtype: str):
    """Global operand with per-rank payload ``m`` elements. Values are
    small integers so every reduction is exact in every swept dtype
    (bf16 represents ints < 256 exactly) and equality checks can be
    bitwise across algorithms."""
    dt = jnp.dtype(dtype)
    if coll == "allgather" or coll == "scatter":
        return (jnp.arange(M * m) % 97).astype(dt)
    if coll == "broadcast":
        return (jnp.arange(m) % 97 + 1).astype(dt)
    if coll == "allreduce":
        return (jnp.arange(M * m) % 5).astype(dt).reshape(M, m)
    if coll == "reduce_scatter":
        return (jnp.arange(M * M * m) % 5).astype(dt).reshape(M, M * m)
    if coll == "alltoall":
        return (jnp.arange(M * M * m) % 97).astype(dt).reshape(M, M, m)
    raise ValueError(coll)


def _oracle(coll: str, x):
    """Pure-numpy semantics of each collective on the global operand."""
    a = np.asarray(x.astype(jnp.float32))
    if coll == "allgather":
        return np.stack([a] * M)          # row d = full gather on device d
    if coll == "scatter":
        return a                           # shards concatenate to the input
    if coll == "broadcast":
        return np.stack([a] * M)
    if coll == "allreduce":
        return np.stack([a.sum(0)] * M)
    if coll == "reduce_scatter":
        return a.sum(0)
    if coll == "alltoall":
        return a.transpose(1, 0, 2)
    raise ValueError(coll)


def _feasible(coll: str, algo: str) -> bool:
    return algo in autotune.candidates(coll, topo)


def _run(coll: str, algo: str, x, **kw):
    out = COMM.invoke(coll, x, algo=algo, **kw)
    return np.asarray(out.astype(jnp.float32))


def _run_persistent(coll: str, algo: str, x, **kw):
    """The same plan through a persistent op: init (plan resolved +
    compiled once), one start/wait."""
    op = COMM.persistent(coll, x, algo=algo, **kw)
    return np.asarray(op.start(x).wait().astype(jnp.float32))


def _assert_conforms(coll: str, algo: str, m: int, dtype: str, **kw):
    if not _feasible(coll, algo):
        pytest.skip(f"{algo} infeasible on {N}x{P}")
    x = _operand(coll, m, dtype)
    got = _run(coll, algo, x, **kw)
    ref = _run(coll, REF[coll], x)
    # integer-valued payloads: every algorithm must agree with the vendor
    # reference bitwise, in every dtype
    np.testing.assert_array_equal(
        got, ref, err_msg=f"{coll}/{algo} m={m} {dtype} {kw}")
    np.testing.assert_array_equal(
        ref, _oracle(coll, x), err_msg=f"{coll}/{REF[coll]} oracle m={m}")


# ---------------------------------------------------------------------------
# fast leg: every registered pair, f32, odd payload (runs at every device
# count in the CI matrix; 1-device under tier-1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("coll,algo", PAIRS)
def test_conformance_every_pair_odd_payload(coll, algo):
    _assert_conforms(coll, algo, 5, "float32")


@pytest.mark.parametrize("coll,algo", CHUNKED_PAIRS)
def test_conformance_chunked_pairs_basic(coll, algo):
    # a chunk count that does not divide the payload (remainder segment)
    _assert_conforms(coll, algo, 5, "float32", chunks=2)
    _assert_conforms(coll, algo, 5, "float32", chunks=3)


# ---------------------------------------------------------------------------
# persistent leg: blocking vs persistent-nonblocking execution of ONE plan
# must be bitwise identical, for every (collective x algorithm x chunks x
# codec) plan; plus handle-misuse errors (double wait, start past depth)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("coll,algo", PAIRS)
def test_persistent_matches_blocking_every_pair(coll, algo):
    if not _feasible(coll, algo):
        pytest.skip(f"{algo} infeasible on {N}x{P}")
    x = _operand(coll, 5, "float32")
    np.testing.assert_array_equal(_run_persistent(coll, algo, x),
                                  _run(coll, algo, x),
                                  err_msg=f"{coll}/{algo} persistent")


@pytest.mark.parametrize("coll,algo", CHUNKED_PAIRS)
def test_persistent_matches_blocking_chunked(coll, algo):
    if not _feasible(coll, algo):
        pytest.skip(f"{algo} infeasible on {N}x{P}")
    x = _operand(coll, 5, "float32")
    for chunks in (2, 3):
        np.testing.assert_array_equal(
            _run_persistent(coll, algo, x, chunks=chunks),
            _run(coll, algo, x, chunks=chunks),
            err_msg=f"{coll}/{algo} c={chunks} persistent")


@pytest.mark.parametrize("coll,algo,cd", CODEC_TRIPLES)
def test_persistent_matches_blocking_compressed(coll, algo, cd):
    """Lossy plans too: same compiled plan, deterministic execution —
    persistent start/wait must reproduce the blocking result bitwise."""
    if not _feasible(coll, algo):
        pytest.skip(f"{algo} infeasible on {N}x{P}")
    x = _operand(coll, 80, "float32")
    np.testing.assert_array_equal(_run_persistent(coll, algo, x, codec=cd),
                                  _run(coll, algo, x, codec=cd),
                                  err_msg=f"{coll}/{algo}@{cd} persistent")


@pytest.mark.parametrize("coll", sorted(runtime.collectives()))
def test_persistent_auto_plan_matches_blocking(coll):
    """algo="auto" resolves to the same plan at init and call time — the
    persistent op and the blocking method share one executable."""
    x = _operand(coll, 5, "float32")
    np.testing.assert_array_equal(_run_persistent(coll, "auto", x),
                                  _run(coll, "auto", x))


def test_persistent_compiles_once_across_starts():
    """Repeated start/wait on one op never re-enters the exec cache."""
    x = _operand("allreduce", 16, "float32")
    op = COMM.allreduce_init(x, algo="pip_mcoll")
    misses0 = runtime.cache_stats().exec_misses
    outs = [np.asarray(op.start(x).wait()) for _ in range(4)]
    assert runtime.cache_stats().exec_misses == misses0
    assert op.starts == 4
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])
    # a second op of the same spec is an exec-cache hit, not a compile
    COMM.allreduce_init(x, algo="pip_mcoll")
    assert runtime.cache_stats().exec_misses == misses0


def test_persistent_handle_misuse_errors():
    x = _operand("allreduce", 8, "float32")
    op = COMM.allreduce_init(x, algo="pip_mcoll")  # depth=1
    h = op.start(x)
    with pytest.raises(RuntimeError, match="outstanding"):
        op.start(x)  # start before wait without double buffering
    h.wait()
    with pytest.raises(RuntimeError, match="double wait"):
        h.wait()
    op.start(x).wait()  # slot released: pairing works again
    # depth=2 (double buffering) allows exactly one extra outstanding start
    op2 = COMM.allreduce_init(x, algo="pip_mcoll", depth=2)
    h1, h2 = op2.start(x), op2.start(x)
    with pytest.raises(RuntimeError, match="outstanding"):
        op2.start(x)
    np.testing.assert_array_equal(np.asarray(h1.wait()),
                                  np.asarray(h2.wait()))


def test_persistent_rejects_operand_spec_mismatch():
    x = _operand("allreduce", 8, "float32")
    op = COMM.allreduce_init(x, algo="pip_mcoll")
    with pytest.raises(ValueError, match="compiled for"):
        op.start(_operand("allreduce", 9, "float32"))
    with pytest.raises(ValueError, match="compiled for"):
        op.start(_operand("allreduce", 8, "int32"))


CARRY_ALGOS = sorted({algo for coll, algo in CODEC_PAIRS
                      if coll == "allreduce"
                      and runtime.supports_carry("allreduce", algo)})


@pytest.mark.parametrize("cd", sorted(compress.lossy()))
@pytest.mark.parametrize("algo", CARRY_ALGOS)
def test_persistent_carry_threads_error_feedback(algo, cd):
    """The carry-threaded persistent op (``start(x, carry=err)`` ->
    ``wait() -> (y, new_err)``) is the per-bucket error-feedback hookup of
    the overlapped gradient sync: its result must stay inside the codec's
    stated collective bound, match the runtime's carry program bitwise
    (shared lowering), and be deterministic so the overlap/barrier step
    twins stay bit-identical."""
    if not _feasible("allreduce", algo):
        pytest.skip(f"{algo} infeasible on {N}x{P}")
    x = _operand("allreduce", 80, "float32")
    e0 = jnp.zeros_like(x)
    op = COMM.persistent("allreduce", x, algo=algo, codec=cd, carry=True)
    assert op.carry
    y1, e1 = op.start(x, carry=e0).wait()
    ref = _run("allreduce", REF["allreduce"], x)
    tol = compress.collective_tolerance(
        cd, "allreduce", M, float(np.abs(np.asarray(x)).max()))
    err = np.abs(np.asarray(y1, np.float32) - ref).max()
    assert err <= tol, f"allreduce/{algo}@{cd} carry: {err} > {tol}"
    fn = runtime.build(COMM.mesh, topo, "allreduce", algo, carry=True,
                       codec=cd)
    ry, re = fn(x, e0)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(ry),
                                  err_msg=f"{algo}@{cd} carry result")
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(re),
                                  err_msg=f"{algo}@{cd} carry state")
    # determinism under a threaded (possibly nonzero) state: the same
    # (payload, err) pair always produces the same (result, state)
    y2a, e2a = op.start(x, carry=e1).wait()
    y2b, e2b = op.start(x, carry=e1).wait()
    np.testing.assert_array_equal(np.asarray(y2a), np.asarray(y2b))
    np.testing.assert_array_equal(np.asarray(e2a), np.asarray(e2b))


# ---------------------------------------------------------------------------
# compressed leg: every (collective x codec) pair vs the xla reference,
# asserting the codec's stated relative-error bound instead of equality
# (CI runs this as its own matrix step via ``-k compressed``)
# ---------------------------------------------------------------------------


def _assert_conforms_compressed(coll: str, algo: str, cd: str, m: int,
                                **kw):
    if not _feasible(coll, algo):
        pytest.skip(f"{algo} infeasible on {N}x{P}")
    x = _operand(coll, m, "float32")
    got = _run(coll, algo, x, codec=cd, **kw)
    ref = _run(coll, REF[coll], x)
    tol = compress.collective_tolerance(cd, coll, M,
                                        float(jnp.abs(x).max())) + 1e-6
    err = np.abs(got - ref).max()
    assert err <= tol, f"{coll}/{algo}@{cd} m={m} {kw}: {err} > {tol}"


@pytest.mark.parametrize("coll,algo,cd", CODEC_TRIPLES)
def test_conformance_compressed_pairs(coll, algo, cd):
    _assert_conforms_compressed(coll, algo, cd, 80)


@pytest.mark.parametrize("coll,algo", CODEC_PAIRS)
def test_conformance_compressed_none_is_bitwise(coll, algo):
    """codec="none" on a codec-capable algorithm is the lossless algorithm
    exactly — one plan, bitwise equal to the bare call."""
    x = _operand(coll, 5, "float32")
    np.testing.assert_array_equal(_run(coll, algo, x, codec="none"),
                                  _run(coll, algo, x))


@pytest.mark.parametrize(
    "coll,algo", [(c, a) for c, a in CODEC_PAIRS
                  if mcoll.supports_chunks(c, a)])
def test_conformance_compressed_chunked_compose(coll, algo):
    """codec composes with chunks: compressed segments pipeline
    independently and still land inside the codec bound."""
    _assert_conforms_compressed(coll, algo, "int8_block", 80, chunks=3)


@pytest.mark.parametrize("coll", sorted({c for c, _ in CODEC_PAIRS}))
def test_conformance_compressed_auto_budget(coll):
    """algo="auto" under an error budget resolves to a plan (lossless or
    admissible codec) that conforms within the loosest admissible bound."""
    budget = float(compress.meta("int8_block").error_bound)
    x = _operand(coll, 64, "float32")
    got = _run(coll, "auto", x, error_budget=budget)
    ref = _run(coll, REF[coll], x)
    tol = compress.collective_tolerance("int8_block", coll, M,
                                        float(jnp.abs(x).max())) + 1e-6
    assert np.abs(got - ref).max() <= tol


def test_compressed_rejects_integer_payloads():
    """Lossy codecs on integer payloads must fail clearly at trace time,
    not silently round token ids (checked before the degenerate-topology
    shortcut, so the error does not depend on the device count)."""
    x = _operand("allreduce", 5, "int32")
    with pytest.raises(ValueError, match="integer payload"):
        _run("allreduce", "pip_mcoll", x, codec="int8_block")
    # ... while auto under a budget resolves integer payloads lossless
    # instead of crashing, and stays exact
    got = _run("allreduce", "auto", x, error_budget=1.0)
    np.testing.assert_array_equal(got, _run("allreduce", REF["allreduce"],
                                            x))


@pytest.mark.slow
@pytest.mark.parametrize("coll,algo,cd", CODEC_TRIPLES)
@given(m=st.sampled_from([1, 7, 64, 300]))
@settings(max_examples=4, deadline=None)
def test_conformance_compressed_shape_sweep(coll, algo, cd, m):
    """Odd / non-block-divisible payloads through every codec pair."""
    _assert_conforms_compressed(coll, algo, cd, m)


# ---------------------------------------------------------------------------
# fused-kernel leg: every fused codec x codec-capable collective x chunk
# plan, A/B against the pure-jnp reference paths (compress.
# jnp_reference_paths flips the routing; the runtime caches key on the
# toggle so the two variants compile separately). Lossy fused codecs agree
# within collective_tolerance (decode+reduce accumulates in a different
# order, which can flip one requantization rounding); lossless plans are
# bitwise invariant under the toggle.
# ---------------------------------------------------------------------------

FUSED_TRIPLES = [(coll, algo, cd) for coll, algo in CODEC_PAIRS
                 for cd in compress.fused_codecs()]


def _assert_fused_matches_jnp(coll: str, algo: str, cd: str, m: int, **kw):
    if not _feasible(coll, algo):
        pytest.skip(f"{algo} infeasible on {N}x{P}")
    x = _operand(coll, m, "float32")
    got_fused = _run(coll, algo, x, codec=cd, **kw)
    with compress.jnp_reference_paths():
        got_jnp = _run(coll, algo, x, codec=cd, **kw)
    tol = compress.collective_tolerance(cd, coll, M,
                                        float(jnp.abs(x).max())) + 1e-6
    ab = np.abs(got_fused - got_jnp).max()
    assert ab <= tol, f"{coll}/{algo}@{cd} fused-vs-jnp m={m} {kw}: " \
                      f"{ab} > {tol}"
    # the fused path also conforms to the lossless reference on its own
    ref = _run(coll, REF[coll], x)
    err = np.abs(got_fused - ref).max()
    assert err <= tol, f"{coll}/{algo}@{cd} fused-vs-ref m={m} {kw}: " \
                       f"{err} > {tol}"


@pytest.mark.parametrize("coll,algo,cd", FUSED_TRIPLES)
def test_conformance_fused_matches_jnp_reference(coll, algo, cd):
    _assert_fused_matches_jnp(coll, algo, cd, 80)


@pytest.mark.parametrize("chunks", [2, 3])
@pytest.mark.parametrize(
    "coll,algo,cd", [t for t in FUSED_TRIPLES
                     if mcoll.supports_chunks(t[0], t[1])])
def test_conformance_fused_chunked_plans(coll, algo, cd, chunks):
    """Fusion composes with chunked pipelining: every chunk segment rides
    the fused kernels independently."""
    _assert_fused_matches_jnp(coll, algo, cd, 80, chunks=chunks)


@pytest.mark.parametrize("coll,algo", CODEC_PAIRS)
def test_conformance_fused_toggle_lossless_bitwise(coll, algo):
    """codec="none" never routes through a fused lowering — the toggle
    must be bitwise invisible on lossless plans."""
    x = _operand(coll, 5, "float32")
    a = _run(coll, algo, x, codec="none")
    with compress.jnp_reference_paths():
        b = _run(coll, algo, x, codec="none")
    np.testing.assert_array_equal(a, b)


@pytest.mark.slow
@pytest.mark.parametrize("coll,algo,cd", FUSED_TRIPLES)
@given(m=st.sampled_from([1, 7, 64, 300]))
@settings(max_examples=4, deadline=None)
def test_conformance_fused_shape_sweep(coll, algo, cd, m):
    """Odd / non-block-divisible payloads through every fused pair."""
    _assert_fused_matches_jnp(coll, algo, cd, m)


# ---------------------------------------------------------------------------
# root-encodes-once wire form (broadcast/scatter) + the lossless integer
# packer: compressed one-to-all moves the ROOT's encoded form verbatim, so
# even a lossy codec's output is bitwise decode(encode(x)) on every rank —
# re-encoding at each tree hop would compound the error and break this.
# The reference round trip runs under jit like the collective does (XLA's
# fused scale arithmetic differs from eager by an ulp on some blocks).
# ---------------------------------------------------------------------------


def _jit_roundtrip(cd, flat):
    cdo = compress.codec(cd)
    L = flat.shape[1]
    return np.asarray(jax.jit(lambda v: cdo.decode(cdo.encode(v), L))(flat))


@pytest.mark.parametrize("cd", sorted(compress.lossy()))
def test_broadcast_root_encodes_once_wire_form(cd):
    m = 2 * compress.BLOCK + 7
    x = jax.random.normal(jax.random.PRNGKey(0), (m,), jnp.float32)
    got = np.asarray(COMM.broadcast(x, algo="pip_mcoll", codec=cd))
    want = _jit_roundtrip(cd, x.reshape(1, -1)).reshape(m)
    for d in range(M):
        np.testing.assert_array_equal(
            got[d], want, err_msg=f"broadcast@{cd} rank {d} re-encoded")


@pytest.mark.parametrize("cd", sorted(compress.lossy()))
def test_scatter_root_encodes_once_wire_form(cd):
    m = compress.BLOCK + 3
    x = jax.random.normal(jax.random.PRNGKey(1), (M * m,), jnp.float32)
    got = np.asarray(COMM.scatter(x, algo="pip_mcoll", codec=cd))
    flat = x.reshape(M, -1)  # one wire row per destination rank
    want = _jit_roundtrip(cd, flat)
    np.testing.assert_array_equal(
        got.reshape(M, m), want, err_msg=f"scatter@{cd} re-encoded")


@pytest.mark.parametrize("coll", sorted({c for c, _ in CODEC_PAIRS}
                                        - {"allreduce", "reduce_scatter"}))
def test_zlib_sim_bitwise_on_integer_payloads(coll):
    """The lossless integer packer is bitwise-exact end to end on every
    non-reducing collective (its admissible domain)."""
    x = _operand(coll, 40, "int32")
    got = COMM.invoke(coll, x, algo="pip_mcoll", codec="zlib_sim")
    ref = COMM.invoke(coll, x, algo=REF[coll])
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_zlib_sim_preserves_large_int32_values():
    """Values above 2^24 (unrepresentable in f32) survive: integer-only
    codecs never touch the f32 pre-cast path, and only the per-slice RANGE
    must fit 16 bits."""
    base = 1 << 28
    x = ((jnp.arange(M * 5) % 97) + base).astype(jnp.int32)
    got = np.asarray(COMM.allgather(x, algo="pip_mcoll", codec="zlib_sim"))
    want = np.stack([np.asarray(x)] * M)
    np.testing.assert_array_equal(got, want)


def test_zlib_sim_rejected_on_reducing_and_float():
    x = _operand("allreduce", 5, "int32")
    with pytest.raises(ValueError, match="not additive|not admissible"):
        _run("allreduce", "pip_mcoll", x, codec="zlib_sim")
    xf = _operand("broadcast", 5, "float32")
    with pytest.raises(ValueError, match="float payload|not admissible"):
        _run("broadcast", "pip_mcoll", xf, codec="zlib_sim")


def test_auto_integer_broadcast_can_pick_zlib_sim():
    """Selection layer: for an integer broadcast, zlib_sim is an
    admissible candidate at budget 0 — and an explicit measured entry
    naming it wins resolution."""
    sel = autotune.Selector()
    c = Communicator(mesh, topo, selector=sel)
    sel.table.record(topo, "broadcast", "int32", 4 * 40,
                     autotune.encode_plan("pip_mcoll", 1, "zlib_sim"), 1e-12)
    s = sel.choose("broadcast", topo, 4 * 40, dtype="int32")
    assert (s.algo, s.codec) == ("pip_mcoll", "zlib_sim")
    x = _operand("broadcast", 40, "int32")
    np.testing.assert_array_equal(np.asarray(c.broadcast(x)),
                                  np.asarray(_run("broadcast",
                                                  REF["broadcast"], x)))


# ---------------------------------------------------------------------------
# group leg: comm.split() sub-communicators — every collective x algorithm
# over the mesh split along each axis (and both), asserting bitwise
# equality against the reference algorithm restricted to the group AND a
# pure-numpy group oracle (CI selects this leg with ``-k group``)
# ---------------------------------------------------------------------------

GROUP_AXES = [("node",), ("local",), ("node", "local")]
GROUP_IDS = ["node", "local", "node-local"]


def _group_members(axes):
    """Flat mesh ranks of every group, each in group-rank order (mesh is
    (N, P) row-major: flat rank d = n * P + p)."""
    if axes == ("node",):
        return [[n * P + p for n in range(N)] for p in range(P)]
    if axes == ("local",):
        return [[n * P + p for p in range(P)] for n in range(N)]
    return [list(range(M))]


def _group_operand(coll: str, G: int, m: int, dtype: str):
    """Global operand per the group I/O convention (D = mesh devices,
    G = group world; see runtime.build)."""
    dt = jnp.dtype(dtype)
    if coll == "allgather":
        return (jnp.arange(M * m) % 97).astype(dt)
    if coll == "scatter":
        return (jnp.arange(G * m) % 97).astype(dt)
    if coll == "broadcast":
        return (jnp.arange(m) % 97 + 1).astype(dt)
    if coll == "allreduce":
        return (jnp.arange(M * m) % 5).astype(dt).reshape(M, m)
    if coll == "reduce_scatter":
        return (jnp.arange(M * G * m) % 5).astype(dt).reshape(M, G * m)
    if coll == "alltoall":
        return (jnp.arange(M * G * m) % 97).astype(dt).reshape(M, G, m)
    raise ValueError(coll)


def _group_oracle(coll: str, x, members, m: int):
    """Pure-numpy group collective: every group reduces/gathers over its
    own members only."""
    a = np.asarray(x.astype(jnp.float32))
    where = {d: (mem, r) for mem in members for r, d in enumerate(mem)}
    G = len(members[0])
    if coll == "allgather":
        return np.stack([np.concatenate(
            [a[j * m:(j + 1) * m] for j in where[d][0]]) for d in range(M)])
    if coll == "broadcast":
        return np.stack([a] * M)
    if coll == "scatter":
        return np.concatenate(
            [a[where[d][1] * m:(where[d][1] + 1) * m] for d in range(M)])
    if coll == "allreduce":
        return np.stack([a[where[d][0]].sum(0) for d in range(M)])
    if coll == "reduce_scatter":
        s = a.shape[1] // G
        return np.concatenate(
            [a[where[d][0]].sum(0)[where[d][1] * s:(where[d][1] + 1) * s]
             for d in range(M)])
    if coll == "alltoall":
        out = np.empty_like(a)
        for d in range(M):
            mem, r = where[d]
            for j in range(G):
                out[d, j] = a[mem[j], r]
        return out
    raise ValueError(coll)


@pytest.mark.parametrize("axes", GROUP_AXES, ids=GROUP_IDS)
@pytest.mark.parametrize("coll", sorted(runtime.collectives()))
def test_group_conformance_every_algorithm(coll, axes):
    g = COMM.split(axes=axes if len(axes) > 1 else axes[0])
    members = _group_members(axes)
    m = 3
    x = _group_operand(coll, g.topo.world, m, "float32")
    want = _group_oracle(coll, x, members, m)
    ref = np.asarray(g.invoke(coll, x, algo=REF[coll]).astype(jnp.float32))
    np.testing.assert_array_equal(
        ref, want, err_msg=f"group {axes} {coll}/{REF[coll]} vs oracle")
    for algo in autotune.candidates(coll, g.topo):
        got = np.asarray(g.invoke(coll, x, algo=algo).astype(jnp.float32))
        np.testing.assert_array_equal(
            got, ref, err_msg=f"group {axes} {coll}/{algo}")


@pytest.mark.parametrize("axes", GROUP_AXES, ids=GROUP_IDS)
def test_group_conformance_root_sweep(axes):
    g = COMM.split(axes=axes if len(axes) > 1 else axes[0])
    G = g.topo.world
    members = _group_members(axes)
    for coll in ("broadcast", "scatter"):
        x = _group_operand(coll, G, 4, "float32")
        want = _group_oracle(coll, x, members, 4)
        for root in sorted({0, G - 1}):
            got = np.asarray(
                g.invoke(coll, x, algo="pip_mcoll", root=root)
                .astype(jnp.float32))
            np.testing.assert_array_equal(
                got, want, err_msg=f"group {axes} {coll} root={root}")


@pytest.mark.parametrize("axes", GROUP_AXES, ids=GROUP_IDS)
def test_group_persistent_matches_blocking(axes):
    g = COMM.split(axes=axes if len(axes) > 1 else axes[0])
    x = _group_operand("allreduce", g.topo.world, 6, "float32")
    op = g.allreduce_init(x, algo="pip_mcoll")
    np.testing.assert_array_equal(
        np.asarray(op.start(x).wait()),
        np.asarray(g.allreduce(x, algo="pip_mcoll")))


@pytest.mark.parametrize("axes", GROUP_AXES, ids=GROUP_IDS)
def test_group_compressed_broadcast_in_bounds(axes):
    g = COMM.split(axes=axes if len(axes) > 1 else axes[0])
    m = 2 * compress.BLOCK + 5
    x = jax.random.normal(jax.random.PRNGKey(2), (m,), jnp.float32)
    got = np.asarray(g.broadcast(x, algo="pip_mcoll", codec="int8_block"))
    want = np.stack([np.asarray(x)] * M)
    tol = compress.collective_tolerance(
        "int8_block", "broadcast", g.topo.world, float(jnp.abs(x).max()))
    assert np.abs(got - want).max() <= tol + 1e-6


def test_group_split_of_split_matches_direct():
    """comm.split(...).split(...) lands on the same group semantics as the
    direct split (and the same memoized child when specs agree)."""
    direct = COMM.split(axes="local")
    nested = COMM.split(axes=("node", "local")).split(axes="local")
    x = _group_operand("allreduce", direct.topo.world, 4, "float32")
    np.testing.assert_array_equal(
        np.asarray(direct.allreduce(x, algo="pip_mcoll")),
        np.asarray(nested.allreduce(x, algo="pip_mcoll")))


def test_group_split_lattice_calibration_lands_measured_rows():
    """comm.calibrate(include_splits=True) walks the split lattice: every
    mesh-aligned group shape gets measured /g:-keyed tuning rows before
    first use, in the one shared selector table."""
    from repro.core import autotune as _autotune
    from repro.core.comm import Communicator as _Comm

    local = _Comm(mesh, topo, selector=_autotune.Selector(
        table=_autotune.TuningTable()))
    kids = local.split_lattice()
    active = tuple(topo.active_axes)
    want_groups = {"x".join(c) for c in
                   ([(a,) for a in active]
                    + ([tuple(active)] if len(active) > 1 else []))}
    assert {k.topo.group for k in kids} == want_groups
    rows = local.calibrate(include_splits=True, names=("allreduce",),
                           sizes=(256,), iters=1)
    assert {r.group for r in rows} == want_groups | {""}
    # every lattice child resolves auto from measurement, not the prior
    for k in kids:
        assert local.selector.table.lookup(
            k.topo, "allreduce", "float32", 256) is not None
        assert _autotune.topo_key(k.topo).endswith(f"/g:{k.topo.group}")


@pytest.mark.parametrize("coll", ("allreduce", "reduce_scatter"))
def test_conformance_compressed_multidim_payload(coll):
    """Compressed reductions accept trailing payload dims like their
    lossless forms ('(M*s, ...)' input), flattening row-major internally."""
    if coll == "allreduce":
        x = (jnp.arange(M * 10 * 3) % 5).astype(jnp.float32).reshape(
            M, 10, 3)
    else:
        x = (jnp.arange(M * M * 4 * 3) % 5).astype(jnp.float32).reshape(
            M, M * 4, 3)
    got = _run(coll, "pip_mcoll", x, codec="int8_block")
    ref = _run(coll, REF[coll], x)
    assert got.shape == ref.shape
    tol = compress.collective_tolerance("int8_block", coll, M,
                                        float(jnp.abs(x).max())) + 1e-6
    assert np.abs(got - ref).max() <= tol


# ---------------------------------------------------------------------------
# slow legs: dtype x odd-shape sweep, chunk-count sweep, auto-plan sweep
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("coll,algo", PAIRS)
@given(m=st.sampled_from([1, 3, 6, 7]), dtype=st.sampled_from(DTYPES))
@settings(max_examples=8, deadline=None)
def test_conformance_dtype_shape_sweep(coll, algo, m, dtype):
    _assert_conforms(coll, algo, m, dtype)


@pytest.mark.slow
@pytest.mark.parametrize("coll,algo", CHUNKED_PAIRS)
@given(m=st.sampled_from([1, 4, 7]), chunks=st.integers(1, 5))
@settings(max_examples=8, deadline=None)
def test_conformance_chunk_sweep(coll, algo, m, chunks):
    # chunk counts beyond the payload clamp internally; remainder segments
    # must round-trip exactly (zero padding never leaks into results)
    _assert_conforms(coll, algo, m, "float32", chunks=chunks)


@pytest.mark.slow
@pytest.mark.parametrize("coll", sorted(runtime.collectives()))
@given(m=st.sampled_from([1, 5, 64]), dtype=st.sampled_from(DTYPES))
@settings(max_examples=6, deadline=None)
def test_conformance_auto_plan(coll, m, dtype):
    """algo="auto" resolves an (algo, chunks) plan that conforms too."""
    x = _operand(coll, m, dtype)
    got = _run(coll, "auto", x)
    ref = _run(coll, REF[coll], x)
    np.testing.assert_array_equal(got, ref,
                                  err_msg=f"{coll}/auto m={m} {dtype}")


# ---------------------------------------------------------------------------
# pure-logic properties: chunk planning math (no devices involved)
# ---------------------------------------------------------------------------


@given(rounds=st.integers(2, 512), nbytes=st.integers(64, 1 << 26))
@settings(max_examples=60, deadline=None)
def test_optimal_pipeline_chunks_is_local_minimum(rounds, nbytes):
    """The analytic c* beats its integer neighbors under the stage model
    (C + B/c·beta)(rounds + c − 1)."""
    alpha, beta = 1.0e-6, 1 / 2.5e10
    c = costmodel.optimal_pipeline_chunks(alpha, nbytes, beta, rounds)
    t = costmodel.pipeline_time(alpha, nbytes, beta, rounds, c)
    assert 1 <= c <= costmodel.MAX_CHUNKS
    if c > 1:
        assert t <= costmodel.pipeline_time(alpha, nbytes, beta, rounds,
                                            c - 1) * (1 + 1e-12)
    if c < costmodel.MAX_CHUNKS:
        assert t <= costmodel.pipeline_time(alpha, nbytes, beta, rounds,
                                            c + 1) * (1 + 1e-12)


@given(nbytes=st.sampled_from([256, 4096, 1 << 16, 1 << 20, 1 << 24]))
@settings(max_examples=10, deadline=None)
def test_pipeline_crossover_vs_unchunked(nbytes):
    """The cost model must show the pipelining crossover: chunking never
    helps the latency regime, and wins the bandwidth regime."""
    t16 = Topology(16, 16, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    net = costmodel.net_for(t16)
    c = costmodel.optimal_chunks("allreduce", "pip_pipeline", t16, nbytes,
                                 net)
    t1 = costmodel.allreduce_cost("pip_pipeline", t16, nbytes, net,
                                  chunks=1).time
    tc = costmodel.allreduce_cost("pip_pipeline", t16, nbytes, net,
                                  chunks=c).time
    assert tc <= t1 * (1 + 1e-12)
    if nbytes >= 1 << 20:
        assert c > 1 and tc < t1, (nbytes, c)
    if nbytes <= 256:
        assert c == 1


def test_scatter_rejects_non_divisible_payload():
    """Regression: a payload that cannot shard evenly used to silently
    truncate (dim0 // world); it must be a clear error instead."""
    if M == 1:
        pytest.skip("every payload divides on 1 device")
    x = jnp.arange(float(M * 3 + 1))
    with pytest.raises(ValueError, match="divisible by world"):
        COMM.scatter(x, algo="pip_mcoll")


def test_plan_encode_decode_round_trip():
    assert autotune.encode_plan("pip_pipeline", 1) == "pip_pipeline"
    assert autotune.encode_plan("pip_pipeline", 8) == "pip_pipeline#c8"
    assert autotune.encode_plan("pip_pipeline", 8, "int8_block") == \
        "pip_pipeline#c8@int8_block"
    assert autotune.encode_plan("pip_mcoll", 1, "topk") == "pip_mcoll@topk"
    assert autotune.decode_plan("pip_pipeline#c8") == \
        ("pip_pipeline", 8, "none")
    assert autotune.decode_plan("pip_pipeline#c8@int8_block") == \
        ("pip_pipeline", 8, "int8_block")
    assert autotune.decode_plan("pip_mcoll@fp8_sim") == \
        ("pip_mcoll", 1, "fp8_sim")
    assert autotune.decode_plan("ring") == ("ring", 1, "none")


def test_plans_cover_registry_with_chunk_and_codec_variants():
    t = Topology(4, 4, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    for coll in runtime.collectives():
        ps = autotune.plans(coll, t, 1 << 20)
        algos = {a for a, _, _ in ps}
        assert algos == set(autotune.candidates(coll, t))
        for a, c, cd in ps:
            assert c >= 1
            if c > 1:
                assert mcoll.supports_chunks(coll, a)
            if cd != "none":
                assert mcoll.supports_codec(coll, a)
        # every chunk-capable algorithm gets at least one chunked variant
        # at a bandwidth-regime size
        for a in algos:
            if mcoll.supports_chunks(coll, a):
                assert any(c > 1 for aa, c, _ in ps if aa == a), (coll, a)
        # every codec-capable algorithm gets every lossy codec variant
        for a in algos:
            if mcoll.supports_codec(coll, a):
                planned = {cd for aa, _, cd in ps if aa == a}
                assert set(compress.lossy()) <= planned, (coll, a)
