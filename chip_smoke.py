"""Bring the main path up on a TPU and check what comes out.

  python chip_smoke.py             one chip, one process:
      (a) every collective x algorithm of core/mcoll.py through a
          Communicator on a (1, 1) ("node", "local") mesh at 16 B, 512 B
          and a 4 MiB gradient bucket, bitwise against the collective's
          xla entry, plus one persistent allreduce init -> start -> wait;
      (b) the fused codec kernels, compiled, on a 4 MiB f32 bucket and on
          a shape whose rows end in a partial tile, against the jnp
          reference paths within the codec's collective tolerance;
      (c) smollm-360m at full width: steps through the overlapped
          data-parallel train step and through ``repro.launch.train``;
      (d) serving the same model through ``serve.Engine``.
  python chip_smoke.py --chips 4   four chips, one process, and nothing
      else: (a) on a (2, 2) mesh, a compressed allreduce per fused codec,
      and one data-parallel smollm-360m step against the single-jit step,
      compared on the synced gradient (Adam's new first moment) as well
      as the loss and the updated params.

Progress and set-up times (compilation included; they are not speed) go
to stdout. The last line is one JSON object naming the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, without that line, when JAX finds no TPU or
any check fails. The compile cache goes where ``repro.launch.cache``
puts it (``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "smollm-360m"
SIZES = (16, 512, 4 << 20)          # collective payload bytes per device
BUCKET = 4 << 20                    # the gradient bucket of (a) and (b)
CODEC_SLICES = 4                    # (b): the bucket as 4 wire slices
CODEC_EDGE = (3, 46000)             # (b): 540 block rows, a partial tile
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_LR = 5, 4, 512, 3e-3
TRAIN_BUCKET_BYTES = 128 << 20      # overlapped-step segments (~3 layers)
LOSS_RTOL = 5e-3                    # step-0 loss, overlapped vs single jit
SERVE_PROMPTS = (16, 40) * 4        # 8 requests of mixed prompt length
SERVE_NEW, SERVE_BATCH, SERVE_MAX_LEN = 16, 8, 64
SERVE_TIE_ULPS = 4                  # engine vs recompute logit near-tie
DP_LR = 1e-3                        # four-chip step: constant lr, no warmup
# per-leaf |m_dp - m_jit| / |m_jit|: 5x the v5e reading (2.47e-2 on
# attn.wq; 2 layers at d_model 128 on the CPU read 1.8e-3), 8x under the
# weakest planted fault (0.978)
DP_GRAD_RTOL = 2.0 ** -3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def timed(name: str, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    log(f"phase {name}: passed in {time.perf_counter() - t0:.1f} s "
        f"(set-up, compilation included) {json.dumps(out)}")
    return out


def peak_bytes(devices):
    return [d.memory_stats().get("peak_bytes_in_use", 0) for d in devices]


# ---------------------------------------------------------------------------
# (a) collectives
# ---------------------------------------------------------------------------


def phase_collectives(mesh):
    import numpy as np
    from repro.core import autotune, mcoll, runtime
    from repro.core.comm import Communicator
    from repro.core.topology import Topology

    topo = Topology.from_mesh(mesh)
    comm = Communicator(mesh, topo)
    n_dev = mesh.devices.size
    checked, infeasible = 0, []
    for name in runtime.collectives():
        feasible = autotune.candidates(name, topo)
        for nbytes in SIZES:
            x = runtime.example_input(name, topo, nbytes, devices=n_dev)
            # scatter registers no xla entry: its result is the operand
            want = (np.asarray(comm.invoke(name, x, algo="xla"))
                    if "xla" in mcoll.algorithms(name) else np.asarray(x))
            for algo in mcoll.algorithms(name):
                if algo not in feasible:
                    infeasible.append(f"{name}/{algo}")
                    continue
                got = np.asarray(comm.invoke(name, x, algo=algo))
                if got.shape != want.shape or not np.array_equal(got, want):
                    raise AssertionError(f"{name}/{algo} at {nbytes} B "
                                         f"differs from the reference")
                checked += 1
    z = runtime.example_input("allreduce", topo, BUCKET, devices=n_dev)
    op = comm.allreduce_init(z, algo="auto")
    got = np.asarray(op.start(z).wait())
    if not np.array_equal(got, np.asarray(comm.allreduce(z, algo="xla"))):
        raise AssertionError(f"persistent allreduce ({op.plan}) differs")
    return {"mesh": list(mesh.devices.shape), "checked": checked,
            "infeasible": sorted(set(infeasible)), "persistent": op.plan}


def phase_compressed_allreduce(mesh):
    import jax
    import numpy as np
    from repro.core import compress
    from repro.core.comm import Communicator
    from repro.core.topology import Topology

    topo = Topology.from_mesh(mesh)
    comm = Communicator(mesh, topo)
    world = topo.world
    x = jax.random.normal(jax.random.PRNGKey(3), (world, BUCKET // 4)) * 0.01
    want = np.asarray(comm.allreduce(x, algo="xla"))
    A = float(np.abs(np.asarray(x)).max())
    out = {}
    for codec in compress.fused_codecs():
        tol = compress.collective_tolerance(codec, "allreduce", world, A)
        got = np.asarray(comm.allreduce(x, algo="pip_mcoll", codec=codec))
        err = float(np.abs(got - want).max())
        if not err <= tol:
            raise AssertionError(f"{codec} allreduce error {err} > {tol}")
        out[codec] = {"max_err": err, "tol": tol}
    return out


# ---------------------------------------------------------------------------
# (b) fused codec kernels
# ---------------------------------------------------------------------------


def phase_codecs():
    """Every fused codec on the 4 MiB bucket as CODEC_SLICES wire slices,
    and on CODEC_EDGE, whose rows end in a partial tile."""
    import jax
    import numpy as np
    from repro.core import compress
    from repro.kernels import codec as ckern
    from repro.kernels import ops

    out = {"interpret": ops.interpret()}
    for S, L in ((CODEC_SLICES, BUCKET // 4 // CODEC_SLICES), CODEC_EDGE):
        k1, k2 = jax.random.split(jax.random.PRNGKey(L))
        x = jax.random.normal(k1, (S, L))
        err = jax.random.normal(k2, (S, L)) * 0.01
        A = float(np.abs(np.asarray(x + err)).max())
        for name in ckern.fused_codec_names():
            out[f"{name}/{S}x{L}"] = _codec_check(name, x, err, A)
    return out


def _codec_check(name, x, err, A):
    import jax
    import numpy as np
    from repro.core import compress
    from repro.kernels import codec as ckern

    S, L = x.shape
    cd = compress.codec(name)

    def paths():
        comp, res = jax.jit(cd.encode_with_feedback)(x, err)
        comp_r, res_r = jax.jit(cd.encode_residual)(x)
        red = jax.jit(lambda c: cd.decode_reduce(c, L))(comp)
        return comp, res, res_r, red, comp_r

    def ef_gap(p):
        """How far each residual is from payload - decode(its wire), the
        error-feedback invariant, with every op run on its own."""
        return max(float(np.abs(np.asarray(r - (c - cd.decode(w, L)))).max())
                   for r, c, w in ((p[1], x + err, p[0]), (p[2], x, p[4])))

    got = paths()
    with compress.jnp_reference_paths():
        ref = paths()
    # two valid encodings each decode within eps*A of the payload
    tol = 2 * compress.collective_tolerance(name, "allgather", 1, A)
    red_tol = compress.collective_tolerance(name, "reduce_scatter", S, A)
    dec = [np.asarray(cd.decode(p[0], L)) for p in (got, ref)]
    errs = {"decode": float(np.abs(dec[0] - dec[1]).max()),
            "feedback": float(np.abs(np.asarray(got[1] - ref[1])).max()),
            "residual": float(np.abs(np.asarray(got[2] - ref[2])).max()),
            "decode_reduce": float(np.abs(np.asarray(got[3] - ref[3])).max())}
    bitwise = all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree.leaves(got[0]),
                                  jax.tree.leaves(ref[0])))
    for k, v in errs.items():
        if not v <= (red_tol if k == "decode_reduce" else tol):
            raise AssertionError(f"{name} {S}x{L} {k}: {v} over tolerance")
    # the kernels' residuals hold to a few f32 ulps of the payload
    gap, gap_tol = ef_gap(got), 2.0 ** -20 * A
    if not gap <= gap_tol:
        raise AssertionError(f"{name} {S}x{L}: fused residual is {gap} "
                             f"from payload - decode(wire) (> {gap_tol})")
    if ckern.lowering(name) is None or got[3].shape != (L,):
        raise AssertionError(f"{name}: no fused lowering ran")
    return dict(errs, wire_bitwise=bitwise, tol=tol, reduce_tol=red_tol,
                ef_gap=gap, jnp_ef_gap=ef_gap(ref))


# ---------------------------------------------------------------------------
# (c) training
# ---------------------------------------------------------------------------


def _losses_ok(name, losses):
    import math
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{name}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall {losses}")


def phase_train(mesh, cfg):
    """The overlapped step and ``repro.launch.train.main`` on the same
    model, data and optimizer settings (``launch.train``'s own)."""
    import gc

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.topology import Topology
    from repro.data.pipeline import SyntheticLM
    from repro.launch import cache as compile_cache
    from repro.launch import train as launch_train
    from repro.models import decoder
    from repro.models.decoder import RunFlags
    from repro.optim import adamw
    from repro.train import manual_step
    from repro.train.step import TrainConfig

    ocfg = adamw.AdamWConfig(lr=TRAIN_LR, warmup_steps=20,
                             total_steps=TRAIN_STEPS)
    tcfg = TrainConfig(optimizer=ocfg, flags=RunFlags(remat="none"))
    # placed as the step's outputs are, so step 1 reuses step 0's programs
    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    params = jax.device_put(decoder.init(jax.random.PRNGKey(0), cfg), rep)
    opt = jax.device_put(adamw.init(params, ocfg), rep)
    step = manual_step.make_overlapped_train_step(
        cfg, tcfg, mesh, Topology.from_mesh(mesh),
        bucket_bytes=TRAIN_BUCKET_BYTES)
    data = SyntheticLM(cfg.vocab, TRAIN_SEQ, TRAIN_BATCH)
    overlapped, times, compiles = [], [], []
    for s in range(TRAIN_STEPS):
        b = jax.device_put(data.batch(s), dat)
        n0 = compile_cache.stats()["requests"]
        t0 = time.perf_counter()
        params, opt, m = step(params, opt, b)
        overlapped.append(float(m["loss"]))
        times.append(time.perf_counter() - t0)
        compiles.append(compile_cache.stats()["requests"] - n0)
    mode, segments = step.mode, len(step.bounds)
    del params, opt, step, m
    gc.collect()
    single = launch_train.main(
        ["--arch", cfg.name, "--steps", str(TRAIN_STEPS),
         "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
         "--lr", str(TRAIN_LR), "--log-every", "1"])
    _losses_ok("overlapped", overlapped)
    _losses_ok("launch.train", single)
    rel = abs(overlapped[0] - single[0]) / abs(single[0])
    if not rel <= LOSS_RTOL:
        raise AssertionError(f"step-0 losses differ by {rel:.2e} relative "
                             f"(> {LOSS_RTOL}): {overlapped[0]} vs "
                             f"{single[0]}")
    return {"batch": TRAIN_BATCH, "seq": TRAIN_SEQ, "mode": mode,
            "segments": segments,
            "overlapped_losses": overlapped, "single_jit_losses": single,
            "step0_rel_diff": rel, "overlapped_step_s": times,
            "overlapped_compiles": compiles}


def _grad_rel(m, ref):
    """Per-leaf L2 ``|m - ref| / |ref|``: the largest, and its leaf."""
    import jax
    import jax.numpy as jnp

    rel = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (jax.tree_util.keystr(path), float(
            jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))),
        m, ref)
    leaf, worst = max(jax.tree.leaves(rel, is_leaf=lambda t: isinstance(
        t, tuple)), key=lambda t: t[1])
    return worst, leaf


def _grad_rel_by_layer(m, ref):
    """For each layer of the stacked blocks, the largest relative L2 gap
    over the leaves under ``groups``."""
    import jax
    import jax.numpy as jnp

    def per_layer(a, b):
        axes = tuple(range(1, a.ndim))
        return (jnp.sqrt(jnp.sum((a - b) ** 2, axes))
                / jnp.maximum(jnp.sqrt(jnp.sum(b ** 2, axes)), 1e-30))

    rel = jnp.stack([per_layer(a, b) for a, b in zip(
        jax.tree.leaves(m["groups"]), jax.tree.leaves(ref["groups"]))])
    return [float(v) for v in rel.max(axis=0)]


def phase_dp_step(mesh, cfg):
    """One data-parallel overlapped step against the single-jit step on
    the same mesh: the same loss, updated params within Adam's step, and
    the same synced gradient. The gradient is read off Adam's new first
    moment, ``(1 - b1) * g`` at the first step with clipping off; each
    leaf must agree within DP_GRAD_RTOL relative norm. The gradients a
    broken sync would give (chip 0's shard alone, half the batch) are run
    through the single-jit step and must fail that check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.topology import Topology
    from repro.data.pipeline import SyntheticLM
    from repro.models import decoder
    from repro.models.decoder import RunFlags
    from repro.optim import adamw
    from repro.train import manual_step
    from repro.train.step import TrainConfig, train_step

    ocfg = adamw.AdamWConfig(lr=DP_LR, warmup_steps=0, total_steps=1,
                             schedule="constant", grad_clip=1e9)
    tcfg = TrainConfig(optimizer=ocfg, flags=RunFlags(remat="none"))
    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    world = mesh.devices.size
    rows = 2 * world
    # built in place on every chip, so nothing sits on chip 0 alone
    init = jax.jit(lambda k: decoder.init(k, cfg), out_shardings=rep)
    init_opt = jax.jit(lambda p: adamw.init(p, ocfg), out_shardings=rep)
    data = SyntheticLM(cfg.vocab, TRAIN_SEQ, rows).batch(0)

    def place(n):
        """The batch's first ``n`` rows, repeated to the full batch."""
        return {k: jax.device_put(np.concatenate([v[:n]] * (rows // n)), dat)
                for k, v in data.items()}

    jit_step = jax.jit(lambda p, o, b: train_step(p, o, b, cfg, tcfg),
                       in_shardings=(rep, rep, dat),
                       out_shardings=(rep, rep, None), donate_argnums=(1,))
    params = init(jax.random.PRNGKey(0))
    p0 = jax.tree.map(lambda a: float(jnp.abs(a.astype(jnp.float32)).max()),
                      params)
    jit_p, jit_o, jit_m = jit_step(params, init_opt(params), place(rows))
    jit_loss, ref_m = float(jit_m["loss"]), jit_o["m"]
    del jit_o

    step = manual_step.make_overlapped_train_step(
        cfg, tcfg, mesh, Topology.from_mesh(mesh),
        bucket_bytes=TRAIN_BUCKET_BYTES)
    ov_p, ov_o, ov_m = step(params, init_opt(params), place(rows))
    ov_loss = float(ov_m["loss"])
    grad_rel, grad_leaf = _grad_rel(ov_o["m"], ref_m)
    by_layer = _grad_rel_by_layer(ov_o["m"], ref_m)
    del step, ov_o, params
    rel = abs(ov_loss - jit_loss) / abs(jit_loss)
    if not (np.isfinite(ov_loss) and rel <= LOSS_RTOL):
        raise AssertionError(f"DP loss {ov_loss} vs single jit {jit_loss}")
    if not grad_rel <= DP_GRAD_RTOL:
        raise AssertionError(f"synced gradient differs: {grad_leaf} at "
                             f"{grad_rel:.3e} relative (> {DP_GRAD_RTOL})")
    # Adam moves an element by at most ~lr, so two correct updates differ
    # by <= 2 lr plus half a bf16 ulp of the leaf's magnitude
    leaf_tol = [2 * DP_LR + 2.0 ** -8 * m for m in jax.tree.leaves(p0)]
    diffs = [float(jnp.abs(a.astype(jnp.float32)
                           - b.astype(jnp.float32)).max())
             for a, b in zip(jax.tree.leaves(ov_p), jax.tree.leaves(jit_p))]
    del ov_p, jit_p
    bad = [(d, t) for d, t in zip(diffs, leaf_tol) if not d <= t]
    if bad:
        raise AssertionError(f"updated params differ: {bad[:4]}")
    faults = {}
    for name, n in (("chip0_shard_only", rows // world),
                    ("half_batch", rows // 2)):
        params = init(jax.random.PRNGKey(0))
        _, fo, _ = jit_step(params, init_opt(params), place(n))
        faults[name] = _grad_rel(fo["m"], ref_m)[0]
        del fo
        if faults[name] <= DP_GRAD_RTOL:
            raise AssertionError(f"the gradient check passes a broken sync "
                                 f"({name}: {faults[name]:.3e})")
    return {"overlapped_loss": ov_loss, "single_jit_loss": jit_loss,
            "loss_rel_diff": rel, "max_param_diff": max(diffs),
            "params_bitwise": max(diffs) == 0.0, "grad_rel": grad_rel,
            "grad_rel_leaf": grad_leaf, "grad_rtol": DP_GRAD_RTOL,
            "grad_rel_by_layer": by_layer,
            "planted_fault_grad_rel": faults}


# ---------------------------------------------------------------------------
# (d) serving
# ---------------------------------------------------------------------------


def phase_serve(cfg):
    import jax
    import numpy as np
    from repro.models import decoder
    from repro.serve.engine import Engine, Request

    params = decoder.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=SERVE_NEW) for n in SERVE_PROMPTS]
    eng = Engine(params, cfg, max_batch=SERVE_BATCH, max_len=SERVE_MAX_LEN)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    if len(done) != len(reqs) or any(len(r.out_tokens) != SERVE_NEW
                                     for r in done):
        raise AssertionError("not every request got its tokens")
    # no-cache greedy recompute of every request, teacher-forced on the
    # engine's tokens: position i's argmax is the engine's token i, or a
    # near-tie. The logits are bf16 and the cached decode sums in another
    # order than the full forward, so two logits within SERVE_TIE_ULPS bf16
    # ulps of the top one are a tie either path may break; a wrong cache
    # row or position puts the engine's token far below the top.
    forward = jax.jit(lambda p, t: decoder.forward(p, t, cfg)[0])
    exact = ties = 0
    for n, r in enumerate(done):
        seq = np.concatenate([r.prompt, r.out_tokens[:-1]]).astype(np.int32)
        lg = np.asarray(forward(params, seq[None])[0, len(r.prompt) - 1:],
                        np.float32)
        for i, tok in enumerate(r.out_tokens):
            top = lg[i].max()
            ulp = 2.0 ** (np.floor(np.log2(max(abs(top), 2.0 ** -126))) - 7)
            gap = top - lg[i, tok]
            if gap > SERVE_TIE_ULPS * ulp:
                raise AssertionError(
                    f"request {n} token {i}: engine {tok} is {gap} below "
                    f"the recompute's top logit {top} (> {SERVE_TIE_ULPS} "
                    f"bf16 ulps)")
            exact += int(gap == 0)
            ties += int(gap > 0)
    if not exact >= ties:
        raise AssertionError(f"only {exact} of {exact + ties} tokens are "
                             f"the recompute's argmax")
    return {"requests": len(done), "new_tokens": SERVE_NEW,
            "prompt_lengths": sorted(set(SERVE_PROMPTS)),
            "exact_tokens": exact, "near_ties": ties, "serve_wall_s": wall,
            "ticks": eng.metrics()["ticks"]}


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro.launch import cache as compile_cache
        from repro.launch.mesh import make_mesh
    except ImportError as e:
        fail(f"the repro package is not beside this script: {e}")
    import jax

    cache_dir = compile_cache.enable()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    dev0 = devices[0]
    if dev0.platform != "tpu":
        fail(f"no TPU found: JAX's devices are {dev0.platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPUs, found "
             f"{len(devices)}")
    log(f"{len(devices)} x {dev0.device_kind}; compile cache {cache_dir}")

    from repro.configs import get_config
    cfg = get_config(ARCH)
    if args.chips == 1:
        mesh = make_mesh((1, 1), ("node", "local"), devices=devices[:1])
        timed("a/collectives", phase_collectives, mesh)
        timed("b/codecs", phase_codecs)
        timed("c/train", phase_train, mesh, cfg)
        timed("d/serve", phase_serve, cfg)
        log(f"peak_bytes_in_use {peak_bytes(devices[:1])}")
    else:
        mesh = make_mesh((2, 2), ("node", "local"), devices=devices[:4])
        timed("a/collectives", phase_collectives, mesh)
        timed("a/compressed_allreduce", phase_compressed_allreduce, mesh)
        timed("dp_step", phase_dp_step, mesh, cfg)
        peaks = peak_bytes(devices[:4])
        log(f"peak_bytes_in_use per chip {peaks}")
        if max(peaks) > 1.25 * min(peaks):
            fail(f"unbalanced peak memory across chips: {peaks}")
    log(f"compile cache {json.dumps(compile_cache.stats())}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    try:
        main()
    except AssertionError as e:
        fail(str(e))
