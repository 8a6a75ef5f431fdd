"""One run of a training cell: set-up, the checked first steps, the
measured window, the trace's reduction and the comparison with the
reference.

The system under test is the program's training step, built as its own
entry points build it; everything else here (weights, batches, the
reference, the reduction of the trace) belongs to the benchmark.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.chip import cells, check, data, flops, peaks, trace, weights

#: the reference's head runs over blocks of rows of at most this many
#: f32 logits' bytes
HEAD_BLOCK_BYTES = 1 << 28
TRACE_DIR = cells.ROOT / ".bench_out" / "trace"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class CompileCounter:
    """Compile requests and backend compilations in this process."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == self.EVENTS[0]:
            self.count += 1

    def _duration(self, name, _secs, **_):
        if name == self.EVENTS[1]:
            self.count += 1


def find_devices(chips: int, require_tpu: bool = True) -> List:
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no device: {e}") from None
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="decoder",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or 0,
        qkv_bias=bool(cfg.get("qkv_bias")),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings")))


def optimizer(traffic: dict):
    from repro.optim import adamw
    o = traffic["optimizer"]
    return adamw.AdamWConfig(
        lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
        warmup_steps=o["warmup_steps"], schedule=o["schedule"])


class Trainer:
    """The program's step for a cell, with the state's placement and the
    benchmark's readers of that state."""

    def __init__(self, cell: cells.Cell, devices: List):
        from repro.launch.mesh import make_mesh
        from repro.models.decoder import RunFlags
        from repro.optim import adamw
        from repro.train.step import TrainConfig, train_step

        cfg, tr = cell.config, cell.traffic
        self.traffic = tr
        self.ocfg = ocfg = optimizer(tr)
        self.mcfg = mcfg = model_config(cfg)
        self.tcfg = tcfg = TrainConfig(
            optimizer=ocfg, z_loss=float(tr["z_loss"]),
            flags=RunFlags(remat=tr["remat"]))
        axes = tuple(tr["mesh"]["axes"])
        self.mesh = make_mesh(tuple(tr["mesh"]["shape"]), axes,
                              devices=devices)
        self.rep = NamedSharding(self.mesh, P())
        self.dat = NamedSharding(self.mesh, P(axes))
        self.rows = int(tr["batch_per_chip"]) * len(devices)
        self.names = names = sorted(weights.names(cfg))
        if tr["step"] == "single_jit":
            # as launch.train builds it
            self.step = jax.jit(
                lambda p, o, b: train_step(p, o, b, mcfg, tcfg),
                donate_argnums=(0, 1))
        elif tr["step"] == "overlapped":
            from repro.core.topology import Topology
            from repro.train import manual_step
            self.step = manual_step.make_overlapped_train_step(
                mcfg, tcfg, self.mesh,
                Topology.from_mesh(self.mesh, axes[0], axes[1]),
                algo=tr.get("algo", "auto"), codec=tr.get("codec"),
                bucket_bytes=int(tr["bucket_bytes"]))
        else:
            raise ValueError(f"unknown step {tr['step']!r}")
        self.init = jax.jit(
            lambda k: weights.to_program(weights.init(
                jax.random.wrap_key_data(k), cfg)),
            out_shardings=self.rep)
        self.opt_init = jax.jit(lambda p: adamw.init(p, ocfg),
                                out_shardings=self.rep)

        def norms(tree):
            t = weights.from_program(tree)
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                t[n].astype(jnp.float32)))) for n in names])

        self.replica_norms = self._per_replica(norms)
        self._leaf_change = self._per_replica(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32))))[None])

    def replica_change(self, params, start: Dict[str, np.ndarray]):
        """Per-leaf norms of every replica's change from ``start`` (the
        starting weights, kept on the host), a leaf at a time so that no
        second copy of the weights is held on the chips."""
        now = weights.from_program(params)
        return np.concatenate(
            [np.asarray(self._leaf_change(
                now[n], jax.device_put(start[n], self.rep)))
             for n in self.names], axis=1)

    def _per_replica(self, fn: Callable) -> Callable:
        """``fn`` run on every chip's replica of replicated inputs; the
        result is stacked over chips."""
        axes = self.mesh.axis_names
        body = lambda *a: fn(*a)[None]
        return jax.jit(jax.shard_map(body, mesh=self.mesh, in_specs=P(),
                                     out_specs=P(axes), check_vma=False))

    def put(self, batch: Dict[str, np.ndarray]):
        return {k: jax.device_put(v, self.dat) for k, v in batch.items()}


def _row_block(rows: int, seq: int, vocab: int) -> int:
    best = 1
    for b in range(1, rows + 1):
        if rows % b == 0 and b * seq * vocab * 4 <= HEAD_BLOCK_BYTES:
            best = b
    return best


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    summary: Optional[trace.Summary]
    steps: int
    flops_per_step: float
    chips: int
    peak_flops: float


def measure(tm: Trainer, params, opt, pool: List[dict], seconds: float):
    """The measured window: steps through the program's step and feed
    until ``seconds`` have passed, each ending when its loss is on the
    host. Returns the state, the window's start, each step's end and
    each step's loss."""
    stamps, losses = [], []
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("data"):
                b = tm.put(pool[len(stamps) % len(pool)])
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt, m = tm.step(params, opt, b)
            with jax.profiler.TraceAnnotation("loss_fetch"):
                losses.append(float(m["loss"]))
            stamps.append(time.perf_counter())
            if stamps[-1] - t0 >= seconds:
                return params, opt, t0, stamps, losses


def run(cell: cells.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, require_tpu: bool = True,
        plant: Optional[Callable[[Trainer], None]] = None) -> dict:
    """One run; returns the result line's object. ``--trace 1`` runs
    measure the traffic's ``trace_seconds`` (at most ``seconds``) under
    the profiler and report the per-layer metrics instead of the
    end-to-end ones."""
    devices = find_devices(cell.chips, require_tpu)
    kind = devices[0].device_kind
    log(f"devices found: {time.perf_counter() - t_start:.1f} s")
    compiles = CompileCounter()
    cfg, tr = cell.config, cell.traffic
    tm = Trainer(cell, devices)
    if plant is not None:
        plant(tm)
    log(f"{cell.name}: {len(devices)} x {kind}, {tr['step']} step, "
        f"global batch {tm.rows} x {tr['seq_len']}, seed {seed}; "
        f"{time.perf_counter() - t_start:.1f} s")

    kd = np.asarray(jax.random.key_data(weights.key_from_seed(seed)))
    stream = data.TokenStream(cfg["vocab_size"], tr["seq_len"], seed,
                              tr.get("data"))
    n_check = int(tr["check_steps"])
    checked = [stream.batch(s, tm.rows) for s in range(n_check)]
    pool = [stream.batch(n_check + i, tm.rows)
            for i in range(int(tr["pool_batches"]))]
    params, opt, prog = checked_steps(tm, kd, checked)
    log(f"checked steps' losses {prog['loss']}; compile requests so far "
        f"{compiles.count}; {time.perf_counter() - t_start:.1f} s")
    # the window's own shapes once more, so that nothing compiles inside
    params, opt, m = tm.step(params, opt, tm.put(pool[0]))
    float(m["loss"])
    del m

    if traced:
        seconds = min(seconds, float(tr.get("trace_seconds") or seconds))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the harness's own spans suffice
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    before = compiles.count
    params, opt, t0, stamps, losses = measure(tm, params, opt, pool, seconds)
    if traced:
        jax.profiler.stop_trace()
    inside = compiles.count - before
    n = len(stamps)
    log(f"window: {n} steps in {stamps[-1] - t0:.3f} s; compile requests "
        f"inside {inside}; set-up {t0 - t_start:.3f} s")
    per_step = np.diff([t0] + stamps) * 1e3
    log(f"steps by the host clock (a diagnostic, not a metric): median "
        f"{np.median(per_step):.3f} ms, p90 "
        f"{np.percentile(per_step, 90):.3f} ms, longest "
        f"{per_step.max():.3f} ms at step {per_step.argmax()}")
    if inside:
        raise RuntimeError(f"{inside} compilations inside the window")
    stats = [d.memory_stats() or {} for d in devices]
    mem = [st.get("peak_bytes_in_use", 0) for st in stats]
    log(f"peak_bytes_in_use per chip {mem}")
    # free the program's state before the reference runs
    del params, opt
    tm.step = None
    gc.collect()

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(max(mem))}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if not traced:
        vals = {"step_ms": (stamps[-1] - t0) / n * 1e3,
                "setup_s": t0 - t_start}
        metrics = {m_["name"]: {"value": vals[m_["name"]],
                                "unit": m_["unit"]}
                   for m_ in cell.end_to_end}
    else:
        summary = trace.summarize(trace.load(trace.find_xplane(
            str(TRACE_DIR))))
        ctx = Context(summary, n,
                      flops.train_step_flops(cfg, tm.rows, tr["seq_len"]),
                      len(devices), peaks.peaks(kind).flops_bf16
                      if require_tpu else 0.0)
        for m_ in cell.per_layer:
            v = cell.readers[m_["name"]].read(ctx)
            if v is not None:
                metrics[m_["name"]] = {"value": float(v), "unit": m_["unit"]}
        if summary is not None:
            device["busy_s"] = (sum(c.busy for c in summary.chips)
                                / len(summary.chips) * 1e-9)
            device["window_s"] = summary.window_ns * 1e-9
            breakdown = trace.breakdown(summary)

    t_ref = time.perf_counter()
    ref = Reference(cell, tm.rows)(kd, checked, devices[0])
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    ok, checks = check.decide(check.gaps(prog, ref), cell.limits)
    result = {"correct": ok, "attempted": n,
              "failed": int(sum(1 for x in losses if not np.isfinite(x))),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def checked_steps(tm: Trainer, kd: np.ndarray, checked: List[dict]):
    """Fresh weights from the seed's key data ``kd``, then the checked
    steps through the program's step and feed. Returns the state after
    them and the program's readings: each step's loss, every replica's
    per-leaf norms of the first gradient (Adam's first moment after step
    1, over 1 - b1) and of the parameters' change over the steps."""
    params = tm.init(kd)
    start = jax.device_get(weights.from_program(params))
    opt = tm.opt_init(params)
    losses = []
    for s, b in enumerate(checked):
        params, opt, m = tm.step(params, opt, tm.put(b))
        losses.append(float(m["loss"]))
        if s == 0:
            grad_norm = (np.asarray(tm.replica_norms(opt["m"]))
                         / (1.0 - tm.ocfg.b1))
    change_norm = tm.replica_change(params, start)
    return params, opt, {"loss": losses, "grad_norm": grad_norm,
                         "change_norm": change_norm}


class Reference:
    """The reference that the cell's configuration names, built for
    the cell, run on one chip from the seed's weights and the checked
    batches."""

    def __init__(self, cell: cells.Cell, rows: int, precision: str = "f32"):
        cfg, tr = cell.config, cell.traffic
        self.ref = cell.reference.Reference(
            cfg, tr["optimizer"], float(tr["z_loss"]), precision,
            _row_block(rows, tr["seq_len"], cfg["vocab_size"]))
        self._init = jax.jit(
            lambda k: weights.init(jax.random.wrap_key_data(k), cfg))

    def __call__(self, kd: np.ndarray, checked: List[dict], device) -> dict:
        return self.ref.train(self._init(jax.device_put(kd, device)),
                              checked, device)
