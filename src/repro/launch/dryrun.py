import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract memory/cost/roofline artifacts. MUST be the only entry point that
forces 512 host devices (smoke tests and benches see 1 device).

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl   (resumable)
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import mesh as mesh_lib
from repro.launch import specs
from repro.models.decoder import RunFlags
from repro.roofline import hlo as hlo_lib
from repro.roofline import terms as terms_lib
from repro.train.step import TrainConfig


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             flags: RunFlags = None, tcfg: TrainConfig = None,
             keep_text: bool = False) -> dict:
    if tcfg is None and flags is not None:
        tcfg = TrainConfig(flags=flags)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}
    t0 = time.time()
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rules = mesh_lib.default_rules(mesh, shape.kind, shape.global_batch,
                                   shape.seq_len,
                                   param_bytes=cfg.n_params() * 2.0)
    flags = flags or RunFlags()
    with mesh:
        jitted, args = specs.build_cell(cfg, shape, mesh, rules, tcfg=tcfg,
                                        flags=flags)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    # attention score tiles are VMEM-resident on the TPU target (Pallas
    # flash kernel); exclude them from HBM traffic, then add back the
    # kernel's true streamed K/V traffic analytically.
    costs = hlo_lib.analyze(text, vmem_tile=(flags.q_chunk, flags.kv_chunk,
                                             cfg.head_dim))
    # analytic Pallas-flash streaming traffic, kept as a cross-check against
    # the HLO-derived memory term (the score-tile VMEM exclusion above means
    # K/V streaming enters through operand accounting of the tile dots)
    flash_hbm = terms_lib.flash_hbm_traffic(cfg, shape, mesh, flags)
    chips = mesh.devices.size
    mf = terms_lib.model_flops(cfg, shape)
    mfa = terms_lib.model_flops_attn(cfg, shape)
    link_bw = (terms_lib.DCN_BW if multi_pod
               else terms_lib.peaks(terms_lib.V5E).ici_bw)
    terms = terms_lib.compute_terms(costs.flops, costs.memory_bytes,
                                    costs.collective_bytes, chips, mf + mfa,
                                    costs.collective_counts, link_bw)
    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok",
        "mesh": {"shape": list(mesh.devices.shape),
                 "axes": list(mesh.axis_names)},
        "rules": {"batch": rules.batch, "fsdp": rules.fsdp, "tp": rules.tp,
                  "seq": rules.seq},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "xla_cost": {k: cost.get(k) for k in ("flops", "bytes accessed")
                     if k in cost},
        "hlo": {
            "flops_per_dev": costs.flops,
            "bytes_per_dev": costs.memory_bytes,
            "collective_bytes_per_dev": costs.collective_bytes,
            "collective_counts": costs.collective_counts,
            "collective_bytes_by_op": costs.collective_bytes_by_op,
        },
        "roofline": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "bottleneck": terms.bottleneck,
            "model_flops": mf,
            "model_flops_attn": mfa,
            "flash_hbm_bytes": flash_hbm,
            "useful_ratio": terms.useful_ratio,
            "step_lower_bound_s": terms.total_s(),
            "roofline_fraction": terms.roofline_fraction(),
        },
    }
    if keep_text:
        rec["hlo_text"] = text
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--q-chunk", type=int, default=512)
    ap.add_argument("--kv-chunk", type=int, default=1024)
    args = ap.parse_args()

    flags = RunFlags(remat=args.remat, q_chunk=args.q_chunk,
                     kv_chunk=args.kv_chunk)
    tcfg = TrainConfig(flags=flags, microbatches=args.microbatches)

    cells = []
    if args.all:
        pods = [False, True]
        if args.single_pod_only:
            pods = [False]
        if args.multi_pod_only:
            pods = [True]
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for mp in pods:
                    cells.append((arch, shape, mp))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, args.multi_pod)]

    done = set()
    out_path = pathlib.Path(args.out) if args.out else None
    if out_path and out_path.exists():
        for line in out_path.read_text().splitlines():
            try:
                r = json.loads(line)
                if r.get("status") in ("ok", "skipped"):
                    done.add((r["arch"], r["shape"], r["multi_pod"]))
            except json.JSONDecodeError:
                pass

    for arch, shape, mp in cells:
        key = (arch, shape, mp)
        if key in done:
            print(f"[dryrun] cached {key}", flush=True)
            continue
        print(f"[dryrun] {arch} x {shape} multi_pod={mp} ...", flush=True)
        try:
            rec = run_cell(arch, shape, mp, flags=flags, tcfg=tcfg)
        except Exception as e:  # noqa: BLE001 — record and continue
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
        line = json.dumps(rec)
        if out_path:
            with out_path.open("a") as f:
                f.write(line + "\n")
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" bottleneck={r['bottleneck']}"
                     f" step>={r['step_lower_bound_s']:.4f}s"
                     f" frac={r['roofline_fraction']:.3f}"
                     f" compile={rec['compile_s']}s")
        elif status == "error":
            extra = " " + rec["error"][:200]
        print(f"[dryrun] {arch} x {shape} mp={mp}: {status}{extra}",
              flush=True)


if __name__ == "__main__":
    main()
