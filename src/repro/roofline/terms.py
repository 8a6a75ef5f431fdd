"""Roofline terms per (arch x shape x mesh) from the compiled dry-run.

  compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory term     = HLO_bytes / (chips * HBM_bw)
  collective term = collective_bytes / (chips * link_bw)

Hardware constants come from :data:`PEAKS`, one row per ``device_kind``;
a kind that is not in the table is an error, not a default.
HLO_FLOPs/bytes come from the trip-count-weighted HLO analysis (hlo.py) of
the post-SPMD compiled module; both are PER-DEVICE quantities, so `chips`
does not divide them again — the formulas below therefore use per-chip
peaks directly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float   # FLOP/s
    hbm_bytes: float    # bytes
    hbm_bw: float       # bytes/s
    ici_bw: float       # bytes/s per chip-to-chip link
    source: str


#: ``jax.Device.device_kind`` -> published peaks. TPU v5e: 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links
#: (50 GB/s each).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(197e12, 16e9, 819e9, 50e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}

#: The kind the production mesh, the dry run and the analytic codec
#: rooflines model.
V5E = "TPU v5 lite"

DCN_BW = 25e9              # bytes/s / host (pod axis); modeled, no chip row


def peaks(device_kind: str) -> Peaks:
    """The peaks row for ``device_kind``; raises on a kind not in
    :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * chips)
    bottleneck: str
    collective_counts: Dict[str, int]

    def total_s(self) -> float:
        """Perfect-overlap lower bound: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """Useful-compute roofline fraction: time the chips *must* spend on
        model FLOPs vs the bound (max term)."""
        ideal = self.compute_s * self.useful_ratio
        return ideal / self.total_s() if self.total_s() > 0 else 0.0


def compute_terms(hlo_flops_per_dev: float, hlo_bytes_per_dev: float,
                  collective_bytes_per_dev: float, chips: int,
                  model_flops_global: float,
                  collective_counts: Optional[Dict[str, int]] = None,
                  link_bw: Optional[float] = None) -> RooflineTerms:
    pk = peaks(V5E)
    compute_s = hlo_flops_per_dev / pk.flops_bf16
    memory_s = hlo_bytes_per_dev / pk.hbm_bw
    coll_s = collective_bytes_per_dev / (link_bw or pk.ici_bw)
    useful = (model_flops_global / (hlo_flops_per_dev * chips)
              if hlo_flops_per_dev else 0.0)
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    return RooflineTerms(compute_s, memory_s, coll_s, hlo_flops_per_dev,
                         hlo_bytes_per_dev, collective_bytes_per_dev,
                         model_flops_global, useful, bottleneck,
                         collective_counts or {})


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D for train (fwd+bwd), 2*N*D for inference,
    with N = active params; D = tokens processed this step. (Reported as-is
    per the assignment formula; attention-matmul FLOPs are reported
    separately via model_flops_attn for the useful-ratio diagnostic.)"""
    n_active = cfg.active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def _n_attn_layers(cfg) -> int:
    pat = cfg.block_pattern
    per_cycle = sum(1 for k in pat if k == "attn")
    return cfg.n_layers // len(pat) * per_cycle


def model_flops_attn(cfg, shape) -> float:
    """Attention score+value matmul FLOPs (excluded from 6ND but real work:
    dominates small-d_model long-seq cells). Causal halves the square."""
    B, S = shape.global_batch, shape.seq_len
    H, hd = cfg.n_heads, cfg.head_dim
    L = _n_attn_layers(cfg)
    if cfg.family == "rwkv":
        # wkv recurrence: ~4 flops per (head_dim^2) per token per layer
        per_tok = 4.0 * cfg.d_model * cfg.rwkv_head_dim * cfg.n_layers
        mult = {"train": 3.0, "prefill": 1.0, "decode": 1.0}[shape.kind]
        toks = B * (S if shape.kind != "decode" else 1)
        return per_tok * toks * mult
    if shape.kind == "train":
        fwd = 2.0 * B * H * S * S * hd * L  # qk+av, causal halved
        extra = 0.0
        if cfg.family == "encdec":
            # enc self (bidir, S/2 each side) + dec cross
            fwd = fwd / 4  # both streams are S//2 long
            Le = cfg.enc_layers
            fwd += 4.0 * B * H * (S // 2) ** 2 * hd * Le / 2
            fwd += 4.0 * B * H * (S // 2) ** 2 * hd * L
        return 3.0 * (fwd + extra)
    if shape.kind == "prefill":
        return 2.0 * B * H * S * S * hd * L
    return 4.0 * B * H * S * hd * L  # decode: 1 token vs S keys


def flash_hbm_traffic(cfg, shape, mesh, flags) -> float:
    """Per-device HBM bytes the Pallas flash kernel actually streams for
    attention (K/V read once per query chunk, Q/O once), replacing the
    CPU-HLO score-tile fusions excluded by the vmem_tile filter.
    Train counts forward + remat-recompute + backward (3 passes)."""
    B, S = shape.global_batch, shape.seq_len
    L = _n_attn_layers(cfg)
    if L == 0 or cfg.family == "rwkv":
        return 0.0
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = axes.get("pod", 1) * axes.get("data", 1)
    tp = axes.get("model", 1)
    B_dev = max(1, B // dp)
    KV, H, hd = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    kv_dev = max(1, KV // tp) if KV % tp == 0 else KV
    h_dev = max(1, H // tp) if H % tp == 0 else H
    if shape.kind == "decode":
        # one-token decode: read the whole (sharded) cache once
        seq_shard = axes.get("data", 1) if (B < dp) else 1
        return (2.0 * B_dev * (S // seq_shard) * kv_dev * hd * 2) * L
    nq = max(1, S // flags.q_chunk)
    kv_bytes = S * kv_dev * hd * 2 * 2          # K+V bf16
    q_o = 2.0 * S * h_dev * hd * 2
    per_layer = nq * kv_bytes + q_o
    passes = 3.0 if shape.kind == "train" else 1.0
    return per_layer * L * B_dev * passes
