"""Three-term roofline analysis from a compiled dry-run artifact.

  compute    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory     = HLO_bytes / (chips * HBM_bw)
  collective = collective_bytes / (chips * links * link_bw)

``cost_analysis()`` supplies FLOPs/bytes.  Collective bytes are NOT in
cost_analysis: we parse the post-partitioning HLO and sum the result-shape
bytes of every collective op (shapes there are already per-device), scaled
by a per-op ring-cost factor (all-reduce = 2x: reduce-scatter + all-gather).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s
per ICI link with ~2 usable links per sharded axis direction.
"""

from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_LINK_BW = 50e9
ICI_LINKS = 2.0

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# result-bytes multiplier approximating ring cost per chip
_OP_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
    "collective-broadcast": 1.0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)(?:-start)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Per-op-kind and total per-device collective bytes from HLO text."""
    per_kind: dict[str, float] = {}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str) * _OP_FACTOR[kind]
        per_kind[kind] = per_kind.get(kind, 0.0) + b
    per_kind["total"] = sum(v for k, v in per_kind.items() if k != "total")
    return per_kind


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float          # per chip (XLA costs the SPMD partition)
    hlo_bytes: float          # per chip
    collective_bytes: float   # per chip
    model_flops: float        # global (all chips)
    compute_s: float
    memory_s: float
    collective_s: float
    bytes_per_device: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS, both per chip.  < 1 because HLO also
        carries attention/norm/aux work; >> drops flag redundant compute
        (remat, replicated einsums); << 1 flags missing parallelism."""
        return (self.model_flops / self.chips) / max(self.hlo_flops, 1.0)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self) | {
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def roofline_terms(*, arch: str, shape: str, mesh_name: str, chips: int,
                   cost: dict, collective: dict, model_fl: float,
                   bytes_per_device: float) -> RooflineReport:
    """cost: compiled.cost_analysis() dict.  NOTE on conventions: XLA's
    cost analysis reports the per-partition program; we treat `flops` and
    `bytes accessed` as per-chip numbers for the SPMD program."""
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = float(collective.get("total", 0.0))
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts, collective_bytes=coll,
        model_flops=model_fl,
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=coll / (ICI_LINKS * ICI_LINK_BW),
        bytes_per_device=bytes_per_device,
    )


# ---------------------------------------------------------------------------
# MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); decode: 2 N per token
# ---------------------------------------------------------------------------

def param_count(cfg, *, active_only: bool = False) -> float:
    """Analytic parameter count for the assigned configs."""
    d, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    n = V * d  # embedding
    if not cfg.tie_embeddings:
        n += d * V

    def attn_params():
        if cfg.use_mla:
            qk_hd = cfg.nope_head_dim + cfg.rope_head_dim
            return (d * cfg.num_heads * qk_hd + d * cfg.kv_lora_rank +
                    d * cfg.rope_head_dim +
                    cfg.kv_lora_rank * cfg.num_heads *
                    (cfg.nope_head_dim + cfg.v_head_dim) +
                    cfg.num_heads * cfg.v_head_dim * d)
        hd = cfg.head_dim
        return d * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)

    def mlp_params(ff):
        return 3 * d * ff

    at = cfg.arch_type
    if at == "ssm":
        di, H = cfg.d_inner, cfg.ssm_heads
        GN = cfg.ssm_n_groups * cfg.ssm_state
        per = (2 * d * di + 2 * d * GN + d * H + di * d +
               cfg.conv_width * (di + 2 * GN))
        n += L * per
    elif at == "hybrid":
        period = len(cfg.block_pattern)
        n_attn = (L // period) * sum(
            1 for b in cfg.block_pattern if b == "attention")
        n_rec = L - n_attn
        r = cfg.lru_width
        rec_per = 2 * d * r + 2 * r * r + r * d + cfg.conv_width * r
        n += n_attn * (attn_params() + mlp_params(cfg.d_ff))
        n += n_rec * (rec_per + mlp_params(cfg.d_ff))
    elif at == "moe":
        nd = cfg.first_dense_layers
        moe_per = (cfg.num_experts * 3 * d * cfg.moe_d_ff +
                   cfg.num_shared_experts * 3 * d * cfg.moe_d_ff +
                   d * cfg.num_experts)
        active_per = ((cfg.experts_per_token + cfg.num_shared_experts) *
                      3 * d * cfg.moe_d_ff + d * cfg.num_experts)
        ff_term = active_per if active_only else moe_per
        n += nd * (attn_params() + mlp_params(cfg.first_dense_d_ff or cfg.d_ff))
        n += (L - nd) * (attn_params() + ff_term)
    elif at == "audio":
        n += cfg.num_encoder_layers * (attn_params() + mlp_params(cfg.d_ff))
        # decoder: self-attn + cross-attn + mlp
        n += L * (2 * attn_params() + mlp_params(cfg.d_ff))
    else:  # dense / vlm
        n += L * (attn_params() + mlp_params(cfg.d_ff))
    return float(n)


def model_flops(cfg, shape_cfg) -> float:
    """6*N*D for train, 2*N*D for prefill (fwd only), 2*N per decoded
    token; MoE uses active params."""
    n_active = param_count(cfg, active_only=True)
    if shape_cfg.kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * n_active * tokens
    if shape_cfg.kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence per step
    return 2.0 * n_active * shape_cfg.global_batch
