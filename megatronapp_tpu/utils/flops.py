"""FLOPs accounting for throughput/MFU logging.

Parity with /root/reference/megatron/training/training.py:142
(num_floating_point_operations): counts dense matmul + attention + logit
FLOPs per token for the standard transformer; used by training_log to report
TFLOP/s/device and by bench.py for MFU.
"""

from __future__ import annotations

from megatronapp_tpu.config.transformer_config import (
    ActivationKind, TransformerConfig,
)

# Peak bf16 FLOP/s per chip for MFU math, keyed by a substring of the
# lower-cased device_kind (TPU v5e = 197 TFLOP/s bf16 — the oft-quoted 394
# is the int8 TOPS figure; v5p ≈ 459 bf16). A device that is not here has
# no MFU: callers raise, they do not default.
TPU_PEAK_FLOPS = {
    "v5litepod": 197e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}

# HBM bytes/s per chip, by the same keys: with the peak above, the flops a
# byte of a streamed operand has to pay for before the stream is hidden.
TPU_HBM_BYTES_PER_S = {
    "v5litepod": 819e9,
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v4": 1228e9,
    "v6e": 1640e9,
    "v6 lite": 1640e9,
}


def tpu_roofs(device_kind: str):
    """(peak bf16 flop/s, HBM bytes/s) of a device kind, or None for a
    device the tables do not know (a CPU)."""
    kind = device_kind.lower()
    for key, peak in TPU_PEAK_FLOPS.items():
        if key in kind:
            return peak, TPU_HBM_BYTES_PER_S[key]
    return None


def flops_per_token(cfg: TransformerConfig, seq_len: int) -> float:
    """Forward+backward FLOPs per token (3x forward matmul FLOPs;
    recomputed operations do not count). A full-attention layer counts
    seq_len keys a query, a sliding-window layer (its own head count where
    the model gives it one) min(seq_len, window); an MoE layer its top-k
    picks, a shared expert and the router's whole width, behind
    moe_first_k_dense dense layers. A share of the experts
    (moe_experts_held) is counted as the whole model: what the other
    shares' chips would compute of a token is in the number."""
    h = cfg.hidden_size
    d = cfg.head_dim
    nkv = cfg.num_query_groups

    def attention(nq, keys):
        # Projections Q + KV + out; scores + context over `keys`.
        return (2 * h * (nq * d) + 2 * h * (2 * nkv * d) + 2 * (nq * d) * h
                + 2 * 2 * keys * nq * d)

    attn = attention(cfg.num_attention_heads, seq_len)
    mixers = (cfg.num_layers - cfg.num_window_layers) * attn
    if cfg.num_window_layers:
        mixers += cfg.num_window_layers * attention(
            cfg.window_heads, min(seq_len, cfg.sliding_window))
    gated = cfg.activation in (ActivationKind.swiglu, ActivationKind.geglu)
    per_width = (3 if gated else 2) * 2 * h
    dense = per_width * cfg.ffn_hidden_size
    if cfg.is_moe:
        f_active = cfg.moe_ffn_hidden_size * cfg.moe_router_topk + (
            cfg.moe_shared_expert_intermediate_size or 0)
        moe = per_width * f_active + 2 * h * cfg.moe_router_width
        lead = cfg.moe_first_k_dense
        ffns = lead * dense + (cfg.num_layers - lead) * moe
    else:
        ffns = cfg.num_layers * dense
    logits = 2 * h * cfg.vocab_size
    return 3.0 * (mixers + ffns + logits)  # fwd + bwd (2x fwd)


def mfu(tokens_per_sec_per_chip: float, cfg: TransformerConfig,
        seq_len: int, peak_flops: float) -> float:
    return tokens_per_sec_per_chip * flops_per_token(cfg, seq_len) / peak_flops
