"""Named model-family presets.

Parity targets from the reference example configs (examples/gpt3,
examples/mixtral/train_mixtral_8x7b_distributed.sh:51,85, run_single_gpt.sh,
BASELINE.md parity list).
"""

from __future__ import annotations

from megatronapp_tpu.config.transformer_config import (
    ActivationKind, NormKind, PositionEmbeddingKind, TransformerConfig,
)


def gpt2_125m(**kw) -> TransformerConfig:
    d = dict(num_layers=12, hidden_size=768, num_attention_heads=12,
             vocab_size=50304, true_vocab_size=50257,
             max_position_embeddings=1024,
             position_embedding=PositionEmbeddingKind.learned_absolute,
             add_qkv_bias=True)
    d.update(kw)
    return TransformerConfig(**d)


def gpt3_2p7b(**kw) -> TransformerConfig:
    """BASELINE.md north-star model (GPT-3 2.7B)."""
    d = dict(num_layers=32, hidden_size=2560, num_attention_heads=32,
             vocab_size=50304, max_position_embeddings=2048)
    d.update(kw)
    return TransformerConfig(**d)


def gpt_16l_2048h(**kw) -> TransformerConfig:
    """Reference DPP/FBD test model (test_train_gpt_single_dpp.sh:30-66:
    16L / h2048 / 32 heads / seq 2048)."""
    d = dict(num_layers=16, hidden_size=2048, num_attention_heads=32,
             vocab_size=50304, max_position_embeddings=2048)
    d.update(kw)
    return TransformerConfig(**d)


def llama3_8b(**kw) -> TransformerConfig:
    d = dict(num_layers=32, hidden_size=4096, num_attention_heads=32,
             num_query_groups=8, ffn_hidden_size=14336, vocab_size=128256,
             max_position_embeddings=8192, rotary_base=500000.0,
             activation=ActivationKind.swiglu,
             normalization=NormKind.rmsnorm, add_bias_linear=False,
             untie_embeddings_and_output_weights=True)
    d.update(kw)
    return TransformerConfig(**d)


def mixtral_8x7b(**kw) -> TransformerConfig:
    """examples/mixtral parity: 8 experts, top-2, GQA-8."""
    d = dict(num_layers=32, hidden_size=4096, num_attention_heads=32,
             num_query_groups=8, ffn_hidden_size=14336, vocab_size=32000,
             max_position_embeddings=32768, rotary_base=1e6,
             activation=ActivationKind.swiglu,
             normalization=NormKind.rmsnorm, add_bias_linear=False,
             untie_embeddings_and_output_weights=True,
             num_moe_experts=8, moe_router_topk=2,
             moe_ffn_hidden_size=14336, moe_aux_loss_coeff=0.02)
    d.update(kw)
    return TransformerConfig(**d)


def deepseek_v2_lite(**kw) -> TransformerConfig:
    """DeepSeek-V2-Lite (15.7B, 2.4B active) as its config.json publishes
    it (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite): 27 layers,
    MLA without a query latent, layer 0 dense, then 64 experts top-6 with 2
    shared experts (one SwiGLU of twice the width), probabilities not
    renormalised, YaRN x40 with mscale == mscale_all_dim == 0.707.
    tests/test_deepseek_v2.py holds this, the benchmark's configuration
    file and the catalog's numbers to each other."""
    d = dict(num_layers=27, hidden_size=2048, num_attention_heads=16,
             ffn_hidden_size=10944, vocab_size=102400,
             max_position_embeddings=163840,
             activation=ActivationKind.swiglu,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-6,
             add_bias_linear=False,
             untie_embeddings_and_output_weights=True,
             position_embedding=PositionEmbeddingKind.yarn,
             rotary_base=10000.0, rope_scaling_factor=40.0,
             yarn_original_max_position=4096, yarn_beta_fast=32.0,
             yarn_beta_slow=1.0, yarn_mscale_coeff=0.1 * 0.707,
             multi_latent_attention=True, q_lora_rank=None,
             kv_lora_rank=512, qk_head_dim=128, qk_pos_emb_head_dim=64,
             v_head_dim=128,
             num_moe_experts=64, moe_router_topk=6,
             moe_ffn_hidden_size=1408,
             moe_shared_expert_intermediate_size=2 * 1408,
             moe_router_norm_topk_prob=False,
             moe_routed_scaling_factor=1.0, moe_first_k_dense=1)
    d.update(kw)
    return TransformerConfig(**d)


def longcat_flash_chat(**kw) -> TransformerConfig:
    """LongCat-Flash-Chat (560B, 18.6-31.3B active a token) as its
    config.json publishes it
    (https://huggingface.co/meituan-longcat/LongCat-Flash-Chat): 28
    shortcut-connected double layers (two latent-attention sublayers with a
    query latent of 1536 and the two sqrt(hidden / rank) scale corrections,
    two dense SwiGLUs of 12288, one MoE on a shortcut), a 768-way softmax
    router over 512 experts of width 2048 and 256 zero-compute (identity)
    experts, top-12 on biased scores, weights unbiased x 6 and not
    renormalised, RoPE theta 1e7 over 64 roped columns, no YaRN. Whole it
    is 1.1 TB of bf16 weights: a deployment passes moe_experts_held (its
    rank's experts), a vocabulary slice and its stages' num_layers, as the
    benchmark's configuration does (perfbench/configs/
    longcat-flash-chat.json; tests/test_longcat_flash.py holds the two and
    the catalog's numbers to each other)."""
    d = dict(num_layers=28, hidden_size=6144, num_attention_heads=64,
             ffn_hidden_size=12288, vocab_size=131072,
             max_position_embeddings=131072,
             activation=ActivationKind.swiglu,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-5,
             add_bias_linear=False,
             untie_embeddings_and_output_weights=True,
             position_embedding=PositionEmbeddingKind.rope,
             rotary_base=10000000.0,
             multi_latent_attention=True, q_lora_rank=1536,
             kv_lora_rank=512, qk_head_dim=128, qk_pos_emb_head_dim=64,
             v_head_dim=128, mla_scale_q_lora=True, mla_scale_kv_lora=True,
             num_moe_experts=512, moe_zero_experts=256, moe_router_topk=12,
             moe_ffn_hidden_size=2048, moe_router_norm_topk_prob=False,
             moe_routed_scaling_factor=6.0, moe_router_selection_bias=True,
             moe_shortcut_double_layer=True)
    d.update(kw)
    return TransformerConfig(**d)


def bert_base(**kw) -> TransformerConfig:
    from megatronapp_tpu.models.bert import bert_config
    d = dict(num_layers=12, hidden_size=768, num_attention_heads=12,
             vocab_size=30592, max_position_embeddings=512)
    d.update(kw)
    return bert_config(**d)


def t5_base(**kw) -> TransformerConfig:
    from megatronapp_tpu.models.t5 import t5_config
    d = dict(num_layers=12, hidden_size=768, num_attention_heads=12,
             vocab_size=32128, max_position_embeddings=512)
    d.update(kw)
    return t5_config(**d)


def mamba_130m(**kw) -> TransformerConfig:
    """state-spaces/mamba-130m-class dims (24 layers, d_model 768)."""
    d = dict(num_layers=24, hidden_size=768, num_attention_heads=12,
             vocab_size=50280, max_position_embeddings=2048,
             normalization=NormKind.rmsnorm)
    d.update(kw)
    return TransformerConfig(**d)


def jamba2_3b(**kw) -> TransformerConfig:
    """ai21labs/AI21-Jamba2-3B: 28 layers of which 7 and 21 attend (20
    heads, 1 key/value head) and 26 are Mamba-1 layers; a dense SwiGLU of
    8192 in every layer; RMSNorm, a tied head, no positional term. Serves
    through --engine dynamic."""
    d = dict(num_layers=28, hidden_size=2560, num_attention_heads=20,
             num_query_groups=1, ffn_hidden_size=8192, vocab_size=65536,
             max_position_embeddings=262144,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-6,
             activation=ActivationKind.swiglu, add_bias_linear=False,
             position_embedding=PositionEmbeddingKind.none,
             attn_layer_period=14, attn_layer_offset=7, ssm_state_dim=16,
             ssm_conv_kernel=4, ssm_expand=2, ssm_dt_rank=160,
             ssm_inner_norms=True)
    d.update(kw)
    return TransformerConfig(**d)


def lfm2_24b_a2b(**kw) -> TransformerConfig:
    """LiquidAI/LFM2-24B-A2B (`lfm2_moe`; 24B, 2B active a token) as its
    config.json publishes it: 40 layers of H 2048 of which 2, 6, ..., 38
    attend (32 query and 8 key/value heads of 64, RMS norms on each head's
    query and key, RoPE theta 1e6) and 30 are gated short convolutions of 3
    taps; layers 0 and 1 end in a dense SwiGLU of 11776, the other 38 in 64
    experts of width 1536, top-4 on sigmoid scores + a selection bias,
    weights unbiased and renormalised; RMSNorm 1e-5, a tied head. Whole it
    is 47 GB of bf16 weights: a deployment passes its stages' num_layers,
    attn_layer_offset and moe_first_k_dense, as the benchmark's
    configuration does (perfbench/configs/lfm2-24b-a2b.json). Serves
    through --engine dynamic."""
    d = dict(num_layers=40, hidden_size=2048, num_attention_heads=32,
             num_query_groups=8, ffn_hidden_size=11776, vocab_size=65536,
             max_position_embeddings=128000,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-5,
             activation=ActivationKind.swiglu, add_bias_linear=False,
             position_embedding=PositionEmbeddingKind.rope,
             rotary_base=1000000.0, qk_layernorm=True,
             attn_layer_period=4, attn_layer_offset=2, shortconv_kernel=3,
             num_moe_experts=64, moe_router_topk=4, moe_ffn_hidden_size=1536,
             moe_first_k_dense=2, moe_router_score="sigmoid",
             moe_router_selection_bias=True, moe_router_norm_topk_prob=True,
             moe_routed_scaling_factor=1.0)
    d.update(kw)
    return TransformerConfig(**d)


def laguna_xs2(**kw) -> TransformerConfig:
    """poolside/Laguna-XS.2 (33.4B parameters, ~2.8B active) as its
    config.json publishes it: 40 layers of H 2048 over 8 key/value heads of
    128, of which layers 0, 4, 8, ... are full attention with 48 query heads
    (YaRN on half of each head: theta 5e5, factor 64 over 4096, beta 64 / 1,
    attention factor 1.41589) and the other 30 sliding-window attention of
    64 query heads over the last 512 keys (plain RoPE, theta 1e4, the whole
    head); RMS norms on each head's q and k, a per-head sigmoid gate on the
    attention output; layer 0 ends in a dense SwiGLU of 8192, the other 39
    in 256 experts of width 512 beside a shared one, top-8 on sigmoid scores
    + a selection bias, weights renormalised, x 2.5; RMSNorm 1e-6, an untied
    head over 100,352. Whole it is 67 GB of bf16 weights: a deployment
    passes its stages' num_layers, as the benchmark's configuration does
    (perfbench/configs/laguna-xs.2.json, which also lists what the config
    leaves to the family's convention). Serves through --engine dynamic."""
    d = dict(num_layers=40, hidden_size=2048, num_attention_heads=48,
             num_query_groups=8, kv_channels=128, ffn_hidden_size=8192,
             vocab_size=100352, max_position_embeddings=262144,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-6,
             activation=ActivationKind.swiglu, add_bias_linear=False,
             untie_embeddings_and_output_weights=True, qk_layernorm=True,
             attention_output_gate=True,
             position_embedding=PositionEmbeddingKind.yarn,
             rotary_base=500000.0, rotary_percent=0.5,
             rope_scaling_factor=64.0, yarn_original_max_position=4096,
             yarn_beta_fast=64.0, yarn_beta_slow=1.0,
             yarn_attention_factor=1.4158883083359672,
             attn_layer_period=4, attn_layer_offset=0,
             sliding_window=512, sliding_window_heads=64,
             sliding_rotary_base=10000.0, sliding_rotary_percent=1.0,
             num_moe_experts=256, moe_router_topk=8, moe_ffn_hidden_size=512,
             moe_shared_expert_intermediate_size=512, moe_first_k_dense=1,
             moe_router_score="sigmoid", moe_router_selection_bias=True,
             moe_router_norm_topk_prob=True, moe_routed_scaling_factor=2.5)
    d.update(kw)
    return TransformerConfig(**d)


def mellum2_12b_a2p5b(**kw) -> TransformerConfig:
    """JetBrains/Mellum2-12B-A2.5B(-Instruct) (12.15B parameters, ~2.5B
    active; `model_type` mellum) as its config.json publishes it
    (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/
    config.json): 28 pre-norm layers of H 2304, 32 query heads over 4
    key/value heads of 128, no bias; layers 3, 7, 11, ... attend to every
    earlier key with YaRN (theta 5e5, factor 16 over 8192, beta 32 / 1,
    attention factor 1.27726), the other 21 to the last 1,024 keys with
    plain rotary tables at the same theta; every layer ends in 64 experts
    of width 896, the 8 largest of a softmax over all 64, renormalised, no
    shared expert (`intermediate_size` 7168 is unused); RMSNorm 1e-6, an
    untied head over 98,304. Assumed, as the family whose keys these are
    (Qwen3-MoE) has them: an RMS norm on each head's q and k before the
    rotation, half-rotation pairing, a load-balancing loss at 0.001
    (`router_aux_loss_coef`; here divided by the top-k, Megatron's form),
    init 0.02 and 0.02 / sqrt(2 x 28) whatever part of the depth is run
    (`scaled_init_layers`). The "MTP head" its card
    mentions is described by no key and left out. Whole it is 194 GB of
    training state: a job passes its share (num_layers, moe_experts_held,
    vocab_size), as the benchmark's configuration does
    (perfbench/configs/mellum2-12b-a2.5b.json). Trains through
    pretrain_gpt.py; window layers run the flash kernels' band."""
    d = dict(num_layers=28, hidden_size=2304, num_attention_heads=32,
             num_query_groups=4, kv_channels=128, ffn_hidden_size=7168,
             vocab_size=98304, max_position_embeddings=131072,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-6,
             activation=ActivationKind.swiglu, add_bias_linear=False,
             untie_embeddings_and_output_weights=True, qk_layernorm=True,
             position_embedding=PositionEmbeddingKind.yarn,
             rotary_base=500000.0, rope_scaling_factor=16.0,
             yarn_original_max_position=8192,
             yarn_beta_fast=32.0, yarn_beta_slow=1.0,
             yarn_attention_factor=1.2772588722239782,
             attn_layer_period=4, attn_layer_offset=3,
             sliding_window=1024, sliding_rotary_base=500000.0,
             num_moe_experts=64, moe_router_topk=8, moe_ffn_hidden_size=896,
             moe_router_norm_topk_prob=True, moe_aux_loss_coeff=0.001,
             scaled_init_layers=28)
    d.update(kw)
    return TransformerConfig(**d)


def granite_4_0_h_small(**kw) -> TransformerConfig:
    """ibm-granite/granite-4.0-h-small (`granitemoehybrid`; 32B parameters,
    ~9B active) as its config.json publishes it
    (https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/
    config.json): 40 layers of H 4096 of which 5, 15, 25 and 35 attend (32
    query heads over 8 key/value heads of 128, NO positional term, softmax
    of q.k / 128) and 36 are Mamba-2 mixers (128 heads of 64 columns, a
    [64, 128] state a head, one group, convolution of 4 taps over x, B and
    C, chunks of 256, a gated RMS norm before the output projection); every
    layer ends in 72 experts of width 768, the 10 largest logits softmaxed
    among themselves, beside a shared expert of 1536; the embedding times
    12, each half's output times 0.22, the logits over 16; RMSNorm 1e-5, a
    tied head over 100,352. Whole it is 64 GB of bf16 weights: a deployment
    passes its share (num_layers, moe_experts_held, vocab_size), as the
    benchmark's configuration does
    (perfbench/configs/granite-4.0-h-small.json, which also lists what the
    config leaves to the family's convention). Serves through --engine
    dynamic."""
    d = dict(num_layers=40, hidden_size=4096, num_attention_heads=32,
             num_query_groups=8, ffn_hidden_size=1536, vocab_size=100352,
             max_position_embeddings=131072,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-5,
             activation=ActivationKind.swiglu, add_bias_linear=False,
             position_embedding=PositionEmbeddingKind.none,
             attn_layer_period=10, attn_layer_offset=5,
             ssm_state_dim=128, ssm_conv_kernel=4, ssm_expand=2,
             ssm_heads=128, ssm_head_dim=64, ssm_groups=1,
             ssm_chunk_size=256,
             num_moe_experts=72, moe_router_topk=10,
             moe_ffn_hidden_size=768,
             moe_shared_expert_intermediate_size=1536,
             moe_router_norm_topk_prob=True,
             embedding_multiplier=12.0, attention_multiplier=0.0078125,
             residual_multiplier=0.22, logits_scaling=16.0,
             scaled_init_layers=40)
    d.update(kw)
    return TransformerConfig(**d)


NEMOTRON_3_NANO_PATTERN = (
    "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")


def nemotron_3_nano_30b_a3b(**kw) -> TransformerConfig:
    """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (`nemotron_h`; 31.6B
    parameters, ~3.2B active) as its config.json publishes it
    (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/
    blob/main/config.json): 52 layers of H 2688, each ONE sublayer behind an
    RMS norm (hybrid_override_pattern: 23 Mamba-2 mixers, 23 expert layers,
    6 attention layers). Mamba-2: 64 heads of 64 columns (inner width 4096,
    not 2 x 2688), a [64, 128] state a head, B and C in 8 groups of 8 heads,
    a gated RMS norm over each group's 512 columns, convolution of 4 taps,
    chunks of 128. Attention: 32 query heads over 2 key/value heads of 128,
    NO positional term. Experts: 128 of width 1856, two matrices and relu^2
    (no gate), a sigmoid router whose 6 picks are the largest of s + b, the
    weights s renormalised and times 2.5, beside a shared expert of 3712.
    RMSNorm 1e-5, an untied head over 131,072. Whole it is 63 GB of bf16
    weights: a deployment passes its share (num_layers and layer_pattern,
    moe_experts_held, vocab_size), as the benchmark's configuration does
    (perfbench/configs/nemotron-3-nano-30b-a3b.json, which also lists what
    the config leaves to the family's convention). Serves through --engine
    dynamic. The router divides the picks' weights by their sum + 1e-6
    (transformer/moe.py's sigmoid constant) where `nemotron_h` adds 1e-20:
    3e-7 of a weight."""
    d = dict(num_layers=52, hidden_size=2688, num_attention_heads=32,
             num_query_groups=2, kv_channels=128, ffn_hidden_size=1856,
             vocab_size=131072, max_position_embeddings=262144,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-5,
             activation=ActivationKind.squared_relu, add_bias_linear=False,
             position_embedding=PositionEmbeddingKind.none,
             untie_embeddings_and_output_weights=True,
             layer_pattern=NEMOTRON_3_NANO_PATTERN, scaled_init_layers=52,
             ssm_state_dim=128, ssm_conv_kernel=4, ssm_heads=64,
             ssm_head_dim=64, ssm_groups=8, ssm_chunk_size=128,
             num_moe_experts=128, moe_router_topk=6,
             moe_ffn_hidden_size=1856,
             moe_shared_expert_intermediate_size=3712,
             moe_router_score="sigmoid", moe_router_selection_bias=True,
             moe_router_norm_topk_prob=True, moe_routed_scaling_factor=2.5)
    d.update(kw)
    if "num_layers" in kw and "layer_pattern" not in kw:
        d["layer_pattern"] = NEMOTRON_3_NANO_PATTERN[:kw["num_layers"]]
    return TransformerConfig(**d)


def solar_open2_250b(**kw) -> TransformerConfig:
    """upstage/Solar-Open2-250B (`solar_open2`; 250B parameters, ~15B
    active) as its config.json publishes it
    (https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json):
    48 layers of H 4096 of which 0, 4, 8, ... attend (gqa_layers: 64 query
    heads over 8 key/value heads of 128, NO positional term, an elementwise
    sigmoid gate on the heads' outputs) and the other 36 are Kimi delta
    attention (transformer/kda.py: 64 heads, a [128, 128] float32 state a
    head under the gated delta rule with a decay a key channel, b in (0, 2),
    convolutions of 4 taps over q, k and v, low-rank (128) decay and output
    gates); every layer ends in 320 experts of width 1280 (SwiGLU), a
    sigmoid router whose 8 picks are the largest of s + b, the weights s
    renormalised, beside one shared expert of 1280; RMSNorm 1e-5, an untied
    head over 196,608. Whole it is 500 GB of bf16 weights: a deployment
    passes its share (num_layers, moe_experts_held, vocab_size), as the
    benchmark's configuration does
    (perfbench/configs/solar-open2-250b.json, which also lists what the
    config leaves to the family's convention). Serves through --engine
    dynamic."""
    d = dict(num_layers=48, hidden_size=4096, num_attention_heads=64,
             num_query_groups=8, kv_channels=128, ffn_hidden_size=10240,
             vocab_size=196608, max_position_embeddings=1048576,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-5,
             activation=ActivationKind.swiglu, add_bias_linear=False,
             position_embedding=PositionEmbeddingKind.none,
             untie_embeddings_and_output_weights=True,
             attn_layer_period=4, attn_layer_offset=0, scaled_init_layers=48,
             attention_output_gate=True, attention_gate_elementwise=True,
             kda_heads=64, ssm_head_dim=128, ssm_state_dim=128,
             ssm_conv_kernel=4, ssm_chunk_size=64,
             num_moe_experts=320, moe_router_topk=8,
             moe_ffn_hidden_size=1280,
             moe_shared_expert_intermediate_size=1280,
             moe_router_score="sigmoid", moe_router_selection_bias=True,
             moe_router_norm_topk_prob=True, moe_routed_scaling_factor=1.0)
    d.update(kw)
    return TransformerConfig(**d)


def solar_open2_tiny(**kw) -> TransformerConfig:
    """solar_open2_250b at a size the CPU tests run: 5 layers (G K K K G) of
    H 64, 4 query heads over 2 key/value heads of 16, 2 Kimi-delta-attention
    heads with a [16, 16] state in chunks of 32, 8 experts of width 32 top-2
    beside a shared one, 256 tokens."""
    return solar_open2_250b(**{**dict(
        num_layers=5, hidden_size=64, num_attention_heads=4,
        num_query_groups=2, kv_channels=16, ffn_hidden_size=128,
        vocab_size=256, max_position_embeddings=512, scaled_init_layers=5,
        kda_heads=2, ssm_head_dim=16, ssm_state_dim=16, ssm_chunk_size=32,
        num_moe_experts=8, moe_router_topk=2, moe_ffn_hidden_size=32,
        moe_shared_expert_intermediate_size=32), **kw})


def evabyte_6p5b(**kw) -> TransformerConfig:
    """EvaByte/EvaByte (6.5B, byte-level) as its config.json publishes it:
    32 layers of H 4096, 32 query and 32 key/value heads of 128, RoPE
    theta 100,000, SwiGLU 11008, RMSNorm 1e-5 whose scale is 1 + g, no
    bias, an untied head of 8 byte-prediction heads x 320, and EVA
    attention: an exact window of 2048 bytes and one pooled key/value row
    for every 16 older bytes (transformer/eva.py). Serves through --engine
    dynamic, ids in and out (no byte tokenizer yet)."""
    d = dict(num_layers=32, hidden_size=4096, num_attention_heads=32,
             num_query_groups=32, ffn_hidden_size=11008, vocab_size=320,
             true_vocab_size=320, max_position_embeddings=32768,
             rotary_base=100000.0, activation=ActivationKind.swiglu,
             normalization=NormKind.rmsnorm, layernorm_epsilon=1e-5,
             norm_unit_offset=True, add_bias_linear=False,
             untie_embeddings_and_output_weights=True, num_pred_heads=8,
             init_method_std=0.01275,
             eva_window_size=2048, eva_chunk_size=16)
    d.update(kw)
    return TransformerConfig(**d)


PRESETS = {
    "solar-open2-250b": solar_open2_250b,
    "solar-open2-tiny": solar_open2_tiny,
    "nemotron-3-nano-30b-a3b": nemotron_3_nano_30b_a3b,
    "evabyte-6.5b": evabyte_6p5b,
    "granite-4.0-h-small": granite_4_0_h_small,
    "jamba2-3b": jamba2_3b,
    "lfm2-24b-a2b": lfm2_24b_a2b,
    "laguna-xs.2": laguna_xs2,
    "mellum2-12b-a2.5b": mellum2_12b_a2p5b,
    "gpt2-125m": gpt2_125m,
    "gpt3-2.7b": gpt3_2p7b,
    "mamba-130m": mamba_130m,
    "gpt-16l-2048h": gpt_16l_2048h,
    "llama3-8b": llama3_8b,
    "mixtral-8x7b": mixtral_8x7b,
    "deepseek-v2-lite": deepseek_v2_lite,
    "longcat-flash-chat": longcat_flash_chat,
    "bert-base": bert_base,
    "t5-base": t5_base,
}
