"""Transformer architecture configuration.

TPU-native analogue of the reference's ``TransformerConfig`` dataclass
(/root/reference/megatron/core/transformer/transformer_config.py:18) and
``ModelParallelConfig`` (/root/reference/megatron/core/model_parallel_config.py).
The reference couples these to CUDA-era concerns (TE, fp8 recipes, CUDA graphs);
here the config describes the *math* of the model plus TPU-relevant choices
(dtype policy, remat policy, kernel implementation selection).
"""

from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Optional, Tuple

import jax.numpy as jnp


class AttnMaskType(enum.Enum):
    causal = "causal"
    padding = "padding"
    bidirectional = "bidirectional"


class ActivationKind(enum.Enum):
    gelu = "gelu"
    swiglu = "swiglu"
    geglu = "geglu"
    relu = "relu"
    squared_relu = "squared_relu"


class NormKind(enum.Enum):
    layernorm = "LayerNorm"
    rmsnorm = "RMSNorm"


class PositionEmbeddingKind(enum.Enum):
    rope = "rope"
    learned_absolute = "learned_absolute"
    yarn = "yarn"
    none = "none"


# The letters of TransformerConfig.layer_pattern (HF `nemotron_h`'s
# hybrid_override_pattern): the ONE sublayer a layer is, and the key of the
# parameter tree's "block" under which the layers of that kind are stacked.
PATTERN_KINDS = {"M": "a Mamba-2 mixer", "*": "attention",
                 "E": "the experts", "-": "a dense feed-forward"}
PATTERN_STACKS = {"M": "mixers_ssm", "*": "mixers_attn", "E": "ffn",
                  "-": "ffn_dense"}


@functools.lru_cache(maxsize=None)
def _stack_plan(num_layers, period, offset, pattern, lead, conv, window,
                kda):
    """TransformerConfig.stack_plan of the fields that spell it."""
    if pattern is not None:
        return tuple((PATTERN_STACKS[letter],) for letter in pattern)
    if period is None:
        return None
    other = ("mixers_swa" if window else "mixers_conv" if conv else
             "mixers_kda" if kda else "mixers_ssm")
    return tuple(("mixers_attn" if i % period == offset else other,
                  "ffn_lead" if i < lead else "ffn")
                 for i in range(num_layers))


@dataclasses.dataclass
class TransformerConfig:
    """Architecture hyperparameters.

    Field semantics follow the reference TransformerConfig
    (transformer_config.py:18) — num_layers/hidden_size/num_attention_heads/
    num_query_groups/ffn_hidden_size/kv_channels etc. — expressed TPU-first.
    """

    num_layers: int = 2
    hidden_size: int = 128
    num_attention_heads: int = 8
    # GQA: number of KV heads (reference: num_query_groups).
    num_query_groups: Optional[int] = None
    ffn_hidden_size: Optional[int] = None
    kv_channels: Optional[int] = None
    vocab_size: int = 50304
    # Tokenizer's true vocab when vocab_size is padded to a TP-friendly
    # multiple (reference --make-vocab-size-divisible-by): inference masks
    # logits for padded ids so sampling cannot emit out-of-vocab tokens.
    true_vocab_size: Optional[int] = None
    # The published vocabulary of which this model's vocab_size rows are
    # one rank's slice (embedding and head sharded over the vocabulary in
    # the deployment). A sliced vocabulary is simply a smaller one: ids,
    # logits and sampling run over vocab_size. None: nothing is sliced.
    vocab_slice_of: Optional[int] = None
    max_position_embeddings: int = 2048

    # Normalization / activation / position embedding.
    normalization: NormKind = NormKind.layernorm
    layernorm_epsilon: float = 1e-5
    activation: ActivationKind = ActivationKind.gelu
    position_embedding: PositionEmbeddingKind = PositionEmbeddingKind.rope
    rotary_base: float = 10000.0
    rotary_percent: float = 1.0
    # YaRN context extension (position_embedding=yarn; reference
    # yarn_rotary_pos_embedding.py): trained-context multiplier and the
    # original pretraining context length.
    rope_scaling_factor: float = 1.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_coeff: float = 0.1
    # HF `attention_factor`: what YaRN multiplies cos and sin by, where the
    # model states it (None: 1 + yarn_mscale_coeff ln(rope_scaling_factor)).
    yarn_attention_factor: Optional[float] = None
    add_qkv_bias: bool = False
    add_bias_linear: bool = True
    qk_layernorm: bool = False
    attn_mask_type: AttnMaskType = AttnMaskType.causal
    untie_embeddings_and_output_weights: bool = False

    # Dropout (structural parity; usually 0 for LLM pretraining).
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0

    # Initialization.
    init_method_std: float = 0.02

    # Softmax / logits details (reference: apply_query_key_layer_scaling etc.).
    attention_softmax_in_fp32: bool = True
    apply_query_key_layer_scaling: bool = False
    # Four scalar facts of a model (HF `granite*`: embedding_multiplier,
    # attention_multiplier, residual_multiplier, logits_scaling), none a
    # tuning knob: the embedding row is multiplied by the first; the
    # attention scores are q.k times the second IN PLACE of 1 / sqrt(head
    # dim) (None: that); each half's output is multiplied by the third
    # before it joins the residual stream; the logits are DIVIDED by the
    # fourth. Plain attention layers on the plain and paged paths read the
    # second (no MLA, EVA or cp ring).
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # MoE (reference: transformer_config.py moe_* fields; moe/ directory).
    num_moe_experts: Optional[int] = None
    moe_router_topk: int = 2
    moe_ffn_hidden_size: Optional[int] = None
    moe_aux_loss_coeff: float = 0.0
    moe_z_loss_coeff: float = 0.0
    moe_shared_expert_intermediate_size: Optional[int] = None
    moe_capacity_factor: Optional[float] = None
    # Layer frequency: 1 = every layer is MoE; k = every k-th layer.
    moe_layer_freq: int = 1
    # Whether the router divides the k chosen probabilities by their sum
    # (HF `norm_topk_prob`; Mixtral does, DeepSeek-V2-Lite does not), and
    # the factor on the routed experts' output (HF `routed_scaling_factor`).
    moe_router_norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 1.0
    # The first k layers are dense MLPs of width ffn_hidden_size, the
    # rest MoE (HF `first_k_dense_replace`; DeepSeek-V2/V3: 1 or 3). They
    # run as a prologue before the scanned stack (params["lead_block"]).
    moe_first_k_dense: int = 0
    # Model facts of a router wider than the experts a layer computes (HF
    # `longcat_flash`), none of them a tuning knob:
    # moe_zero_experts: zero-compute experts behind the num_moe_experts
    #   computing ones in the router's softmax (HF `zero_expert_num`, type
    #   identity): a pick of one returns the MoE's own input times its weight
    #   and costs no GEMM row.
    # moe_router_selection_bias: the router holds a bias b ("router_bias",
    #   one entry a routed expert, zero-compute ones included): the top-k is
    #   taken on p + b, the weights stay p (HF `e_score_correction_bias`).
    # moe_experts_held: (first, count), the experts of the published
    #   num_moe_experts whose weights this layer holds (one rank of an
    #   expert-parallel deployment). It routes over all of them, computes its
    #   own experts' terms and the identity term, and leaves the others' out.
    # moe_shortcut_double_layer: a layer is two attention sublayers and two
    #   dense FFNs, with one MoE on the first sublayer's normed output whose
    #   result is added after the second FFN (transformer/block.py); it owns
    #   two planes of the KV pools. moe_first_k_dense stays 0 with it.
    # moe_router_score: what the router's scores are, "softmax" over its
    #   width or an elementwise "sigmoid" (HF `lfm2_moe`: the top-k is taken
    #   on sigmoid(logits) + b where there is a selection bias, the weights
    #   are the chosen scores themselves, and norm_topk_prob divides them by
    #   their sum + 1e-6, the published constant). Sigmoid scores have no
    #   load-balance or z loss defined here.
    moe_zero_experts: int = 0
    moe_router_selection_bias: bool = False
    moe_router_score: str = "softmax"
    moe_experts_held: Optional[Tuple[int, int]] = None
    moe_shortcut_double_layer: bool = False

    # A stack of several kinds of layer has two spellings, which are the
    # models' own, and ONE plan (stack_plan, below) that everything behind
    # this file reads. This one (HF `jamba`, `granitemoehybrid`:
    # attn_layer_period / attn_layer_offset): layer i attends iff i % period
    # == offset, and every other layer's first half is a selective-state-
    # space mixer (transformer/ssm.py). None = every layer attends. The
    # ssm_* fields are that mixer's sizes (HF mamba_d_state, mamba_d_conv,
    # mamba_expand, mamba_dt_rank; None = ceil(hidden / 16)) and Jamba's
    # RMS norms on dt, B and C. The convolution has a bias and the two
    # projections none (HF mamba_conv_bias true, mamba_proj_bias false).
    # Which mixer it is, is a fact of the model: with ssm_heads (HF
    # mamba_n_heads) it is Mamba-2: ssm_heads heads of ssm_head_dim columns
    # (HF mamba_d_head / mamba_head_dim; the inner width is heads x head_dim,
    # ssm_inner, whatever ssm_expand says: `nemotron_h` has 64 x 64 = 4096
    # beside a hidden size of 2688), each with a matrix state [head_dim,
    # ssm_state_dim], one scalar decay and one dt a head, B and C [ssm_groups,
    # ssm_state_dim] a token, shared by the ssm_heads / ssm_groups heads of a
    # group (HF mamba_n_groups / n_groups: head h reads group h // (heads /
    # groups)), a gated RMS norm before the output projection that runs over
    # each group's columns alone, and a prefill that runs as matrix products
    # over chunks of ssm_chunk_size positions (HF mamba_chunk_size). Without
    # ssm_heads it is Mamba-1 (a vector state [ssm_state_dim] a channel,
    # ssm_expand x hidden_size of them).
    # shortconv_kernel > 0 makes every non-attention layer's first half a
    # gated short convolution instead (HF `lfm2` / `lfm2_moe`: layer_types
    # "conv", conv_L_cache taps, conv_bias false; transformer/shortconv.py):
    # no recurrence h, its whole state is the convolution's last
    # shortconv_kernel - 1 gated inputs. Any of these stacks may run MoE
    # feed-forwards behind moe_first_k_dense leading dense ones.
    attn_layer_period: Optional[int] = None
    attn_layer_offset: int = 0
    # The other spelling: a stack whose layers are ONE sublayer each (HF
    # `nemotron_h`: hybrid_override_pattern): x' = x + Sub_i(norm(x)) once a
    # layer, the i-th letter of the pattern saying which: "M" a state-space
    # mixer, "*" attention, "E" the experts (with their shared expert), "-"
    # a dense feed-forward. One letter a layer; no period and no offset, so
    # attn_layer_period stays None. An "E" or "-" layer owns no plane of any
    # pool. The residual-out projections are initialised at std /
    # sqrt(depth): a layer adds to the stream once.
    layer_pattern: Optional[str] = None
    # The depth that the scaled init of the residual-out projections divides
    # by (std / sqrt(2 x depth)): the whole model's where this configuration
    # is a stage or a share of it, so that a stage is initialised as the
    # model's layers are. None = num_layers. Read by the plain and hybrid
    # stacks of transformer/block.py.
    scaled_init_layers: Optional[int] = None
    ssm_state_dim: int = 16
    ssm_conv_kernel: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: Optional[int] = None
    ssm_inner_norms: bool = False
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_chunk_size: int = 256
    shortconv_kernel: int = 0
    # kda_heads > 0 makes every non-attention layer's first half a Kimi
    # delta attention mixer instead (Kimi Linear, arXiv:2510.26692; HF
    # `solar_open2`: linear_attn_config, kda_*; transformer/kda.py): kda_heads
    # heads, each a matrix state S [ssm_state_dim, ssm_head_dim] (key
    # channels x value columns) under the gated delta rule, S' = diag(a) S;
    # S = S' + b k (v - S'^T k)^T, with a decay a KEY CHANNEL a in (0, 1)
    # and b in (0, 2); q, k and v each through a causal convolution of
    # ssm_conv_kernel taps; the decay and the output gate from low-rank
    # projections of the head's width (rank ssm_head_dim). Its state
    # lives in the state-space mixers' tenant ([L, slots, ssm_state_dim,
    # ssm_inner] float32 and one tail row over q, k and v), and a prefill
    # runs in chunks of ssm_chunk_size positions.
    kda_heads: int = 0

    # Sliding-window attention layers beside full ones in one stack (HF
    # `laguna`: layer_types, sliding_window, num_attention_heads_per_layer,
    # a rope_parameters group a layer kind, gating). With sliding_window > 0
    # the layers of a hybrid stack (attn_layer_period / attn_layer_offset)
    # that are NOT its full-attention layers are attention layers too, whose
    # query at position t sees the keys t - sliding_window + 1 .. t (HF:
    # t - s < sliding_window): no state-space or convolution mixer then. A
    # window layer may differ from a full one in three things, each a fact
    # of the model: its query heads (sliding_window_heads over the same
    # num_query_groups key/value heads; None = num_attention_heads), and its
    # rotary table, which is plain RoPE at sliding_rotary_base over
    # sliding_rotary_percent of a head whatever position_embedding says of
    # the full layers (None = the full layers' table). Its cached rows live
    # in planes of their own that give a slot's blocks back as they fall
    # behind the window (inference/paged_cache.py).
    # attention_output_gate: every attention layer multiplies each head's
    # output by sigmoid(u w_g) of the layer's normed input u before the
    # output projection ("gate_kernel" [hidden, heads]); with
    # attention_gate_elementwise the gate is one an ELEMENT of the heads'
    # outputs ("gate_kernel" [hidden, heads x head_dim]: the G1 form of Qiu et
    # al., arXiv:2505.06708; HF `solar_open2` use_gqa_gate).
    sliding_window: int = 0
    sliding_window_heads: Optional[int] = None
    sliding_rotary_base: Optional[float] = None
    sliding_rotary_percent: float = 1.0
    attention_output_gate: bool = False
    attention_gate_elementwise: bool = False

    # EVA attention (HF `evabyte`: attention_class "eva", window_size,
    # chunk_size; Zheng et al. 2023, "Efficient Attention via Control
    # Variates", transformer/eva.py): a query sees the rows of its own
    # aligned window of eva_window_size positions exactly and ONE pooled
    # key/value row for every eva_chunk_size positions of each earlier
    # window, under one softmax. 0 = plain attention. Each attention layer
    # then holds two more leaves a key/value head, eva_phi and eva_mu.
    eva_window_size: int = 0
    eva_chunk_size: int = 0
    # RMSNorm / LayerNorm whose scale is 1 + g (HF norm_add_unit_offset):
    # the layers' two norms and the final one; g starts at 0.
    norm_unit_offset: bool = False
    # The head has num_pred_heads x vocab_size columns (HF num_pred_heads:
    # head j predicts token t+1+j); generation samples the first
    # vocab_size of them. Needs an untied head.
    num_pred_heads: int = 1

    # Multi-token prediction (DeepSeek-V3; reference
    # multi_token_prediction.py + transformer_config mtp_num_layers /
    # mtp_loss_scaling_factor).
    mtp_num_layers: Optional[int] = None
    mtp_loss_scaling_factor: float = 0.1

    # Multi-latent attention (DeepSeek-style MLA; reference multi_latent_attention.py:44).
    multi_latent_attention: bool = False
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    # HF `mla_scale_q_lora` / `mla_scale_kv_lora`: the expanded query and
    # the normed latent are multiplied by sqrt(hidden_size / rank)
    # (transformer/mla.py); the latent is cached scaled.
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    qk_head_dim: int = 128
    qk_pos_emb_head_dim: int = 64
    v_head_dim: int = 128

    # dtype policy: params kept in fp32, compute in bf16 (TPU-native mixed precision).
    params_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    # Rematerialization policy for the layer scan:
    # 'none' | 'full' | 'selective' | 'selective_attn'.
    # 'selective' checkpoints only attention internals (reference
    # --recompute-activations semantics, arguments.py recompute group);
    # 'selective_attn' additionally saves the attention outputs so the
    # flash kernel forward is not re-executed in the backward pass.
    remat_policy: str = "selective"

    # Context-parallel attention mode (reference cp_comm_type,
    # transformer_config.py:458-462): 'p2p' ring / 'a2a' Ulysses /
    # 'allgather'.
    cp_comm_type: str = "p2p"
    # Inner all-to-all group size for cp_comm_type='a2a+p2p' (reference
    # --hierarchical-context-parallel-sizes inner dimension).
    hierarchical_cp_a2a_size: int = 2
    # Causal 'p2p' ring uses the load-balanced zigzag layout (rank i holds
    # chunks i and 2cp-1-i — the reference's TE ring behavior). Disable to
    # force the contiguous-layout ring (debug/oracle comparisons).
    cp_zigzag: bool = True
    # Latency-hiding contiguous ring attention (ops/context_parallel.py):
    # every KV-block ppermute hop is issued before the block compute it
    # feeds, and the p2p ring carries a custom_vjp whose backward runs the
    # symmetric reverse ring fused with the dK/dV accumulation (one pass,
    # accumulators travel with their blocks). Disable to fall back to the
    # plain unrolled ring differentiated by autodiff (debug/A-B baselines).
    cp_comm_overlap: bool = True
    # Latency-hiding MoE expert dispatch (transformer/moe.py
    # _chunked_a2a_ffn): the ep token exchange is decomposed into per-peer
    # ppermute hops, each issued before the expert GEMMs on the
    # previously-arrived chunk (results return the same way). Disable for
    # the bulk two-all_to_all dispatch (debug/A-B baselines).
    moe_comm_overlap: bool = True

    # Kernel implementation selection (spec_utils.py ModuleSpec analogue):
    # 'reference' = pure jnp; 'pallas' = fused Pallas flash attention;
    # 'auto' = on TPU, pallas from S 2048 on and wherever the call's dense
    # scores were measured to leave the chip or would not fit, from the
    # call's own shapes (ops/pallas/flash_attention.py choose_attention);
    # reference elsewhere.
    attention_impl: str = "auto"

    # Latency-hiding tensor-parallel matmuls (reference --tp-comm-overlap;
    # parallel/overlap.py): replace the GSPMD column/row-parallel
    # projections in attention/MLP with manual ring all-gather-matmul /
    # matmul-reduce-scatter so the tp collective hops ride under the
    # dependent GEMM chunks. Chunk count auto-derives from the tp degree.
    # Defaults off; ineligible layouts (tp=1, cp>1, inside a manual pp
    # region, indivisible projection dims) silently keep the GSPMD path.
    tp_comm_overlap: bool = False

    # tp-SHARDED stage bodies inside the full-manual pp pipeline
    # (parallel/pipeline.py tp_shard + overlap.py tp_stage_eligible):
    # activations shard over tp along the sequence between stages and the
    # stage projections run the manual ring primitives on per-shard weight
    # slices — tp× fewer stage FLOPs and tp× smaller pp hops than the
    # tp-replicated body. On by default wherever eligible (cp == 1,
    # divisible S/heads/ffn); this is the A/B kill-switch
    # (--no-tp-sharded-stage) forcing the replicated baseline.
    # tp_comm_overlap picks ring (True) vs bulk (False) collectives
    # INSIDE the sharded body.
    tp_sharded_stage: bool = True

    # Tiles of the Pallas flash kernels. Unset: from the call's S
    # (ops/pallas/flash_attention.py flash_tiles); a value is honoured.
    flash_block_q: Optional[int] = None
    flash_block_kv: Optional[int] = None

    # lax.scan unroll factor for the layer stack (PERF.md lever #3:
    # unrolling lets XLA software-pipeline across layer boundaries at
    # the cost of code size/compile time). Must divide num_layers.
    # Honored by training (block_forward) AND the serving decode /
    # multi-query step scans (ISSUE 11) — unrolling the decode layer
    # loop removes its while-iteration dispatch overhead.
    scan_unroll: int = 1

    # Head-fold flash BACKWARD kernels (PERF.md lever #1, ISSUE 11,
    # --flash-head-fold): fold q-head pairs into the trailing block dim
    # (D=64 → full 128-lane vreg rows for every q/do load and gradient
    # accumulator, half the grid's head extent). Opt-in A/B knob until
    # the on-chip numbers land; ineligible layouts (2D > 128, odd head
    # counts, packed segments) silently keep the standard kernels.
    flash_head_fold: bool = False

    # fp8 (e4m3) training GEMMs with delayed-scaling amax history
    # (ISSUE 13, --fp8): the tp-overlap ring matmuls quantize both
    # operands to fp8 with per-(layer, site, tensor) scales derived
    # from an amax history threaded through the train state
    # (training/fp8.py). Requires tp_comm_overlap on a tp>1, pp==1,
    # cp==1, dense non-MLA/non-MoE layout (fp8_ineligible_reason names
    # the first failed predicate). fp8_margin: scale = FP8_MAX /
    # (amax * 2**margin) — headroom against inter-step amax growth.
    # fp8_amax_history_len: history window H (TE-default-ish 16; the
    # scale follows max over the window).
    fp8: bool = False
    fp8_margin: int = 0
    fp8_amax_history_len: int = 16

    # Heterogeneous per-layer structure (reference
    # heterogeneous_config.py HeterogeneousTransformerConfig): the HF
    # Nemotron "block_configs" JSON (encoded string). When set, layers
    # follow their individual specs (no-op / linear-replacement /
    # per-layer GQA + FFN sizes) and the block unrolls instead of
    # scanning.
    heterogeneous_layers_config_json: Optional[str] = None

    def __post_init__(self):
        self.hetero_block_specs = None
        if self.heterogeneous_layers_config_json:
            from megatronapp_tpu.transformer.heterogeneous import (
                parse_block_configs,
            )
            self.hetero_block_specs = parse_block_configs(
                self.heterogeneous_layers_config_json,
                num_attention_heads=self.num_attention_heads,
                hidden_size=self.hidden_size)
        if self.ffn_hidden_size is None:
            if self.activation in (ActivationKind.swiglu, ActivationKind.geglu):
                self.ffn_hidden_size = int(4 * self.hidden_size * 2 / 3)
            else:
                self.ffn_hidden_size = 4 * self.hidden_size
        if self.kv_channels is None:
            self.kv_channels = self.hidden_size // self.num_attention_heads
        if self.num_query_groups is None:
            self.num_query_groups = self.num_attention_heads
        if self.num_attention_heads % self.num_query_groups != 0:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must be divisible by "
                f"num_query_groups ({self.num_query_groups})"
            )
        if self.num_moe_experts is not None and self.moe_ffn_hidden_size is None:
            self.moe_ffn_hidden_size = self.ffn_hidden_size
        if self.moe_first_k_dense and not (
                self.is_moe and self.moe_layer_freq == 1
                and 0 < self.moe_first_k_dense < self.num_layers):
            raise ValueError(
                f"moe_first_k_dense={self.moe_first_k_dense} needs an MoE "
                f"model with moe_layer_freq 1 and more than that many "
                f"layers (num_layers={self.num_layers})")
        if ((self.moe_zero_experts or self.moe_router_selection_bias
             or self.moe_experts_held or self.moe_shortcut_double_layer)
                and not (self.is_moe and self.moe_layer_freq == 1
                         and self.moe_capacity_factor is None)):
            raise ValueError(
                "moe_zero_experts, moe_router_selection_bias, "
                "moe_experts_held and moe_shortcut_double_layer are facts of "
                "a dropless MoE model with experts in every layer "
                "(num_moe_experts, moe_layer_freq 1, no capacity factor)")
        if self.moe_experts_held is not None:
            first, count = self.moe_experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.num_moe_experts):
                raise ValueError(
                    f"moe_experts_held={self.moe_experts_held} is no "
                    f"(first, count) within num_moe_experts="
                    f"{self.num_moe_experts}")
        if self.moe_shortcut_double_layer and (
                self.moe_first_k_dense or self.mtp_num_layers
                or self.moe_shared_expert_intermediate_size
                or self.heterogeneous_layers_config_json):
            raise ValueError(
                "the shortcut-connected double layer is one uniform stack "
                "with no shared expert: no moe_first_k_dense, MTP or "
                "heterogeneous block configs")
        if (self.mla_scale_q_lora and not self.q_lora_rank) or (
                (self.mla_scale_q_lora or self.mla_scale_kv_lora)
                and not self.multi_latent_attention):
            raise ValueError(
                "mla_scale_q_lora / mla_scale_kv_lora scale the latents of "
                "multi_latent_attention (the first needs q_lora_rank)")
        if self.vocab_slice_of is not None and not (
                self.vocab_size <= self.vocab_slice_of):
            raise ValueError(
                f"vocab_slice_of={self.vocab_slice_of} is the published "
                f"vocabulary that vocab_size={self.vocab_size} rows are a "
                "slice of")
        if self.attn_layer_period is not None:
            if not 0 <= self.attn_layer_offset < self.attn_layer_period:
                raise ValueError(
                    f"attn_layer_offset={self.attn_layer_offset} must lie "
                    f"in [0, attn_layer_period={self.attn_layer_period})")
            if (self.multi_latent_attention
                    or self.heterogeneous_layers_config_json):
                raise ValueError(
                    "a hybrid stack (attn_layer_period) runs plain "
                    "attention layers in its own layer loop: no MLA or "
                    "heterogeneous block configs")
            if self.is_moe and (
                    self.moe_layer_freq != 1 or self.moe_zero_experts
                    or self.moe_shortcut_double_layer or self.mtp_num_layers):
                raise ValueError(
                    "a hybrid stack's MoE feed-forwards sit in every layer "
                    "behind moe_first_k_dense leading dense ones: no "
                    "moe_layer_freq, moe_zero_experts, shortcut double "
                    "layer or MTP")
        if self.layer_pattern is not None:
            kinds = set(self.layer_pattern)
            if (len(self.layer_pattern) != self.num_layers
                    or kinds - set(PATTERN_KINDS)):
                raise ValueError(
                    f"layer_pattern={self.layer_pattern!r} names one "
                    f"sublayer a layer of num_layers={self.num_layers}, "
                    "each of " + ", ".join(
                        f"{k!r} ({what})"
                        for k, what in PATTERN_KINDS.items()))
            if (self.attn_layer_period is not None or self.moe_first_k_dense
                    or self.shortconv_kernel or self.sliding_window
                    or self.multi_latent_attention or self.is_eva
                    or self.moe_shortcut_double_layer or self.mtp_num_layers
                    or self.moe_zero_experts or self.residual_multiplier != 1
                    or self.heterogeneous_layers_config_json
                    or (self.is_moe and self.moe_layer_freq != 1)):
                raise ValueError(
                    "a layer_pattern stack is plain attention, state-space, "
                    "expert and dense sublayers, one a layer: the pattern "
                    "says which layers attend (no attn_layer_period) and "
                    "which hold experts (no moe_first_k_dense or "
                    "moe_layer_freq), and no short convolution, sliding "
                    "window, MLA, EVA, double layer, zero-compute experts, "
                    "MTP, residual multiplier or heterogeneous block "
                    "configs is written for it")
            if "E" in kinds and not self.is_moe:
                raise ValueError(
                    f"layer_pattern={self.layer_pattern!r}: its 'E' layers "
                    "are the model's experts (num_moe_experts)")
            if "M" in kinds and not self.ssm_heads:
                raise ValueError(
                    "a layer_pattern stack's 'M' layers are Mamba-2 mixers "
                    "(ssm_heads): Mamba-1 is not written for it")
        if self.ssm_heads:
            if ((self.attn_layer_period is None
                 and self.layer_pattern is None) or self.shortconv_kernel
                    or self.sliding_window or self.ssm_inner_norms
                    or self.ssm_groups < 1
                    or self.ssm_heads % self.ssm_groups
                    or self.ssm_chunk_size < 1):
                raise ValueError(
                    f"ssm_heads={self.ssm_heads} makes the state-space "
                    "layers of a hybrid stack (attn_layer_period or "
                    "layer_pattern; no shortconv_kernel, sliding_window or "
                    "ssm_inner_norms) Mamba-2 mixers of inner width "
                    f"ssm_heads x ssm_head_dim ({self.ssm_inner}; "
                    "ssm_expand is not read), whose B and C are shared by "
                    f"the heads of each of ssm_groups={self.ssm_groups} "
                    "groups: a whole number of heads a group")
        if self.kda_heads and (
                self.attn_layer_period is None or self.ssm_heads
                or self.shortconv_kernel or self.sliding_window
                or self.ssm_chunk_size < 1):
            raise ValueError(
                f"kda_heads={self.kda_heads} makes the non-attention layers "
                "of a hybrid stack (attn_layer_period) Kimi delta attention "
                "mixers: no ssm_heads, shortconv_kernel or sliding_window")
        if self.attention_gate_elementwise and not self.attention_output_gate:
            raise ValueError(
                "attention_gate_elementwise is the form of "
                "attention_output_gate's gate")
        if self.attention_multiplier is not None and (
                self.multi_latent_attention or self.is_eva):
            raise ValueError(
                "attention_multiplier replaces 1 / sqrt(head dim) in plain "
                "attention layers: no MLA, no EVA")
        if self.shortconv_kernel and (
                self.shortconv_kernel < 2 or self.attn_layer_period is None):
            raise ValueError(
                f"shortconv_kernel={self.shortconv_kernel} is the taps (at "
                "least 2) of the gated short convolution that the "
                "non-attention layers of a hybrid stack (attn_layer_period) "
                "run")
        if self.sliding_window:
            heads = self.sliding_window_heads or self.num_attention_heads
            if (self.sliding_window < 0 or self.attn_layer_period is None
                    or self.shortconv_kernel or self.is_eva
                    or heads % self.num_query_groups):
                raise ValueError(
                    f"sliding_window={self.sliding_window} is the keys a "
                    "window layer's query sees, in a stack whose full-"
                    "attention layers attn_layer_period / attn_layer_offset "
                    "name and whose other layers are sliding-window "
                    "attention (no shortconv_kernel, no EVA), with "
                    f"sliding_window_heads ({heads}) a multiple of "
                    f"num_query_groups ({self.num_query_groups})")
        elif (self.sliding_window_heads is not None
              or self.sliding_rotary_base is not None):
            raise ValueError(
                "sliding_window_heads and sliding_rotary_base describe the "
                "window layers of a sliding_window stack")
        if self.moe_router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_router_score={self.moe_router_score!r}: the router "
                "scores by 'softmax' or 'sigmoid'")
        if self.moe_router_score == "sigmoid" and (
                not self.is_moe or self.moe_aux_loss_coeff
                or self.moe_z_loss_coeff or self.moe_zero_experts):
            raise ValueError(
                "sigmoid router scores (moe_router_score) belong to an MoE "
                "model and have no load-balance loss, z loss or zero-compute "
                "experts defined: balance such a router by its selection "
                "bias (moe_router_selection_bias), which nothing here "
                "updates yet (ROADMAP M2)")
        if self.eva_window_size or self.eva_chunk_size:
            w, c = self.eva_window_size, self.eva_chunk_size
            if w <= 0 or c <= 0 or w % c:
                raise ValueError(
                    f"EVA attention needs eva_window_size ({w}) a positive "
                    f"multiple of eva_chunk_size ({c})")
            if (self.multi_latent_attention or self.mtp_num_layers
                    or self.attn_layer_period is not None
                    or self.heterogeneous_layers_config_json
                    or self.attn_mask_type != AttnMaskType.causal):
                raise ValueError(
                    "EVA attention (eva_window_size) runs plain causal "
                    "attention layers in one uniform stack: no MLA, MTP, "
                    "hybrid state-space stack or heterogeneous block "
                    "configs")
        if self.norm_unit_offset and (
                self.mtp_num_layers or self.heterogeneous_layers_config_json):
            raise ValueError(
                "norm_unit_offset covers the layers' two norms and the "
                "final norm: no MTP depth modules or heterogeneous block "
                "configs, which norm on their own")
        if self.num_pred_heads < 1 or (
                self.num_pred_heads > 1
                and not self.untie_embeddings_and_output_weights):
            raise ValueError(
                f"num_pred_heads={self.num_pred_heads} needs an untied "
                "head (untie_embeddings_and_output_weights)")
        from megatronapp_tpu.ops.context_parallel import CP_COMM_TYPES
        if self.cp_comm_type not in CP_COMM_TYPES:
            raise ValueError(
                f"cp_comm_type must be one of {sorted(CP_COMM_TYPES)} "
                f"('p2p' = ring, 'a2a' = Ulysses), got "
                f"{self.cp_comm_type!r}")

    @property
    def is_moe(self) -> bool:
        return self.num_moe_experts is not None

    @property
    def is_eva(self) -> bool:
        return self.eva_window_size > 0

    @property
    def head_dim(self) -> int:
        return self.kv_channels

    @property
    def stack_plan(self):
        """A stack of several kinds of layer, one entry a layer: the keys of
        the parameter tree's "block" whose stacks hold the layer's halves, in
        the order it runs them (each stack in layer order, so a layer's row
        of one is the number of earlier entries that name it). None: one
        uniform stack. The ONE place that says which layer is of which kind,
        from either spelling: a period of two-half layers (a mixer:
        "mixers_attn" where i % attn_layer_period == attn_layer_offset, else
        "mixers_swa" with sliding_window, "mixers_conv" with
        shortconv_kernel, "mixers_kda" with kda_heads, "mixers_ssm"; and a
        feed-forward: "ffn_lead", the
        dense one of the moe_first_k_dense leading layers, else "ffn"), or a
        layer_pattern of single-sublayer ones (PATTERN_STACKS). What walks,
        initialises or serves such a stack reads this (transformer/block.py
        layer_loop)."""
        return _stack_plan(
            self.num_layers, self.attn_layer_period, self.attn_layer_offset,
            self.layer_pattern, self.moe_first_k_dense,
            bool(self.shortconv_kernel), bool(self.sliding_window),
            bool(self.kda_heads))

    @property
    def hybrid_stack(self) -> bool:
        """Whether the layers are of more than one kind (stack_plan)."""
        return self.stack_plan is not None

    def _layers_holding(self, key: str) -> int:
        return sum(key in layer for layer in self.stack_plan or ())

    def layer_is_attention(self, i: int) -> bool:
        plan = self.stack_plan
        return plan is None or "mixers_attn" in plan[i]

    @property
    def num_attention_layers(self) -> int:
        """Layers that attend, and so own a plane of the KV cache."""
        if self.stack_plan is None:
            return self.num_layers
        return self._layers_holding("mixers_attn")

    @property
    def kv_planes(self) -> int:
        """Planes of the paged KV pools: one an attention sublayer."""
        return self.num_attention_layers * (
            2 if self.moe_shortcut_double_layer else 1)

    @property
    def moe_router_width(self) -> int:
        """What the router's softmax runs over: the published computing
        experts and, behind them, the zero-compute ones."""
        return (self.num_moe_experts or 0) + self.moe_zero_experts

    @property
    def moe_picks_unheld(self) -> bool:
        """Whether a router's pick may fall on no expert whose weights are
        held here: on a zero-compute expert, or on one held elsewhere."""
        return self.moe_experts_held is not None or self.moe_zero_experts > 0

    @property
    def moe_experts_here(self) -> Tuple[int, int]:
        """(first, count) of the computing experts whose weights are held."""
        return self.moe_experts_held or (0, self.num_moe_experts or 0)

    @property
    def num_window_layers(self) -> int:
        """Sliding-window attention layers. Each owns a plane of the WINDOW
        pools; num_attention_layers and kv_planes count the full layers
        alone."""
        return self._layers_holding("mixers_swa")

    @property
    def window_heads(self) -> int:
        """Query heads of a sliding-window layer."""
        return self.sliding_window_heads or self.num_attention_heads

    @property
    def num_recurrent_layers(self) -> int:
        """Layers whose mixer is no attention but one with a state a
        sequence."""
        return self.num_ssm_layers + self.num_conv_layers

    @property
    def num_ssm_layers(self) -> int:
        """Layers whose mixer keeps a matrix or vector state a slot beside
        a convolution's tail: state-space mixers, or Kimi delta attention
        (num_kda_layers; a model has one of the two)."""
        return self._layers_holding("mixers_ssm") + self.num_kda_layers

    @property
    def num_kda_layers(self) -> int:
        """Layers whose mixer is Kimi delta attention."""
        return self._layers_holding("mixers_kda")

    @property
    def num_conv_layers(self) -> int:
        """Layers whose mixer is a gated short convolution."""
        return self._layers_holding("mixers_conv")

    @property
    def num_moe_layers(self) -> int:
        """Layers that hold experts: the plan's "ffn" of an MoE model, or
        every layer of a uniform stack behind moe_first_k_dense dense
        ones."""
        if not self.is_moe:
            return 0
        if self.stack_plan is None:
            return self.num_layers - self.moe_first_k_dense
        return self._layers_holding("ffn")

    @property
    def ssm_inner(self) -> int:
        """E, the state-space mixer's inner width: Mamba-2's heads x
        head_dim, Mamba-1's ssm_expand x hidden_size; Kimi delta attention's
        heads x value columns."""
        if self.ssm_heads or self.kda_heads:
            return (self.ssm_heads or self.kda_heads) * self.ssm_head_dim
        return self.ssm_expand * self.hidden_size

    @property
    def ssm_conv_channels(self) -> int:
        """Columns the state-space mixer's causal convolution runs over, and
        so of a slot's cached tail: Mamba-1's expanded input; Mamba-2's x, B
        and C side by side; Kimi delta attention's q, k and v."""
        if self.kda_heads:
            return 2 * self.kda_heads * self.ssm_state_dim + self.ssm_inner
        if self.ssm_heads:
            return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_dim
        return self.ssm_inner

    @property
    def moe_counts_load(self) -> bool:
        """Whether the serving steps count the load of each held expert
        (moe.HELD_COUNTS, the most rows one expert got among them): where
        picks may miss the held experts, and where the router carries a
        selection bias, which exists to balance that load."""
        return self.moe_picks_unheld or self.moe_router_selection_bias

    def num_parameters(self) -> int:
        """Approximate parameter count (embedding + blocks + final norm)."""
        h = self.hidden_size
        v = self.vocab_size
        n_kv = self.num_query_groups
        d = self.head_dim
        per_layer = (
            h * (self.num_attention_heads * d)  # Q
            + 2 * h * (n_kv * d)  # K,V
            + (self.num_attention_heads * d) * h  # out proj
            + 2 * h  # ln
        )
        if self.activation in (ActivationKind.swiglu, ActivationKind.geglu):
            per_layer += 3 * h * self.ffn_hidden_size
        else:
            per_layer += 2 * h * self.ffn_hidden_size
        per_layer += 2 * h  # second ln
        total = v * h + per_layer * self.num_layers + 2 * h
        if self.position_embedding == PositionEmbeddingKind.learned_absolute:
            total += self.max_position_embeddings * h
        if self.untie_embeddings_and_output_weights:
            total += v * h
        return total
