"""Checkpoint converter: HuggingFace ↔ megatronapp-tpu parameter pytrees.

Parity with /root/reference/tools/checkpoint/convert.py (+ loader/saver
plugins for llama/mistral/HF models): maps HF transformer weights into our
functional param layout (models/gpt.py) and saves an Orbax checkpoint that
pretrain_gpt --load / the inference server can consume.

Usage:
  python tools/checkpoint/convert.py --model-type gpt2 \
      --hf-path /path/to/hf_model --save-dir /ckpts/gpt2
  python tools/checkpoint/convert.py --model-type llama \
      --hf-path meta-llama/... --save-dir /ckpts/llama
"""

import argparse
import sys

sys.path.insert(0, __file__.rsplit("/tools/", 1)[0])

import numpy as np


def convert_gpt2_state_dict(sd, cfg):
    """HF GPT-2 state dict → our GPT param pytree.

    HF GPT-2 uses Conv1D ([in, out] kernels — no transpose needed) with a
    fused c_attn [H, 3H]."""
    import jax.numpy as jnp

    h = cfg.hidden_size

    def t(name):
        return np.asarray(sd[name], np.float32)

    layers = {}
    per_layer = []
    for i in range(cfg.num_layers):
        pre = f"h.{i}."
        c_attn_w = t(pre + "attn.c_attn.weight")   # [H, 3H]
        c_attn_b = t(pre + "attn.c_attn.bias")
        per_layer.append({
            "ln1_scale": t(pre + "ln_1.weight"),
            "ln1_bias": t(pre + "ln_1.bias"),
            "ln2_scale": t(pre + "ln_2.weight"),
            "ln2_bias": t(pre + "ln_2.bias"),
            "attention": {
                "q_kernel": c_attn_w[:, :h],
                "kv_kernel": c_attn_w[:, h:],
                "q_bias": c_attn_b[:h],
                "kv_bias": c_attn_b[h:],
                "out_kernel": t(pre + "attn.c_proj.weight"),
                "out_bias": t(pre + "attn.c_proj.bias"),
            },
            "mlp": {
                "fc1_kernel": t(pre + "mlp.c_fc.weight"),
                "fc1_bias": t(pre + "mlp.c_fc.bias"),
                "fc2_kernel": t(pre + "mlp.c_proj.weight"),
                "fc2_bias": t(pre + "mlp.c_proj.bias"),
            },
        })
    import jax
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)

    wte = t("wte.weight")
    vocab_pad = cfg.vocab_size - wte.shape[0]
    if vocab_pad > 0:  # pad vocab rows to the configured (TP-friendly) size
        wte = np.concatenate([wte, np.zeros((vocab_pad, h), np.float32)])
    return {
        "embedding": {
            "word": jnp.asarray(wte),
            "pos": jnp.asarray(t("wpe.weight")),
        },
        "block": layers,
        "final_ln_scale": jnp.asarray(t("ln_f.weight")),
        "final_ln_bias": jnp.asarray(t("ln_f.bias")),
    }


def convert_llama_state_dict(sd, cfg):
    """HF Llama state dict → our GPT param pytree (swiglu/rmsnorm/GQA).

    HF Linear kernels are [out, in] → transpose; gate/up fuse into our
    fc1 [H, 2F] with the GATE half first (transformer/mlp.py split order)."""
    import jax
    import jax.numpy as jnp

    def t(name):
        return np.asarray(sd[name], np.float32)

    def lin(name):
        return t(name).T  # [out,in] → [in,out]

    per_layer = []
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        k_w = lin(pre + "self_attn.k_proj.weight")
        v_w = lin(pre + "self_attn.v_proj.weight")
        gate = lin(pre + "mlp.gate_proj.weight")
        up = lin(pre + "mlp.up_proj.weight")
        per_layer.append({
            "ln1_scale": t(pre + "input_layernorm.weight"),
            "ln2_scale": t(pre + "post_attention_layernorm.weight"),
            "attention": {
                "q_kernel": lin(pre + "self_attn.q_proj.weight"),
                "kv_kernel": np.concatenate([k_w, v_w], axis=1),
                "out_kernel": lin(pre + "self_attn.o_proj.weight"),
            },
            "mlp": {
                "fc1_kernel": np.concatenate([gate, up], axis=1),
                "fc2_kernel": lin(pre + "mlp.down_proj.weight"),
            },
        })
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    p = {
        "embedding": {"word": jnp.asarray(t("model.embed_tokens.weight"))},
        "block": layers,
        "final_ln_scale": jnp.asarray(t("model.norm.weight")),
    }
    if "lm_head.weight" in sd:
        p["output"] = jnp.asarray(lin("lm_head.weight"))
    return p


def convert_mixtral_state_dict(sd, cfg):
    """HF Mixtral state dict → our MoE GPT param pytree.

    Parity with /root/reference/tools/checkpoint/loader_mixtral_hf.py
    (router gate + per-expert w1/w2/w3 mapping, :230-246). Attention and
    norms are Llama-shaped; each layer's MLP is a top-k router
    (block_sparse_moe.gate) plus experts whose w1 (gate) and w3 (up) fuse
    into our fc1 [E, H, 2F] — gate half first (transformer/moe.py
    _apply_act split order) — and w2 (down) becomes fc2 [E, F, H]."""
    import jax
    import jax.numpy as jnp

    def t(name):
        # pop: expert weights dominate host RAM at real Mixtral scale —
        # release each HF entry as it is consumed.
        return np.asarray(sd.pop(name), np.float32)

    def lin(name):
        return t(name).T

    e = cfg.num_moe_experts
    per_layer = []
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        k_w = lin(pre + "self_attn.k_proj.weight")
        v_w = lin(pre + "self_attn.v_proj.weight")
        fc1 = np.stack([
            np.concatenate(
                [lin(pre + f"block_sparse_moe.experts.{j}.w1.weight"),
                 lin(pre + f"block_sparse_moe.experts.{j}.w3.weight")],
                axis=1)
            for j in range(e)])                      # [E, H, 2F]
        fc2 = np.stack([
            lin(pre + f"block_sparse_moe.experts.{j}.w2.weight")
            for j in range(e)])                      # [E, F, H]
        per_layer.append({
            "ln1_scale": t(pre + "input_layernorm.weight"),
            "ln2_scale": t(pre + "post_attention_layernorm.weight"),
            "attention": {
                "q_kernel": lin(pre + "self_attn.q_proj.weight"),
                "kv_kernel": np.concatenate([k_w, v_w], axis=1),
                "out_kernel": lin(pre + "self_attn.o_proj.weight"),
            },
            "moe": {
                "router_kernel": lin(pre + "block_sparse_moe.gate.weight"),
                "fc1_kernel": fc1,
                "fc2_kernel": fc2,
            },
        })
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    p = {
        "embedding": {"word": jnp.asarray(t("model.embed_tokens.weight"))},
        "block": layers,
        "final_ln_scale": jnp.asarray(t("model.norm.weight")),
    }
    if "lm_head.weight" in sd:
        p["output"] = jnp.asarray(lin("lm_head.weight"))
    return p


def convert_clip_vision_tower(sd, vis_cfg, prefix="vision_tower."):
    """HF CLIP vision encoder → our ViT backbone params (models/vision.py).

    Keeps CLIP's pre-encoder layernorm as 'pre_ln_*' and OMITS the final
    norm: LLaVA reads an intermediate feature layer (vision_feature_layer,
    default -2) that is never post-normalized, so only the first
    vis_cfg.num_layers encoder layers are loaded."""
    import jax
    import jax.numpy as jnp

    pre = prefix + "vision_model."

    def t(name):
        return np.asarray(sd[pre + name], np.float32)

    def lin(name):
        return t(name).T

    # Conv patch embedding [H, C, p, p] → our matmul rows ordered
    # (p_row, p_col, channel) to match vision.patchify's flattening.
    conv = t("embeddings.patch_embedding.weight")
    h = conv.shape[0]
    patch_proj = conv.transpose(2, 3, 1, 0).reshape(-1, h)

    per_layer = []
    for i in range(vis_cfg.num_layers):
        lp = f"encoder.layers.{i}."
        k_w = lin(lp + "self_attn.k_proj.weight")
        v_w = lin(lp + "self_attn.v_proj.weight")
        k_b = t(lp + "self_attn.k_proj.bias")
        v_b = t(lp + "self_attn.v_proj.bias")
        per_layer.append({
            "ln1_scale": t(lp + "layer_norm1.weight"),
            "ln1_bias": t(lp + "layer_norm1.bias"),
            "ln2_scale": t(lp + "layer_norm2.weight"),
            "ln2_bias": t(lp + "layer_norm2.bias"),
            "attention": {
                "q_kernel": lin(lp + "self_attn.q_proj.weight"),
                "q_bias": t(lp + "self_attn.q_proj.bias"),
                "kv_kernel": np.concatenate([k_w, v_w], axis=1),
                "kv_bias": np.concatenate([k_b, v_b]),
                "out_kernel": lin(lp + "self_attn.out_proj.weight"),
                "out_bias": t(lp + "self_attn.out_proj.bias"),
            },
            "mlp": {
                "fc1_kernel": lin(lp + "mlp.fc1.weight"),
                "fc1_bias": t(lp + "mlp.fc1.bias"),
                "fc2_kernel": lin(lp + "mlp.fc2.weight"),
                "fc2_bias": t(lp + "mlp.fc2.bias"),
            },
        })
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    return {
        "patch_proj": jnp.asarray(patch_proj),
        "patch_bias": jnp.zeros((h,), jnp.float32),  # CLIP conv has no bias
        "cls_token": jnp.asarray(
            t("embeddings.class_embedding").reshape(1, 1, h)),
        "pos": jnp.asarray(t("embeddings.position_embedding.weight")),
        "pre_ln_scale": jnp.asarray(t("pre_layrnorm.weight")),
        "pre_ln_bias": jnp.asarray(t("pre_layrnorm.bias")),
        "block": layers,
        # no final_ln_*: feature layer is pre-norm (vit_backbone skips).
    }


def convert_llava_state_dict(sd, lm_cfg, vis_cfg):
    """HF LLaVA state dict → our {'vision','projector','lm'} VLM pytree
    (models/multimodal.py layout).

    Parity with /root/reference/tools/checkpoint/loader_llava.py /
    saver_llava.py: CLIP vision tower + 2-layer MLP projector + Llama LM."""
    import jax.numpy as jnp

    def lin(name):
        return np.asarray(sd[name], np.float32).T

    def t(name):
        return np.asarray(sd[name], np.float32)

    lm_sd = {k.removeprefix("language_model."): v for k, v in sd.items()
             if k.startswith("language_model.")}
    return {
        "vision": convert_clip_vision_tower(sd, vis_cfg),
        "projector": {
            "fc1": lin("multi_modal_projector.linear_1.weight"),
            "fc1_bias": t("multi_modal_projector.linear_1.bias"),
            "fc2": lin("multi_modal_projector.linear_2.weight"),
            "fc2_bias": t("multi_modal_projector.linear_2.bias"),
        },
        "lm": convert_llama_state_dict(lm_sd, lm_cfg),
    }


def llava_configs_from_hf(path):
    """Build (lm_cfg, vis_cfg, VitSpec) from an HF LLaVA config.json —
    the vision cfg keeps only the layers below vision_feature_layer."""
    import json
    import os

    import jax.numpy as jnp

    from megatronapp_tpu.config.transformer_config import (
        ActivationKind, NormKind, TransformerConfig,
    )
    from megatronapp_tpu.models.vision import VitSpec, vit_config

    with open(os.path.join(path, "config.json")) as f:
        js = json.load(f)
    strategy = js.get("vision_feature_select_strategy", "default")
    if strategy != "default":
        # vlm_forward drops CLS unconditionally (multimodal.py); a 'full'
        # checkpoint would convert silently but diverge from HF.
        raise SystemExit(
            f"vision_feature_select_strategy={strategy!r} unsupported: "
            "only 'default' (drop CLS) matches models/multimodal.py")
    tc, vc = js["text_config"], js["vision_config"]
    lm_cfg = TransformerConfig(
        num_layers=tc["num_hidden_layers"],
        hidden_size=tc["hidden_size"],
        num_attention_heads=tc["num_attention_heads"],
        num_query_groups=tc.get("num_key_value_heads"),
        ffn_hidden_size=tc["intermediate_size"],
        vocab_size=js.get("vocab_size", tc.get("vocab_size")),
        max_position_embeddings=tc.get("max_position_embeddings", 4096),
        activation=ActivationKind.swiglu,
        normalization=NormKind.rmsnorm, add_bias_linear=False,
        untie_embeddings_and_output_weights=True,
        layernorm_epsilon=tc.get("rms_norm_eps", 1e-6),
        compute_dtype=jnp.float32, remat_policy="none")
    # hidden_states[k] = output of encoder layer k (index 0 is the
    # embeddings), so a negative index -n keeps L+1-n layers and a
    # non-negative index k keeps exactly k layers.
    feature_layer = js.get("vision_feature_layer", -2)
    n_vis_layers = (feature_layer if feature_layer >= 0
                    else vc["num_hidden_layers"] + 1 + feature_layer)
    spec = VitSpec(image_size=vc["image_size"],
                   patch_size=vc["patch_size"], num_classes=0)
    vis_cfg = vit_config(
        num_layers=n_vis_layers, hidden_size=vc["hidden_size"],
        num_attention_heads=vc["num_attention_heads"],
        ffn_hidden_size=vc["intermediate_size"],
        vocab_size=1, max_position_embeddings=1 + spec.num_patches,
        layernorm_epsilon=vc.get("layer_norm_eps", 1e-5),
        compute_dtype=jnp.float32, remat_policy="none")
    return lm_cfg, vis_cfg, spec


CONVERTERS = {"gpt2": convert_gpt2_state_dict,
              "llama": convert_llama_state_dict,
              "mixtral": convert_mixtral_state_dict,
              "llava": None}  # llava builds cfgs from HF config.json


def load_hf_state_dict(path):
    """Load an HF checkpoint directory (safetensors or torch .bin)."""
    import os
    entries = {}
    names = [f for f in os.listdir(path)
             if f.endswith((".safetensors", ".bin"))]
    if not names:
        raise FileNotFoundError(f"no weight files in {path}")
    for f in sorted(names):
        full = os.path.join(path, f)
        if f.endswith(".safetensors"):
            from safetensors.numpy import load_file
            entries.update(load_file(full))
        else:
            import torch
            sd = torch.load(full, map_location="cpu", weights_only=True)
            entries.update({k: v.numpy() for k, v in sd.items()})
    # Strip common prefixes.
    return {k.removeprefix("transformer."): v for k, v in entries.items()}


def main():
    import jax

    from megatronapp_tpu.training.checkpointing import CheckpointManager

    ap = argparse.ArgumentParser()
    ap.add_argument("--model-type", required=True, choices=sorted(CONVERTERS))
    ap.add_argument("--hf-path", required=True)
    ap.add_argument("--save-dir", required=True)
    ap.add_argument("--preset", default=None)
    args = ap.parse_args()

    from megatronapp_tpu.models.presets import PRESETS
    sd = load_hf_state_dict(args.hf_path)
    if args.model_type == "llava":
        if args.preset:
            raise SystemExit("--preset is not supported for llava: model "
                             "geometry comes from the HF config.json")
        lm_cfg, vis_cfg, _spec = llava_configs_from_hf(args.hf_path)
        params = convert_llava_state_dict(sd, lm_cfg, vis_cfg)
    else:
        if args.preset:
            cfg = PRESETS[args.preset]()
        else:
            cfg = PRESETS[{"gpt2": "gpt2-125m",
                           "mixtral": "mixtral-8x7b"}.get(
                               args.model_type, "llama3-8b")]()
        params = CONVERTERS[args.model_type](sd, cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    mngr = CheckpointManager(args.save_dir, async_save=False)
    mngr.save(0, {"step": 0, "params": params, "opt_state": {}},
              force=True)
    mngr.wait()
    mngr.close()
    print(f"converted {n/1e6:.1f}M params → {args.save_dir}")


if __name__ == "__main__":
    main()
