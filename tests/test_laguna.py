"""Laguna-XS.2's architecture at tiny widths on the CPU (hidden 64, heads 6
full / 8 sliding over 2 key/value heads of 16, window 8, 8 experts top-2 + a
shared one, 5 layers in the published pattern: dense full, then sliding,
sliding, sliding, full): the program's plain forward against
perfbench/models/laguna.py's float32 reference; both rotary tables against
a direct formula; the gate, the router, the window chooser; the
configuration file and the preset against the sizes the issue states."""
import copy
import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.models.gpt import (
    gpt_forward, gpt_rope_tables, init_gpt_params,
)
from megatronapp_tpu.models.presets import PRESETS
from perfbench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
model = manifest.load_module("models", "laguna")
WINDOW = 8


def tiny_config(**over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "laguna-xs.2.json")) as f:
        config = json.load(f)
    config.update(copy.deepcopy(model.REHEARSAL))
    config.update(sliding_window=WINDOW, **over)
    return config


@pytest.fixture(scope="module")
def tiny():
    config = tiny_config()
    cfg = model.model_config(config, "float32", compute_dtype=jnp.float32,
                             remat_policy="none")
    params = model.init_params(cfg, 7)
    # a selection bias that moves picks, and norm scales off 1
    moe = params["block"]["ffn"]["moe"]
    moe["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), moe["router_bias"].shape)
    return config, cfg, params


def _reference(config, params, tokens, **kw):
    return model.reference_logits(
        params, config, tokens, jnp.zeros_like(tokens),
        jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape), **kw)


def _tokens(b, t, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 512, (b, t)),
                       jnp.int32)


def test_the_stack_is_laid_out_as_published(tiny):
    _, cfg, params = tiny
    block = params["block"]
    assert set(block) == {"mixers_attn", "mixers_swa", "ffn_lead", "ffn"}
    full, slide = block["mixers_attn"]["attention"], block[
        "mixers_swa"]["attention"]
    assert full["q_kernel"].shape == (2, 64, 6 * 16)
    assert slide["q_kernel"].shape == (3, 64, 8 * 16)
    assert full["kv_kernel"].shape[1:] == slide["kv_kernel"].shape[1:] == (
        64, 2 * 2 * 16)
    assert full["gate_kernel"].shape == (2, 64, 6)
    assert slide["gate_kernel"].shape == (3, 64, 8)
    assert block["ffn"]["moe"]["fc1_kernel"].shape == (4, 8, 64, 64)
    assert block["ffn"]["moe"]["shared_fc1"].shape == (4, 64, 64)
    assert (cfg.num_attention_layers, cfg.num_window_layers,
            cfg.num_recurrent_layers, cfg.kv_planes) == (2, 3, 0, 2)
    assert [cfg.layer_is_attention(i) for i in range(5)] == [
        True, False, False, False, True]


def test_plain_forward_against_the_reference(tiny):
    config, cfg, params = tiny
    tokens = _tokens(2, 40)
    logits, _ = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(params, tokens)
    ref = _reference(config, params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_packed_segments_against_the_reference(tiny):
    """Two sequences a row: the band and the causal mask stay inside a
    segment, positions restart."""
    config, cfg, params = tiny
    tokens = _tokens(1, 36, seed=3)
    segs = jnp.asarray([[0] * 21 + [1] * 15])
    pos = jnp.asarray([list(range(21)) + list(range(15))])
    logits, _ = gpt_forward(params, tokens, cfg, segment_ids=segs)
    ref = model.reference_logits(params, config, tokens, segs, pos)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    alone = _reference(config, params, tokens[:, 21:])
    np.testing.assert_allclose(np.asarray(ref[:, 21:]), np.asarray(alone),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("control", ["window-1", "window+1", "one-table",
                                     "no-gate", "no-bias"])
def test_each_wrong_model_moves_the_logits(tiny, control):
    """What the cell's limits have to tell from the model: a window of 7 or
    9, the full layers' rotary table on the sliding layers, no gate, a
    selection that ignores the bias."""
    config, _, params = tiny
    tokens = _tokens(2, 40)
    ref = _reference(config, params, tokens)
    wrong = _reference(config, params, tokens, control=control)
    assert float(jnp.max(jnp.abs(wrong - ref))) > 0.01
    # a window's edge shows only past the window
    if control.startswith("window"):
        np.testing.assert_allclose(np.asarray(wrong[:, :WINDOW - 1]),
                                   np.asarray(ref[:, :WINDOW - 1]),
                                   atol=1e-5)


def _direct_yarn(rot, theta, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies column by column, from the paper's formula."""
    def column(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(column(beta_fast)), 0)
    high = min(math.ceil(column(beta_slow)), rot - 1)
    out = []
    for i in range(rot // 2):
        plain = theta ** (-2 * i / rot)
        ramp = min(max((i - low) / max(high - low, 1), 0.0), 1.0)
        out.append(plain * (1 - ramp) + plain / factor * ramp)
    return np.asarray(out)


@pytest.mark.parametrize("which", ["program", "reference"])
def test_both_rotary_tables_against_a_direct_formula(which):
    """At the PUBLISHED sizes: a full layer rotates the first 64 of 128
    columns by YaRN's frequencies (theta 5e5, factor 64 over 4096, beta 64 /
    1) and scales cos and sin by the attention factor; a sliding layer all
    128 by theta 1e4."""
    cfg = PRESETS["laguna-xs.2"](num_layers=5)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "laguna-xs.2.json")) as f:
        config = json.load(f)
    pos = np.asarray([0, 1, 511, 4096, 19455])
    if which == "program":
        full = gpt_rope_tables(cfg, 1, positions=jnp.asarray(pos))
        slide = gpt_rope_tables(cfg, 1, positions=jnp.asarray(pos),
                                window=True)
    else:
        full = model.rope_tables(config, model.FULL, jnp.asarray(pos))
        slide = model.rope_tables(config, model.SLIDING, jnp.asarray(pos))
    assert full[0].shape == (5, 32) and slide[0].shape == (5, 64)
    inv = _direct_yarn(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    factor = 1.4158883083359672
    assert abs(factor - (1 + 0.1 * math.log(64))) < 1e-12
    angles = pos[:, None].astype(np.float64) * inv[None]
    np.testing.assert_allclose(np.asarray(full[0]), np.cos(angles) * factor,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(full[1]), np.sin(angles) * factor,
                               atol=2e-3)
    # the slowest columns are interpolated by 64, the fastest left alone
    assert abs(inv[0] - 1.0) < 1e-12
    assert abs(inv[-1] - 500000.0 ** (-62 / 64) / 64) < 1e-12
    plain = 10000.0 ** (-np.arange(0, 128, 2) / 128)
    angles = pos[:, None].astype(np.float64) * plain[None]
    np.testing.assert_allclose(np.asarray(slide[0]), np.cos(angles),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(slide[1]), np.sin(angles),
                               atol=2e-3)


def test_half_rotation_leaves_the_other_half_alone():
    from megatronapp_tpu.ops.rotary import apply_rope
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 2, 16)),
                    jnp.float32)
    pos = jnp.arange(3)
    angles = pos[:, None] * jnp.asarray([1.0, 0.5, 0.25, 0.125])
    out = apply_rope(x, jnp.cos(angles), jnp.sin(angles))
    np.testing.assert_array_equal(np.asarray(out[..., 8:]),
                                  np.asarray(x[..., 8:]))
    # pairs (i, i + 4) of the first 8 columns
    want = x[0, 1, 0, 1] * math.cos(0.5) - x[0, 1, 0, 5] * math.sin(0.5)
    assert abs(float(out[0, 1, 0, 1]) - float(want)) < 1e-6
    ref = model._rope(x, jnp.cos(angles)[None], jnp.sin(angles)[None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_zeroing_the_gate_halves_every_heads_output(tiny):
    """sigmoid(0) = 1/2: with W_g = 0 an attention layer's output is half
    what it is without a gate."""
    from megatronapp_tpu.transformer.attention import attention_forward
    _, cfg, params = tiny
    layer = jax.tree.map(lambda a: a[1],
                         params["block"]["mixers_swa"]["attention"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 12, 64)),
                    jnp.float32)
    cos, sin = gpt_rope_tables(cfg, 12, window=True)
    zeroed = dict(layer, gate_kernel=jnp.zeros_like(layer["gate_kernel"]))
    ungated = {k: v for k, v in layer.items() if k != "gate_kernel"}
    half, _ = attention_forward(zeroed, x, cfg, cos, sin, window=WINDOW)
    whole, _ = attention_forward(ungated, x, cfg, cos, sin, window=WINDOW)
    np.testing.assert_allclose(np.asarray(half), 0.5 * np.asarray(whole),
                               atol=1e-6)
    gated, _ = attention_forward(layer, x, cfg, cos, sin, window=WINDOW)
    assert float(jnp.max(jnp.abs(gated - half))) > 1e-4


def test_the_router_scores_by_sigmoid_selects_with_the_bias_and_scales():
    """The bias moves the choice and not the weights; the chosen scores are
    divided by their sum and multiplied by 2.5."""
    from megatronapp_tpu.transformer import moe
    cfg = TransformerConfig(
        num_layers=2, hidden_size=16, num_attention_heads=2,
        num_moe_experts=8, moe_router_topk=2, moe_ffn_hidden_size=8,
        moe_router_score="sigmoid", moe_router_selection_bias=True,
        moe_router_norm_topk_prob=True, moe_routed_scaling_factor=2.5)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.zeros((8,)).at[3].set(10.0)
    s = np.asarray(jax.nn.sigmoid(x @ kernel))
    for b in (jnp.zeros((8,)), bias):
        got = model.router_weights(x, kernel, b, 2, 2.5)
        for t in range(5):
            top = np.argsort(-(s[t] + np.asarray(b)))[:2]
            want = np.zeros(8)
            want[top] = s[t, top] / (s[t, top].sum() + 1e-6) * 2.5
            np.testing.assert_allclose(np.asarray(got[t]), want, atol=1e-6)
    assert (np.asarray(model.router_weights(x, kernel, bias, 2, 2.5))[:, 3]
            > 0).all()
    idx, probs, _ = moe._router({"router_kernel": kernel,
                                 "router_bias": bias}, x, cfg)[:3]
    dense = np.zeros((5, 8))
    for t in range(5):
        dense[t, np.asarray(idx[t])] = np.asarray(probs[t])
    np.testing.assert_allclose(
        dense, np.asarray(model.router_weights(x, kernel, bias, 2, 2.5)),
        atol=1e-6)


def test_a_window_layer_is_chosen_for_like_a_full_one():
    """Since the flash kernels have a window term (PR 48) a window layer's
    whole sequences go where a full layer's go, and say their window."""
    from megatronapp_tpu.ops.pallas.flash_attention import choose_attention
    kw = dict(batch=1, seq=4096, heads=64, head_dim=128, dtype=jnp.bfloat16,
              segments=False, backend="tpu")
    assert choose_attention(impl="auto", **kw).impl == "pallas"
    for impl in ("auto", "pallas"):
        choice = choose_attention(impl=impl, window=512, **kw)
        assert choice.impl == "pallas"
        assert choice.why == "S=4096 D=128 window 512"
    assert choose_attention(impl="reference", window=512, **kw).impl == (
        "reference")
    assert choose_attention(impl="auto", window=512,
                            **dict(kw, backend="cpu")).impl == "reference"


@pytest.mark.parametrize("bad,match", [
    (dict(sliding_window=8), "sliding_window=8"),
    (dict(sliding_window=8, attn_layer_period=2, shortconv_kernel=3),
     "sliding_window=8"),
    (dict(sliding_window=8, attn_layer_period=2, num_query_groups=2,
          sliding_window_heads=5), "multiple of"),
    (dict(sliding_window_heads=8), "describe the window layers"),
    (dict(sliding_rotary_base=1e4), "describe the window layers"),
])
def test_the_configuration_refuses_what_the_stack_is_not(bad, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(num_layers=4, hidden_size=64,
                          num_attention_heads=4, **bad)


def test_what_a_window_layer_cannot_run_with_is_said():
    from megatronapp_tpu.transformer.attention import (
        attention_forward, init_attention_params,
    )
    cfg = TransformerConfig(num_layers=2, hidden_size=32,
                            num_attention_heads=2, attn_layer_period=2,
                            sliding_window=4, compute_dtype=jnp.float32)
    p, _ = init_attention_params(jax.random.PRNGKey(0), cfg, 0.02)
    x = jnp.zeros((1, 8, 32))
    cache = (jnp.zeros((1, 8, 2, 16)),) * 2
    with pytest.raises(NotImplementedError, match="dense .unpaged. cache"):
        attention_forward(p, x, cfg, kv_cache=cache, cache_index=0,
                          window=4)


def test_the_configuration_file_is_the_catalog_rows_but_for_its_cut():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "laguna-xs.2.json")) as f:
        mine = json.load(f)
    assert mine["reduced"] == ["num_hidden_layers", "layer_types",
                               "mlp_layer_types",
                               "num_attention_heads_per_layer"]
    assert mine["num_hidden_layers"] == 5
    assert mine["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert mine["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert mine["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    pub = mine["published"]
    assert pub["num_hidden_layers"] == 40 == len(pub["layer_types"])
    for key in mine["reduced"][1:]:
        assert mine[key] == pub[key][:5], key
    for key, value in {
            "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 48,
            "num_key_value_heads": 8, "intermediate_size": 8192,
            "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "num_experts": 256,
            "num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5,
            "sliding_window": 512, "vocab_size": 100352,
            "rms_norm_eps": 1e-6, "gating": True,
            "max_position_embeddings": 262144}.items():
        assert mine[key] == value, key
    assert len(mine["source"]) <= 200
    assert set(mine["assumed"]) >= {"gate", "router", "qk_norm",
                                    "shared_expert_gate"}
    for key in ("gate", "router", "qk_norm", "shared_expert_gate"):
        assert set(mine["assumed"][key]) == {"value", "what", "evidence"}
    assert "eight pipeline stages of five layers" in mine["deployment"]
    assert "3,869.9M parameters = 7.74 GB" in mine["reduced_why"]
    # the file parses to the sizes the issue states
    assert round(model.params_count(mine) / 1e6, 1) == 3869.9
    assert model.kv_bytes_per_token(mine, "bfloat16") == 2 * 4096
    assert model.window_bytes_per_token(mine, "bfloat16") == 3 * 4096
    cfg = model.model_config(mine, "bfloat16")
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    counted = sum(a.size for a in jax.tree.leaves(abstract))
    assert counted == model.params_count(mine)
    assert counted * 2 / 1e9 == pytest.approx(7.74, abs=0.005)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row, = (json.loads(ln) for ln in f if '"Laguna-XS.2"' in ln)
    assert mine["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
        else:
            assert mine["published"][key] == value, key


def test_the_preset_is_the_file_at_its_published_depth():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "laguna-xs.2.json")) as f:
        mine = json.load(f)
    cut = dataclasses.asdict(model.model_config(mine, "bfloat16"))
    preset = dataclasses.asdict(PRESETS["laguna-xs.2"](
        num_layers=5, params_dtype=jnp.bfloat16))
    assert cut == preset
    whole = PRESETS["laguna-xs.2"]()
    assert (whole.num_layers, whole.num_attention_layers,
            whole.num_window_layers) == (40, 10, 30)
    published = dict(mine, **mine["published"])
    assert round(model.params_count(published) / 1e9, 2) == 33.44


def test_the_scope_map_tells_a_window_layers_attention_apart():
    """`window` is no part of its own: a window layer's operations stay in
    `attention` (attention_ms_round is the two kinds' sum) and carry the
    sub-part."""
    from megatronapp_tpu.trace import scope_map
    assert "window" not in scope_map.PARTS
    assert scope_map.part_of(
        "jit(step)/while/body/attention/window/dot_general") == (
        "attention", "fwd")
    assert scope_map.sub_of(
        "jit(step)/while/body/attention/window/dot_general") == "window"
    assert scope_map.sub_of("jit(step)/attention/dot_general") == ""
    assert scope_map.sub_of("jit(step)/moe/window/dot_general") == ""
