"""DeepSeek-V2-Lite's architecture against its plain float32 reference
(perfbench/models/deepseek_v2.py: the published equations in jax.numpy, MLA
unabsorbed, every token through its experts), at tiny widths on the CPU with
seeded random weights: 1 dense + 2 MoE layers, 8 experts top-3 with 1 shared,
kv_lora 32, YaRN on. Each test fails if the mechanism it names is left out."""
import dataclasses
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.inference.dynamic_engine import (
    DynamicInferenceEngine, _paged_decode_step, _paged_multiquery_step,
)
from megatronapp_tpu.models.presets import PRESETS
from megatronapp_tpu.transformer.moe import routing_counts
from perfbench import manifest

from jitted import gpt_forward  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MODEL = manifest.load_module("models", "deepseek_v2")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "deepseek-v2-lite.json")) as f:
    PUBLISHED = json.load(f)
TINY = dict(PUBLISHED, **MODEL.REHEARSAL)

# float32 on both sides: what is left is the order of summation (the
# program absorbs kv_up into the query and sums experts in sorted groups, the
# reference expands keys and loops over experts); logits are ~0.2 in size and
# the two agree to 1e-6. A wrong scale, frequency, router weight or a missing
# shared expert moves them by 1e-3 or more (measured: renormalised router
# 1.7e-2, YaRN coefficient 0.1 instead of 0.0707 3.6e-3).
TOL_F32 = 1e-4
# bf16 activations and cache against the float32 reference on the same
# float32 weights: rounding to 8 bits of mantissa through 3 layers gives
# 3.6e-3 on logits of ~0.2 (measured); the limit is four times that. A
# wrong page, position or plane gives 0.1 or more.
TOL_BF16 = 1.5e-2


@functools.cache
def _model(compute_dtype=jnp.float32):
    """(cfg, params), built once for every case (none writes into the
    tree it is handed)."""
    cfg = MODEL.model_config(TINY, "float32", compute_dtype=compute_dtype)
    return cfg, MODEL.init_params(cfg, seed=5)


def _reference(params, tokens):
    tokens = jnp.asarray(tokens)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    return np.asarray(MODEL.reference_logits(
        params, TINY, tokens, jnp.zeros_like(tokens), pos))


@pytest.fixture(scope="module")
def reference_2x40():
    """The reference's logits of TestForward's two rows of 40 tokens."""
    return _reference(_model()[1], _tokens((2, 40)))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], shape).astype(np.int32)


class TestForward:
    def test_gpt_forward_matches_reference(self, reference_2x40):
        cfg, params = _model()
        assert cfg.moe_first_k_dense == 1 and cfg.num_layers == 3
        assert "mlp" in params["lead_block"] and "moe" in params["block"]
        toks = _tokens((2, 40))
        logits, _ = gpt_forward(params, jnp.asarray(toks), cfg)
        assert np.abs(np.asarray(logits) - reference_2x40).max() < TOL_F32

    @pytest.mark.parametrize("field,value", [
        ("moe_router_norm_topk_prob", True),
        ("moe_routed_scaling_factor", 2.0),
        ("yarn_mscale_coeff", 0.1),
    ])
    def test_each_architecture_field_is_live(self, field, value,
                                             reference_2x40):
        """The reference is DeepSeek-V2-Lite's: a router that renormalises
        its top-k, another routed scale or YaRN's default coefficient is
        another model and must not pass."""
        cfg, params = _model()
        other = dataclasses.replace(cfg, **{field: value})
        toks = _tokens((2, 40))
        logits, _ = gpt_forward(params, jnp.asarray(toks), other)
        assert np.abs(np.asarray(logits) - reference_2x40).max() \
            > 10 * TOL_F32

    def test_leading_dense_layer_is_not_skipped(self):
        cfg, params = _model()
        toks = _tokens((1, 24))
        without = dict(params)
        del without["lead_block"]
        logits, _ = gpt_forward(without, jnp.asarray(toks), cfg)
        assert np.abs(np.asarray(logits) - _reference(params, toks)).max() \
            > 10 * TOL_F32


MAX_LEN = 64


def _paged_steps(compute_dtype):
    """The engine's two step functions of TINY in a compute type, jitted."""
    cfg, _ = _model(compute_dtype)
    return (jax.jit(lambda *a: _paged_multiquery_step(*a, cfg, MAX_LEN)),
            jax.jit(lambda *a: _paged_decode_step(*a, cfg, MAX_LEN)))


_shared_steps = functools.cache(_paged_steps)   # compiled once a shape


def _prefill_then_decode(cfg, params, prompt, n_new, chunk=8, bs=4,
                         steps=_shared_steps):
    """The engine's two step functions on a hand-made page table: the
    prompt in [1, chunk] calls (the last one ragged), then n_new greedy
    decode steps. Returns (tokens fed, logits at every position, pools)."""
    max_len = MAX_LEN
    nb = max_len // bs
    dt = cfg.compute_dtype
    pages = (jnp.zeros((cfg.num_layers, nb, bs, cfg.kv_lora_rank), dt),
             jnp.zeros((cfg.num_layers, nb, bs, cfg.qk_pos_emb_head_dim), dt))
    table = jnp.arange(nb, dtype=jnp.int32)[None]
    active = jnp.ones((1,), bool)
    prefill, decode = steps(dt)
    rows, pos = [], 0
    while pos < len(prompt):
        count = min(chunk, len(prompt) - pos)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :count] = prompt[pos:pos + count]
        logits, _, pages = prefill(
            params, jnp.asarray(buf), pages, table,
            jnp.asarray([pos], jnp.int32), jnp.asarray([count], jnp.int32),
            active)
        rows.append(np.asarray(logits[0, :count]))
        pos += count
    seq = list(prompt)
    for _ in range(n_new):
        seq.append(int(np.argmax(rows[-1][-1])))
        logits, counts, pages = decode(
            params, jnp.asarray([[seq[-1]]], jnp.int32), pages, table,
            jnp.asarray([len(seq) - 1], jnp.int32), active)
        rows.append(np.asarray(logits))
    return np.asarray(seq, np.int32), np.concatenate(rows), pages, counts


class TestPagedLatentPool:
    @pytest.mark.parametrize("dtype,weights,above,below", [
        (jnp.float32, None, 0.0, TOL_F32),
        (jnp.bfloat16, None, TOL_F32, TOL_BF16),
        (jnp.bfloat16, jnp.bfloat16, TOL_F32, TOL_BF16)],
        ids=["fp32", "bf16", "bf16-weights"])
    def test_prefill_then_decode_matches_reference(self, dtype, weights,
                                                   above, below):
        """Chunked prefill (20 tokens in chunks of 8: a ragged tail), then
        12 decoded tokens through the paged latent pool: the logits at every
        position against the reference's one full forward pass. bf16's gap
        also lies ABOVE float32's limit: the two limits tell the types
        apart. With float32 weights the float32 steps read the expert
        stacks in place and the bf16 steps a converted slice; with the
        weights rounded to bf16 (the serving cell's case; the reference
        runs on the same rounded weights) the bf16 steps read them in
        place."""
        cfg, params = _model(compute_dtype=dtype)
        if weights is not None:
            params = jax.tree.map(
                lambda a: a.astype(weights) if a.dtype == jnp.float32 else a,
                params)
            params["block"]["moe"]["router_kernel"] = params["block"]["moe"][
                "router_kernel"].astype(jnp.float32)
        seq, logits, _, _ = _prefill_then_decode(cfg, params,
                                                 _tokens((20,), 1), 12)
        assert len(seq) == 32 and logits.shape[0] == 32
        gap = np.abs(logits - _reference(params, seq[None])[0]).max()
        print(f"{dtype.__name__}: largest gap {gap:.3e}")
        assert above <= gap < below

    def test_leading_layer_writes_plane_0_and_the_scan_the_rest(self):
        cfg, params = _model()
        prompt = _tokens((20,), 2)
        seq, _, (lat, pe), _ = _prefill_then_decode(cfg, params, prompt, 3)
        lat = np.asarray(lat).reshape(cfg.num_layers, -1, cfg.kv_lora_rank)
        written = len(seq)              # every token of seq was fed
        assert np.all(lat[:, written:] == 0)
        assert np.all(np.abs(lat[:, :written]).sum(-1) > 0)
        # Plane 0 holds what the DENSE layer's attention caches: the normed
        # latent of the embedded tokens, from the leading layer's weights.
        lead = jax.tree.map(lambda a: np.asarray(a[0], np.float64),
                            params["lead_block"])

        def rms(x, w):
            return x / np.sqrt((x * x).mean(-1, keepdims=True)
                               + cfg.layernorm_epsilon) * w

        x = np.asarray(params["embedding"]["word"], np.float64)[seq[:written]]
        ckv = rms(x, lead["ln1_scale"]) @ lead["attention"]["kv_down"]
        want = rms(ckv[:, :cfg.kv_lora_rank],
                   lead["attention"]["kv_ln_scale"])
        assert np.abs(lat[0, :written] - want).max() < 1e-5
        for plane in (1, 2):
            assert np.abs(lat[plane, :written] - want).max() > 1e-2
        assert np.abs(lat[1, :written] - lat[2, :written]).max() > 1e-2

    def test_decode_step_counts_routing(self):
        cfg, params = _model()
        _, _, _, counts = _prefill_then_decode(cfg, params,
                                               _tokens((9,), 3), 1)
        moe_layers = cfg.num_layers - cfg.moe_first_k_dense
        assert counts.shape == (2,) and counts.dtype == jnp.int32
        # One active row: top-3 distinct experts in each of 2 MoE layers.
        assert int(counts[0]) == moe_layers * cfg.moe_router_topk
        assert int(counts[1]) == moe_layers * cfg.moe_router_topk


def test_routing_counts_skips_rows_that_are_no_tokens():
    idx = jnp.asarray([[0, 1], [1, 2], [5, 6]])
    got = routing_counts(idx, jnp.asarray([True, True, False]), 8)
    assert got.tolist() == [4, 3]


class TestEngine:
    def test_engine_counters_and_startup_line(self):
        cfg, params = _model()
        eng = DynamicInferenceEngine(params, cfg, max_batch=2,
                                     max_seq_len=64, paged=True,
                                     num_blocks=16, block_size=4,
                                     prefill_chunk=8)
        assert "paged=True" in eng.startup_line()
        for seed in (4, 5):
            eng.add_request(_tokens((10,), seed), 5)
        eng.run_to_completion()
        moe = eng.stats_snapshot()["moe"]
        # 2 requests x 4 decode rounds (the first token is prefill's) x
        # top-3 x 2 MoE layers; a round can touch 2 x 8 (layer, expert)s.
        assert moe["decode_rounds"] == 4
        assert moe["assignments"] == 2 * 4 * 3 * 2
        assert moe["expert_pairs_possible"] == 4 * 2 * 8
        assert 4 * 2 * 3 <= moe["expert_pairs_touched"] <= moe["assignments"]

    @pytest.mark.parametrize("experts,slices", [("plain", 0), ("int8", 2)])
    def test_decode_dispatch_counts_expert_stack_slices(self, experts,
                                                        slices):
        """`decode_dispatch.expert_stack_slices`: equations of the traced
        decode step that cut one layer's expert kernel out of its stack. 0
        when the layer loop hands the grouped GEMMs the stack and the layer
        id; 2 a layer loop (fc1 and fc2 as the scan's xs) where the kernels
        stay per-layer operands, as resident int8 pairs do. The in-place
        read is the Pallas grouped GEMM's (two calls a MoE layer beside the
        latent kernel); the per-layer operands keep ``lax.ragged_dot``."""
        from megatronapp_tpu.inference.quantization import (
            quantize_params, residentize_params,
        )
        cfg, params = _model()
        if experts == "int8":
            params = residentize_params(
                quantize_params(params, resident_only=True)[0])
            assert "qint8" in params["block"]["moe"]["fc1_kernel"]
        eng = DynamicInferenceEngine(params, cfg, max_batch=2,
                                     max_seq_len=64, paged=True,
                                     num_blocks=16, block_size=4,
                                     prefill_chunk=8)
        disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
        assert disp["expert_stack_slices"] == slices, disp
        assert disp["scatters"] == 0, disp      # rows move by gathers alone
        moe_layers = cfg.num_layers - cfg.moe_first_k_dense
        assert disp["kernels"] == cfg.num_layers + (   # the latent kernel
            0 if experts == "int8" else 2 * moe_layers)

    @pytest.mark.parametrize("n,seed,chunk", [
        (10, 4, 8), (17, 5, 8), (45, 6, 32), (45, 6, None), (45, 6, 7)],
        ids=["10-in-8s", "17-in-8s", "45-in-32", "45-in-a-v5e's",
             "45-in-7s"])
    def test_streams_equal_the_dense_oracle(self, monkeypatch, n, seed,
                                            chunk):
        """The greedy stream of the two paged steps (experts read through
        the layer id) is that of gpt_forward over the whole sequence (the
        training scan: per-layer kernels), in float32. The steps are
        driven directly: a fresh engine on the CPU now and then leaves
        the oracle whatever the model (ROADMAP S3). Whatever the width of
        the prefill call (ISSUE 35): 8, 32 (every engine's until then; the
        latent kernel here cuts the call into query tiles of 8 under a
        small VMEM budget), what the engine chooses for these shapes on a
        v5e and 7, which divides no prompt."""
        from megatronapp_tpu.inference.dynamic_engine import (
            choose_prefill_width,
        )
        from megatronapp_tpu.ops.pallas import kernel_gen
        cfg, params = _model()
        if chunk is None:
            # float32 weights, 2 of 4 experts a position: over 500 flops
            # a byte; held to a max_seq_len
            chunk = choose_prefill_width(cfg, params, 16, 4,
                                         device_kind="TPU v5 lite")
            assert chunk == 16
        steps = _shared_steps
        if chunk == 32:
            monkeypatch.setattr(kernel_gen, "_query_vmem_budget",
                                lambda *a: 100_000)
            steps = _paged_steps    # its own: traced under that budget
        prompt = _tokens((n,), seed)
        seq, _, _, _ = _prefill_then_decode(cfg, params, prompt, 6,
                                            chunk=chunk, steps=steps)
        assert seq[:n].tolist() == prompt.tolist() and len(seq) == n + 6
        # the oracle's greedy stream is seq: causal, so its argmax after
        # every prefix of seq is read off one pass over seq
        logits, _ = gpt_forward(params, jnp.asarray(seq[None, :-1]), cfg)
        assert seq[n:].tolist() == np.argmax(
            np.asarray(logits[0, n - 1:]), -1).tolist()

    def test_dense_model_step_is_unchanged(self):
        """A dense model's decode step returns no counts, so its sampler
        gets no tail and /stats no `moe` section."""
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        from megatronapp_tpu.models.gpt import init_gpt_params
        cfg = TransformerConfig(num_layers=2, hidden_size=32,
                                num_attention_heads=2, vocab_size=64,
                                max_position_embeddings=32,
                                compute_dtype=jnp.float32)
        params = init_gpt_params(jax.random.PRNGKey(0), cfg)[0]
        eng = DynamicInferenceEngine(params, cfg, max_batch=2,
                                     max_seq_len=32, paged=True)
        eng.add_request(_tokens((6,), 6) % 64, 3)
        eng.run_to_completion()
        snap = eng.stats_snapshot(include_dispatch=True)
        assert "moe" not in snap and eng.moe_stats["decode_rounds"] == 0
        assert snap["decode_dispatch"]["expert_stack_slices"] == 0

    def test_dense_cache_engines_refuse_leading_layers(self):
        from megatronapp_tpu.inference.engine import StaticInferenceEngine
        cfg, params = _model()
        eng = StaticInferenceEngine(params, cfg, max_seq_len=32)
        with pytest.raises(ValueError, match="paged engine"):
            eng.generate(np.asarray([[1, 2, 3]], np.int32), 2)


class TestTrainingPath:
    def test_pretrain_gpt_builds_and_steps_it_from_flags(self):
        from megatronapp_tpu.config.arguments import (
            build_parser, configs_from_args,
        )
        from megatronapp_tpu.training.train import pretrain_gpt
        args = build_parser().parse_args([
            "--num-layers", "3", "--hidden-size", "64",
            "--num-attention-heads", "4", "--ffn-hidden-size", "160",
            "--vocab-size", "128", "--max-position-embeddings", "64",
            "--seq-length", "16", "--micro-batch-size", "2",
            "--global-batch-size", "2", "--train-iters", "3",
            "--log-interval", "1", "--lr", "1e-3",
            "--normalization", "RMSNorm", "--swiglu",
            "--disable-bias-linear", "--untie-embeddings-and-output-weights",
            "--position-embedding-type", "yarn",
            "--rope-scaling-factor", "40", "--yarn-original-max-position",
            "16", "--yarn-mscale-coeff", "0.0707",
            "--multi-latent-attention", "--kv-lora-rank", "32",
            "--qk-head-dim", "16", "--qk-pos-emb-head-dim", "8",
            "--v-head-dim", "16", "--num-experts", "8",
            "--moe-router-topk", "3", "--moe-ffn-hidden-size", "48",
            "--moe-shared-expert-intermediate-size", "48",
            "--moe-first-k-dense", "1", "--moe-router-no-norm-topk-prob",
            "--moe-routed-scaling-factor", "1.0"])
        model, par, train, opt = configs_from_args(args)
        assert model.moe_first_k_dense == 1
        assert model.moe_router_norm_topk_prob is False
        assert model.rope_scaling_factor == 40.0
        assert model.yarn_mscale_coeff == pytest.approx(0.0707)
        from megatronapp_tpu.parallel.mesh import build_mesh
        ctx = build_mesh(par, devices=jax.devices()[:1])
        res = pretrain_gpt(model, par, train, opt, ctx=ctx)
        assert len(res.losses) == 3 and np.all(np.isfinite(res.losses))
        assert res.losses[-1] < res.losses[0]


class TestThreeSourcesAgree:
    """The preset, the benchmark's configuration file and the catalog's row
    say the same model; only the depth of the file differs (`reduced`)."""

    def test_file_holds_the_catalog_numbers(self):
        if not os.path.exists(CATALOG):
            pytest.skip("no catalog beside the model-configs guide here")
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"] == "DeepSeek-V2-Lite")
        assert PUBLISHED["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in PUBLISHED["reduced"]:
                continue
            assert PUBLISHED[key] == value, key
        assert row["config"]["num_hidden_layers"] == 27
        assert PUBLISHED["num_hidden_layers"] == PUBLISHED["num_layers"] == 9

    def test_preset_is_the_file_at_full_depth(self):
        preset = PRESETS["deepseek-v2-lite"]()
        full = dict(PUBLISHED, num_layers=27, num_hidden_layers=27)
        built = MODEL.model_config(full, "float32")
        skip = {"hetero_block_specs"}
        for field in dataclasses.fields(preset):
            if field.name in skip:
                continue
            a, b = getattr(preset, field.name), getattr(built, field.name)
            if isinstance(a, float):
                assert a == pytest.approx(b), field.name
            else:
                assert a == b, field.name

    def test_benchmark_entry_points_at_the_file(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest_json = json.load(f)
        entry = next(c for c in manifest_json["configs"]
                     if c["name"] == "deepseek-v2-lite")
        assert entry["file"] == "perfbench/configs/deepseek-v2-lite.json"
        assert entry["source"] == PUBLISHED["source"]
        assert entry["reduced"] == PUBLISHED["reduced"]
        cell = next(w for w in manifest_json["workloads"]
                    if w["config"] == "deepseek-v2-lite")
        assert cell["chips"] == 1 and cell["traffic"] == "longgen-closed"
        assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") == 9 * 576 * 2
