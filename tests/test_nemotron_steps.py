"""The lowered programs and the seeded weights of the stacks the benchmark
serves, pinned against the commit before the PR that last touched them: the
paged steps of every serving configuration, the differentiated walk of the
two stacks that train through block_forward's plan walker, and the block
parameters of the six stacks of several kinds of layer (ISSUE 57: one plan,
one walker, one initialiser); and what NVIDIA-Nemotron-3-Nano-30B-A3B's
three sources say alike: the preset, the benchmark's configuration file and
the catalog's row."""
import copy
import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.models.presets import PRESETS
from perfbench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = manifest.load_module("models", "nemotron_h")
with open(os.path.join(ROOT, "perfbench", "configs",
                       "nemotron-3-nano-30b-a3b.json")) as f:
    PUBLISHED = json.load(f)


# ---- the other stacks' steps -----------------------------------------------

# sha256[:16] of the two paged steps' lowered text (_step_hashes) at the
# commit before the change each comment names: a PR that leaves a stack's
# program alone leaves its text alone, instruction for instruction.
PARENT_STEP_SHA = {
    # Re-pinned at ISSUE 57 (one plan, one walker), whose texts differ from
    # the parent's (67a371b) in rank-0 int32 arithmetic on rows and layer ids
    # alone: the same `while` nest and carry, no slice or copy of a stack
    # (CHANGES.md, PR 57, has the diff's count a step).
    "jamba2-3b": ("0f87c8757f623313", "241f1c39e8748487"),
    # pinned at 85aae3c, the commit before ISSUE 56 (heads in a page's rows
    # at 2 or 4 key/value heads of 128): the dense cell's steps
    "gpt3-2.7b": ("39a6c57cfa2ab242", "4ec78d2d59251593"),
    # The two above hold no expert and did NOT move at ISSUE 60, which is the
    # proof that no dense stack's program changed. The six below were
    # re-pinned at ISSUE 60, whose change they are: in each MoE half the
    # float32 scatter-add of the T*k pick rows and `bincount`'s scatter-add
    # went, a second sort (the permutation's inverse), one gather of the
    # picks' rows pick-major and the sum of its k slabs came
    # (transformer/moe.py `_sorted_picks`, `_combine_rows`); moe.py is the
    # one module on a step's path that differs from the parent's (9eb0e4a).
    "granite-4.0-h-small": ("60956102c4ce7b52", "17779eb4c9b0e56f"),
    "lfm2-24b-a2b": ("67d1244aa8623917", "39a110f7dd74b50f"),
    "laguna-xs.2": ("e612a9a798aebe1a", "456f97f9e93b1114"),
    "longcat-flash-chat": ("cbb87eac5da2f8bb", "6070c8ea6dc495f9"),
    "deepseek-v2-lite": ("acce5b4c8ed14005", "177cc5704ebdf995"),
    "nemotron-3-nano-30b-a3b": ("bd8e851880eb6af1", "b63c6c78dc51fe2a"),
}


def _rehearsal(name, **over):
    """(its model module, configuration `name` at the module's rehearsal
    sizes with `over` on top as the program's model configuration)."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    model = manifest.load_module("models", config["model"])
    config.update(copy.deepcopy(model.REHEARSAL), **over)
    return model, model.model_config(config, "float32")


def _sha(*texts):
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts)


def _step_texts(name):
    """(decode, prefill): configuration `name`'s two paged steps' StableHLO
    at its model module's rehearsal sizes."""
    from megatronapp_tpu.models.gpt import init_gpt_params
    _, cfg = _rehearsal(name)
    # a text is lowered from shapes: no weights are drawn
    eng = DynamicInferenceEngine(
        jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                       jax.random.PRNGKey(0)),
        cfg, max_batch=2, max_seq_len=64,
        paged=True, num_blocks=16, block_size=4, prefill_chunk=8)
    sds = jax.ShapeDtypeStruct

    def i32(*shape):
        return sds(shape, jnp.int32)

    b = eng.max_batch
    return (
        eng._decode.lower(
            eng.params, i32(b, 1), eng._pools(), eng.pool.scales,
            eng._tables(), i32(b), sds((b,), bool), None).as_text(),
        eng._mq_step.lower(
            eng.params, i32(1, eng.prefill_chunk), eng._pools(),
            eng.pool.scales, eng._tables(slice(0, 1)), i32(1), i32(1),
            sds((1,), bool), None, i32(1) if eng.has_state else None,
            i32(1)).as_text())


def _step_hashes(name):
    return _sha(*_step_texts(name))


@pytest.mark.parametrize("name", sorted(PARENT_STEP_SHA))
def test_the_other_stacks_steps_lower_to_the_parents_text(name):
    """(A change to the steps' other code moves these hashes too: re-pin
    them from the commit before it.)"""
    assert _step_hashes(name) == PARENT_STEP_SHA[name]


# ---- the differentiated walk ------------------------------------------------

# Loss and gradients of a micro-batch through block_forward's walk
# (scan_runs=False): two periods of the share-training cell's stack (a scan
# over the period, its run of three window layers written out, every body
# recomputed "selective"), and the pattern stack at its rehearsal letters.
TRAINED = {
    "mellum2-12b-a2.5b": dict(
        num_hidden_layers=8, mlp_layer_types=["sparse"] * 8,
        layer_types=["sliding_attention"] * 3 + ["full_attention"]
        + ["sliding_attention"] * 3 + ["full_attention"]),
    "nemotron-3-nano-30b-a3b": {},
}
# Mellum's differs from the parent's (67a371b) text in rank-0 int32 index
# arithmetic and in one [2] int32 stack more among the forward scan's
# residuals (a turn's feed-forward row, which the parent read off the layer
# id); the same scan over the period, the same bodies written out. The
# pattern stack's is this PR's own: its "ME" x 2 is an outermost unit of
# several layers, which ISSUE 57's one rule keeps a scan where the parent
# wrote every repeat of a pattern out (no cell trains one). Both re-pinned
# at ISSUE 60 on the change's own tree: every layer of both holds experts,
# whose rows that issue moves by gathers alone in both passes (the combine's
# scatter-add, the dispatch's transposed gather, `bincount` and the router's
# top-k transpose went: tests/test_moe_rows.py counts the scatters left, 0).
PARENT_TRAIN_SHA = {
    "mellum2-12b-a2.5b": "0708cb8dc947a45c",
    "nemotron-3-nano-30b-a3b": "fb16edde9ed75d2f",
}


def _train_text(name):
    """StableHLO of value_and_grad(gpt_loss) of configuration `name` at
    TRAINED's sizes, over 2 x 32 tokens (packed, where no layer keeps a
    state along the row)."""
    from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params
    _, cfg = _rehearsal(name, **TRAINED[name])
    cfg = dataclasses.replace(cfg, remat_policy="selective")
    params = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)

    def loss(p, tokens, labels, mask, segments):
        # a state-space layer's state would cross packed segments
        return gpt_loss(p, tokens, labels, mask, cfg,
                        segment_ids=None if cfg.num_ssm_layers else segments)

    return jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, tok, tok, jax.ShapeDtypeStruct((2, 32), jnp.float32),
        tok).as_text()


@pytest.mark.parametrize("name", sorted(PARENT_TRAIN_SHA))
def test_the_differentiated_walk_lowers_to_the_parents_text(name):
    assert _sha(_train_text(name)) == (PARENT_TRAIN_SHA[name],)


# ---- the seeded weights ----------------------------------------------------

# sha256[:16] over the leaves (path, shape, dtype, bytes) of
# gpt_dense.init_params(cfg, seed=1)["block"] at rehearsal sizes, at the
# commit before ISSUE 57: the benchmark draws its models through this
# initialiser (Nemotron's module then calibrates its routers' bias on top),
# and another draw routes otherwise.
PARENT_BLOCK_SHA = {
    "jamba2-3b": "bbb15e90495114a2",
    "granite-4.0-h-small": "1b4c44dfe5f00191",
    "lfm2-24b-a2b": "0a527616cdd78658",
    "laguna-xs.2": "c553657b52bfe2f2",
    "mellum2-12b-a2.5b": "ed2858f0fb822bf6",
    "nemotron-3-nano-30b-a3b": "c364afca2a250b75",
}


def _block_sha(name):
    _, cfg = _rehearsal(name)
    digest = hashlib.sha256()
    # the draw every model module starts from
    draw = manifest.load_module("models", "gpt_dense").init_params
    leaves = jax.tree_util.tree_leaves_with_path(
        draw(cfg, seed=1)["block"])
    for path, leaf in sorted(
            (jax.tree_util.keystr(path), leaf) for path, leaf in leaves):
        leaf = np.asarray(leaf)
        digest.update(f"{path} {leaf.shape} {leaf.dtype}".encode())
        digest.update(leaf.tobytes())
    return digest.hexdigest()[:16]


@pytest.fixture(scope="module")
def as_the_benchmark_compiles():
    """A draw's last bits follow the compiler: the benchmark's processes
    compile with XLA's optimisations on, this one without most of them
    (tests/conftest.py), and a program compiled before the switch is kept,
    so the caches go too."""
    jax.config.update("jax_disable_most_optimizations", False)
    jax.clear_caches()
    yield
    jax.config.update("jax_disable_most_optimizations", True)
    jax.clear_caches()


@pytest.mark.parametrize("name", sorted(PARENT_BLOCK_SHA))
def test_the_seeded_block_is_the_parents_bit_for_bit(
        name, as_the_benchmark_compiles):
    assert _block_sha(name) == PARENT_BLOCK_SHA[name]


class TestThreeSourcesAgree:
    """The preset, the benchmark's configuration file and the catalog's row
    say the same model; the file differs by its four `reduced` keys."""
    CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

    def test_file_holds_the_catalog_numbers(self):
        if not os.path.exists(self.CATALOG):
            pytest.skip("no catalog beside the model-configs guide here")
        with open(self.CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert PUBLISHED["source"] == row["source_url"]
        assert PUBLISHED["reduced"] == [
            "num_hidden_layers", "hybrid_override_pattern",
            "n_routed_experts", "vocab_size"]
        for key, value in row["config"].items():
            if key in PUBLISHED["reduced"]:
                assert PUBLISHED["published"][key] == value
            else:
                assert PUBLISHED[key] == value, key
        assert PUBLISHED["hybrid_override_pattern"] == row["config"][
            "hybrid_override_pattern"][:13]

    def test_the_files_cut_is_the_presets_share(self):
        cfg = MODEL.model_config(PUBLISHED, "bfloat16")
        preset = PRESETS["nemotron-3-nano-30b-a3b"](
            num_layers=13, moe_experts_held=(0, 64), vocab_size=65536,
            vocab_slice_of=131072, params_dtype=jnp.bfloat16)
        assert isinstance(cfg, TransformerConfig) and cfg == preset

    def test_the_cut_holds_the_stated_parameters(self):
        """The table of the configuration's reduced_why, leaf by leaf."""
        from megatronapp_tpu.models.gpt import init_gpt_params
        cfg = MODEL.model_config(PUBLISHED, "bfloat16")
        tree = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))

        def count(t):
            return sum(a.size for a in jax.tree.leaves(t))

        blk = tree["block"]
        assert count(blk["mixers_ssm"]) == 6 * 38_744_896
        assert count(blk["mixers_attn"]) == 2 * 23_399_040
        experts = count({k: blk["ffn"]["moe"][k]
                         for k in ("fc1_kernel", "fc2_kernel")})
        assert experts == 5 * 64 * 9_977_856
        assert count(blk["ffn"]) - experts == 5 * 20_302_592
        assert count(tree["embedding"]) == count(tree["output"]) \
            == 65_536 * 2688
        assert count(tree) == 3_926_018_560
        assert MODEL.state_bytes_per_slot(PUBLISHED, "float32") \
            == 6 * 2_134_016
        assert MODEL.kv_bytes_per_token(PUBLISHED, "bfloat16") * 16 == 32_768
