"""What NVIDIA-Nemotron-3-Nano-30B-A3B's PR left as it was, and what its
three sources say alike: the paged steps of the stacks the benchmark already
serves, which this PR's groups, inner width, single-sublayer layers and
pattern loop leave instruction for instruction the parent's; and the
preset, the benchmark's configuration file and the catalog's row."""
import copy
import hashlib
import json
import os

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

# sha256[:16] of the two paged steps' lowered text at the parent commit
# (e44ba59), by _step_hashes run there: this PR's groups, inner width,
# single-sublayer layers and pattern loop leave the programs of the stacks
# the benchmark already serves as they were, instruction for instruction.
PARENT_STEP_SHA = {
    "jamba2-3b": ("ce6a5eb71a9343d5", "98407e6d9df0494b"),
    "granite-4.0-h-small": ("dfede5ecf3f97b30", "a61a29068a205cc8"),
    "lfm2-24b-a2b": ("77e79b5e094da346", "b6ef03faa7549118"),
    "longcat-flash-chat": ("80a3e0ed3b2db33a", "f23b233fcf2ddf07"),
    "laguna-xs.2": ("8d37ec62f7139778", "f5d104a2d77a44ad"),
}


def _step_hashes(name):
    """(decode, prefill): sha256[:16] of configuration `name`'s two paged
    steps' StableHLO at its model module's rehearsal sizes."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    model = manifest.load_module("models", config["model"])
    config.update(copy.deepcopy(model.REHEARSAL))
    cfg = model.model_config(config, "float32")
    eng = DynamicInferenceEngine(
        model.init_params(cfg, seed=1), cfg, max_batch=2, max_seq_len=64,
        paged=True, num_blocks=16, block_size=4, prefill_chunk=8)
    sds = jax.ShapeDtypeStruct

    def i32(*shape):
        return sds(shape, jnp.int32)

    b = eng.max_batch
    texts = (
        eng._decode.lower(
            eng.params, i32(b, 1), eng._pools(), eng.pool.scales,
            eng._tables(), i32(b), sds((b,), bool), None).as_text(),
        eng._mq_step.lower(
            eng.params, i32(1, eng.prefill_chunk), eng._pools(),
            eng.pool.scales, eng._tables(slice(0, 1)), i32(1), i32(1),
            sds((1,), bool), None, i32(1) if eng.has_state else None,
            i32(1)).as_text())
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts)


@pytest.mark.parametrize("name", sorted(PARENT_STEP_SHA))
def test_the_other_stacks_steps_lower_to_the_parents_text(name):
    """(A change to the steps' other code moves these hashes too: re-pin
    them from the commit before it.)"""
    assert _step_hashes(name) == PARENT_STEP_SHA[name]


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
