"""trace/scope_map.py on a tiny paged engine: ``scope_maps()`` covers the
decode step, the prefill step and the sampler, and leaves the engine's
trace counters as it found them. (A file of its own: xdist hands out whole
files, and the engine's two compiles are this one's seconds.)"""

import collections

import jax
import numpy as np
import pytest

from megatronapp_tpu.trace import scope_map as sm


def _passes_by_part(made):
    out = collections.defaultdict(set)
    for s in made.instructions.values():
        out[s.part].add(s.pass_)
    return out


@pytest.fixture(scope="module")
def engine_maps():
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.inference.engine import SamplingParams
    from megatronapp_tpu.models.gpt import init_gpt_params

    sm.clear()
    cfg = TransformerConfig(num_layers=2, hidden_size=32,
                            num_attention_heads=2, vocab_size=64,
                            max_position_embeddings=64)
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    eng = DynamicInferenceEngine(params, cfg, max_batch=2, max_seq_len=32,
                                 paged=True, num_blocks=8,
                                 prefill_chunk=8)
    eng.add_request(np.arange(5, dtype=np.int32), 3,
                    SamplingParams(greedy=True))
    eng.run_to_completion()
    before = (eng.decode_traces, eng.mq_traces)
    maps = sm.scope_maps()
    after = (eng.decode_traces, eng.mq_traces)
    sm.clear()
    return maps, before, after


def test_engine_maps_cover_its_steps(engine_maps):
    maps, _, _ = engine_maps
    by_kind = collections.defaultdict(list)
    for m in maps:
        by_kind[m.kind].append(m)
    assert set(by_kind) == {"decode", "prefill", "sampler"}
    assert by_kind["decode"][0].module == "jit__decode_traced"
    assert by_kind["prefill"][0].module == "jit__mq_traced"
    # the sampler ran with one row (the prefill's first sample) and two
    assert len(by_kind["sampler"]) == 2
    for kind in ("decode", "prefill"):
        parts = set(_passes_by_part(by_kind[kind][0]))
        assert {"attention", "mlp", "embedding", "head"} <= parts


def test_the_sampler_is_one_part(engine_maps):
    maps, _, _ = engine_maps
    for m in maps:
        if m.kind == "sampler":
            assert set(_passes_by_part(m)) == {"sampler"}


def test_making_maps_leaves_the_trace_counters(engine_maps):
    _, before, after = engine_maps
    assert before == after == (1, 1)
