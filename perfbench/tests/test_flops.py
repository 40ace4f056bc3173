import json
import os

import pytest

from perfbench import flops, manifest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name,seq", [("gpt2-medium", 1024),
                                      ("gpt3-2.7b", 2048),
                                      ("gpt3-2.7b-tp2dp2", 2048)])
def test_benchmark_count_equals_the_programs_today(name, seq):
    from megatronapp_tpu.utils.flops import flops_per_token
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    cfg = manifest.load_module("models", config["model"]).model_config(
        config, "float32")
    assert flops.flops_per_token(config, seq) == flops_per_token(cfg, seq)
    assert cfg.head_dim == config["head_dim"]


def test_peaks_have_sources():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    for kind, row in table.items():
        assert row["source"] and row["bf16_flops_per_s"] > 0
