import json
import os

import numpy as np
import pytest

from perfbench import manifest, stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_quartile_spread_is_statistics_quantiles():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    import statistics
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


def _key(reqs):
    return [(r.prompt.tolist(), r.max_new_tokens) for r in reqs]


def test_closed_loop_stream_repeats_for_a_seed():
    mix = _mix("batch-closed")
    closed = manifest.load_module("generators", mix["kind"])
    take = lambda seed: [next(s) for s in [closed.requests(
        mix, seed, 50257)] for _ in range(50)]
    a, b, c = take(9), take(9), take(10)
    assert _key(a) == _key(b) != _key(c)
    assert all(len(r.prompt) + r.max_new_tokens <= 1024 for r in a)
    # the sizes come in the same order whatever the seed
    sizes = lambda rs: [(len(r.prompt), r.max_new_tokens) for r in rs]
    assert sizes(a) == sizes(c)


def test_packed_batches_rows_and_masks():
    job = _mix("packed-1k")
    packed = manifest.load_module("generators", job["kind"])
    a = next(packed.batches(job, 5, 50257, 1024))
    b = next(packed.batches(job, 5, 50257, 1024))
    c = next(packed.batches(job, 6, 50257, 1024))
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["tokens"] == c["tokens"]).all()
    assert (a["segment_ids"] == c["segment_ids"]).all()   # same documents
    assert a["tokens"].shape == (16, 1024)
    # labels are the next token; the mask drops a label that is another
    # document's first token; positions restart with each segment
    assert (a["labels"][:, :-1] == a["tokens"][:, 1:]).all()
    seg = a["segment_ids"]
    same_next = seg[:, 1:] == seg[:, :-1]
    assert (a["loss_mask"][:, :-1] == same_next).all()
    starts = np.concatenate([np.ones((16, 1), bool), ~same_next], axis=1)
    assert (a["position_ids"][starts] == 0).all()
    assert (np.diff(a["position_ids"], axis=1)[same_next] == 1).all()
    assert (seg[:, 0] == 0).all() and (np.diff(seg, axis=1) >= 0).all()


def test_every_file_names_code_that_is_there():
    """A configuration names its model module, a traffic file its generator
    and its runner, a per-layer metric its reader: all found by name."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cfg in bench["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), cfg["file"])) as f:
            model = manifest.load_module("models", json.load(f)["model"])
        for name in ("model_config", "init_params", "reference_logits",
                     "reference_loss", "flops_per_token",
                     "kv_bytes_per_token", "REHEARSAL"):
            assert hasattr(model, name), (cfg["name"], name)
    for cell in bench["workloads"]:
        mix = manifest.load_traffic(cell)
        assert manifest.load_module("generators", mix["kind"])
        assert callable(manifest.load_module("cells", mix["runner"]).run_cell)
    for m in bench["per_layer"]:
        assert callable(manifest.load_reader(m["name"])), m["name"]
    with pytest.raises(SystemExit):
        manifest.load_module("generators", "no-such-kind")
