"""``kda_update_roofline_pct.doc``, the one per-layer reader of
``serve.solar-open2-250b.doc-closed`` that is its own, on a hand-built
``run`` (a 10 ms window with two decode rounds of 128 and 96 rows and 1.5 ms
in the state's decode kernel), on runs that lack what it reads, and on a
slice of a traced chip run; ``perfbench/solar_bytes.py`` against the sizes
of the configuration's table counted by hand; and the traffic file's trace
of sizes, which no seed moves."""
import gzip
import json
import os

import numpy as np
import pytest

from perfbench import manifest as mf, solar_bytes, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "perfbench", "configs",
                       "solar-open2-250b.json")) as f:
    CONFIG = json.load(f)
MS = 1_000_000
KERNEL = {"op": "custom-call", "target": "tpu_custom_call"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NAME = "kda_update_roofline_pct.doc"
# a row's states of the three layers, read and written, and its vectors
ROW_BYTES = 3 * (2 * 64 * 128 * 128 * 4 + (5 * 64 * 128 + 64) * 4)
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "tpu_v5e_doc_70ms.json.gz")


def ev(name, start_ms, end_ms, info=None):
    return [name, round(start_ms * MS), round((end_ms - start_ms) * MS),
            dict(info or {})]


def run_of(device, host, stats=None):
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU", "lines": [{"name": "stepper", "events": host}]}]}
    return {"trace": trace, "config": CONFIG, "peaks": PEAKS,
            "device_summary": trace_reduce.device_summary(trace),
            "xplane_stats": stats, "engine_stats": {}}


def read(run):
    return mf.load_reader(NAME)(run)


def test_the_bytes_against_a_count_by_hand():
    # 3 KDA layers x (read + write) x 64 heads x [128, 128] float32: a
    # 4 MiB plane a layer, and 164,096 B of vectors
    assert solar_bytes.kda_layers(CONFIG) == 3
    assert solar_bytes.kda_update_bytes(CONFIG, 1) == ROW_BYTES \
        == 3 * (2 * (4 << 20) + 164_096) == 25_658_112
    assert solar_bytes.kda_update_bytes(CONFIG, 128) == 128 * ROW_BYTES


def test_the_reader_from_known_rows_and_seconds():
    run = run_of(
        [ev("kda_update.7", 1, 2, KERNEL), ev("fusion.7", 2, 3.5,
                                              {"op": "fusion"}),
         ev("paged_decode.3", 3.5, 4, KERNEL),
         ev("kda_update.7", 6, 6.5, KERNEL)],
        [ev("bench.window", 0, 10), ev("mta.engine.decode_round", 1, 5),
         ev("mta.engine.decode_round", 6, 8)],
        {"spans": [ev("mta.engine.decode_round", 1, 5, {"batch": 128}),
                   ev("mta.engine.decode_round", 6, 8, {"batch": 96})]})
    assert read(run) == pytest.approx(
        100 * (224 * ROW_BYTES / 819e9) / 1.5e-3)


def test_without_a_device_summary_none_without_the_kernel_zero():
    assert read({"engine_stats": {}}) is None
    # the parent commit under this PR's benchmark files, or another model:
    # no such kernel, no such attribute
    other = run_of([ev("ssm_update.1", 0, 9, KERNEL)],
                   [ev("bench.window", 0, 10)],
                   {"spans": [ev("mta.engine.decode_round", 1, 5,
                                 {"batch": 128})]})
    assert read(other) == 0.0
    no_rows = run_of([ev("kda_update.7", 1, 2, KERNEL)],
                     [ev("bench.window", 0, 10)], {"spans": []})
    assert read(no_rows) == 0.0


def test_the_reader_on_a_slice_of_a_traced_run():
    """70 ms (three rounds and a bit, no prefill call) cut out of a traced
    run of the cell on a TPU v5e (``tools/cut_fixture.py``; my chip run,
    PR 59, seed 3000000059), with the ``mta.engine.decode_round`` spans'
    attributes beside it: the kernel by name, 1.68 ms a layer a round of
    126-128 rows, and the share of its roof."""
    with gzip.open(FIXTURE, "rt") as f:
        trace = json.load(f)
    spans = trace.pop("spans")
    trace["rehearsal"] = False
    run = {"trace": trace, "config": CONFIG, "peaks": PEAKS,
           "device_summary": trace_reduce.device_summary(trace),
           "xplane_stats": {"spans": spans}, "engine_stats": {}}
    assert run["device_summary"]["window_s"] == pytest.approx(0.070)
    lo, hi = run["device_summary"]["window"]
    rows = sum(s[3]["batch"] * (min(s[1] + s[2], hi) - max(s[1], lo)) / s[2]
               for s in spans if "batch" in s[3])
    seconds = trace_reduce.summed_s(
        trace, (lo, hi), lambda e: "kda_update" in e[0])
    assert 300 < rows < 450 and 0.012 < seconds < 0.020
    share = read(run)
    assert share == pytest.approx(
        100 * rows * ROW_BYTES / 819e9 / seconds, rel=1e-6)
    assert 70 < share < 90


def test_the_trace_of_sizes_is_the_same_for_two_seeds():
    """The generator draws the sizes from the file's ``shape_seed``; --seed
    draws the ids alone."""
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "doc-closed.json")) as f:
        mix = json.load(f)
    gen = mf.load_module("generators", mix["kind"])
    streams = [gen.requests(mix, seed, 24576) for seed in (1, 3000000059)]
    a, b = ([next(s) for _ in range(130)] for s in streams)
    assert [(len(r.prompt), r.max_new_tokens) for r in a] \
        == [(len(r.prompt), r.max_new_tokens) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    # the pool of 128 sizes comes round again
    assert len(a[128].prompt) == len(a[0].prompt)
    assert max(len(r.prompt) + r.max_new_tokens for r in a) <= 18432
    assert max(int(r.prompt.max()) for r in a) < 24576
