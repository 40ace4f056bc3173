"""``perfbench/scope_time.py`` and the metrics that read it, on a hand-made
``run``: a 10 ms window on one chip in which a decode step, the sampler and
a prefill step run, the two steps' instruction names colliding; and the
same readers on a program without the registry."""
import json
import types

import pytest

from perfbench import lastline, manifest as mf, scope_time, trace_reduce

MS = 1_000_000
NEW = [m["name"] for m in mf.load_manifest()["per_layer"]
       if m["name"].startswith(("attention_ms", "mlp_ms", "moe_ms", "ssm_ms",
                                "head_", "optimizer_ms", "scope_",
                                "prefill_device"))]


def ev(name, start_ms, end_ms, op="fusion", shape="bf16[24,1,2560]{2,1,0}"):
    return [name, round(start_ms * MS), round((end_ms - start_ms) * MS),
            {"op": op, "shape": shape} if op else {}]


def scoped(part, pass_="fwd", opcode="fusion", shape="bf16[24,1,2560]",
           op_name=""):
    return types.SimpleNamespace(part=part, pass_=pass_, opcode=opcode,
                                 shape=shape, op_name=op_name)


def a_map(module, kind, **instructions):
    return types.SimpleNamespace(
        module=module, kind=kind, compile_s=1.5, collectives={},
        instructions={k.replace("_", "."): v
                      for k, v in instructions.items()})


MAPS = [
    a_map("jit__decode_traced", "decode",
          fusion_1=scoped("attention"), fusion_2=scoped("mlp"),
          copy_3=scoped("other", opcode="copy",
                        op_name="jit(_decode_traced)/while/body/dynamic_slice"),
          fusion_4=scoped("head"), fusion_9=scoped("moe"),
          fusion_8=scoped("ssm")),
    # the same names mean other parts in the prefill step
    a_map("jit__mq_traced", "prefill",
          fusion_1=scoped("mlp"), fusion_2=scoped("attention")),
    a_map("jit__sample_batched", "sampler", fusion_1=scoped("sampler")),
    # a second sampler program (one row) whose fusion.1 is another shape
    a_map("jit__sample_batched", "sampler",
          fusion_1=scoped("sampler", shape="f32[1,64]")),
]
OPS = [
    ev("while.1", 1, 4.5, op="while"),           # encloses: not a leaf
    ev("fusion.1", 1, 3), ev("fusion.2", 3, 4), ev("copy.3", 4, 4.5,
                                                   op="copy"),
    ev("fusion.4", 4.5, 5),
    ev("fusion.1", 5.2, 5.4),                    # the sampler's
    ev("fusion.1", 6, 7), ev("fusion.2", 7, 9),  # the prefill step's
    ev("fusion.77", 9, 9.2),                     # the map lacks it
    ev("fusion.1", 9.5, 11),                     # straddles the window's end
    ev("broadcast_select_fusion", 11.5, 12),     # outside the window
]
MODULES = [ev("jit__decode_traced(11)", 1, 5, op=None),
           ev("jit__sample_batched(22)", 5.2, 5.4, op=None),
           ev("jit__mq_traced(33)", 6, 9.3, op=None),
           ev("jit__decode_traced(11)", 9.5, 11.2, op=None),
           ev("jit__where(44)", 11.5, 12, op=None)]
HOST = [ev("bench.window", 0, 10, op=None),
        ev("mta.engine.decode_round", 1, 5.5, op=None),
        ev("mta.engine.decode_round", 9, 11, op=None)]   # half inside


def run_of(maps, modules=MODULES, traced_steps=None):
    lines = [{"name": "XLA Ops", "events": OPS}]
    if modules is not None:
        lines.insert(0, {"name": "XLA Modules", "events": modules})
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": lines},
        {"name": "/host:CPU", "lines": [{"name": "stepper",
                                         "events": HOST}]}]}
    run = {"trace": trace, "traced_steps": traced_steps,
           "device_summary": trace_reduce.device_summary(trace)}
    if maps is not None:
        run["scope_maps"] = maps
    return run


def read(name, run):
    return mf.load_reader(name)(run)


def test_colliding_names_are_split_by_the_modules_line():
    t = scope_time.table(run_of(MAPS))
    s = {k: pytest.approx(v) for k, v in t["seconds"].items()}
    assert s == {
        # decode: fusion.1 2 ms + the half millisecond inside the window
        ("decode", "attention", "fwd"): 2.5e-3,
        ("decode", "mlp", "fwd"): 1e-3,
        ("decode", "other", "fwd"): 0.5e-3,
        ("decode", "head", "fwd"): 0.5e-3,
        ("sampler", "sampler", "fwd"): 0.2e-3,
        # prefill: the same names, the other way round
        ("prefill", "mlp", "fwd"): 1e-3,
        ("prefill", "attention", "fwd"): 2e-3,
    }
    assert t["unmatched_s"] == pytest.approx(0.2e-3)
    assert list(t["unmatched"]) == [
        ("jit__mq_traced", "fusion.77 fusion bf16[24,1,2560]{2,1,0}")]
    # parts + other + unmatched are the window's summed leaf seconds
    assert sum(t["seconds"].values()) + t["unmatched_s"] \
        == pytest.approx(t["leaf_s"]) == pytest.approx(7.9e-3)
    assert t["other"] == {("decode", "copy.3 copy bf16[24,1,2560]",
                           "jit(_decode_traced)/while/body/dynamic_slice"):
                          pytest.approx(0.5e-3)}
    assert t["by_module"]["jit__mq_traced"]["kind"] == "prefill"
    assert t["by_module"]["jit__mq_traced"]["unmatched_s"] \
        == pytest.approx(0.2e-3)


def test_metrics_divide_by_rounds_counted_by_share():
    run = run_of(MAPS)
    rounds = 1.5                    # one whole, one half inside the window
    assert read("attention_ms_round", run) == pytest.approx(2.5 / rounds)
    assert read("mlp_ms_round", run) == pytest.approx(1.0 / rounds)
    assert read("scope_other_ms_round", run) == pytest.approx(0.5 / rounds)
    assert read("head_sampler_ms_round", run) \
        == pytest.approx((0.5 + 0.2) / rounds)
    assert read("moe_ms_round", run) == read("ssm_ms_round", run) == 0.0
    assert read("prefill_device_share", run) == pytest.approx(30.0)
    assert read("scope_unmatched_share.serve", run) \
        == pytest.approx(100 * 0.2 / 7.9)


def test_a_map_of_another_program_explains_nothing():
    """Names alone do not make a match: opcode and result shape must agree
    with the compiled text, or the time is unmatched."""
    wrong = [a_map("jit__decode_traced", "decode",
                   fusion_1=scoped("attention", shape="bf16[8,1,512]"),
                   fusion_2=scoped("mlp", opcode="copy"))]
    t = scope_time.table(run_of(wrong))
    assert t["seconds"] == {}
    assert t["unmatched_s"] == pytest.approx(t["leaf_s"])


def test_training_metrics_divide_by_traced_steps():
    maps = [a_map("jit__decode_traced", "train",
                  fusion_1=scoped("attention", "bwd"),
                  fusion_2=scoped("mlp"), fusion_4=scoped("optimizer"),
                  copy_3=scoped("grad_accum", opcode="copy"))]
    run = run_of(maps, traced_steps=2)
    assert read("attention_ms_step", run) == pytest.approx(2.5 / 2)
    assert read("mlp_ms_step", run) == pytest.approx(1.0 / 2)
    assert read("optimizer_ms_step", run) == pytest.approx((0.5 + 0.5) / 2)
    assert read("head_loss_ms_step", run) == 0.0
    assert read("scope_other_ms_step", run) == 0.0
    assert read("scope_unmatched_share.train", run) > 0


def test_without_a_modules_line_any_map_may_explain():
    t = scope_time.table(run_of(MAPS[:1], modules=None))
    assert t["seconds"][("decode", "attention", "fwd")] \
        == pytest.approx(2e-3 + 0.2e-3 + 1e-3 + 0.5e-3)


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_gives_zero_without_a_registry(name, monkeypatch):
    """The parent commit under this PR's benchmark files: no registry, so
    no map; and a run with no device trace at all."""
    monkeypatch.setattr(scope_time, "_program_maps", lambda: [])
    for run in (run_of(None), run_of(None, traced_steps=10),
                {"device_summary": None}):
        value = read(name, run)
        assert value == 0.0 and isinstance(value, float)


def test_the_line_of_a_program_without_a_registry_is_valid(monkeypatch):
    monkeypatch.setattr(scope_time, "_program_maps", lambda: [])
    run = run_of(None)
    wanted = {m["name"]: m["unit"] for m in mf.load_manifest()["per_layer"]
              if m["name"] in NEW}
    line = {"correct": True, "attempted": 3, "failed": 0,
            "metrics": {n: {"value": float(read(n, run)), "unit": u}
                        for n, u in wanted.items()},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "busy_s": 5.0,
                       "window_s": 5.4}}
    assert lastline.faults(line, wanted, chips=1, traced=True) == []
    json.dumps(line, allow_nan=False)


def test_the_program_without_the_module_makes_no_map(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_scope_map(name, *a, **kw):
        if name == "megatronapp_tpu.trace.scope_map":
            raise ImportError(name)
        return real(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_scope_map)
    assert scope_time._program_maps() == []


def test_the_tool_prints_the_table():
    from perfbench.tools import device_by_scope
    out = device_by_scope.report(run_of(MAPS))
    json.dumps(out)
    assert out["by_scope"][0][:3] == ["decode", "attention", "fwd"]
    assert out["by_scope"][-1][0] == "unmatched"
    assert out["per"]["rounds"] == pytest.approx(1.5)
    assert out["compile_s"]["decode jit__decode_traced"] == 1.5
