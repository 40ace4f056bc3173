"""trace/scope_map.py: the part of the model behind every compiled
instruction, the registry of hot-path steps, and MegaScan's use of both.

CPU, tiny widths. One train step is compiled once and shared by the cases
that read it; the paged engine's cases are in test_scope_map_engine.py
(xdist hands out whole files).
"""

import collections
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.trace import scope_map as sm


# ---------------------------------------------------------------------------
# op_name -> (part, pass)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name, expected", [
    ("jit(step)/while/body/closed_call/jvp()/while/body/closed_call/"
     "attention/bqhd,bkhd->bhqk/dot_general", ("attention", "fwd")),
    ("jit(step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/mlp/dot_general", ("mlp", "bwd")),
    ("jit(step)/transpose(jvp(attention))/dot_general",
     ("attention", "bwd")),
    ("jit(step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/rematted_computation/attention/exp",
     ("attention", "bwd")),
    ("jit(step)/optimizer/cond/branch_1_fun/mul", ("optimizer", "fwd")),
    ("jit(step)/while/body/grad_accum/add", ("grad_accum", "fwd")),
    ("jit(f)/attention/shard_map/psum", ("attention", "fwd")),
    ("jit(f)/vmap(moe)/ragged_dot", ("moe", "fwd")),
    # the primitive called transpose is no backward pass
    ("jit(_decode_traced)/while/body/attention/transpose",
     ("attention", "fwd")),
    # the innermost named part wins
    ("jit(step)/head/jvp(jit(_take))/embedding/gather",
     ("embedding", "fwd")),
    ("jit(_decode_traced)/while/body/dynamic_slice", ("other", "fwd")),
    ("", ("other", "fwd")),
])
def test_part_of(op_name, expected):
    assert sm.part_of(op_name) == expected


HLO = """HloModule jit_toy, is_scheduled=true, entry_computation_layout={()->f32[8]}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0:T(256)} parameter(0)
  ROOT %multiply.1 = f32[8]{0:T(256)} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(toy)/mlp/mul"}
}

ENTRY %main.7 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0:T(256)} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0:T(256)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %all-reduce-start.1 = (f32[8]{0:T(256)S(1)}, f32[8]{0:T(256)S(1)}) all-reduce-start(%fusion.1), channel_id=1, replica_groups={{0,1},{2,3}}, to_apply=%add, metadata={op_name="jit(toy)/transpose(jvp(attention))/psum"}
  %all-reduce-done.1 = f32[8]{0:T(256)} all-reduce-done(%all-reduce-start.1)
  ROOT %copy.3 = f32[8]{0:T(256)} copy(%all-reduce-done.1), metadata={op_name="jit(toy)/while/body/dynamic_slice"}
}
"""


def test_hand_written_text():
    parsed = sm.parse_hlo_text(HLO)
    assert parsed.module == "jit_toy"
    assert parsed.roots == {"fused_computation.1": "multiply.1",
                            "main.7": "copy.3"}
    made = sm.scope_map(parsed, kind="toy")
    ins = made.instructions
    # a fusion without metadata of its own takes its root's part, whole
    assert (ins["fusion.1"].part, ins["fusion.1"].opcode,
            ins["fusion.1"].shape) == ("mlp", "fusion", "f32[8]")
    assert (ins["all-reduce-start.1"].part,
            ins["all-reduce-start.1"].pass_) == ("attention", "bwd")
    # in no part: `other`, with the op_name that says what it is
    assert ins["copy.3"].part == "other"
    assert ins["copy.3"].op_name == "jit(toy)/while/body/dynamic_slice"
    assert sm.scope_map(parsed, default_part="sampler") \
        .instructions["copy.3"].part == "sampler"


def test_a_custom_call_takes_the_part_around_it():
    """XLA:TPU's library calls come with their metadata rewritten
    (``ragged-dot-none``); what reads them keeps the scope."""
    text = HLO.replace(
        "  %all-reduce-start.1", """  %lib-meta = (s32[17]{0:T(128)}, s32[1]{0:T(128)}) custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %gte.1 = s32[17]{0:T(128)} get-tuple-element(%lib-meta), index=0
  %lib-dot = f32[8]{0:T(256)} custom-call(%gte.1, %Arg_0.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %mul.9 = f32[8]{0:T(256)} multiply(%lib-dot, %lib-dot), metadata={op_name="jit(toy)/while/body/closed_call/moe/mul"}
  %kernel.2 = f32[8]{0:T(256)} custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/attention/paged_decode"}
  %all-reduce-start.1""")
    parsed = sm.parse_hlo_text(text)
    assert parsed.instructions["lib-dot"].operands == ("gte.1", "Arg_0.1")
    ins = sm.scope_map(parsed).instructions
    assert ins["lib-dot"].part == "moe"          # its user's
    assert ins["lib-meta"].part == "moe"         # through the plumbing
    assert ins["kernel.2"].part == "attention"   # its own name stack
    assert ins["gte.1"].part == "other"          # plumbing stays plumbing


def test_collectives_are_a_view_of_the_same_parse():
    from megatronapp_tpu.trace.profiler_collectives import (
        extract_hlo_collectives,
    )
    # An async collective's tuple shape with tiled layouts (parentheses
    # inside parentheses): the one parser reads it.
    assert extract_hlo_collectives(HLO) == {"all-reduce-start.1": {
        "kind": "all-reduce", "bytes": 32, "groups": [[0, 1], [2, 3]],
        "axes": "", "in_loop": False}}
    assert sm.scope_map(sm.parse_hlo_text(HLO)).collectives \
        == extract_hlo_collectives(HLO)


# A step as the train step compiles: an accumulation loop whose body calls
# a computation that holds a layer loop; collectives at each depth, one in
# the outer loop's CONDITION and one behind the loops.
LOOPS_HLO = """HloModule jit_step, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y)
}

%layer_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g.1 = f32[8]{0} get-tuple-element(%p), index=1
  %all-reduce.layer = f32[8]{0} all-reduce(%g.1), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add
  %i.1 = s32[] get-tuple-element(%p), index=0
  ROOT %t.1 = (s32[], f32[8]) tuple(%i.1, %all-reduce.layer)
}

%layer_cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]) parameter(0)
  ROOT %lt = pred[] constant(false)
}

%called (q: (s32[], f32[8])) -> (s32[], f32[8]) {
  %q = (s32[], f32[8]) parameter(0)
  ROOT %while.layers = (s32[], f32[8]) while(%q), condition=%layer_cond, body=%layer_body
}

%micro_body (r: (s32[], f32[8])) -> (s32[], f32[8]) {
  %r = (s32[], f32[8]) parameter(0)
  %g.3 = f32[8]{0} get-tuple-element(%r), index=1
  %all-gather.micro = f32[16]{0} all-gather(%g.3), channel_id=3, replica_groups=[2,2]<=[4], dimensions={0}, use_global_device_ids=true
  ROOT %call.1 = (s32[], f32[8]) call(%r), to_apply=%called
}

%micro_cond (r.1: (s32[], f32[8])) -> pred[] {
  %r.1 = (s32[], f32[8]) parameter(0)
  %g.2 = f32[8]{0} get-tuple-element(%r.1), index=1
  %all-reduce.cond = f32[8]{0} all-reduce(%g.2), channel_id=2, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add
  ROOT %lt.1 = pred[] constant(false)
}

ENTRY %main (a: (s32[], f32[8])) -> f32[8] {
  %a = (s32[], f32[8]) parameter(0)
  %while.micro = (s32[], f32[8]) while(%a), condition=%micro_cond, body=%micro_body
  %g.4 = f32[8]{0} get-tuple-element(%while.micro), index=1
  ROOT %reduce-scatter.once = f32[4]{0} reduce-scatter(%g.4), channel_id=4, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true, to_apply=%add
}
"""


@pytest.mark.parametrize("name, in_loop, axes", [
    ("all-gather.micro", True, "tp"),        # a `while` body
    ("all-reduce.layer", True, "dp"),        # a body a body calls
    ("all-reduce.cond", False, "tp"),        # the outer loop's condition
    ("reduce-scatter.once", False, "dp"),    # behind the loops
])
def test_in_loop_on_hand_written_loops(name, in_loop, axes):
    """`in_loop`: the instruction lives in a `while` body, or in what one
    calls, however deep. `axes` are read by a group's PARTITIONS (positions
    in the mesh's row-major device order), so a mesh whose device ids are
    permuted, as a v5e 2x2's are (0, 1, 3, 2), names its axes right."""
    from megatronapp_tpu.trace.profiler_collectives import collectives_of
    parsed = sm.parse_hlo_text(LOOPS_HLO)
    # the inner loop's condition runs once an outer iteration
    assert parsed.loop_computations() == {"micro_body", "called",
                                          "layer_body", "layer_cond", "add"}
    Dev = collections.namedtuple("Dev", "id")
    Mesh = collections.namedtuple("Mesh", "devices axis_names")
    mesh = Mesh(np.array([[Dev(0), Dev(1)], [Dev(3), Dev(2)]], object),
                ("dp", "tp"))
    got = collectives_of(parsed, mesh)[name]
    assert (got["in_loop"], got["axes"]) == (in_loop, axes)


def test_shard_map_is_peeled(devices8):
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(devices8[:2]), ("tp",))

    def f(x):
        with jax.named_scope("attention"):
            return jax.shard_map(lambda a: jax.lax.psum(a * 2.0, "tp"),
                                 mesh=mesh, in_specs=P("tp"),
                                 out_specs=P())(x)

    text = jax.jit(f).lower(jnp.ones((8, 4))).compile().as_text()
    made = sm.scope_map(sm.parse_hlo_text(text))
    assert made.collectives                     # the psum is in there
    assert {made.instructions[name].part for name in made.collectives} \
        == {"attention"}


# ---------------------------------------------------------------------------
# A compiled train step
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def train_map(devices8):
    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import OptimizerConfig
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.optimizer import get_optimizer
    from megatronapp_tpu.training.train import gpt_microbatch_loss
    from megatronapp_tpu.training.train_state import setup_train_state
    from megatronapp_tpu.training.train_step import make_train_step

    model = TransformerConfig(num_layers=2, hidden_size=32,
                              num_attention_heads=2, vocab_size=64,
                              max_position_embeddings=16,
                              remat_policy="selective")
    par = ParallelConfig()
    ctx = build_mesh(par, devices=devices8[:1])
    opt = OptimizerConfig()
    optimizer = get_optimizer(opt, 10,
                              distributed=par.distributed_optimizer)
    state, shardings, _ = setup_train_state(
        jax.random.PRNGKey(0), lambda k: init_gpt_params(k, model),
        optimizer, ctx)
    step = make_train_step(gpt_microbatch_loss(model, ctx=ctx), optimizer,
                           opt, ctx, shardings, 10)
    shape = (2, 2, 16)                  # two micro-batches
    batch = {"tokens": np.zeros(shape, np.int32),
             "labels": np.zeros(shape, np.int32),
             "loss_mask": np.ones(shape, np.float32)}
    with ctx.mesh:
        text = step.lower(state, batch).compile().as_text()
    return sm.scope_map(sm.parse_hlo_text(text), kind="train")


def _passes_by_part(made):
    out = collections.defaultdict(set)
    for s in made.instructions.values():
        out[s.part].add(s.pass_)
    return out


@pytest.mark.parametrize("part", ["attention", "mlp", "head", "embedding"])
def test_train_step_names_the_model_in_both_passes(train_map, part):
    assert _passes_by_part(train_map)[part] == {"fwd", "bwd"}


@pytest.mark.parametrize("part", ["optimizer", "grad_accum"])
def test_train_step_names_the_loop(train_map, part):
    assert "fwd" in _passes_by_part(train_map)[part]


def test_train_step_other_keeps_its_op_name(train_map):
    assert train_map.module == "jit_step"
    others = [s for s in train_map.instructions.values()
              if s.part == "other" and s.op_name]
    assert others
    # the micro-batch scan and the layer scan are peeled, not parts
    assert any("while/body" in s.op_name for s in others)
    assert all(s.op_name == "" for s in train_map.instructions.values()
               if s.part != "other")


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

@pytest.fixture
def registry():
    sm.clear()
    yield
    sm.clear()


def test_registering_lowers_nothing_and_holds_no_array(registry):
    lowered = []
    jitted = jax.jit(lambda p, x: (p["w"] @ x).sum(), donate_argnums=())

    class Counting:
        def __call__(self, *a, **kw):
            return jitted(*a, **kw)

        def lower(self, *a, **kw):
            lowered.append(a)
            return jitted.lower(*a, **kw)

    step = sm.noted(Counting(), kind="toy")
    params = {"w": jax.device_put(jnp.ones((4, 4)), jax.devices()[0])}
    for _ in range(3):
        step(params, jnp.ones((4, 2)))
    step(params, jnp.ones((4, 3)))              # another shape, another call
    assert lowered == []
    calls = step.scope_step.calls
    assert len(calls) == 2
    leaves = jax.tree.leaves(list(calls.values()))
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                          for x in leaves)
    # a committed array keeps its sharding, an uncommitted one says none
    assert [x.sharding is not None for x in leaves] \
        == [True, False, True, False]
    maps = sm.scope_maps()
    assert len(lowered) == 2 and len(maps) == 2
    assert sm.scope_maps() == maps and len(lowered) == 2    # made once


def test_a_step_that_will_not_lower_yields_no_map(registry, caplog):
    def lower(*args, **kwargs):
        raise RuntimeError("no such backend")

    step = sm.register(lower, kind="broken")
    step.note("k", (np.zeros(3),))
    with caplog.at_level(logging.WARNING, logger=sm.__name__):
        assert sm.scope_maps() == []
    assert "broken" in caplog.text and "no such backend" in caplog.text


def test_the_registry_keeps_the_newest_steps(registry):
    steps = [sm.register(lambda: None, kind=str(i))
             for i in range(sm.MAX_STEPS + 3)]
    assert sm.registered_steps() == steps[3:]


# ---------------------------------------------------------------------------
# MegaScan reads the same map
# ---------------------------------------------------------------------------

def test_a_traced_window_without_callbacks(devices8, tmp_path, monkeypatch):
    """A backend without host callbacks keeps the host scopes and gets the
    profiled iteration's device operations by part; no fenced dispatches."""
    from megatronapp_tpu.config.parallel_config import ParallelConfig
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.config.transformer_config import TransformerConfig
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.trace import tracer as tracer_mod
    from megatronapp_tpu.trace.aggregate import aggregate_dir
    from megatronapp_tpu.training.train import pretrain_gpt

    monkeypatch.setattr(tracer_mod, "callbacks_supported", lambda: False)
    trace_dir = str(tmp_path / "trace")
    model = TransformerConfig(num_layers=2, hidden_size=32,
                              num_attention_heads=2, vocab_size=64,
                              max_position_embeddings=32)
    par = ParallelConfig()
    ctx = build_mesh(par, devices=devices8[:1])
    train = TrainingConfig(micro_batch_size=2, global_batch_size=4,
                           seq_length=16, train_iters=2, log_interval=2,
                           trace=True, trace_dir=trace_dir,
                           trace_interval=2, continuous_trace_iterations=1)
    logged = []
    pretrain_gpt(model, par, train, OptimizerConfig(lr=1e-3), ctx=ctx,
                 log_fn=logged.append)
    assert any("lacks host callbacks" in m for m in logged)
    trace = aggregate_dir(trace_dir, os.path.join(trace_dir, "agg.json"))
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert any(e["name"] == "train-step" for e in spans)
    assert not any(e.get("args", {}).get("fenced") for e in spans)
    ops = [e for e in spans if "part" in e.get("args", {})]
    parts = {e["args"]["part"] for e in ops}
    assert {"attention", "mlp", "head", "optimizer", "other"} <= parts
    assert {e["args"]["pass"] for e in ops} == {"fwd", "bwd"}
    assert all(e["pid"] >= 1000 for e in ops)       # device rows


def test_a_tpu_trace_names_operations_by_the_event(tmp_path):
    """The shape of a v5e's ``*.trace.json.gz`` (my chip run, PR 36): the
    operation is the event's name on the ``XLA Ops`` thread of a
    ``/device:TPU:<n>`` process; nothing carries ``hlo_op``."""
    import gzip
    import json
    from megatronapp_tpu.trace.profiler_collectives import (
        device_op_events, parse_profile_dir,
    )
    meta = [{"ph": "M", "pid": 3, "name": "process_name",
             "args": {"name": "/device:TPU:1"}},
            {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 701, "name": "process_name",
             "args": {"name": "/host:CPU"}}]
    events = [{"ph": "X", "pid": 3, "tid": 2, "ts": 1.0, "dur": 9.0,
               "name": "jit_toy(123)", "args": {"run_id": "4"}},
              {"ph": "X", "pid": 3, "tid": 3, "ts": 2.0, "dur": 3.0,
               "name": "fusion.1", "args": {"long_name": "%fusion.1 = ..."}},
              {"ph": "X", "pid": 3, "tid": 3, "ts": 5.0, "dur": 1.0,
               "name": "copy.3", "args": {"hlo_category": "copy"}},
              {"ph": "X", "pid": 701, "tid": 9, "ts": 0.0, "dur": 20.0,
               "name": "python3", "args": {}}]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": meta + events}, f)
    raw = parse_profile_dir(str(tmp_path))
    assert [(e["args"]["hlo_op"], e["args"]["device_ordinal"])
            for e in raw] == [("fusion.1", 1), ("copy.3", 1)]
    recs = device_op_events(raw, sm.scope_map(sm.parse_hlo_text(HLO)),
                            iteration=7, process_index=0)
    assert [(r["name"], r["pid"], r["args"]["part"], r["args"]["pass"])
            for r in recs] == [("fusion.1", 1001, "mlp", "fwd"),
                               ("copy.3", 1001, "other", "fwd")]


@pytest.mark.parametrize("error, supported", [
    (jax.errors.JaxRuntimeError("UNIMPLEMENTED: host send/recv"), False),
    (None, True),
])
def test_callbacks_probe_answers(monkeypatch, error, supported):
    from megatronapp_tpu.trace import tracer as tracer_mod
    monkeypatch.setattr(tracer_mod, "_CALLBACKS_SUPPORTED", None)
    if error is not None:
        def raising(*a, **kw):
            raise error
        monkeypatch.setattr(jax, "device_get", raising)
    assert tracer_mod.callbacks_supported() is supported


def test_callbacks_probe_does_not_swallow_a_bug(monkeypatch):
    from megatronapp_tpu.trace import tracer as tracer_mod
    monkeypatch.setattr(tracer_mod, "_CALLBACKS_SUPPORTED", None)

    def raising(*a, **kw):
        raise ValueError("a bug, not a backend's answer")
    monkeypatch.setattr(jax, "device_get", raising)
    with pytest.raises(ValueError):
        tracer_mod.callbacks_supported()
