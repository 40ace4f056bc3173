"""Weight gradients cross dp once a step (training/train_step.py,
ops/per_rank.py): the mathematics is the one-device step's.

CPU, tiny widths, float32 compute so that the parameters after two steps
can be held to 2e-4 (Adam's first steps move a weight by about lr whatever
its gradient's size, so a bf16 run's rounding flips a few by 2 lr). Every
case runs 4 micro-batches of packed documents whose loss-mask counts differ
between data-parallel ranks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.config.training_config import OptimizerConfig
from megatronapp_tpu.models.gpt import init_gpt_params
from megatronapp_tpu.models.presets import PRESETS
from megatronapp_tpu.parallel.mesh import build_mesh
from megatronapp_tpu.training import train_step as ts
from megatronapp_tpu.training.optimizer import get_optimizer
from megatronapp_tpu.training.train import (
    gpt_microbatch_loss, gpt_rank_kernels,
)
from megatronapp_tpu.training.train_state import setup_train_state

M, B, S, V = 4, 4, 32, 128


def _model(**kw):
    return PRESETS["gpt2-125m"](
        num_layers=2, hidden_size=64, num_attention_heads=4,
        ffn_hidden_size=128, vocab_size=V, max_position_embeddings=64,
        remat_policy="selective", compute_dtype=jnp.float32, **kw)


def _batch(nan_row=None):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, V, (M, B, S)).astype(np.int32)
    mask = (rng.random((M, B, S)) < 0.7).astype(np.float32)
    mask[:, 0, :20] = 0          # rank 0 counts fewer tokens than the rest
    seg = np.zeros((M, B, S), np.int32)
    seg[:, :, 16:] = 1
    seg[:, 1, 10:] = 1
    if nan_row is not None:
        mask[1, nan_row, 3] = np.nan
    return {"tokens": tok, "labels": np.roll(tok, -1, -1),
            "loss_mask": mask, "segment_ids": seg,
            "position_ids": np.broadcast_to(
                np.arange(S, dtype=np.int32), (M, B, S)).copy()}


def _run(devices, tp, dp, zero1, impl="reference", steps=2, nan_row=None,
         **opt):
    """(metrics of each step, parameters after the last, the step's
    `gradients:` line or None)."""
    cfg = _model(attention_impl=impl)
    par = ParallelConfig(tensor_parallel=tp, data_parallel=dp,
                         distributed_optimizer=zero1)
    ctx = build_mesh(par, devices=devices[:tp * dp])
    opt_cfg = OptimizerConfig(lr=1e-2, **opt)
    optimizer = get_optimizer(opt_cfg, 10, distributed=zero1)
    ts._announced.clear()
    with ctx.mesh:
        state, shardings, _ = setup_train_state(
            jax.random.PRNGKey(0), lambda k: init_gpt_params(k, cfg),
            optimizer, ctx)
        step = ts.make_train_step(
            gpt_microbatch_loss(cfg, ctx=ctx), optimizer, opt_cfg, ctx,
            shardings, 10, donate=False)
        batch, out = _batch(nan_row), []
        for _ in range(steps):
            state, m = step(state, batch)
            out.append({k: float(v) for k, v in m.items()})
    line = next(iter(ts._announced), None)
    return out, jax.tree.map(np.asarray, state["params"]), line


@pytest.fixture(scope="module")
def one_device(devices8):
    return _run(devices8, 1, 1, False)


@pytest.mark.parametrize("tp, dp, zero1, impl", [
    (2, 2, True, "reference"), (2, 2, False, "reference"),
    (1, 4, True, "reference"), (1, 4, False, "reference"),
    # the flash kernels (interpreted), placed over (dp, ep, tp) as ever
    (2, 2, True, "pallas"),
])
def test_two_steps_match_one_device(devices8, one_device, tp, dp, zero1,
                                    impl):
    want, want_params, said = one_device
    assert said is None                     # one rank: nothing to say
    got, params, line = _run(devices8, tp, dp, zero1, impl)
    assert line.startswith("weights summed over dp once a step, fp32, ")
    assert "(was: inside each of 4 micro-batches" in line
    for w, g in zip(want, got):
        for key in ("loss", "lm_loss", "grad_norm"):
            np.testing.assert_allclose(g[key], w[key], atol=2e-5, rtol=0)
        assert g["skipped"] == 0
    for w, g in zip(jax.tree.leaves(want_params), jax.tree.leaves(params)):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=0)


@pytest.mark.parametrize("nan_row", [0, 3], ids=["rank0", "last-rank"])
def test_a_nan_in_one_ranks_micro_batch_skips_the_step(devices8, nan_row):
    start = _run(devices8, 2, 2, True, steps=0)[1]
    got, params, _ = _run(devices8, 2, 2, True, steps=1, nan_row=nan_row)
    assert got[0]["skipped"] == 1 and not np.isfinite(got[0]["loss"])
    for a, b in zip(jax.tree.leaves(start), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_the_manual_zero1_update_takes_the_one_sum(devices8, one_device):
    """`--dist-opt-comm ring`: its region reads `grads` laid out like the
    parameters, which is what the one sum hands it."""
    got, params, line = _run(devices8, 2, 2, True, dist_opt_comm="ring")
    assert line.startswith("weights summed over dp once a step")
    np.testing.assert_allclose(got[1]["loss"], one_device[0][1]["loss"],
                               atol=2e-5, rtol=0)
    for w, g in zip(jax.tree.leaves(one_device[1]),
                    jax.tree.leaves(params)):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=0)


# ---------------------------------------------------------------------------
# The modes that keep the sum inside every micro-batch, and the line
# ---------------------------------------------------------------------------

def _ctx(devices, **par):
    return build_mesh(ParallelConfig(**par),
                      devices=devices[:int(np.prod(list(par.values())))])


def _shardings(ctx, spec=None):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return {"params": {"w": NamedSharding(ctx.mesh, spec or P())}}


@pytest.mark.parametrize("mode, why", [
    (dict(mesh=dict(pipeline_parallel=2, data_parallel=2), pipeline=True),
     "the pipeline schedules its micro-batches"),
    (dict(mesh=dict(expert_parallel=2, data_parallel=2)), "ep > 1"),
    (dict(mesh=dict(context_parallel=2, data_parallel=2)), "cp > 1"),
    (dict(mesh=dict(data_parallel=2), fp8=True), "fp8 multiplies inside"),
    (dict(mesh=dict(data_parallel=2), model=dict(num_moe_experts=4)),
     "the experts' grouped products"),
    (dict(mesh=dict(tensor_parallel=2, data_parallel=2),
          model=dict(tp_comm_overlap=True)), "--tp-comm-overlap's rings"),
    (dict(mesh=dict(data_parallel=2), loss=lambda p, b: (0.0, {})),
     "this loss names no per-rank kernels"),
    (dict(mesh=dict(data_parallel=2), fsdp=True),
     "the parameters are split over dp themselves"),
])
def test_the_modes_that_keep_the_per_micro_batch_sum_say_so(devices8, mode,
                                                            why):
    from jax.sharding import PartitionSpec as P
    ctx = _ctx(devices8, **mode["mesh"])
    loss = mode.get("loss") or gpt_microbatch_loss(
        _model(**mode.get("model", {})), ctx=ctx)
    reason = ts.per_micro_batch_reason(
        loss, ctx, _shardings(ctx, P("dp") if mode.get("fsdp") else None),
        pipeline=mode.get("pipeline", False), fp8=mode.get("fp8", False))
    assert reason is not None and why in reason, reason


def test_a_dense_gpt_names_its_kernels(devices8):
    kernels, why_not = gpt_rank_kernels(_model())
    assert why_not is None
    # (the copies' axis, their type): behind the layers' axis and in the
    # compute type for the stack's, first and as it is for the embedding
    assert {p[-1]: v for p, v in kernels.items()} == {
        **{k: (1, jnp.float32) for k in (
            "q_kernel", "kv_kernel", "out_kernel", "fc1_kernel",
            "fc2_kernel")}, "word": (0, None)}
    assert ("output",) in gpt_rank_kernels(
        _model(untie_embeddings_and_output_weights=True))[0]
    ctx = _ctx(devices8, tensor_parallel=2, data_parallel=2)
    assert ts.per_micro_batch_reason(
        gpt_microbatch_loss(_model(), ctx=ctx), ctx, _shardings(ctx)) is None


def test_a_step_on_the_old_path_prints_the_other_line(devices8, capsys):
    """An MoE model at dp 2: the step still runs, GSPMD sums inside every
    micro-batch, and the line says why."""
    cfg = _model(num_moe_experts=4)
    ctx = _ctx(devices8, data_parallel=2)
    opt_cfg = OptimizerConfig(lr=1e-2)
    optimizer = get_optimizer(opt_cfg, 10, distributed=True)
    ts._announced.clear()
    with ctx.mesh:
        state, shardings, _ = setup_train_state(
            jax.random.PRNGKey(0), lambda k: init_gpt_params(k, cfg),
            optimizer, ctx)
        step = ts.make_train_step(
            gpt_microbatch_loss(cfg, ctx=ctx), optimizer, opt_cfg, ctx,
            shardings, 10, donate=False)
        _, m = step(state, _batch())
    assert np.isfinite(float(m["loss"]))
    assert ("gradients: summed over dp inside every micro-batch (GSPMD): "
            "the experts' grouped products" in capsys.readouterr().out)
