"""The chip's compiler accepts the main path at real widths.

Every test compiles for a DESCRIBED TPU v5e 2x2 (no chip attached, nothing
runs): the Pallas kernels of the training and serving path, and the steps
the entry points jit around them. A compile that passes is not a chip run
— `chip_smoke.py` is — but what the chip's compiler refuses here costs no
chip time (on-chip-measurement guide, section 2).

This is the only file of its kind, on purpose: the process that describes
the topology loads the TPU library and keeps it until it exits, so a second
such file could land on another xdist worker and skip itself. The topology
is described inside a module-scoped fixture, never at import.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from megatronapp_tpu.config.parallel_config import ParallelConfig
from megatronapp_tpu.models.presets import PRESETS


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(monkeypatch):
    """Kernels compiled (not interpreted), `auto` attention told it is on a
    TPU (it chooses what the chip would run), the persistent compilation
    cache off: an entry written for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    from megatronapp_tpu.ops.pallas import flash_attention, kernel_gen
    from megatronapp_tpu.transformer import attention
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    monkeypatch.setattr(kernel_gen, "_interpret", lambda: False)
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the chip's compiler as the chip runs it (tests/conftest.py compiles
    # the CPU's programs without most optimisations)
    fast = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", fast)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

FLASH_SHAPES = {
    "gpt2-125m-B4-S1024-H12-D64": (4, 1024, 12, 64),
    "B2-S4096-H16-D128": (2, 4096, 16, 128),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
def test_flash_attention(one_chip, chip_compile, shape, backward):
    from megatronapp_tpu.ops.pallas.flash_attention import flash_attention
    x = _sds(FLASH_SHAPES[shape], jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    compiled = jax.jit(fn).lower(x, x, x).compile()
    # forward kernel; backward adds dq and dk/dv
    assert _custom_calls(compiled) >= (3 if backward else 1)


# (batch, query heads, kv heads, head dim, pool blocks, table blocks) of a
# dense cell; (batch, heads, latent, roped-key and value columns, pool
# blocks, table blocks) of a latent one. Blocks of 16 rows.
PAGED_SHAPES = {
    "gpt2-125m": (4, 12, 12, 64, 1024, 64),
    # serve.gpt3-2.7b.batch-closed: D 80 (padded to 128 lanes), 24 slots,
    # 2048 positions
    "gpt3-2.7b": (24, 32, 32, 80, 896, 128),
    # serve.evabyte-6.5b.bytegen-closed: 32 key/value heads of 128, the most
    # VMEM a query and a page take in any cell; a two-region table
    "evabyte-6.5b": (32, 32, 32, 128, 3584, 192),
    # serve.jamba2-3b.chat-closed: 20 query heads over one key/value head
    "jamba2-3b": (128, 20, 1, 128, 16384, 128),
    # serve.lfm2-24b-a2b.assist-closed: 4 queries a key/value head of 64
    "lfm2-24b-a2b": (192, 32, 8, 64, 16384, 128),
    # serve.laguna-xs.2.code-closed's full layers: 6 queries a head of 128,
    # 19,456 positions (its window layers' 8 a head walk the same pages)
    "laguna-xs.2": (32, 48, 8, 128, 20480, 1216),
    # serve.nemotron-3-nano-30b-a3b.reason-closed: 16 queries a key/value
    # head, 2 heads of 128 that lie in a page's rows (ISSUE 56)
    "nemotron-3-nano-30b-a3b": (192, 32, 2, 128, 49152, 384),
}
# Where a page is whole tiles the kernel starts its copies itself, a step
# ahead, into two buffers of its own (ISSUE 46): copies a step it starts, of
# the step's all (pages x pools; the others are the pipeline's blocked
# operands). 8 heads of 128 fold 256 keys a step, blocked walks 128, two
# heads of 128 in a page's rows 512.
KERNEL_COPIES = {"evabyte-6.5b": (16, 16), "laguna-xs.2": (32, 32),
                 "nemotron-3-nano-30b-a3b": (64, 64),
                 "deepseek-v2-lite": (16, 32), "longcat-flash-chat": (16, 32)}
LATENT_SHAPES = {
    # serve.deepseek-v2-lite.longgen-closed: rows of 512 + 64 columns, 32
    # slots, 4096 positions
    "deepseek-v2-lite": (32, 16, 512, 64, 128, 8192, 256),
    # serve.longcat-flash-chat.agent-closed: the same rows under 64 heads,
    # 64 slots
    "longcat-flash-chat": (64, 64, 512, 64, 128, 16384, 256),
}


@pytest.mark.parametrize("q_len", [1, 32, 512],
                         ids=["decode", "multiquery-32", "multiquery-512"])
@pytest.mark.parametrize("shape", list(PAGED_SHAPES) + list(LATENT_SHAPES))
def test_paged_attention(one_chip, chip_compile, shape, q_len):
    """The paged kernels at the cells' shapes, bf16 pools of blocks of 16:
    one query a slot, the [1, 32] ragged call of a speculative verify or a
    narrow prefill, and a [1, 512] call, which no step could hold whole
    (ISSUE 35: ~100 KB of VMEM a query at 32 heads of 128 lanes) and the
    entry points cut into query tiles."""
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    from megatronapp_tpu.ops.pallas.paged_attention import (
        paged_attention_decode, paged_attention_multiquery,
    )
    bs = 16
    bf16 = functools.partial(_sds, dtype=jnp.bfloat16, sharding=one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    if shape in LATENT_SHAPES:
        b, nq, klat, dpe, dv, nb, mb = LATENT_SHAPES[shape]
        b = b if q_len == 1 else 1
        lead = (b,) if q_len == 1 else (b, q_len)
        fn = functools.partial(kg.paged_attention_latent,
                               softmax_scale=(128 + dpe) ** -0.5)
        args = (bf16(lead + (nq, klat)), bf16(lead + (nq, dpe)),
                bf16((nb, bs, klat)), bf16((nb, bs, dpe)), i32((b, mb)),
                i32((b,)), bf16((klat, nq, dv)))
        args += () if q_len == 1 else (i32((b,)),)
    else:
        b, hq, hkv, d, nb, mb = PAGED_SHAPES[shape]
        b = b if q_len == 1 else 1
        pages = bf16((nb, bs, hkv, d))
        if q_len == 1:
            fn = paged_attention_decode
            args = (bf16((b, hq, d)), pages, pages, i32((b, mb)), i32((b,)))
        else:
            fn = paged_attention_multiquery
            args = (bf16((b, q_len, hq, d)), pages, pages, i32((b, mb)),
                    i32((b,)), i32((b,)))
    compiled = jax.jit(fn).lower(*args).compile()
    assert _custom_calls(compiled) == 1
    # Mosaic took the walk's own buffers within its default scope (the
    # call asks for no more); which pools it copies follows the shapes
    from megatronapp_tpu.utils.dispatch import page_copies
    walk, = page_copies(jax.make_jaxpr(fn)(*args).jaxpr).values()
    assert (walk["page_copies_kernel"], walk["page_copies_step"]) == \
        KERNEL_COPIES.get(shape, (0, 16))


@pytest.mark.parametrize("q_len", [1, 32], ids=["decode", "multiquery-32"])
@pytest.mark.parametrize("heads", [8, 32])
@pytest.mark.parametrize("kv", ["int8", "fp8"])
def test_paged_attention_quantized(one_chip, chip_compile, kv, heads, q_len):
    """int8 and fp8 pools of 8 and of 32 key/value heads of 128 (their tile
    is (32, 128), a bf16 pool's (16, 128)): the kernel starts the copies of
    the key and value pages itself, a page cut out of its buffer along an
    untiled dim, and the fp32 scale pages [16, heads] stay blocked operands
    of the same call; Mosaic takes both at either width. A quantized LATENT
    pool is no case here: its scale page [1, 16] of [NB, 16] is a block
    Pallas refuses for a TPU in every form, this one and the one before
    ISSUE 46 (PERF.md section 7)."""
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    from megatronapp_tpu.utils.dispatch import page_copies
    bs, nb, mb, d = 16, 2048, 128, 128
    sds = functools.partial(_sds, sharding=one_chip)
    i32 = functools.partial(sds, dtype=jnp.int32)
    pages = sds((nb, bs, heads, d), kg.QUANT_DTYPES[kv][0])
    scales = sds((nb, bs, heads), jnp.float32)
    b = 32 if q_len == 1 else 1
    lead = (b,) if q_len == 1 else (b, q_len)

    def fn(q, k, v, ks, vs, table, lens, *q_lens):
        return kg.paged_attention(q, k, v, table, lens, *q_lens,
                                  k_scales=ks, v_scales=vs)

    args = (sds(lead + (32, d), jnp.bfloat16), pages, pages, scales, scales,
            i32((b, mb)), i32((b,))) + (() if q_len == 1 else (i32((b,)),))
    compiled = jax.jit(fn).lower(*args).compile()
    assert _custom_calls(compiled) == 1
    walk, = page_copies(jax.make_jaxpr(fn)(*args).jaxpr).values()
    assert (walk["page_copies_kernel"], walk["page_copies_step"]) == (16, 32)


@pytest.mark.parametrize("q_len", [1, 32], ids=["decode", "multiquery-32"])
def test_paged_attention_tp2(topo, chip_compile, q_len):
    """The head-sharded placement (`--serve-tp`; `_tp_place`, a full-manual
    shard_map around the same call) over a tp 2 mesh at 32 key/value heads
    of 128: a shard's pool of 16 heads stays in HBM inside the shard_map
    and its kernel starts the page copies itself, as on one device (16
    pages of 16 heads a step: 2 MiB). No
    chip has run this placement for ISSUE 46 (`chip_smoke.py --chips 4`
    trains and serves nothing)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from megatronapp_tpu.config.parallel_config import TP_AXIS
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.utils.dispatch import page_copies
    ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                     devices=topo.devices[:2])

    def sds(shape, dtype, *spec):
        return _sds(shape, dtype, NamedSharding(ctx.mesh, P(*spec)))

    bs, nb, mb, heads, d = 16, 3584, 192, 32, 128
    b = 32 if q_len == 1 else 1
    lead = (b,) if q_len == 1 else (b, q_len)
    pages = sds((nb, bs, heads, d), jnp.bfloat16, None, None, TP_AXIS)
    args = (sds(lead + (heads, d), jnp.bfloat16, *[None] * len(lead),
                TP_AXIS), pages, pages, sds((b, mb), jnp.int32),
            sds((b,), jnp.int32))
    args += () if q_len == 1 else (sds((b,), jnp.int32),)
    fn = functools.partial(kg.paged_attention, mesh=ctx.mesh)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _custom_calls(compiled) == 1
    walk, = page_copies(jax.make_jaxpr(fn)(*args).jaxpr).values()
    assert (walk["page_copies_kernel"], walk["page_copies_step"]) == (32, 32)


@pytest.mark.parametrize("model,cut,width,tile", [
    ("deepseek-v2-lite", {"num_layers": 9}, 1024, 64),
    ("longcat-flash-chat", None, 512, 16),
])
def test_latent_walk_at_the_cells_widths(one_chip, chip_compile,
                                         monkeypatch, model, cut, width,
                                         tile):
    """ISSUE 39: both latent kernels at the two cells' shapes (32 slots of
    16 heads, 64 of 64) and the prefill width the engine chooses for each.
    The walk sums latent rows: its accumulator and output block are 512
    float32 columns a head, where they were a head's 128 value columns, and
    the query tile the shapes give (16 pages, 256 keys a step) fits
    Mosaic's 16 MiB (a search over `vmem_limit_bytes` reads 8.7 MB at 64
    rows of 16 heads, 10.7 at 16 rows of 64); kv_up's value columns are no
    operand of the kernel (8 MB at 64 heads), its result is the latent sum,
    and one product after it gives the heads' values."""
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    cut = LONGCAT_CUT if cut is None else cut
    seq = 4096
    assert _cell_prefill_width(one_chip, model, seq, **cut) == width
    b, nq, klat, dpe, dv, nb, mb = LATENT_SHAPES[model]
    bs = 16
    bf16 = functools.partial(_sds, dtype=jnp.bfloat16, sharding=one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    pools = (bf16((nb, bs, klat)), bf16((nb, bs, dpe)))
    w_v = bf16((klat, nq, dv))
    fn = functools.partial(kg.paged_attention_latent,
                           softmax_scale=(128 + dpe) ** -0.5)

    def lower(lead, rows, *q_lens):
        return jax.jit(fn).lower(
            bf16(lead + (nq, klat)), bf16(lead + (nq, dpe)), *pools,
            i32((rows, mb)), i32((rows,)), w_v, *q_lens).compile()

    def check(compiled, lead):
        text = compiled.as_text()
        call, = (line for line in text.splitlines()
                 if "custom-call(" in line and "tpu_custom_call" in line)
        assert " f32[" + ",".join(map(str, lead + (nq, klat))) + "]" in (
            call.split("custom-call(")[0])
        assert f"[{klat},{nq * dv}]" not in call
        assert f"[{klat},{nq},{dv}]" not in call.split("custom-call(")[1]

    check(lower((b,), b), (b,))
    seen = []
    rule = kg.query_rows_per_step
    monkeypatch.setattr(
        kg, "query_rows_per_step",
        lambda *a: seen.append(rule(*a)) or seen[-1])
    compiled = lower((1, width), 1, i32((1,)))
    assert seen == [tile]
    check(compiled, (width // tile, tile))


@pytest.mark.parametrize("q_len", [1, "chosen"],
                         ids=["decode", "multiquery-chosen-width"])
def test_paged_attention_latent_tp2(topo, one_chip, chip_compile, q_len):
    """The latent-column tp placement (`--serve-tp` on an MLA model: block
    scores, a replicated softmax, the weighted sum, two Pallas calls around
    two psums) at DeepSeek-V2-Lite's widths over a tp 2 mesh: one query a
    slot, and a prefill call of the width the engine chooses for the model
    on this chip. Its kernels hold a row's whole query block, so the call's
    queries go in as query tiles (ISSUE 35; in one block a [1, 1024] call
    asks Mosaic for 24 MiB). Blocks of 128 rows: the kernels' score block
    is [rows, block], and Mosaic takes no 16-lane block. The kernels and
    not the engine's step: an engine puts its pools on its mesh when it is
    built, which a described mesh cannot hold."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    from megatronapp_tpu.parallel.mesh import build_mesh
    if q_len == "chosen":
        q_len = _cell_prefill_width(one_chip, "deepseek-v2-lite", 4096,
                                    num_layers=9)
        assert q_len == 1024
    ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                     devices=topo.devices[:2])
    everywhere = NamedSharding(ctx.mesh, P())
    bf16 = functools.partial(_sds, dtype=jnp.bfloat16, sharding=everywhere)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=everywhere)
    b, nq, klat, dpe, dv, _, _ = LATENT_SHAPES["deepseek-v2-lite"]
    bs, nb, mb = 128, 1024, 32
    b = b if q_len == 1 else 1
    lead = (b,) if q_len == 1 else (b, q_len)
    args = (bf16(lead + (nq, klat)), bf16(lead + (nq, dpe)),
            bf16((nb, bs, klat)), bf16((nb, bs, dpe)), i32((b, mb)),
            i32((b,)), bf16((klat, nq, dv)))
    args += () if q_len == 1 else (i32((b,)),)
    compiled = jax.jit(functools.partial(
        kg.paged_attention_latent, softmax_scale=(128 + dpe) ** -0.5,
        mesh=ctx.mesh)).lower(*args).compile()
    assert _custom_calls(compiled) == 3        # scores twice, the sum
    assert "all-reduce" in compiled.as_text()


# ---------------------------------------------------------------------------
# The steps the entry points jit
# ---------------------------------------------------------------------------

def _train_step_for(devices, parallel, model, micro, global_batch, seq,
                    segments=False):
    """(jitted step, abstract state, abstract batch, ctx) exactly as
    pretrain_gpt assembles them, on a mesh of described devices. The state
    is only traced (eval_shape), never placed. `segments`: packed
    documents, a `segment_ids` row beside the tokens."""
    from megatronapp_tpu.config.training_config import (
        OptimizerConfig, TrainingConfig,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.parallel.mesh import build_mesh
    from megatronapp_tpu.training.optimizer import get_optimizer
    from megatronapp_tpu.training.train import gpt_microbatch_loss
    from megatronapp_tpu.training.train_state import setup_train_state
    from megatronapp_tpu.training.train_step import (
        batch_shardings, make_train_step,
    )
    ctx = build_mesh(parallel, devices=devices)
    train = TrainingConfig(micro_batch_size=micro,
                           global_batch_size=global_batch, seq_length=seq,
                           train_iters=10)
    opt = OptimizerConfig()
    optimizer = get_optimizer(opt, train.train_iters,
                              distributed=parallel.distributed_optimizer)
    captured = {}

    def init(rng):
        state, shardings, _ = setup_train_state(
            rng, lambda k: init_gpt_params(k, model), optimizer, ctx)
        captured["shardings"] = shardings
        return state

    struct = jax.eval_shape(init, jax.random.PRNGKey(0))
    shardings = captured["shardings"]
    state = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                         struct, shardings)
    step = make_train_step(gpt_microbatch_loss(model, ctx=ctx), optimizer,
                           opt, ctx, shardings, train.train_iters)
    num_micro = train.num_microbatches(ctx.dp * ctx.ep)
    shape = (num_micro, global_batch // num_micro, seq)
    bsh = batch_shardings(ctx)
    batch = {"tokens": _sds(shape, jnp.int32, bsh),
             "labels": _sds(shape, jnp.int32, bsh),
             "loss_mask": _sds(shape, jnp.float32, bsh),
             "position_ids": _sds(shape, jnp.int32, bsh)}
    if segments:
        batch["segment_ids"] = _sds(shape, jnp.int32, bsh)
    return step, state, batch, ctx


def test_gpt2_125m_loss_and_grad_with_flash(one_chip, chip_compile):
    """Full depth and width, micro-batch 4 x 1024, selective remat, the
    flash kernels on: forward, dq, dk/dv (and the remat'd forward)."""
    from megatronapp_tpu.models.gpt import gpt_loss, init_gpt_params
    cfg = PRESETS["gpt2-125m"](attention_impl="pallas",
                               remat_policy="selective")
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                       jax.random.PRNGKey(0)))
    tok = _sds((4, 1024), jnp.int32, one_chip)
    mask = _sds((4, 1024), jnp.float32, one_chip)

    def loss(p, tokens, labels, loss_mask):
        return gpt_loss(p, tokens, labels, loss_mask, cfg)[0]

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, tok, tok, mask).compile()
    assert _custom_calls(compiled) >= 3     # "tpu_custom_call" in the text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16 * 2**30


def _gpt2_medium(**kw):
    """perfbench/configs/gpt2-medium.json's widths: 16 heads of 64, FFN
    4096, 1024 positions (24 layers in the cell)."""
    return PRESETS["gpt2-125m"](hidden_size=1024, num_attention_heads=16,
                                ffn_hidden_size=4096, **kw)


# (model, micro-batch, packed segments, the line `auto` prints)
ONE_CHIP_STEPS = {
    "auto": (lambda: PRESETS["gpt2-125m"](num_layers=2), 4, False,
             "1024x1024, S=1024 D=64, 192 MiB of scores (compiled)"),
    "pallas": (lambda: PRESETS["gpt2-125m"](num_layers=2,
                                            attention_impl="pallas"),
               4, False, "1024x1024, S=1024 D=64 (compiled)"),
    # train.gpt2-medium.packed-1k: micro-batch 4 x 1024, 16 heads of 64,
    # packed documents, selective recomputation; depth cut 24 -> 2
    "auto-cell-1": (lambda: _gpt2_medium(num_layers=2,
                                         remat_policy="selective"),
                    4, True, "1024x1024, S=1024 D=64 segments, 256 MiB of "
                             "scores (compiled)"),
}


@pytest.mark.parametrize("case", list(ONE_CHIP_STEPS))
def test_train_step_one_chip(topo, chip_compile, capsys, case):
    """make_train_step's jit (optimizer, donation, NaN guard and all) on
    the one-device mesh build_mesh lays out for a TPU; real widths, depth
    cut to 2 layers to keep the test short. `auto` is told its backend is
    a TPU (`chip_compile`) and takes the flash kernels at S 1024, as the
    chip's own process does, inside the chip's memory."""
    from megatronapp_tpu.transformer import attention
    model, micro, segments, line = ONE_CHIP_STEPS[case]
    step, state, batch, ctx = _train_step_for(
        topo.devices[:1], ParallelConfig(), model(), micro=micro,
        global_batch=micro, seq=1024, segments=segments)
    attention._announced.clear()
    with ctx.mesh:
        compiled = step.lower(state, batch).compile()
    assert ("attention: self-attention -> pallas flash kernel, " + line
            in capsys.readouterr().out)
    _assert_kernels_named(compiled, "flash_fwd", "flash_bwd_dq",
                          "flash_bwd_dkv")
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16 * 2**30


def test_train_step_of_the_expert_share_cell(topo, chip_compile, capsys):
    """train.mellum2-12b-a2.5b.packed-8k's step as pretrain_gpt assembles it
    (perfbench/configs/mellum2-12b-a2.5b.json: 4 layers, 16 of 64 experts,
    micro-batch 1 x 8192 packed, selective recomputation) for a described
    v5e: the window layers in the flash kernels' band and the full layer in
    the plain kernels, the experts' grouped products, no [B, heads, S, S]
    scores, and a step that fits the chip and is no larger than the one
    that ran on it."""
    import json
    import os
    from perfbench import manifest
    from megatronapp_tpu.transformer import attention
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        config = json.load(f)
    tr = config["train"]
    model = manifest.load_module("models", "mellum").model_config(
        config, tr["params_dtype"], remat_policy=tr["remat_policy"])
    step, state, batch, ctx = _train_step_for(
        topo.devices[:1], ParallelConfig(), model,
        micro=tr["micro_batch_size"], global_batch=8, seq=8192,
        segments=True)
    attention._announced.clear()
    with ctx.mesh:
        compiled = step.lower(state, batch).compile()
    said = capsys.readouterr().out
    assert ("attention: self-attention -> pallas flash kernel, 512x512, "
            "S=8192 D=128 window 1024 segments (compiled)") in said
    assert ("attention: self-attention -> pallas flash kernel, 512x512, "
            "S=8192 D=128 segments (compiled)") in said
    _assert_kernels_named(compiled, "flash_window_fwd", "flash_window_bwd_dq",
                          "flash_window_bwd_dkv", "flash_fwd",
                          "flash_bwd_dq", "flash_bwd_dkv")
    text = compiled.as_text()
    assert "ragged-dot" in text
    # dense scores of one sequence would be [1, 32, 8192, 8192]
    assert not re.search(r"\[1,32,8192,8192\]|\[32,8192,8192\]", text)
    # Inside the chip's memory: the compiler holds a described v5e to its
    # 15.75 GiB of HBM and refuses a step that needs more (RESOURCE_EXHAUSTED:
    # this step differentiated by its float32 leaves "Used 15.86G of 15.75G
    # hbm"; micro-batch 2 on the chip 15.77G), so compiling at all is the
    # bound ISSUE 48 asked for. `temp_size_in_bytes` counts the donated
    # state (6.65 GiB) with the temporaries: 10.49 GiB here, where the
    # chip's allocator peaked at 10.9 GB running the step (PERF.md, PR 48);
    # 14.00 with the run of three window layers scanned. Since ISSUE 49 the
    # held experts' row buffer is one of four sizes behind a switch a pass:
    # 11.29 GiB here (11.20 to 11.29 whichever rungs, one compact rung or
    # three) while the chip's allocator peaked at 10.91 to 10.98 GB, where
    # it had; JAX's own derivative of that switch needs 20.5 GB and is
    # refused. The bound guards against a step that grows back.
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 11.5 * 2**30, (
        mem.temp_size_in_bytes / 2**30)
    assert text.count(" conditional(") >= 8     # a switch a layer and pass


def test_train_step_tp2_dp2(topo, chip_compile):
    """The sharded step of `chip_smoke.py --chips 4`: tp 2 x dp 2 on the
    2x2 mesh create_device_mesh lays out, collectives and all. A device's
    share is 2 x 1024 at 6 heads, 48 MiB of dense scores: `auto` keeps XLA's
    dense attention there, on a TPU too."""
    model = PRESETS["gpt2-125m"](num_layers=2)
    step, state, batch, ctx = _train_step_for(
        topo.devices, ParallelConfig(tensor_parallel=2), model, micro=2,
        global_batch=8, seq=1024)
    assert dict(ctx.mesh.shape) == {"pp": 1, "dp": 2, "ep": 1, "cp": 1,
                                    "tp": 2}
    assert len(set(ctx.mesh.devices.ravel().tolist())) == 4
    with ctx.mesh:
        compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    assert _custom_calls(compiled) == 0
    per_device = compiled.memory_analysis()
    assert per_device.argument_size_in_bytes < 16 * 2**30


def _gpt3_2p7b_cell(**kw):
    """perfbench/configs/gpt3-2.7b-tp2dp2.json's widths: H 2560, 32 heads
    of 80, FFN 10240, 2048 positions, fp32 parameters, selective
    recomputation (20 layers in the cell)."""
    return PRESETS["gpt2-125m"](
        hidden_size=2560, num_attention_heads=32, ffn_hidden_size=10240,
        max_position_embeddings=2048, remat_policy="selective", **kw)


def _collectives(compiled, mesh):
    from megatronapp_tpu.trace.profiler_collectives import collectives_of
    from megatronapp_tpu.trace.scope_map import parse_hlo_text
    parsed = parse_hlo_text(compiled.as_text())
    return parsed, collectives_of(parsed, mesh)


# What the parent's step (PR 41) held inside its loops over tp at these
# widths, 1 MB and more, by (kind, bytes): four bf16[1,2048,2560]
# all-reduces in the layer loops and one in the head, the head's float32
# one, the loss's tuple, and the all-to-alls around the flash kernels.
# Measured on the parent with this file's own counting (PERF.md, PR 42).
PARENT_TP_IN_LOOP = {
    ("all-reduce", 10485760): 5, ("all-reduce", 20971520): 1,
    ("all-reduce", 10493952): 1, ("all-to-all", 10485760): 1,
    ("all-to-all", 5242880): 2,
}
PARENT_FLASH_CALLS = 4      # forward, its recomputation, dq, dk/dv


def test_train_step_tp2_dp2_at_cell_widths(topo, chip_compile, capsys):
    """`train.gpt3-2.7b.tp2dp2-2k`'s step (tp 2 x dp 2, ZeRO-1, 8
    micro-batches of 1 x 2048 a rank, packed segments; depth cut 20 -> 2):
    the weights' gradients leave the loops. No collective over dp above
    1 MB lives in a `while` body but the parent's 20 MB all-to-all of the
    position embedding's gradient (no per-rank kernel); the sums over dp
    behind the loops are float32 reduce-scatters into ZeRO-1's layout, one
    a kernel; the loops' tp collectives of 1 MB and more are the parent's by
    kind, count and bytes, with no all-gather of an activation; the flash
    kernels are all there."""
    import collections
    from megatronapp_tpu.training import train_step
    model = _gpt3_2p7b_cell(num_layers=2)
    step, state, batch, ctx = _train_step_for(
        topo.devices,
        ParallelConfig(tensor_parallel=2, data_parallel=2,
                       distributed_optimizer=True),
        model, micro=1, global_batch=16, seq=2048, segments=True)
    train_step._announced.clear()
    with ctx.mesh:
        compiled = step.lower(state, batch).compile()
    assert ("gradients: weights summed over dp once a step, fp32, "
            "0.57 GB a chip (was: inside each of 8 micro-batches, as the "
            "other leaves' 0.02 GB still are)" in capsys.readouterr().out)
    parsed, colls = _collectives(compiled, ctx.mesh)
    big = {n: c for n, c in colls.items() if c["bytes"] >= 2 ** 20}

    dp_in_loop = [(c["kind"], c["bytes"]) for c in big.values()
                  if "dp" in c["axes"] and c["in_loop"]]
    assert dp_in_loop in ([], [("all-to-all", 20971520)]), dp_in_loop

    once = {n: c for n, c in big.items()
            if "dp" in c["axes"] and not c["in_loop"]
            and c["kind"] in ("all-reduce", "reduce-scatter")}
    # q, kv, out, fc1, fc2 and the word embedding
    assert [c["kind"] for c in once.values()] == ["reduce-scatter"] * 6
    assert all(parsed.instructions[n].shape.startswith("f32[")
               for n in once)

    tp_in_loop = collections.Counter(
        (c["kind"], c["bytes"]) for c in big.values()
        if c["axes"] == "tp" and c["in_loop"])
    assert tp_in_loop == PARENT_TP_IN_LOOP
    assert not [n for n, c in big.items()
                if c["kind"] == "all-gather" and c["in_loop"]]

    flash = [i for i in parsed.instructions.values()
             if i.opcode == "custom-call" and "flash_" in i.name]
    assert len(flash) == PARENT_FLASH_CALLS
    _assert_kernels_named(compiled, "flash_fwd", "flash_bwd_dq",
                          "flash_bwd_dkv")
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes
            - mem.alias_size_in_bytes) < 16 * 2**30


def test_train_step_one_chip_has_no_collective(topo, chip_compile, capsys):
    """dp = 1: no copy a rank is made, the step says nothing about
    gradients, and its compiled text holds no collective."""
    step, state, batch, ctx = _train_step_for(
        topo.devices[:1], ParallelConfig(), _gpt2_medium(num_layers=2),
        micro=4, global_batch=4, seq=1024, segments=True)
    with ctx.mesh:
        compiled = step.lower(state, batch).compile()
    assert "gradients:" not in capsys.readouterr().out
    assert not _collectives(compiled, ctx.mesh)[1]
    _assert_kernels_named(compiled, "flash_fwd", "flash_bwd_dq",
                          "flash_bwd_dkv")


_HLO_RESULT = re.compile(r"%([\w.\-]+) = \(?\w+\[([\d,]*)\]")


def _pool_shaped(compiled, named, shapes):
    """Names of the compiled module's instructions that match the pattern
    `named` and whose result has one of `shapes`."""
    dims = {",".join(map(str, sh)) for sh in shapes}
    return [name for name, d in _HLO_RESULT.findall(compiled.as_text())
            if d in dims and re.search(named, name)]


def _assert_projection_reads_the_stack(compiled, head_dim, *attentions):
    """The q/kv projections of the step read a layer's kernels out of the
    stacks `attentions` (trees of [L, H, heads x D] leaves) where they lie
    (ISSUE 51): nothing the step runs ON ITS OWN (an instruction of no
    fusion's computation: a `dynamic-slice` inside the dot's fusion is the
    read in place) is a slice or a copy with the shape of one layer's
    q_kernel or kv_kernel, in either axis order, flat or with the heads
    split off. The parent cut each out (`constant_dynamic-slice_fusion.4/.5`)
    and wrote it again heads-major (`copy.47/.48`) for a dot that had taken
    the reshape to [tokens, heads, D] in. (`slice-start` / `copy-start` and
    their `-done` are the compiler's prefetch of an operand into fast
    memory, beside other work: the one read the dot needs, made early.)"""
    from megatronapp_tpu.trace.scope_map import parse_hlo_text
    parsed = parse_hlo_text(compiled.as_text())
    fused = {i.calls for i in parsed.instructions.values()
             if i.opcode == "fusion"}
    dims = set()
    for attention in attentions:
        for name in ("q_kernel", "kv_kernel"):
            _, h, n = attention[name].shape
            for sh in ((h, n), (n, h), (h, n // head_dim, head_dim),
                       (n // head_dim, head_dim, h)):
                dims |= {",".join(map(str, sh)),
                         ",".join(map(str, (1,) + sh))}
    cut = [i.name for i in parsed.instructions.values()
           if i.computation not in fused and re.search("slice|copy", i.name)
           and not i.opcode.endswith(("-start", "-done"))
           and any(d in dims for d in re.findall(r"\w+\[([\d,]*)\]",
                                                 i.shape))]
    assert not cut, cut


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
def test_engine_paged_steps(one_chip, chip_compile, which, kv):
    """DynamicInferenceEngine's own jits at GPT-2 125M widths (depth cut
    to 2): the paged decode step at batch 4 and the one-chunk prefill, over
    a pool of 1024 blocks so that ONE LAYER's K slice (25 MB bf16, 12.6 MB
    int8) is far above everything else a step holds.

    The step updates the pool in place: what the compiler reports aliased
    is at least the pools, its temporaries are smaller than one layer's K
    slice (the parent's held a second pool), and no `copy` or
    `dynamic-update-slice` in its text gives a K/V pool or a layer's slice
    of one. An int8 pool's fp32 scale rows are part of a tile, so they are
    written by dynamic_update_slice on the loop's carry: in place too (the
    temporaries say so), and never relayouted (`copy.N`; at this size the
    compiler parks the 1.5 MB scale pools in fast memory for the loop, a
    `copy-start`/`copy-done` pair that a deployment's pool is too big
    for)."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    cfg = PRESETS["gpt2-125m"](num_layers=2)
    params, _ = init_gpt_params(jax.random.PRNGKey(0), cfg)
    eng = DynamicInferenceEngine(params, cfg, max_batch=4, paged=True,
                                 num_blocks=1024, kv_cache_dtype=kv)

    def spec(a):
        return _sds(a.shape, a.dtype, one_chip)

    p = jax.tree.map(spec, eng.params)
    pages = jax.tree.map(spec, eng.pool.pages)
    scales = jax.tree.map(spec, eng.pool.scales)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    if which == "decode":
        b = eng.max_batch
        compiled = eng._decode.lower(
            p, i32(b, 1), pages, scales, i32(b, mb), i32(b),
            _sds((b,), jnp.bool_, one_chip), None).compile()
    else:
        compiled = eng._mq_step.lower(
            p, i32(1, eng.prefill_chunk), pages, scales, i32(1, mb),
            i32(1), i32(1), _sds((1,), jnp.bool_, one_chip),
            None).compile()
    _assert_kernels_named(
        compiled, "paged_decode" if which == "decode" else "paged_mq",
        "paged_append")

    k = eng.pool.pages[0]
    layer_bytes = k[0].size * k.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= eng.pool.bytes_total
    assert mem.temp_size_in_bytes < layer_bytes, (
        mem.temp_size_in_bytes, layer_bytes)
    kv_shapes = [k.shape, (1,) + k.shape[1:], k.shape[1:]]
    all_shapes = kv_shapes + [sh for sc in eng.pool.scales or ()
                              for sh in (sc.shape, (1,) + sc.shape[1:],
                                         sc.shape[1:])]
    assert not _pool_shaped(compiled, r"^copy(\.\d+)?$", all_shapes)
    assert not _pool_shaped(
        compiled, r"copy|dynamic[-_](update[-_])?slice", kv_shapes)


def _cell_prefill_width(one_chip, model, seq, **cut):
    """The width `choose_prefill_width` gives a serving cell on the
    described chip: from the cell's own weights (its cut of the depth, bf16,
    as abstract values) and its max_seq_len."""
    from megatronapp_tpu.inference.dynamic_engine import choose_prefill_width
    from megatronapp_tpu.models.gpt import init_gpt_params
    cfg = PRESETS[model](params_dtype=jnp.bfloat16, **cut)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    kind = next(iter(one_chip.device_set)).device_kind
    return choose_prefill_width(cfg, abstract, seq, 16, device_kind=kind)


_SCOPED = re.compile(
    r'%(grouped_gemm[\w.\-]*) = [^\n]*"scoped_memory_configs":\[\{[^\]]*?'
    r'"offset":"(\d+)","size":"(\d+)"[^\n]*"used_scoped_memory_configs":'
    r'\[\{[^\]]*?"size":"(\d+)"')


def _assert_grouped_gemms(compiled, experts, rows):
    """The step's grouped GEMMs are the Pallas kernel and none is XLA's
    `ragged-dot*`; each call asked Mosaic for the VMEM of the tiles the
    chooser gives fc1's or fc2's call (past the default 16 MiB), and what
    Mosaic took of it (a call's scoped region starts at an offset, behind
    what XLA keeps for itself) holds the two weight blocks."""
    from megatronapp_tpu.ops.pallas import grouped_gemm as gg
    text = compiled.as_text()
    assert "ragged-dot" not in text
    calls = _SCOPED.findall(text)
    assert len(calls) >= 2, _kernel_names(compiled)
    want = {}
    for key in ("fc1_kernel", "fc2_kernel"):
        _, e, k, n = experts[key].shape
        tiles = gg.choose_gemm_tiles(rows, e, k, n, experts[key].dtype)
        blocks = 2 * k * tiles.n * experts[key].dtype.itemsize
        assert 8 * 2**20 < blocks <= 2 * gg.WEIGHT_BLOCK_BYTES
        want[gg.vmem_limit(tiles, experts[key].dtype)] = blocks
    assert min(want) > 16 * 2**20
    assert {int(asked) for _, _, asked, _ in calls} == set(want), (calls,
                                                                   want)
    for name, offset, asked, used in calls:
        took = int(used) - int(offset)
        assert want[int(asked)] < took <= int(asked), (name, asked, took)


# The agent cell's cut of LongCat-Flash-Chat: one chip's share of a
# deployment (perfbench/configs/longcat-flash-chat.json).
LONGCAT_CUT = {"num_layers": 4, "vocab_size": 16384,
               "vocab_slice_of": 131072, "moe_experts_held": (0, 16)}


@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
@pytest.mark.parametrize("model,batch,blocks,seq,cut,width", [
    ("gpt3-2.7b", 24, 896, 2048, {}, 256),
    ("deepseek-v2-lite", 32, 8192, 4096, {"num_layers": 9}, 1024),
    ("evabyte-6.5b", 32, 3584, 16384, {"num_layers": 8}, 256),
    ("longcat-flash-chat", 64, 16384, 4096, LONGCAT_CUT, 512),
])
def test_engine_paged_steps_at_cell_shapes(one_chip, chip_compile, model,
                                           batch, blocks, seq, cut, width,
                                           which):
    """The same two jits at the serving cells' attention shapes (a small
    vocabulary; depth cut to 2, and for DeepSeek-V2-Lite to 1 dense + 2 MoE
    layers of 8 experts at the published widths, so that the layer loop
    is a loop): D 80 over a table of 128 blocks, and latent rows of 512 +
    64 columns over a table of 256; EvaByte's 32 key/value heads of 128 (8
    layers, as the cell cuts it) over a two-region table of 192 blocks,
    not 1024, with `eva_summary` pooling filled chunks in place. The
    weights and the pool go in as abstract values; the step still aliases
    its pools.

    The experts' grouped GEMMs are the Pallas kernel `grouped_gemm*`
    (ISSUE 43; no `ragged-dot*` is left in a step), which reads the
    fc1/fc2 stacks in place through the layer id (ISSUE 31): nothing
    sliced or copied has the shape of one layer's experts, and the step's
    temporaries are smaller than one layer's fc1 kernel (the parent held
    one: `dynamic-slice_bitcast_fusion`, 94.5 MB of temporaries here).
    Mosaic takes its VMEM at the tiles `choose_gemm_tiles` gives the call:
    two whole-K weight blocks of up to 8 MiB, past the default 16 MiB.

    The prefill call is as wide as the engine makes it on this chip for the
    cell's configuration (ISSUE 35; the width is chosen from the cell's own
    weights, the step compiled at the cut depth): 256 for the dense bf16
    models (a v5e's 240 flops a byte); 1024 where a position computes on 6
    of 64 experts of what the call streams. Its ragged kernel, cut into
    query tiles, fits Mosaic's VMEM, and its head runs on one position.

    LongCat-Flash-Chat at its published widths, 2 double layers of the
    cell's 4: 64 heads through both latent kernels (the absorbed queries
    are [rows, 64, 512 + 64]; kv_up's value columns [512, 64 x 128] are an
    8 MB operand of every tile; a 512-wide call is cut into 22 tiles of 24
    positions), two planes a layer of pools [2L, 16384, 16, .], 16 held
    experts read in place, a call 512 wide (10.4 GB of weights over 5.3
    GFLOP a position: a position computes on 12 x 16 / 768 of an expert)."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine, _moe_of,
    )
    assert _cell_prefill_width(one_chip, model, seq, **cut) == width
    from megatronapp_tpu.models.gpt import init_gpt_params
    over = dict(num_layers=2, vocab_size=1024)
    if model == "gpt3-2.7b":
        # as the cell holds them (perfbench/configs/gpt3-2.7b.json)
        over.update(params_dtype=jnp.bfloat16, add_qkv_bias=True)
    if model == "deepseek-v2-lite":
        # bf16 weights, as the cell holds them: a float32 stack is
        # converted a layer at a time, which is a slice
        over.update(num_layers=3, num_moe_experts=8,
                    max_position_embeddings=seq,
                    params_dtype=jnp.bfloat16)
    if model == "evabyte-6.5b":
        over.update(num_layers=8, vocab_size=320,
                    params_dtype=jnp.bfloat16)
    if model == "longcat-flash-chat":
        # (not the cell's 16384 rows: kv_up's [512, 64 x 256] slice of a
        # layer would read as [1, width, columns] logits below)
        over = dict(cut, num_layers=2, vocab_size=1024,
                    params_dtype=jnp.bfloat16)
    cfg = PRESETS[model](**over)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    eng = DynamicInferenceEngine(abstract, cfg, max_batch=batch,
                                 max_seq_len=seq, paged=True, num_blocks=8,
                                 prefill_chunk=width)

    def spec(a):
        return _sds(a.shape, a.dtype, one_chip)

    pages = tuple(_sds((cfg.kv_planes, blocks) + p.shape[2:], p.dtype,
                       one_chip) for p in eng.pool.pages)
    mb = eng.pool.page_table.shape[1]
    assert mb == (128 + 8 * 8 if cfg.is_eva else seq // 16)

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    p = jax.tree.map(spec, abstract)
    if which == "decode":
        compiled = eng._decode.lower(
            p, i32(batch, 1), pages, None, i32(batch, mb), i32(batch),
            _sds((batch,), jnp.bool_, one_chip), None).compile()
    else:
        compiled = eng._mq_step.lower(
            p, i32(1, eng.prefill_chunk), pages, None, i32(1, mb), i32(1),
            i32(1), _sds((1,), jnp.bool_, one_chip), None, None,
            i32(1)).compile()
        # the head ran on one position: no [1, width, columns] logits
        columns = (abstract["output"].shape[1] if "output" in abstract
                   else abstract["embedding"]["word"].shape[0])
        assert columns != cfg.hidden_size
        assert not _pool_shaped(compiled, r".", [(1, width, columns)])
    family = "paged_decode" if which == "decode" else "paged_mq"
    _assert_kernels_named(
        compiled, family + ("_latent" if cfg.multi_latent_attention else ""),
        *(("eva_summary", "paged_append") if cfg.is_eva else ()))
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pages)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    if not cfg.multi_latent_attention:
        # ... and with them went what the step held of a layer's kernels
        attention = abstract["block"]["attention"]
        _assert_projection_reads_the_stack(compiled, cfg.head_dim, attention)
        kv = attention["kv_kernel"]
        assert mem.temp_size_in_bytes < (kv.size // kv.shape[0]
                                         * kv.dtype.itemsize)
    if cfg.is_moe:
        experts = _moe_of(abstract["block"])
        _assert_grouped_gemms(
            compiled, experts,
            (batch if which == "decode" else width) * cfg.moe_router_topk)
        assert not _pool_shaped(
            compiled, r"slice|copy",
            [experts[k].shape[1:] for k in ("fc1_kernel", "fc2_kernel")])
        fc1 = experts["fc1_kernel"]
        assert mem.temp_size_in_bytes < (fc1.size // fc1.shape[0]
                                         * fc1.dtype.itemsize)


@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
def test_engine_state_steps_at_cell_shapes(one_chip, chip_compile, which):
    """The two jits for a hybrid state-space stack at Jamba2-3B's published
    widths and the chat cell's sizes (a small vocabulary; 6 layers of which
    1 and 4 attend, so that both kinds of run are loops): 128 slots of
    h [16, 5120] float32 a layer, one key/value head of 128, a prefill call
    of the 256 positions the engine chooses for the cell on this chip.
    Mosaic takes `ssm_update` and the one-head pools; the step aliases the
    page pools and the state pools alike, copies nothing of the state
    pools' shape or one plane's, and holds less in temporaries than one
    layer's states: the chunk scan runs block after block
    (`transformer/ssm.SCAN_BLOCK`), and a block's operands,
    [1, 64, 16, 5120] float32 each, are no temporaries in HBM (20 MB in all
    at 256 positions; one scan over 128 positions holds 120 MB there)."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    batch, blocks, seq = 128, 16384, 2048
    width = _cell_prefill_width(one_chip, "jamba2-3b", seq)
    assert width == 256
    cfg = PRESETS["jamba2-3b"](num_layers=6, attn_layer_period=3,
                               attn_layer_offset=1, vocab_size=1024,
                               params_dtype=jnp.bfloat16)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    eng = DynamicInferenceEngine(abstract, cfg, max_batch=batch,
                                 max_seq_len=seq, paged=True, num_blocks=8,
                                 prefill_chunk=width)
    ssm, conv = eng.pool.state
    assert ssm.shape == (4, 128, 16, 5120) and ssm.dtype == jnp.float32
    pools = tuple(_sds(p.shape[:1] + (blocks,) + p.shape[2:], p.dtype,
                       one_chip) for p in eng.pool.pages) \
        + tuple(_sds(p.shape, p.dtype, one_chip) for p in (ssm, conv))
    assert pools[0].shape == (2, blocks, 16, 1, 128)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    p = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), abstract)
    if which == "decode":
        compiled = eng._decode.lower(
            p, i32(batch, 1), pools, None, i32(batch, mb), i32(batch),
            _sds((batch,), jnp.bool_, one_chip), None).compile()
        _assert_kernels_named(compiled, "paged_decode", "ssm_update")
    else:
        compiled = eng._mq_step.lower(
            p, i32(1, eng.prefill_chunk), pools, None, i32(1, mb), i32(1),
            i32(1), _sds((1,), jnp.bool_, one_chip), None, i32(1),
            i32(1)).compile()
        _assert_kernels_named(compiled, "paged_mq")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in pools)
    # (an in-place dynamic-update-slice has its operand's shape. The
    # convolution's tails are not held to this here: at 4 layers their pool
    # is 16 MB and XLA prefetches all of it; at 26 it does not: PERF.md)
    assert not _pool_shaped(compiled,
                            r"copy|transpose|(?<!update[_-])slice",
                            [ssm.shape, ssm.shape[1:], (1,) + ssm.shape[1:]])
    assert mem.temp_size_in_bytes < ssm.size // ssm.shape[0] * 4
    _assert_projection_reads_the_stack(
        compiled, cfg.head_dim, abstract["block"]["mixers_attn"]["attention"])


@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
def test_engine_ssd_moe_steps_at_cell_shapes(one_chip, chip_compile, which):
    """The two jits at granite-4.0-h-small's published widths and the rag
    cell's sizes (its cut: layers 0-9, experts 0-35 of 72, half the
    vocabulary): 64 slots of h [128, 8192] float32 a Mamba-2 layer, one
    attention layer of 8 key/value heads of 128, a prefill call of the 512
    positions the engine chooses for the cell on this chip (two chunks of
    256). Mosaic takes `ssm_update` with E tiled (a [128, 2048] block a grid
    step: the whole plane, 4 MiB, would not fit beside its copy), the
    grouped GEMMs of the held experts and the paged kernels; the step
    aliases the page pools and the state pools alike, copies nothing of the
    state pools' shape or one plane's, and a prefill call holds one chunk's
    decays, not a call's."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    batch, blocks, seq = 64, 16384, 7168
    cut = dict(num_layers=10, moe_experts_held=(0, 36), vocab_size=50176,
               vocab_slice_of=100352)
    width = _cell_prefill_width(one_chip, "granite-4.0-h-small", seq, **cut)
    assert width == 512
    cfg = PRESETS["granite-4.0-h-small"](params_dtype=jnp.bfloat16, **cut)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(abstract)) == 4_757_211_776
    # two slots give the jits; the cell's 64 go in as abstract pools
    eng = DynamicInferenceEngine(abstract, cfg, max_batch=2, max_seq_len=seq,
                                 paged=True, num_blocks=8,
                                 prefill_chunk=width)
    ssm, conv = ((p.shape[0], batch) + p.shape[2:] for p in eng.pool.state)
    assert ssm == (9, 64, 128, 8192) and conv == (9, 64, 3 * 8448)
    pools = tuple(_sds(p.shape[:1] + (blocks,) + p.shape[2:], p.dtype,
                       one_chip) for p in eng.pool.pages) \
        + (_sds(ssm, jnp.float32, one_chip),
           _sds(conv, jnp.bfloat16, one_chip))
    assert pools[0].shape == (1, blocks, 16, 8, 128)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    p = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), abstract)
    if which == "decode":
        compiled = eng._decode.lower(
            p, i32(batch, 1), pools, None, i32(batch, mb), i32(batch),
            _sds((batch,), jnp.bool_, one_chip), None).compile()
        _assert_kernels_named(compiled, "paged_decode", "ssm_update",
                              "grouped_gemm")
    else:
        compiled = eng._mq_step.lower(
            p, i32(1, eng.prefill_chunk), pools, None, i32(1, mb), i32(1),
            i32(1), _sds((1,), jnp.bool_, one_chip), None, i32(1),
            i32(1)).compile()
        _assert_kernels_named(compiled, "paged_mq", "grouped_gemm")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in pools)
    assert not _pool_shaped(compiled,
                            r"copy|transpose|(?<!update[_-])slice",
                            [ssm, ssm[1:], (1,) + ssm[1:]])
    # less than one layer's states (268 MB): a chunk's decays
    # [128, 256, 256] float32 are 33.5 MB, with the scores times them in
    # bf16 and the call's projections 120 MB; Mamba-1's scan block at these
    # sizes, [1, 64, 128, 8192] float32 an operand, would be 268 MB each
    assert mem.temp_size_in_bytes < ssm[1] * ssm[2] * ssm[3] * 4
    # weights 9.51 GB + state 2.45 GB + pages 1.07 GB and the step's own
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 13.0e9 < total < 13.5e9


@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
def test_engine_pattern_steps_at_cell_shapes(one_chip, chip_compile, which,
                                             capsys):
    """The two jits at NVIDIA-Nemotron-3-Nano-30B-A3B's published widths and
    the reason cell's sizes (its cut: layers 0-12 MEMEM*EMEMEM*, experts 0-63
    of 128, half the vocabulary): 192 slots of h [128, 4096] float32 a
    Mamba-2 layer in 8 groups, two attention layers of 2 key/value heads of
    128 under 32 query heads, five layers of 64 held two-matrix experts of
    width 1856, a prefill call of the 1,024 positions the engine chooses for
    the cell on this chip (eight chunks of 128). Mosaic takes `ssm_update`
    with a tile inside one group (a [128, 512] block a grid step, its b and
    c the group's), `grouped_gemm` at N 1856 (no multiple of 128, so
    `choose_gemm_tiles` takes N whole: fc1 a [2688, 1856] block of 9.98 MB,
    over WEIGHT_BLOCK_BYTES; fc2 K 1856 whole, 7 lane tiles of N a block)
    and the paged kernels at 16 queries a key/value head; the step aliases
    the page pools and the state pools alike and copies nothing of the state
    pools' shape or one plane's; an E layer owns no plane of any pool."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    from megatronapp_tpu.ops.pallas.grouped_gemm import (
        WEIGHT_BLOCK_BYTES, choose_gemm_tiles,
    )
    batch, blocks, seq = 192, 49152, 6144
    cut = dict(num_layers=13, moe_experts_held=(0, 64), vocab_size=65536,
               vocab_slice_of=131072)
    width = _cell_prefill_width(one_chip, "nemotron-3-nano-30b-a3b", seq,
                                **cut)
    assert width == 1024
    cfg = PRESETS["nemotron-3-nano-30b-a3b"](params_dtype=jnp.bfloat16,
                                             **cut)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(abstract)) == 3_926_018_560
    # two slots give the jits; the cell's 192 go in as abstract pools
    eng = DynamicInferenceEngine(abstract, cfg, max_batch=2, max_seq_len=seq,
                                 paged=True, num_blocks=8,
                                 prefill_chunk=width)
    ssm, conv = ((p.shape[0], batch) + p.shape[2:] for p in eng.pool.state)
    assert ssm == (6, 192, 128, 4096) and conv == (6, 192, 3 * 6144)
    pools = tuple(_sds(p.shape[:1] + (blocks,) + p.shape[2:], p.dtype,
                       one_chip) for p in eng.pool.pages) \
        + (_sds(ssm, jnp.float32, one_chip),
           _sds(conv, jnp.bfloat16, one_chip))
    assert pools[0].shape == (2, blocks, 16, 2, 128)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    p = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), abstract)
    if which == "decode":
        rows = batch * 6
        step, args = eng._decode, (
            p, i32(batch, 1), pools, None, i32(batch, mb), i32(batch),
            _sds((batch,), jnp.bool_, one_chip), None)
        compiled = step.lower(*args).compile()
        _assert_kernels_named(compiled, "paged_decode", "ssm_update",
                              "grouped_gemm")
    else:
        rows = width * 6
        step, args = eng._mq_step, (
            p, i32(1, eng.prefill_chunk), pools, None, i32(1, mb), i32(1),
            i32(1), _sds((1,), jnp.bool_, one_chip), None, i32(1), i32(1))
        compiled = step.lower(*args).compile()
        _assert_kernels_named(compiled, "paged_mq", "grouped_gemm")
    # ISSUE 56: a page of 2 heads of 128 goes to the kernel as [32, 128]
    # rows, whole tiles, and the kernel starts every copy of a step itself
    # (32 pages of K and of V, 8,192 B each); the view is a bitcast of the
    # pool as it lies: the step holds no copy, transpose or slice of a
    # pool's shape, a layer's or the view's
    from megatronapp_tpu.utils.dispatch import page_copies
    walk, = page_copies(jax.make_jaxpr(step)(*args).jaxpr).values()
    assert walk == {"page_copies_step": 64, "page_copies_kernel": 64,
                    "page_copy_bytes": [8192, 8192]}
    kv = pools[0].shape
    assert not _pool_shaped(
        compiled, r"copy|transpose|(?<!update[_-])slice",
        [kv, kv[1:], (1,) + kv[1:], kv[:2] + (32, 128), (blocks, 32, 128),
         (1, blocks, 32, 128)])
    assert _pool_shaped(compiled, "bitcast", [kv[:2] + (32, 128)])
    # what choose_gemm_tiles chose at the off-lane width, and Mosaic took
    tm1, tk1, tn1 = choose_gemm_tiles(rows, 64, 2688, 1856, jnp.bfloat16)
    tm2, tk2, tn2 = choose_gemm_tiles(rows, 64, 1856, 2688, jnp.bfloat16)
    assert (tk1, tn1) == (2688, 1856) and tk1 * tn1 * 2 > WEIGHT_BLOCK_BYTES
    assert (tk2, tn2) == (1856, 896)
    said = capsys.readouterr().out
    assert f"groups of [2688, 1856] -> pallas, tiles ({tm1}, 2688, 1856)" \
        in said
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in pools)
    assert not _pool_shaped(compiled,
                            r"copy|transpose|(?<!update[_-])slice",
                            [ssm, ssm[1:], (1,) + ssm[1:]])
    # weights 7.85 GB + state 2.46 GB + pages 1.61 GB and the step's own
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.9e9 < total < 13.0e9, total


@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
def test_engine_kda_steps_at_cell_shapes(one_chip, chip_compile, which):
    """The two jits at Solar-Open2-250B's published widths and the doc
    cell's sizes (its cut: layers 0-3 G K K K, experts 0-39 of 320, an
    eighth of the vocabulary): 128 slots of S [128, 8192] float32 a Kimi-
    delta-attention layer and one tail row over q, k and v, one gated GQA
    layer of 8 key/value heads of 128 under 64 query heads, four layers of
    40 held three-matrix experts of width 1280, a prefill call of the 1,024
    positions the engine chooses for the cell on this chip (sixteen chunks
    of 64). Mosaic takes `kda_update` (16 heads, a [128, 2048] block, a grid
    step; its q, k and decay as [128, 48] columns) beside `paged_decode` at
    8 heads (a page is whole tiles: the kernel starts its copies), and the
    chunked pass is plain XLA under the prefill call; the step aliases the
    page pools and the state pools alike and copies nothing of the state
    pool's shape or one plane's; it fits the chip."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    batch, blocks, seq = 128, 65536, 18432
    cut = dict(num_layers=4, moe_experts_held=(0, 40), vocab_size=24576,
               vocab_slice_of=196608)
    width = _cell_prefill_width(one_chip, "solar-open2-250b", seq, **cut)
    assert width == 1024
    cfg = PRESETS["solar-open2-250b"](params_dtype=jnp.bfloat16, **cut)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(abstract)) == 3_308_377_920
    # two slots give the jits; the cell's 128 go in as abstract pools
    eng = DynamicInferenceEngine(abstract, cfg, max_batch=2, max_seq_len=seq,
                                 paged=True, num_blocks=8,
                                 prefill_chunk=width)
    ssm, conv = ((p.shape[0], batch) + p.shape[2:] for p in eng.pool.state)
    assert ssm == (3, 128, 128, 8192) and conv == (3, 128, 3 * 24576)
    pools = tuple(_sds(p.shape[:1] + (blocks,) + p.shape[2:], p.dtype,
                       one_chip) for p in eng.pool.pages) \
        + (_sds(ssm, jnp.float32, one_chip),
           _sds(conv, jnp.bfloat16, one_chip))
    assert pools[0].shape == (1, blocks, 16, 8, 128)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    p = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), abstract)
    if which == "decode":
        step, args = eng._decode, (
            p, i32(batch, 1), pools, None, i32(batch, mb), i32(batch),
            _sds((batch,), jnp.bool_, one_chip), None)
        compiled = step.lower(*args).compile()
        _assert_kernels_named(compiled, "paged_decode", "kda_update",
                              "grouped_gemm")
    else:
        step, args = eng._mq_step, (
            p, i32(1, eng.prefill_chunk), pools, None, i32(1, mb), i32(1),
            i32(1), _sds((1,), jnp.bool_, one_chip), None, i32(1), i32(1))
        compiled = step.lower(*args).compile()
        _assert_kernels_named(compiled, "paged_mq", "grouped_gemm")
    from megatronapp_tpu.utils.dispatch import page_copies
    walk, = page_copies(jax.make_jaxpr(step)(*args).jaxpr).values()
    assert walk == {"page_copies_step": 32, "page_copies_kernel": 32,
                    "page_copy_bytes": [32768, 32768]}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in pools)
    assert not _pool_shaped(compiled,
                            r"copy|transpose|(?<!update[_-])slice",
                            [ssm, ssm[1:], (1,) + ssm[1:]])
    # weights 6.62 GB + state 1.67 GB + pages 4.29 GB and the step's own
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 12.5e9 < total < 13.2e9, total


# The assist cell's cut of LFM2-24B-A2B: published layers 1..9
# (perfbench/configs/lfm2-24b-a2b.json).
LFM2_CUT = {"num_layers": 9, "attn_layer_offset": 1, "moe_first_k_dense": 1}


@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
def test_engine_conv_moe_steps_at_cell_shapes(one_chip, chip_compile, which):
    """The two jits for a hybrid stack of gated short convolutions with MoE
    feed-forwards at LFM2-24B-A2B's published widths and the assist cell's
    sizes (a small vocabulary; the cell's own 9 layers: a leading dense
    convolution layer, then two periods of attention + 3 convolutions, each
    with all 64 experts): 192 slots, 8 key/value heads of 64 under 4 query
    heads each, a tail pool [7, 192, 2 x 2048] bf16, a prefill call of the
    2048 positions the engine chooses for the cell on this chip. Mosaic takes
    the D 64 group-4 pools in both kernels; the step aliases the page pools
    and the tail pool alike and cuts no layer's experts out of their stacks
    (a scanned slice of [8, 64, 2048, 3072] is a copy of 0.8 GB a layer): the
    Pallas grouped GEMM reads them through the layer id, 768 rows a decode
    round and 8,192 a prefill call, and no `ragged-dot*` is left."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    batch, blocks, seq = 192, 20480, 3072
    width = _cell_prefill_width(one_chip, "lfm2-24b-a2b", seq, **LFM2_CUT)
    assert width == 2048
    cfg = PRESETS["lfm2-24b-a2b"](vocab_size=1024, params_dtype=jnp.bfloat16,
                                  **LFM2_CUT)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    eng = DynamicInferenceEngine(abstract, cfg, max_batch=batch,
                                 max_seq_len=seq, paged=True, num_blocks=8,
                                 prefill_chunk=width)
    tails, = eng.pool.state
    assert tails.shape == (7, 192, 2 * 2048) and tails.dtype == jnp.bfloat16
    pools = tuple(_sds(p.shape[:1] + (blocks,) + p.shape[2:], p.dtype,
                       one_chip) for p in eng.pool.pages) \
        + (_sds(tails.shape, tails.dtype, one_chip),)
    assert pools[0].shape == (2, blocks, 16, 8, 64)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    p = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), abstract)
    if which == "decode":
        compiled = eng._decode.lower(
            p, i32(batch, 1), pools, None, i32(batch, mb), i32(batch),
            _sds((batch,), jnp.bool_, one_chip), None).compile()
        _assert_kernels_named(compiled, "paged_decode", "grouped_gemm")
    else:
        compiled = eng._mq_step.lower(
            p, i32(1, eng.prefill_chunk), pools, None, i32(1, mb), i32(1),
            i32(1), _sds((1,), jnp.bool_, one_chip), None, i32(1),
            i32(1)).compile()
        _assert_kernels_named(compiled, "paged_mq", "grouped_gemm")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in pools)
    _assert_grouped_gemms(
        compiled, abstract["block"]["ffn"]["moe"],
        (batch if which == "decode" else width) * cfg.moe_router_topk)
    fc1 = abstract["block"]["ffn"]["moe"]["fc1_kernel"]
    assert fc1.shape == (8, 64, 2048, 3072)
    assert not _pool_shaped(compiled, r"copy|(?<!update[_-])slice",
                            [fc1.shape[1:], (1,) + fc1.shape[1:]])
    assert mem.temp_size_in_bytes < (fc1.size // fc1.shape[0]
                                     * fc1.dtype.itemsize)


# What perfbench/configs/laguna-xs.2.json cuts the preset to: published
# layers 0-4.
LAGUNA_CUT = {"num_layers": 5}


@pytest.mark.parametrize("which", ["decode", "chunked-prefill"])
def test_engine_window_moe_steps_at_cell_shapes(one_chip, chip_compile,
                                                which):
    """The two jits for a sliding-window stack with MoE feed-forwards at
    Laguna-XS.2's published widths and the code cell's sizes (a small
    vocabulary; the cell's own 5 layers: a leading dense full-attention
    layer, then sliding, sliding, sliding, full, each with all 256 experts
    and a shared one): 32 slots of up to 19,456 positions, 8 key/value heads
    of 128 under 6 query heads each in the two full planes and 8 in the
    three window planes, a prefill call of the 2048 positions the engine
    chooses for the cell on this chip. Mosaic takes both families at both
    group sizes, the window walk's traced start among its prefetched
    scalars; the window layers' kernels carry their own names; the step
    aliases the full and the window pools alike and cuts no layer's experts
    out of their stacks."""
    from megatronapp_tpu.inference.dynamic_engine import (
        DynamicInferenceEngine,
    )
    from megatronapp_tpu.models.gpt import init_gpt_params
    batch, blocks, seq = 32, 32768, 19456
    width = _cell_prefill_width(one_chip, "laguna-xs.2", seq, **LAGUNA_CUT)
    assert width == 2048
    cfg = PRESETS["laguna-xs.2"](vocab_size=1024, params_dtype=jnp.bfloat16,
                                 **LAGUNA_CUT)
    abstract = jax.eval_shape(lambda k: init_gpt_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
    eng = DynamicInferenceEngine(abstract, cfg, max_batch=batch,
                                 max_seq_len=seq, paged=True, num_blocks=8,
                                 prefill_chunk=width)
    # every slot's window (512 / 16 + 2 blocks) and one call's rows
    assert eng.pool.num_window_blocks == 32 * 34 + 2048 // 16 + 1
    pools = tuple(_sds(p.shape[:1] + (blocks,) + p.shape[2:], p.dtype,
                       one_chip) for p in eng.pool.pages) \
        + tuple(_sds(p.shape, p.dtype, one_chip)
                for p in eng.pool.window_pages)
    assert pools[0].shape == (2, blocks, 16, 8, 128)
    assert pools[2].shape == (3, 1217, 16, 8, 128)
    mb = eng.pool.page_table.shape[1]

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    p = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), abstract)
    if which == "decode":
        compiled = eng._decode.lower(
            p, i32(batch, 1), pools, None, (i32(batch, mb), i32(batch, mb)),
            i32(batch), _sds((batch,), jnp.bool_, one_chip), None).compile()
        _assert_kernels_named(compiled, "paged_decode",
                              "paged_window_decode", "grouped_gemm")
    else:
        compiled = eng._mq_step.lower(
            p, i32(1, eng.prefill_chunk), pools, None,
            (i32(1, mb), i32(1, mb)), i32(1), i32(1),
            _sds((1,), jnp.bool_, one_chip), None, None, i32(1)).compile()
        _assert_kernels_named(compiled, "paged_mq", "paged_window_mq",
                              "grouped_gemm")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in pools)
    # (an expert's whole matrix is one block of 4 or 2 MiB here: the VMEM a
    # call asks stays under _assert_grouped_gemms' floor for large experts)
    assert "ragged-dot" not in compiled.as_text()
    assert len(_SCOPED.findall(compiled.as_text())) >= 2
    fc1 = abstract["block"]["ffn"]["moe"]["fc1_kernel"]
    assert fc1.shape == (4, 256, 2048, 1024)
    assert not _pool_shaped(compiled, r"copy|(?<!update[_-])slice",
                            [fc1.shape[1:], (1,) + fc1.shape[1:]])
    assert mem.temp_size_in_bytes < (fc1.size // fc1.shape[0]
                                     * fc1.dtype.itemsize)
    if which == "decode":
        # (a call of 2048 positions at H 2048 has activations of the
        # kernels' own shapes)
        _assert_projection_reads_the_stack(
            compiled, cfg.head_dim,
            abstract["block"]["mixers_attn"]["attention"],
            abstract["block"]["mixers_swa"]["attention"])


# ---------------------------------------------------------------------------
# Kernel names (ISSUE 26): a device trace names an event by its HLO
# instruction, and perfbench's readers tell kernels apart by family prefix
# ---------------------------------------------------------------------------

_KERNEL_NAME = re.compile(
    r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"")
_ANONYMOUS = ("closed_call", "shard_map", "custom-call")


def _kernel_names(compiled):
    """Instruction names of the compiled module's Pallas kernels."""
    return _KERNEL_NAME.findall(compiled.as_text())


def _assert_kernels_named(compiled, *families):
    """Every Pallas kernel of a compiled step carries its family's name
    (inside a scan body or under autodiff too), none the name of whatever
    encloses it."""
    names = _kernel_names(compiled)
    assert not [n for n in names if n.startswith(_ANONYMOUS)], names
    for family in families:
        assert any(family in n for n in names), (family, names)


def _compile_family(family, one_chip):
    from megatronapp_tpu.ops.pallas import flash_attention as fa
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    from megatronapp_tpu.ops.pallas.paged_attention import (
        paged_attention_decode, paged_attention_multiquery,
    )

    def bf16(*shape):
        return _sds(shape, jnp.bfloat16, one_chip)

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    if family.startswith("flash"):
        # D 64 takes the transposed orientation, D 128 the straight one.
        x = bf16(2, 1024, 4, 128 if family.endswith("_d128") else 64)

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        fn = loss if "fwd" in family else jax.grad(loss, argnums=(0, 1, 2))
        return jax.jit(fn).lower(x, x, x).compile()
    if family == "paged_append":
        # a stacked pool of 2 layers, 24 rows into layer `lid`
        return jax.jit(kg.paged_append).lower(
            bf16(2, 256, 16, 12, 64), bf16(24, 12, 64), i32(), i32(24),
            i32(24)).compile()
    if family.endswith("_latent"):
        b, nq, klat, dpe, dv, nb, bs = 4, 16, 512, 64, 128, 256, 16
        lead = (b,) if family == "paged_decode_latent" else (b, 32)
        args = (bf16(*lead, nq, klat), bf16(*lead, nq, dpe),
                bf16(nb, bs, klat), bf16(nb, bs, dpe), i32(b, 16), i32(b),
                bf16(klat, nq, dv))
        args += () if family == "paged_decode_latent" else (i32(b),)
        return jax.jit(functools.partial(
            kg.paged_attention_latent, softmax_scale=0.07)).lower(
                *args).compile()
    assert family in ("paged_decode", "paged_mq")
    b, h, d, nb, bs = 4, 12, 64, 256, 16
    pages, table, lens = bf16(nb, bs, h, d), i32(b, 16), i32(b)
    if family == "paged_decode":
        return jax.jit(paged_attention_decode).lower(
            bf16(b, h, d), pages, pages, table, lens).compile()
    return jax.jit(paged_attention_multiquery).lower(
        bf16(b, 32, h, d), pages, pages, table, lens, lens).compile()


def test_sampler_sorts_only_inside_its_ordered_branch(one_chip, chip_compile):
    """The compiled `_sample_batched` at the dense cell's shape: XLA keeps
    the one `sort` inside the conditional (a greedy round runs none), and
    what the top level does to the `[rows, vocabulary]` logits besides the
    `argmax` is an asynchronous prefetch, not a sort's relayout."""
    from megatronapp_tpu.inference.dynamic_engine import _sample_batched
    b, v = 24, 50304
    row = lambda dt: _sds((b,), dt, one_chip)  # noqa: E731
    text = jax.jit(_sample_batched).lower(
        _sds((b, v), jnp.float32, one_chip), row(jnp.int32), row(jnp.int32),
        row(jnp.int32), row(jnp.float32), row(jnp.int32), row(jnp.float32),
        row(jnp.bool_)).compile().as_text()
    assert len(re.findall(r" sort\(", text)) == 1
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert " sort(" not in entry and " conditional(" in entry
    assert not re.search(rf"f32\[{b},{v}\]\S* copy\(", entry)


@pytest.mark.parametrize("family,prefixes", [
    ("flash_fwd", ["flash_fwd"]),
    ("flash_fwd_d128", ["flash_fwd"]),
    ("flash_bwd", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ("flash_bwd_d128", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ("paged_decode", ["paged_decode"]),
    ("paged_mq", ["paged_mq"]),
    ("paged_decode_latent", ["paged_decode_latent"]),
    ("paged_mq_latent", ["paged_mq_latent"]),
    ("paged_append", ["paged_append"]),
])
def test_kernel_family_names(one_chip, chip_compile, family, prefixes):
    """Each family's kernel is a `tpu_custom_call` whose HLO instruction
    name starts with the family's name. Under autodiff JAX wraps the name
    in the transformation (`jvp_flash_fwd_t_`,
    `transpose_jvp_flash_bwd_dq_t__`), so there, and in every reader, the
    rule is that the name CONTAINS the family's."""
    names = _kernel_names(_compile_family(family, one_chip))
    assert names, "no tpu_custom_call in the compiled module"
    differentiated = "bwd" in family
    for prefix in prefixes:
        assert any(prefix in n if differentiated else n.startswith(prefix)
                   for n in names), (prefix, names)
    assert not [n for n in names if n.startswith(_ANONYMOUS)], names


def test_lora_kernel_name():
    """The segmented LoRA kernel's name, from the traced program: Mosaic
    refuses the kernel today (it loads a vector from SMEM; LoRA serving has
    not run on the chip, ROADMAP S7), so there is no compiled HLO to read."""
    from megatronapp_tpu.ops.pallas import kernel_gen as kg
    jaxpr = jax.make_jaxpr(kg.lora_segmented_delta)(
        jnp.zeros((8, 64), jnp.bfloat16), jnp.zeros((4, 64, 8), jnp.bfloat16),
        jnp.zeros((4, 8, 64), jnp.bfloat16), jnp.zeros((8,), jnp.int32))
    names = [e.params["name"]
             for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert names and all(n.startswith("lora_") for n in names), names
