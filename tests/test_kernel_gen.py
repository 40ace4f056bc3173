"""The paged-attention kernel generator (ops/pallas/kernel_gen.py).

- the GENERATOR emits kernels held two ways (ISSUE 29: the walk folds
  several pages a step, an order the legacy bodies do not have): BITWISE
  against a test-local jax.numpy replay of the walk (same pages a step,
  same tile order), and allclose against the legacy hand-written
  variants it replaced. The legacy bodies are deleted from the tree, so
  FROZEN copies live here as the oracle (verbatim the pre-ISSUE-11
  `_decode_kernel` / `_multiquery_kernel` + their pallas_call
  builders), pinned across {fp32, bf16} × {bf16, int8 pools} × {tp1,
  tp2} × {q_len 1, ragged} × {GQA, MHA};
- the walk itself: all four bodies over fp32, bf16, int8 and fp8 pages,
  scale pages included, never read past a slot's length;
- the engine on these kernels: what /stats counts of a decode step, and
  streams at the dense cell's heads of 80 against the dense oracle;
- flash backward head-fold grad parity <= 1e-5 and scan-unroll loss
  parity (exact) — the two staged PERF levers;
- eligibility reasons name the SPECIFIC failed predicate.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatronapp_tpu.config.parallel_config import TP_AXIS, ParallelConfig
from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.inference.dynamic_engine import DynamicInferenceEngine
from megatronapp_tpu.inference.engine import SamplingParams
from megatronapp_tpu.models.gpt import init_gpt_params
from megatronapp_tpu.ops.pallas import kernel_gen
from megatronapp_tpu.ops.pallas.kernel_gen import (
    _NEG_INF, _dequant_block, _interpret, _pages_vmem_bytes,
    default_kv_tile, paged_attention, paged_attention_latent,
    pages_per_step,
)
from megatronapp_tpu.ops.pallas.paged_attention import (
    paged_attention_latent_reference, paged_attention_multiquery_reference,
    paged_attention_reference, quantize_kv_rows,
)
from megatronapp_tpu.parallel.mesh import build_mesh

# ---------------------------------------------------------------------------
# FROZEN legacy kernels (pre-ISSUE-11 ops/pallas/paged_attention.py,
# verbatim): the bitwise oracle for the generator. Do not "fix" or
# refactor these — their op order IS the spec.
# ---------------------------------------------------------------------------


def _legacy_decode_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                          scale, block_size, num_blocks_seq, hkv, group,
                          quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    hq = hkv * group

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    kv_len = lens_ref[b]

    @pl.when(j * block_size < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        if quantized:
            k = _dequant_block(k_ref[0], ks_ref[0])
            v = _dequant_block(v_ref[0], vs_ref[0])
        else:
            k = k_ref[0]
            v = v_ref[0]
        d = q.shape[-1]
        q3 = q.reshape(hkv, group, d)
        k3 = jnp.swapaxes(k, 0, 1)
        v3 = jnp.swapaxes(v, 0, 1)
        s = jax.lax.dot_general(
            q3.astype(k3.dtype), k3,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)[0]
        valid = pos < kv_len
        s = jnp.where(valid[None, None, :], s, _NEG_INF)
        s2 = s.reshape(hq, block_size)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1))
        m_safe = jnp.maximum(m_new, _NEG_INF / 2)
        p = jnp.exp(s2 - m_safe[:, None])
        p = jnp.where(valid[None, :], p, 0.0)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, corr)
        l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=1)
        p3 = p.reshape(hkv, group, block_size)
        pv = jax.lax.dot_general(
            p3.astype(v3.dtype), v3,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr[:, None] + pv.reshape(hq, d)
        m_scr[:, 0] = m_new

    @pl.when(j == num_blocks_seq - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0], 1e-20)
        o_ref[0] = (acc[:] / l[:, None]).astype(o_ref.dtype)


def legacy_paged_attention_decode(q, k_pages, v_pages, page_table, kv_lens,
                                  softmax_scale=None, k_scales=None,
                                  v_scales=None):
    b, hq, d = q.shape
    nb, bs, hkv, _ = k_pages.shape
    mb = page_table.shape[1]
    group = hq // hkv
    quantized = k_scales is not None
    if softmax_scale is None:
        softmax_scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _legacy_decode_kernel, scale=float(softmax_scale), block_size=bs,
        num_blocks_seq=mb, hkv=hkv, group=group, quantized=quantized)

    kv_spec = pl.BlockSpec((1, bs, hkv, d),
                           lambda b_, j, t, l: (t[b_, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, hq, d), lambda b_, j, t, l: (b_, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        sc_spec = pl.BlockSpec((1, bs, hkv),
                               lambda b_, j, t, l: (t[b_, j], 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, d), lambda b_, j, t, l: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, d), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        interpret=_interpret(),
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
      *operands)


def _legacy_multiquery_kernel(table_ref, lens_ref, qlens_ref, q_ref, k_ref,
                              v_ref, *rest, scale, block_size,
                              num_blocks_seq, hkv, group, s_q,
                              quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    hq = hkv * group

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    kv_len = lens_ref[b]
    q_len = qlens_ref[b]
    q_start = kv_len - q_len

    @pl.when(j * block_size < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        if quantized:
            k = _dequant_block(k_ref[0], ks_ref[0])
            v = _dequant_block(v_ref[0], vs_ref[0])
        else:
            k = k_ref[0]
            v = v_ref[0]
        d = q.shape[-1]
        q3 = jnp.transpose(q.reshape(s_q, hkv, group, d),
                           (1, 0, 2, 3)).reshape(hkv, s_q * group, d)
        k3 = jnp.swapaxes(k, 0, 1)
        v3 = jnp.swapaxes(v, 0, 1)
        s = jax.lax.dot_general(
            q3.astype(k3.dtype), k3,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)[0]
        row_q = jax.lax.broadcasted_iota(
            jnp.int32, (s_q * group, 1), 0)[:, 0] // group
        abs_q = q_start + row_q
        valid = ((pos[None, :] <= abs_q[:, None])
                 & (pos[None, :] < kv_len))
        s = jnp.where(valid[None], s, _NEG_INF)
        s2 = jnp.transpose(
            s.reshape(hkv, s_q, group, block_size),
            (1, 0, 2, 3)).reshape(s_q * hq, block_size)
        valid2 = jnp.transpose(
            jnp.broadcast_to(valid.reshape(1, s_q, group, block_size),
                             (hkv, s_q, group, block_size)),
            (1, 0, 2, 3)).reshape(s_q * hq, block_size)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1))
        m_safe = jnp.maximum(m_new, _NEG_INF / 2)
        p = jnp.exp(s2 - m_safe[:, None])
        p = jnp.where(valid2, p, 0.0)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, corr)
        l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=1)
        p3 = jnp.transpose(
            p.reshape(s_q, hkv, group, block_size),
            (1, 0, 2, 3)).reshape(hkv, s_q * group, block_size)
        pv = jax.lax.dot_general(
            p3.astype(v3.dtype), v3,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        pv2 = jnp.transpose(
            pv.reshape(hkv, s_q, group, d),
            (1, 0, 2, 3)).reshape(s_q * hq, d)
        acc[:] = acc[:] * corr[:, None] + pv2
        m_scr[:, 0] = m_new

    @pl.when(j == num_blocks_seq - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0], 1e-20)
        a = acc[:]
        o_ref[0] = (a / l[:, None]).reshape(
            s_q, hq, a.shape[-1]).astype(o_ref.dtype)


def legacy_paged_attention_multiquery(q, k_pages, v_pages, page_table,
                                      kv_lens, q_lens, softmax_scale=None,
                                      k_scales=None, v_scales=None):
    b, s_q, hq, d = q.shape
    nb, bs, hkv, _ = k_pages.shape
    mb = page_table.shape[1]
    group = hq // hkv
    quantized = k_scales is not None
    if softmax_scale is None:
        softmax_scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _legacy_multiquery_kernel, scale=float(softmax_scale),
        block_size=bs, num_blocks_seq=mb, hkv=hkv, group=group, s_q=s_q,
        quantized=quantized)

    kv_spec = pl.BlockSpec((1, bs, hkv, d),
                           lambda b_, j, t, l, ql: (t[b_, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, s_q, hq, d),
                     lambda b_, j, t, l, ql: (b_, 0, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        sc_spec = pl.BlockSpec((1, bs, hkv),
                               lambda b_, j, t, l, ql: (t[b_, j], 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, s_q, hq, d),
                               lambda b_, j, t, l, ql: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s_q * hq, d), jnp.float32),
            pltpu.VMEM((s_q * hq, 1), jnp.float32),
            pltpu.VMEM((s_q * hq, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_q, hq, d), q.dtype),
        interpret=_interpret(),
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q_lens.astype(jnp.int32), *operands)


# ---------------------------------------------------------------------------
# Generator pins: bitwise vs a replay of the walk, allclose vs the legacy
# ---------------------------------------------------------------------------


def _mk_inputs(rng, b, s_q, hq, hkv, d, bs, mb, quant, dtype):
    nb = b * mb + 1
    shape = (b, s_q, hq, d) if s_q else (b, hq, d)
    q = jnp.asarray(rng.normal(size=shape), dtype)
    kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), dtype)
    tbl = jnp.asarray(
        rng.permutation(nb - 1)[: b * mb].reshape(b, mb) + 1, jnp.int32)
    lens = jnp.asarray(rng.integers(1, bs * mb, b), jnp.int32)
    ks = vs = None
    if quant:
        kp, ks = quantize_kv_rows(kp)
        vp, vs = quantize_kv_rows(vp)
    return q, kp, vp, tbl, lens, ks, vs


def _pages_for(pools, mb, key_tile=None):
    """Pages a step, as the entry points derive them for these pools
    ([NB, bs, ...] each, KV pools first, then their scale pools)."""
    pools = [p[None] for p in pools if p is not None]
    quant = len(pools) == 4
    tile = default_kv_tile("int8" if quant else None)
    bs, page = pools[0].shape[2], _pages_vmem_bytes(pools, tile)
    copied = [kernel_gen._page_is_tiles(p) for p in pools[:2]]
    return pages_per_step(
        bs, page, mb,
        key_tile=key_tile or kernel_gen.dense_key_tile(bs, page, copied))


def _step_blocks(tbl_row, kv_len, i, pages, bs):
    """Pool blocks of step i of a slot's walk, as `_walk_call`'s index
    maps name them: page i*pages + p where the slot holds it, else the
    page that operand held a step earlier (the slot's last page when
    there is no earlier step), block 0 for a slot that holds nothing."""
    held = (kv_len + bs - 1) // bs
    page = i * pages + jnp.arange(pages, dtype=jnp.int32)
    page = jnp.where(page < held, page,
                     jnp.where(i > 0, page - pages, held - 1))
    return jnp.where(held > 0, tbl_row[jnp.maximum(page, 0)], 0)


def _fold_tile(s, valid, live, state, values):
    """One online-softmax fold of the walk (emit_paged_kernel's `_fold`,
    op for op); a step past the slot's rows (not `live`) keeps the
    state, which is value-identical to the kernel not running it."""
    acc, m_scr, l_scr = state
    s = jnp.where(valid, s, _NEG_INF)
    m_new = jnp.maximum(m_scr, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_new, _NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(jnp.minimum(m_scr - m_new, 0.0))
    corr = jnp.where(m_scr <= _NEG_INF / 2, 0.0, corr)
    l_new = l_scr * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + values(p)
    return (jnp.where(live, acc_new, acc), jnp.where(live, m_new, m_scr),
            jnp.where(live, l_new, l_scr))


@functools.partial(jax.jit, static_argnames=("scale", "pages", "ragged"))
def _dense_walk_sim(q, kp, vp, tbl, kv_lens, q_lens, ks, vs, *, scale,
                    pages, ragged):
    """jnp replay of the dense walk (emit_paged_kernel + _dense_tile):
    the same pages a step, the same tile order, the same ops a tile.
    Jitted as ONE computation so XLA fuses as it does the interpreted
    kernel body. Do not "simplify" the arithmetic: its order is the
    pin."""
    if ragged:
        b, s_q, hq, d = q.shape
    else:
        (b, hq, d), s_q = q.shape, 1
    _, bs, hkv, _ = kp.shape
    group = hq // hkv
    rows, width = s_q * group, pages * bs
    steps = -(-tbl.shape[1] // pages)

    def tile(pool, scales, blocks):
        x = pool[blocks].reshape(width, hkv, d)
        if scales is None:
            return x
        return (x.astype(jnp.float32)
                * scales[blocks].reshape(width, hkv)[..., None])

    outs = []
    for bi in range(b):
        qf = q[bi].astype(jnp.float32) * scale
        if s_q > 1:
            q3 = jnp.transpose(qf.reshape(s_q, hkv, group, d),
                               (1, 0, 2, 3)).reshape(hkv, rows, d)
        else:
            q3 = qf.reshape(hkv, group, d)
        kv_len = kv_lens[bi]
        state = (jnp.zeros((hkv, rows, d), jnp.float32),
                 jnp.full((hkv, rows, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((hkv, rows, 1), jnp.float32))
        for i in range(steps):
            blocks = _step_blocks(tbl[bi], kv_len, i, pages, bs)
            k3 = jnp.swapaxes(tile(kp, ks, blocks), 0, 1)
            v3 = jnp.swapaxes(tile(vp, vs, blocks), 0, 1)
            s = jax.lax.dot_general(
                q3.astype(k3.dtype), k3, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            pos = i * width + jnp.arange(width, dtype=jnp.int32)[None, :]
            row_q = jnp.arange(rows, dtype=jnp.int32)[:, None] // group
            abs_q = (kv_len - (q_lens[bi] if ragged else 1)) + row_q
            valid = (pos < kv_len) & (pos <= abs_q)
            state = _fold_tile(
                s, valid, i * width < kv_len, state,
                lambda p, v3=v3: jax.lax.dot_general(
                    p.astype(v3.dtype), v3, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32))
        a = state[0] / jnp.maximum(state[2], 1e-20)
        if s_q > 1:
            a = jnp.transpose(a.reshape(hkv, s_q, group, d), (1, 0, 2, 3))
        outs.append(a.reshape(q.shape[1:]).astype(q.dtype))
    return jnp.stack(outs)


def _dense_sim(q, kp, vp, tbl, lens, q_lens=None, ks=None, vs=None):
    return _dense_walk_sim(
        q, kp, vp, tbl, lens, q_lens, ks, vs,
        scale=1.0 / (q.shape[-1] ** 0.5),
        pages=_pages_for([kp, vp, ks, vs], tbl.shape[1]),
        ragged=q_lens is not None)


def _legacy_tol(dtype):
    """The walk against the frozen legacy bodies: the same mathematics
    folded a tile of several pages at a time, so fp32 agrees to rounding
    (measured 4.8e-7 at these shapes) and a bf16 result to its last bits
    (measured 3.9e-3 = half an ulp at 1: one rounding of the output)."""
    return (dict(atol=2e-6, rtol=2e-6) if dtype == jnp.float32
            else dict(atol=1.6e-2, rtol=1.6e-2))


def _assert_close(a, b, **tol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **tol)


# (block size, table blocks): one step of 4 pages; three steps of 4
# pages with a partial last one
_WALK_SHAPES = [(8, 4), (32, 10)]


class TestGeneratorBitwise:
    """The emitted kernels are held two ways (ISSUE 29): BITWISE against
    the test-local replay of the walk (same pages a step, same tile
    order: the op order is the contract) and allclose against the frozen
    legacy bodies, which fold one page at a time."""

    @pytest.mark.parametrize("bs,mb", _WALK_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("quant", [False, True])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
    def test_decode_bitwise(self, dtype, quant, hq, hkv, bs, mb):
        rng = np.random.default_rng(0)
        q, kp, vp, tbl, lens, ks, vs = _mk_inputs(
            rng, 3, 0, hq, hkv, 16, bs, mb, quant, dtype)
        gen = paged_attention(q, kp, vp, tbl, lens, k_scales=ks,
                              v_scales=vs)
        sim = _dense_sim(q, kp, vp, tbl, lens, ks=ks, vs=vs)
        assert bool(jnp.all(gen == sim))
        legacy = legacy_paged_attention_decode(q, kp, vp, tbl, lens,
                                               k_scales=ks, v_scales=vs)
        _assert_close(gen, legacy, **_legacy_tol(dtype))

    @pytest.mark.parametrize("bs,mb", _WALK_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("quant", [False, True])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
    def test_multiquery_bitwise_ragged(self, dtype, quant, hq, hkv, bs, mb):
        rng = np.random.default_rng(1)
        s_q = 5
        q, kp, vp, tbl, lens, ks, vs = _mk_inputs(
            rng, 3, s_q, hq, hkv, 16, bs, mb, quant, dtype)
        lens = jnp.maximum(lens, s_q)
        qlens = jnp.asarray([s_q, 2, 1], jnp.int32)
        gen = paged_attention(q, kp, vp, tbl, lens, q_lens=qlens,
                              k_scales=ks, v_scales=vs)
        sim = _dense_sim(q, kp, vp, tbl, lens, qlens, ks, vs)
        assert bool(jnp.all(gen == sim))
        legacy = legacy_paged_attention_multiquery(
            q, kp, vp, tbl, lens, qlens, k_scales=ks, v_scales=vs)
        _assert_close(gen, legacy, **_legacy_tol(dtype))

    @pytest.mark.parametrize("bs,mb", _WALK_SHAPES)
    def test_multiquery_qlen1_bitwise_vs_decode(self, bs, mb):
        """At q_len == 1 the ragged emission collapses bitwise to the
        decode emission (one template, two points), and both to the
        legacy decode body within rounding."""
        rng = np.random.default_rng(2)
        q, kp, vp, tbl, lens, ks, vs = _mk_inputs(
            rng, 3, 0, 4, 2, 16, bs, mb, False, jnp.float32)
        dec = paged_attention(q, kp, vp, tbl, lens)
        mq = paged_attention(q[:, None], kp, vp, tbl, lens,
                             q_lens=jnp.ones((3,), jnp.int32))
        assert bool(jnp.all(dec == mq[:, 0]))
        _assert_close(dec, legacy_paged_attention_decode(
            q, kp, vp, tbl, lens), **_legacy_tol(jnp.float32))

    @pytest.mark.parametrize("quant", [False, True])
    def test_tp2_bitwise_vs_legacy_shard(self, devices8, quant):
        """tp2 placement: the generator's mesh path is BITWISE the replay
        of the walk (heads are independent, so the shards' walks are the
        whole walk's head slices) and allclose a shard_map of the FROZEN
        legacy kernel, for bf16 and int8 pools."""
        from jax.sharding import PartitionSpec as P

        from megatronapp_tpu.parallel.collectives import shard_map_compat

        rng = np.random.default_rng(3)
        q, kp, vp, tbl, lens, ks, vs = _mk_inputs(
            rng, 3, 0, 4, 2, 16, 8, 4, quant, jnp.float32)
        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=jax.devices()[:2])
        head = P(None, TP_AXIS, None)
        pages = P(None, None, TP_AXIS, None)
        scales = P(None, None, TP_AXIS)
        rep2, rep1 = P(None, None), P(None)
        if quant:
            legacy = shard_map_compat(
                lambda q_, k_, v_, t_, l_, ks_, vs_:
                legacy_paged_attention_decode(q_, k_, v_, t_, l_,
                                              k_scales=ks_, v_scales=vs_),
                ctx.mesh,
                in_specs=(head, pages, pages, rep2, rep1, scales, scales),
                out_specs=head)(q, kp, vp, tbl, lens, ks, vs)
        else:
            legacy = shard_map_compat(
                lambda q_, k_, v_, t_, l_:
                legacy_paged_attention_decode(q_, k_, v_, t_, l_),
                ctx.mesh, in_specs=(head, pages, pages, rep2, rep1),
                out_specs=head)(q, kp, vp, tbl, lens)
        gen = paged_attention(q, kp, vp, tbl, lens, k_scales=ks,
                              v_scales=vs, mesh=ctx.mesh)
        sim = _dense_sim(q, kp, vp, tbl, lens, ks=ks, vs=vs)
        assert bool(jnp.all(jnp.asarray(gen) == sim))
        _assert_close(gen, legacy, **_legacy_tol(jnp.float32))

    def test_non_ragged_multi_query_rejected(self):
        from megatronapp_tpu.ops.pallas.kernel_gen import PagedSpec
        with pytest.raises(ValueError, match="ragged"):
            PagedSpec(ragged=False, quant_dtype=None, s_q=3, block_size=8,
                      num_blocks_seq=4, hkv=2, group=2, scale=1.0)


# ---------------------------------------------------------------------------
# MLA latent kernel pins (ISSUE 17)
# ---------------------------------------------------------------------------


def _mk_latent_inputs(rng, b, s_q, nq, klat, dpe, dv, bs, mb, quant,
                      dtype):
    nb = b * mb + 1
    if s_q:
        q_lat = jnp.asarray(rng.normal(size=(b, s_q, nq, klat)), dtype)
        q_pe = jnp.asarray(rng.normal(size=(b, s_q, nq, dpe)), dtype)
    else:
        q_lat = jnp.asarray(rng.normal(size=(b, nq, klat)), dtype)
        q_pe = jnp.asarray(rng.normal(size=(b, nq, dpe)), dtype)
    lat = jnp.asarray(rng.normal(size=(nb, bs, klat)), dtype)
    pe = jnp.asarray(rng.normal(size=(nb, bs, dpe)), dtype)
    w_v = jnp.asarray(rng.normal(size=(klat, nq, dv)), dtype)
    tbl = jnp.asarray(
        rng.permutation(nb - 1)[: b * mb].reshape(b, mb) + 1, jnp.int32)
    lens = jnp.asarray(rng.integers(1, bs * mb, b), jnp.int32)
    ls = ps = None
    if quant:
        lat, ls = quantize_kv_rows(lat)
        pe, ps = quantize_kv_rows(pe)
    return q_lat, q_pe, lat, pe, w_v, tbl, lens, ls, ps


@functools.partial(jax.jit, static_argnames=("scale", "pages", "ragged"))
def _latent_sim_jit(q_lat, q_pe, lat_pages, pe_pages, tbl, kv_lens, w_v,
                    q_lens, lat_scales, pe_scales, *, scale, pages,
                    ragged):
    """jnp replay of the latent walk (emit_paged_kernel + _latent_tile):
    the same pages a step, the same tile order, the same op sequence a
    tile (scaled-q dots, mask, online-softmax rescale, p · latent into a
    [rows, klat] accumulator), rows head-major (row = h*s_q + s); ->
    the normalised latent sums in float32, which the caller expands as
    the entry point does (ISSUE 39). The replay must be
    jitted as ONE computation so XLA applies the same fusions (mul+add →
    FMA) it applies to the interpreted kernel body — op-by-op eager
    replay drifts by one ulp on multi-tile accumulators. Do not
    "simplify" the arithmetic here: its order is the pin."""
    if ragged:
        b, s_q, nq, klat = q_lat.shape
    else:
        (b, nq, klat), s_q = q_lat.shape, 1
    dpe = q_pe.shape[-1]
    bs = lat_pages.shape[1]
    rows, width = nq * s_q, pages * bs
    steps = -(-tbl.shape[1] // pages)

    def head_major(x, d):
        x = x.astype(jnp.float32)
        if s_q > 1:
            x = jnp.swapaxes(x, 0, 1)
        return x.reshape(rows, d) * scale

    def tile(pool, scales, blocks):
        x = pool[blocks].reshape(width, pool.shape[-1])
        if scales is None:
            return x
        return (x.astype(jnp.float32)
                * scales[blocks].reshape(width)[..., None])

    outs = []
    for bi in range(b):
        ql = head_major(q_lat[bi], klat)
        qp = head_major(q_pe[bi], dpe)
        kv_len = kv_lens[bi]
        state = (jnp.zeros((rows, klat), jnp.float32),
                 jnp.full((rows, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((rows, 1), jnp.float32))
        for i in range(steps):
            blocks = _step_blocks(tbl[bi], kv_len, i, pages, bs)
            lat = tile(lat_pages, lat_scales, blocks)
            pe = tile(pe_pages, pe_scales, blocks)
            nt = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(ql.astype(lat.dtype), lat, nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qp.astype(pe.dtype), pe, nt,
                                       preferred_element_type=jnp.float32))
            pos = i * width + jnp.arange(width, dtype=jnp.int32)[None, :]
            row_q = jnp.arange(rows, dtype=jnp.int32)[:, None] % s_q
            abs_q = (kv_len - (q_lens[bi] if ragged else 1)) + row_q
            valid = (pos < kv_len) & (pos <= abs_q)

            def values(p, lat=lat):
                return jnp.dot(p.astype(lat.dtype), lat,
                               preferred_element_type=jnp.float32)

            state = _fold_tile(s, valid, i * width < kv_len, state, values)
        a = state[0] / jnp.maximum(state[2], 1e-20)
        if s_q > 1:
            a = jnp.swapaxes(a.reshape(nq, s_q, klat), 0, 1)
        outs.append(a.reshape(q_lat.shape[1:]))
    return jnp.stack(outs)


def _latent_blockwise_sim(q_lat, q_pe, lat_pages, pe_pages, tbl, kv_lens,
                          w_v, q_lens=None, softmax_scale=None,
                          lat_scales=None, pe_scales=None):
    summed = _latent_sim_jit(
        q_lat, q_pe, lat_pages, pe_pages, tbl, kv_lens, w_v, q_lens,
        lat_scales, pe_scales, scale=float(softmax_scale),
        pages=_pages_for([lat_pages, pe_pages, lat_scales, pe_scales],
                         tbl.shape[1], kernel_gen.LATENT_KEY_TILE),
        ragged=q_lens is not None)
    return kernel_gen._expand_values(summed, w_v).astype(q_lat.dtype)


class TestLatentKernelPins:
    """ISSUE 17 tentpole pins: the MLA latent-space kernel is held two
    ways — BITWISE vs a test-local jnp replay of its exact block loop
    (the op order IS the contract), and allclose vs the dense
    gather + kv_up re-expansion oracle it replaced
    (paged_attention_latent_reference: plain softmax, different
    contraction order, so bitwise is not expected there)."""

    SCALE = 1.0 / ((16 + 8) ** 0.5)   # 1/sqrt(dqk + dpe) at test dims

    def _tol(self, dtype):
        return dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32 \
            else dict(atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("quant", [False, True])
    def test_decode_bitwise_vs_blockwise_sim(self, dtype, quant):
        rng = np.random.default_rng(17)
        q_lat, q_pe, lat, pe, w_v, tbl, lens, ls, ps = _mk_latent_inputs(
            rng, 3, 0, 4, 32, 8, 16, 8, 4, quant, dtype)
        out = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, softmax_scale=self.SCALE,
                                     lat_scales=ls, pe_scales=ps)
        sim = _latent_blockwise_sim(q_lat, q_pe, lat, pe, tbl, lens,
                                    w_v, softmax_scale=self.SCALE,
                                    lat_scales=ls, pe_scales=ps)
        assert bool(jnp.all(out == sim))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("quant", [False, True])
    def test_ragged_bitwise_vs_blockwise_sim(self, dtype, quant):
        rng = np.random.default_rng(18)
        s_q = 5
        q_lat, q_pe, lat, pe, w_v, tbl, lens, ls, ps = _mk_latent_inputs(
            rng, 3, s_q, 4, 32, 8, 16, 8, 4, quant, dtype)
        lens = jnp.maximum(lens, s_q)
        qlens = jnp.asarray([s_q, 2, 1], jnp.int32)
        out = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, q_lens=qlens,
                                     softmax_scale=self.SCALE,
                                     lat_scales=ls, pe_scales=ps)
        sim = _latent_blockwise_sim(q_lat, q_pe, lat, pe, tbl, lens,
                                    w_v, q_lens=qlens,
                                    softmax_scale=self.SCALE,
                                    lat_scales=ls, pe_scales=ps)
        assert bool(jnp.all(out == sim))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("quant", [False, True])
    def test_decode_matches_dense_reference(self, dtype, quant):
        rng = np.random.default_rng(19)
        q_lat, q_pe, lat, pe, w_v, tbl, lens, ls, ps = _mk_latent_inputs(
            rng, 3, 0, 4, 32, 8, 16, 8, 4, quant, dtype)
        out = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, softmax_scale=self.SCALE,
                                     lat_scales=ls, pe_scales=ps)
        ref = paged_attention_latent_reference(
            q_lat, q_pe, lat, pe, tbl, lens, w_v,
            softmax_scale=self.SCALE, lat_scales=ls, pe_scales=ps)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **self._tol(dtype))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("quant", [False, True])
    def test_ragged_matches_dense_reference(self, dtype, quant):
        rng = np.random.default_rng(20)
        s_q = 5
        q_lat, q_pe, lat, pe, w_v, tbl, lens, ls, ps = _mk_latent_inputs(
            rng, 3, s_q, 4, 32, 8, 16, 8, 4, quant, dtype)
        lens = jnp.maximum(lens, s_q)
        qlens = jnp.asarray([s_q, 3, 1], jnp.int32)
        out = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, q_lens=qlens,
                                     softmax_scale=self.SCALE,
                                     lat_scales=ls, pe_scales=ps)
        ref = paged_attention_latent_reference(
            q_lat, q_pe, lat, pe, tbl, lens, w_v, q_lens=qlens,
            softmax_scale=self.SCALE, lat_scales=ls, pe_scales=ps)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **self._tol(dtype))

    def test_qlen1_ragged_bitwise_vs_decode(self):
        """At q_len == 1 the ragged latent emission collapses bitwise
        to the decode emission (one template, two points — same pin the
        dense family carries)."""
        rng = np.random.default_rng(21)
        q_lat, q_pe, lat, pe, w_v, tbl, lens, _, _ = _mk_latent_inputs(
            rng, 3, 0, 4, 32, 8, 16, 8, 4, False, jnp.float32)
        dec = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, softmax_scale=self.SCALE)
        mq = paged_attention_latent(q_lat[:, None], q_pe[:, None], lat,
                                    pe, tbl, lens, w_v,
                                    q_lens=jnp.ones((3,), jnp.int32),
                                    softmax_scale=self.SCALE)
        assert bool(jnp.all(dec == mq[:, 0]))

    @pytest.mark.parametrize("quant", [False, True])
    def test_tp2_latent_columns_allclose(self, devices8, quant):
        """Carve-out (b): the latent-COLUMN tp placement (two-phase
        psum'd scores + host softmax) matches the single-device kernel.
        allclose, not bitwise: the tp algorithm reassociates the
        latent contraction across shards."""
        rng = np.random.default_rng(22)
        q_lat, q_pe, lat, pe, w_v, tbl, lens, ls, ps = _mk_latent_inputs(
            rng, 3, 0, 4, 32, 8, 16, 8, 4, quant, jnp.float32)
        ref = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, softmax_scale=self.SCALE,
                                     lat_scales=ls, pe_scales=ps)
        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=jax.devices()[:2])
        tp = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                    w_v, softmax_scale=self.SCALE,
                                    lat_scales=ls, pe_scales=ps,
                                    mesh=ctx.mesh)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(tp),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("quant", [False, True])
    def test_tp2_ragged_latent_columns_allclose(self, devices8, quant):
        rng = np.random.default_rng(23)
        s_q = 5
        q_lat, q_pe, lat, pe, w_v, tbl, lens, ls, ps = _mk_latent_inputs(
            rng, 3, s_q, 4, 32, 8, 16, 8, 4, quant, jnp.float32)
        lens = jnp.maximum(lens, s_q)
        qlens = jnp.asarray([s_q, 2, 1], jnp.int32)
        ref = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                     w_v, q_lens=qlens,
                                     softmax_scale=self.SCALE,
                                     lat_scales=ls, pe_scales=ps)
        ctx = build_mesh(ParallelConfig(tensor_parallel=2),
                         devices=jax.devices()[:2])
        tp = paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens,
                                    w_v, q_lens=qlens,
                                    softmax_scale=self.SCALE,
                                    lat_scales=ls, pe_scales=ps,
                                    mesh=ctx.mesh)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(tp),
                                   atol=2e-5, rtol=2e-5)

    def test_softmax_scale_required(self):
        """The MLA scale is 1/sqrt(dqk + dpe) — NOT derivable from the
        latent width, so both the kernel and the dense reference refuse
        to guess."""
        rng = np.random.default_rng(24)
        q_lat, q_pe, lat, pe, w_v, tbl, lens, _, _ = _mk_latent_inputs(
            rng, 1, 0, 2, 16, 8, 8, 8, 2, False, jnp.float32)
        with pytest.raises(ValueError, match="softmax_scale"):
            paged_attention_latent(q_lat, q_pe, lat, pe, tbl, lens, w_v)
        with pytest.raises(ValueError, match="softmax_scale"):
            paged_attention_latent_reference(q_lat, q_pe, lat, pe, tbl,
                                             lens, w_v)


# ---------------------------------------------------------------------------
# The walk itself (ISSUE 29): a slot's blocks, several pages a step
# ---------------------------------------------------------------------------

_BODIES = ["paged_decode", "paged_mq", "paged_decode_latent",
           "paged_mq_latent"]
# 8 pages a step, 2.5 tiles a row; a latent body 16 pages, 1.25 tiles
_W_BS, _W_MB, _W_SQ = 16, 20, 3
_W_SCALE = 1.0 / ((16 + 8) ** 0.5)


# pages' dtype, and the dtype their rows are quantised to (None: stored
# as they are)
_WALK_POOLS = {"fp32": (jnp.float32, None), "bf16": (jnp.bfloat16, None),
               "int8": (jnp.float32, jnp.int8),
               "fp8": (jnp.float32, jnp.float8_e4m3fn)}


def _walk_case(body, lens, nan_past=False, pool="fp32", seed=29,
               s_q=_W_SQ, q_lens=None, heads=4, widths=None, bs=_W_BS,
               mb=_W_MB):
    """(kernel output, oracle output or None) of `body` over slots of
    `lens` cached rows at the walk shapes, pages stored as `pool` (int8
    and fp8 pages come with their fp32 scale pools). A ragged body's slots
    bring `s_q` query rows, of which `q_lens` are real (by default as many
    as the slot's length allows); a latent body has `heads` query heads.
    widths: (query heads, key/value heads, head dim) of a dense body
    (default 4, 2, 16), (latent, roped-key columns) of a latent one
    (default 32, 8): pages of whole tiles (8 x 128 a row and more) are the
    ones whose copies the kernel starts itself. bs: rows a block; mb: blocks
    a table row. nan_past: every
    table entry past a slot's length names a page filled with NaN, and a
    scale page filled with NaN (an int8 page cannot hold one: there the
    scale page alone carries it), and so is every block of the pool that
    no slot's table names within its length; no oracle then: the oracles
    gather the whole table."""
    rng = np.random.default_rng(seed)
    b, ragged, latent = len(lens), "_mq" in body, "latent" in body
    s_q = s_q if ragged else 0
    dtype, qdtype = _WALK_POOLS[pool]
    kv_lens = jnp.asarray(lens, jnp.int32)
    if ragged:
        q_lens = (jnp.minimum(kv_lens, s_q) if q_lens is None
                  else jnp.asarray(q_lens, jnp.int32))
    if latent:
        klat, dpe = widths or (32, 8)
        ql, qp, lat, pe, w_v, tbl, _, _, _ = _mk_latent_inputs(
            rng, b, s_q, heads, klat, dpe, 16, bs, mb, False, dtype)
        pools = [lat, pe]
    else:
        hq, hkv, d = widths or (4, 2, 16)
        q, kp, vp, tbl, _, _, _ = _mk_inputs(
            rng, b, s_q, hq, hkv, d, bs, mb, False, dtype)
        pools = [kp, vp]
    scales = [None, None]
    if qdtype is not None:
        pools, scales = zip(*(quantize_kv_rows(p, dtype=qdtype)
                              for p in pools))
    if nan_past:
        # the pool's second half is NaN, and only entries past a slot's
        # length name it; of the first half, the blocks that a slot's
        # table names within its length are spared
        nb = pools[0].shape[0]
        held = (kv_lens[:, None] + bs - 1) // bs
        within = jnp.arange(mb)[None, :] < held
        read = jnp.zeros((nb,), bool).at[
            jnp.where(within, tbl, nb)].set(True, mode="drop")

        def dirty(p):
            fill = jnp.nan if jnp.issubdtype(p.dtype, jnp.floating) else 127
            spared = read.reshape((nb,) + (1,) * (p.ndim - 1))
            return jnp.concatenate([jnp.where(spared, p, fill),
                                    jnp.full_like(p, fill)])

        pools = [dirty(p) for p in pools]
        scales = [None if sc is None else dirty(sc) for sc in scales]
        tbl = jnp.where(within, tbl, tbl + nb)
    if latent:
        args = (ql, qp, *pools, tbl, kv_lens, w_v)
        kw = dict(q_lens=q_lens, softmax_scale=_W_SCALE,
                  lat_scales=scales[0], pe_scales=scales[1])
        out = paged_attention_latent(*args, **kw)
        ref = None if nan_past else paged_attention_latent_reference(
            *args, **kw)
    else:
        kw = dict(k_scales=scales[0], v_scales=scales[1])
        if ragged:
            out = paged_attention(q, *pools, tbl, kv_lens, q_lens=q_lens,
                                  **kw)
            ref = (None if nan_past else
                   paged_attention_multiquery_reference(
                       q, *pools, tbl, kv_lens, q_lens, **kw))
        else:
            out = paged_attention(q, *pools, tbl, kv_lens, **kw)
            ref = None if nan_past else paged_attention_reference(
                q, *pools, tbl, kv_lens, **kw)
    if ragged:
        # rows past a slot's q_len are padding: whatever they hold is
        # dropped by the caller
        keep = (jnp.arange(s_q)[None, :] < q_lens[:, None])[..., None,
                                                            None]
        out = jnp.where(keep, out, 0.0)
        ref = None if ref is None else jnp.where(keep, ref, 0.0)
    return out, ref


class TestWalk:
    """What ISSUE 29 changed: the kernels visit the blocks a slot holds,
    `pages_per_step` pages a step, and nothing past the slot's length.
    All four bodies, against the gather-everything jnp oracles."""

    TOL = dict(atol=2e-5, rtol=2e-5)

    def _tol(self, body, pool):
        """Quantised pages are dequantised by kernel and oracle alike, so
        they agree as fp32 pages do; bf16 pages to the last bits of a
        bf16 result, as TestGeneratorBitwise and TestLatentKernelPins
        hold them."""
        if pool != "bf16":
            return self.TOL
        return (dict(atol=3e-2, rtol=3e-2) if "latent" in body
                else _legacy_tol(jnp.bfloat16))

    @pytest.mark.parametrize("length", [
        1, _W_BS, _W_BS + 1, 8 * _W_BS, 8 * _W_BS + 1, 16 * _W_BS,
        16 * _W_BS + 1, _W_MB * _W_BS],
        ids=["one", "page", "page+1", "tile", "tile+1", "latent-tile",
             "latent-tile+1", "table"])
    @pytest.mark.parametrize("body", _BODIES)
    def test_one_slot_of_length(self, body, length):
        out, ref = _walk_case(body, [length])
        _assert_close(out, ref, **self.TOL)

    @pytest.mark.parametrize("pool", ["fp32", "bf16", "int8", "fp8"])
    @pytest.mark.parametrize("body", _BODIES)
    def test_shortest_beside_longest(self, body, pool):
        out, ref = _walk_case(body, [1, _W_MB * _W_BS, 8 * _W_BS + 1, 2],
                              pool=pool)
        _assert_close(out, ref, **self._tol(body, pool))

    @pytest.mark.parametrize("body,pages", [
        ("paged_mq", 8), ("paged_mq_latent", 8), ("paged_mq_latent", 16)])
    def test_ragged_tail_straddles_a_tile(self, body, pages):
        """The new rows sit at positions 127, 128, 129 (a latent body's
        tile is 16 pages: 255, 256, 257 too): the causal tail mask crosses
        from one step's tile into the next."""
        out, ref = _walk_case(body, [pages * _W_BS + 2, pages * _W_BS + 1])
        _assert_close(out, ref, **self.TOL)

    @pytest.mark.parametrize("body", _BODIES)
    def test_slot_with_no_row_writes_zeros(self, body):
        """kv_len 0 (a padding row of a ragged call): one step that
        computes nothing; the row is zero and finite, its neighbour
        right."""
        out, ref = _walk_case(body, [0, 40])
        assert bool(jnp.all(out[0] == 0.0))
        _assert_close(out[1], ref[1], **self.TOL)

    @pytest.mark.parametrize("pool", ["fp32", "bf16", "int8", "fp8"])
    @pytest.mark.parametrize("body", _BODIES)
    def test_pages_past_the_length_are_never_read(self, body, pool):
        """Table entries past a slot's length name NaN pages, and NaN
        scale pages where the pool has them: the output is the clean
        run's, bit for bit."""
        lens = [1, _W_BS + 1, 8 * _W_BS + 1, 0]
        dirty, _ = _walk_case(body, lens, nan_past=True, pool=pool)
        clean, _ = _walk_case(body, lens, pool=pool)
        assert bool(jnp.all(jnp.isfinite(dirty)))
        assert bool(jnp.all(dirty == clean))

    def test_pages_per_step_follows_the_shapes(self):
        bf16 = jnp.bfloat16

        def pools(*shapes, dtype=bf16):
            return [jax.ShapeDtypeStruct(sh, dtype) for sh in shapes]

        # the dense cell: 32 layers, 896 blocks of 16 rows of 32 heads of
        # 80 (a page is 16 x 32 x 128 lanes x 2 B = 128 KiB a pool)
        dense = pools((32, 896, 16, 32, 80), (32, 896, 16, 32, 80))
        page = _pages_vmem_bytes(dense, default_kv_tile(None))
        assert page == 2 * 16 * 32 * 128 * 2
        assert pages_per_step(16, page, 128) == 8
        # the MoE cell: latent rows of 512 and roped-key rows of 64
        mla = pools((9, 8192, 16, 512), (9, 8192, 16, 64))
        lat_page = _pages_vmem_bytes(mla, default_kv_tile(None))
        assert lat_page == 16 * (512 + 128) * 2
        assert pages_per_step(16, lat_page, 256) == 8
        # ... whose own key tile is 256 wide (ISSUE 39): 16 pages
        assert pages_per_step(
            16, lat_page, 256, key_tile=kernel_gen.LATENT_KEY_TILE) == 16
        # int8 K/V pages are (32, 128) tiles, their fp32 scale pages
        # (8, 128) ones
        int8 = (pools((2, 64, 16, 32, 80), (2, 64, 16, 32, 80),
                      dtype=jnp.int8)
                + pools((2, 64, 16, 32), (2, 64, 16, 32),
                        dtype=jnp.float32))
        assert _pages_vmem_bytes(int8, default_kv_tile("int8")) == (
            2 * 16 * 32 * 128 + 2 * 16 * 128 * 4)
        # fewer under a small budget, never none, never more than the
        # table holds, one where a page is a tile already
        assert pages_per_step(16, page, 128, budget=4 * 2 * page) == 4
        assert pages_per_step(16, page, 128, budget=page) == 1
        assert pages_per_step(16, page, 3) == 3
        assert pages_per_step(128, page, 128) == 1
        assert pages_per_step(256, page, 128) == 1


# ---------------------------------------------------------------------------
# Two or four key/value heads of 128: the heads lie in a page's rows
# (ISSUE 56)
# ---------------------------------------------------------------------------


def _stacked_pool(bs, hkv, d, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((2, 64, bs, hkv, d), jnp.dtype(dtype))


class TestHeadsInRows:
    """A pool of 2 or 4 key/value heads of 128 goes to the kernel as the
    view [L, NB, bs*Hkv, 128] (`_heads_fold`): a page with no head axis,
    whole tiles, copied by the kernel itself, 512 keys a step. Both
    kernels, against the gather-everything oracles: a slot that holds
    nothing, one step, a whole step and a partial one (32 + 1 pages), a
    full table."""

    MB = 40
    LENS = [0, 40, 32 * _W_BS + 1, MB * _W_BS]

    @pytest.mark.parametrize("hkv,d,dtype,pages", [
        # heads in the rows: pages a step of a table of 384 blocks (512
        # keys where 32 pages of K and V are at most 1 MiB, else 256)
        (2, 128, jnp.bfloat16, 32), (4, 128, jnp.bfloat16, 32),
        (2, 128, jnp.float32, 32), (4, 128, jnp.float32, 16),
        (2, 256, jnp.bfloat16, 32),
        # not: one head of bf16 lies padded to two rows a key on the chip; 6
        # to 8; 8 and 32 are whole tiles as they lie; D 80 and D 64 are no
        # whole lanes; a quantized pool's scale pages have a head axis
        (1, 128, jnp.bfloat16, 0), (6, 128, jnp.bfloat16, 0),
        (8, 128, jnp.bfloat16, 0), (32, 128, jnp.bfloat16, 0),
        (2, 80, jnp.bfloat16, 0), (4, 64, jnp.bfloat16, 0),
        (2, 128, jnp.int8, 0), (4, 128, jnp.float8_e4m3fn, 0)])
    def test_the_rule(self, hkv, d, dtype, pages):
        pool = _stacked_pool(16, hkv, d, dtype)
        folds = pages > 0
        assert kernel_gen._heads_fold(pool) is folds
        if kernel_gen.quant_dtype_of(dtype):
            return
        spec = {}

        def call_pools(k, v):
            pools, by_pools = kernel_gen._call_pools([k, v], [None, None],
                                                     384)
            spec.update(by_pools)
            return pools

        pools = jax.eval_shape(call_pools, pool, pool)
        assert spec["heads_in_rows"] is folds and spec["block_size"] == 16
        assert pools[0].shape == ((2, 64, 16 * hkv, d) if folds
                                  else pool.shape)
        whole_tiles = folds or (hkv % 8 == 0 and d % 128 == 0)
        assert spec["copied"] == (whole_tiles,) * 2
        # as they were: 8 heads of 128 fold 256 keys a step, 32 heads and
        # every walk of blocked operands 128
        assert spec["pages"] == (pages or (16 if hkv == 8 else 8))

    def test_query_tile_counts_a_steps_keys(self):
        """The reason cell's prefill call (1,024 rows, 32 heads over 2): a
        query head's scores are as wide as the step's 512 keys, not as its
        1,024 rows of the tile, so the call is thirteen tiles of 80 rows
        beside two buffers of 32 pages of 16 KiB."""
        tile = {}

        def call(q, k, v):
            pools, by_pools = kernel_gen._call_pools([k, v], [None, None],
                                                     384)
            tile["rows"] = kernel_gen._query_tile([q], 128, pools, by_pools)
            return pools

        pool = jax.ShapeDtypeStruct((2, 49152, 16, 2, 128), jnp.bfloat16)
        jax.eval_shape(call, jax.ShapeDtypeStruct((1, 1024, 32, 128),
                                                  jnp.bfloat16), pool, pool)
        assert tile["rows"] == 80

    def test_rows_of_a_block_must_be_whole_tiles(self):
        assert kernel_gen._heads_fold(_stacked_pool(8, 2, 128))
        assert not kernel_gen._heads_fold(_stacked_pool(4, 2, 128))
        # a caller's one layer [NB, bs, Hkv, D] is stacked first
        assert not kernel_gen._heads_fold(
            jax.ShapeDtypeStruct((64, 16, 2, 128), jnp.bfloat16))

    @pytest.mark.parametrize("pool", ["fp32", "bf16"])
    @pytest.mark.parametrize("hkv", [2, 4])
    @pytest.mark.parametrize("body", ["paged_decode", "paged_mq"])
    def test_walk_matches_the_oracle(self, body, hkv, pool):
        out, ref = _walk_case(body, self.LENS, pool=pool,
                              widths=(8, hkv, 128), mb=self.MB)
        tol = TestWalk.TOL if pool == "fp32" else _legacy_tol(jnp.bfloat16)
        assert bool(jnp.all(out[0] == 0.0))
        _assert_close(out[1:], ref[1:], **tol)

    @pytest.mark.parametrize("body", ["paged_decode", "paged_mq"])
    def test_pages_past_the_length_are_never_read(self, body):
        lens = [1, _W_BS + 1, 32 * _W_BS + 1, 0]
        case = dict(pool="bf16", widths=(8, 2, 128), mb=self.MB)
        dirty, _ = _walk_case(body, lens, nan_past=True, **case)
        clean, _ = _walk_case(body, lens, **case)
        assert bool(jnp.all(jnp.isfinite(dirty)))
        assert bool(jnp.all(dirty == clean))

    def test_window_walk(self):
        """A sliding-window layer's one-query walk over such pages: the
        band's first rows are masked inside a page's rows too."""
        rng = np.random.default_rng(56)
        window, lens = 40, [5, 43, 32 * _W_BS + 47, 32 * _W_BS]
        q, kp, vp, tbl, _, _, _ = _mk_inputs(
            rng, len(lens), 0, 8, 2, 128, _W_BS, self.MB, False,
            jnp.float32)
        kv_lens = jnp.asarray(lens, jnp.int32)
        out = paged_attention(q, kp, vp, tbl, kv_lens, window=window)
        ref = paged_attention_reference(q, kp, vp, tbl, kv_lens,
                                        window=window)
        _assert_close(out, ref, **TestWalk.TOL)

    def test_the_walks_metadata_says_who_copies(self):
        from megatronapp_tpu.utils.dispatch import page_copies
        rng = np.random.default_rng(57)
        q, kp, vp, tbl, lens, _, _ = _mk_inputs(
            rng, 2, 0, 8, 2, 128, _W_BS, self.MB, False, jnp.bfloat16)
        walk, = page_copies(jax.make_jaxpr(paged_attention)(
            q, kp, vp, tbl, lens).jaxpr).values()
        assert walk == {"page_copies_step": 64, "page_copies_kernel": 64,
                        "page_copy_bytes": [8192, 8192]}


# ---------------------------------------------------------------------------
# The engine on the paged kernels
# ---------------------------------------------------------------------------


def _engine_cfg(**over):
    kw = dict(num_layers=2, hidden_size=128, num_attention_heads=4,
              num_query_groups=2, vocab_size=128,
              max_position_embeddings=128,
              compute_dtype=jnp.float32, remat_policy="none")
    kw.update(over)
    return TransformerConfig(**kw)


def _engine_case(cfg, seed=5):
    params, _ = init_gpt_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9, 17)]
    return params, prompts


def _stream(cfg, params, prompts, max_new=8, **kw):
    eng = DynamicInferenceEngine(params, cfg, max_batch=3, max_seq_len=64,
                                 paged=True, block_size=8, **kw)
    ids = [eng.add_request(p, max_new, SamplingParams(greedy=True))
           for p in prompts]
    res = eng.run_to_completion()
    return [res[i].tolist() for i in ids], eng


from jitted import greedy_oracle as _greedy_oracle  # noqa: E402


class TestPagedEngine:
    def test_stats_snapshot_exposes_dispatch(self):
        cfg = _engine_cfg()
        params, prompts = _engine_case(cfg)
        _, eng = _stream(cfg, params, prompts[:1], max_new=2)
        snap = eng.stats_snapshot()
        assert snap["decode_traces"] >= 1          # jit-count counter
        assert "decode_dispatch" not in snap       # no tracing by default
        disp = eng.stats_snapshot(include_dispatch=True)["decode_dispatch"]
        # a layer's K append, V append and attention
        assert disp["kernels"] == 3 * cfg.num_layers
        assert "compiled" not in disp
        # what a step of the walk copies (ISSUE 46): 64 positions in blocks
        # of 8 are one step of 8 pages of K and of V, as blocked operands
        # (a page of these widths is no whole tiles)
        page = 8 * cfg.num_query_groups * cfg.head_dim * 4
        assert disp["page_copies_step"] == {"paged_decode": 16}
        assert disp["page_copy_bytes"] == {"paged_decode": [page, page]}
        assert disp["page_copies_kernel"] == {"paged_decode": 0}

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_streams_at_heads_of_80(self, kv_dtype):
        """The dense cell's head geometry (4 heads of 80 in 2 KV groups;
        80 is no multiple of a lane tile's 128 and twice decided what
        Mosaic would take): streams through the paged engine are the
        dense oracle's, on the int8 pool as tests/test_kv_quant.py holds
        int8 streams, and the pool's books balance. The default init, as
        there: a large one makes the streams depend on their context,
        and then a fresh engine now and then emits a wrong stream on the
        CPU, at the parent too (ROADMAP S3: arrays handed to an
        asynchronous step and then written)."""
        cfg = _engine_cfg(hidden_size=320)
        assert cfg.head_dim == 80
        params, prompts = _engine_case(cfg, seed=80)
        out, eng = _stream(cfg, params, prompts, kv_cache_dtype=kv_dtype)
        eng.pool.audit()
        assert eng.pool.pages[0].shape[-1] == 80
        for p, toks in zip(prompts, out):
            assert toks == _greedy_oracle(params, cfg, p, 8)


def _mla_cfg(**over):
    kw = dict(multi_latent_attention=True, kv_lora_rank=32,
              qk_head_dim=16, qk_pos_emb_head_dim=8, v_head_dim=16)
    kw.update(over)
    return _engine_cfg(**kw)



# ---------------------------------------------------------------------------
# PERF levers: flash backward head-fold + scan unroll
# ---------------------------------------------------------------------------


class TestHeadFold:
    @pytest.mark.parametrize("h,hkv,d", [(4, 4, 64), (4, 2, 64),
                                         (8, 2, 16), (6, 3, 64)])
    def test_grad_parity(self, h, hkv, d):
        from megatronapp_tpu.ops.pallas.flash_attention import (
            flash_attention, head_fold_eligible,
        )
        assert head_fold_eligible(h, hkv, d)
        rng = np.random.default_rng(0)
        sq = 96      # not a block multiple — exercises bounded masking
        q = jnp.asarray(rng.normal(size=(2, sq, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, sq, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, sq, hkv, d)), jnp.float32)

        def loss(fold):
            return lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=True, block_q=32, block_kv=32,
                head_fold=fold)))

        g0 = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
        g1 = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g0, g1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_ineligible_layouts_fall_back(self):
        from megatronapp_tpu.ops.pallas.flash_attention import (
            flash_attention, head_fold_eligible,
        )
        assert not head_fold_eligible(4, 4, 128)   # 2D > 128
        assert not head_fold_eligible(3, 3, 64)    # odd heads
        assert not head_fold_eligible(6, 2, 64)    # group 3 straddles kv
        assert not head_fold_eligible(4, 4, 64, segs="x")
        # Fallback is exact (same kernels run).
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 64, 4, 128)), jnp.float32)

        def loss(fold):
            return lambda x: jnp.sum(flash_attention(
                x, q, q, causal=True, block_q=32, block_kv=32,
                head_fold=fold))

        g0 = jax.grad(loss(False))(q)
        g1 = jax.grad(loss(True))(q)
        assert bool(jnp.all(g0 == g1))


class TestScanUnroll:
    def test_train_loss_parity_across_unrolls(self):
        """Lever 3: unrolling the layer scan must not move the loss
        (exact on CPU)."""
        from megatronapp_tpu.models.gpt import gpt_loss
        cfg = _engine_cfg(num_layers=4)
        params, _ = init_gpt_params(jax.random.PRNGKey(2), cfg)
        rng = np.random.default_rng(2)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                             jnp.int32)
        labels = jnp.roll(tokens, -1, axis=-1)
        mask = jnp.ones((2, 32), jnp.float32)
        losses = []
        for u in (1, 2, 4):
            c = dataclasses.replace(cfg, scan_unroll=u)
            loss, _ = gpt_loss(params, tokens, labels, mask, c)
            losses.append(float(loss))
        assert losses[0] == losses[1] == losses[2]


# ---------------------------------------------------------------------------
# Eligibility reasons name the specific predicate
# ---------------------------------------------------------------------------


class TestEligibilityReasons:
    def test_tp_paged_reasons(self):
        from megatronapp_tpu.ops.pallas.paged_attention import (
            tp_paged_eligible, tp_paged_ineligible_reason,
        )

        class Ctx:
            tp = 2

        cfg = _engine_cfg()
        assert tp_paged_ineligible_reason(cfg, None).startswith("no mesh")
        assert "num_attention_heads" in tp_paged_ineligible_reason(
            _engine_cfg(num_attention_heads=3, num_query_groups=3), Ctx())
        assert "num_query_groups" in tp_paged_ineligible_reason(
            _engine_cfg(num_attention_heads=4, num_query_groups=1), Ctx())
        assert tp_paged_ineligible_reason(cfg, Ctx()) is None
        assert tp_paged_eligible(cfg, Ctx())

    def test_tp_paged_mla_reasons(self):
        """ISSUE 17 carve-out (b): MLA shards the latent pool on latent
        COLUMNS — eligibility is kv_lora_rank % tp, never the head
        counts (MLA has no kv heads to split), and the reason names the
        failed predicate."""
        from megatronapp_tpu.ops.pallas.paged_attention import (
            tp_paged_eligible, tp_paged_ineligible_reason,
        )

        class Ctx:
            tp = 2

        assert tp_paged_ineligible_reason(_mla_cfg(), Ctx()) is None
        assert tp_paged_eligible(_mla_cfg(), Ctx())
        reason = tp_paged_ineligible_reason(_mla_cfg(kv_lora_rank=33),
                                            Ctx())
        assert "kv_lora_rank" in reason and "latent columns" in reason
        # Head counts never gate MLA: one query group would reject a
        # standard layout, but the latent pool has no head axis.
        assert tp_paged_ineligible_reason(
            _mla_cfg(num_query_groups=1), Ctx()) is None

    def test_tp_stage_reasons(self):
        from megatronapp_tpu.parallel.overlap import (
            tp_stage_eligible, tp_stage_ineligible_reason,
        )

        class Ctx:
            tp, pp, cp = 2, 2, 1
            abstract_collectives = False

        cfg = _engine_cfg(ffn_hidden_size=512)
        assert tp_stage_ineligible_reason(cfg, Ctx(), 64) is None
        assert tp_stage_eligible(cfg, Ctx(), 64)
        assert "seq_len" in tp_stage_ineligible_reason(cfg, Ctx(), 63)
        # cp > 1 composes since ISSUE 15 (dense non-MLA/non-MoE on the
        # p2p cp ring); seq must divide by cp*tp, and the excluded
        # layouts name their predicate.
        c2 = Ctx()
        c2.cp = 2
        assert tp_stage_ineligible_reason(cfg, c2, 64) is None
        assert "cp*tp" in tp_stage_ineligible_reason(cfg, c2, 34)
        a2a = dataclasses.replace(cfg, cp_comm_type="a2a")
        assert "p2p" in tp_stage_ineligible_reason(a2a, c2, 64)
        off = dataclasses.replace(cfg, tp_sharded_stage=False)
        assert "kill-switch" in tp_stage_ineligible_reason(off, Ctx(), 64)
        assert "ffn_hidden_size" in tp_stage_ineligible_reason(
            _engine_cfg(ffn_hidden_size=511), Ctx(), 64)
