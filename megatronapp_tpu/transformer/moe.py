"""Mixture-of-Experts layer (top-k router, EP-sharded experts).

Parity with /root/reference/megatron/core/transformer/moe/ — TopKRouter
(router.py:102), token dispatchers (token_dispatcher.py:114,248,909), grouped
experts (experts.py:90 GroupedMLP), shared experts, aux-loss balancing
(moe_utils.py). The reference dispatches tokens with explicit
allgather/all-to-all collectives; TPU-first, we build GShard-style dispatch/
combine einsums against experts stacked on an 'experts'-sharded leading axis —
XLA lowers the token exchange to a ragged all-to-all over the 'ep' mesh axis.

The router scores with a softmax over all experts and takes the top k.
Whether the k chosen probabilities are then divided by their sum is the
model's choice, not this module's: ``cfg.moe_router_norm_topk_prob`` (HF
config key ``norm_topk_prob``: true for Mixtral and the reference TopKRouter's
default, false for DeepSeek-V2-Lite), and the routed experts' output is
multiplied by ``cfg.moe_routed_scaling_factor`` (HF ``routed_scaling_factor``).

Two dispatch modes, matching the reference's semantics:
- moe_capacity_factor=None (the reference DEFAULT): exact dropless —
  token copies are sorted by expert and run through grouped GEMMs (static
  shapes, no capacity buffer, no token dropping; the reference's
  allgather/a2a dispatchers with no capacity): ``lax.ragged_dot`` in
  training, on a mesh and over int8-resident experts; the Pallas kernel of
  ops/pallas/grouped_gemm.py where the paged serving loop hands a layer
  its place in the experts' stack (StackedLayer), so that the stack is
  read in place and every touched expert once (_grouped_gemm).
- moe_capacity_factor=F: GShard capacity dispatch (tokens beyond
  F*T*k/E per expert dropped, prob-weighted combine) — the reference's
  --moe-expert-capacity-factor path; the GroupedMLP becomes one batched
  einsum over the expert axis (MXU-friendly).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.ops.activations import apply_activation, is_gated
from megatronapp_tpu.ops.pallas.grouped_gemm import grouped_gemm

# The grouped products' outputs under jax.checkpoint (checkpoint_name).
EXPERT_GEMM_OUT = "expert_gemm_out"


def init_moe_params(rng, cfg: TransformerConfig, out_std: float):
    h = cfg.hidden_size
    f = cfg.moe_ffn_hidden_size
    # The router is as wide as the model publishes it (its zero-compute
    # experts behind the computing ones); the kernels are those of the
    # experts held here (cfg.moe_experts_held: all of them unless told).
    e = cfg.moe_experts_here[1]
    k_router, k1, k2, k_shared = jax.random.split(rng, 4)
    std = cfg.init_method_std
    fc1_out = 2 * f if is_gated(cfg.activation) else f
    p = {
        # Router in fp32 (reference router.py keeps router params fp32).
        "router_kernel": jax.random.normal(
            k_router, (h, cfg.moe_router_width), jnp.float32) * std,
        "fc1_kernel": jax.random.normal(k1, (e, h, fc1_out), cfg.params_dtype) * std,
        "fc2_kernel": jax.random.normal(k2, (e, f, h), cfg.params_dtype) * out_std,
    }
    ax = {
        "router_kernel": ("embed", None),
        "fc1_kernel": ("experts", "embed", "mlp"),
        "fc2_kernel": ("experts", "mlp", "embed"),
    }
    if cfg.moe_router_selection_bias:
        p["router_bias"] = jnp.zeros((cfg.moe_router_width,), jnp.float32)
        ax["router_bias"] = (None,)
    if cfg.moe_shared_expert_intermediate_size:
        fs = cfg.moe_shared_expert_intermediate_size
        shared_out = 2 * fs if is_gated(cfg.activation) else fs
        ks1, ks2 = jax.random.split(k_shared)
        p["shared_fc1"] = jax.random.normal(ks1, (h, shared_out), cfg.params_dtype) * std
        p["shared_fc2"] = jax.random.normal(ks2, (fs, h), cfg.params_dtype) * out_std
        ax["shared_fc1"] = ("embed", "mlp")
        ax["shared_fc2"] = ("mlp", "embed")
    return p, ax


def _router(p, x_flat: jnp.ndarray, cfg: TransformerConfig,
            stats_mean=None):
    """Top-k softmax router with load-balance + z losses.

    x_flat: [T, H]. Returns (topk_idx [T,K], topk_probs [T,K], aux_loss).
    Softmax over all experts, then top-k (reference TopKRouter,
    router.py:102). The k probabilities are divided by their sum only when
    ``cfg.moe_router_norm_topk_prob`` says so (HF ``norm_topk_prob``), and
    carry ``cfg.moe_routed_scaling_factor`` (HF ``routed_scaling_factor``).
    With ``cfg.moe_router_score`` "sigmoid" the scores are the logits'
    elementwise sigmoid, and their renormalisation divides by the sum
    + 1e-6; the config refuses the aux losses with them.
    The softmax runs over the router's whole width (cfg.moe_router_width:
    zero-compute experts have ids past the computing ones). A router that
    holds a selection bias ("router_bias") takes its top-k on p + b and
    keeps p, unbiased, as the weights.

    stats_mean: optional reducer applied to the per-expert token-mean
    statistics (frac, mean_prob, z² mean) BEFORE the nonlinear aux-loss
    combination. The manual-ep dispatch passes a pmean over the
    token-splitting mesh axes so the aux loss is computed from GLOBAL
    stats — bit-matching the single-shard router instead of averaging
    per-shard products (which differs whenever shards see different
    routing mixes).
    """
    e = cfg.moe_router_width
    logits = x_flat.astype(jnp.float32) @ p["router_kernel"]
    sigmoid = cfg.moe_router_score == "sigmoid"
    probs = (jax.nn.sigmoid(logits) if sigmoid
             else jax.nn.softmax(logits, axis=-1))
    if "router_bias" in p:
        _, topk_idx = jax.lax.top_k(probs + p["router_bias"],
                                    cfg.moe_router_topk)
        topk_probs = jnp.take_along_axis(probs, topk_idx, axis=-1)
    else:
        topk_probs, topk_idx = jax.lax.top_k(probs, cfg.moe_router_topk)
    topk_probs = _picked_given(probs, topk_idx,
                               jax.lax.stop_gradient(topk_probs))
    if cfg.moe_router_norm_topk_prob:
        total = jnp.sum(topk_probs, -1, keepdims=True)
        # + 1e-6: the published constant of the sigmoid routers (lfm2_moe)
        topk_probs = topk_probs / (total + 1e-6 if sigmoid
                                   else jnp.maximum(total, 1e-9))
    if cfg.moe_routed_scaling_factor != 1.0:
        topk_probs = topk_probs * cfg.moe_routed_scaling_factor

    if stats_mean is None:
        stats_mean = lambda s: s  # noqa: E731 — identity reducer
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe_aux_loss_coeff:
        # Switch/GShard load-balancing loss (moe_utils.py switch_load_balancing
        # _loss_func): sum(probs_pe * tokens_pe) * E * coeff / (T^2 * topk) —
        # the 1/topk keeps the loss scale invariant in k (reference
        # normalization; advisor finding r1).
        onehot = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)  # [T,K,E]
        frac = stats_mean(
            jnp.mean(jnp.sum(onehot, axis=1), axis=0) / cfg.moe_router_topk)
        mean_prob = stats_mean(jnp.mean(probs, axis=0))
        aux = aux + cfg.moe_aux_loss_coeff * e * jnp.sum(frac * mean_prob)
    if cfg.moe_z_loss_coeff:
        z = jax.nn.logsumexp(logits, axis=-1)
        aux = aux + cfg.moe_z_loss_coeff * stats_mean(
            jnp.mean(jnp.square(z)))
    return topk_idx, topk_probs, aux


@jax.custom_vjp
def _picked_given(probs, idx, picked):
    """`picked` [T, k], which is probs[t, idx[t, j]] as the top-k found it,
    with that gather's gradient by compare-and-sum: the transpose JAX gives
    ``lax.top_k`` and ``take_along_axis`` is a scatter-add of T*k scalars
    (0.49 ms a layer pass of the share-training cell where this takes 0.05:
    PERF.md, PR 60). A token's k picks are distinct, so every sum holds one
    term and the gradient is the scatter's bit for bit."""
    return picked


def _picked_given_fwd(probs, idx, picked):
    return picked, (idx, probs.shape[-1])


def _picked_given_bwd(res, g):
    idx, width = res
    hit = idx[..., None] == jnp.arange(width)
    return (jnp.sum(jnp.where(hit, g[..., None], 0), axis=-2), None,
            jnp.zeros_like(g))


_picked_given.defvjp(_picked_given_fwd, _picked_given_bwd)


def _apply_act(cfg: TransformerConfig, y: jnp.ndarray) -> jnp.ndarray:
    """Apply the configured activation, splitting gate‖value for gated
    kinds (the fc1 kernels emit 2F columns when gated)."""
    if is_gated(cfg.activation):
        gate, val = jnp.split(y, 2, axis=-1)
        return apply_activation(cfg.activation, val, gate)
    return apply_activation(cfg.activation, y)


class StackedLayer(NamedTuple):
    """Layer `layer` of `stack`: the other way of naming one layer's expert
    kernel. An expert kernel is either an [E, K, N] array (or its resident
    int8 pair) or this pair of the whole [L, E, K, N] stack and an int32
    index, which the paged serving loop builds
    (inference/dynamic_engine._scan_paged_layers) so that the dropless
    grouped GEMM reads the stack where it lies; every other consumer takes
    the slice (_expert_kernel)."""
    stack: jnp.ndarray
    layer: jnp.ndarray


def _expert_kernel(w, dt) -> jnp.ndarray:
    """One layer's [E, K, N] expert kernel at matmul entry, in `dt`: a
    StackedLayer gives its slice, a serving-resident int8 pair dequantizes
    (inference/quantization.py resolve_param — a no-op on plain arrays, so
    the int8 stack is what lives in HBM and the per-channel dequant fuses
    into the consuming GEMM, exactly like the dense fc1/fc2 path)."""
    from megatronapp_tpu.inference.quantization import resolve_param
    if isinstance(w, StackedLayer):
        w = jax.lax.dynamic_index_in_dim(w.stack, w.layer, keepdims=False)
    return resolve_param(w, dt)


def _expert_ffn(p, x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """Batched expert MLP: x [E, C, H] → [E, C, H] (GroupedMLP analogue)."""
    dt = cfg.compute_dtype
    y = jnp.einsum("ech,ehf->ecf", x.astype(dt),
                   _expert_kernel(p["fc1_kernel"], dt))
    return jnp.einsum("ecf,efh->ech", _apply_act(cfg, y),
                      _expert_kernel(p["fc2_kernel"], dt))


def _grouped_gemm(x, w, group_sizes, dt, given=None) -> jnp.ndarray:
    """The rows x [M, K], sorted by expert, against one layer's expert
    kernel w, group by group; group_sizes [E] int32. Rows behind the last
    group belong to none and their output rows are undefined.

    A StackedLayer held in the compute dtype (the paged serving loop on one
    device, plain arrays, no gradient) runs the Pallas grouped GEMM
    (ops/pallas/grouped_gemm.py): it reads the [L, E, K, N] stack where it
    lies, through the layer id, and streams every touched expert's matrix
    once, in tiles chosen from the call's shapes (choose_gemm_tiles); what
    it chose is printed once a shape (`grouped gemm: ...`). A grouped GEMM
    is a custom call either way, which cannot take a fused slice as its
    operand: handed a layer's slice of a stack it would first copy the
    layer out (1.1 GB a DeepSeek-V2-Lite layer, more time than the GEMM
    itself).

    Everything else (training, a mesh, resident int8 pairs, a stack that is
    not held in the compute dtype and would be converted whole) takes the
    layer's own [E, K, N] kernel through ``lax.ragged_dot``, which XLA
    differentiates and partitions; `given`, the product as a forward pass
    kept it, stands in for computing it again (_product_given)."""
    if isinstance(w, StackedLayer) and w.stack.dtype == dt:
        return grouped_gemm(x, w.stack, group_sizes, layer=w.layer)
    if given is not None:
        return _product_given(x, _expert_kernel(w, dt), group_sizes, given)
    # Named for the layer loop's recomputation policy: a grouped product is
    # a matrix product, and 'selective' keeps those (transformer/block.py).
    return checkpoint_name(
        _ragged_dot(x, _expert_kernel(w, dt), group_sizes), EXPERT_GEMM_OUT)


def _ragged_dot(x, w, group_sizes):
    """``lax.ragged_dot`` with the rows behind the last group (a share of the
    experts: `_dropless_held_experts`) set to 0 on both sides of it. Such
    rows' output is undefined, on a TPU whatever the buffer held, NaN among
    it, and so is their row of x's cotangent in the backward products. A
    reader that only masks what it reads is not safe under a gradient: the
    zero cotangent of a masked row still meets the row's value (0 x NaN in
    the router weights' and the gated activation's gradients), and an
    undefined cotangent row is a row of the buffer that the tokens' gradient
    is gathered from (_dispatch_rows selects by position, not by value). With
    both selects every value a training step touches is defined (its gradient
    was NaN on the chip without them, from the first step or some steps
    later: PERF.md, PR 48). The paged serving steps run the Pallas kernel,
    not this."""
    keep = (jnp.arange(x.shape[0]) < jnp.sum(group_sizes))[:, None]
    out = jax.lax.ragged_dot(jnp.where(keep, x, jnp.zeros_like(x)), w,
                             group_sizes)
    return jnp.where(keep, out, jnp.zeros_like(out))


@jax.custom_vjp
def _product_given(x, w, group_sizes, out):
    """`out`, which is _ragged_dot(x, w, group_sizes) as an earlier pass
    computed it, with that product's gradient: a backward pass that was
    handed the product (_laddered_rows) differentiates through it without
    multiplying again."""
    return out


def _product_given_fwd(x, w, group_sizes, out):
    return out, (x, w, group_sizes)


def _product_given_bwd(res, g):
    x, w, group_sizes = res
    dx, = jax.linear_transpose(
        lambda x: _ragged_dot(x, w, group_sizes), x)(g)
    dw, = jax.linear_transpose(
        lambda w: _ragged_dot(x, w, group_sizes), w)(g)
    return dx, dw, None, jnp.zeros_like(g)


_product_given.defvjp(_product_given_fwd, _product_given_bwd)


def _placed(at, values):
    """out[at[i]] = values[i] for a permutation `at` [N] of the N places:
    what a scatter would write, by a sort of the pairs (a TPU sorts 65,536
    pairs in 36 us, gathers as many scalars in 470 and scatters 20,480 of
    them in 174: PERF.md, PR 60)."""
    return jax.lax.sort((at, values), num_keys=1, is_stable=False)[1]


def _sorted_picks(slot, count: int):
    """The sort of the picks by `slot` ([T*k] int32 in [0, count]: a pick's
    expert among the `count` held here, or `count` for a pick of none of
    them): (order, inv, group_sizes). `order` [T*k] is the stable sort's
    permutation (the pick at each sorted position), `inv` [T*k] its inverse
    (each pick's sorted position), `group_sizes` [count] int32 the picks of
    each held expert, which lie first and group by group. Nothing here
    scatters: the sizes are a compare-and-sum, and the inverse is a second
    sort, of the positions by the picks found there."""
    order = jnp.argsort(slot)
    inv = _placed(order, jnp.arange(slot.shape[0], dtype=order.dtype))
    group_sizes = jnp.sum(slot[:, None] == jnp.arange(count), axis=0,
                          dtype=jnp.int32)
    return order, inv, group_sizes


def _sum_of_picks(buf, at, n, w=None):
    """out[t] = Σ_j (w[j, t] ·) buf[at[j, t]] over the picks j whose sorted
    position at[j, t] lies among the first `n`, [T, H] float32: ONE gather of
    the k*T picks' rows of buf [rows, H], pick-major, and the sum of its k
    [T, H] slabs one after another in pick order. An elementwise sum of
    whole slabs, not a reduction: over an axis of k a TPU pads 10 rows to a
    tile of 16 or copies the buffer to reshape it, and converts it whole
    before it adds (PERF.md, PR 60). A pick behind the first n adds 0.0 by
    a select, never by a product."""
    k, t = at.shape
    picked = jnp.take(buf, at.reshape(-1), axis=0, mode="clip")

    def term(j):
        rows = picked[j * t:(j + 1) * t].astype(jnp.float32)
        if w is not None:
            rows = rows * w[j][:, None]
        return jnp.where((at[j] < n)[:, None], rows, 0.0)
    return functools.reduce(jnp.add, map(term, range(k)))


@jax.custom_vjp
def _dispatch_rows(x, token_of, at, n):
    """x[token_of]: the tokens' rows x [T, H] in the sorted picks' order
    (token_of [rows]: the first `rows` sorted picks' tokens). Its transpose
    is a gather too, through `at` [k, T], the sorted position of token t's
    pick j: a token's cotangent is the sum of its k picks' rows, of those
    among the first `n` (the rows of the groups; behind them a row belongs
    to no expert and a compact buffer may not hold it at all)."""
    return jnp.take(x, token_of, axis=0, mode="clip")


def _dispatch_rows_fwd(x, token_of, at, n):
    return _dispatch_rows(x, token_of, at, n), (at, n)


def _dispatch_rows_bwd(res, g):
    at, n = res
    return _sum_of_picks(g, at, n).astype(g.dtype), None, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(y, w, order, at, n):
    """out[t] = Σ_j w[t, j] · y[at[j, t]] over the picks j of token t that
    lie among the first `n` sorted rows, [T, H] float32 (_sum_of_picks: a
    gather of the picks' rows of y [rows, H] through `at` [k, T], products
    and sum in float32, in pick order). A pick behind the groups (an absent
    or zero-compute expert's, or one behind a compact buffer) adds 0.0 by a
    select: those rows of y are undefined on the chip, NaN among them
    (_ragged_dot). Its transpose gathers as well, through `order` [T*k], the
    picks of the buffer's rows; what it moves of scalars, a weight or its
    cotangent a pick, a sort moves (_placed)."""
    return _sum_of_picks(y, at, n, w.T.astype(jnp.float32))


def _combine_rows_fwd(y, w, order, at, n):
    return _combine_rows(y, w, order, at, n), (y, w, order, at, n)


def _combine_rows_bwd(res, g):
    y, w, order, at, n = res
    rows, (t, k) = y.shape[0], w.shape
    live = (jnp.arange(rows) < n)[:, None]
    g_rows = jnp.take(g, order[:rows] // k, axis=0, mode="clip")
    w_rows = _placed(at.reshape(-1), w.T.reshape(-1).astype(jnp.float32))
    dy = jnp.where(live, g_rows * w_rows[:rows, None], 0.0).astype(y.dtype)
    dw_rows = jnp.sum(jnp.where(live, g_rows * y.astype(jnp.float32), 0.0),
                      axis=1)
    dw = _placed(order, jnp.pad(dw_rows, (0, t * k - rows)))
    return dy, dw.reshape(t, k).astype(w.dtype), None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _dropless_experts(p, x_flat, topk_idx, topk_probs,
                      cfg: TransformerConfig) -> jnp.ndarray:
    """Exact dropless dispatch: sort the T*k token copies by expert id and
    run grouped GEMMs (_grouped_gemm) over the contiguous per-expert
    row groups — static shapes, no capacity buffer, zero drops. This is
    the reference's default behavior (no --moe-expert-capacity-factor ⇒
    dispatchers never drop; experts.py GroupedMLP runs ragged groups).
    Rows move by gathers alone, through the sort's permutation and its
    inverse (_sorted_picks, _held_rows).

    A layer that holds a share of the experts (cfg.moe_experts_held) or
    routes to zero-compute ones (cfg.moe_zero_experts) takes
    _dropless_held_experts: the same sort and GEMMs over its own experts'
    rows alone."""
    if cfg.moe_picks_unheld:
        return _dropless_held_experts(p, x_flat, topk_idx, topk_probs, cfg)
    rows = topk_idx.size
    picks = _sorted_picks(topk_idx.reshape(rows), cfg.num_moe_experts)
    return _held_rows((p["fc1_kernel"], p["fc2_kernel"]), x_flat, topk_probs,
                      *picks, cfg, rows)[0]


def _held_slot(flat_expert, cfg: TransformerConfig):
    """A pick's place among the experts held here: its expert's index in
    [0, count), or count for a pick of an expert held elsewhere or of a
    zero-compute one."""
    first, count = cfg.moe_experts_here
    local = flat_expert - first
    return jnp.where((local >= 0) & (local < count), local, count), count


# A compact row buffer is another copy of the held experts' body to compile
# and a conditional on the device: one is offered only where it leaves at
# least this many rows of a call out (a paged serving step's 768 or 6,144
# rows keep the one full buffer, and so the program they had).
_RUNG_MIN_SKIPPED = 8192
# Rungs are whole row tiles of either grouped product (lax.ragged_dot's 64
# and 512 on a TPU, choose_gemm_tiles' at most 512).
_RUNG_TILE = 512
# The compact rungs, over the rows the picks land here with by chance. The
# first lies above the mode of the calls' shares, not on it: a rung on the
# mode moves calls between rungs with every change of the routing, and the
# step's time with them (PERF.md, PR 49, has the calls' distribution).
_RUNG_FACTORS = ((5, 4), (3, 2), (2, 1))


def _row_buffer_rungs(rows: int, count: int, width: int) -> Tuple[int, ...]:
    """The sizes the held experts' row buffer may take in a call of `rows`
    picks whose layer holds `count` of the router's `width` outputs,
    ascending; the last is `rows`, which holds whatever the router does. The
    compact ones are 5/4, 3/2 and 2 times rows x count / width, the picks
    that land here by chance, in whole tiles: a call takes the smallest
    that holds its held picks (_dropless_held_experts). A function of the
    three numbers alone."""
    tiles = {-(-rows * count * num // (width * den * _RUNG_TILE))
             for num, den in _RUNG_FACTORS}
    return tuple(r for r in sorted(t * _RUNG_TILE for t in tiles)
                 if rows - r >= _RUNG_MIN_SKIPPED) + (rows,)


def _rung_taken(n, rungs: Tuple[int, ...]):
    """Index of the smallest of `rungs` that holds `n` rows (int32 scalar on
    the device)."""
    return jnp.sum(n > jnp.asarray(rungs[:-1], jnp.int32)).astype(jnp.int32)


def row_buffer_rows(n, rows: int, cfg: TransformerConfig):
    """Rows of the buffer the dropless experts walk in a call of `rows`
    picks of which `n` landed on the experts held here (a layer that holds
    every expert has the one rung, `rows`)."""
    rungs = _row_buffer_rungs(rows, cfg.moe_experts_here[1],
                              cfg.moe_router_width)
    return jnp.asarray(rungs, jnp.int32)[_rung_taken(n, rungs)]


def _held_rows(kernels, x_flat, topk_probs, order, inv, group_sizes,
               cfg: TransformerConfig, rows: int, given=(None, None)):
    """Σ_{picks of a held expert e} w_e · FFN_e(x) over a buffer of the first
    `rows` sorted picks (_sorted_picks' `order`, its inverse and the groups'
    sizes): ([T, H] float32, the two grouped products). Every group's rows
    have to lie inside the buffer; the rows behind the groups belong to
    none, cost no GEMM step, and their (undefined) output rows are never
    read into the weighted sum (_combine_rows). `given`: the two products
    as a forward pass kept them."""
    dt = cfg.compute_dtype
    fc1, fc2 = kernels
    t, k = topk_probs.shape
    at = inv.reshape(t, k).T
    n = jnp.sum(group_sizes)
    x_sorted = _dispatch_rows(x_flat.astype(dt), order[:rows] // k, at, n)
    y1 = _grouped_gemm(x_sorted, fc1, group_sizes, dt, given[0])
    y2 = _grouped_gemm(_apply_act(cfg, y1), fc2, group_sizes, dt, given[1])
    return _combine_rows(y2, topk_probs, order, at, n), (y1, y2)


def _laddered_rows(cfg: TransformerConfig, rungs: Tuple[int, ...]):
    """_held_rows' sum over the smallest of `rungs` that holds the call's
    held picks, as a function of _held_rows' six operands: ``lax.switch``
    over one copy of the body a rung, of which a TPU runs the one taken.

    It carries its own backward pass, a second switch whose branch
    differentiates that rung's body (``jax.vjp`` inside the branch), because
    JAX's derivative of a conditional hands every branch's residuals out of
    it, zero-filled by the branches not taken: the cell's step then needs
    20.5 GB of the chip's 15.75 GiB (the compiler's refusal; PERF.md, PR 49).
    What passes from the forward switch to the backward one is the two
    grouped products alone, in buffers of the largest compact rung's rows
    (half of what the T*k buffer's were), named for the layer loop's
    recomputation policy outside the switch: 'selective' keeps them and the
    backward pass multiplies nothing twice; without a policy that keeps
    them, or in the last rung, whose products would not fit them, the
    branch computes its own."""
    kept_rows = rungs[-2]           # the largest compact rung

    def taken(group_sizes):
        return _rung_taken(jnp.sum(group_sizes), rungs)

    def forward_rung(rows):
        def run(*operands):
            out, products = _held_rows(*operands, cfg, rows)
            return out, tuple(
                jnp.pad(y, ((0, kept_rows - rows), (0, 0)))
                if rows <= kept_rows
                else jnp.zeros((kept_rows, y.shape[1]), y.dtype)
                for y in products)
        return run

    def walk_fwd(*operands):
        out, products = jax.lax.switch(
            taken(operands[-1]), [forward_rung(r) for r in rungs], *operands)
        return out, (operands, tuple(checkpoint_name(y, EXPERT_GEMM_OUT)
                                     for y in products))

    @jax.custom_vjp
    def walk(*operands):
        return walk_fwd(*operands)[0]

    def backward_rung(rows):
        def run(operands, products, g):
            kernels, x_flat, topk_probs, *sorted_picks = operands
            given = (tuple(y[:rows] for y in products)
                     if rows <= kept_rows else (None, None))
            _, vjp = jax.vjp(
                lambda *diff: _held_rows(*diff, *sorted_picks, cfg, rows,
                                         given)[0],
                kernels, x_flat, topk_probs)
            return vjp(g)
        return run

    def walk_bwd(res, g):
        operands, products = res
        grads = jax.lax.switch(
            taken(operands[-1]), [backward_rung(r) for r in rungs],
            operands, products, g)
        return (*grads, None, None, None)

    walk.defvjp(walk_fwd, walk_bwd)
    return walk


def _dropless_held_experts(p, x_flat, topk_idx, topk_probs,
                           cfg: TransformerConfig) -> jnp.ndarray:
    """_dropless_experts for a layer that is told which experts it holds
    and whose router may pick zero-compute experts:

        Σ_{picks of a held expert e} w_e · FFN_e(x)  +  (Σ_{picks of a
        zero-compute expert} w_e) · x

    The picks of the held experts sort to the front, group by group; the
    group sizes cover those rows alone, so the grouped GEMMs' tiles run
    over them and over nothing else. What is gathered, multiplied, weighed
    and gathered back is a row buffer of the first R sorted picks
    (_held_rows), R chosen in the call, on the device, as the smallest of a
    few static sizes (_row_buffer_rungs) that holds the n picks that landed
    here: one branch a size, of which a TPU runs the one taken. The last
    size is T*k (any token may pick k held experts), so nothing is ever
    dropped, and the sum is the T*k buffer's bit for bit: a token's picks
    are summed in pick order whatever the buffer, and the picks behind it
    add 0.0. What the absent experts would have added is left out; the
    identity term needs no weights and is computed here whole."""
    rows = topk_idx.size
    slot, count = _held_slot(topk_idx.reshape(rows), cfg)
    rungs = _row_buffer_rungs(rows, count, cfg.moe_router_width)
    operands = ((p["fc1_kernel"], p["fc2_kernel"]), x_flat, topk_probs,
                *_sorted_picks(slot, count))
    if len(rungs) == 1:
        out, _ = _held_rows(*operands, cfg, rows)
    else:
        out = _laddered_rows(cfg, rungs)(*operands)
    if cfg.moe_zero_experts:
        w_zero = jnp.sum(jnp.where(topk_idx >= cfg.num_moe_experts,
                                   topk_probs.astype(jnp.float32), 0.0),
                         axis=-1)
        out = out + w_zero[:, None] * x_flat.astype(jnp.float32)
    return out


def routing_counts(topk_idx, count_rows, num_experts: int) -> jnp.ndarray:
    """int32 [2] of one layer's routing: token-expert assignments of the
    rows that `count_rows` ([T] bool) marks as real tokens, and how many of
    the experts those rows touched."""
    hit = jax.nn.one_hot(topk_idx, num_experts, dtype=jnp.bool_)  # [T,K,E]
    hit = hit & count_rows[:, None, None]
    return jnp.stack([jnp.sum(hit), jnp.sum(jnp.any(hit, axis=(0, 1)))]
                     ).astype(jnp.int32)


# What routing_counts_held returns, in order (the engine's `moe` counters).
HELD_COUNTS = ("assignments", "expert_pairs_touched", "assignments_zero",
               "assignments_here", "assignments_absent", "here_max_rows")
# What a training step's MoE layer counts (moe_forward(train_counts=)): those,
# and the rows of the buffer its held experts walked (row_buffer_rows).
TRAIN_COUNTS = HELD_COUNTS + ("row_buffer_rows",)


def routing_counts_held(topk_idx, count_rows,
                        cfg: TransformerConfig) -> jnp.ndarray:
    """routing_counts for a layer that counts its held experts' load
    (cfg.moe_counts_load: it holds a share of the experts, routes to
    zero-compute ones, or balances by a selection bias; on a layer that
    holds every expert the zero and absent picks read 0),
    int32 [6] in HELD_COUNTS' order: the real
    tokens' assignments (tokens x top-k); how many of the experts HELD HERE
    they touched; their picks of zero-compute experts, of held experts and
    of experts held elsewhere, each counted from the indices (the three add
    up to the first); and the most rows one held expert got."""
    slot, count = _held_slot(topk_idx, cfg)
    hit = jax.nn.one_hot(slot, count, dtype=jnp.int32)            # [T,K,C]
    rows = jnp.sum(hit * count_rows[:, None, None], axis=(0, 1))  # [C]
    real = count_rows[:, None]
    zero = topk_idx >= cfg.num_moe_experts
    return jnp.stack([
        jnp.sum(real) * topk_idx.shape[1], jnp.sum(rows > 0),
        jnp.sum(real & zero), jnp.sum(rows),
        jnp.sum(real & ~zero & (slot == count)),
        jnp.max(rows)]).astype(jnp.int32)


def moe_forward(p, x: jnp.ndarray, cfg: TransformerConfig, layer_id=None,
                ctx=None, tp_sharded: bool = False, count_rows=None,
                train_counts: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B,S,H] → ([B,S,H], aux_loss scalar).

    count_rows: [B,S] bool, given by the serving steps (which have no use
    for the aux loss): the second result is then ``routing_counts`` of
    those rows (``routing_counts_held`` where cfg.moe_counts_load), for
    the engine's always-on `moe` counters.

    train_counts: a training step that counts its held experts' load too
    (cfg.moe_counts_load): the second result is then (aux_loss, int32 [7]
    in TRAIN_COUNTS' order: ``routing_counts_held`` of every row and the
    rows of the buffer the held experts walked), the router's loss over its
    whole width whatever is held.

    ctx with ep > 1 selects the explicit all-to-all dispatch
    (_a2a_expert_forward): expert weights stay home on their ep shard and
    token activations travel, the reference MoEAlltoAllTokenDispatcher
    (core/transformer/moe/token_dispatcher.py). Without it, XLA's SPMD
    partitioner faces token-sharded ⇄ expert-sharded layout transitions
    it can only solve by full rematerialization (replicate + repartition
    — the 'Involuntary full rematerialization' warnings).

    tp_sharded: the ambient manual region (pp pipeline stage body) runs
    with the residual stream tp-SHARDED along the sequence — x is this
    shard's [B, S/tp, H] chunk, each shard routes only its local tokens
    (FLOPs cut tp×), and tp joins the token-splitting axes of the router
    aux-stat pmean so the load-balance loss still matches the global
    router exactly."""
    b, s, h = x.shape
    t = b * s
    e = cfg.num_moe_experts
    k = cfg.moe_router_topk

    from megatronapp_tpu.parallel.collectives import current_manual_axes
    if cfg.moe_picks_unheld and (
            tp_sharded or (ctx is not None and getattr(ctx, "ep", 1) > 1)):
        raise NotImplementedError(
            "a layer that holds a share of the experts (moe_experts_held) "
            "or routes to zero-compute ones runs without an exchange on "
            "one device: no ep all-to-all between shares and no tp-sharded "
            "stage body yet (ROADMAP M3)")
    if "router_bias" in p and ctx is not None and getattr(ctx, "ep", 1) > 1:
        raise NotImplementedError(
            "the ep all-to-all dispatch routes by the router's kernel "
            "alone: a router with a selection bias runs without ep "
            "(ROADMAP M2)")
    if (ctx is not None and getattr(ctx, "ep", 1) > 1
            and not current_manual_axes()
            and e % ctx.ep == 0
            and b % (ctx.dp * ctx.ep) == 0
            and (ctx.cp == 1 or s % ctx.cp == 0)):
        # Explicit ep all-to-all dispatch (full-manual shard_map — the
        # partial-auto manual regions of this jax build abort XLA:CPU,
        # parallel/overlap.py docstring). Unavailable inside an ambient
        # manual region (the pp/cp pipeline body): nesting shard_maps is
        # unsupported in this JAX build, so moe+pp falls through to the
        # local dense dispatch below (each manual shard routes its own
        # tokens against the full expert stack). Ineligible layouts
        # (indivisible batch/experts) keep the compiler-sharded GSPMD
        # fallback.
        out, aux = _a2a_expert_forward(p, x, cfg, ctx)
        x_flat = x.reshape(t, h)
        return _with_shared(p, x_flat, out.reshape(t, h), cfg).reshape(
            b, s, h).astype(x.dtype), aux

    x_flat = x.reshape(t, h)
    # Inside an ambient manual region (the pp/cp pipeline body) each shard
    # routes only its local tokens; pmean the router stats over the
    # token-splitting manual axes BEFORE the nonlinear aux combination so
    # the load-balance loss matches the global router exactly — the same
    # global-stats discipline as the _a2a dispatch path above.
    stats_mean = None
    manual = current_manual_axes()
    if manual:
        from megatronapp_tpu.config.parallel_config import (
            CP_AXIS, DP_AXIS, EP_AXIS, TP_AXIS,
        )
        token_axes = tuple(a for a in (DP_AXIS, EP_AXIS, CP_AXIS)
                           if a in manual)
        if tp_sharded:
            # tp-sharded stage body: the sequence (hence tokens) splits
            # over tp too — without this entry each shard's aux loss
            # would combine LOCAL routing stats nonlinearly and drift
            # from the global router.
            token_axes = token_axes + (TP_AXIS,)
        if token_axes:
            stats_mean = lambda st: jax.lax.pmean(st, token_axes)  # noqa: E731
    topk_idx, topk_probs, aux = _router(p, x_flat, cfg,
                                        stats_mean=stats_mean)

    if count_rows is not None:
        if cfg.moe_counts_load:
            aux = routing_counts_held(topk_idx, count_rows.reshape(t), cfg)
        else:
            aux = routing_counts(topk_idx, count_rows.reshape(t), e)
    elif train_counts:
        counts = routing_counts_held(topk_idx, jnp.ones((t,), bool), cfg)
        walked = row_buffer_rows(
            counts[HELD_COUNTS.index("assignments_here")], t * k, cfg)
        aux = (aux, jnp.concatenate([counts, walked[None]]))

    if cfg.moe_capacity_factor is None:
        out = _dropless_experts(p, x_flat, topk_idx, topk_probs, cfg)
    else:
        out = _capacity_experts(p, x_flat, topk_idx, topk_probs, cfg)
    return _with_shared(p, x_flat, out, cfg).reshape(
        b, s, h).astype(x.dtype), aux


def _chunked_a2a_ffn(send, fc1, fc2, cfg: TransformerConfig, ep: int):
    """Decomposed, latency-hiding all-to-all → expert FFN → all-to-all.

    send [ep, e_loc, cap, h]: send[j] = this shard's capacity buffer bound
    for the experts on shard j. Instead of one bulk ``lax.all_to_all``
    followed by one big grouped GEMM (exposed exchange, then exposed
    compute), the exchange is decomposed into ep-1 ``ppermute`` hops —
    hop s delivers the chunk from shard me-s — and each hop is issued
    BEFORE the expert GEMMs on the previously-arrived chunk, so on
    hardware with an async collective engine the token exchange rides
    under expert compute (T3-style, arXiv:2401.16677). Results return the
    same way: the return hop for chunk s is issued while chunk s+1's FFN
    runs. Returns y [ep, e_loc, cap, h] with y[j] = the FFN outputs of
    this shard's tokens that were dispatched to shard j.
    """
    from megatronapp_tpu.config.parallel_config import EP_AXIS
    from megatronapp_tpu.parallel.collectives import ring_span

    me = jax.lax.axis_index(EP_AXIS)
    params = {"fc1_kernel": fc1, "fc2_kernel": fc2}

    def chunk_for_shift(s):
        # What I must hand to the shard s hops ahead: send[(me + s) % ep].
        return jax.lax.dynamic_index_in_dim(send, (me + s) % ep,
                                            keepdims=False)

    y = jnp.zeros_like(send)
    # Own chunk needs no comm; hop 1 is issued first so it flies under it.
    # Hop s delivers chunk(i, i+s) from every source i to its dest i+s —
    # each shard receives the chunk from shard me-s bound for its experts.
    nxt = None
    if ep > 1:
        ring_span("moe-a2a-permute", "B", send, EP_AXIS, step=0, op="fwd")
        nxt = jax.lax.ppermute(
            chunk_for_shift(1), EP_AXIS,
            [(i, (i + 1) % ep) for i in range(ep)])
        ring_span("moe-a2a-permute", "E", nxt, EP_AXIS, step=0, op="fwd")
    ring_span("moe-a2a-compute", "B", send, EP_AXIS, step=0, op="fwd")
    y = jax.lax.dynamic_update_index_in_dim(
        y, _expert_ffn(params, chunk_for_shift(0), cfg), me, 0)
    ring_span("moe-a2a-compute", "E", y, EP_AXIS, step=0, op="fwd")
    for s in range(1, ep):
        arrived = nxt
        nxt = None
        if s + 1 < ep:
            # Pre-issue the next inbound hop under this chunk's GEMMs.
            ring_span("moe-a2a-permute", "B", arrived, EP_AXIS, step=s,
                      op="fwd")
            nxt = jax.lax.ppermute(
                chunk_for_shift(s + 1), EP_AXIS,
                [(i, (i + s + 1) % ep) for i in range(ep)])
            ring_span("moe-a2a-permute", "E", nxt, EP_AXIS, step=s,
                      op="fwd")
        ring_span("moe-a2a-compute", "B", arrived, EP_AXIS, step=s,
                  op="fwd")
        ys = _expert_ffn(params, arrived, cfg)
        ring_span("moe-a2a-compute", "E", ys, EP_AXIS, step=s, op="fwd")
        # Return the results to the tokens' home shard (dest i-s); what
        # arrives here is MY chunk's result from shard me+s. The receive
        # side of this hop overlaps the next iteration's FFN.
        ring_span("moe-a2a-permute", "B", ys, EP_AXIS, step=s, op="ret")
        back = jax.lax.ppermute(
            ys, EP_AXIS, [(i, (i - s) % ep) for i in range(ep)])
        ring_span("moe-a2a-permute", "E", back, EP_AXIS, step=s, op="ret")
        y = jax.lax.dynamic_update_index_in_dim(y, back, (me + s) % ep, 0)
    return y


def _a2a_expert_forward(p, x: jnp.ndarray, cfg: TransformerConfig, ctx
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel dispatch as explicit ICI collectives.

    FULL-MANUAL shard_map over every mesh axis (the partial-auto regions
    of this jax build abort XLA:CPU — parallel/overlap.py design notes):
    token batch threads over (dp, ep), sequence over cp, expert weights
    over ep; tp rides replicated inside the region (the expert GEMMs
    compute redundantly per tp rank — the GSPMD mlp-dim sharding of the
    old partial-auto region needed exactly the mode this build aborts
    on). Each (dp, ep, cp) shard routes its own tokens, packs per-expert
    capacity buffers, exchanges them with the experts' home ep shards,
    runs the local expert FFNs, and sends results back — the reference's
    MoEAlltoAllTokenDispatcher. With ``cfg.moe_comm_overlap`` (default)
    the exchange is the chunked, latency-hiding ``_chunked_a2a_ffn``
    above; otherwise one bulk lax.all_to_all each way.

    Capacity: moe_capacity_factor when set (GShard drop semantics);
    otherwise T_local*k — every copy provably fits, keeping the default
    dropless-exact semantics at the cost of a fatter buffer (the
    reference pads to capacity on this path too,
    --moe-pad-expert-input-to-capacity).
    """
    from megatronapp_tpu.config.parallel_config import (
        CP_AXIS, DP_AXIS, EP_AXIS,
    )
    from megatronapp_tpu.parallel.collectives import shard_map_compat

    e = cfg.num_moe_experts
    k = cfg.moe_router_topk
    ep = ctx.ep
    cp = ctx.cp
    e_loc = e // ep
    dt = cfg.compute_dtype
    if cfg.moe_capacity_factor is not None and cfg.moe_capacity_factor <= 0:
        raise ValueError(
            f"moe_capacity_factor must be > 0 (got "
            f"{cfg.moe_capacity_factor}); omit it (None) for dropless "
            "dispatch")
    # Token-splitting axes of the manual region: aux stats pmean over them
    # so the load-balance loss is computed from GLOBAL per-expert stats
    # (exact parity with the single-shard router).
    token_axes = (DP_AXIS, EP_AXIS) + ((CP_AXIS,) if cp > 1 else ())

    def body(router_kernel, fc1, fc2, x_loc):
        bl, sl, h = x_loc.shape
        t_loc = bl * sl
        xf = x_loc.reshape(t_loc, h)
        topk_idx, topk_probs, aux = _router(
            {"router_kernel": router_kernel}, xf, cfg,
            stats_mean=lambda st: jax.lax.pmean(st, token_axes))

        if cfg.moe_capacity_factor is not None:
            cap = max(int(cfg.moe_capacity_factor * t_loc * k / e), 1)
        else:
            # top_k indices are distinct per token, so an expert receives
            # at most one copy per token: cap = t_loc is provably
            # dropless.
            cap = t_loc
        flat_e = topk_idx.reshape(t_loc * k)
        pos = _position_in_expert(flat_e, e)                  # [T*k]
        valid = pos < cap
        idx_e = jnp.where(valid, flat_e, 0)
        idx_p = jnp.where(valid, pos, 0)
        token_of = jnp.arange(t_loc * k) // k

        vals = (xf[token_of].astype(dt) *
                valid[:, None].astype(dt))                    # [T*k, H]
        send = jnp.zeros((e, cap, h), dt).at[idx_e, idx_p].add(vals)

        # tokens → expert home shards (experts live contiguously:
        # shard i holds [i*e_loc, (i+1)*e_loc), the fc1/fc2 'experts'
        # axis sharding).
        send = send.reshape(ep, e_loc, cap, h)
        if getattr(cfg, "moe_comm_overlap", True):
            y = _chunked_a2a_ffn(send, fc1, fc2, cfg, ep)
            y = y.reshape(e, cap, h)
        else:
            recv = jax.lax.all_to_all(send, EP_AXIS, split_axis=0,
                                      concat_axis=0)          # [ep_src,...]
            xin = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, h)
            y = _expert_ffn({"fc1_kernel": fc1, "fc2_kernel": fc2}, xin,
                            cfg)
            y = y.reshape(e_loc, ep, cap, h).transpose(1, 0, 2, 3)
            y = jax.lax.all_to_all(y, EP_AXIS, split_axis=0,
                                   concat_axis=0)             # back home
            y = y.reshape(e, cap, h)

        w = (topk_probs.reshape(t_loc * k) *
             valid.astype(topk_probs.dtype))
        contrib = y[idx_e, idx_p].astype(jnp.float32) * w[:, None]
        out = contrib.reshape(t_loc, k, h).sum(axis=1)        # [T_loc, H]
        return out.reshape(bl, sl, h), aux

    from jax.sharding import PartitionSpec as P
    batch_axes = (DP_AXIS, EP_AXIS)
    x_spec = P(batch_axes, CP_AXIS if cp > 1 else None, None)
    # manual-ok: _a2a_expert_forward is gated on `not current_manual_axes()`
    sm = shard_map_compat(
        body, ctx.shard_map_mesh,
        in_specs=(P(), P(EP_AXIS), P(EP_AXIS), x_spec),
        out_specs=(x_spec, P()))
    return sm(p["router_kernel"], p["fc1_kernel"], p["fc2_kernel"], x)


def _position_in_expert(flat_expert: jnp.ndarray, e: int) -> jnp.ndarray:
    """Arrival-order slot of each (token, choice) copy within its
    expert's capacity buffer (GShard position accounting, shared by the
    capacity and a2a dispatchers). flat_expert: [T*k] → pos [T*k]."""
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # [T*k, E]
    before = jnp.cumsum(onehot, axis=0) - onehot
    return jnp.sum(before * onehot, axis=1)


def _capacity_experts(p, x_flat, topk_idx, topk_probs,
                      cfg: TransformerConfig) -> jnp.ndarray:
    """GShard capacity dispatch (reference --moe-expert-capacity-factor
    path): tokens beyond F*T*k/E per expert are dropped."""
    t, _h = x_flat.shape
    e = cfg.num_moe_experts
    k = cfg.moe_router_topk
    if cfg.moe_capacity_factor <= 0:
        raise ValueError(
            f"moe_capacity_factor must be > 0 (got "
            f"{cfg.moe_capacity_factor}); omit it (None) for dropless "
            "dispatch")
    capacity = max(int(cfg.moe_capacity_factor * t * k / e), 1)

    onehot = jax.nn.one_hot(topk_idx, e, dtype=jnp.int32)  # [T,K,E]
    pos = _position_in_expert(topk_idx.reshape(t * k), e).reshape(t, k)
    keep = pos < capacity

    # Dispatch tensor [T, E, C] (GShard combine/dispatch einsum pattern).
    probs_masked = topk_probs * keep.astype(topk_probs.dtype)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                            dtype=jnp.float32)  # [T,K,C] (dropped → all-zero)
    combine = jnp.einsum("tke,tkc,tk->tec", onehot.astype(jnp.float32),
                         pos_oh, probs_masked)  # [T,E,C]
    dispatch = (combine > 0).astype(cfg.compute_dtype)

    expert_in = jnp.einsum("tec,th->ech", dispatch,
                           x_flat.astype(cfg.compute_dtype))
    expert_out = _expert_ffn(p, expert_in, cfg)
    return jnp.einsum("tec,ech->th", combine.astype(jnp.float32),
                      expert_out.astype(jnp.float32))


def _with_shared(p, x_flat, out, cfg: TransformerConfig):
    """Add the always-on shared expert(s) (reference shared_experts.py)."""
    if "shared_fc1" not in p:
        return out
    dt = cfg.compute_dtype
    y = _apply_act(cfg, x_flat.astype(dt) @ p["shared_fc1"].astype(dt))
    return out + (y @ p["shared_fc2"].astype(dt)).astype(jnp.float32)
