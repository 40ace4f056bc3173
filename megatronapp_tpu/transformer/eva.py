"""EVA attention (HF `evabyte`, attention_class "eva"; Zheng et al. 2023,
"Efficient Attention via Control Variates", in its deterministic form).

With W = cfg.eva_window_size, C = cfg.eva_chunk_size, s = head_dim ** -0.5
and two learned vectors a key/value head, phi and mu (`eva_phi`, `eva_mu`
[Hkv, D] beside the attention layer's kernels):

- chunk c holds positions C*c .. C*c + C-1; its summary is ONE key and ONE
  value: alpha_j = softmax_{j in c}(s * k_j . phi),
  k~_c = sum_j alpha_j k_j + mu, v~_c = sum_j alpha_j v_j (keys roped);
- query t sees exactly the rows of its own ALIGNED window,
  { j : W * (t // W) <= j <= t }, and one summary a chunk of every earlier
  window, { c : c < (W / C) * (t // W) }, under one softmax.

So a decode step at context length T reads
R(T) = (W / C) * (T // W) + T % W + 1 rows where full attention reads T + 1.

Two forms live here. `eva_attention` is the whole-sequence layer by XLA ops
(gpt_forward, the CPU tests; the flash kernels have no window term and
refuse such a model). The paged engine keeps, for every slot, ONE page table
of two regions, summary rows first and the open window's exact rows behind
them (inference/paged_cache.py): `table_rows` maps a position to its row of
that table, which is all the paged kernels need to be told (keys are roped
before they are cached, so a row's place carries no meaning, and the ragged
causal tail falls inside the open window, the table's tail);
`write_summaries` pools the chunks a step has just filled, a page each,
into rows the table does not show until their window closes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig


def init_eva_params(rng, cfg: TransformerConfig):
    """phi and mu a key/value head: a normal draw clamped to +-1, times
    head_dim ** -0.5."""
    shape = (cfg.num_query_groups, cfg.head_dim)
    k_phi, k_mu = jax.random.split(rng)
    scale = cfg.head_dim ** -0.5

    def draw(key):
        return (jnp.clip(jax.random.normal(key, shape, jnp.float32), -1, 1)
                * scale).astype(cfg.params_dtype)

    ax = ("kv_heads", "head_dim")
    return ({"eva_phi": draw(k_phi), "eva_mu": draw(k_mu)},
            {"eva_phi": ax, "eva_mu": ax})


def rows_walked(cfg: TransformerConfig, length):
    """R(T): the rows a decode step at context length T reads (the new
    row's among them). `length` a Python int or an integer array."""
    w = cfg.eva_window_size
    return (w // cfg.eva_chunk_size) * (length // w) + length % w + 1


def table_rows(cfg: TransformerConfig, positions):
    """The row of a slot's two-region table that holds position t: the
    summaries of the windows closed before t's, then t's place in its
    window."""
    return rows_walked(cfg, positions) - 1


def summarise(k, v, phi, mu, scale: float):
    """k, v [..., C, Hkv, D] -> (k~, v~) [..., Hkv, D] in float32: the
    chunk's rows pooled by a softmax of their keys against phi."""
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    logit = jnp.sum(k * phi.astype(jnp.float32), axis=-1,
                    keepdims=True) * scale                  # [..., C, H, 1]
    alpha = jax.nn.softmax(logit, axis=-3)
    return (jnp.sum(alpha * k, axis=-3) + mu.astype(jnp.float32),
            jnp.sum(alpha * v, axis=-3))


def eva_attention(q, k, v, phi, mu, cfg: TransformerConfig):
    """The whole-sequence layer by XLA ops. q [B, S, Hq, D], k / v
    [B, S, Hkv, D] (roped), from position 0 -> [B, S, Hq, D]. Dense within
    a window and against every summary, masked: memory grows with
    S * (W + S / C), so this is the training-shaped and test path, not the
    serving one."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    w, c = cfg.eva_window_size, cfg.eva_chunk_size
    scale = d ** -0.5
    sp = -(-s // w) * w
    pad = ((0, 0), (0, sp - s), (0, 0), (0, 0))
    q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    nw, nc, spw = sp // w, sp // c, w // c
    ks, vs = summarise(k.reshape(b, nc, c, hkv, d),
                       v.reshape(b, nc, c, hkv, d), phi, mu, scale)
    # A summary is cached (and so attended) in the keys' own type.
    ks, vs = ks.astype(k.dtype), vs.astype(v.dtype)
    qw = q.reshape(b, nw, w, hkv, hq // hkv, d)
    kw = k.reshape(b, nw, w, hkv, d)
    vw = v.reshape(b, nw, w, hkv, d)
    f32 = jnp.float32
    near = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qw, kw,
                      preferred_element_type=f32) * scale
    far = jnp.einsum("bnqhgd,bchd->bnhgqc", qw, ks,
                     preferred_element_type=f32) * scale
    idx = jnp.arange(w)
    near = jnp.where(idx[:, None] >= idx[None, :], near, -jnp.inf)
    closed = (jnp.arange(nc)[None, :] < spw * jnp.arange(nw)[:, None])
    far = jnp.where(closed[None, :, None, None, None, :], far, -jnp.inf)
    probs = jax.nn.softmax(jnp.concatenate([near, far], axis=-1), axis=-1)
    out = (jnp.einsum("bnhgqk,bnkhd->bnqhgd", probs[..., :w].astype(v.dtype),
                      vw, preferred_element_type=f32)
           + jnp.einsum("bnhgqc,bchd->bnqhgd", probs[..., w:].astype(v.dtype),
                        vs, preferred_element_type=f32))
    return out.reshape(b, sp, hq, d)[:, :s].astype(q.dtype)


def summary_blocks_per_window(cfg: TransformerConfig, block_size: int) -> int:
    """Blocks a closed window's summaries fill; the paged form keeps a chunk
    a page and whole blocks of summaries a window."""
    w, c = cfg.eva_window_size, cfg.eva_chunk_size
    if c != block_size or (w // c) % block_size:
        raise ValueError(
            f"the paged cache keeps an EVA chunk a page and a closed "
            f"window's chunk summaries in whole blocks: eva_chunk_size "
            f"({c}) must equal block_size ({block_size}) and "
            f"eva_window_size / eva_chunk_size ({w // c}) be a multiple "
            "of it")
    return (w // c) // block_size


def chunk_plan(cfg: TransformerConfig, page_table, starts, counts, active,
               n: int, num_blocks: int):
    """Which chunks a step fills, and where their summaries go.

    Row b appends counts[b] positions from starts[b] (true positions; a
    call never crosses a window's edge, and one of several tokens starts on
    a chunk's edge or inside the only chunk it touches). Its candidates are
    the `n` chunks from the one that holds starts[b]; one is filled if its
    last position is among the appended. Returns (src, dst, offsets), each
    [B * n] int32: the block of the slot's table that holds the chunk's
    exact rows, and the block and row of the table's last
    `summary_blocks_per_window` columns (the open window's summaries,
    which the kernels do not walk) for its summary. A candidate that is not
    filled, or whose row is inactive, names block `num_blocks`: dropped."""
    w, c = cfg.eva_window_size, cfg.eva_chunk_size
    spw = w // c
    spb = summary_blocks_per_window(cfg, c)
    g = starts[:, None] // c + jnp.arange(n, dtype=jnp.int32)[None, :]
    last = (g + 1) * c
    filled = (active[:, None] & (last > starts[:, None])
              & (last <= (starts + counts)[:, None]))
    j = g % spw                                  # chunk within its window
    cols = page_table.shape[1]
    src_col = jnp.minimum(spb * (g // spw) + j, cols - 1)
    dst_col = cols - spb + j // c
    src = jnp.take_along_axis(page_table, src_col, axis=1)
    dst = jnp.take_along_axis(page_table, dst_col, axis=1)
    drop = jnp.int32(num_blocks)
    return (jnp.where(filled, src, drop).reshape(-1).astype(jnp.int32),
            jnp.where(filled, dst, drop).reshape(-1).astype(jnp.int32),
            (j % c).reshape(-1).astype(jnp.int32))


def write_summaries(p, cfg: TransformerConfig, pools, page_table, starts,
                    counts, active, plane, width: int = 1):
    """After a step's exact rows are in the pools: pool every chunk the step
    has filled, a page each, into its summary row, in place. pools: the
    STACKED (K, V) [L, NB, bs, Hkv, D]; starts [B] and counts [B] as
    chunk_plan takes them (counts None: one token a row), `width` the
    tokens a row of the call can hold; returns the pools."""
    from megatronapp_tpu.ops.pallas import kernel_gen
    ck, cv = pools
    c = cfg.eva_chunk_size
    if counts is None:
        counts = jnp.ones(starts.shape, jnp.int32)
    if width > c and width % c:
        raise ValueError(
            f"a call of {width} tokens a row must hold whole EVA chunks "
            f"({c}) or lie inside one")
    src, dst, off = chunk_plan(cfg, page_table, starts, counts, active,
                               max(1, width // c), ck.shape[1])
    return kernel_gen.eva_summary(
        ck, cv, p["eva_phi"], p["eva_mu"], plane, src, dst, off,
        scale=cfg.head_dim ** -0.5)
