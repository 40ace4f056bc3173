"""Selective-state-space mixers: the third kind of a layer's first half,
beside attention.py and mla.py. Two of them, and which one a model has is a
fact of it (SsmDims.heads): Mamba-1 (HF `jamba`) and Mamba-2 (HF
`granitemoehybrid`, `mamba2`).

MAMBA-1. Parity with /root/reference/megatron/core/ssm/mamba_mixer.py and HF
`modeling_jamba.JambaMambaMixer`: in_proj -> (u, z); causal depthwise
conv1d over the last k positions; silu; data-dependent dt, B, C (Jamba:
each through an RMS norm of its own); the recurrence

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * B_t) * u_t ;  y_t = C_t . h_t + D * u_t

over a diagonal A; gate by silu(z); out_proj. The reference leans on Triton
kernels for the scan; here a whole sequence (training, a prefill chunk) is
a `lax.associative_scan` a block of positions (the first-order recurrence
is associative, so XLA lowers it to a log-depth parallel scan), the blocks
in sequence, and a decode step is one
Pallas call that updates the state in place (ops/pallas/ssm_update.py).

The state is h [B, N, E] float32, E (the expanded width) minor: E is a
whole number of 128-lane vregs where N (16) would be padded eightfold. The
parameters keep the published layout (A_log [E, N]).

Param leaves: in_kernel [H, 2E], conv_kernel [k, E], conv_bias [E],
x_proj [E, R + 2N], dt_proj [R, E], dt_bias [E], A_log [E, N], D [E],
out_kernel [E, H]; with ssm_inner_norms dt_ln_scale [R], b_ln_scale [N],
c_ln_scale [N]. The convolution has a bias and the two projections none:
every model here says so (HF mamba_conv_bias true, mamba_proj_bias false),
so they are no switches.

MAMBA-2 (Dao & Gu 2024, "Transformers are SSMs"; HF
`modeling_granitemoehybrid.GraniteMoeHybridMambaLayer`,
`modeling_nemotron_h.NemotronHMamba2Mixer`): E = heads x P columns in heads
of P (SsmDims.head_dim; E is NOT expand x H: `nemotron_h` has 64 x 64 beside
H 2688); in_proj -> [z | xBC | dt] (E | E + 2GN | heads); the convolution
and silu run over x, B and C TOGETHER; dt and A are one scalar a head, B and
C [G, N] a token, shared by the heads / G heads of a group (head h reads
group h // (heads / G); Granite has one group, `nemotron_h` 8); the state a
head is a matrix S [P, N]:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t[g] ;  y_t = S_t C_t[g] + D x_t

then out = RMS_g(y * silu(z); g) W_out, the norm over each group's E / G
columns alone (one group: over all E). The state
is kept as Mamba-1's h [B, N, E] (h[n, head * P + p] = S[head][p, n]): with
dt, A and D broadcast over a head's P columns the decode step IS Mamba-1's,
and runs in the same kernel and the same pool; with G groups a column of E
reads its group's B and C. A whole sequence cannot be
Mamba-1's scan (its [B, block, N, E] float32 operands are 268 MB each at N
128, E 8192): it is the chunked (SSD) form, matrix products over chunks of
`chunk` positions carried chunk to chunk (`ssd_chunked`), the scores C B^T
one [Q, Q] a group.

Param leaves: in_kernel [H, 2E + 2GN + heads], conv_kernel [k, E + 2GN],
conv_bias [E + 2GN], dt_bias [heads], A_log [heads], D [heads], norm_scale
[E], out_kernel [E, H].
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from megatronapp_tpu.config.transformer_config import TransformerConfig
from megatronapp_tpu.ops.normalization import rms_norm


class SsmDims(NamedTuple):
    """The mixer's sizes and its one switch (TransformerConfig's ssm_*
    fields; models/mamba.py builds one from its MambaConfig)."""
    state_dim: int = 16
    conv_kernel: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    inner_norms: bool = False
    heads: int = 0              # > 0: Mamba-2, of `heads` heads
    chunk: int = 256            # positions a chunk of Mamba-2's prefill
    head_dim: int = 0           # Mamba-2: a head's columns
    groups: int = 1             # Mamba-2: groups of heads that share B, C

    def rank(self, hidden: int) -> int:
        return self.dt_rank or max(hidden // 16, 1)

    def inner(self, hidden: int) -> int:
        """E: Mamba-2's heads x head_dim, Mamba-1's expand x hidden."""
        return self.heads * self.head_dim or self.expand * hidden


def ssm_dims(cfg: TransformerConfig) -> SsmDims:
    return SsmDims(cfg.ssm_state_dim, cfg.ssm_conv_kernel, cfg.ssm_expand,
                   cfg.ssm_dt_rank, cfg.ssm_inner_norms, cfg.ssm_heads,
                   cfg.ssm_chunk_size, cfg.ssm_head_dim, cfg.ssm_groups)


def _init_dt_bias(key, shape, dtype):
    """softplus(dt_bias) log-uniform in [1e-3, 1e-1] (reference dt init)."""
    return jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1))))).astype(
            dtype)


def _init_ssm2_params(rng, cfg: TransformerConfig, dims: SsmDims, out_std):
    """Mamba-2's leaves. dt_bias as Mamba-1's (softplus of it log-uniform in
    [1e-3, 1e-1]), A = -(1..16) uniform a head (the published mixer's
    A_init_range), D = 1, the gated norm's scale 1."""
    h = cfg.hidden_size
    e = dims.inner(h)
    n = dims.state_dim
    c = e + 2 * dims.groups * n
    keys = jax.random.split(rng, 5)
    std = cfg.init_method_std
    p = {
        "in_kernel": jax.random.normal(
            keys[0], (h, e + c + dims.heads), cfg.params_dtype) * std,
        "conv_kernel": jax.random.normal(
            keys[1], (dims.conv_kernel, c), cfg.params_dtype) * std,
        "conv_bias": jnp.zeros((c,), cfg.params_dtype),
        "dt_bias": _init_dt_bias(keys[2], (dims.heads,), cfg.params_dtype),
        "A_log": jnp.log(jax.random.uniform(
            keys[3], (dims.heads,), jnp.float32, 1.0, 16.0)).astype(
                cfg.params_dtype),
        "D": jnp.ones((dims.heads,), cfg.params_dtype),
        "norm_scale": jnp.ones((e,), cfg.params_dtype),
        "out_kernel": jax.random.normal(
            keys[4], (e, h), cfg.params_dtype) * out_std,
    }
    ax = {
        "in_kernel": ("embed", "mlp"), "conv_kernel": (None, "mlp"),
        "conv_bias": ("mlp",), "dt_bias": (None,), "A_log": (None,),
        "D": (None,), "norm_scale": ("mlp",),
        "out_kernel": ("mlp", "embed"),
    }
    return p, ax


def init_ssm_params(rng, cfg: TransformerConfig, dims: SsmDims,
                    out_std=None):
    if out_std is None:
        out_std = cfg.init_method_std / jnp.sqrt(2.0 * cfg.num_layers)
    if dims.heads:
        return _init_ssm2_params(rng, cfg, dims, out_std)
    h = cfg.hidden_size
    e = dims.expand * h
    n = dims.state_dim
    dt_rank = dims.rank(h)
    keys = jax.random.split(rng, 6)
    std = cfg.init_method_std
    p = {
        "in_kernel": jax.random.normal(keys[0], (h, 2 * e),
                                       cfg.params_dtype) * std,
        "conv_kernel": jax.random.normal(
            keys[1], (dims.conv_kernel, e), cfg.params_dtype) * std,
        "conv_bias": jnp.zeros((e,), cfg.params_dtype),
        # x → (Δ_rank, B, C)
        "x_proj": jax.random.normal(keys[2], (e, dt_rank + 2 * n),
                                    cfg.params_dtype) * std,
        "dt_proj": jax.random.normal(keys[3], (dt_rank, e),
                                     cfg.params_dtype) * std,
        "dt_bias": _init_dt_bias(keys[4], (e,), cfg.params_dtype),
        # A negative-real diagonal, initialized -[1..N] per channel.
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32),
                                  (e, 1))).astype(cfg.params_dtype),
        "D": jnp.ones((e,), cfg.params_dtype),
        "out_kernel": jax.random.normal(
            keys[5], (e, h), cfg.params_dtype) * out_std,
    }
    ax = {
        "in_kernel": ("embed", "mlp"), "conv_kernel": (None, "mlp"),
        "conv_bias": ("mlp",),
        "x_proj": ("mlp", None), "dt_proj": (None, "mlp"),
        "dt_bias": ("mlp",), "A_log": ("mlp", None), "D": ("mlp",),
        "out_kernel": ("mlp", "embed"),
    }
    if dims.inner_norms:
        for name, width in (("dt_ln_scale", dt_rank), ("b_ln_scale", n),
                            ("c_ln_scale", n)):
            p[name] = jnp.ones((width,), cfg.params_dtype)
            ax[name] = (None,)
    return p, ax


# Positions one parallel scan holds. A longer sequence is scanned block
# after block, the state carried between them: lax.associative_scan makes
# log2(S) passes over [S, N, E] float32 operands, and what it costs a
# position depends on where XLA keeps them. Read off a v5e at [., 16, 5120]
# (21 MB an operand at 64 positions): one scan costs 0.05 ms a position of a
# 26-layer prefill call up to 64 positions and 0.3 ms from 96 on; compiled
# for that chip, a [1, 256] call in blocks of 64 (or 32) holds 20 MB of
# temporaries in HBM and one in blocks of 128 holds 120 MB; on the chip,
# blocks of 32 and of 64 serve alike (PERF.md section 6, PR 35).
SCAN_BLOCK = 64


def _scan_block(u, dt, a_t, b, c, d, h0=None):
    """selective_scan over one block: a parallel associative scan."""
    a = jnp.exp(dt[:, :, None, :] * a_t[None, None])          # [B,S,N,E]
    x = dt[:, :, None, :] * b[..., None] * u[:, :, None, :]   # [B,S,N,E]
    if h0 is not None:
        x = x.at[:, 0].add(a[:, 0] * h0)

    def combine(left, right):
        a_l, x_l = left
        a_r, x_r = right
        return a_l * a_r, a_r * x_l + x_r

    _, h = jax.lax.associative_scan(combine, (a, x), axis=1)
    y = jnp.einsum("bsne,bsn->bse", h, c) + u * d[None, None]
    return y, h[:, -1]


def selective_scan(u, dt, a_t, b, c, d, h0=None):
    """u, dt [B,S,E]; a_t [N,E] (A transposed); b, c [B,S,N]; d [E];
    h0 [B,N,E] or None (zeros) → (y [B,S,E], h_S [B,N,E]).

    A parallel associative scan over blocks of SCAN_BLOCK positions, the
    blocks one after the other (S up to a block: one scan, no loop). A
    position whose dt is 0 leaves the state as it was (exp(0) = 1 and a
    zero input): so does the padding of the last block."""
    bsz, s, e = u.shape
    if s <= SCAN_BLOCK:
        return _scan_block(u, dt, a_t, b, c, d, h0)
    blocks = -(-s // SCAN_BLOCK)

    def split(t):                           # [B,S,.] → [blocks,B,block,.]
        t = jnp.pad(t, ((0, 0), (0, blocks * SCAN_BLOCK - s), (0, 0)))
        return jnp.swapaxes(
            t.reshape(bsz, blocks, SCAN_BLOCK, t.shape[-1]), 0, 1)

    def step(h, xs):
        y, h = _scan_block(*xs[:2], a_t, *xs[2:], d, h)
        return h, y

    if h0 is None:
        h0 = jnp.zeros((bsz,) + a_t.shape,
                       jnp.result_type(u, dt, a_t, b))
    h, y = jax.lax.scan(step, h0, tuple(map(split, (u, dt, b, c))))
    return jnp.swapaxes(y, 0, 1).reshape(bsz, -1, e)[:, :s], h


def ssd_chunked(x, dt, a, b, c, d, chunk: int, h0=None):
    """Mamba-2's recurrence over a whole sequence as matrix products (the
    SSD form). x [B,S,E], E = heads x P; dt [B,S,heads] float32; a, d
    [heads] float32 (a < 0); b, c [B,S,N], or [B,S,G,N] where G groups of
    heads / G heads each read a B and C of their own (the scores are then
    one [Q, Q] a group, and a group's E / G columns of the state meet its
    own B and C); h0 [B,N,E] float32 or None (zeros) -> (y [B,S,E] float32,
    h_S [B,N,E] float32).

    Chunks of `chunk` positions, one after the other, the state carried
    between them (a `lax.scan`: one chunk's [B, heads, Q, Q] decays live at
    a time, 33 MB at 128 heads and Q 256). With cum_i the running sum of
    dt a inside a chunk:

      y_i  = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j   (in it)
           + exp(cum_i) C_i h                              (what came in)
      h'   = exp(cum_Q) h + sum_j exp(cum_Q - cum_j) dt_j B_j (x) x_j

    A position whose dt is 0 leaves the state as it was and adds nothing to
    a later y: so does the padding of the last chunk. The products take
    their operands in x's type and add up in float32; the state stays
    float32."""
    bsz, s, e = x.shape
    heads, n = dt.shape[-1], b.shape[-1]
    p = e // heads
    f32 = jnp.float32
    q = min(chunk, s)
    chunks = -(-s // q)

    def split(t):                           # [B,S,...] -> [chunks,B,q,...]
        t = jnp.pad(t, ((0, 0), (0, chunks * q - s))
                    + ((0, 0),) * (t.ndim - 2))
        return jnp.swapaxes(t.reshape((bsz, chunks, q) + t.shape[2:]), 0, 1)

    def wide(t):                            # [..., heads] -> [..., E]
        return jnp.repeat(t, p, axis=-1)

    lower = jnp.tril(jnp.ones((q, q), bool))
    # One group: the products as they were. G groups: the scores batched
    # over the group, and the two products with the state one a group over
    # the group's columns of E (contiguous, whole lane tiles), side by side:
    # batched over the group they would want the state [B, G, N, E / G], and
    # XLA relays the whole pool out around the step for it (seen compiling
    # the reason cell's prefill call for a described v5e: two copies of
    # [6, 192, 128, 4096] float32 a call).
    groups = b.shape[2] if b.ndim == 4 else 0
    if groups:
        width = e // groups

        def scores_of(c_c, b_c):                            # [B,heads,q,q]
            return jnp.repeat(jnp.einsum(
                "bign,bjgn->bgij", c_c, b_c, preferred_element_type=f32),
                heads // groups, axis=1)

        def read(c_c, h):                                   # C h: [B,q,E]
            return jnp.concatenate([jnp.einsum(
                "bin,bnw->biw", c_c[:, :, g].astype(f32),
                h[..., g * width:(g + 1) * width])
                for g in range(groups)], axis=-1)

        def write(b_c, xw):                                 # B (x) x: [B,N,E]
            return jnp.concatenate([jnp.einsum(
                "bjn,bjw->bnw", b_c[:, :, g],
                xw[..., g * width:(g + 1) * width],
                preferred_element_type=f32)
                for g in range(groups)], axis=-1)
    else:
        def scores_of(c_c, b_c):
            return jnp.einsum("bin,bjn->bij", c_c, b_c,
                              preferred_element_type=f32)[:, None]

        def read(c_c, h):
            return jnp.einsum("bin,bne->bie", c_c.astype(f32), h)

        def write(b_c, xw):
            return jnp.einsum("bjn,bje->bne", b_c, xw,
                              preferred_element_type=f32)

    def step(h, xs):
        x_c, dt_c, b_c, c_c = xs
        cum = jnp.cumsum(dt_c * a, axis=1)                  # [B,q,heads]
        cum_t = jnp.swapaxes(cum, 1, 2)                     # [B,heads,q]
        decay = jnp.exp(jnp.where(
            lower, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))
        m = (scores_of(c_c, b_c) * decay).astype(x.dtype)   # [B,heads,q,q]
        xdt = (x_c.reshape(bsz, q, heads, p).astype(f32)
               * dt_c[..., None]).astype(x.dtype)
        y = jnp.einsum("bhij,bjhp->bihp", m, xdt,
                       preferred_element_type=f32).reshape(bsz, q, e)
        y = y + wide(jnp.exp(cum)) * read(c_c, h)
        left = jnp.exp(cum[:, -1:] - cum) * dt_c            # [B,q,heads]
        h = wide(jnp.exp(cum[:, -1]))[:, None, :] * h + write(
            b_c, (x_c.astype(f32) * wide(left)).astype(x.dtype))
        return h, y

    if h0 is None:
        h0 = jnp.zeros((bsz, n, e), f32)
    h, y = jax.lax.scan(step, h0, tuple(map(split, (x, dt, b, c))))
    y = jnp.swapaxes(y, 0, 1).reshape(bsz, -1, e)[:, :s]
    return y + x.astype(f32) * wide(d), h


def _plain_update(h, dt, u, b, c, a_t, d):
    from megatronapp_tpu.ops.pallas.ssm_update import ssm_update_reference
    return ssm_update_reference(h, dt, u, b, c, a_t, d)


def _causal_conv(raw, tail, p, k: int):
    """The causal depthwise convolution of `raw` [B,S,C] over the k - 1
    inputs before it (`tail` [B,k-1,C]; None: a sequence's start, zeros),
    its bias (where the mixer has one) and silu, in float32 -> (the padded
    inputs [B,S+k-1,C], the result [B,S,C])."""
    s = raw.shape[1]
    if tail is None:
        padded = jnp.pad(raw, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        padded = jnp.concatenate([tail.astype(raw.dtype), raw], axis=1)
    # k shifted products summed in float32, elementwise: as a dot_general
    # (one contraction of length k a channel) XLA:TPU lays the operands
    # out batch-minor and relayouts the tails on the way in and out.
    f32 = jnp.float32
    taps = p["conv_kernel"].astype(f32)
    conv = sum(padded[:, i:i + s].astype(f32) * taps[i] for i in range(k))
    if "conv_bias" in p:
        conv = conv + p["conv_bias"].astype(f32)
    return padded, jax.nn.silu(conv)


def _last_inputs(padded, s: int, counts, k: int):
    """The k - 1 inputs behind the last REAL position of each row of
    `padded` [B,S+k-1,C] (counts [B]; None: every position is real)."""
    if counts is None:
        return padded[:, s:]
    return jnp.take_along_axis(
        padded, (counts[:, None] + jnp.arange(k - 1)[None, :])[..., None],
        axis=1)


def ssm_forward(p, x, cfg: TransformerConfig, dims: SsmDims, state=None,
                counts=None, update=_plain_update):
    """x [B,S,H] → (out [B,S,H], (conv_tail [B,k-1,E], h [B,N,E])).

    state: the (conv_tail, h) a sequence arrives with; None is a sequence's
    start (zeros). conv_tail holds the last k-1 inputs of the convolution.
    counts [B]: row b's first counts[b] positions are real and the rest
    padding, which neither advances h (its dt is 0) nor enters the new
    tail: that is the last k-1 REAL inputs, taken across the chunk's edge
    from the old tail where the count is under k-1.
    update(h, dt, u, b, c, a_t, d) → (y, h'): how one token (S == 1 on a
    given state) advances h; the paged engine passes its in-place kernel,
    whose h is the whole pool (state[1] goes to it as it came)."""
    if dims.heads:
        return _ssm2_forward(p, x, cfg, dims, state, counts, update)
    bsz, s, hidden = x.shape
    n = dims.state_dim
    dt_rank = dims.rank(hidden)
    k = dims.conv_kernel
    f32 = jnp.float32
    cd = cfg.compute_dtype
    u_raw, z = jnp.split(x.astype(cd) @ p["in_kernel"].astype(cd), 2,
                         axis=-1)

    # Causal depthwise conv along seq, over the tail and the new inputs.
    tail, h0 = state if state is not None else (None, None)
    u_pad, u = _causal_conv(u_raw, tail, p, k)
    u = u.astype(cd)

    proj = u @ p["x_proj"].astype(u.dtype)  # [B,S,dt_rank+2N]
    dt_r, b_, c_ = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    if "dt_ln_scale" in p:
        eps = cfg.layernorm_epsilon
        dt_r = rms_norm(dt_r, p["dt_ln_scale"], eps)
        b_ = rms_norm(b_, p["b_ln_scale"], eps)
        c_ = rms_norm(c_, p["c_ln_scale"], eps)
    dt = jax.nn.softplus(
        dt_r.astype(f32) @ p["dt_proj"].astype(f32)
        + p["dt_bias"].astype(f32))
    if counts is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] < counts[:, None, None],
                       dt, 0.0)
    a_t = -jnp.exp(p["A_log"].astype(f32)).T
    d = p["D"].astype(f32)
    if s == 1 and h0 is not None:
        y, h_new = update(h0, dt[:, 0], u[:, 0].astype(f32),
                          b_[:, 0].astype(f32), c_[:, 0].astype(f32), a_t, d)
        y = y[:, None]
    else:
        y, h_new = selective_scan(u.astype(f32), dt, a_t, b_.astype(f32),
                                  c_.astype(f32), d, h0)
    y = y.astype(cd) * jax.nn.silu(z)
    out = y @ p["out_kernel"].astype(cd)
    return out, (_last_inputs(u_pad, s, counts, k), h_new)


def _ssm2_forward(p, x, cfg: TransformerConfig, dims: SsmDims, state,
                  counts, update):
    """ssm_forward for a Mamba-2 mixer: the same arguments and results, the
    tail [B, k-1, E + 2N] (the convolution runs over x, B and C)."""
    bsz, s, hidden = x.shape
    e, n, k = dims.inner(hidden), dims.state_dim, dims.conv_kernel
    groups = dims.groups
    f32 = jnp.float32
    cd = cfg.compute_dtype
    z, raw, dt = jnp.split(x.astype(cd) @ p["in_kernel"].astype(cd),
                           [e, 2 * e + 2 * groups * n], axis=-1)
    tail, h0 = state if state is not None else (None, None)
    padded, xbc = _causal_conv(raw, tail, p, k)
    u, b_, c_ = jnp.split(xbc.astype(cd), [e, e + groups * n], axis=-1)
    if groups > 1:      # [B,S,G,N]: a group's heads read their own B and C
        b_, c_ = (t.reshape(bsz, s, groups, n) for t in (b_, c_))
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    if counts is not None:
        dt = jnp.where(jnp.arange(s)[None, :, None] < counts[:, None, None],
                       dt, 0.0)
    a = -jnp.exp(p["A_log"].astype(f32))
    d = p["D"].astype(f32)
    if s == 1 and h0 is not None:
        # One scalar a head, broadcast over the head's columns, makes the
        # step Mamba-1's: a_t one row [1, E] for all N.
        def wide(t):
            return jnp.repeat(t, e // dims.heads, axis=-1)

        y, h_new = update(h0, wide(dt[:, 0]), u[:, 0].astype(f32),
                          b_[:, 0].astype(f32), c_[:, 0].astype(f32),
                          wide(a)[None], wide(d))
        y = y[:, None]
    else:
        with jax.named_scope("ssd_chunk"):
            y, h_new = ssd_chunked(u, dt, a, b_, c_, d, dims.chunk, h0)
    with jax.named_scope("gated_norm"):
        y = y.astype(f32) * jax.nn.silu(z.astype(f32))
        if groups > 1:  # the norm over each group's columns alone
            y = rms_norm(y.reshape(bsz, s, groups, -1),
                         p["norm_scale"].reshape(groups, -1),
                         cfg.layernorm_epsilon).reshape(bsz, s, e).astype(cd)
        else:
            y = rms_norm(y, p["norm_scale"],
                         cfg.layernorm_epsilon).astype(cd)
    out = y @ p["out_kernel"].astype(cd)
    return out, (_last_inputs(padded, s, counts, k), h_new)


def ssm_paged_forward(p, x, cfg: TransformerConfig, state, rows=None,
                      starts=None, counts=None, active=None):
    """The mixer inside a paged serving step (inference/dynamic_engine.py).

    state = (ssm [L, slots, N, E] f32, conv [L, slots, (k-1) * C], index; C
    the convolution's channels, Mamba-1's E or Mamba-2's E + 2N):
    the engine's stacked state pools and this layer's plane of them (a
    slot's k-1 convolution inputs side by side in one row, so that the two
    minor dims tile whole and a slot's tail is one row to slice). x's
    row b belongs to slot rows[b] (None: row b is slot b, a decode round
    over every slot). Returns (out, (ssm, conv)): the pools that came in,
    plane `index` of the active rows' slots advanced, in place where the
    caller's copies are dead (the layer loop's carry).

    A decode round (counts None, one token a row) reads the convolution's
    tails and shifts them in XLA and advances h in the ssm_update kernel.
    A prefill chunk (counts given) scans each row from its slot's state
    (zeros where starts[b] == 0: the row begins a sequence, whatever the
    slot held) up to counts[b] and writes the state back."""
    from megatronapp_tpu.ops.pallas.ssm_update import ssm_update
    ssm, conv, index = state
    dims = ssm_dims(cfg)
    bsz = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    if active is None:
        active = jnp.ones((bsz,), bool)
    zero = jnp.int32(0)
    # the tail's columns: Mamba-1's E, Mamba-2's E + 2N
    taps = dims.conv_kernel - 1
    e = conv.shape[2] // taps
    if counts is None:
        if rows is not None or bsz != ssm.shape[1] or x.shape[1] != 1:
            raise ValueError("a decode round advances every slot by one "
                             "token: x is [slots, 1, H]")
        # Lane slices and a stack, not a reshape: [slots, taps * E] ->
        # [slots, taps, E] is no bitcast on a TPU, and XLA would rather
        # relayout the whole pool around the step than the plane.
        flat = jax.lax.dynamic_index_in_dim(conv, index, 0, keepdims=False)
        tail = jnp.stack([flat[:, i * e:(i + 1) * e] for i in range(taps)],
                         axis=1)

        def update(pool, dt, u, b, c, a_t, d):
            return ssm_update(pool, index, dt, u, b, c, a_t, d, active)

        # The state given is the whole pool: ssm_update reads and writes
        # this layer's plane of it and hands the pool back.
        out, (new_tail, ssm) = ssm_forward(p, x, cfg, dims,
                                           state=(tail, ssm), update=update)
        new_tail = jnp.where(active[:, None, None], new_tail.astype(
            conv.dtype), tail)
        conv = jax.lax.dynamic_update_slice(
            conv, jnp.concatenate([new_tail[:, i] for i in range(taps)],
                                  axis=-1)[None], (index, zero, zero))
        return out, (ssm, conv)

    if rows is None:
        rows = jnp.arange(bsz, dtype=jnp.int32)

    def h_of(b):
        return jax.lax.dynamic_slice(
            ssm, (index, rows[b], zero, zero), (1, 1) + ssm.shape[2:])[0]

    def tail_of(b):
        return jax.lax.dynamic_slice(
            conv, (index, rows[b], zero), (1, 1, taps * e)).reshape(
                1, taps, e)

    fresh = (starts == 0)[:, None, None]
    h0 = jnp.concatenate([h_of(b) for b in range(bsz)])
    tail = jnp.concatenate([tail_of(b) for b in range(bsz)])
    out, (new_tail, h_new) = ssm_forward(
        p, x, cfg, dims, counts=counts,
        state=(jnp.where(fresh, 0, tail), jnp.where(fresh, 0.0, h0)))
    keep = active[:, None, None]
    h_new = jnp.where(keep, h_new, h0)
    new_tail = jnp.where(keep, new_tail.astype(conv.dtype), tail)
    for b in range(bsz):
        ssm = jax.lax.dynamic_update_slice(
            ssm, h_new[b][None, None], (index, rows[b], zero, zero))
        conv = jax.lax.dynamic_update_slice(
            conv, new_tail[b].reshape(1, 1, taps * e),
            (index, rows[b], zero))
    return out, (ssm, conv)
