"""Layers of several kinds in one model: the gated short-convolution mixer,
its state as a two-row tail of each pool block, the state-space mixer, its
state an array a slot, the EVA mixer, its cache two kinds of row, the layer
whose mixer is TWO mixers side by side, and the layer loop over runs of
layers of one kind.

A layer is a mixer, or two mixers side by side on one norm, and at most one
feed-forward half. (A decoder that feeds a second decoder adds three kinds:
the Mamba-1 mixer, :func:`ssm1_branch`; the gated memory unit,
:func:`gmu_mixer`, which reads an ACTIVATION the last Mamba-1 layer made in
the same step and the layer loop carries up; and the cross layer, differential
attention over ANOTHER layer's rows, :func:`diff_branch`: their equations are
below the others'.) The mixer is rotary-GQA attention
(:func:`~.decode.gqa_mixer`, the dense block's own: over the whole context,
or, a ``window`` layer, over the last ``cfg.window`` positions, its rows in a
pool and under a table of their own, :class:`~.decode.PagedWindowCache`), a
gated short convolution (:func:`conv_mixer`), a state-space mixer
(:func:`ssm_mixer`), EVA attention (:func:`eva_mixer`) or, a ``parallel``
layer, the state-space mixer AND rotary GQA at once (below); the
feed-forward half the dense SwiGLU, the routed-expert layer
(:func:`~.experts.expert_layer`) or none. ``cfg.layer_types``
and ``cfg.ffn_kinds`` say which layer is what; :func:`hybrid_layers` cuts the
depth into runs of consecutive layers of one (mixer, feed-forward) kind,
``(ssm | attention | window | conv | eva | parallel) x (dense | expert |
none)``, and scans each run under one body, with no branch on a layer's kind
in the traced program. Every per-kind stack rides whole (the K/V pools, the
tail pool, the experts' one stack, each kind's weights on a leading axis of
ITS layers) and a layer's part is reached by offset: attention layer ``a`` at
``table + a·N``, window layer ``w`` at ``wtable + w·Nw``, conv layer ``c`` at
``table + c·N``, state-space layer ``m`` at ``m·S + slot``, expert layer
``e`` at ``e·held``; a parallel layer is attention layer ``a`` and
state-space layer ``m`` at once.

A serial mixer is a thin wrapper, ``x + branch(norm(x))``, around its
**branch** (:func:`ssm_branch`, :func:`~.decode.gqa_branch`: normed rows in,
what the mixer adds and its cache arrays out). The two-branch layer calls the
two branches itself, for the ONE normed residual ``h = norm(x)`` (``mup``:
``cfg.mup``, the family's fixed multipliers, :class:`~.transformer.Multipliers`;
``mu``: ``ssm_multipliers`` a segment of ``[z | x | B | C | dt]``)::

    x <- x + s_out · SSM(s_in · h) + a_out · Attn(a_in · h)
    SSM(u):   [z | xBC | dt] = (u · W_in) ⊙ mu, then as the state-space mixer
    Attn(u):  q, k, v = u·W_q, kappa · (u·W_k), u·W_v, then as rotary GQA
              (the cached row holds the scaled key)
    MLP(h2) = g2 · (silu(g1 · (h2·W_1)) ⊙ (h2·W_3)) · W_2
    x_0 = e · E[token],   logits = lam · (norm(x) · W_head)

A multiplier of 1.0 emits no multiply, so a model without them compiles to
what it compiled to before they existed; they are never folded into a stored
weight.

The conv mixer, for the normed residual ``h``::

    [b | c | u] = h · W_in          (D -> 3D, split in that order)
    z = b ⊙ u
    s_t = Σ_k w_k ⊙ z_{t-2+k}        (depthwise, causal, 3 taps; z = 0 before
                                     the sequence's start)
    y = (c ⊙ s) · W_out

Its state at position ``t`` is ``z_{t-1}``, ``z_{t-2}``
(:class:`~.decode.PagedHybridCache` says where they live and why).

The state-space (Mamba-2) mixer, for the normed residual ``h``, head ``i`` of
group ``i // (heads / groups)``::

    [z | xBC | dt] = h · W_in       (D -> inner + conv_dim + heads)
    xBC_t <- silu(Σ_k w_k ⊙ xBC_{t-taps+1+k} + b)   (depthwise, causal; zero
                                     before the sequence's start)
    Δ_t = softplus(dt_t + dt_bias_i),  a_t = exp(Δ_t · A_i),  A_i = -exp(A_log_i)
    S_t = a_t · S_{t-1} + Δ_t · x_t ⊗ B_t           (d_head x d_state, float32)
    y_t = S_t · C_t + D_i · x_t
    y <- RMSNorm_groups(y ⊙ silu(z)) · W_out

Its state at position ``t`` is ``S_{t-1}`` and the last ``taps - 1``
pre-activation ``xBC`` rows (:class:`~.decode.PagedStateCache`).

The EVA mixer (``W`` = ``cfg.window``, ``C`` = ``cfg.chunk``, ``s = d^-1/2``,
``phi`` and ``mu`` two learned vectors a head), for rotated ``q, k, v`` and a
row at ``t``, ``w0 = (t // W) * W``::

    alpha_m = softmax_{m in chunk c}(k_m . phi)      (no s; after the rotary)
    k~_c = sum_m alpha_m k_m + mu,   v~_c = sum_m alpha_m v_m
    o_t = [sum_{j=w0..t} e^{s q_t.k_j} v_j + sum_{c < w0/C} e^{s q_t.k~_c} v~_c]
          / [the same sums without v]

one softmax over the exact rows of the row's own window and one summary row
for every chunk of every window closed before it, computed as two partials
(each pool's paged call) joined by ``ops/reference.py`` ``merge_partials``.

The decoder that feeds a second decoder (``cfg.ssm1`` set; ``LN``: LayerNorm
with bias, ``cfg.norm`` ``"layer"``), for the normed residual ``h``::

    Mamba-1:  [x | z] = h · W_in;  x <- silu(conv(x) + b);  [δ | B | C] = x · W_x
              Δ_t = softplus(δ · W_Δ + b_Δ),  A = -exp(A_log)     (d_state, inner)
              S_t[n,c] = exp(Δ_t[c] A[n,c]) S_{t-1}[n,c] + B_t[n] Δ_t[c] x_t[c]
              y_t[c] = Σ_n C_t[n] S_t[n,c] + D[c] x_t[c];  adds (y ⊙ silu(z)) · W_out
    memory:   m = y of the LAST Mamba-1 layer (with the skip, before the gate)
    GMU:      adds (silu(h · W_in) ⊙ m) · W_out        (m the SAME row's)
    diff:     a_{p,σ} = softmax(s · q_{2p+σ} · k_{2j+σ} + mask) · [v_2j | v_2j+1]
              λ = exp(λ_q1·λ_k1) - exp(λ_q2·λ_k2) + λ₀(l),  λ₀(l) = 0.8 - 0.6 e^(-0.3 l)
              o_p = RMSNorm(a_{p,0} - λ a_{p,1}) (1 - λ₀(l));  adds concat(o) · W_o + b
    cross:    diff with queries alone of its own, over the shared layer's k, v

Its state at position ``t`` is ``S_{t-1}`` and the last ``taps - 1``
pre-activation ``x`` rows (:class:`~.decode.PagedStateWindowCache`); in a
packed step the rows no slot samples from leave the stack after the shared
layer (``cfg.row_cut``; :func:`hybrid_layers`' ``cut``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax


from tree_attention_tpu.models.decode import (
    PagedHybridCache,
    PagedStateCache,
    PagedStateWindowCache,
    PagedWindowCache,
    _Attend,
    _RowGroup,
    _join_rows,
    _pack_heads,
    _pool_write,
    chunks_closed,
    decode_attention,
    gqa_branch,
    gqa_mixer,
    pool_write_path,
    window_rules,
)
from tree_attention_tpu.models.transformer import (
    GQA_SERVED,
    Params,
    StateSpace,
    TransformerConfig,
    _heads,
    _mlp_block,
    _unheads,
    gqa_qkv,
    norm_rows,
    rms_norm,
    times,
    times_out_major,
)
from tree_attention_tpu.ops.reference import merge_partials
from tree_attention_tpu.obs import scopes


def _tail_rows(flat: jax.Array, g: _RowGroup, c, back: int, n_blocks: int,
               block: int) -> jax.Array:
    """``z`` at position ``start - back`` of every member of ``g``, read
    from conv layer ``c`` of the flat tail pool ``(layers·N, 2·D)`` through
    the member's table (the block's row, then the position's half of it);
    zero before position 0."""
    D = flat.shape[1] // 2
    p = g.start - back
    lb = jnp.clip(p // block, 0, g.table.shape[1] - 1)
    pb = jnp.take_along_axis(g.table, lb[:, None], axis=1)[:, 0]
    rows = flat[c * n_blocks + jnp.clip(pb, 0, n_blocks - 1)]
    half = jnp.where((p % 2 == 1)[:, None], rows[:, D:], rows[:, :D])
    return jnp.where((p >= 0)[:, None], half, 0)


def _tail_write(flat: jax.Array, z: jax.Array, g: _RowGroup, c,
                n_blocks: int, block: int) -> Tuple[jax.Array, jax.Array]:
    """Leave in every block the members of ``g`` wrote into the ``z``
    ``(batch, tq, D)`` of the block's two highest valid positions, position
    ``q`` in half ``q % 2`` of the block's row: the only rows a later step
    reads. It moves whole rows, as :func:`~.decode._paged_pool_write` moves
    whole blocks: a touched block's row is read, overlaid with the halves
    the step has a position for (with one row in the block the other half
    stays what it was, the position before), and scattered back by the
    major dimension alone. A member's blocks are its own, so no two
    entries name one row; a block no valid row falls in is sent past the
    pool and dropped. Returns the pool and how many rows were written."""
    batch, tq, D = z.shape
    NB = g.table.shape[1]
    nblk = (tq + block - 2) // block + 1   # blocks tq consecutive rows touch
    lb = (g.start // block)[:, None] + jnp.arange(nblk, dtype=jnp.int32)
    end = g.start + g.n_valid
    first = jnp.maximum(lb * block, g.start[:, None])
    hi = jnp.minimum((lb + 1) * block, end[:, None]) - 1
    pb = jnp.take_along_axis(g.table, jnp.clip(lb, 0, NB - 1), axis=1)
    live = (hi >= first) & (lb < NB) & (pb >= 0) & (pb < n_blocks)
    # The position of each parity among the block's two highest.
    pos = hi[..., None] - (hi[..., None] - jnp.arange(2, dtype=jnp.int32)) % 2
    ok = live[..., None] & (pos >= first[..., None])
    j = jnp.clip(pos - g.start[:, None, None], 0, tq - 1)
    new = jnp.take_along_axis(
        z, j.reshape(batch, nblk * 2, 1), axis=1).reshape(batch, nblk, 2, D)
    at = c * n_blocks + jnp.clip(pb, 0, n_blocks - 1)
    old = flat[at].reshape(batch, nblk, 2, D)
    merged = jnp.where(ok[..., None], new.astype(flat.dtype), old)
    idx = jnp.where(live, at, flat.shape[0])
    flat = flat.at[idx.reshape(-1)].set(
        merged.reshape(-1, 2 * D), mode="drop")
    return flat, jnp.sum(live, dtype=jnp.int32)


def _tail_step(flat: jax.Array, rows: jax.Array, w: jax.Array, g: _RowGroup,
               c, n_blocks: int, block: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The conv state's step for one group in plain XLA: from the group's
    ``[b | c | u]`` rows ``(batch, tq, 3D)`` and the float32 taps ``w``, the
    two rows before each member's first position read once, the taps by
    shifts inside the rows, each touched block's tail written. Returns the
    pool, ``c * s`` ``(batch, tq, D)`` and the block tails written."""
    D = rows.shape[-1] // 3
    zg = rows[..., :D] * rows[..., 2 * D:]
    before = [_tail_rows(flat, g, c, back, n_blocks, block)[:, None]
              for back in (2, 1)]
    zz = jnp.concatenate(before + [zg], axis=1).astype(jnp.float32)
    s = sum(w[k] * zz[:, k:k + g.tq] for k in range(3))
    flat, n = _tail_write(flat, zg, g, c, n_blocks, block)
    return flat, rows[..., D:2 * D] * s.astype(rows.dtype), n


def tail_write_path(tq: int, tail: jax.Array) -> str:
    """Which of the tail pool's two steps a group of ``tq`` rows a slot
    takes: the answer :func:`~.decode.pool_write_path` gives every pool
    (``"row"``: one row a slot, on a TPU), where the row kernel
    (``ops/pallas_conv.py`` ``conv_tail_step``) can cut this pool (bf16,
    a layer's blocks a multiple of its cut, whole lane tiles a half row);
    else ``"block"``, the XLA path (:func:`_tail_step`)."""
    from tree_attention_tpu.ops.pallas_conv import CUT

    cuts = tail.dtype == jnp.bfloat16 and not tail.shape[1] % CUT \
        and not tail.shape[2] % 256
    return pool_write_path(tq) if cuts else "block"


def conv_mixer(layer: Params, x: jax.Array, tail: jax.Array, c,
               groups: Tuple[_RowGroup, ...], cfg: TransformerConfig,
               block: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The gated short convolution over every group of the step's rows.
    ``layer``: this layer's leaves (``ln1`` ``(D,)``, ``w_in`` ``(D, 3D)``,
    ``w_conv`` ``(3, D)`` with tap ``k`` on ``z_{t-2+k}``, ``w_out`` ``(D,
    D)``); ``tail`` the WHOLE tail pool ``(conv layers, N, 2·D)``, this
    layer's entries reached by offset ``c·N``. A group's state step is
    :func:`_tail_step`, or, for a group of ONE row a slot on a TPU
    (:func:`tail_write_path`), one launch of ``ops/pallas_conv.py``
    ``conv_tail_step``; both leave the same bits. Returns the residual with
    the mixer's output added, the pool, and the block tails written."""
    from tree_attention_tpu.ops.pallas_conv import (
        conv_tail_plan, conv_tail_step,
    )

    h = rms_norm(x, layer["ln1"], cfg.norm_eps)
    bcu = h @ layer["w_in"]
    n_blocks = tail.shape[1]
    flat = tail.reshape(-1, 2 * cfg.d_model)
    outs, wrote = [], jnp.int32(0)
    for g in groups:
        rows = g.take(bcu[:, None])[:, 0]                 # (batch, tq, 3D)
        if tail_write_path(g.tq, tail) == "row":
            plan = g.tail if g.tail is not None else conv_tail_plan(
                g.table, g.start, g.n_valid, n_blocks, block)
            flat, gated = conv_tail_step(
                flat, rows[:, 0], layer["w_conv"], plan, c * n_blocks)
            gated, n = gated[:, None], plan.count
        else:
            flat, gated, n = _tail_step(
                flat, rows, layer["w_conv"].astype(jnp.float32), g, c,
                n_blocks, block)
        outs.append(gated[:, None])
        wrote = wrote + n
    y = _join_rows(groups, outs)[:, 0] @ layer["w_out"]
    return x + y, flat.reshape(tail.shape), wrote



# ---------------------------------------------------------------------------
# The state-space mixer
# ---------------------------------------------------------------------------

_HI = lax.Precision.HIGHEST


def pack_state(s: jax.Array, sm: StateSpace) -> jax.Array:
    """``(..., heads, d_head, d_state)`` as the pool holds it
    (``StateSpace.state_shape``): ``pack`` heads side by side on the
    lanes."""
    lead = s.shape[:-3]
    s = s.reshape(lead + (sm.n_heads // sm.pack, sm.pack, sm.d_head,
                          sm.d_state))
    return jnp.moveaxis(s, -1, -3).reshape(lead + sm.state_shape)


def unpack_state(s: jax.Array, sm: StateSpace) -> jax.Array:
    """:func:`pack_state`'s inverse."""
    lead = s.shape[:-3]
    s = s.reshape(lead + (sm.n_heads // sm.pack, sm.d_state, sm.pack,
                          sm.d_head))
    return jnp.moveaxis(s, -3, -1).reshape(
        lead + (sm.n_heads, sm.d_head, sm.d_state))


def ssm_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, s0: jax.Array, chunk: int
             ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t
    = S_t . C_t`` over ``T`` rows in its chunked form: blocks of ``chunk``
    rows, the products inside a block as matrix products over the block's
    rows, the state carried block to block. ``x`` ``(b, T, H, P)``, ``dt``
    ``(b, T, H)`` (0: the row leaves the state as it is and adds nothing),
    ``A`` ``(H,)``, ``B`` / ``C`` ``(b, T, G, N)``, ``s0`` ``(b, H, P, N)``;
    all float32. Returns ``y`` ``(b, T, H, P)`` and the state after the last
    row. Float32 at the highest matmul precision: what is added to a state
    is never rounded below it."""
    b, T, H, P = x.shape
    G = B.shape[2]
    L = min(T, chunk)
    pad = -T % L
    if pad:
        # Rows with dt 0: they move no state and their y is dropped.
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    rep = H // G
    tri = jnp.tril(jnp.ones((L, L), bool))
    s, ys = s0, []
    for j in range((T + pad) // L):
        xb, dtb, Bb, Cb = (t[:, j * L:(j + 1) * L] for t in (x, dt, B, C))
        cum = jnp.cumsum(dtb * A, axis=1)                    # (b, L, H) <= 0
        dx = dtb[..., None] * xb                             # (b, L, H, P)
        # Inside the block: row l sees row s <= l through exp(cum_l - cum_s).
        cb = jnp.einsum("blgn,bsgn->blsg", Cb, Bb, precision=_HI)
        seen = jnp.where(tri[None, :, :, None],
                         cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
        w = jnp.exp(seen) * jnp.repeat(cb, rep, axis=3)      # (b, l, s, H)
        y = jnp.einsum("blsh,bshp->blhp", w, dx, precision=_HI)
        # From the state the block started with.
        Ch = jnp.repeat(Cb, rep, axis=2)                     # (b, L, H, N)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "blhn,bhpn->blhp", Ch, s, precision=_HI)
        ys.append(y)
        # The state the block leaves.
        to_end = jnp.exp(cum[:, -1:, :] - cum)               # (b, L, H)
        s = jnp.exp(cum[:, -1])[..., None, None] * s + jnp.einsum(
            "blhp,blhn->bhpn", to_end[..., None] * dx,
            jnp.repeat(Bb, rep, axis=2), precision=_HI)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    return y[:, :T], s


def scan_path(tq: int, sm: StateSpace, state: Any) -> str:
    """Which of the two scans a chunk group of ``tq`` rows a member takes:
    ``"kernel"`` (``ops/pallas_ssm.py`` ``ssm_chunk_scan``: one launch a
    layer, from and into the state pool as it lies) on a TPU, where the
    kernel can cut this pool (``state``: the pool or its shape and type;
    float32, a row of heads and ``d_state`` whole lane tiles, at most four
    heads a row: their ``cum`` and ``dt`` share a tile's eight sublanes)
    and the group (8 divides its rows); else ``"xla"`` (:func:`ssm_scan`
    between a gather and a scatter of the members' states), which is also
    the kernel's oracle. One algorithm whose path follows what the program
    observes, the same for every model, as
    :func:`~.decode.pool_write_path`."""
    from tree_attention_tpu.ops import _on_tpu, _pallas_available

    cuts = state.dtype == jnp.float32 and sm.pack <= 4 \
        and not (sm.pack * sm.d_head) % 128 and not sm.d_state % 128 \
        and tq > 1 and not tq % 8
    return "kernel" if cuts and _on_tpu() and _pallas_available() else "xla"


def ssm_step(state: jax.Array, x: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token's update of packed states ``(batch, Hp, N, L)``: what
    ``ops/pallas_ssm.py`` ``ssm_decode_update`` computes in place, here in
    ``jax.numpy`` (off the TPU, and the kernel's oracle). ``x`` / ``a``
    ``(batch, Hp, L)``, ``b`` / ``c`` ``(batch, G, N)``. Returns the new
    states and ``y`` ``(batch, Hp, L)``."""
    rep = state.shape[1] // b.shape[1]
    b, c = (jnp.repeat(t, rep, axis=1)[..., None] for t in (b, c))
    new = a[:, :, None, :] * state + b * x[:, :, None, :]
    return new, jnp.sum(new * c, axis=2)


def _in_multipliers(cfg: TransformerConfig) -> Optional[jax.Array]:
    """``Multipliers.ssm_multipliers`` as the vector ``W_in``'s output is
    multiplied by, a scalar a segment of ``[z | x | B | C | dt]``; None
    where all five are 1.0 (no multiply)."""
    sm, m = cfg.ssm, cfg.mup.ssm_multipliers
    if all(v == 1.0 for v in m):
        return None
    gn = sm.n_groups * sm.d_state
    return jnp.concatenate([
        jnp.full((n,), v, jnp.float32)
        for n, v in zip((sm.inner, sm.inner, gn, gn, sm.n_heads), m)])


def _taps_step(g: _RowGroup, xg: jax.Array, flat_t: jax.Array, at, to,
               fresh, taps: jax.Array, conv_b: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """A state-space mixer's short convolution over one group's rows ``xg``
    ``(batch, tq, cd)``, from and into the flat tail pool ``(layers x S,
    (taps - 1) x cd)``: member ``i``'s last pre-activation rows at ``at[i]``
    (zero where ``fresh``), the rows it leaves written at ``to[i]`` (past
    the pool, and dropped, for a member with no row). ``taps`` ``(taps,
    cd)`` float32. Returns ``silu(conv + bias)`` ``(batch, tq, cd)`` float32
    and the pool."""
    n_taps, cd = taps.shape
    back = n_taps - 1
    old = jnp.where(fresh[:, None], 0, flat_t[at])
    bias = conv_b.astype(jnp.float32)
    if g.tq == 1:
        # A slot's rows as they lie on the lanes: a (slots, 3,
        # conv_dim) view of them is a copy in another tiling.
        rows = [old[:, k * cd:(k + 1) * cd]
                for k in range(back)] + [xg[:, 0].astype(old.dtype)]
        conv = sum(taps[k] * rows[k].astype(jnp.float32)
                   for k in range(n_taps))[:, None]
        left = jnp.concatenate(rows[1:], axis=-1)
    else:
        pre = jnp.concatenate(
            [old.reshape(g.batch, back, cd),
             xg.astype(old.dtype)], axis=1)
        conv = sum(
            taps[k] * pre[:, k:k + g.tq].astype(jnp.float32)
            for k in range(n_taps))
        # The tail a member leaves: the rows before its next.
        keep = g.n_valid[:, None] + jnp.arange(
            back, dtype=jnp.int32)
        left = jnp.take_along_axis(
            pre, keep[:, :, None], axis=1).reshape(g.batch, -1)
    conv = jax.nn.silu(conv + bias)
    return conv, flat_t.at[to].set(left, mode="drop")


def ssm_branch(layer: Params, h: jax.Array, state: jax.Array,
               tail: jax.Array, m, groups: Tuple[_RowGroup, ...],
               cfg: TransformerConfig
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The state-space mixer's branch over every group of the step's rows,
    from rows ``h`` already normed. ``layer``: this layer's leaves
    (``w_in`` ``(D, in_dim)``, ``conv_w`` ``(taps, conv_dim)`` with tap ``k`` on
    ``xBC_{t-taps+1+k}``, ``conv_b``, ``dt_bias`` / ``A_log`` / ``D``
    ``(heads,)`` float32, ``norm`` ``(inner,)``, ``w_out`` ``(inner, D)``);
    ``state`` / ``tail`` the WHOLE pools of a :class:`PagedStateCache`, slot
    ``s`` of this layer at ``m·S + s`` of their flat views.

    A **decode group** (one row a slot) takes the single-step recurrence:
    on a TPU in place, through ``ssm_decode_update`` over the list of slots
    that have a row (``g.live``). A **chunk group** runs the chunked scan
    from each member's state and puts the state it leaves back: on a TPU
    one launch of ``ops/pallas_ssm.py`` ``ssm_chunk_scan``, from and into
    the pool as it lies (the scan's ``(T, T, heads)`` intermediates and the
    states' two changes of layout never reach HBM: ISSUE 51); where the
    kernel cannot cut the pool or the group, and off the TPU,
    :func:`ssm_scan` between a gather and a scatter of the members' states
    (:func:`scan_path` says which, from the shapes alone; both are the one
    recurrence in float32 at the highest precision, and the tests hold the
    kernel to the scan). Three rules: a member whose first position is 0
    starts from a zero state and a zero tail, whatever the pools hold; a row
    past a member's valid count has ``Δ = 0`` and no input, so it leaves the
    state bit for bit; a member with no row writes nothing (and a decode
    slot with no row is not read either). The state, ``Δ``, ``a`` and ``S .
    C`` in float32, the projections in the served type. ``W_in``'s output
    is multiplied by ``cfg.mup.ssm_multipliers`` a segment, BEFORE the
    convolution's bias and ``dt_bias`` (:func:`_in_multipliers`). Returns
    what the mixer adds to the residual, the two pools, and how many states
    were written."""
    from tree_attention_tpu.ops.pallas_ssm import (
        ssm_chunk_scan, ssm_decode_update,
    )

    sm = cfg.ssm
    H, P, G, N = sm.n_heads, sm.d_head, sm.n_groups, sm.d_state
    inner, cd, back = sm.inner, sm.conv_dim, sm.taps - 1
    S = state.shape[1]
    with jax.named_scope(scopes.ATTN_IN):
        zxd = h @ layer["w_in"]
        mu = _in_multipliers(cfg)
        if mu is not None:
            zxd = (zxd.astype(jnp.float32) * mu).astype(zxd.dtype)
    with jax.named_scope(scopes.CONV):
        z, xbc, dt = jnp.split(zxd, [inner, inner + cd], axis=-1)
        A = -jnp.exp(layer["A_log"].astype(jnp.float32))
        taps = layer["conv_w"].astype(jnp.float32)
        flat_s = state.reshape((-1,) + state.shape[2:])
        flat_t = tail.reshape(-1, tail.shape[2])     # a slot's rows, flat
        outs, wrote = [], jnp.int32(0)
        for g in groups:
            at = m * S + g.slot
            has = g.n_valid > 0
            fresh = g.start == 0
            to = jnp.where(has, at, flat_s.shape[0])     # no row: dropped
            wrote = wrote + jnp.sum(has, dtype=jnp.int32)
            with jax.named_scope(scopes.SSM_TAPS):
                xg = g.take(xbc[:, None])[:, 0]          # (batch, tq, cd)
                conv, flat_t = _taps_step(
                    g, xg, flat_t, at, to, fresh, taps, layer["conv_b"])
                # [x | B | C] as the convolution lays them, and by head and
                # by group.
                wide = jnp.split(conv, [inner, inner + G * N], axis=-1)
                xs = wide[0].reshape(g.batch, g.tq, H, P)
                Bm = wide[1].reshape(g.batch, g.tq, G, N)
                Cm = wide[2].reshape(g.batch, g.tq, G, N)
                dts = jax.nn.softplus(
                    g.take(dt[:, None])[:, 0].astype(jnp.float32)
                    + layer["dt_bias"].astype(jnp.float32))
                dts = jnp.where(g.valid[..., None], dts, 0.0)
            if g.tq == 1:
                with jax.named_scope(scopes.SSM_UPDATE):
                    # A fresh member's old state decays to nothing.
                    a = jnp.where(fresh[:, None], 0.0,
                                  jnp.exp(dts[:, 0] * A))            # (b, H)
                    rows = (g.batch, H // sm.pack, sm.pack * P)
                    dx = (dts[:, 0, :, None] * xs[:, 0]).reshape(rows)
                    a = jnp.repeat(a, P, axis=-1).reshape(rows)
                    if g.live is not None:
                        flat_s, y = ssm_decode_update(
                            flat_s, dx, a, Bm[:, 0], Cm[:, 0], *g.live,
                            m * S)
                        y = jnp.where(has[:, None, None], y, 0.0)
                    else:
                        new, y = ssm_step(flat_s[at], dx, a, Bm[:, 0],
                                          Cm[:, 0])
                        flat_s = flat_s.at[to].set(new, mode="drop")
                    y = y.reshape(g.batch, 1, H, P)
            else:
                with jax.named_scope(scopes.SSM_SCAN):
                    if scan_path(g.tq, sm, state) == "kernel":
                        flat_s, y = ssm_chunk_scan(
                            flat_s, wide[0], dts, A, *wide[1:], at,
                            g.n_valid, fresh)
                        y = y.reshape(xs.shape)
                    else:
                        s0 = jnp.where(fresh[:, None, None, None], 0.0,
                                       unpack_state(flat_s[at], sm))
                        y, s1 = ssm_scan(xs, dts, A, Bm, Cm, s0, sm.chunk)
                        flat_s = flat_s.at[to].set(pack_state(s1, sm),
                                                   mode="drop")
            with jax.named_scope(scopes.SSM_NORM):
                y = y + layer["D"].astype(jnp.float32)[:, None] * xs
                y = y.reshape(g.batch, g.tq, inner) * jax.nn.silu(
                    g.take(z[:, None])[:, 0].astype(jnp.float32))
                yg = y.reshape(g.batch, g.tq, G, inner // G)
                yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                    + cfg.norm_eps)
                y = yg.reshape(y.shape) * layer["norm"]
                outs.append(y.astype(h.dtype)[:, None])
        y = _join_rows(groups, outs)[:, 0]
    with jax.named_scope(scopes.ATTN_OUT):
        y = y @ layer["w_out"]
    return (y, flat_s.reshape(state.shape), flat_t.reshape(tail.shape),
            wrote)


def ssm_mixer(layer: Params, x: jax.Array, state: jax.Array,
              tail: jax.Array, m, groups: Tuple[_RowGroup, ...],
              cfg: TransformerConfig
              ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The state-space mixer as a layer of its own: the norm (``ln1``), the
    branch (:func:`ssm_branch`), the add. Returns the residual with the
    mixer's output added, the two pools, and how many states were
    written."""
    with jax.named_scope(scopes.ATTN_IN):
        h = rms_norm(x, layer["ln1"], cfg.norm_eps)
    y, state, tail, wrote = ssm_branch(layer, h, state, tail, m, groups, cfg)
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + y
    return x, state, tail, wrote


# ---------------------------------------------------------------------------
# The Mamba-1 mixer, the gated memory unit, differential attention
# ---------------------------------------------------------------------------


def ssm1_rows(s0: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
              B: jax.Array, C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The Mamba-1 recurrence a row at a time, in ``jax.numpy``: ``S_t =
    exp(dt_t (x) A) S_{t-1} + B_t (x) (dt_t x_t)``, ``y_t = C_t . S_t``.
    ``s0`` ``(b, N, Ch)`` (the pool's layout, ``Mamba1.state_shape``), ``x``
    / ``dt`` ``(b, T, Ch)`` (``dt`` 0: the row leaves the state as it is and
    adds nothing), ``A`` ``(N, Ch)``, ``B`` / ``C`` ``(b, T, N)``; all
    float32. Returns ``y`` ``(b, T, Ch)`` and the state after the last row.
    The path off the TPU and the oracle of ``ops/pallas_ssm.py``
    ``ssm1_scan``: a decay a (channel, state) pair has no chunked matrix
    form."""
    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None, :] * A) * s \
            + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s, y = lax.scan(step, s0, tuple(jnp.moveaxis(t, 1, 0)
                                    for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), s


def scan1_path(state: Any) -> str:
    """Which of the two Mamba-1 recurrences a group takes, at any ``tq``:
    ``"kernel"`` (``ops/pallas_ssm.py`` ``ssm1_scan``: one launch a layer,
    from and into the state pool as it lies) on a TPU, where the kernel can
    cut this pool (``state``: the pool or its shape and type; float32, the
    states a whole number of sublane tiles, the channels of lane chunks);
    else ``"xla"`` (:func:`ssm1_rows` between a gather and a scatter of the
    members' states). The same for every model, as :func:`scan_path`."""
    from tree_attention_tpu.ops import _on_tpu, _pallas_available
    from tree_attention_tpu.ops.pallas_ssm import SSM1_LANES

    cuts = state.dtype == jnp.float32 and not state.shape[-2] % 8 \
        and not state.shape[-1] % SSM1_LANES
    return "kernel" if cuts and _on_tpu() and _pallas_available() else "xla"


def ssm1_branch(layer: Params, h: jax.Array, state: jax.Array,
                tail: jax.Array, m, groups: Tuple[_RowGroup, ...],
                cfg: TransformerConfig):
    """The Mamba-1 mixer's branch over every group of the step's rows, from
    rows ``h`` already normed. ``layer``: ``w_in`` ``(D, 2 x inner)`` (``[x
    | z]``), ``conv_w`` ``(taps, inner)`` / ``conv_b``, ``w_x`` ``(inner,
    dt_rank + 2 x d_state)`` (``[δ | B | C]``), ``w_dt`` ``(dt_rank,
    inner)`` / ``dt_bias`` ``(inner,)`` float32, ``A_log`` ``(d_state,
    inner)`` float32 (the pool's layout), ``D`` ``(inner,)`` float32,
    ``w_out`` ``(inner, D)``; ``state`` / ``tail`` the WHOLE pools of a
    :class:`PagedStateWindowCache`, slot ``s`` of this layer at ``m·S + s``
    of their flat views. A group's rows go through the recurrence at any
    ``tq`` by ONE launch of ``ssm1_scan`` on a TPU (:func:`scan1_path`),
    else :func:`ssm1_rows`; :func:`ssm_branch`'s three rules hold as they
    are (a fresh member starts from zeros, a row past the valid count
    leaves state and tail bit for bit, a member with no row is neither read
    nor written). Returns what the mixer adds to the residual, the two
    pools, how many states were written, and the scan output ``y`` (with
    the ``D·x`` skip, before the gate) on the step's row axis: the MEMORY a
    gated memory unit reads, where this is the memory layer."""
    from tree_attention_tpu.ops.pallas_ssm import ssm1_scan

    sm, f32 = cfg.ssm1, jnp.float32
    inner, N = sm.inner, sm.d_state
    S = state.shape[1]
    with jax.named_scope(scopes.ATTN_IN):
        xz = h @ layer["w_in"]
    with jax.named_scope(scopes.CONV):
        x, z = jnp.split(xz, [inner], axis=-1)
        A = -jnp.exp(layer["A_log"].astype(f32))
        taps = layer["conv_w"].astype(f32)
        flat_s = state.reshape((-1,) + state.shape[2:])
        flat_t = tail.reshape(-1, tail.shape[2])
        outs, mems, wrote = [], [], jnp.int32(0)
        for g in groups:
            at = m * S + g.slot
            has = g.n_valid > 0
            fresh = g.start == 0
            to = jnp.where(has, at, flat_s.shape[0])     # no row: dropped
            wrote = wrote + jnp.sum(has, dtype=jnp.int32)
            with jax.named_scope(scopes.SSM_TAPS):
                xg = g.take(x[:, None])[:, 0]            # (batch, tq, inner)
                xc, flat_t = _taps_step(
                    g, xg, flat_t, at, to, fresh, taps, layer["conv_b"])
                dbc = jnp.dot(xc.astype(h.dtype), layer["w_x"],
                              preferred_element_type=f32)
                delta, Bm, Cm = jnp.split(
                    dbc, [sm.dt_rank, sm.dt_rank + N], axis=-1)
                dts = jax.nn.softplus(
                    jnp.dot(delta.astype(h.dtype), layer["w_dt"],
                            preferred_element_type=f32)
                    + layer["dt_bias"].astype(f32))
                dts = jnp.where(g.valid[..., None], dts, 0.0)
            with jax.named_scope(
                    scopes.SSM_UPDATE if g.tq == 1 else scopes.SSM_SCAN):
                if scan1_path(state) == "kernel":
                    flat_s, y = ssm1_scan(
                        flat_s, xc, dts, A, Bm, Cm, at, g.n_valid, fresh,
                        live=g.live)
                else:
                    s0 = jnp.where(fresh[:, None, None], 0.0, flat_s[at])
                    y, s1 = ssm1_rows(s0, xc, dts, A, Bm, Cm)
                    flat_s = flat_s.at[to].set(s1, mode="drop")
            with jax.named_scope(scopes.SSM_NORM):
                y = y + layer["D"].astype(f32) * xc
                mems.append(y.astype(h.dtype)[:, None])
                y = y * jax.nn.silu(g.take(z[:, None])[:, 0].astype(f32))
                outs.append(y.astype(h.dtype)[:, None])
        y = _join_rows(groups, outs)[:, 0]
        mem = _join_rows(groups, mems)[:, 0]
    with jax.named_scope(scopes.ATTN_OUT):
        y = y @ layer["w_out"]
    return (y, flat_s.reshape(state.shape), flat_t.reshape(tail.shape),
            wrote, mem)


def gmu_mixer(layer: Params, x: jax.Array, mem: jax.Array,
              cfg: TransformerConfig) -> jax.Array:
    """A gated memory unit as a layer's mixer: ``x + (silu(norm(x) · W_in)
    ⊙ m) · W_out``, ``m`` the SAME row's memory (the memory layer's scan
    output, carried up the layer loop). No cache. ``W_in`` and the ``silu``
    under ``attn_in``, the product with ``m`` and ``W_out`` under
    ``attn_out``."""
    with jax.named_scope(scopes.ATTN_IN):
        gate = jax.nn.silu(norm_rows(cfg, x, layer, "ln1") @ layer["w_in"])
    with jax.named_scope(scopes.ATTN_OUT):
        return x + (gate * mem) @ layer["w_out"]


def lambda_init(l) -> jax.Array:
    """``λ₀(l) = 0.8 - 0.6 · e^(-0.3 l)``, ``l`` the layer's index from 0
    (an int, or the layer loop's traced index)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))


def diff_tail(out: jax.Array, layer: Params, l,
              cfg: TransformerConfig) -> jax.Array:
    """Differential attention's few row-wise operations after the paged
    call, from the packed output ``(B, H, T, 2 x d)`` whose head ``2p + σ``
    holds ``a_{p,σ} = softmax(s · q_{2p+σ} · k_{2j+σ}) · [v_2j | v_2j+1]``
    on ALL its lanes: ``o_p = RMSNorm(a_{p,0} - λ · a_{p,1}) · (1 - λ₀(l))``
    with ``λ = exp(λ_q1 · λ_k1) - exp(λ_q2 · λ_k2) + λ₀(l)`` (``layer["lam"]``
    ``(4, d)``: ``λ_q1, λ_k1, λ_q2, λ_k2``; the norm's gain ``sub_ln`` ``(2 x
    d,)``). Float32, rounded once. Returns ``(B, H / 2, T, 2 x d)``."""
    B, H, T, W = out.shape
    lam = layer["lam"].astype(jnp.float32)
    lam0 = lambda_init(l)
    full = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + lam0
    a = out.astype(jnp.float32).reshape(B, H // 2, 2, T, W)
    o = a[:, :, 0] - full * a[:, :, 1]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    return (o * layer["sub_ln"] * (1.0 - lam0)).astype(out.dtype)


def diff_branch(attend: _Attend, layer: Params, h: jax.Array, k_cache,
                v_cache, l, pool_l, base):
    """A differential-attention mixer's branch over every group of the
    step's rows, from rows already normed; window, full and cross layers
    alike (``attend`` says which: its window, and ``write`` false for a
    cross layer, whose ``layer`` holds ``W_q`` alone and whose rows read
    what the shared layer wrote). The pool rows are PAIRS of KV heads
    (``_pack_heads``); query head ``2p + σ`` lies in half ``σ`` of its row
    with zeros beside it, so ``q · k`` over the packed row is its dot
    product with key head ``2j + σ`` and the call's output row is ``a_{p,σ}``
    itself: K and V are read ONCE a call by the kernels every attention
    layer uses, and the subtraction, ``λ``, the norm and ``(1 - λ₀)`` follow
    (:func:`diff_tail`). ``l``: the layer's index in the model (``λ₀``'s
    depth); ``pool_l`` / ``base``: its layer and first block in the pool it
    attends to. Returns what the mixer adds to the residual and the two
    cache arrays."""
    cfg, groups = attend.cfg, attend.groups
    with jax.named_scope(scopes.ATTN_IN):
        if GQA_SERVED in layer:  # the served form: one product
            qkv = times_out_major(h, layer[GQA_SERVED])
        else:
            qkv = jnp.concatenate(
                [h @ layer[n] for n in ("wq", "wk", "wv") if n in layer],
                axis=-1)
        qkv = qkv + layer["bqkv"].astype(qkv.dtype)
        q, k_new, v_new = qkv, None, None
        if attend.write:
            q, k_new, v_new = jnp.split(
                qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
            k_new = _heads(k_new, cfg.n_kv_heads, cfg.d_head)
            v_new = _heads(v_new, cfg.n_kv_heads, cfg.d_head)
        q, k_new, v_new = _pack_heads(
            _heads(q, cfg.n_heads, cfg.d_head), k_new, v_new, cfg)
    outs = []
    for gi in range(len(groups)):
        out, k_cache, v_cache, _, _ = attend(
            gi, q, k_new, v_new, k_cache, v_cache, None, None, None, pool_l,
            base)
        outs.append(out)
    with jax.named_scope(scopes.ATTN_DECODE):
        out = diff_tail(_join_rows(groups, outs), layer, l, cfg)
    with jax.named_scope(scopes.ATTN_OUT):
        y = _unheads(out) @ layer["wo"] + layer["bo"].astype(out.dtype)
    return y, k_cache, v_cache


# ---------------------------------------------------------------------------
# The EVA mixer
# ---------------------------------------------------------------------------


def _chunk_rows(pools: Tuple[jax.Array, ...], blk: jax.Array,
                row: jax.Array, count: jax.Array, chunk: int
                ) -> Tuple[jax.Array, ...]:
    """``chunk`` consecutive rows from row ``row`` of block ``blk`` of every
    flat pool ``(blocks, Hkv, block, D)``, for ``(batch, J)`` of each:
    ``(batch, J, Hkv, chunk, D)`` a pool. A slice a chunk, never the block:
    on a TPU one kernel's copies (``ops/pallas_decode.py``
    ``paged_chunk_read``, the first ``count`` of a member's only; handed to
    XLA as a gather, the compiler re-laid the whole pool for it), elsewhere
    a gather."""
    from tree_attention_tpu.ops import _on_tpu, _pallas_available

    if _on_tpu() and _pallas_available():
        from tree_attention_tpu.ops.pallas_decode import paged_chunk_read

        return paged_chunk_read(pools, blk, row, count, chunk)
    _, Hkv, _, D = pools[0].shape

    def one(flat):
        def cut(b, r):
            return lax.dynamic_slice(flat, (b, 0, r, 0), (1, Hkv, chunk, D))[0]

        return jax.vmap(jax.vmap(cut))(blk, row)

    return tuple(one(flat) for flat in pools)


def eva_summaries(layer: Params, g: _RowGroup, wk: jax.Array, wv: jax.Array,
                  l, cfg: TransformerConfig
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The summaries of the chunks that the rows of ``g`` close, from the
    chunk's rows AS THE LOCAL POOL HOLDS THEM (``wk`` / ``wv``, this layer's
    blocks at ``l * Nw``, the group's rows already written): a pure
    function of sixteen pool rows, so nothing is kept between ticks, and a
    chunk a prompt began and a decode row ends is formed like any other. A
    chunk closes inside its own window, whose blocks the slot still holds.
    Returns ``(k~, v~)`` ``(batch, Hkv, J, D)`` in the pool's type, ``J`` the
    most chunks ``tq`` rows can close, and ``(first, count)``: member ``i``'s
    are summary rows ``first[i] .. first[i] + count[i] - 1``, candidates
    past ``count`` are of rows not written and dropped by the write."""
    C = cfg.chunk
    L, Nw, Hkv, block, D = wk.shape
    first, count = chunks_closed(g.start, g.n_valid, C)
    J = -(-g.tq // C)
    pos = (first[:, None] + jnp.arange(J, dtype=jnp.int32)) * C
    pb = jnp.take_along_axis(
        g.wtable, jnp.clip(pos // block, 0, g.wtable.shape[1] - 1), axis=1)
    blk = l * Nw + jnp.clip(pb, 0, Nw - 1)
    kc, vc = (a.astype(jnp.float32) for a in _chunk_rows(
        (wk.reshape(L * Nw, Hkv, block, D), wv.reshape(L * Nw, Hkv, block, D)),
        blk, pos % block, count, C))
    phi = layer["phi"].astype(jnp.float32)[:, None, :]       # (Hkv, 1, D)
    # Products and sums of float32 on the vector unit: a weight is never
    # rounded on its way into a matrix unit.
    alpha = jax.nn.softmax(jnp.sum(kc * phi, axis=-1), axis=-1)[..., None]
    ks = jnp.sum(alpha * kc, axis=-2) + layer["mu"].astype(jnp.float32)
    vs = jnp.sum(alpha * vc, axis=-2)                        # (b, J, Hkv, D)
    return (jnp.swapaxes(ks, 1, 2).astype(wk.dtype),
            jnp.swapaxes(vs, 1, 2).astype(wv.dtype), first, count)


def eva_mixer(attend: _Attend, layer: Params, x: jax.Array,
              positions: jax.Array, k: jax.Array, v: jax.Array,
              wk: jax.Array, wv: jax.Array, l):
    """EVA attention over every group of the step's rows. ``attend`` is the
    rotary-GQA body built for the LOCAL pool (the aligned window, the second
    table, partials returned); ``k`` / ``v`` the summary pools, ``wk`` /
    ``wv`` the exact rows', whole, this layer's blocks at ``l * N`` / ``l *
    Nw``. A group's rows go into the local pool; the chunks they close are
    summarised from it and written to the summary pool
    (:func:`eva_summaries`); its queries take one partial from each pool,
    and ``merge_partials`` makes of the two the one softmax. A group none
    of whose rows has a closed window behind it makes no second call.
    Returns the residual with the mixer's output added, the four pools, and
    the summary rows written."""
    cfg, groups = attend.cfg, attend.groups
    rule, _ = window_rules(cfg)
    N, Nw = k.shape[1], wk.shape[1]
    with jax.named_scope(scopes.ATTN_IN):
        h = rms_norm(x, layer["ln1"], cfg.norm_eps)
        q, k_new, v_new = gqa_qkv(layer, h, positions, cfg)
    outs, wrote = [], jnp.int32(0)
    for gi, g in enumerate(groups):
        (out, lse), wk, wv, _, _ = attend(
            gi, q, k_new, v_new, wk, wv, None, None, None, l, l * Nw)
        with jax.named_scope(scopes.ATTN_CACHE), \
                jax.named_scope(scopes.EVA_SUMMARY):
            ks, vs, first, count = eva_summaries(layer, g, wk, wv, l, cfg)
            k, v = _pool_write((k, v), (ks, vs), g.table, first, count, l,
                               g.at)
            wrote = wrote + jnp.sum(count, dtype=jnp.int32)
        with jax.named_scope(
                scopes.ATTN_CHUNK if g.chunk else scopes.ATTN_DECODE):
            qg = g.take(q)

            def both(out, lse):
                # (Traced here and now: the loop's names are this group's.)
                o2, l2 = decode_attention(
                    qg, k.reshape((-1,) + k.shape[2:]),
                    v.reshape((-1,) + v.shape[2:]), q_position=g.start,
                    block_table=l * N + g.table,
                    step_plan=g.plan and g.plan.shifted(l * N),
                    window=rule, mesh=attend.mesh,
                    data_axis=None, seq_axis=attend.axes["seq"],
                    model_axis=attend.axes["model"],
                    block_size=cfg.attn_block_size, impl=cfg.attn_impl,
                    num_splits=attend.num_splits)
                return merge_partials(
                    jnp.stack([out, o2]), jnp.stack([lse, l2]))[0]

            # A row sees a summary once its position has passed a window.
            last = g.start + g.n_valid - 1
            out = lax.cond(
                jnp.any((g.n_valid > 0) & (last >= cfg.window)),
                both, lambda out, lse: out, out, lse)
        outs.append(out)
    with jax.named_scope(scopes.ATTN_DECODE):
        out = _join_rows(groups, outs)
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + _unheads(out) @ layer["wo"]
    return x, k, v, wk, wv, wrote


def layer_runs(cfg: TransformerConfig) -> List[Tuple[str, str, int, int, int]]:
    """The depth cut into runs of consecutive layers of one kind:
    ``(mixer, ffn, layers, first of its mixer kind, first of its ffn
    kind)``, the two offsets counted among the layers of that kind. A
    ``"parallel"`` layer is an attention layer and a state-space layer at
    once; a model of them has no layer of another kind
    (``TransformerConfig.__post_init__``), so its offset among either is
    its offset among the parallel layers."""
    types = cfg.layer_types or ("attention",) * cfg.n_layers
    runs: List[list] = []
    seen = {"attention": 0, "window": 0, "conv": 0, "ssm": 0, "eva": 0,
            "parallel": 0, "ssm1": 0, "gmu": 0, "cross": 0, "dense": 0,
            "expert": 0, "none": 0}
    for mixer, ffn in zip(types, cfg.ffn_kinds):
        if runs and runs[-1][:2] == [mixer, ffn]:
            runs[-1][2] += 1
        else:
            runs.append([mixer, ffn, 1, seen[mixer], seen[ffn]])
        seen[mixer] += 1
        seen[ffn] += 1
    return [tuple(r) for r in runs]


class _Sub(NamedTuple):
    """One layer of a run's period: its kinds, and where its first pass
    finds its parts: ``m0`` among the layers of its mixer kind, ``f0`` among
    those of its feed-forward kind, ``l0`` among all; pass ``i`` of the run
    finds them ``i`` strides further (``dm``, ``df``, ``dl``: the layers of
    that kind, and all, a period holds)."""

    mixer: str
    ffn: str
    m0: int
    f0: int
    l0: int
    dm: int = 1
    df: int = 1
    dl: int = 1


def period_runs(cfg: TransformerConfig) -> List[Tuple[Tuple[_Sub, ...], int]]:
    """:func:`layer_runs` as the layer loop scans it: ``(period, passes)``,
    the period one layer for every model but a decoder that feeds a second
    decoder, whose depth alternates by construction (a Mamba-1 layer and a
    window layer; a gated memory unit and a cross layer): there a stretch
    of ``A, B, A, B, ...`` single layers is ONE run whose period is the
    pair, so that the body is still one a run, with no branch on a layer's
    kind in the traced program; the memory layer and the shared layer stay
    runs of one."""
    singles, l = [], 0
    for mixer, ffn, n, m0, f0 in layer_runs(cfg):
        singles.append(((_Sub(mixer, ffn, m0, f0, l),), n))
        l += n
    if cfg.ssm1 is None:
        return singles
    kinds = [(sub.mixer, sub.ffn, n) for (sub,), n in singles]
    runs, i = [], 0
    while i < len(singles):
        reps = 0
        while i + 2 * reps + 1 < len(singles) and all(
                kinds[i + 2 * reps + j] == kinds[i + j][:2] + (1,)
                for j in range(2)):
            reps += 1
        if reps < 2:
            runs.append(singles[i])
            i += 1
            continue
        pair = (singles[i][0][0], singles[i + 1][0][0])
        df = 2 if pair[0].ffn == pair[1].ffn else 1
        runs.append((tuple(sub._replace(df=df, dl=2) for sub in pair), reps))
        i += 2 * reps
    return runs


def hybrid_layers(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cache: Union[PagedHybridCache, PagedWindowCache, PagedStateCache,
                 PagedStateWindowCache],
    cfg: TransformerConfig,
    attend: _Attend,
    stats: Optional[Dict[str, Any]],
    cut: Optional[Tuple[jax.Array, _RowGroup]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Every layer of a step for a model whose layers are of several kinds
    (:func:`~.decode._step_layers`' third branch): one body a (mixer,
    feed-forward) kind, a run of consecutive layers of one kind under one
    ``lax.scan`` (a run of one layer is called as it is), the K/V pools, the
    tail pool and the residual the carry of them all. ``params`` holds a
    stack a kind on a leading axis of that kind's layers: ``attn``
    (``ln1``, ``wq``, ``wk``, ``wv``, ``wo``, with QK-norm ``q_ln`` /
    ``k_ln``), ``wattn`` (the window layers: the same leaves), ``conv``
    (:func:`conv_mixer`'s leaves), ``ssm`` (:func:`ssm_mixer`'s), ``eva``
    (the attention leaves and ``phi`` / ``mu`` ``(Hkv, d)``), ``dense``
    (``ln2``, ``w1``, ``w3``, ``w2``) and ``layers`` (the expert layers:
    ``ln2``, ``router``, ``router_bias``, ``we1`` / ``we3`` / ``we2`` and
    the shared experts' ``ws*``; experts in a latent: ``w_down`` / ``w_up``
    too; ungated experts: no ``we3`` / ``ws3``). A layer whose feed-forward
    kind is ``"none"`` is its mixer alone. A decoder that feeds a second
    decoder adds ``ssm1`` (:func:`ssm1_branch`'s leaves), ``gmu`` (``ln1``,
    ``w_in``, ``w_out``) and ``xattn`` (the cross layers: ``wq``, ``bqkv``,
    ``lam``, ``sub_ln``, ``wo``, ``bo``), its ``attn`` / ``wattn`` layers
    the differential leaves too, every norm a bias (``ln1_b``, ``ln2_b``);
    the carry gains the step's MEMORY rows ``(1, R, inner)``, set by the
    last Mamba-1 layer and read by every gated memory unit; a cross layer
    is "attention layer 0's pool, no write". ``cut`` (``(rows, group)``,
    a packed step's): after layer ``cfg.row_cut`` the residual and the
    memory are gathered to ``rows`` (one a slot) and the layers above run
    on those alone, the cross layers over ``group``. Returns the residual
    and the pools by field name."""
    from tree_attention_tpu.models.experts import expert_layer, held_counts

    groups = attend.groups
    N, block = cache.blocks, cache.block
    # The one rotary-GQA body, built a kind: a full layer's, and a window
    # layer's (its window, the second table's pools, its own rotary rule).
    attend = dataclasses.replace(attend, rotary=cfg.rotates("attention"))
    attend_w, Nw = None, 0
    if cfg.window_layers:
        attend_w = dataclasses.replace(
            attend, window=cfg.window, rotary=cfg.rotates("window"))
        Nw = cache.window_blocks
    if cfg.eva_layers:
        # ... and an EVA layer's, for its exact rows: the aligned window
        # over the second table's pools, its partial handed back.
        attend_w = dataclasses.replace(
            attend, window=window_rules(cfg)[1], partial=True)
    valid = groups[0].valid
    if groups[0].lo is not None:
        valid = jnp.concatenate([g.valid.reshape(-1) for g in groups])[None]
    experts = routers = None
    if cfg.n_expert_layers:
        # Every layer's experts as ONE stack (a bitcast), a layer's reached
        # by offset: sliced out, the kernel would be handed a copy.
        stack = params["layers"]
        experts = tuple(stack[n].reshape((-1,) + stack[n].shape[2:])
                        for n in cfg.moe.leaves)
        routers = {n: a for n, a in stack.items() if n not in cfg.moe.leaves}

    def of(stack, i):
        """Layer ``i`` of a kind's stack: ``i`` a Python int (a run of
        one) or the scan's index."""
        if isinstance(i, int):
            return jax.tree.map(lambda a: a[i], stack)
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), stack)

    # A cross layer's body: the shared layer's pool, read and not written,
    # by the rows that go on above the seam (a packed step's one row a
    # slot, ``cut``; else the step's own groups).
    attend_x = dataclasses.replace(
        attend, write=False,
        groups=groups if cut is None else (cut[1],))

    def layer_of(carry, sub, i):
        """Layer ``sub`` of pass ``i`` of its run."""
        x, k, v, tail, wk, wv, state, stail, mem = carry
        mixer, ffn = sub.mixer, sub.ffn
        mi, fi, l = (first + (i if d == 1 else d * i) for first, d in (
            (sub.m0, sub.dm), (sub.f0, sub.df), (sub.l0, sub.dl)))
        wrote = jnp.int32(0)
        if mixer == "ssm":
            x, state, stail, wrote = ssm_mixer(
                of(params["ssm"], mi), x, state, stail, mi, groups, cfg)
        elif mixer == "ssm1":
            layer = of(params["ssm1"], mi)
            with jax.named_scope(scopes.ATTN_IN):
                h = norm_rows(cfg, x, layer, "ln1")
            y, state, stail, wrote, scanned = ssm1_branch(
                layer, h, state, stail, mi, groups, cfg)
            with jax.named_scope(scopes.ATTN_OUT):
                x = x + y
            if sub.m0 == cfg.ssm_layers - 1:
                mem = scanned        # the memory layer: the last of them
        elif mixer == "gmu":
            x = gmu_mixer(of(params["gmu"], mi), x, mem, cfg)
        elif cfg.diff_attn:
            # Differential attention, one body three ways: the shared
            # layer's (pool ``k`` / ``v``), a window layer's (``wk`` /
            # ``wv``), a cross layer's (the shared pool again, no write).
            name, att, pool_l, n_blocks = {
                "attention": ("attn", attend, mi, N),
                "window": ("wattn", attend_w, mi, Nw),
                "cross": ("xattn", attend_x, 0, N)}[mixer]
            layer = of(params[name], mi)
            with jax.named_scope(scopes.ATTN_IN):
                h = norm_rows(cfg, x, layer, "ln1")
            pools = (wk, wv) if mixer == "window" else (k, v)
            y, *pools = diff_branch(
                att, layer, h, *pools, l, pool_l, pool_l * n_blocks)
            if mixer == "window":
                wk, wv = pools
            else:
                k, v = pools
            with jax.named_scope(scopes.ATTN_OUT):
                x = x + y
        elif mixer == "attention":
            x, k, v, _, _ = gqa_mixer(
                attend, of(params["attn"], mi), x, positions, k, v,
                None, None, None, mi, mi * N)
        elif mixer == "window":
            x, wk, wv, _, _ = gqa_mixer(
                attend_w, of(params["wattn"], mi), x, positions, wk, wv,
                None, None, None, mi, mi * Nw)
        elif mixer == "eva":
            x, k, v, wk, wv, wrote = eva_mixer(
                attend_w, of(params["eva"], mi), x, positions, k, v,
                wk, wv, mi)
        elif mixer == "parallel":
            # Two mixers side by side on ONE normed residual (the
            # module's docstring): the layer is attention layer ``mi``
            # and state-space layer ``mi``; its one norm is ``ln1`` of
            # its attention leaves. The two branches, and no third
            # mixer; the sum in float32, rounded once.
            mup, f32 = cfg.mup, jnp.float32
            attn = of(params["attn"], mi)
            with jax.named_scope(scopes.ATTN_IN):
                h = rms_norm(x, attn["ln1"], cfg.norm_eps)
                h_s = times(h, mup.ssm_in_multiplier)
                h_a = times(h, mup.attention_in_multiplier)
            y_s, state, stail, wrote = ssm_branch(
                of(params["ssm"], mi), h_s, state, stail, mi, groups,
                cfg)
            y_a, k, v, _, _ = gqa_branch(
                attend, attn, h_a, positions, k, v, None, None, None,
                mi, mi * N)
            with jax.named_scope(scopes.ATTN_OUT):
                x = (x.astype(f32)
                     + times(y_s.astype(f32), mup.ssm_out_multiplier)
                     + times(y_a.astype(f32),
                             mup.attention_out_multiplier)
                     ).astype(x.dtype)
        else:
            with jax.named_scope(scopes.CONV):
                x, tail, wrote = conv_mixer(
                    of(params["conv"], mi), x, tail, mi, groups, cfg,
                    block)
        if ffn == "dense":
            with jax.named_scope(scopes.FFN):
                layer = of(params["dense"], fi)
                x = x + _mlp_block(
                    layer, norm_rows(cfg, x, layer, "ln2"),
                    cfg.mup.mlp_multipliers)
        if ffn != "expert":
            return (x, k, v, tail, wk, wv, state, stail, mem), (None, wrote)
        with jax.named_scope(scopes.ROUTE):
            layer = of(routers, fi)
            h32 = rms_norm(
                x.astype(jnp.float32), layer["ln2"], cfg.norm_eps)
            h = h32.astype(x.dtype)
        y, chosen = expert_layer(
            layer, h, cfg.moe, router_input=h32,
            experts=experts, first=fi * cfg.moe.held,
        )
        with jax.named_scope(scopes.ROUTE):
            return (x + y, k, v, tail, wk, wv, state, stail, mem), (
                held_counts(chosen, valid, cfg.moe), wrote)

    def body_of(period):
        def body(carry, i):
            rows, wrote = None, jnp.int32(0)
            for sub in period:
                carry, (r, w) = layer_of(carry, sub, i)
                rows = r if r is not None else rows
                wrote = wrote + w
            return carry, (rows, wrote)

        # (One layer a period: the layer's own outputs, as they are.)
        return body if len(period) > 1 else (
            lambda carry, i: layer_of(carry, period[0], i))

    # A pool the cache has not is None: an empty part of the carry.
    carry = (x, cache.k, cache.v, getattr(cache, "tail", None),
             getattr(cache, "wk", None), getattr(cache, "wv", None),
             getattr(cache, "ssm_state", None),
             getattr(cache, "ssm_tail", None), None)
    counts, wrote, done = [], jnp.int32(0), 0
    for period, n in period_runs(cfg):
        body = body_of(period)
        if n == 1:
            carry, (rows, w) = body(carry, 0)
            rows = None if rows is None else rows[None]
        else:
            carry, (rows, w) = lax.scan(
                body, carry, jnp.arange(n, dtype=jnp.int32))
        if rows is not None:
            counts.append(rows)
        wrote = wrote + jnp.sum(w)
        done += n * len(period)
        if cut is not None and done == cfg.row_cut:
            # The seam: the rows no slot samples from leave the stack.
            with jax.named_scope(scopes.ATTN_OUT):
                x, *pools, mem = carry
                carry = (x[:, cut[0]], *pools, mem[:, cut[0]])
    if stats is not None:
        if counts:
            stats["expert_rows"] = jnp.concatenate(counts, axis=0)
        if cfg.conv_layers:
            stats["tail_blocks"] = wrote
        if cfg.ssm_layers:
            stats["ssm_states"] = wrote
        if cfg.eva_layers:
            stats["eva_summaries"] = wrote
    x, k, v, tail, wk, wv, state, stail, _ = carry
    if cfg.ssm1 is not None:
        return x, {"k": k, "v": v, "wk": wk, "wv": wv, "ssm_state": state,
                   "ssm_tail": stail}
    if cfg.window_layers or cfg.eva_layers:
        return x, {"k": k, "v": v, "wk": wk, "wv": wv}
    if cfg.ssm_layers:
        return x, {"k": k, "v": v, "ssm_state": state, "ssm_tail": stail}
    return x, {"k": k, "v": v, "tail": tail}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_hybrid_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    """Parameters of a model served by :func:`hybrid_layers`, in the layout
    it reads: a stack a kind. One jitted call; every leaf is drawn layer by
    layer (expert by expert) in float32 and rounded at once to the served
    type, so the peak is the weights themselves."""
    from tree_attention_tpu.models.experts import ROUTER_BIAS_STD, _normal

    @functools.partial(jax.jit, static_argnums=(1,))
    def make(key, cfg):
        ks = jax.random.split(key, 6)
        D, ex = cfg.d_model, cfg.moe
        res_std = 0.02 / (2 * cfg.n_layers) ** 0.5
        normal = functools.partial(_normal, dtype=cfg.dtype)

        def ln(name):
            """A norm's leaves: the gain, and under LayerNorm the bias."""
            out = {name: jnp.ones((D,), jnp.float32)}
            if cfg.norm == "layer":
                out[name + "_b"] = jnp.zeros((D,), jnp.float32)
            return out

        def attn(k, own_kv=True):
            k = jax.random.split(k, 4)
            out = {
                **ln("ln1"),
                "wq": normal(k[0], (D, cfg.q_dim), 0.02),
                "wo": normal(k[3], (cfg.q_dim, D), res_std),
            }
            if own_kv:
                out.update(wk=normal(k[1], (D, cfg.kv_dim), 0.02),
                           wv=normal(k[2], (D, cfg.kv_dim), 0.02))
            if cfg.qk_norm:
                out["q_ln"] = jnp.ones((cfg.d_head,), jnp.float32)
                out["k_ln"] = jnp.ones((cfg.d_head,), jnp.float32)
            if cfg.diff_attn:
                # The published init: the four lambda vectors normal(0,
                # 0.1), the pair's norm at one, zero biases.
                wide = cfg.q_dim + (2 * cfg.kv_dim if own_kv else 0)
                out.update(
                    bqkv=jnp.zeros((wide,), cfg.dtype),
                    bo=jnp.zeros((D,), cfg.dtype),
                    lam=_normal(jax.random.fold_in(k[3], 1), (4, cfg.d_head), 0.1,
                                jnp.float32),
                    sub_ln=jnp.ones((2 * cfg.d_head,), jnp.float32))
            return out

        def cross(k):
            return attn(k, own_kv=False)

        def ssm1(k):
            # The family's init: -A over 1..d_state a channel, dt
            # log-uniform over the published time-step range, D at one.
            sm = cfg.ssm1
            k = jax.random.split(k, 6)
            dt = jnp.exp(jax.random.uniform(
                k[4], (sm.inner,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return {
                **ln("ln1"),
                "w_in": normal(k[0], (D, 2 * sm.inner), 0.02),
                "conv_w": normal(k[1], (sm.taps, sm.inner), sm.taps ** -0.5),
                "conv_b": jnp.zeros((sm.inner,), cfg.dtype),
                "w_x": normal(k[2], (sm.inner, sm.x_dim), sm.inner ** -0.5),
                "w_dt": normal(k[3], (sm.dt_rank, sm.inner),
                               sm.dt_rank ** -0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, sm.d_state + 1, dtype=jnp.float32)[:, None],
                    sm.state_shape)),
                "D": jnp.ones((sm.inner,), jnp.float32),
                "w_out": normal(k[5], (sm.inner, D), res_std),
            }

        def gmu(k):
            k = jax.random.split(k, 2)
            return {
                **ln("ln1"),
                "w_in": normal(k[0], (D, cfg.ssm1.inner), 0.02),
                "w_out": normal(k[1], (cfg.ssm1.inner, D), res_std),
            }

        def eva(k):
            # phi spreads a chunk's weights (k . phi of the order of 1), mu
            # is of a pooled key's size.
            kp, km = jax.random.split(jax.random.fold_in(k, 1))
            hd = (cfg.n_kv_heads, cfg.d_head)
            return {**attn(k), "phi": normal(kp, hd, 1.0),
                    "mu": normal(km, hd, 0.02 * D ** 0.5)}

        def conv(k):
            k = jax.random.split(k, 3)
            return {
                "ln1": jnp.ones((D,), jnp.float32),
                "w_in": normal(k[0], (D, 3 * D), 0.02),
                "w_conv": normal(k[1], (cfg.conv_taps, D),
                                 cfg.conv_taps ** -0.5),
                "w_out": normal(k[2], (D, D), res_std),
            }

        def ssm(k):
            # The published init: -A over 1-16, dt log-uniform over the
            # published time-step range, D at one.
            sm = cfg.ssm
            k = jax.random.split(k, 5)
            dt = jnp.exp(jax.random.uniform(
                k[3], (sm.n_heads,), jnp.float32,
                jnp.log(1e-3), jnp.log(1e-1)))
            # (A parallel layer's one norm is its attention leaves' ln1.)
            own_norm = {} if "parallel" in cfg.layer_types else {
                "ln1": jnp.ones((D,), jnp.float32)}
            return {
                **own_norm,
                "w_in": normal(k[0], (D, sm.in_dim), 0.02),
                "conv_w": normal(k[1], (sm.taps, sm.conv_dim),
                                 sm.taps ** -0.5),
                "conv_b": jnp.zeros((sm.conv_dim,), cfg.dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    k[4], (sm.n_heads,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((sm.n_heads,), jnp.float32),
                "norm": jnp.ones((sm.inner,), jnp.float32),
                "w_out": normal(k[2], (sm.inner, D), res_std),
            }

        def dense(k):
            k = jax.random.split(k, 3)
            return {
                **ln("ln2"),
                "w1": normal(k[0], (D, cfg.d_ff), 0.02),
                "w3": normal(k[1], (D, cfg.d_ff), 0.02),
                "w2": normal(k[2], (cfg.d_ff, D), res_std),
            }

        def expert(k):
            k_r, k_e, k_s = jax.random.split(k, 3)
            De = ex.latent or D      # the width the routed experts see

            def one(k):
                k = jax.random.split(k, 3)
                w = {"we1": normal(k[0], (De, ex.width), 0.02),
                     "we3": normal(k[1], (De, ex.width), 0.02),
                     "we2": normal(k[2], (ex.width, De), res_std)}
                return tuple(w[n] for n in ex.leaves)

            out = {
                "ln2": jnp.ones((D,), jnp.float32),
                "router": normal(k_r, (D, ex.n_experts), 0.02),
                **dict(zip(ex.leaves, lax.map(
                    one, jax.random.split(k_e, ex.held)))),
            }
            if ex.latent:
                k_d, k_u = jax.random.split(jax.random.fold_in(k_r, 2))
                out.update(w_down=normal(k_d, (D, De), 0.02),
                           w_up=normal(k_u, (De, D), 0.02))
            if ex.corrected:
                # Of the order of the gaps between neighbouring scores at
                # the top: a sigmoid's lie some twenty times wider apart
                # than a softmax's over as many experts.
                std = ROUTER_BIAS_STD * (20 if ex.scoring == "sigmoid" else 1)
                out["router_bias"] = _normal(
                    jax.random.fold_in(k_r, 1), (ex.n_experts,), std,
                    jnp.float32)
            if ex.shared_width:
                s = jax.random.split(k_s, 3)
                out.update(
                    ws1=normal(s[0], (D, ex.shared_width), 0.02),
                    ws2=normal(s[2], (ex.shared_width, D), res_std))
                if ex.gated:
                    out.update(
                        ws3=normal(s[1], (D, ex.shared_width), 0.02))
            return out

        out = {
            "embed": normal(ks[0], (cfg.vocab_size, D), 0.02),
            **ln("ln_f"),
        }
        if not cfg.tied_head:
            out["wout"] = normal(
                ks[1], (D, cfg.pred_heads * cfg.vocab_size), 0.02)
        n_dense = cfg.n_dense_layers
        types = cfg.layer_types or ()
        for name, make_one, n, k in (
                ("attn", attn, cfg.cache_layers - cfg.eva_layers, ks[2]),
                ("eva", eva, cfg.eva_layers, jax.random.fold_in(ks[2], 2)),
                ("wattn", attn, cfg.window_layers,
                 jax.random.fold_in(ks[2], 1)),
                ("xattn", cross, types.count("cross"),
                 jax.random.fold_in(ks[2], 3)),
                ("conv", conv, cfg.conv_layers, ks[3]),
                ("ssm", ssm, cfg.ssm_layers - types.count("ssm1"),
                 jax.random.fold_in(ks[3], 1)),
                ("ssm1", ssm1, types.count("ssm1"),
                 jax.random.fold_in(ks[3], 2)),
                ("gmu", gmu, types.count("gmu"),
                 jax.random.fold_in(ks[3], 3)),
                ("dense", dense, n_dense, ks[4]),
                ("layers", expert, cfg.n_expert_layers, ks[5])):
            if n:
                out[name] = lax.map(make_one, jax.random.split(k, n))
        return out

    return make(key, cfg)
