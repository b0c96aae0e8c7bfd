"""Layers of several kinds in one model: the gated short-convolution mixer,
its state as a two-row tail of each pool block, and the layer loop over runs
of layers of one kind.

A layer is a mixer and a feed-forward half. The mixer is rotary-GQA attention
(:func:`~.decode.gqa_mixer`, the dense block's own: over the whole context,
or, a ``window`` layer, over the last ``cfg.window`` positions, its rows in a
pool and under a table of their own, :class:`~.decode.PagedWindowCache`) or a
gated short convolution (:func:`conv_mixer`); the feed-forward half the dense
SwiGLU or the
routed-expert layer (:func:`~.experts.expert_layer`). ``cfg.layer_types`` and
``cfg.moe.first_dense`` say which layer is what; :func:`hybrid_layers` cuts the
depth into runs of consecutive layers of one (mixer, feed-forward) kind and
scans each run under one body, with no branch on a layer's kind in the traced
program. Every per-kind stack rides whole (the K/V pools, the tail pool, the
experts' one stack, each kind's weights on a leading axis of ITS layers) and a
layer's part is reached by offset: attention layer ``a`` at ``table + a·N``,
window layer ``w`` at ``wtable + w·Nw``, conv layer ``c`` at ``table + c·N``,
expert layer ``e`` at ``e·held``.

The conv mixer, for the normed residual ``h``::

    [b | c | u] = h · W_in          (D -> 3D, split in that order)
    z = b ⊙ u
    s_t = Σ_k w_k ⊙ z_{t-2+k}        (depthwise, causal, 3 taps; z = 0 before
                                     the sequence's start)
    y = (c ⊙ s) · W_out

Its state at position ``t`` is ``z_{t-1}``, ``z_{t-2}``
(:class:`~.decode.PagedHybridCache` says where they live and why).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax


from tree_attention_tpu.models.decode import (
    PagedHybridCache,
    PagedWindowCache,
    _Attend,
    _RowGroup,
    _join_rows,
    gqa_mixer,
)
from tree_attention_tpu.models.transformer import (
    Params,
    TransformerConfig,
    _mlp_block,
    rms_norm,
)
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


def conv_mixer(layer: Params, x: jax.Array, tail: jax.Array, c,
               groups: Tuple[_RowGroup, ...], cfg: TransformerConfig,
               block: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The gated short convolution over every group of the step's rows.
    ``layer``: this layer's leaves (``ln1`` ``(D,)``, ``w_in`` ``(D, 3D)``,
    ``w_conv`` ``(3, D)`` with tap ``k`` on ``z_{t-2+k}``, ``w_out`` ``(D,
    D)``); ``tail`` the WHOLE tail pool ``(conv layers, N, 2·D)``, this
    layer's entries reached by offset ``c·N``. A group reads the two rows
    before its members' first positions once, convolves inside its rows by
    shifts and writes each touched block's tail. Returns the residual with
    the mixer's output added, the pool, and the block tails written."""
    D = cfg.d_model
    h = rms_norm(x, layer["ln1"], cfg.norm_eps)
    bcu = h @ layer["w_in"]
    z = bcu[..., :D] * bcu[..., 2 * D:]
    w = layer["w_conv"].astype(jnp.float32)
    n_blocks = tail.shape[1]
    flat = tail.reshape(-1, 2 * D)
    outs, wrote = [], jnp.int32(0)
    for g in groups:
        zg = g.take(z[:, None])[:, 0]                     # (batch, tq, D)
        before = [_tail_rows(flat, g, c, back, n_blocks, block)[:, None]
                  for back in (2, 1)]
        zz = jnp.concatenate(before + [zg], axis=1).astype(jnp.float32)
        s = sum(w[k] * zz[:, k:k + g.tq] for k in range(3))
        outs.append(s.astype(x.dtype)[:, None])
        flat, n = _tail_write(flat, zg, g, c, n_blocks, block)
        wrote = wrote + n
    s = _join_rows(groups, outs)[:, 0]
    y = (bcu[..., D:2 * D] * s) @ layer["w_out"]
    return x + y, flat.reshape(tail.shape), wrote


def layer_runs(cfg: TransformerConfig) -> List[Tuple[str, str, int, int, int]]:
    """The depth cut into runs of consecutive layers of one kind:
    ``(mixer, ffn, layers, first of its mixer kind, first of its ffn
    kind)``, the two offsets counted among the layers of that kind."""
    types = cfg.layer_types or ("attention",) * cfg.n_layers
    runs: List[list] = []
    seen = {"attention": 0, "window": 0, "conv": 0, "dense": 0, "expert": 0}
    for l, mixer in enumerate(types):
        ffn = "dense" if l < cfg.n_dense_layers else "expert"
        if runs and runs[-1][:2] == [mixer, ffn]:
            runs[-1][2] += 1
        else:
            runs.append([mixer, ffn, 1, seen[mixer], seen[ffn]])
        seen[mixer] += 1
        seen[ffn] += 1
    return [tuple(r) for r in runs]


def hybrid_layers(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cache: Union[PagedHybridCache, PagedWindowCache],
    cfg: TransformerConfig,
    attend: _Attend,
    stats: Optional[Dict[str, Any]],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Every layer of a step for a model whose layers are of several kinds
    (:func:`~.decode._step_layers`' third branch): one body a (mixer,
    feed-forward) kind, a run of consecutive layers of one kind under one
    ``lax.scan`` (a run of one layer is called as it is), the K/V pools, the
    tail pool and the residual the carry of them all. ``params`` holds a
    stack a kind on a leading axis of that kind's layers: ``attn``
    (``ln1``, ``wq``, ``wk``, ``wv``, ``wo``, with QK-norm ``q_ln`` /
    ``k_ln``), ``wattn`` (the window layers: the same leaves), ``conv``
    (:func:`conv_mixer`'s leaves), ``dense`` (``ln2``,
    ``w1``, ``w3``, ``w2``) and ``layers`` (the expert layers: ``ln2``,
    ``router``, ``router_bias``, ``we1`` / ``we3`` / ``we2`` and the shared
    experts' ``ws*``). Returns the residual and the pools by field name."""
    from tree_attention_tpu.models.experts import (
        EXPERT_LEAVES, expert_layer, held_counts,
    )

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
    valid = groups[0].valid
    if groups[0].lo is not None:
        valid = jnp.concatenate([g.valid.reshape(-1) for g in groups])[None]
    experts = routers = None
    if cfg.n_layers > cfg.n_dense_layers:
        # Every layer's experts as ONE stack (a bitcast), a layer's reached
        # by offset: sliced out, the kernel would be handed a copy.
        stack = params["layers"]
        experts = tuple(stack[n].reshape((-1,) + stack[n].shape[2:])
                        for n in EXPERT_LEAVES)
        routers = {n: a for n, a in stack.items() if n not in EXPERT_LEAVES}

    def of(stack, i):
        """Layer ``i`` of a kind's stack: ``i`` a Python int (a run of
        one) or the scan's index."""
        if isinstance(i, int):
            return jax.tree.map(lambda a: a[i], stack)
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), stack)

    def body_of(mixer, ffn, m0, f0):
        def body(carry, i):
            x, k, v, tail, wk, wv = carry
            mi, fi = m0 + i, f0 + i
            wrote = jnp.int32(0)
            if mixer == "attention":
                x, k, v, _, _ = gqa_mixer(
                    attend, of(params["attn"], mi), x, positions, k, v,
                    None, None, None, mi, mi * N)
            elif mixer == "window":
                x, wk, wv, _, _ = gqa_mixer(
                    attend_w, of(params["wattn"], mi), x, positions, wk, wv,
                    None, None, None, mi, mi * Nw)
            else:
                with jax.named_scope(scopes.CONV):
                    x, tail, wrote = conv_mixer(
                        of(params["conv"], mi), x, tail, mi, groups, cfg,
                        block)
            if ffn == "dense":
                with jax.named_scope(scopes.FFN):
                    layer = of(params["dense"], fi)
                    x = x + _mlp_block(
                        layer, rms_norm(x, layer["ln2"], cfg.norm_eps))
                return (x, k, v, tail, wk, wv), (None, wrote)
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
                return (x + y, k, v, tail, wk, wv), (
                    held_counts(chosen, valid, cfg.moe), wrote)

        return body

    # A pool the cache has not is None: an empty part of the carry.
    carry = (x, cache.k, cache.v, getattr(cache, "tail", None),
             getattr(cache, "wk", None), getattr(cache, "wv", None))
    counts, wrote = [], jnp.int32(0)
    for mixer, ffn, n, m0, f0 in layer_runs(cfg):
        body = body_of(mixer, ffn, m0, f0)
        if n == 1:
            carry, (rows, w) = body(carry, 0)
            rows = None if rows is None else rows[None]
        else:
            carry, (rows, w) = lax.scan(
                body, carry, jnp.arange(n, dtype=jnp.int32))
        if rows is not None:
            counts.append(rows)
        wrote = wrote + jnp.sum(w)
    if stats is not None:
        if counts:
            stats["expert_rows"] = jnp.concatenate(counts, axis=0)
        if cfg.conv_layers:
            stats["tail_blocks"] = wrote
    x, k, v, tail, wk, wv = carry
    if cfg.window_layers:
        return x, {"k": k, "v": v, "wk": wk, "wv": wv}
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

        def attn(k):
            k = jax.random.split(k, 4)
            out = {
                "ln1": jnp.ones((D,), jnp.float32),
                "wq": normal(k[0], (D, cfg.q_dim), 0.02),
                "wk": normal(k[1], (D, cfg.kv_dim), 0.02),
                "wv": normal(k[2], (D, cfg.kv_dim), 0.02),
                "wo": normal(k[3], (cfg.q_dim, D), res_std),
            }
            if cfg.qk_norm:
                out["q_ln"] = jnp.ones((cfg.d_head,), jnp.float32)
                out["k_ln"] = jnp.ones((cfg.d_head,), jnp.float32)
            return out

        def conv(k):
            k = jax.random.split(k, 3)
            return {
                "ln1": jnp.ones((D,), jnp.float32),
                "w_in": normal(k[0], (D, 3 * D), 0.02),
                "w_conv": normal(k[1], (cfg.conv_taps, D),
                                 cfg.conv_taps ** -0.5),
                "w_out": normal(k[2], (D, D), res_std),
            }

        def dense(k):
            k = jax.random.split(k, 3)
            return {
                "ln2": jnp.ones((D,), jnp.float32),
                "w1": normal(k[0], (D, cfg.d_ff), 0.02),
                "w3": normal(k[1], (D, cfg.d_ff), 0.02),
                "w2": normal(k[2], (cfg.d_ff, D), res_std),
            }

        def expert(k):
            k_r, k_e, k_s = jax.random.split(k, 3)

            def one(k):
                k = jax.random.split(k, 3)
                return (normal(k[0], (D, ex.width), 0.02),
                        normal(k[1], (D, ex.width), 0.02),
                        normal(k[2], (ex.width, D), res_std))

            we1, we3, we2 = lax.map(one, jax.random.split(k_e, ex.held))
            out = {
                "ln2": jnp.ones((D,), jnp.float32),
                "router": normal(k_r, (D, ex.n_experts), 0.02),
                "we1": we1, "we3": we3, "we2": we2,
            }
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
                    ws3=normal(s[1], (D, ex.shared_width), 0.02),
                    ws2=normal(s[2], (ex.shared_width, D), res_std))
            return out

        out = {
            "embed": normal(ks[0], (cfg.vocab_size, D), 0.02),
            "ln_f": jnp.ones((D,), jnp.float32),
        }
        if not cfg.tied_head:
            out["wout"] = normal(ks[1], (D, cfg.vocab_size), 0.02)
        n_dense = cfg.n_dense_layers
        for name, make_one, n, k in (
                ("attn", attn, cfg.cache_layers, ks[2]),
                ("wattn", attn, cfg.window_layers,
                 jax.random.fold_in(ks[2], 1)),
                ("conv", conv, cfg.conv_layers, ks[3]),
                ("dense", dense, n_dense, ks[4]),
                ("layers", expert, cfg.n_layers - n_dense, ks[5])):
            if n:
                out[name] = lax.map(make_one, jax.random.split(k, n))
        return out

    return make(key, cfg)
