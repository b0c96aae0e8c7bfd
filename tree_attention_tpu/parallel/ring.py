"""Ring attention: the comparator baseline for the tree reduction.

Tree attention's headline claim (BASELINE.json north star, and the paper the
reference reimplements) is measured *against ring attention*, so the framework
carries an honest, non-strawman ring implementation (SURVEY.md §7 hard part 4):
Q, K, V all sequence-sharded, KV shards rotated around the mesh's ``seq`` axis
with ``lax.ppermute`` while every device accumulates online-softmax partial
state against its resident Q block. N-1 permute steps of O(local KV) payload
each — the O(N) latency chain the tree merge's O(log N) collectives are
positioned against.

Not a strawman because:

- the next KV block's ``ppermute`` is issued *before* the current block's
  attention compute, so XLA's latency-hiding scheduler can overlap
  communication with the flash kernel (the standard ring-attention trick);
- the per-step kernel is the same :func:`flash_attention
  <tree_attention_tpu.ops.flash_attention>` the tree path uses — both sides of
  the benchmark run identical local math;
- the merge is the same safe-softmax monoid, carried as running
  ``(max, numerator, denominator)`` in float32.

Differentiable end-to-end: ``ppermute`` transposes to the inverse permutation
and the scan transposes to a reverse-order scan, so the backward pass is
itself a ring rotation — no custom VJP needed.

The reference contains no ring code (tree attention is positioned against it,
SURVEY.md §2.4); this module exists so the benchmark's "vs ring" number is
produced by this framework rather than assumed.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tree_attention_tpu import obs
from tree_attention_tpu.ops import flash_attention, resolve_impl_for_mesh
from tree_attention_tpu.ops.reference import NEG_INF, finalize_merge
from tree_attention_tpu.parallel.accounting import (
    account_payload as _account_payload,
    shard_counts as _shard_counts,
)
from tree_attention_tpu.parallel.mesh import AXIS_SEQ


def _merge_step(
    m: jax.Array, num: jax.Array, den: jax.Array,
    out_b: jax.Array, lse_b: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fold one block's ``(out, lse)`` into running ``(m, num, den)`` state.

    The same safe-softmax monoid as the tree merge
    (:func:`tree_attention_tpu.ops.reference.merge_partials`), specialised to
    a running left fold. ``m`` may be ``-inf`` (no visible keys yet) — the
    stabilising shift is clamped to 0 there so ``exp(-inf - 0) = 0`` and the
    empty side drops out without NaNs.
    """
    m_new = jnp.maximum(m, lse_b)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.exp(m - m_safe)
    beta = jnp.exp(lse_b - m_safe)
    num_new = num * alpha[..., None] + out_b.astype(jnp.float32) * beta[..., None]
    den_new = den * alpha + beta
    return m_new, num_new, den_new


def ring_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    seq_axis: str = AXIS_SEQ,
    data_axis: Optional[str] = None,
    head_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    q_position: Optional[int] = None,
    impl: str = "auto",
    block_size: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Replicated-Q decode via an N−1-hop ring merge — the O(N) comparator
    for :func:`tree_decode <tree_attention_tpu.parallel.tree.tree_decode>`'s
    O(log N) collective merge on the decode shape.

    Decode is the reference's entire workload
    (``/root/reference/model.py:140-145``: one query token against a long
    sequence-sharded KV buffer), and the shape where the two families'
    communication *depth* differs most starkly: the local compute is
    identical (same kernel, same per-shard ``(out, lse)`` partial — KV
    never moves in either family), so the whole contest is the merge. Tree
    merges with one ``pmax`` + one ``psum`` (log-depth, XLA's ICI
    collectives); this ring instead rotates each device's partial around
    the ``seq_axis`` with ``lax.ppermute`` — N−1 *sequential* hops, each a
    full O(B·H·Tq·(D+1)) payload — folding arrivals into the running
    safe-softmax state (:func:`_merge_step`, the same monoid). Every
    device sees all N partials after N−1 hops, so the result lands
    replicated, the same contract tree's psum provides.

    Not a strawman: rotating *partials* is the cheapest honest ring for
    this shape — rotating KV shards instead (the training-shape pattern)
    would move O(T/N·Hkv·D) per hop for no benefit when Q is already
    replicated. The hop loop is unrolled (N is a mesh axis, known at
    trace time), which both keeps every hop visible to the compiler's
    latency scheduler and makes the collective count auditable in the
    compiled HLO (``bench/comm.py``).

    Same signature and sharding contract as ``tree_decode``.
    """
    Tk_global = k.shape[2]
    Tq = q.shape[2]
    if q_position is None:
        q_position = Tk_global - Tq
    n_shards = mesh.shape[seq_axis]
    if Tk_global % n_shards:
        raise ValueError(
            f"global KV length {Tk_global} must divide over {n_shards} "
            f"'{seq_axis}' shards"
        )
    Tk_local = Tk_global // n_shards
    impl = resolve_impl_for_mesh(impl, mesh)

    q_spec = P(data_axis, head_axis, None, None)
    kv_spec = P(data_axis, head_axis, seq_axis, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=(q_spec, P(data_axis, head_axis, None)),
        check_vma=False,
    )
    def _sharded(q_l, k_l, v_l):
        # The mesh axis size is static at trace time; closing over it (vs
        # lax.axis_size, which moved API homes across JAX versions) keeps
        # the unrolled hop count visibly constant.
        n = n_shards
        me = lax.axis_index(seq_axis)
        out_b, lse_b = flash_attention(
            q_l, k_l, v_l,
            causal=causal, scale=scale,
            q_offset=q_position, kv_offset=me * Tk_local,
            impl=impl, block_size=block_size,
        )
        # Seed the running state with the resident partial, then rotate the
        # partials: after hop j each device folds the partial originally
        # computed n−j hops upstream. The monoid is commutative, so every
        # device converges to the same merged result in n−1 hops.
        m0 = jnp.full(lse_b.shape, NEG_INF, jnp.float32)
        num0 = jnp.zeros(out_b.shape, jnp.float32)
        den0 = jnp.zeros(lse_b.shape, jnp.float32)
        m, num, den = _merge_step(m0, num0, den0, out_b, lse_b)
        perm = [(i, (i + 1) % n) for i in range(n)]
        rot_o, rot_l = out_b, lse_b
        for _ in range(n - 1):
            rot_o = lax.ppermute(rot_o, seq_axis, perm)
            rot_l = lax.ppermute(rot_l, seq_axis, perm)
            m, num, den = _merge_step(m, num, den, rot_o, rot_l)
        return finalize_merge(num, den, m, q.dtype)

    # N−1 sequential partial rotations, each the (out, lse) pair — the
    # O(N)-depth chain the tree merge's log-depth collectives are raced
    # against; like tree_decode's merge, context-independent. Per-device:
    # global batch/head dims divide over any data/model axes.
    d_sh, h_sh = _shard_counts(mesh, data_axis, head_axis)
    rows = -(-q.shape[0] // d_sh) * -(-q.shape[1] // h_sh) * Tq
    hops = mesh.shape[seq_axis] - 1
    _account_payload(
        "ring_decode",
        ppermute=hops * rows * (q.dtype.itemsize * q.shape[3] + 4),
    )
    with obs.span("ring_decode", cat="dispatch",
                  args=None if not obs.TRACER.active else
                  {"ctx": Tk_global, "hops": hops}):
        return _sharded(q, k, v)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    seq_axis: str = AXIS_SEQ,
    data_axis: Optional[str] = None,
    head_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    q_position: Optional[int] = None,
    impl: str = "auto",
    block_size: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fully sequence-sharded exact attention via KV ring rotation.

    Same contract and sharding as :func:`tree_attention
    <tree_attention_tpu.parallel.tree.tree_attention>` — ``q/k/v`` of shapes
    ``(B, Hq, T, D)`` / ``(B, Hkv, T, D)`` sharded along dim 2 over
    ``seq_axis`` — but the communication pattern is the O(N)-step ring the
    tree reduction is benchmarked against.

    Returns:
      ``(out, lse)`` sharded like ``q``.
    """
    B, Hq, Tq_global, D = q.shape
    if q_position is None:
        q_position = k.shape[2] - Tq_global
    n_shards = mesh.shape[seq_axis]
    if Tq_global % n_shards or k.shape[2] % n_shards:
        raise ValueError(
            f"sequence lengths (q={Tq_global}, k={k.shape[2]}) must divide "
            f"over {n_shards} '{seq_axis}' shards"
        )
    Tq_local = Tq_global // n_shards
    Tk_local = k.shape[2] // n_shards
    impl = resolve_impl_for_mesh(impl, mesh)

    spec = P(data_axis, head_axis, seq_axis, None)
    lse_spec = P(data_axis, head_axis, seq_axis)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, lse_spec),
        check_vma=False,
    )
    def _sharded(q_l, k_l, v_l):
        n = n_shards  # static mesh axis size (see ring_decode)
        me = lax.axis_index(seq_axis)
        # Send my block to the next device; after step j I hold the KV shard
        # originally resident on device (me - j) mod n.
        perm = [(i, (i + 1) % n) for i in range(n)]
        Hq_l, Tq_l = q_l.shape[1], q_l.shape[2]
        q_off = q_position + me * Tq_local

        m0 = jnp.full((q_l.shape[0], Hq_l, Tq_l), NEG_INF, jnp.float32)
        num0 = jnp.zeros(q_l.shape[:3] + (D,), jnp.float32)
        den0 = jnp.zeros_like(m0)

        def attend(k_cur, v_cur, step, m, num, den):
            src = (me - step) % n
            out_b, lse_b = flash_attention(
                q_l, k_cur, v_cur,
                causal=causal, scale=scale,
                q_offset=q_off,
                kv_offset=src * Tk_local,
                impl=impl, block_size=block_size,
            )
            return _merge_step(m, num, den, out_b, lse_b)

        def body(carry, step):
            k_cur, v_cur, m, num, den = carry
            # Issue the rotation for the *next* step first: the permute has no
            # data dependency on this step's attention, so XLA can overlap the
            # ICI transfer with the kernel.
            k_nxt = lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = lax.ppermute(v_cur, seq_axis, perm)
            m, num, den = attend(k_cur, v_cur, step, m, num, den)
            return (k_nxt, v_nxt, m, num, den), None

        # n-1 rotate-and-attend steps, then the last resident block with no
        # trailing (wasted) permute — the ring does exactly n-1 transfers.
        (k_last, v_last, m, num, den), _ = lax.scan(
            body, (k_l, v_l, m0, num0, den0), jnp.arange(n - 1)
        )
        m, num, den = attend(k_last, v_last, n - 1, m, num, den)
        return finalize_merge(num, den, m, q.dtype)

    # N−1 KV-shard rotations of the local (k, v) pair per step (per-device:
    # batch/head dims divided over any data/model axes).
    d_sh, h_sh = _shard_counts(mesh, data_axis, head_axis)
    _account_payload(
        "ring_attention",
        ppermute=(n_shards - 1) * 2 * -(-B // d_sh) * -(-k.shape[1] // h_sh)
        * Tk_local * D * k.dtype.itemsize,
    )
    with obs.span("ring_attention", cat="dispatch",
                  args=None if not obs.TRACER.active else
                  {"seq": Tq_global, "hops": n_shards - 1}):
        return _sharded(q, k, v)
