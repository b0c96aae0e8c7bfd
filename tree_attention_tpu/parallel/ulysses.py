"""Ulysses (all-to-all head-swap) sequence parallelism.

The third sequence-parallel family, alongside the tree reduction
(:mod:`tree_attention_tpu.parallel.tree`) and the ring comparator
(:mod:`tree_attention_tpu.parallel.ring`). The reference implements none of
them but positions tree against ring (SURVEY.md §2.4); Ulysses is included
because the three families trade communication *shape*, and a framework
claiming the sequence-parallel capability should let the deployment pick:

- **tree**: KV stay resident; Q rides a chunked all-gather and the merge is
  an O(log N) collective of O(B·H·Tq·D) safe-softmax partials. Best when
  the merge payload is small relative to KV (decode, GQA).
- **ring**: KV shards rotate N−1 hops of O(local KV) each, overlapped with
  compute. Latency chain O(N), payload KV-only.
- **ulysses** (this module): ONE ``all_to_all`` re-shards sequence→heads,
  each device runs *full-sequence* attention for ``H/N`` heads with the
  plain single-device kernel (no cross-device softmax state at all), and
  one ``all_to_all`` re-shards the output back. Payload is Q+K+V+O (not
  KV-only), but the collective count is constant and the local kernel sees
  the whole sequence — no per-shard masking geometry, no merge monoid.
  Requires ``Hq % N == 0`` and ``Hkv % N == 0``.

Differentiable end-to-end: ``all_to_all`` transposes to the inverse
``all_to_all``, and the local kernel is the custom-VJP
:func:`tree_attention_tpu.ops.flash_attention`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tree_attention_tpu import obs
from tree_attention_tpu.ops import flash_attention, resolve_impl_for_mesh
from tree_attention_tpu.parallel.accounting import (
    account_payload as _account_payload,
    shard_counts as _shard_counts,
)
from tree_attention_tpu.parallel.mesh import AXIS_SEQ


def ulysses_decode(
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
    """Replicated-Q decode via the Ulysses head-swap — the third entry in
    the decode-shape comparator (VERDICT r3 item 1).

    Same contract as :func:`tree_decode
    <tree_attention_tpu.parallel.tree.tree_decode>` and :func:`ring_decode
    <tree_attention_tpu.parallel.ring.ring_decode>`: Q ``(B, Hq, Tq, D)``
    replicated over ``seq_axis``, K/V ``(B, Hkv, Tk, D)`` sequence-sharded
    along dim 2; returns ``(out, lse)`` replicated.

    The family's communication shape is what makes this entry interesting:
    from a sequence-sharded cache, each decode step must ``all_to_all``
    the **entire KV buffer** (seq-sharding → head-sharding, O(Tk·Hkv·D/N)
    bytes per device) before the purely local full-context kernel runs,
    then ``all_gather`` the O(B·Hq·Tq·D) head-slice outputs. Tree and ring
    move O(B·H·Tq·D) *independent of context length*; Ulysses' per-step
    wire volume grows linearly with the context — the founding claim of
    the tree merge, made measurable (``bench/comm.py`` counts both).
    Requires ``Hq % N == 0`` and ``Hkv % N == 0``.
    """
    B, Hq, Tq, D = q.shape
    Hkv, Tk_global = k.shape[1], k.shape[2]
    if q_position is None:
        q_position = Tk_global - Tq
    n = mesh.shape[seq_axis]
    if Tk_global % n:
        raise ValueError(
            f"global KV length {Tk_global} must divide over {n} "
            f"'{seq_axis}' shards"
        )
    # Like ulysses_attention: with a head-parallel axis in play the
    # all-to-all splits the PER-SHARD head slice, so validate the local
    # counts, not the global ones.
    h_shards = mesh.shape[head_axis] if head_axis is not None else 1
    if Hq % h_shards or Hkv % h_shards:
        raise ValueError(
            f"heads (q={Hq}, kv={Hkv}) must divide over {h_shards} "
            f"'{head_axis}' shards"
        )
    if (Hq // h_shards) % n or (Hkv // h_shards) % n:
        raise ValueError(
            f"ulysses re-shards the head dim: per-shard heads "
            f"(q={Hq // h_shards}, kv={Hkv // h_shards}) must divide over "
            f"{n} '{seq_axis}' shards (use tree/ring decode for head "
            f"counts smaller than the mesh axis)"
        )
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
        me = lax.axis_index(seq_axis)
        # seq-sharded -> head-sharded: (B, Hkv, Tk/n, D) -> (B, Hkv/n, Tk, D).
        def to_heads(x):
            return lax.all_to_all(
                x, seq_axis, split_axis=1, concat_axis=2, tiled=True
            )

        kh, vh = to_heads(k_l), to_heads(v_l)
        # Q is replicated over seq (its head dim may still be head-sharded):
        # slice the resident seq-shard's head group from the LOCAL slice.
        g = q_l.shape[1] // n
        qh = lax.dynamic_slice_in_dim(q_l, me * g, g, axis=1)
        out_h, lse_h = flash_attention(
            qh, kh, vh, causal=causal, scale=scale,
            q_offset=q_position, kv_offset=0,
            impl=impl, block_size=block_size,
        )
        # Gather the head slices back to the replicated output contract.
        out = lax.all_gather(out_h, seq_axis, axis=1, tiled=True)
        lse = lax.all_gather(lse_h, seq_axis, axis=1, tiled=True)
        return out.astype(q.dtype), lse.astype(jax.numpy.float32)

    # The family's founding liability, counted: each step all-to-alls the
    # ENTIRE local KV buffer (O(Tk/N) per device — linear in context, where
    # tree/ring move O(B·H·Tq·D)), then gathers back the head-slice
    # (out, lse) partials. Per-device dims: batch over the data axis, heads
    # over the model axis (the seq axis divides KV tokens / head groups).
    d_sh, _ = _shard_counts(mesh, data_axis, None)
    B_l = -(-B // d_sh)
    g = (Hq // h_shards) // n
    _account_payload(
        "ulysses_decode",
        all_to_all=2 * B_l * (Hkv // h_shards) * (Tk_global // n) * D
        * k.dtype.itemsize,
        all_gather=B_l * g * Tq * (D * q.dtype.itemsize + 4),
    )
    with obs.span("ulysses_decode", cat="dispatch",
                  args=None if not obs.TRACER.active else
                  {"ctx": Tk_global, "shards": n}):
        return _sharded(q, k, v)


def ulysses_attention(
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
    """Sequence-sharded exact attention via the Ulysses head-swap.

    Same contract and sharding as :func:`tree_attention
    <tree_attention_tpu.parallel.tree.tree_attention>` and
    :func:`ring_attention <tree_attention_tpu.parallel.ring.ring_attention>`:
    ``q`` of shape ``(B, Hq, Tq, D)`` and ``k``/``v`` of ``(B, Hkv, Tk, D)``
    sharded along dim 2 over ``seq_axis``; returns ``(out, lse)`` sharded
    like ``q``. ``q_position`` is the global position of q's first row
    (default: suffix-aligned, ``Tk - Tq``).

    Head divisibility is a hard requirement of the family: the all-to-all
    re-shards the head dim, so both ``Hq`` and ``Hkv`` must divide by the
    shard count (use tree/ring otherwise — e.g. GQA with fewer KV heads
    than devices).
    """
    B, Hq, Tq_global, D = q.shape
    Hkv, Tk_global = k.shape[1], k.shape[2]
    if q_position is None:
        q_position = Tk_global - Tq_global
    n = mesh.shape[seq_axis]
    if Tq_global % n or Tk_global % n:
        raise ValueError(
            f"sequence lengths (q={Tq_global}, k={Tk_global}) must divide "
            f"over {n} '{seq_axis}' shards"
        )
    # The all-to-all splits each device's LOCAL head slice, so with a
    # head-parallel axis in play the requirement is on the per-shard head
    # count, not the global one.
    h_shards = mesh.shape[head_axis] if head_axis is not None else 1
    if Hq % h_shards or Hkv % h_shards:
        raise ValueError(
            f"heads (q={Hq}, kv={Hkv}) must divide over {h_shards} "
            f"'{head_axis}' shards"
        )
    if (Hq // h_shards) % n or (Hkv // h_shards) % n:
        raise ValueError(
            f"ulysses re-shards the head dim: per-shard heads "
            f"(q={Hq // h_shards}, kv={Hkv // h_shards}"
            f"{f' after {h_shards}-way head sharding' if h_shards > 1 else ''})"
            f" must divide over {n} '{seq_axis}' shards (use tree/ring "
            f"attention for head counts smaller than the mesh axis)"
        )
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
        # seq-sharded -> head-sharded: (B, H, T/n, D) -> (B, H/n, T, D).
        # One collective per tensor; afterwards each device owns the FULL
        # sequence for its head slice, so the local kernel needs no shard
        # offsets and no cross-device softmax state.
        def to_heads(x):
            return lax.all_to_all(
                x, seq_axis, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = to_heads(q_l), to_heads(k_l), to_heads(v_l)
        out_h, lse_h = flash_attention(
            qh, kh, vh, causal=causal, scale=scale,
            q_offset=q_position, kv_offset=0,
            impl=impl, block_size=block_size,
        )
        # head-sharded -> seq-sharded: (B, H/n, T, D) -> (B, H, T/n, D),
        # and the (B, H/n, T) lse likewise.
        out_l = lax.all_to_all(
            out_h, seq_axis, split_axis=2, concat_axis=1, tiled=True
        )
        lse_l = lax.all_to_all(
            lse_h, seq_axis, split_axis=2, concat_axis=1, tiled=True
        )
        return out_l.astype(q.dtype), lse_l.astype(jax.numpy.float32)

    # Five all-to-alls per step: Q/K/V seq→head, then (out, lse) back.
    # Per-device dims: batch over the data axis, heads over the model axis.
    d_sh, _ = _shard_counts(mesh, data_axis, None)
    B_l = -(-B // d_sh)
    itm = q.dtype.itemsize
    _account_payload(
        "ulysses_attention",
        all_to_all=(
            B_l * (Hq // h_shards) * (Tq_global // n) * D * itm      # q
            + 2 * B_l * (Hkv // h_shards) * (Tk_global // n) * D * itm  # k, v
            + B_l * (Hq // h_shards) * (Tq_global // n) * (D * itm + 4)  # out, lse
        ),
    )
    with obs.span("ulysses_attention", cat="dispatch",
                  args=None if not obs.TRACER.active else
                  {"seq": Tq_global, "shards": n}):
        return _sharded(q, k, v)
