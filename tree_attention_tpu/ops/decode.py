"""Split-KV flash decode: the q_len≈1 inference shape, parallelised over KV.

Decode is the reference's entire workload (``/root/reference/model.py:140-145``:
one query token against a 64k-token KV), and it is the shape where a plain
blockwise scan is weakest on TPU: with Tq=1 each KV block contributes one tiny
matvec, and a sequential ``lax.scan`` over blocks serialises what is really a
bandwidth-bound reduction. The standard fix (flash-decode / split-KV) is to
cut KV into S independent chunks, compute per-chunk partial ``(out, lse)`` in
parallel — XLA maps the ``vmap`` over chunks onto parallel work — and combine
with the same safe-softmax monoid the tree reduction uses
(:func:`~tree_attention_tpu.ops.reference.merge_partials`). The split is the
single-device mirror of the cross-device tree merge: same math, chunks instead
of mesh shards.

Masking is uniformly causal-with-offsets: a query at global position
``q_position + i`` sees keys at global positions ``<= q_position + i``. A
padded or partially-filled KV buffer (a cache of capacity Tmax holding
``length`` valid tokens) needs no separate length mask — pass
``q_position = length - Tq`` and every slot ``>= length`` is in the masked
future.
"""

from __future__ import annotations

import numbers
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tree_attention_tpu import obs
from tree_attention_tpu.ops.block_utils import WindowRule, pad_to_block
from tree_attention_tpu.ops.reference import attention_blockwise, merge_partials
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("ops")

# Dispatch accounting (trace-time under an enclosing jit — see
# obs.metrics): which decode path served the call. Execution-true token
# totals live in the host loops (bench/harness.py).
_DECODE_DISPATCH = obs.counter(
    "decode_dispatch_total",
    "flash_decode dispatches by kernel path (trace-time under jit)",
    labels=("path",),
)


# Paths of ``_account_dispatch`` that do NOT run a Pallas kernel.
_REFERENCE_PATHS = ("chunked_vmap", "paged_local_partial_reference",
                    "mla_paged_reference")


def _account_dispatch(path: str, kv_tokens: int) -> None:
    """Make the kernel-or-reference choice visible: one debug line per
    dispatch resolution (trace time under jit, so once per program build)
    and, when the registry is armed, the dispatch counters."""
    log.debug(
        "decode dispatch: %s (%s, kv_tokens=%d)", path,
        "reference path" if path in _REFERENCE_PATHS else "Pallas kernel",
        kv_tokens,
    )
    if not obs.REGISTRY.enabled:
        return
    _DECODE_DISPATCH.labels(path=path).inc()


def default_num_splits(kv_len: int, block_size: int) -> int:
    """Enough chunks to expose parallelism, never smaller than one block.

    The cap scales with context: a flat 16 under-parallelises the
    chunked-vmap path at 256k+ tokens (16 chunks of 16k+ each serialise
    inside one ``lax.scan`` apiece), so beyond 256k tokens the cap grows
    linearly — one extra chunk per 16k tokens — while short contexts keep
    the measured 16-way default.
    """
    cap = max(16, kv_len // 16384)
    return max(1, min(cap, kv_len // max(block_size, 1)))


def gather_paged_kv(
    k: jax.Array, v: jax.Array, block_table: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Materialise the logical ``(B, Hkv, NB·block, D)`` view of a paged
    pool: row ``b``'s logical block ``j`` is pool row ``block_table[b, j]``.

    The eager reference of the block-table kernels — the Pallas paged
    path streams exactly these rows in exactly this order through its
    index maps, so "gather then run the contiguous path" and "run the
    paged kernel" are bit-identical by construction (the oracle the
    randomized block-table tests pin). Out-of-range entries clamp (the
    engine keeps unmapped entries at 0; clamped garbage is causally
    masked either way)."""

    def g(pool: jax.Array) -> jax.Array:
        B, NB = block_table.shape
        N, Hkv, blk, D = pool.shape
        idx = jnp.clip(block_table, 0, N - 1)
        rows = jnp.moveaxis(pool[idx], 1, 2)  # (B, Hkv, NB, blk, D)
        return rows.reshape(B, Hkv, NB * blk, D)

    return g(k), g(v)


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_position=None,
    scale: Optional[float] = None,
    num_splits: Optional[int] = None,
    block_size: Optional[int] = None,
    block_table: Optional[jax.Array] = None,
    tree_mask: Optional[jax.Array] = None,
    step_plan=None,
    window: Optional[WindowRule] = None,
    launch: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Causal decode attention of a few new queries against a long KV buffer.

    Args:
      q: ``(B, Hq, Tq, D)`` — the new tokens' queries (Tq is typically 1;
        a few for speculative/chunked decode).
      k, v: ``(B, Hkv, Tk, D)`` KV buffer; only positions ``<= q_position + i``
        are visible to query ``i``, so a cache longer than the valid prefix is
        handled by ``q_position`` alone.
      q_position: global position of the first query row. Defaults to
        ``Tk - Tq`` (queries are the newest tokens of a fully-valid buffer).
        May be a traced scalar — decode steps jit once and run at every
        sequence length — or a ``(B,)`` vector for a **ragged batch**:
        each batch row is a cache slot with its own filled length, and the
        causal rule masks every row's unwritten tail independently (slot
        ``i``'s query sits at ``q_position[i]``; everything beyond is its
        masked future).
      num_splits: KV chunks computed in parallel on the chunked-vmap (CPU)
        path; default scales with ``Tk / block_size``, capped at
        ``max(16, Tk // 16384)`` (see :func:`default_num_splits` — the cap
        grows with context so 256k+ buffers keep exposing parallelism).
        The TPU Pallas kernel is split-KV internally (one chunk per
        ``block_size`` KV tile), so this knob is inert there.
      block_size: KV tile length. ``None`` picks the impl-appropriate
        default (the measured :mod:`~tree_attention_tpu.ops.tuning` table
        for the flash-decode kernel, 512 for the Q-tiled prefill kernel and
        the chunked path); an explicit value is honored as given everywhere.

    Returns:
      ``(out, lse)``: ``(B, Hq, Tq, D)`` in q's dtype, ``(B, Hq, Tq)`` float32.

    With ``block_table`` the buffer is **paged** (``k``/``v`` are
    ``(N, Hkv, block, D)`` pools, see
    :class:`~tree_attention_tpu.models.decode.PagedKVCache`): on the TPU
    decode-kernel path the table rides scalar prefetch into the Pallas
    kernel (no gather); everywhere else — the chunked-vmap CPU path and
    prefill-sized Tq on the Q-tiled kernel — the logical view is
    gathered once via :func:`gather_paged_kv` and the contiguous path
    runs unchanged, which keeps eager and Pallas bit-exact. ``step_plan``
    (``ops.pallas_decode.decode_plan``) is the paged kernel's work list
    where the caller has built it for several calls; the other paths have
    no use for it.

    ``tree_mask`` (a ``(B, Tq, Tq)`` bool array; requires a ``(B,)``
    ``q_position`` and ``Tq <= 32``) switches on the speculative
    tree-verification window rule (SpecInfer, arXiv:2305.09781): the Tq
    query rows are packed draft-tree nodes occupying KV positions
    ``[q_position[b], q_position[b] + Tq)`` of their slot, and row ``i``
    sees a window position ``j`` iff ``tree_mask[b, i, j]`` (its
    ancestors and itself); everything below the window stays visible,
    everything past it masked. A lower-triangular mask IS the plain
    causal rule, bit-for-bit. Supported on the chunked-vmap path and the
    Pallas decode kernels (as a packed bitmask in SMEM-adjacent VMEM
    lanes); the Q-tiled prefill kernel never sees spec-sized Tq.

    ``window`` (a sliding-window layer; no ``tree_mask``): query row ``i``
    sees only the ``window`` positions up to its own, ``(q_position + i -
    window, q_position + i]``. A paged call on the TPU runs the paged
    decode kernel at EVERY ``Tq`` (its work list starts at the step that
    holds the lowest visible position, so a 256-row chunk reads ``window +
    256`` tokens through the table and never gathers the context); every
    other call masks on the chunked path. ``window`` may also be one of
    ``block_utils``' other rules: :class:`~.block_utils.AlignedWindow` (a
    row sees its own block of ``window`` positions up to itself) or
    :class:`~.block_utils.ChunkSummaries` (the pool's rows are one summary
    a chunk; a row sees those of the windows closed before its own).
    """
    B, Hq, Tq, D = q.shape
    if window is not None and tree_mask is not None:
        raise ValueError("a sliding window takes no tree_mask")
    if tree_mask is not None:
        if Tq > 32:
            raise ValueError(
                f"tree_mask packs ancestor sets into int32 bitmasks: "
                f"Tq={Tq} exceeds 32"
            )
        if getattr(q_position, "ndim", 0) != 1:
            raise ValueError(
                "tree_mask needs a per-slot (B,) q_position (the window "
                "start is each slot's committed length)"
            )
        if tree_mask.shape != (B, Tq, Tq):
            raise ValueError(
                f"tree_mask must be (B, Tq, Tq) = {(B, Tq, Tq)}, got "
                f"{tree_mask.shape}"
            )
    Tk = (
        block_table.shape[1] * k.shape[2] if block_table is not None
        else k.shape[2]
    )
    if q_position is None:
        if block_table is not None:
            # Defaulting to Tk - Tq would place the queries at the END of
            # the LOGICAL capacity, causally exposing every table entry —
            # including unwritten ones still pointing at block 0 (some
            # other slot's data). Paged callers know their true lengths.
            raise ValueError("paged decode needs an explicit q_position")
        q_position = Tk - Tq
    # Ragged batch: one q_position per batch row (cache slot).
    ragged = getattr(q_position, "ndim", 0) == 1

    # On TPU the Pallas flash-decode kernel subsumes the chunked-vmap form:
    # it is itself split-KV (sequential KV tiles with carried online-softmax
    # state) and streams at the HBM roofline at any context length.
    from tree_attention_tpu.ops import _on_tpu, _pallas_available

    if _on_tpu(q) and _pallas_available() and (
            window is None or block_table is not None):
        # Kernel choice and tile defaults live in ops.tuning (shared with
        # flash_attention's auto gate). Prefill-sized Tq takes the Q-tiled
        # kernel: the decode kernel's group packing would spill into
        # multiple Q tiles, each re-streaming the whole KV buffer.
        from tree_attention_tpu.ops.tuning import (
            default_block_size,
            tpu_kernel_for,
        )

        impl = tpu_kernel_for(Tq)
        if tree_mask is not None and impl != "pallas_decode":
            # Spec-tree chunks are <= 32 rows, squarely the decode
            # kernel's regime; the Q-tiled kernel has no mask path.
            impl = "pallas_decode"
        if window is not None:
            impl = "pallas_decode"  # the one kernel with a lower edge
        if block_table is not None:
            if impl == "pallas_decode":
                from tree_attention_tpu.ops.pallas_decode import (
                    attention_pallas_decode,
                )

                # The paged kernel: table-driven DMA, no gather copy.
                _account_dispatch("paged_decode", Tk)
                return attention_pallas_decode(
                    q, k, v, causal=True, scale=scale,
                    q_offset=q_position, kv_offset=0,
                    block_table=block_table, tree_mask=tree_mask,
                    step_plan=step_plan, window=window, launch=launch,
                )
            # Prefill-sized Tq rides the Q-tiled kernel, which has no
            # table path — one gather materialises the logical view
            # (amortised over Tq rows of prefill compute).
            k, v = gather_paged_kv(k, v, block_table)
        bk = default_block_size(impl, Tk) if block_size is None else block_size
        # Static int offsets specialise the kernel (grid-level causal cull),
        # which is right for the fixed full-buffer default but would
        # recompile per token if a caller advances q_position as a Python
        # int. Only the default position stays static; any other int is
        # demoted to a traced scalar (one compile, no cull) — callers who
        # decode a growing prefix should pass a traced position anyway
        # (models/decode.py does).
        if (
            isinstance(q_position, numbers.Integral)
            and int(q_position) != Tk - Tq
        ):
            q_position = jnp.asarray(q_position, jnp.int32)
        if impl == "pallas_decode":
            from tree_attention_tpu.ops.pallas_decode import (
                attention_pallas_decode,
            )

            kernel = attention_pallas_decode
        else:
            from tree_attention_tpu.ops.pallas_attention import (
                attention_pallas_fwd,
            )

            kernel = attention_pallas_fwd
        _account_dispatch(impl, Tk)
        # Both kernels take scalar OR (B,) offsets (per-batch SMEM
        # columns), so ragged and uniform batches are one dispatch either
        # way.
        kw = {}
        if impl == "pallas_decode":
            kw["tree_mask"] = tree_mask
        return kernel(
            q, k, v, causal=True, scale=scale,
            q_offset=q_position, kv_offset=0, block_size=bk, **kw,
        )

    if block_table is not None:
        # Eager reference: one gather, then the contiguous chunked path —
        # bit-exact with the paged kernel (see gather_paged_kv).
        k, v = gather_paged_kv(k, v, block_table)

    block_size = 512 if block_size is None else block_size
    S = num_splits if num_splits is not None else default_num_splits(Tk, block_size)
    S = max(1, min(S, Tk))
    chunk = -(-Tk // S)  # ceil

    # Pad to S equal chunks; padded slots sit at global positions >= Tk, in
    # every query's masked future, so the causal mask removes them exactly.
    kp = pad_to_block(k, 2, chunk)
    vp = pad_to_block(v, 2, chunk)
    S = kp.shape[2] // chunk
    kb = kp.reshape(B, k.shape[1], S, chunk, D).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(B, v.shape[1], S, chunk, D).transpose(2, 0, 1, 3, 4)
    offsets = jnp.arange(S) * chunk

    def one_chunk(k_s: jax.Array, v_s: jax.Array, off: jax.Array):
        if ragged:
            # Per-slot offsets: vmap the online-softmax scan over batch so
            # each row masks against its own q_position. Same chunking,
            # same merge — a row's partials are identical to the scalar
            # path's, so ragged and uniform batches agree bit-for-bit.
            # A tree mask rides the same vmap (one (Tq, Tq) ancestor mask
            # per slot, applied against that slot's window offset).
            def per_slot(q_b, k_b, v_b, pos_b, *tm_b):
                o, l = attention_blockwise(
                    q_b[None], k_b[None], v_b[None],
                    causal=True, scale=scale,
                    q_offset=pos_b, kv_offset=off,
                    block_size=min(block_size, chunk),
                    tree_mask=tm_b[0][None] if tm_b else None,
                    window=window,
                )
                return o[0], l[0]

            args = (q, k_s, v_s, q_position)
            if tree_mask is not None:
                args = args + (tree_mask,)
            return jax.vmap(per_slot)(*args)
        return attention_blockwise(
            q, k_s, v_s,
            causal=True, scale=scale,
            q_offset=q_position, kv_offset=off,
            block_size=min(block_size, chunk), window=window,
        )

    _account_dispatch("chunked_vmap", Tk)
    outs, lses = jax.vmap(one_chunk)(kb, vb, offsets)
    return merge_partials(outs, lses)


def paged_local_partial(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    local_table: jax.Array,
    *,
    q_position,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One shard's flash partial over its LOCAL slice of a sequence-sharded
    paged pool (ISSUE 18): the per-shard half of the tree-attention decode
    monoid, run inside ``shard_map`` by
    :func:`~tree_attention_tpu.parallel.tree.paged_tree_decode`.

    Args:
      q: ``(B, Hq, Tq, D)`` — replicated queries (every shard sees all of
        them; the merge weighs the partials).
      k, v: ``(Nl, Hkv, block, D)`` — this shard's pool slice (``Nl = N/W``
        blocks of the global pool).
      local_table: ``(B, NB)`` int32 — the slot tables rebased to LOCAL
        block ids: entries in ``[0, Nl)`` name a local block, **negative
        entries mean the logical block lives on another shard** and its
        keys must not contribute here (the per-slot cull against the
        shard's local coverage). The signed convention is shared with the
        Pallas local-partial kernel
        (:func:`~tree_attention_tpu.ops.pallas_decode
        .attention_pallas_decode` with ``local_blocks=True``).
      q_position: per-slot ``(B,)`` global position of each slot's first
        query row (the ragged serving shape); the causal rule is the usual
        ``key_pos <= q_position[b] + i`` in LOGICAL positions — a logical
        block's keys sit at the same global positions on every shard, so
        the per-shard partials merge into exactly the replicated result.
      k_scale, v_scale: optional ``(Nl, Hkv)`` per-block int8 scales (the
        slice sharded WITH the pool slice); when given, ``k``/``v`` are
        int8 and each local block's keys/values are dequantized under its
        own scale before the partial — the same quantize-then-dequantize
        rows the replicated off-kernel path attends over.

    Returns:
      ``(out, lse)`` — ``(B, Hq, Tq, D)`` in q's dtype and ``(B, Hq, Tq)``
      float32, normalized WITHIN the shard; rows with no locally visible
      key emit the safe-softmax identity ``(0, -inf)`` (see
      :func:`~tree_attention_tpu.ops.reference.finalize`), so empty or
      fully-future shards drop out of the merge exactly.
    """
    from tree_attention_tpu.ops import _on_tpu, _pallas_available
    from tree_attention_tpu.ops.reference import (
        NEG_INF,
        _default_scale,
        finalize,
        matmul_precision,
    )

    B, Hq, Tq, D = q.shape
    Nl, Hkv, blk, _ = k.shape
    NB = local_table.shape[1]
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if getattr(q_position, "ndim", 0) != 1:
        raise ValueError(
            "paged_local_partial needs a per-slot (B,) q_position"
        )

    if not quant and _on_tpu(q) and _pallas_available():
        from tree_attention_tpu.ops.pallas_decode import (
            attention_pallas_decode,
        )

        _account_dispatch("paged_local_partial", NB * blk)
        return attention_pallas_decode(
            q, k, v, causal=True, scale=scale,
            q_offset=q_position, kv_offset=0,
            block_table=local_table, local_blocks=True,
        )

    # Reference path (CPU / interpret / int8-dequant): gather the local
    # logical view — unowned entries clamp to block 0 and are masked out
    # below, mirroring gather_paged_kv's clamp-then-mask contract.
    owned = local_table >= 0
    idx = jnp.clip(local_table, 0, Nl - 1)

    def view(pool: jax.Array, scl: Optional[jax.Array]) -> jax.Array:
        rows = jnp.moveaxis(pool[idx], 1, 2)  # (B, Hkv, NB, blk, D)
        if scl is not None:
            s = jnp.swapaxes(scl[idx], 1, 2)  # (B, Hkv, NB)
            rows = (
                rows.astype(jnp.float32) * s[..., None, None]
            ).astype(q.dtype)
        return rows.reshape(B, Hkv, NB * blk, D)

    kb = view(k, k_scale)
    vb = view(v, v_scale)

    G = Hq // Hkv
    s = _default_scale(D, scale)
    qg = q.reshape(B, Hkv, G, Tq, D)
    logits = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, kb.astype(q.dtype),
        preferred_element_type=jnp.float32,
        precision=matmul_precision(qg.dtype, kb.dtype),
    ) * s
    key_pos = jnp.arange(NB * blk, dtype=jnp.int32)
    q_pos = (
        jnp.asarray(q_position, jnp.int32)[:, None]
        + jnp.arange(Tq, dtype=jnp.int32)[None, :]
    )  # (B, Tq)
    visible = (
        jnp.repeat(owned, blk, axis=1)[:, None, :]          # local coverage
        & (key_pos[None, None, :] <= q_pos[..., None])      # causal
    )  # (B, Tq, K)
    logits = jnp.where(visible[:, None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32),
        precision=matmul_precision(jnp.float32),
    )
    _account_dispatch("paged_local_partial_reference", NB * blk)
    return finalize(
        acc.reshape(B, Hq, Tq, D),
        m.reshape(B, Hq, Tq),
        l.reshape(B, Hq, Tq),
        q.dtype,
    )
