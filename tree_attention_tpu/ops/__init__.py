"""Attention ops: one contract, several implementations.

``flash_attention(q, k, v, ...) -> (out, lse)`` is the framework-wide kernel
contract (the reference's ``flash_res_lse``, ``/root/reference/model.py:60-83``,
done right). Implementations:

- ``"naive"``     — materialised scores, test oracle (:mod:`.reference`)
- ``"blockwise"`` — online-softmax ``lax.scan``, any backend (:mod:`.reference`)
- ``"pallas"``    — Pallas TPU kernels, fwd (:mod:`.pallas_attention`) +
  bwd (:mod:`.pallas_bwd`); Q-tiled, the training shape
- ``"pallas_decode"`` — Pallas TPU split-KV flash-decode kernel
  (:mod:`.pallas_decode`); GQA-group-packed Q tiles for Tq < 128
- ``"auto"``      — decode shapes (Tq < 128) resolve to the flash-decode
  kernel on TPU (any context length; no score transient) and to ``naive``
  elsewhere when the score transient is small; large-Tq shapes resolve to
  ``pallas`` on TPU and ``blockwise`` elsewhere. Pass an explicit impl
  when a specific kernel or backward path must be used. Every resolution
  is logged once per program build at debug level (``tree_attention_tpu.ops``:
  which kernel or reference path serves the call).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from tree_attention_tpu.ops.decode import flash_decode  # noqa: F401
from tree_attention_tpu.utils.logging import get_logger
from tree_attention_tpu.ops.reference import (  # noqa: F401
    attention_blockwise,
    attention_naive,
    finalize,
    merge_partials,
)

log = get_logger("ops")

_IMPLS = ("auto", "naive", "blockwise", "pallas", "pallas_decode")


def _on_tpu(q=None) -> bool:
    """Whether this computation targets TPU.

    A concrete array's placement is authoritative (a CPU-placed array on a
    TPU-default host must not select the Mosaic kernel); tracers carry no
    devices, so jit callers fall back to the default backend — sharded entry
    points resolve from their mesh instead (see ``parallel/tree.py``).
    """
    if q is not None and not isinstance(q, jax.core.Tracer):
        try:
            return {d.platform for d in q.devices()} == {"tpu"}
        except Exception:
            pass
    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:  # no backends initialised
        return False


def mesh_platforms(mesh):
    """The set of device platforms a mesh spans, or None when the mesh has
    no concrete devices to probe (e.g. an AbstractMesh) — callers should
    then trust the compiled path rather than pessimise."""
    try:
        return {d.platform for d in mesh.devices.flat}
    except Exception:
        return None


def resolve_impl_for_mesh(impl: str, mesh) -> str:
    """Pin ``impl='auto'`` for computations running on ``mesh``'s devices.

    Inside ``shard_map``/``jit`` the arrays are tracers, so
    :func:`flash_attention`'s own auto resolution can only consult the
    default backend — wrong when the mesh lives on a different platform
    (e.g. an emulated CPU mesh on a TPU-default host). Sharded entry points
    call this with their mesh before tracing: when the mesh's platform is
    the default backend (or TPU, where every auto branch is valid), "auto"
    passes through; otherwise the portable blockwise path is pinned.
    """
    if impl != "auto":
        return impl
    platforms = mesh_platforms(mesh)
    if platforms is None:
        return impl
    if platforms == {"tpu"}:
        return impl
    try:
        if platforms == {jax.default_backend()}:
            return impl
    except RuntimeError:
        pass
    return "blockwise"


def _pallas_available() -> bool:
    try:
        import tree_attention_tpu.ops.pallas_attention  # noqa: F401
        return True
    except ImportError:
        return False


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    impl: str = "auto",
    block_size: Optional[int] = None,
    block_q: Optional[int] = None,
    custom_vjp: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Compute attention over the sequence axis, returning ``(out, lse)``.

    Args:
      q: ``(B, Hq, Tq, D)`` queries.
      k, v: ``(B, Hkv, Tk, D)`` keys/values; ``Hq % Hkv == 0`` (GQA).
      causal: apply causal masking (``-inf`` before softmax).
      scale: logit scale; default ``D**-0.5``.
      q_offset / kv_offset: global positions of the first local query/key row,
        for causal masking across sequence shards.
      impl: ``auto | naive | blockwise | pallas | pallas_decode``.
      block_size: KV block length for the blockwise/pallas paths. ``None``
        picks the impl's default from :mod:`.tuning` — a measured
        context-bucketed table for the flash-decode kernel, 512 elsewhere;
        an explicit value is honored as given.
      block_q: Q-tile length for the Q-tiled Pallas kernel (fwd and bwd).
        ``None`` picks the tuned default; ignored by the other impls (the
        flash-decode kernel derives its Q packing from the GQA group).
      custom_vjp: use the flash (recompute-from-lse) backward — O(T) residual
        memory but **reverse-mode only** (``jax.jvp``/``jacfwd`` raise on
        custom_vjp functions). Pass False (or ``impl='naive'``) for
        forward-mode differentiability at O(T²) memory.

    Returns:
      ``out``: ``(B, Hq, Tq, D)`` in q's dtype; ``lse``: ``(B, Hq, Tq)``
      float32 logsumexp of the scaled logits (the merge currency of the tree
      reduction).
    """
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    if impl == "auto":
        # Resolution order, all measured on the target chip (TPU v5e):
        # 1. Decode shapes (Tq < 128) on TPU -> "pallas_decode": the
        #    split-KV kernel streams KV at the HBM roofline regardless of
        #    context length (no score transient, GQA streams each KV head
        #    once). This removes round 1's cliff where >=683k-token MHA
        #    decode fell off the naive path's 128 MB transient gate.
        # 2. Decode shapes elsewhere -> "naive" when the score transient is
        #    small (fused two-matmul form; raw autodiff fine for inference).
        #    Gated on 3x the score bytes (f32 logits + masked copy +
        #    probabilities all materialise) staying comfortably small.
        # 3. Large-Tq shapes on TPU -> "pallas" (Q-tiled): verified correct
        #    on-chip and ~4x the blockwise fwd throughput / ~2.3x fwd+bwd
        #    (bf16 operands on the MXU fast path, f32 accumulation).
        # 4. Everything else -> "blockwise" (pure XLA, any backend).
        Tq, Tk = q.shape[2], k.shape[2]
        transient_bytes = 3 * q.shape[0] * q.shape[1] * Tq * Tk * 4
        pallas_ok = _on_tpu(q) and _pallas_available()
        # custom_vjp=False is the documented forward-mode-AD escape hatch;
        # raw Pallas forwards have no autodiff rules, so that request keeps
        # the jnp impls whenever one is viable at the shape.
        naive_ok = Tq <= 8 and transient_bytes <= 128 * 1024 * 1024
        if pallas_ok and (custom_vjp or not naive_ok or Tq >= 128):
            from tree_attention_tpu.ops.tuning import tpu_kernel_for

            impl = tpu_kernel_for(Tq)
        elif naive_ok:
            impl = "naive"
        else:
            impl = "blockwise"
        # Trace time under jit: one line per program build.
        log.debug(
            "flash_attention: impl=auto -> %s (Tq=%d, Tk=%d, %s)", impl, Tq,
            Tk, "Pallas kernel" if impl.startswith("pallas")
            else "reference path",
        )
    # None picks tuned defaults. The bwd kernels get their own (VMEM-capped)
    # default Q tile only when the caller left block_q to the table; an
    # explicit block_q flows to both passes unchanged so tuning sweeps
    # measure what they label.
    block_q_bwd = block_q
    if block_size is None or (block_q is None and impl == "pallas"):
        from tree_attention_tpu.ops.tuning import (
            default_block_q,
            default_block_q_bwd,
            default_block_size,
        )

        if block_size is None:
            block_size = default_block_size(impl, k.shape[2])
        if block_q is None and impl == "pallas":
            block_q = default_block_q(q.shape[2], k.shape[2])
            # The resolved KV tile (possibly caller-supplied) bounds the
            # bwd Q tile: VMEM feasibility scales with bq * bk.
            block_q_bwd = default_block_q_bwd(
                q.shape[2], k.shape[2], block_size
            )
    if impl == "naive":
        # Raw autodiff path: the differential oracle the custom VJP is
        # tested against.
        return attention_naive(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset, kv_offset=kv_offset
        )
    if impl in ("pallas", "pallas_decode"):
        try:
            import tree_attention_tpu.ops.pallas_attention  # noqa: F401
        except ImportError as e:
            raise NotImplementedError(
                f"impl={impl!r} requested but the Pallas kernel module is not "
                "available in this build; use impl='blockwise' or 'auto'"
            ) from e
    if not custom_vjp:
        if impl == "blockwise":
            return attention_blockwise(
                q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                kv_offset=kv_offset, block_size=block_size,
            )
        # Raw Pallas forwards: fine for inference; they have no autodiff
        # rules at all, so this is never silently worse than the custom VJP.
        if impl == "pallas_decode":
            from tree_attention_tpu.ops.pallas_decode import (
                attention_pallas_decode,
            )

            return attention_pallas_decode(
                q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                kv_offset=kv_offset, block_size=block_size,
            )
        from tree_attention_tpu.ops.pallas_attention import attention_pallas_fwd

        kw = {} if block_q is None else {"block_q": block_q}
        return attention_pallas_fwd(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            kv_offset=kv_offset, block_size=block_size, **kw,
        )
    from tree_attention_tpu.ops.vjp import flash_attention_vjp

    return flash_attention_vjp(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset,
        kv_offset=kv_offset, impl=impl, block_size=block_size,
        block_q=block_q if impl == "pallas" else None,
        block_q_bwd=block_q_bwd if impl == "pallas" else None,
    )
