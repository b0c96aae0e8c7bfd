"""Data layer: synthetic Q/KV and LM-batch generation, shard-local.

TPU-native replacement for the reference's ``make_data``
(``/root/reference/model.py:37-56``), which seeds torch's RNG with
``0 + rank`` so each rank draws a *different* KV block — that per-rank seed is
the reference's entire sequence-parallel sharding story. Here the same
semantics come from ``jax.random.fold_in(key, shard_index)``: deterministic,
order-independent, and collision-free per shard.

Two equivalent constructions, tested against each other:

- :func:`make_qkv` — host/global form: concatenates the per-shard blocks, so
  ``n_shards`` only changes *which* random blocks compose the sequence, never
  the contract.
- :func:`make_qkv_sharded` — mesh form: each device generates **its own** KV
  block inside ``shard_map`` (fold_in on ``axis_index``), so a million-token
  cache is born sharded — no host materialisation, no device-0 hotspot. The
  reference instead re-runs ``make_data`` per process (``model.py:145``).

Layout note: the reference creates ``(B, T, nh, C)`` but its kernel assumes
``(B, nh, T, C)`` — the confirmed bug 1 of SURVEY.md §2.1. This framework has
exactly one layout, ``(B, H, T, D)``, everywhere.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tree_attention_tpu.parallel.mesh import AXIS_DATA, AXIS_SEQ
from tree_attention_tpu.utils.config import RunConfig

# Single source of truth for the canonical (reference) workload defaults.
_REF = RunConfig()

_Q, _K, _V = 1, 2, 3  # stream tags folded into the key, one per tensor


def _block(key: jax.Array, tag: int, shard: jax.Array | int,
           shape: Tuple[int, ...], dtype) -> jax.Array:
    """The one definition of a random block: fold (tag, shard) into the key."""
    k = jax.random.fold_in(jax.random.fold_in(key, tag), shard)
    return jax.random.normal(k, shape, dtype)


def make_qkv(
    key: jax.Array,
    *,
    batch: int = _REF.batch,
    heads: int = _REF.heads,
    kv_heads: Optional[int] = None,
    q_len: int = _REF.q_len,
    seq_len: int = _REF.seq_len,
    head_dim: int = _REF.head_dim,
    dtype=jnp.bfloat16,
    n_shards: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Global-form Q/KV: K/V are ``n_shards`` concatenated fold_in blocks.

    Defaults are the reference workload (``model.py:140-145``): B=1, 16 heads,
    head_dim 128, 64k context, single-query decode.
    """
    kv_heads = heads if kv_heads is None else kv_heads
    if seq_len % n_shards:
        raise ValueError(f"seq_len {seq_len} not divisible by {n_shards} shards")
    t_local = seq_len // n_shards
    q = _block(key, _Q, 0, (batch, heads, q_len, head_dim), dtype)
    ks = [_block(key, _K, s, (batch, kv_heads, t_local, head_dim), dtype)
          for s in range(n_shards)]
    vs = [_block(key, _V, s, (batch, kv_heads, t_local, head_dim), dtype)
          for s in range(n_shards)]
    return q, jnp.concatenate(ks, axis=2), jnp.concatenate(vs, axis=2)


def make_qkv_sharded(
    key: jax.Array,
    mesh: Mesh,
    *,
    batch: int = _REF.batch,
    heads: int = _REF.heads,
    kv_heads: Optional[int] = None,
    q_len: int = _REF.q_len,
    seq_len: int = _REF.seq_len,
    head_dim: int = _REF.head_dim,
    dtype=jnp.bfloat16,
    seq_axis: str = AXIS_SEQ,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Mesh-form Q/KV: KV born sharded along ``seq_axis``, Q replicated.

    Bit-identical to :func:`make_qkv` with ``n_shards = mesh.shape[seq_axis]``
    (same fold_in blocks, generated on the devices that own them).
    """
    kv_heads = heads if kv_heads is None else kv_heads
    n = mesh.shape[seq_axis]
    if seq_len % n:
        raise ValueError(f"seq_len {seq_len} not divisible by mesh axis {n}")
    t_local = seq_len // n
    gen = _sharded_gen(
        mesh, seq_axis, batch, heads, kv_heads, q_len, t_local, head_dim,
        jnp.dtype(dtype).name,
    )
    return gen(key)


@functools.lru_cache(maxsize=64)
def _sharded_gen(mesh, seq_axis, batch, heads, kv_heads, q_len, t_local,
                 head_dim, dtype_name):
    """Jitted per-shard generator, cached so config sweeps don't recompile."""
    dtype = jnp.dtype(dtype_name)
    q_spec = P()
    kv_spec = P(None, None, seq_axis, None)

    def _gen(key):
        shard = lax.axis_index(seq_axis)
        q = _block(key, _Q, 0, (batch, heads, q_len, head_dim), dtype)
        k = _block(key, _K, shard, (batch, kv_heads, t_local, head_dim), dtype)
        v = _block(key, _V, shard, (batch, kv_heads, t_local, head_dim), dtype)
        return q, k, v

    return jax.jit(shard_map(
        _gen, mesh=mesh, in_specs=P(),
        out_specs=(q_spec, kv_spec, kv_spec), check_vma=False,
    ))


def make_lm_batch(
    key: jax.Array,
    *,
    batch: int,
    seq_len: int,
    vocab_size: int,
    mesh: Optional[Mesh] = None,
    data_axis: str = AXIS_DATA,
    seq_axis: str = AXIS_SEQ,
) -> Dict[str, jax.Array]:
    """Random next-token LM batch: ``targets`` = ``inputs`` shifted left.

    With a mesh, the batch is placed sharded (batch dim over ``data_axis``,
    sequence dim over ``seq_axis`` when those axes exist).
    """
    tokens = jax.random.randint(key, (batch, seq_len + 1), 0, vocab_size)
    out = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    if mesh is not None:
        for dim, name, size in (("batch", data_axis, batch),
                                ("seq_len", seq_axis, seq_len)):
            if name in mesh.shape and size % mesh.shape[name]:
                raise ValueError(
                    f"{dim}={size} not divisible by mesh axis "
                    f"'{name}'={mesh.shape[name]}"
                )
        spec = P(
            data_axis if data_axis in mesh.shape else None,
            seq_axis if seq_axis in mesh.shape else None,
        )
        out = {k: jax.device_put(v, NamedSharding(mesh, spec))
               for k, v in out.items()}
    return out
