"""tree_attention_tpu — a TPU-native sequence-parallel exact-attention framework.

A from-scratch JAX/XLA/Pallas implementation of the capability sketched by
kyegomez/Tree-Attention-Torch (reference ``model.py``): exact long-context
attention where K/V are sharded along the sequence axis across devices, each
device computes flash-style attention over its local KV shard emitting
``(output, logsumexp)``, and the partials are merged with a topology-aware
tree reduction of the safe-softmax ``(max, numerator, denominator)``.

The reference realises this with torch + NCCL allreduce (``model.py:85-124``);
here the per-shard kernel is a Pallas TPU flash attention and the merge is
``lax.pmax``/``lax.psum`` inside ``shard_map`` over a named device mesh, so the
log-depth reduction rides the ICI torus the way the reference leans on NCCL's
tree allreduce.

Public API highlights:

- :func:`tree_attention_tpu.ops.flash_attention` — single-device attention
  returning ``(out, lse)`` with selectable impl (``naive``/``blockwise``/
  ``pallas``).
- :func:`tree_attention_tpu.parallel.tree_attention` — sequence-parallel
  training-shape attention over a mesh axis.
- :func:`tree_attention_tpu.parallel.tree_decode` — the reference's
  ``tree_decode`` equivalent: replicated single-query Q against
  sequence-sharded KV.
- :mod:`tree_attention_tpu.models` — a decoder-only transformer family built
  on the above.
"""

import time as _time

#: When this package's import began (``time.monotonic()``), before JAX's:
#: where the start-up record begins on a system whose ``/proc`` does not
#: give the process's own start (``obs/flight.py`` ``StartupRecord``).
_T_IMPORT = _time.monotonic()

__version__ = "0.5.0"

from tree_attention_tpu.ops import flash_attention, merge_partials  # noqa: F401
