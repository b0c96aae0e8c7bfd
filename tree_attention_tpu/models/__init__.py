"""Model family: decoder-only transformer LMs over tree attention.

The flagship model exercising the framework the way the reference's driver
exercises its op (``/root/reference/model.py:129-155``) — but as a real LM
with parameters, a loss, and a sharded training step.
"""

from tree_attention_tpu.models.transformer import (  # noqa: F401
    ExpertLayer,
    LatentAttention,
    TransformerConfig,
    YarnRope,
    count_params,
    cross_entropy_loss,
    forward,
    init_params,
    loss_fn,
    model_from_config,
    param_shardings,
    param_specs,
)
from tree_attention_tpu.models.decode import (  # noqa: F401
    KVCache,
    PagedKVCache,
    PagedLatentCache,
    PagedQuantKVCache,
    QuantKVCache,
    decode_attention,
    forward_packed_step,
    forward_step,
    generate,
    init_cache,
    init_paged_cache,
    quantize_cache,
)
from tree_attention_tpu.models.train import (  # noqa: F401
    default_optimizer,
    init_train_state,
    make_train_step,
    shard_batch,
)
