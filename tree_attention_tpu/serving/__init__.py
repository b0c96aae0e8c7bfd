"""Continuous-batching serving: slot scheduler over the ragged decode stack.

The decode stack serves one request shape (``models/decode.py``); this
package serves *traffic*: a fixed batch of S cache slots, a request queue,
and a tick loop that admits pending requests into free slots, runs ONE
compiled decode step for every live slot, and retires/refills slots the
moment a request finishes — static shapes throughout, so one compilation
serves every mixture of request states (the per-slot ``(B,)`` cache lengths
carry the raggedness as data, not shape).
"""

from tree_attention_tpu.serving.engine import (  # noqa: F401
    OUTCOMES,
    Request,
    RequestResult,
    RequestSource,
    ServeReport,
    SlotServer,
    StaticRequestSource,
    synthetic_trace,
)
from tree_attention_tpu.serving.block_pool import (  # noqa: F401
    BlockAllocator,
    ShardedBlockAllocator,
)
from tree_attention_tpu.serving.disagg import DisaggServer  # noqa: F401
from tree_attention_tpu.serving.fleet import (  # noqa: F401
    FleetSupervisor,
    LocalReplica,
    ProcessReplica,
)
from tree_attention_tpu.serving.router import (  # noqa: F401
    FleetRouter,
    ReplicaTree,
    federate_metrics,
)
from tree_attention_tpu.serving.prefix_cache import (  # noqa: F401
    PagedPrefixIndex,
)
from tree_attention_tpu.serving.speculation import (  # noqa: F401
    DraftModelDrafter,
    DraftProposal,
    Drafter,
    PromptLookupDrafter,
    PromptLookupTreeDrafter,
    accept_longest_path,
    make_drafter,
    pack_proposal,
)
