"""Slot-based continuous batching with stall-free chunked prefill.

The lockstep :func:`~tree_attention_tpu.models.decode.generate` decodes one
batch whose rows start, step and stop together — requests with different
prompt lengths, arrival times or stop points cannot share it, so aggregate
tokens/sec dies at real traffic. This engine holds a **fixed batch of S cache
slots** (one :class:`~tree_attention_tpu.models.decode.PagedKVCache`: a
block pool under S block tables, with per-slot lengths) plus a request
queue, and runs a tick loop:

1. **Admit** — every free slot takes the oldest pending request whose
   arrival time has passed; the slot enters the ``prefilling`` state with
   nothing on the device yet.
2. **Step** — ONE compiled step advances the whole batch: every live slot
   contributes its one decode token, and up to ``prefill_budget`` prompt
   tokens of the prefilling slots ride along as fixed-size chunks
   (``prefill_chunk``), written **directly into each slot's blocks of the
   pool** at that slot's running offset. A tick that carries a chunk is
   **packed** (``forward_packed_step``): a compact chunk group of ``C``
   members beside one decode row a slot, ``C·Tq + S`` rows and never
   ``S·Tq``, so a prompt chunk costs a decode tick's weight stream plus
   its own rows' compute. ``C`` is fixed at build (``ceil(prefill_budget
   / prefill_chunk)``), so how many slots happen to chunk at once never
   chooses a program. No B=1 mini cache, no insert copy, no per-admit host
   sync: a long prompt costs each live slot at most one chunk of extra
   latency per tick instead of a whole-prompt stall — the Sarathi-style
   stall-free batching shape (arXiv:2403.02310). Chunk sizes come from a
   small fixed power-of-two bucket set, so occupancy changes and chunk
   mixtures never recompile (pure-decode ticks are the padded
   ``forward_step`` at Tq=1, where padding and packing are the same rows).
3. **Retire** — a slot whose request hit EOS or its token budget frees
   immediately and is refilled on the next admission pass.

The slot lifecycle is ``free -> prefilling -> live -> (EOS | budget) ->
free``. The first sampled token is never fetched on its own: the final
chunk's sample lands in the per-tick batched token fetch that the decode
loop already pays (one host sync per tick, total).

Variants:

- ``quantize=True`` serves from an int8 cache. Admission then runs
  its chunks against ONE preallocated exact **staging** cache (int8 rows
  cannot hold exact prefill activations), and at final-chunk completion the
  staged prefix is masked, quantized block by block under each block's
  own frozen scales, and inserted — the quantize-after-prefill contract,
  per block.
  One prompt stages at a time; decode ticks never wait for more than a
  chunk of prefill work either way.

- **One KV layout: the paged pool** (ISSUE 6; vLLM's PagedAttention,
  arXiv:2309.06180). ONE ref-counted block pool lies under every slot AND
  the prefix cache: each slot is a host-side block table into the pool
  (:class:`~tree_attention_tpu.models.decode.PagedKVCache`), physical
  blocks are allocated on demand by a reservation-based host allocator
  (:mod:`~tree_attention_tpu.serving.block_pool`). Admissions that cannot
  reserve their worst-case block count simply wait in the queue, so the
  pool can be sized well under ``slots × cache_len``. int8 serving pages
  the slot cache with per-BLOCK scale scalars riding the pool (ISSUE 13).

- ``prefix_cache=True`` (ISSUE 5) reuses shared prompt prefixes across
  requests: a host-side radix tree over prompt blocks
  (:mod:`~tree_attention_tpu.serving.prefix_cache`, RadixAttention,
  arXiv:2312.07104) whose nodes own pool blocks. Reuse is
  **reference-in-place** — a radix hit bumps pins and writes pool ids
  into the slot's table (zero KV bytes moved), only the unmatched suffix
  rides the chunk budget, and prefill completion publishes by HANDING
  blocks over to the tree. Quantized blocks publish into and hit from
  the same tree as exact ones — the quantize-after-prefill contract
  holds at block granularity, and an int8 hit dequant-gathers the
  matched blocks into the staging cache. With ``host_blocks > 0`` the
  pool grows a host-RAM demotion tier under it: radix eviction demotes
  refcount-0 blocks (staged D2H, one batched gather per tick) instead of
  freeing them, and a hit on a demoted path restores it with one batched
  H2D scatter — the effective prefix cache becomes host-RAM-sized.

- ``speculate=True`` (ISSUE 8) turns every live slot's tick into a
  **draft-and-verify** step (speculative decoding, arXiv:2211.17192): a
  host drafter proposes up to ``draft_k`` candidate tokens from the
  slot's own history (prompt-lookup n-grams by default — zero extra
  model; a token *tree* with ``drafter="ngram-tree"``, verified under
  the tree-attention ancestor mask — SpecInfer, arXiv:2305.09781; or a
  small draft model), the ONE compiled mixed-Tq step scores all of them
  as a prefill-style chunk, the longest accepted root path commits in a
  burst (plus the model's free bonus token at the divergence), and
  rejections roll the slot's length back through the next step's
  ``reset_val`` — with paged blocks past the rollback point unmapped
  back into the slot's reservation so rolled-back KV never leaks pool
  capacity. Greedy only: committed tokens are token-for-token identical
  to non-speculative decode, the hard parity contract
  (``tests/test_serving_spec.py`` pins it across exact/int8 ×
  device/mesh).

- **Robustness lifecycle** (ISSUE 10): ``serve()`` takes a pre-built
  trace OR a live :class:`RequestSource`; each tick starts with a
  control sweep applying thread-safe mailboxes — :meth:`SlotServer
  .cancel` (client disconnect: retire mid-flight, release prefix pins,
  unmap paged blocks back to the pool — cancellation is cheap by
  construction under the paged layout), per-request deadlines
  (expired-in-queue rejected unserved, expired-in-flight retired with
  outcome ``deadline``), and :meth:`SlotServer.request_drain` (SIGTERM:
  stop admitting, shed the queue, finish in-flight). Every exit arc
  speaks the closed :data:`OUTCOMES` vocabulary
  (``eos|budget|cancelled|deadline|shed|error``), threaded through
  ``serving_requests_total{outcome}``, span args, flight fields, and
  ``ServeReport.outcomes``; :meth:`SlotServer.leak_report` states the
  no-leak invariant the chaos harness asserts. The HTTP front door
  lives in :mod:`~tree_attention_tpu.serving.ingress`.

- **One program ahead** (ISSUE 32): ``serve()`` dispatches tick t+1
  before it fetches tick t whenever t+1 can be planned from counts alone
  (the token vector is carried on the device), so the host's work and the
  fetch's round trip run under the device's. A slot that turns out to
  have left (EOS, cancel, deadline) has one row computed and thrown away;
  speculation, token-tree and fork families and staged int8 prefill
  keep the synchronous order, chosen tick by tick from engine state
  (``_plan_tick``; the flight record's ``ahead`` / ``sync_reason``,
  ``serving_ticks_dispatched_ahead_total``).

Works on one device and on a mesh: the pool is replicated (flash/Pallas
paths) or, with ``kv_shard="seq"``, range-partitioned over the mesh's
sequence shards, where every tick's decode attention runs the tree merge.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import deque
from typing import (
    Any, Callable, Dict, List, Optional, Sequence, Set, Tuple, Union,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from tree_attention_tpu import obs
from tree_attention_tpu.obs import scopes
from tree_attention_tpu.obs.flight import FLIGHT, STARTUP, TickPhases
from tree_attention_tpu.obs.metrics import percentile
from tree_attention_tpu.obs.slo import SLOMonitor
from tree_attention_tpu.models.decode import (
    KVCache,
    cache_block_fixed_bytes,
    cache_token_bytes,
    chunks_closed,
    compact_decode_window,
    copy_pool_block,
    forward_packed_step,
    forward_step,
    gather_kv_blocks,
    init_cache,
    init_paged_cache,
    insert_dequant_prefix,
    paged_insert_slot,
    paged_step_tokens,
    pool_write_path,
    quantize_paged_blocks,
    sample_rows,
    sample_slots,
    scatter_kv_blocks,
    window_rules,
)
from tree_attention_tpu.ops.pallas_decode import PAGED_STEPS
from tree_attention_tpu.ops.tuning import paged_rule_steps
from tree_attention_tpu.serving.block_pool import (
    BlockAllocator,
    ShardedBlockAllocator,
    WindowBlocks,
)
from tree_attention_tpu.serving.host_pool import HostBlockPool
from tree_attention_tpu.serving.prefix_cache import (
    TIER_DEVICE,
    PagedPrefixIndex,
)
from tree_attention_tpu.serving.speculation import (
    Drafter,
    DraftProposal,
    PackedSpec,
    accept_longest_path,
    accept_stochastic_path,
    make_drafter,
    pack_proposal,
    pack_siblings,
)
from tree_attention_tpu.models.hybrid import (
    scan1_path,
    scan_path,
    tail_write_path,
)
from tree_attention_tpu.models.transformer import (
    GQA_SERVED,
    LATENT_SERVED,
    Params,
    TransformerConfig,
    served_layout,
)
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("serving")

# Serving observability. Occupancy/queue/latency metrics are host-loop
# truths (execution-true, not trace-time): the loop sets/observes them as
# slots change hands; token/request/chunk counters count work the engine
# finished.
_SLOTS_OCCUPIED = obs.gauge(
    "serving_slots_occupied",
    "live slots in the serving batch (set once per tick)",
)
_QUEUE_WAIT = obs.histogram(
    "serving_queue_wait_seconds",
    "wall seconds a request waited between becoming visible and admission",
)
_TOKENS = obs.counter(
    "serving_tokens_total",
    "tokens decoded for live slots by executed serving ticks",
)
_REQUESTS = obs.counter(
    "serving_requests_total",
    "requests the engine finished, by outcome",
    labels=("outcome",),
)
_PREFILL_CHUNKS = obs.counter(
    "serving_prefill_chunks_total",
    "prefill chunks scheduled into serving ticks (fused or staged)",
)
_TICKS_AHEAD = obs.counter(
    "serving_ticks_dispatched_ahead_total",
    "tick programs dispatched before the host fetched the tokens of the "
    "program before them (the look-ahead engaged)",
)
_MOE_ROWS = obs.histogram(
    "moe_rows_per_expert",
    "rows a held expert got in one layer of one fetched tick",
    buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096),
)
_MOE_PAIRS_TOTAL = obs.counter(
    "moe_pairs_total",
    "row-expert pairs routed by fetched ticks: held here, held elsewhere "
    "or zero-compute",
)
_MOE_PAIRS_ZERO = obs.counter(
    "moe_pairs_zero",
    "row-expert pairs that fell on zero-compute experts (no weights read)",
)
_MOE_PAIRS_HERE = obs.counter(
    "moe_pairs_here",
    "row-expert pairs that fell on experts held here (computed)",
)
_BLOCK_FIXED_BYTES = obs.gauge(
    "serving_cache_block_fixed_bytes",
    "bytes a pool block holds beside its tokens' rows, all layers (the conv "
    "layers' two-row tails of a hybrid pool; 0 for every other cache)",
)
_STATE_POOL_BYTES = obs.gauge(
    "serving_state_pool_bytes",
    "bytes of a state-space model's per-slot arrays, all layers, by pool "
    "(ssm_state, ssm_tail; set once at build): what the state weighs beside "
    "the K/V pool",
    labels=("pool",),
)
_WEIGHTS_RELAID = obs.gauge(
    "serving_weights_relaid_bytes",
    "bytes of attention input projections the engine serves from in the "
    "out-major served layout, by served leaf (set once at build; 0 for a "
    "model with no such leaf)",
    labels=("leaf",),
)
_TAIL_BLOCKS = obs.counter(
    "serving_conv_tail_blocks_written_total",
    "block tails the conv layers wrote (blocks a tick's rows fell in x conv "
    "layers), by the step they took: row (one row a slot through "
    "conv_tail_step) or block (rows gathered, overlaid and scattered back)",
    labels=("path",),
)
_SSM_STATES = obs.counter(
    "serving_ssm_states_advanced_total",
    "per-slot recurrent states the state-space layers wrote (slots with a "
    "row x state-space layers)",
)
_ROWS_PAST_EXIT = obs.counter(
    "serving_rows_past_exit_total",
    "rows a tick program computed of a model that cuts its rows at a seam "
    "(TransformerConfig.row_cut), by stage: self (the layers up to the "
    "shared full-attention layer: every row) or cross (the layers above it: "
    "one row a slot of a packed program)",
    labels=("stage",),
)
_SCAN_ROWS = obs.counter(
    "ssm_scan_rows_total",
    "rows the state-space layers' chunk scans took in (a tick's chunk rows "
    "x state-space layers), by the scan they took: kernel (one launch of "
    "ssm_chunk_scan a layer, in place on the state pool) or xla (ssm_scan "
    "between a gather and a scatter of the members' states)",
    labels=("path",),
)
# A step's counters that ride the tick's fetch below the slots' rows, in
# this order (``models/decode.py`` ``forward_step``'s ``stats``).
_EVA_SUMMARIES = obs.counter(
    "serving_eva_summaries_written_total",
    "chunk summary rows the EVA layers wrote (chunks a tick's rows closed x "
    "EVA layers)",
)
_STEP_COUNTERS = ("expert_rows", "tail_blocks", "ssm_states",
                  "eva_summaries")
_POOL_ROWS = obs.counter(
    "serving_kv_pool_rows_written_total",
    "token rows the tick programs wrote into the paged pool (a tick's rows x "
    "the layers that cache a row), by the write they took: row (one row a "
    "slot through paged_row_write) or block (whole blocks gathered, "
    "overlaid and scattered back)",
    labels=("path",),
)
_TTFT = obs.histogram(
    "serving_ttft_seconds",
    "wall seconds from request visibility to its first sampled token",
)
_TBT = obs.histogram(
    "serving_tbt_seconds",
    "wall seconds between consecutive tokens of one live slot "
    "(inter-token latency)",
)
_SPEC_PROPOSED = obs.counter(
    "serving_spec_proposed_total",
    "draft tokens proposed into speculative verify ticks",
)
_SPEC_ACCEPTED = obs.counter(
    "serving_spec_accepted_total",
    "proposed draft tokens the verify pass accepted (bonus tokens — the "
    "model's own next token at the divergence point — are not drafts and "
    "do not count)",
)
_SPEC_ACCEPT_RATIO = obs.gauge(
    "serving_spec_acceptance_ratio",
    "lifetime accepted/proposed draft-token ratio (set per verify tick)",
)
_FORKS = obs.counter(
    "serving_forks_total",
    "copy-on-write forks performed (n>1 siblings, best-of-n branches, "
    "and mid-generation fork(uid) branches)",
)
_FORK_SHARED = obs.counter(
    "serving_fork_blocks_shared_total",
    "full ancestor KV blocks a fork SHARED (radix pins + refcounted "
    "CoW blocks) instead of copying or recomputing them",
)
_TREE_BRANCHES = obs.gauge(
    "serving_tree_branches",
    "live sibling branches decoding as token trees in single slots "
    "(set once per tick; 0 when no tree family is in flight)",
)
_SPEC_ACCEPT_SAMPLES = obs.counter(
    "serving_spec_accept_samples_total",
    "per-row stochastic draws consumed by sampled (temperature > 0) "
    "speculative accept walks — the Leviathan ratio test's coupled "
    "samples; greedy verifies draw nothing and do not count",
)


# The ONE retire-outcome vocabulary (ISSUE 10): every way a request can
# leave the engine, threaded unchanged through
# ``serving_requests_total{outcome}``, the per-request span args, and
# ``ServeReport.outcomes`` — a new exit path must add its name here, not
# stringly-type its way in.
OUTCOME_EOS = "eos"              # sampled the request's eos_id
OUTCOME_BUDGET = "budget"        # hit max_new_tokens
OUTCOME_CANCELLED = "cancelled"  # client cancelled (disconnect) mid-flight
OUTCOME_DEADLINE = "deadline"    # per-request deadline expired
OUTCOME_SHED = "shed"            # dropped unserved (drain / load shedding)
OUTCOME_ERROR = "error"          # live-submitted request failed validation
OUTCOMES = (OUTCOME_EOS, OUTCOME_BUDGET, OUTCOME_CANCELLED,
            OUTCOME_DEADLINE, OUTCOME_SHED, OUTCOME_ERROR)


@dataclasses.dataclass
class Request:
    """One generation request for the serving loop.

    ``arrival_tick`` is synthetic-trace time in decode ticks: the request
    becomes visible to the scheduler once the loop's tick counter reaches
    it (0 = already queued at start). ``eos_id`` stops generation early
    when sampled (the EOS token is included in the output).

    The ingress-facing fields (ISSUE 10) all default off:

    - ``deadline_s`` — absolute ``time.monotonic()`` deadline; expired in
      queue the request is rejected unserved, expired in flight it is
      retired with outcome ``deadline`` (work that can no longer meet its
      SLO is shed, not finished late).
    - ``on_token`` / ``on_finish`` — per-request streaming callbacks,
      invoked ON THE ENGINE THREAD as tokens commit / at retire; they
      must hand off fast (the ingress pushes into per-request queues)
      and never raise (a raising callback is logged and dropped, the
      request keeps running).
    - ``visible_at`` — wall-clock visibility override set by live
      sources at submission, so queue-wait/TTFT clocks start when the
      client's request entered the system, not when the loop first saw
      it.
    """

    uid: int
    prompt: Sequence[int]
    max_new_tokens: int
    arrival_tick: int = 0
    eos_id: Optional[int] = None
    deadline_s: Optional[float] = None
    on_token: Optional[Callable[[int], None]] = None
    on_finish: Optional[Callable[["RequestResult"], None]] = None
    visible_at: Optional[float] = None
    # Sampling (ISSUE 15) — None defers to the engine's defaults.
    # ``seed`` salts the request's PRNG key (default: the uid), so a
    # fixed-seed request resamples bit-identically across serves.
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    seed: Optional[int] = None
    # Copy-on-write forking (ISSUE 15): ``n > 1`` serves n completions
    # of one prompt as one prefill + (n-1) forked siblings sharing every
    # full ancestor KV block; ``best_of = k`` runs k branches and
    # streams only the winner by cumulative logprob (requires n == 1).
    # ``fork_at = j`` self-forks the request after its j-th emitted
    # token (the replayable mid-generation-branch trace knob). Branch
    # events stream through ``on_branch_token(index, tok)`` /
    # ``on_branch_finish(index, result)`` when set; otherwise only
    # branch 0 reaches the legacy ``on_token``/``on_finish``.
    n: int = 1
    best_of: Optional[int] = None
    fork_at: Optional[int] = None
    on_branch_token: Optional[Callable[[int, int], None]] = None
    on_branch_finish: Optional[
        Callable[[int, "RequestResult"], None]] = None
    # Cross-process trace context (ISSUE 16): ``(trace_id, parent
    # span_id)`` adopted from the ingress's W3C-traceparent header (or
    # minted there). Rides the Request object through every hop —
    # router relay, replica ingress, disagg prefill→decode adoption —
    # so one Perfetto load of the merged per-process traces shows the
    # request as one connected flow. ``None`` = untraced (direct
    # engine callers; nothing is emitted or allocated).
    trace: Optional[Tuple[str, str]] = None


@dataclasses.dataclass
class RequestResult:
    uid: int
    tokens: List[int]
    prompt_len: int
    arrival_tick: int
    admit_tick: int  # -1: never admitted (cancelled/expired/shed in queue)
    finish_tick: int
    queue_wait_s: float
    completion_s: float  # visible -> finished, wall seconds
    outcome: str  # one of OUTCOMES
    ttft_s: float = 0.0  # visible -> first sampled token, wall seconds
    # Prompt tokens served from the radix prefix cache at admit (0 = cold
    # or cache off). Exposed so a front-end can report per-request reuse
    # upstream — the fleet router's approximate-tree feedback (ISSUE 11)
    # reads it from the ingress's usage block.
    prefix_hit_tokens: int = 0
    # Fork-family branch index (ISSUE 15): 0 = the parent/only branch; a
    # request with n/best_of > 1 (or mid-generation forks) finishes once
    # per branch, all under the family's one uid.
    index: int = 0
    # Sum of the model log-probabilities of this branch's sampled tokens
    # — best-of-n's server-side selection key. Speculative serving tracks
    # it too (ISSUE 20): each verify row's fused output carries the
    # draw's logprob, so accepted bursts accumulate bit-identically to
    # the non-speculative stream.
    cum_logprob: float = 0.0
    # Finished request-cost ledger (ISSUE 16): the dict
    # ``obs.REQLOG.finish`` returned at retire — wall segments, token
    # and KV-block attribution, trace ids. ``None`` when the ledger is
    # disarmed, and on every branch after the first for n>1 families
    # (the ledger is per-uid, closed once).
    ledger: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class _ForkFamily:
    """Host bookkeeping of one n>1 / best-of-n request (ISSUE 15).

    Admission reserves the whole family atomically: the parent's
    worst-case blocks plus each sibling's worst-case NEW blocks (its
    total minus the full ancestor blocks it will share), and one slot
    per branch (siblings park in state ``fpend`` so prefill never
    deadlocks two half-admitted families against each other). The
    siblings fork the moment the parent's first token lands — before
    its EOS check, so even a one-token parent still yields n
    independent samples — each re-consuming the last prompt token into
    its own copy-on-write tail block and sampling its own first token
    under its own key."""

    req: Request
    parent_slot: int
    sibling_slots: List[int]
    sib_reserve: int       # worst-case NEW blocks per sibling
    hold: int              # unspent family reservation (siblings not yet
    #                        forked; returned on pre-fork retirement)
    best_of: bool
    branches: int
    forked: bool = False
    done: List[RequestResult] = dataclasses.field(default_factory=list)
    # Token-tree sibling decode (ISSUE 20): the family's k branches
    # share ONE slot, replaying their divergent suffixes as one
    # verify-shaped row bundle per tick under tree_mask/positions. The
    # device cache is frozen at ``base_len`` committed rows (the shared
    # ancestor path); each live branch's tokens past ``fork_len - 1``
    # are its private suffix, re-verified every tick. Branch b's j-th
    # token samples under fold_in(fold_in(fold_in(base, salt), b),
    # fork_len + depth) — the fork-slot path's exact key chain, so the
    # two layouts are token-identical under one seed.
    tree: bool = False
    base_len: int = 0      # frozen committed length (shared ancestors)
    fork_len: int = 0      # emitted tokens shared by all branches + 1
    br_tokens: List[List[int]] = dataclasses.field(default_factory=list)
    br_cum_lp: List[float] = dataclasses.field(default_factory=list)
    br_live: List[bool] = dataclasses.field(default_factory=list)
    br_index: List[int] = dataclasses.field(default_factory=list)
    br_ttft: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Tail:
    """What one dispatched tick program still owes the host: its fetch,
    the tokens it sampled, the retirements they cause and its flight
    record. ``SlotServer.serve`` lands a tail one tick late, after the
    NEXT program's dispatch, whenever that program can be planned from
    counts alone (ISSUE 32); until then ``SlotServer._tail`` holds it and
    the head plans each of its rows as one token in flight.

    Every field describes THIS program: the record built when the tail
    lands takes ``kind``/``tq``/``group``/the rows/the chunk plan/the
    live slots from here, not from the iteration that lands it."""

    tick: int
    t_s: float             # when the device could start it (monotonic):
    #                        the tick's top, or the landing of the
    #                        program it was dispatched behind
    ahead: bool            # dispatched before the tail before it landed
    why: Optional[str]     # sync_reason where it was not
    kind: str
    tq: int
    group: int             # members of a packed tick's chunk group
    rows_useful: int
    chunk_tokens: int
    plan: List[Tuple[int, int, bool]]
    live: List[int]        # slots with a decode row in the program
    awaits: List[int]      # slots whose first token rides it
    reqs: List[Optional[Request]]   # slot -> the request it ran for
    queue_depth: int
    pending: int
    draining: bool
    fused: Any = None      # the (S [+ expert rows], 2) fetch vehicle
    all_tok: Any = None    # a verify tick's (S, 1 + Tq, 2) instead
    spec_plan: Any = None
    tree_plan: Any = None
    spec_width: int = 0
    # (entries of the paged kernels' work lists, of the whole slots x steps
    # rectangles) over the program's groups of rows, a layer.
    kv_steps: Tuple[int, int] = (0, 0)
    # Rows x layers the program wrote into the pool: (by the row kernel, by
    # the block path) (``_count_pool_rows``).
    pool_rows: Tuple[int, int] = (0, 0)
    # Slots with one row whose conv tails take the row kernel
    # (``_count_tail_rows``).
    tail_rows: int = 0
    # Chunk rows x state-space layers: (through the scan kernel, through
    # the XLA scan) (``_count_scan_rows``).
    scan_rows: Tuple[int, int] = (0, 0)
    # The head's per-tick counters, frozen when the tail is left pending
    # (the iteration that lands it has counted its own by then).
    counts: Optional[Dict[str, Any]] = None

    def __post_init__(self):
        # The slots with a token in flight: the head asks per slot.
        self.rows = frozenset(self.live).union(self.awaits)

    def flying(self, slot: int, req: Optional[Request]) -> bool:
        """True when ``slot`` has a token in flight for ``req``: a row
        of this program whose sample the host has not seen. A slot the
        sweep retired (or re-admitted) since the dispatch has none: its
        row is computed and thrown away."""
        return (req is not None and self.reqs[slot] is req
                and slot in self.rows)


@dataclasses.dataclass
class ServeReport:
    """One serve() run: per-request results plus aggregate accounting."""

    results: List[RequestResult]
    ticks: int
    wall_s: float
    tokens_generated: int
    mean_occupancy: float  # live slots per executed decode tick
    tbt_s: List[float] = dataclasses.field(default_factory=list)
    slo: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Prefix-reuse accounting for THIS run (diff of the pool's lifetime
    # stats over the serve() call); empty when the cache is off.
    prefix: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Paged-pool accounting (block occupancy at run end + peak).
    kv: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Speculative-decoding accounting for THIS run (proposed/accepted
    # draft tokens, acceptance_rate, verify ticks); empty when off.
    spec: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Disaggregated prefill/decode accounting (ISSUE 12): handoff counts,
    # queue peak, blocks transferred, kv_bytes_moved (pinned 0 in-process)
    # — empty for a fused engine.
    handoff: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Per-request ledger aggregates for THIS run (ISSUE 16):
    # ``obs.aggregate_ledgers`` over the finished ledgers attached to
    # results — phase-wall sums/p50s, token and KV-block totals. Empty
    # when the request ledger is disarmed.
    requests: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # One table a tick program built by the run's end, from its operations
    # to the parts of the model (``SlotServer.program_tables``); empty
    # unless the flight recorder or the span tracer was on.
    programs: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # The start-up record as this run ends (``obs/flight.py``
    # ``StartupRecord.snapshot``): what the process did before its first
    # tick and every tick program built since, this ``serve()`` call the
    # one open ``startup:serve`` span.
    startup: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens_generated / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def outcomes(self) -> Dict[str, int]:
        """Retire-outcome counts over the run (the OUTCOMES vocabulary;
        only outcomes that occurred appear)."""
        out: Dict[str, int] = {}
        for r in self.results:
            out[r.outcome] = out.get(r.outcome, 0) + 1
        return {k: out[k] for k in sorted(out)}

    def completion_percentiles(self) -> Dict[str, float]:
        cs = sorted(r.completion_s for r in self.results)
        return {"p50_s": percentile(cs, 0.50), "p95_s": percentile(cs, 0.95)}

    def latency_percentiles(self) -> Dict[str, float]:
        """TTFT (visible -> first token) and inter-token latency (gap
        between consecutive tokens of one slot, pooled over slots) — the
        two serving latencies chunked prefill exists to protect. Requests
        that never produced a token (cancelled/expired/shed unserved)
        have no TTFT and are excluded rather than skewing the
        distribution toward 0."""
        ttft = sorted(r.ttft_s for r in self.results if r.tokens)
        tbt = sorted(self.tbt_s)
        return {
            "ttft_p50_s": percentile(ttft, 0.50),
            "ttft_p95_s": percentile(ttft, 0.95),
            "tbt_p50_s": percentile(tbt, 0.50),
            "tbt_p95_s": percentile(tbt, 0.95),
        }

    def as_dict(self) -> Dict[str, Any]:
        waits = sorted(r.queue_wait_s for r in self.results)
        return {
            "requests": len(self.results),
            "ticks": self.ticks,
            "wall_s": round(self.wall_s, 4),
            "tokens_generated": self.tokens_generated,
            "tokens_per_sec": round(self.tokens_per_sec, 1),
            "mean_occupancy": round(self.mean_occupancy, 2),
            "queue_wait_p50_s": round(waits[len(waits) // 2], 4) if waits else 0.0,
            "outcomes": self.outcomes,
            **{k: round(v, 4) for k, v in self.completion_percentiles().items()},
            **{k: round(v, 5) for k, v in self.latency_percentiles().items()},
            **({"slo": self.slo} if self.slo else {}),
            **({"prefix": self.prefix} if self.prefix else {}),
            **({"kv": self.kv} if self.kv else {}),
            **({"spec": self.spec} if self.spec else {}),
            **({"handoff": self.handoff} if self.handoff else {}),
            **({"request_ledgers": self.requests} if self.requests else {}),
            **({"programs": self.programs} if self.programs else {}),
            **({"startup": self.startup} if self.startup else {}),
        }


def synthetic_trace(
    n_requests: int,
    *,
    prompt_len: int = 32,
    prompt_jitter: int = 0,
    max_new_tokens: int = 16,
    arrival_every: int = 0,
    vocab_size: int = 256,
    seed: int = 0,
    eos_id: Optional[int] = None,
    prefix_share: float = 0.0,
    prefix_len: int = 0,
    prefix_count: int = 1,
    prefix_seed: Optional[int] = None,
    n: int = 1,
    best_of: int = 0,
    fork_at: int = 0,
) -> List[Request]:
    """A reproducible request trace: random prompts, optional length jitter,
    arrivals every ``arrival_every`` ticks (0 = all queued at start).

    ``prefix_share`` / ``prefix_len`` model production traffic's shared
    system prompts and templates (the workload the prefix cache exists
    for): that fraction of requests draws its first ``prefix_len`` tokens
    from a small fixed set of ``prefix_count`` shared prefixes (round-
    robin) and only the remainder is per-request random. The shared part
    is clamped to ``plen - 1`` so every prompt keeps a unique-able
    suffix token. ``prefix_seed`` draws the SHARED prefixes from their
    own rng stream, so traces with different ``seed`` values (fresh
    per-request randomness) can still share one prefix population — the
    shape a warm-pool steady-state measurement needs; ``None`` keeps
    everything on the one ``seed`` stream.

    ``n`` / ``best_of`` / ``fork_at`` (ISSUE 15) stamp the fork-family
    fields onto every request, so fork workloads replay through the
    same bench and chaos harnesses as everything else: ``n > 1`` makes
    each trace entry an n-completion family, ``best_of > 1`` a
    server-side-selected one, and ``fork_at > 0`` self-forks each
    request after that many emitted tokens (the mid-generation-branch
    chaos shape).
    """
    if not 0.0 <= prefix_share <= 1.0:
        raise ValueError(f"prefix_share must be in [0, 1], "
                         f"got {prefix_share}")
    rng = np.random.default_rng(seed)
    prefix_rng = rng if prefix_seed is None else \
        np.random.default_rng(prefix_seed)
    shared = [
        prefix_rng.integers(0, vocab_size,
                            size=max(prefix_len, 0)).astype(np.int32)
        for _ in range(max(prefix_count, 1))
    ] if prefix_share > 0.0 and prefix_len > 0 else []
    reqs = []
    n_shared = 0
    for i in range(n_requests):
        lo = max(1, prompt_len - prompt_jitter)
        hi = prompt_len + prompt_jitter
        plen = int(rng.integers(lo, hi + 1))
        if shared and rng.random() < prefix_share:
            p = min(prefix_len, plen - 1)
            prompt = np.concatenate([
                shared[n_shared % len(shared)][:p],
                rng.integers(0, vocab_size, size=plen - p).astype(np.int32),
            ])
            n_shared += 1
        else:
            prompt = rng.integers(0, vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(
            uid=i,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            arrival_tick=i * arrival_every,
            eos_id=eos_id,
            n=max(n, 1),
            best_of=best_of if best_of > 1 else None,
            fork_at=fork_at if fork_at > 0 else None,
        ))
    return reqs


class RequestSource:
    """Where the tick loop gets its work (ISSUE 10).

    ``serve()`` used to eat a pre-built request list; a real ingress
    feeds requests as clients produce them. This is the seam: the loop
    calls :meth:`poll` once per tick for newly visible requests,
    :meth:`next_arrival` to fast-forward synthetic time across idle
    gaps, :meth:`wait` to block briefly when a live feeder has nothing
    yet, and :meth:`close` when draining. The base class is an empty,
    already-exhausted source; :class:`StaticRequestSource` wraps the
    legacy list, and the ingress's ``QueueRequestSource``
    (:mod:`~tree_attention_tpu.serving.ingress`) is the thread-safe
    live feeder.
    """

    def poll(self, tick: int) -> List[Request]:
        """Requests that became visible by ``tick`` (each returned
        exactly once)."""
        return []

    def next_arrival(self) -> Optional[int]:
        """The next future arrival tick (synthetic sources only), or
        None when arrivals are wall-clock driven or exhausted."""
        return None

    def wait(self, timeout: float) -> bool:
        """Block up to ``timeout`` seconds for new work (live feeders);
        returns True if work may be available. Synthetic sources return
        False immediately — the loop fast-forwards instead of sleeping."""
        return False

    def close(self) -> None:
        """Stop accepting/producing new requests (graceful drain)."""

    @property
    def exhausted(self) -> bool:
        """True when no request will ever be returned again."""
        return True


class StaticRequestSource(RequestSource):
    """The legacy shape: a fixed trace, visible by ``arrival_tick``."""

    def __init__(self, requests: Sequence[Request]):
        self._reqs = sorted(requests,
                            key=lambda r: (r.arrival_tick, r.uid))
        self._pos = 0

    def poll(self, tick: int) -> List[Request]:
        out: List[Request] = []
        while (self._pos < len(self._reqs)
               and self._reqs[self._pos].arrival_tick <= tick):
            out.append(self._reqs[self._pos])
            self._pos += 1
        return out

    def next_arrival(self) -> Optional[int]:
        if self._pos >= len(self._reqs):
            return None
        return self._reqs[self._pos].arrival_tick

    def close(self) -> None:
        self._pos = len(self._reqs)  # drop the rest: nothing more arrives

    @property
    def exhausted(self) -> bool:
        return self._pos >= len(self._reqs)


def serving_params(params: Params) -> Params:
    """``params`` as an engine serves from them: the attention input
    projections in :func:`~tree_attention_tpu.models.transformer.
    served_layout`'s form, every other leaf the caller's own array. The
    weights' outer format (``init_params``', a caller's ``params=``) is what
    comes in; a tree that was here before passes through, so the front ends
    that build several engines from one model (a disaggregated pair, a
    fleet's replicas) re-lay it once and share it. Sets
    ``serving_weights_relaid_bytes`` to what the tree holds re-laid, and
    is a ``startup:params`` span of the start-up record with those
    ``bytes`` (it waits for the tree, so a draw or a placement the caller
    queued is in it)."""
    t_enter = time.monotonic()
    # Waited for: an outer leaf the caller drops on return is then free
    # before the pool is made, not whenever the queued transposition ends.
    served = jax.block_until_ready(served_layout(params))
    held = dict.fromkeys((GQA_SERVED, LATENT_SERVED), 0)
    for path, leaf in jax.tree_util.tree_leaves_with_path(served):
        name = getattr(path[-1], "key", None)
        if name in held:
            held[name] += leaf.size * leaf.dtype.itemsize
    if obs.REGISTRY.enabled:
        for name, nbytes in held.items():
            _WEIGHTS_RELAID.labels(leaf=name).set(nbytes)
    if jax.tree.structure(served) != jax.tree.structure(params):
        log.info("serving layout: attention input projections re-laid "
                 "out-major, bytes by leaf: %s", held)
    STARTUP.add("params", t_enter, bytes=sum(held.values()))
    return served


def _bucket(n: int, cap: int, floor: int = 8) -> int:
    """Pad a count up to a power-of-two bucket (bounded compiles: one
    program per bucket, not per distinct count), capped at ``cap``."""
    b = floor
    while b < n:
        b *= 2
    return min(b, cap)


class SlotServer:
    """Continuous-batching engine: S slots, a queue, one compiled mixed step.

    Args:
      params / cfg: the model served.
      slots: batch size S of the slot cache — the max concurrent requests.
      cache_len: per-slot KV capacity; every admitted request needs
        ``prompt_len + max_new_tokens <= cache_len``.
      mesh (+ axis names): run the tick programs over a mesh; with
        ``kv_shard="seq"`` the pool is sharded over its sequence axis and
        the ragged decode step runs the tree merge per tick.
      quantize: serve from an int8 pool — each request prefills exactly
        (staged) then quantizes its blocks, each under its own frozen
        scales (the quantize-after-prefill contract, per block).
      quant_kernel: which q8 kernel decode ticks run (``"q8q"`` / ``"q8"``).
      temperature / seed: sampling (0 = greedy, the deterministic default).
      prefill_chunk: max prompt tokens one tick may write for one slot
        (clamped to ``cache_len``). Smaller = lower inter-token latency
        spikes for live slots, more ticks per prompt.
      prefill_budget: max TOTAL prompt tokens per tick across prefilling
        slots — the Sarathi-style token budget; live decode tokens always
        ride beside them. Default: ``prefill_chunk``, one chunk a tick.
        A tick computes the rows it carries (``C·Tq + S`` with ``C =
        min(slots, ceil(prefill_budget / prefill_chunk))`` members in its
        chunk group, fixed at build), so the budget is what a tick with
        prompt work costs every live slot in inter-token latency: a
        larger one admits prompts faster and stretches those ticks.
      slo_ttft / slo_tbt / slo_window: the sliding-window SLO monitor's
        targets (seconds) and sample window — a retired request counts
        toward goodput iff its TTFT and worst inter-token gap both met
        the target. The monitor always feeds ``ServeReport.slo``; its
        gauges only publish while the metrics registry records.
      prefix_cache: enable shared-prompt KV reuse — admissions match
        their prompt against a radix tree of published prefixes and skip
        prefill for the matched blocks (reference-in-place — zero KV
        bytes moved, int8 included since per-block scales made its
        blocks shareable, ISSUE 13).
      prefix_block: tokens per prefix block (power of two; the
        match/publish granularity). It is also the default page size
        (``kv_block``) so matching stays block-aligned with the tables.
      prefix_pool_blocks: how many blocks the prefix tree may RETAIN
        (LRU-evicted at refcount 0): a retention cap on the shared pool
        (default None = bounded by the pool itself). The CLI's
        ``--prefix-pool-blocks`` is deprecated in favor of the unified
        ``--kv-blocks`` budget.
      kv_block: tokens per pool block (power of two). Default: follows
        ``prefix_block`` when the prefix cache is on (match granularity
        == page size), else 64. On a real TPU keep it >= the dtype's
        minimum sublane tile (8 f32 / 16 bf16 / 32 int8).
      kv_blocks: TOTAL pool capacity in blocks — the one KV memory
        budget (slots and prefix cache share it). Default:
        ``slots × ceil(cache_len / kv_block)``: every slot can fill its
        table. Size it smaller to over-subscribe:
        admissions whose worst case cannot be reserved wait in the
        queue, and a request that could never fit fails validation with
        a clear message.
      speculate: draft-and-verify speculative decoding (arXiv:2211.17192)
        on the mixed-Tq tick. Every live slot's tick becomes a verify
        chunk: a host drafter proposes up to ``draft_k`` tokens, the ONE
        compiled step scores them all (prefill-style), the longest
        accepted path commits at once and rejections roll the slot's
        device length back (paged blocks past the rollback unmap without
        leaking pool capacity). Greedy only (``temperature`` must be 0 —
        the accept rule is exact there): committed tokens are
        token-for-token identical to non-speculative decode.
      draft_k: max draft tokens per slot per verify tick (1..31 — the
        tree mask packs into int32 bitmasks). One verify commits between
        1 and ``draft_k + 1`` tokens.
      drafter: ``"ngram"`` (default — prompt-lookup over the slot's own
        history, zero extra model), ``"ngram-tree"`` (multi-branch token
        trees verified under the tree-attention mask, SpecInfer
        arXiv:2305.09781), or any :class:`~tree_attention_tpu.serving
        .speculation.Drafter` instance (e.g. ``DraftModelDrafter``).
        Tree proposals fall back to their root-path chain where the
        verify step runs the tree merge (an int8 or sequence-sharded
        pool on a >1-way seq mesh), which takes no mask.
      block_pool: bring-your-own :class:`BlockAllocator` (disaggregated
        serving, ISSUE 12: two engines — a prefill worker and a decode
        worker — share ONE pool ledger so a finished prefill's blocks
        hand over by pure ownership transfer). ``kv_blocks`` defaults
        to (and must equal) the pool's capacity.
        The DEVICE pool arrays are shared by the orchestrator
        (:class:`~tree_attention_tpu.serving.disagg.DisaggServer`
        rebinds both caches to one array set and relays after every
        dispatch); this engine still allocates its own transient
        initial arrays, which the rebind immediately frees.
      prefix_index: bring-your-own
        :class:`~tree_attention_tpu.serving.prefix_cache
        .PagedPrefixIndex` over ``block_pool`` (the disaggregated pair
        shares one radix tree: the prefill worker matches/adopts, the
        decode worker holds the request's pins until retire). Implies
        the prefix cache is on (int8 included since per-block scales
        made int8 blocks shareable, ISSUE 13); the index's block size
        must equal ``kv_block``.
      host_blocks: KV tiering (ISSUE 13) — capacity of the host-RAM
        demotion tier in blocks (``--host-blocks``; 0 = off). Radix
        eviction then DEMOTES refcount-0 blocks into pinned host memory
        (async D2H staged off the tick, one jitted gather per batch)
        instead of freeing them, and a prefix hit on a demoted path
        restores it with one batched H2D scatter into freshly allocated
        device blocks — the effective prefix cache becomes
        host-RAM-sized. Requires the prefix cache (demotion IS radix
        eviction).
    """

    def __init__(
        self,
        params: Params,
        cfg: TransformerConfig,
        *,
        slots: int,
        cache_len: int,
        mesh: Optional[Mesh] = None,
        quantize: bool = False,
        quant_kernel: str = "q8q",
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        prefill_chunk: int = 256,
        prefill_budget: Optional[int] = None,
        slo_ttft: float = 1.0,
        slo_tbt: float = 0.2,
        slo_window: int = 1024,
        prefix_cache: bool = False,
        prefix_block: int = 64,
        prefix_pool_blocks: Optional[int] = None,
        kv_block: Optional[int] = None,
        kv_blocks: Optional[int] = None,
        kv_shard: str = "replicated",
        speculate: bool = False,
        draft_k: int = 4,
        drafter: Union[str, Drafter, None] = None,
        block_pool: Optional[BlockAllocator] = None,
        prefix_index: Optional[Any] = None,
        host_blocks: int = 0,
        tree_sampling: bool = True,
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if kv_shard not in ("replicated", "seq"):
            raise ValueError(
                f"kv_shard must be 'replicated' or 'seq', got {kv_shard!r}"
            )
        if host_blocks < 0:
            raise ValueError(f"host_blocks must be >= 0, got {host_blocks}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {prefill_budget}"
            )
        if cfg.cache_kind != "kv":
            # What the latent pool, the hybrid pool, the window pools and
            # the state pool are not built for is refused here, by the
            # cache kind's name, never served wrong.
            why_not = {
                "latent": "the latent kernel takes no tree_mask",
                "hybrid": "a draft that is rejected has overwritten the "
                          "conv layers' tails, which cannot roll back",
                "window": "a rollback would need window blocks that were "
                          "given back",
                "state": "a draft that is rejected has rewritten the "
                         "recurrent state, which cannot roll back",
                "eva": "a draft that is rejected and crossed a chunk or "
                       "window boundary has written a summary row or given "
                       "blocks back",
                "state_window": "a draft that is rejected has rewritten "
                                "the recurrent state and given window "
                                "blocks back",
            }[cfg.cache_kind]
            for on, what in (
                (quantize, f"int8 {cfg.cache_kind} rows (quantize=True)"),
                (kv_shard == "seq",
                 "a sequence-sharded pool (kv_shard='seq')"),
                (bool(host_blocks), "the host tier (host_blocks > 0)"),
                (speculate, f"speculation ({why_not})"),
                (cfg.cache_kind in ("window", "eva", "state_window") and (
                    block_pool is not None or prefix_index is not None),
                 "disaggregation (a shared block_pool / prefix_index: the "
                 "window layers' blocks are one engine's)"),
                # A summary block spans block x chunk positions where the
                # prefix tree publishes a block of positions (ROADMAP 2A).
                (cfg.cache_kind == "eva" and prefix_cache,
                 "the prefix cache (prefix_cache=True: a hit needs the "
                 "summary rows of the matched prefix)"),
                # A recurrent state is an array a slot that every token
                # rewrites whole: nothing holds it as it was at a block
                # boundary (ROADMAP 2A item 9: snapshots).
                (cfg.cache_kind in ("state", "state_window") and (
                    block_pool is not None or prefix_index is not None),
                 "disaggregation (a shared block_pool / prefix_index: the "
                 "hand-over would need the slot's state)"),
                (cfg.cache_kind in ("state", "state_window")
                 and prefix_cache,
                 "the prefix cache (prefix_cache=True: a hit needs the "
                 "state at the matched boundary)"),
            ):
                if on:
                    raise ValueError(
                        f"a model served from the {cfg.cache_kind} pool "
                        f"(TransformerConfig.cache_kind) does not serve "
                        f"with {what}: not built for that pool")
        self.params = serving_params(params)   # a startup:params span
        # From here to the end of this constructor: ``startup:engine``.
        t_build = time.monotonic()
        self.cfg = cfg
        self.slots = slots
        self.cache_len = cache_len
        self.mesh = mesh
        self.quantize = quantize
        self.quant_kernel = quant_kernel
        self.temperature = float(temperature)
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0 (0 = greedy)")
        self.top_k = int(top_k)
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 = off)")
        self._speculate = bool(speculate)
        if self._speculate:
            # Sampled acceptance (temperature > 0) runs the Leviathan
            # ratio test (arXiv:2211.17192) specialised to point-mass
            # drafts: each verify row draws from the model's own
            # distribution under the request's fold_in(key, j) stream
            # key and accepts the draft iff the draw reproduces it —
            # distribution-exact AND token-identical to the non-spec
            # sampled path under the same seed. Temperature 0 keeps the
            # legacy greedy accept rule bit-for-bit (ISSUE 20).
            if not 1 <= draft_k <= 31:
                raise ValueError(
                    f"draft_k must be in [1, 31] (int32 tree bitmasks), "
                    f"got {draft_k}"
                )
        self.draft_k = draft_k
        self.prefill_chunk = min(prefill_chunk, cache_len)
        self.prefill_budget = (
            self.prefill_chunk if prefill_budget is None else prefill_budget
        )
        # Members of a packed tick's chunk group: what the budget admits
        # at one chunk each. Fixed for the engine's life, so the tick
        # programs are keyed by the Tq bucket alone (a program keyed by how
        # many slots chunk at once would first compile under load).
        self._chunk_group = min(
            slots, -(-self.prefill_budget // self.prefill_chunk))
        # Per-slot sampling state (ISSUE 15). Each slot's PRNG key is
        # its REQUEST's key (fold_in(base, seed-or-uid) then the branch
        # index); the j-th emitted token folds j in — see
        # models.decode.sample_slots for the reproducibility contract.
        # The host mirrors (_temp_np/_topk_np) ride every dispatch as
        # plain operands, so per-request sampling params never recompile.
        self._base_key = jax.random.PRNGKey(seed)
        self._keys = jnp.zeros((slots, 2), jnp.uint32)
        self._lp = jnp.zeros((slots,), jnp.float32)
        self._lp_host = np.zeros((slots,), np.float32)
        self._temp_np = np.zeros((slots,), np.float32)
        self._topk_np = np.zeros((slots,), np.int32)
        # Host mirror of each slot's PRNG salt (seed-or-uid): tree
        # sibling rows re-derive the full fold chain IN-PROGRAM from
        # (salt, branch, stream index) operands, so the verify step
        # needs the raw salt, not just the installed per-slot key.
        self._salt_np = np.zeros((slots,), np.int32)
        self._slot_index = [0] * slots
        self._slot_cum_lp = [0.0] * slots
        self._seed_key = jax.jit(self._seed_key_fn, donate_argnums=(0,))
        # Copy-on-write fork state (ISSUE 15): live fork families by
        # uid, per-slot refcount-shared block sets (released — not
        # freed — on retire; the last owner's release frees), pending
        # device-length resets for freshly forked live slots, the
        # fork(uid) mailbox's deferral carry, and per-tick flight
        # counters.
        self._families: Dict[int, _ForkFamily] = {}
        # Token-tree sibling families by SLOT (ISSUE 20): the n>1 /
        # best-of families whose branches decode as one packed token
        # tree in a single slot instead of n forked slots. Every fam
        # here is also in _families (the join/best-of machinery is
        # shared); the per-tick counters feed the flight recorder.
        self._tree_fams: Dict[int, _ForkFamily] = {}
        # The latent kernel takes no tree_mask, and a tree's siblings
        # would overwrite one another's conv tails: such a model's
        # families fork into slots on shared blocks (a fork copies its
        # partial block, tails and all).
        self._tree_sampling = bool(tree_sampling) and cfg.cache_kind == "kv"
        self._tick_tree_branches = 0
        self._tick_branch_retired = 0
        self._slot_shared: List[set] = [set() for _ in range(slots)]
        self._live_reset: Dict[int, int] = {}
        self._fork_uids: List[int] = []
        self._fork_carry: Dict[int, int] = {}
        self._uid_next_index: Dict[int, int] = {}
        self._forks_life = 0
        self._fork_shared_life = 0
        self._tick_forks = 0
        self._tick_fork_shared = 0
        self._fork_copy = jax.jit(self._fork_copy_fn, donate_argnums=(0,))
        self._sibling_first = jax.jit(self._sibling_first_fn,
                                      donate_argnums=(0, 1))
        self._tree_first = jax.jit(self._tree_first_fn)
        self._tree_branches_life = 0
        self._tree_fams_life = 0
        # Per-slot stash of the prompt-end logits row (device, (V,)) —
        # kept only while the slot's fork family is waiting to expand.
        self._slot_logits: List[Optional[Any]] = [None] * slots

        kw = {"mesh": mesh} if mesh is not None else {}
        self._fs_kw = dict(kw)
        if kv_shard == "seq":
            # Only the batched per-tick steps run on the sharded pool;
            # the B=1 staging programs below use a CONTIGUOUS cache and
            # must not see the flag.
            self._fs_kw["kv_shard"] = "seq"
        # B=1 programs (the quantized staging cache's) cannot shard over a
        # data axis (1 does not divide it) — and need no data parallelism
        # anyway; the batched per-tick step keeps the full mesh spec.
        self._prefill_kw = (
            dict(kw, data_axis=None) if mesh is not None else {}
        )
        self._seq_shards = 1
        if mesh is not None:
            from tree_attention_tpu.parallel.mesh import AXIS_SEQ

            self._seq_shards = max(mesh.shape.get(AXIS_SEQ, 1), 1)
        # Sequence-sharded pool (ISSUE 18): per-device pool bytes drop to
        # 1/W; the allocator range-partitions global block ids over the
        # mesh's seq shards and decode attention runs the shard_map'd
        # 3-collective tree merge. Host bookkeeping (tables, radix keys,
        # private/shared sets) stays in GLOBAL ids throughout — the shard
        # rebase happens only inside the device-side shard_map bodies.
        self.kv_shard = kv_shard
        self._kv_seq_sharded = kv_shard == "seq" and self._seq_shards > 1
        # int8 pool bytes per token — what an int8 paged hit's dequant
        # gather into staging actually moves (ISSUE 13).
        self._kv_token_bytes_q = 2 * cfg.cache_layers * cfg.n_kv_heads \
            * cfg.d_head
        if kv_block is None:
            # Matching granularity == page size keeps radix hits
            # table-aligned (a matched prefix IS whole table entries).
            kv_block = prefix_block if prefix_cache else 64
        elif prefix_cache and kv_block != prefix_block:
            # Honoring only one of them silently would make the
            # recorded config contradict the running granularity.
            raise ValueError(
                f"prefix_block ({prefix_block}) must "
                f"equal kv_block ({kv_block}) — radix matching "
                f"happens at page granularity (pass one of them, or "
                f"equal values)"
            )
        self.kv_block = kv_block
        # Positions a ROW of the pool under the first table stands for: 1,
        # but an EVA model's, whose rows there are one summary a chunk (a
        # block of that table then spans ``kv_block x chunk`` positions).
        self._row_span = cfg.chunk if cfg.cache_kind == "eva" else 1
        self._npb = -(-cache_len // (kv_block * self._row_span))  # table width (blocks)
        if block_pool is not None:
            # Shared-pool mode (disaggregation): the allocator is the
            # ONE ledger both workers admit/retire against, so this
            # engine's view of capacity must be the pool's — a
            # different kv_blocks would let _validate accept requests
            # the shared pool can never hold (or reject ones it can).
            if kv_blocks is not None and kv_blocks != block_pool.blocks:
                raise ValueError(
                    f"kv_blocks {kv_blocks} contradicts the shared "
                    f"block_pool's capacity {block_pool.blocks}"
                )
            if kv_shard == "seq" and (
                not isinstance(block_pool, ShardedBlockAllocator)
                or block_pool.shards != self._seq_shards
            ):
                # Both workers' device pools must agree on the id →
                # shard placement rule, and the shared ledger is
                # where that rule lives.
                raise ValueError(
                    "kv_shard='seq' with a shared block_pool needs a "
                    f"ShardedBlockAllocator over {self._seq_shards} "
                    "shards (one placement rule for every worker)"
                )
            self.kv_blocks = block_pool.blocks
            self._pool = block_pool
        else:
            self.kv_blocks = (
                slots * self._npb if kv_blocks is None else kv_blocks
            )
            if kv_shard == "seq":
                # Round UP to a whole number of per-shard slices: the
                # device pool and the ledger must split evenly, and
                # extra blocks only ever ADD capacity.
                w = self._seq_shards
                self.kv_blocks = -(-self.kv_blocks // w) * w
                self._pool = ShardedBlockAllocator(self.kv_blocks, w)
            else:
                self._pool = BlockAllocator(self.kv_blocks)
        # KV tiering (ISSUE 13): the host-RAM demotion tier under
        # the device pool. Created here (the prefix index attaches
        # to it below); the allocator's flusher hook lets a dry
        # reservation force the staged D2H batch mid-tick, but the
        # steady-state flush point is the end of the tick loop.
        self.host_blocks = host_blocks
        self._host_pool: Optional[HostBlockPool] = None
        self._tick_restored = 0
        if host_blocks:
            if prefix_index is not None:
                raise ValueError(
                    "host_blocks tiering with a shared prefix_index: "
                    "build the index with its own host_pool instead "
                    "(the tier belongs to the shared tree, not one "
                    "engine)"
                )
            if not prefix_cache:
                raise ValueError(
                    "host_blocks KV tiering requires prefix_cache=True "
                    "(demotion is what radix eviction becomes; with "
                    "no radix tree nothing ever demotes)"
                )
            self.attach_host_tier(HostBlockPool(
                host_blocks,
                n_layers=cfg.cache_layers,
                n_kv_heads=cfg.n_kv_heads,
                block=kv_block,
                d_head=cfg.d_head,
                dtype=np.int8 if quantize else np.dtype(
                    jnp.dtype(cfg.dtype).name),
                quantized=quantize,
            ))
        self._host_table = np.zeros((slots, self._npb), np.int32)
        self._table_dirty = False  # device table starts all-zero too
        self._slot_nblocks = [0] * slots
        self._slot_private: List[set] = [set() for _ in range(slots)]
        self._slot_reserve = [0] * slots
        self._peak_blocks_used = 0
        self._defer_gen = -1  # see the admit loop's generation latch
        # Layers whose block counts differ (a model with sliding-window
        # layers): their pool's ledger, their table and what each slot
        # holds of them. None for every other model.
        self._win: Optional[WindowBlocks] = None
        self._tick_wfreed = 0
        self._tick_kv_kinds: Dict[str, Tuple[int, int]] = {}
        self._tick_eva: Dict[str, int] = {}
        # The paged kernels' lists a tick, by kind of layer, each with the
        # rule its rows see by (``models/decode.py`` ``window_rules``).
        # (name, rule, whether its pool lies under the second table)
        rule, wrule = window_rules(cfg)
        first, second = ("summary", "local") if cfg.cache_kind == "eva" \
            else ("full", "window")
        self._kv_kinds: Tuple[Tuple[str, Any, bool], ...] = (
            (first, rule, False),) + (
            ((second, wrule, True),) if wrule is not None else ())
        if cfg.cache_kind in ("window", "eva", "state_window"):
            self._win = WindowBlocks(
                slots=slots, table_width=-(-cache_len // kv_block),
                block=kv_block, window=cfg.window, chunk=self.prefill_chunk,
                rule=cfg.window_rule)
            kw = dict(kw, window_blocks=self._win.blocks)
        self.cache = init_paged_cache(
            cfg, slots, cache_len, self.kv_blocks,
            block=kv_block, quantize=quantize, kv_shard=kv_shard, **kw
        )
        # Bytes a cached token takes over all layers, read from the pool
        # the model built (a latent row is not 2·Hkv·D): the report's
        # ``kv.token_bytes``.
        self._kv_token_bytes = cache_token_bytes(self.cache)
        # What a block holds beside its tokens' rows (a hybrid pool's conv
        # tails): the report's ``kv.block_fixed_bytes``.
        self._kv_block_fixed_bytes = cache_block_fixed_bytes(self.cache)
        self._conv_layers = cfg.conv_layers   # the tail pool's depth
        # ... and its shape and dtype, for the path a tick's tails take
        # (``_count_tail_rows``).
        self._tail_pool = jax.ShapeDtypeStruct(
            self.cache.tail.shape, self.cache.tail.dtype) \
            if cfg.conv_layers else None
        self._ssm_layers = cfg.ssm_layers     # the state pool's depth
        # ... and its shape and dtype, for the scan a tick's chunk rows take
        # (``_count_scan_rows``).
        self._state_pool = jax.ShapeDtypeStruct(
            self.cache.ssm_state.shape, self.cache.ssm_state.dtype) \
            if cfg.ssm_layers else None
        self._eva_layers = cfg.eva_layers     # both EVA pools' depth
        # Calls a tick that read the shared full-attention layer's rows (the
        # layer itself and the cross layers above it); 0: no such model.
        self._shared_kv_calls = 0 if cfg.row_cut is None else \
            1 + cfg.layer_types.count("cross")
        # A state pool's per-slot arrays in bytes, by field name; empty for
        # every other cache: the report's ``kv.state_pool_bytes``.
        self._state_pool_bytes = {
            name: int(getattr(self.cache, name).nbytes)
            for name in ("ssm_state", "ssm_tail") if self._ssm_layers}
        if obs.REGISTRY.enabled:
            _BLOCK_FIXED_BYTES.set(self._kv_block_fixed_bytes)
            for name, size in self._state_pool_bytes.items():
                _STATE_POOL_BYTES.labels(pool=name).set(size)
        # Expert layers' row counts on the tick's fetch: (layers, what
        # ``experts.held_counts`` gives a layer), None for a model without
        # experts.
        self._expert_rows_shape: Optional[Tuple[int, int]] = None
        if cfg.moe is not None and cfg.n_expert_layers:
            from tree_attention_tpu.models.experts import counts_width

            self._expert_rows_shape = (
                cfg.n_expert_layers, counts_width(cfg.moe))
        self.tok = jnp.zeros((slots,), jnp.int32)
        # The host's view of each slot's length on the device, as the
        # tick programs leave it (``_count_kv_steps``), and the tokens a
        # grid step of the paged kernel takes by a group's rows a slot.
        self._kv_len = np.zeros((slots,), np.int64)
        self._kv_step_tokens: Dict[Tuple[str, int], Optional[int]] = {}
        # The layers that cache a token row (``_count_pool_rows``): every
        # pool's under either table, K and V counted once.
        self._pool_layers = sum(
            getattr(self.cache, name).shape[0]
            for name in ("k", "wk", "kv") if hasattr(self.cache, name))

        # Host mirror of slot state (the scheduler's view; device state is
        # the cache + the token vector the mixed step carries). States:
        # "free", "prefill" (chunks in flight), "await" (first sampled
        # token parked in the device token vector until this tick's
        # batched fetch), "live" (decoding).
        self._slot_req: List[Optional[Request]] = [None] * slots
        self._slot_tokens: List[List[int]] = [[] for _ in range(slots)]
        self._slot_admit: List[Tuple[int, float]] = [(0, 0.0)] * slots
        self._slot_state: List[str] = ["free"] * slots
        self._slot_ttft: List[float] = [0.0] * slots
        self._slot_prefix_hit: List[int] = [0] * slots
        self._prefill_pos: List[int] = [0] * slots
        # Where each slot's prefill STARTED (0 cold, the matched length on
        # a prefix hit) — the first consumed chunk resets the slot's
        # device length to exactly this value (a hit is pure host
        # bookkeeping: this is the one place the device learns it).
        self._prefill_start: List[int] = [0] * slots
        self._prompt_np: List[Optional[np.ndarray]] = [None] * slots
        self._prefill_fifo: List[int] = []  # prefilling slots, admit order
        self._last_tok_t: List[float] = [0.0] * slots
        self._slot_wait: List[float] = [0.0] * slots
        self._tok_host = np.zeros((slots,), np.int32)
        # The dispatched program whose tail (fetch, emit, record) the
        # serve loop has not landed yet (ISSUE 32), and the switch a test
        # flips to hold the loop to the synchronous order. Not an option:
        # which ticks look ahead follows from engine state alone.
        self._tail: Optional[_Tail] = None
        self._lookahead = True

        # Thread-safe control mailboxes (ISSUE 10): ingress handler
        # threads only ever touch these two under the control lock —
        # cancel() records a uid, request_drain() raises the flag — and
        # the tick loop sweeps both at tick start, so every actual
        # engine/state mutation stays on the loop thread.
        self._ctl_lock = threading.Lock()
        self._cancel_uids: Set[int] = set()
        self._draining = False
        # Per-tick robustness accounting for the flight recorder.
        self._tick_cancelled = 0
        self._tick_deadline = 0
        self._tick_shed = 0

        # Observability plane (PR 4): a per-request span held open from
        # admit to retire (None while the slot is free / tracing is off),
        # the slot's worst inter-token gap (the SLO verdict's TBT side),
        # and its chunk ordinal (the "chunk k/N" trace tag). The SLO
        # monitor itself always runs — it feeds ServeReport.slo — but its
        # gauges only publish while the registry records.
        self._slot_span: List[Optional[Any]] = [None] * slots
        self._slot_max_tbt: List[float] = [0.0] * slots
        self._chunk_k: List[int] = [0] * slots
        self.slo = SLOMonitor(
            ttft_slo=slo_ttft, tbt_slo=slo_tbt, window=slo_window
        )

        # Prefix reuse (ISSUE 5/6): the radix tree, plus the per-slot ref
        # ledger — nodes a slot matched or published stay pinned
        # (unevictable) until that slot retires. The index lies over
        # the unified pool (zero-copy hits) — int8 included, since
        # per-block scales ride the pool (ISSUE 13).
        self._prefix: Optional[Any] = None
        self._slot_nodes: List[List[Any]] = [[] for _ in range(slots)]
        self._tick_prefix_hits = 0
        self._tick_prefix_reused = 0
        self._hit_bytes_moved = 0
        if prefix_index is not None:
            # Shared-radix mode (disaggregation): both workers hold pins
            # in ONE tree — the prefill worker matches and adopts, the
            # decode worker inherits the request's pins at handoff and
            # releases them at retire (int8 included, since per-block
            # scales ride the shared pool, ISSUE 13).
            if block_pool is None or prefix_index.alloc is not block_pool:
                raise ValueError(
                    "prefix_index must be built over the same shared "
                    "block_pool (one ledger, one tree)"
                )
            if prefix_index.block != self.kv_block:
                raise ValueError(
                    f"prefix_index block {prefix_index.block} must equal "
                    f"kv_block {self.kv_block} (radix matching happens at "
                    f"page granularity)"
                )
            self._prefix = prefix_index
        elif prefix_cache:
            if prefix_block > cache_len:
                # Checked before the pool allocates: a block wider than a
                # slot could never be copied anywhere.
                raise ValueError(
                    f"prefix_block {prefix_block} exceeds cache_len "
                    f"{cache_len}"
                )
            # The in-place index serves int8 too (ISSUE 13): blocks
            # carry per-BLOCK scales in the pool, so a published int8
            # block is self-contained and shareable.
            self._prefix = PagedPrefixIndex(
                block=self.kv_block, alloc=self._pool,
                max_cached=prefix_pool_blocks,
                host_pool=self._host_pool,
                **({} if self._win is None else {
                    "window_alloc": self._win.alloc,
                    "window_need": self._win.hit_blocks}),
            )

        # Quantized admission stages the exact prefill in ONE
        # preallocated B=1 cache (int8 slots cannot hold exact chunk
        # activations; allocating per admit is the cost this engine
        # removes). One prompt stages at a time.
        self._staged_prefill = quantize
        if self._staged_prefill:
            self._staging: KVCache = init_cache(
                cfg, 1, cache_len, **self._prefill_kw
            )
            if self._prefix is not None:
                # int8 paged hits (ISSUE 13): the slot references the
                # matched int8 blocks in place, but the suffix's exact
                # staged prefill needs the prefix as activations-grade
                # rows — ONE jitted dequant gather per hit.
                self._dequant_hit = jax.jit(
                    insert_dequant_prefix, donate_argnums=(0,)
                )

        # jax.jit caches one executable per Tq bucket for the mixed step
        # (pure-decode ticks are the Tq=1 bucket, chunk ticks one of a
        # small power-of-two set) — bounded compiles for every
        # occupancy/chunk mixture.
        # The jit caches are per INSTANCE (bound methods), so a fresh
        # server recompiles — bench/serving.py warms the same server it
        # times. The tick loop reassigns self.cache/self.tok from each
        # call's outputs, so the old buffers are donated — each call
        # updates the (L,S,Hkv,Tmax,D) cache in place instead of copying
        # it (backends without donation just copy).
        # The tick programs also note each Tq bucket they are traced for
        # (:meth:`_noting`): what :meth:`program_tables` describes.
        self._tick_programs: Dict[Tuple[str, int], Optional[Dict[str, Any]]] \
            = {}
        self._untabled = False
        self._mixed = jax.jit(
            self._noting("_mixed", self._mixed_fn), donate_argnums=(6,))
        self._packed = jax.jit(
            self._noting("_packed", self._packed_fn), donate_argnums=(9,))
        if self._staged_prefill:
            self._stage_chunk = jax.jit(
                self._stage_chunk_fn, donate_argnums=(3,)
            )
            self._stage_final = jax.jit(
                self._stage_final_fn, donate_argnums=(3, 4, 5, 6)
            )
        # Speculative decoding (ISSUE 8): the host drafter, the per-slot
        # committed-length ledger (the rollback truth — the device length
        # over-counts by the rejected rows until the next step's reset),
        # and the verify-step programs. The tree program only exists where
        # the mask is plumbed; elsewhere proposals fall back to
        # root-path chains, which are exactly causal.
        self._drafter: Optional[Drafter] = None
        # Tree masks need a mask-plumbed attention path: the tree merge
        # has none, and under a seq mesh both the sequence-sharded pool
        # and the paged-QUANT off-kernel path (its dequantized view,
        # ISSUE 13) run through it.
        self._tree_ok = not (
            self._seq_shards > 1 and (quantize or kv_shard == "seq")
        )
        # Verify chunks ride power-of-two Tq buckets like prefill chunks;
        # the bucket must fit the cache's write window, so the draft size
        # clamps to the largest power of two <= min(32, cache_len).
        cap = 1
        while cap * 2 <= min(32, cache_len):
            cap *= 2
        self._spec_rows_cap = cap
        self._slot_clen = [0] * slots
        # Per-slot token history for the drafter, filled INCREMENTALLY
        # (admit writes the prompt, every commit appends its burst) — a
        # per-tick concatenate of prompt + emitted would make host-side
        # drafting O(n^2) over a generation. history = buf[i, :len].
        self._hist_buf = np.zeros((slots, cache_len + 1), np.int32)
        self._hist_len = [0] * slots
        self._spec_proposed = 0   # lifetime draft tokens proposed
        self._spec_accepted = 0   # lifetime draft tokens accepted
        self._spec_ticks = 0      # ticks that verified >= 1 draft token
        self._spec_verifies = 0   # per-SLOT verify events with >= 1 draft
        self._tick_spec: Tuple[int, int, int] = (0, 0, 0)
        if self._speculate:
            self._drafter = (
                make_drafter(drafter or "ngram")
                if isinstance(drafter, str) or drafter is None else drafter
            )
        # The verify-shaped programs serve BOTH speculation and token-
        # tree sibling decode (ISSUE 20) — jitted unconditionally; an
        # engine that never runs a verify tick never compiles them.
        self._spec_lin = jax.jit(
            self._noting("_spec_lin", self._spec_lin_fn),
            donate_argnums=(8,)
        )
        self._spec_tree = jax.jit(
            self._noting("_spec_tree", self._spec_tree_fn),
            donate_argnums=(10,)
        )
        # The jitted tick programs by name, for :meth:`program_tables`
        # (a test may put a spy in an attribute's place).
        self._tick_jits = {
            "_mixed": self._mixed, "_packed": self._packed,
            "_spec_lin": self._spec_lin, "_spec_tree": self._spec_tree}
        self._compact = jax.jit(self._compact_fn, donate_argnums=(0,))
        # The start-up record's view of a running loop (:meth:`serve` sets
        # them, :meth:`_noting` reads them while it traces): the loop's
        # tick, its phase stamper, and the programs built by ticks whose
        # flight records are still to be written.
        self._serve_tick: Optional[Callable[[], int]] = None
        self._phases: Optional[TickPhases] = None
        self._built: Dict[int, List[Any]] = {}
        STARTUP.add(
            "engine", t_build,
            pool_bytes=sum(int(leaf.nbytes)
                           for leaf in jax.tree.leaves(self.cache)),
            state_pool_bytes=sum(self._state_pool_bytes.values()))

    # -- compiled pieces --------------------------------------------------

    def _seed_key_fn(self, keys, slot, salt, branch):
        """Install slot ``slot``'s request key: fold the request's salt
        (its ``seed`` or uid) and the fork-branch index into the
        engine's base key. Pure function of (engine seed, salt, branch)
        — the reproducibility root: re-serving the same trace re-derives
        the same keys, and every forked sibling gets its own stream."""
        k = jax.random.fold_in(jax.random.fold_in(self._base_key, salt),
                               branch)
        return keys.at[slot].set(k)

    def _fork_copy_fn(self, cache, tok_vec, src, dst, slot, tip,
                      wsrc=None, wdst=None):
        """The fork's ONE device dispatch: copy-on-write the partial
        tail block ``src`` into the child's fresh block ``dst`` (a
        no-op self-copy when the fork point is block-aligned and no
        tail exists — ``src == dst == 0``) and park the child's tip
        token in the device token vector (the pure-decode tick reads
        tokens from there). Everything else about a fork is host
        bookkeeping: table row, refcounts, pins."""
        cache = copy_pool_block(cache, src, dst, wsrc, wdst)
        tok_vec = lax.dynamic_update_index_in_dim(tok_vec, tip, slot,
                                                  axis=0)
        return cache, tok_vec

    def _account_step_counters(self, extra: np.ndarray,
                               tail_rows: int = 0) -> Dict[str, int]:
        """Read the step's counters off the tick's fetch (the rows below
        the slots') into the registry, and return the flight record's
        numbers: the expert layers' (:meth:`_account_expert_rows`), then
        ``tail_blocks_written``, the block tails the conv layers wrote
        (``tail_rows`` slots' by the row kernel, :meth:`_count_tail_rows`), or
        ``ssm_states_advanced``, the (slot, layer) states the state-space
        layers wrote, or ``eva_summaries_written``, the (slot, layer)
        summary rows the EVA layers wrote."""
        flat, out = extra.reshape(-1), {}
        at = 0
        if self._expert_rows_shape is not None:
            layers, width = self._expert_rows_shape
            at = layers * width
            out.update(self._account_expert_rows(flat[:at]))
        if self._conv_layers:
            out["tail_blocks_written"] = int(flat[at])
            if obs.REGISTRY.enabled:
                by_row = tail_rows * self._conv_layers
                _TAIL_BLOCKS.labels(path="row").inc(by_row)
                _TAIL_BLOCKS.labels(path="block").inc(
                    out["tail_blocks_written"] - by_row)
        if self._ssm_layers:
            out["ssm_states_advanced"] = int(flat[at])
            if obs.REGISTRY.enabled:
                _SSM_STATES.inc(out["ssm_states_advanced"])
        if self._eva_layers:
            out["eva_summaries_written"] = int(flat[at])
            if obs.REGISTRY.enabled:
                _EVA_SUMMARIES.inc(out["eva_summaries_written"])
        return out

    def _rows_cross(self, tq: int, group: int) -> Optional[int]:
        """Rows the layers above the seam compute in a program of ``tq``
        rows (``group``: a packed program's chunk members, else 0): one a
        slot of a packed program, every row of a padded one; None for a
        model that cuts no rows."""
        if not self._shared_kv_calls:
            return None
        return self.slots if group else self.slots * tq

    def _count_tail_rows(self, tq, n_vec, chunk) -> int:
        """Slots whose ONE row the program being dispatched takes through
        the conv layers' row kernel (``models/hybrid.py``
        ``tail_write_path``): the tails it writes by that path are these
        times the conv layers, the rest of the program's own count moved
        whole rows. Counted from the rows the host packed, as
        :meth:`_count_pool_rows` counts the K/V rows."""
        if not self._conv_layers or tail_write_path(
                1 if chunk is not None else tq, self._tail_pool) != "row":
            return 0
        return int(n_vec.sum())

    def _count_scan_rows(self, tq, n_vec, chunk) -> Tuple[int, int]:
        """Rows of the program being dispatched that go through the
        state-space layers' chunked scan, times those layers:
        ``(scan_rows_kernel, scan_rows_xla)`` of its flight record, by the
        scan a group of ``tq`` rows takes (``models/hybrid.py``
        ``scan_path``; a Mamba-1 model's: ``scan1_path``, whose kernel takes
        a group at any ``tq``). A packed program's chunk group's rows, or every row
        of a padded program of more than one row a slot; a group of one row
        a slot takes the single-step update and counts nothing here. Counted
        from the rows the host packed, as :meth:`_count_pool_rows` counts
        the K/V rows."""
        if not self._ssm_layers or tq == 1:
            return (0, 0)
        rows = int(np.sum(chunk[1])) if chunk is not None \
            else int(n_vec.sum())
        by = {"kernel": 0, "xla": 0}
        path = scan1_path(self._state_pool) if self.cfg.ssm1 is not None \
            else scan_path(tq, self.cfg.ssm, self._state_pool)
        by[path] = rows * self._ssm_layers
        if obs.REGISTRY.enabled:
            for path, n in by.items():
                _SCAN_ROWS.labels(path=path).inc(n)
        return by["kernel"], by["xla"]

    def _account_expert_rows(self, extra: np.ndarray) -> Dict[str, int]:
        """Read the expert layers' row counts off the tick's fetch into
        the registry, and return the flight record's numbers, over the
        rows that carry a token and all expert layers. Of the held routed
        experts: ``expert_pairs`` (row-expert
        pairs computed here), ``experts_touched`` (held experts with >= 1
        row, summed over layers), ``expert_rows_max`` (the fullest
        expert). Of the router: ``routed_rows`` (its decisions: rows x
        layers), ``routed_pairs`` (``per_token`` a decision),
        ``zero_pairs`` (pairs on zero-compute experts) and
        ``real_row_max`` (the most routed experts, held here or
        elsewhere, one decision chose)."""
        ex = self.cfg.moe
        layers, width = self._expert_rows_shape
        rows = extra.reshape(-1)[:layers * width].reshape(layers, width)
        here = rows[:, :ex.held]
        pairs = int(here.sum())
        zero = int(rows[:, ex.held + 1].sum()) if ex.n_zero else 0
        routed = pairs + int(rows[:, ex.held].sum()) + zero
        if ex.n_zero:
            real_max = int(rows[:, ex.held + 2].max())
        else:
            real_max = ex.per_token if routed else 0
        if obs.REGISTRY.enabled:
            _MOE_PAIRS_HERE.inc(pairs)
            _MOE_PAIRS_ZERO.inc(zero)
            _MOE_PAIRS_TOTAL.inc(routed)
            for n in here.reshape(-1):
                _MOE_ROWS.observe(float(n))
        return {"expert_pairs": pairs,
                "experts_touched": int((here > 0).sum()),
                "expert_rows_max": int(here.max()),
                "routed_rows": routed // ex.per_token,
                "routed_pairs": routed, "zero_pairs": zero,
                "real_row_max": real_max}

    def _count_kv_steps(self, tq, n_vec, reset, reset_val, decode_rows,
                        chunk) -> Tuple[int, int]:
        """What the paged decode kernels' work lists hold for the program
        being dispatched and what the whole rectangles would, a layer:
        ``(kv_steps_run, kv_steps_grid)`` of its flight record. The kernels
        build their lists on the device from the slots' lengths
        (``ops/pallas_decode.py`` ``paged_step_plan``); the host counts
        with the same rule (``tuning.paged_rule_steps``) from the lengths
        it packed: a reset is its value, a live slot's decode row
        (``decode_rows``: the slots and their sample indices) sits at its
        prompt and samples so far, and any other slot where the programs
        before left it (``_kv_len``, which this call moves on by the rows
        the program writes). ``chunk``: a packed program's chunk group
        ``(slots, counts)`` beside one row a slot; else one group of
        ``tq`` rows a slot. A slot no program here has written since a
        side program moved it (a staged int8 prompt's insert) is counted at a
        stale length until its next decode row."""
        pre = np.where(reset, reset_val, self._kv_len)
        if decode_rows is not None:
            for i in decode_rows[0]:
                pre[i] = len(self._slot_req[i].prompt) \
                    + int(decode_rows[1][i]) - 1
        groups = [(tq, pre)] if chunk is None \
            else [(tq, pre[chunk[0]]), (1, pre)]
        run = grid = 0
        # One list a kind of layer: the full layers', and the window
        # layers' (which starts at the step that holds the lowest position
        # a slot's rows see); an EVA model's two, by its two rules.
        kinds = {}
        for kind, rule, second in self._kv_kinds:
            k_run = k_grid = 0
            width = (self.cache.wtable if second else self.cache.table
                     ).shape[1] * self.kv_block   # the table's rows
            for g_tq, lengths in groups:
                key = (kind, g_tq)
                if key not in self._kv_step_tokens:
                    self._kv_step_tokens[key] = paged_step_tokens(
                        self.cache, self.cfg, g_tq, window=second)
                step = self._kv_step_tokens[key]
                if step is None:
                    continue
                n_steps = width // step
                _, live = paged_rule_steps(
                    lengths, 0, g_tq, step, n_steps, rule)
                k_run += int(np.maximum(live, 1).sum())
                k_grid += len(lengths) * n_steps
            kinds[kind] = (k_run, k_grid)
            run, grid = run + k_run, grid + k_grid
        self._tick_kv_kinds = kinds if self._win is not None else {}
        if self._eva_layers:
            self._tick_eva = self._count_eva(pre, n_vec, chunk)
        self._kv_len = pre + n_vec
        if chunk is not None:
            np.add.at(self._kv_len, chunk[0], chunk[1])
        if obs.REGISTRY.enabled:
            PAGED_STEPS.labels(steps="run").inc(run)
            PAGED_STEPS.labels(steps="grid").inc(grid)
        return run, grid

    def _count_eva(self, pre, n_vec, chunk) -> Dict[str, int]:
        """What an EVA model's program is due, from the rows the host
        packed (``pre``: every slot's length before them; ``n_vec``: its
        rows beside the chunk group's ``(slots, counts)``):
        ``eva_summaries_due``, the chunks those rows close x the layers
        (what the program reports back as ``eva_summaries_written``), and,
        a layer, the rows its two calls make visible over the members that
        have a row: ``eva_local_rows`` (from the first row's window start
        to the last row) and ``eva_summary_rows`` (the summaries under the
        last row's window)."""
        W, C = self.cfg.window, self.cfg.chunk
        start, n = pre, n_vec
        if chunk is not None:
            start = np.concatenate([pre[chunk[0]], pre])
            n = np.concatenate([chunk[1], n_vec])
        has = n > 0
        start, end = start[has], (start + n)[has]
        return {
            "eva_summaries_due": int(
                chunks_closed(start, end - start, C)[1].sum())
            * self._eva_layers,
            "eva_local_rows": int((end - start // W * W).sum()),
            "eva_summary_rows": int(((end - 1) // W * (W // C)).sum()),
        }

    def _count_pool_rows(self, tq, n_vec, chunk) -> Tuple[int, int]:
        """Rows the program being dispatched writes into the paged pool,
        times the layers that cache a row: ``(pool_rows_row,
        pool_rows_block)`` of its flight record, by the write each group
        of rows takes (``models/decode.py`` ``pool_write_path``: one row a
        slot on a TPU goes through ``paged_row_write``, everything else
        moves whole blocks). Counted from the rows the host packed:
        ``n_vec`` a slot and, for a packed program, the chunk group's
        ``chunk`` ``(slots, counts)`` beside them."""
        rows = {"row": 0, "block": 0}
        if chunk is not None:
            rows[pool_write_path(tq)] += int(np.sum(chunk[1]))
            tq = 1
        rows[pool_write_path(tq)] += int(n_vec.sum())
        out = (rows["row"] * self._pool_layers,
               rows["block"] * self._pool_layers)
        if obs.REGISTRY.enabled:
            _POOL_ROWS.labels(path="row").inc(out[0])
            _POOL_ROWS.labels(path="block").inc(out[1])
        return out

    def _sample_emit(self, last, keys, temp, topk, idx):
        """The ONE per-slot sampling call every emitting program shares
        (models.decode.sample_slots): argmax where the slot's
        temperature is 0 — value-identical to the legacy greedy path —
        temperature/top-k categorical under fold_in(key, idx)
        otherwise. Returns (tokens, model logprobs of the choices)."""
        with jax.named_scope(scopes.HEAD):
            return sample_slots(last, temp, topk, keys, idx)

    def _chunk_bucket(self, n: int) -> int:
        """Tq bucket for a chunk of ``n`` prompt tokens: power-of-two with
        a floor of 8, capped at ``prefill_chunk`` — the small fixed set of
        mixed-step programs."""
        b = min(8, self.prefill_chunk)
        while b < n:
            b *= 2
        return min(b, self.prefill_chunk)

    def _step_kw(self) -> Dict[str, Any]:
        kw = dict(self._fs_kw)
        if self.quantize:
            kw["quant_kernel"] = self.quant_kernel
        return kw

    def _emit_fused(self, last, held, emit, keys, temp, topk, idx, lp_vec,
                    stats):
        """The tail every tick program shares: sample each slot from its
        row of ``last`` ``(S, V)`` under its own key/temperature/top-k
        (``keys``/``temp``/``topk``/``idx`` — ISSUE 15; temperature-0
        slots are exact argmax); ``emit`` keeps the sample (decode slots
        and final-chunk slots) or holds the slot's ``held`` token AND its
        parked logprob (everything else — in particular a parked first
        token rides through unchanged). Returns the token vector, the
        logprob vector and ONE fused ``(S, 2)`` int32 fetch vehicle (tokens
        + bitcast logprobs — the per-tick host sync stays a single array).
        """
        tok_s, lp_s = self._sample_emit(last, keys, temp, topk, idx)
        with jax.named_scope(scopes.HEAD):
            nxt = jnp.where(emit, tok_s, held)
            lp_out = jnp.where(emit, lp_s, lp_vec)
            fused = jnp.concatenate(
                [nxt[:, None],
                 lax.bitcast_convert_type(lp_out, jnp.int32)[:, None]],
                axis=1,
            )
        if any(n in stats for n in _STEP_COUNTERS):
            # The step's counters ride the tick's one fetch as further
            # rows of the same array (tracing on or off: one program):
            # the expert layers' row counts (``_expert_rows_shape`` says
            # how to read them), then the conv layers' block tails
            # written or the state-space layers' states advanced (one
            # number), :meth:`_account_step_counters`.
            parts = [stats[n].reshape(-1)
                     for n in _STEP_COUNTERS if n in stats]
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            flat = jnp.pad(flat, (0, flat.shape[0] % 2))
            fused = jnp.concatenate([fused, flat.reshape(-1, 2)], axis=0)
        return nxt, lp_out, fused

    def _mixed_fn(self, params, tokens, n_tok, reset, reset_val, emit,
                  cache, keys, temp, topk, idx, lp_vec):
        """The padded tick program: one mixed-Tq forward_step for every
        slot. The serve loop runs it at Tq = 1, the pure-decode tick (a
        tick with a prompt chunk is :meth:`_packed_fn`'s).

        ``tokens`` is ``(S, Tq)``; slot ``i`` consumes ``n_tok[i]`` rows —
        1 for a live decode slot, 0 for everything else (inert: nothing
        written, length frozen). ``reset`` sets a slot's length to
        ``reset_val[i]`` before the write (a forked child's one reset to
        its fork point). Each slot samples from its own last valid row
        (:meth:`_emit_fused`). Returns the token vector, the logprob
        vector, the fused fetch vehicle, the sampled rows' logits, and
        the cache.
        """
        length = jnp.where(reset, reset_val, cache.length)
        cache = dataclasses.replace(cache, length=length)
        stats: Dict[str, Any] = {}
        logits, new_cache = forward_step(
            params, tokens, cache, self.cfg, n_tokens=n_tok, stats=stats,
            **self._step_kw()
        )
        with jax.named_scope(scopes.HEAD):
            row = jnp.maximum(n_tok - 1, 0)
            last = jnp.take_along_axis(
                logits, row[:, None, None], axis=1)[:, 0]
        nxt, lp_out, fused = self._emit_fused(
            last, tokens[:, 0], emit, keys, temp, topk, idx, lp_vec, stats)
        # ``last`` rides out as a device carry: a fork family samples
        # its siblings' first tokens from the PARENT's exact prompt-end
        # logits row (bit-identical to the parent's own sample point —
        # the greedy parity gate's exactness), never re-computing a
        # written KV row. Fetched never, read only at fork time.
        return nxt, lp_out, fused, last, new_cache

    def _packed_fn(self, params, chunk_tok, chunk_slot, chunk_n, dec_tok,
                   dec_n, reset, reset_val, emit, cache, keys, temp, topk,
                   idx, lp_vec):
        """THE program of a tick that carries a prompt chunk: a compact
        chunk group beside one decode row a slot (``forward_packed_step``;
        ``C·Tq + S`` rows where the padded program computes ``S·Tq``).

        ``chunk_tok`` is ``(C, Tq)`` (``C`` fixed at build, Tq a chunk
        bucket): member ``j`` is slot ``chunk_slot[j]`` consuming
        ``chunk_n[j]`` prompt rows, 0 for a member no slot fills.
        ``dec_tok`` ``(S,)`` holds every slot's decode token, ``dec_n`` 1
        for a live decode slot and 0 for everything else (a chunking slot
        included: a slot has rows in one group). ``reset`` sets a slot's
        length to ``reset_val[i]`` before the write — 0 for a cold first
        chunk (the slot index is reused), the matched prefix length on a
        prefix hit (the hit was pure host bookkeeping and THIS is where
        the device learns it). Each slot samples from the ONE row the step
        gives it (its last valid chunk row, or its decode row); the head
        never sees a ``(·, Tq, V)`` array. Returns what :meth:`_mixed_fn`
        returns.
        """
        length = jnp.where(reset, reset_val, cache.length)
        cache = dataclasses.replace(cache, length=length)
        stats: Dict[str, Any] = {}
        last, new_cache = forward_packed_step(
            params, chunk_tok, chunk_slot, chunk_n, dec_tok, dec_n, cache,
            self.cfg, stats=stats, **self._step_kw()
        )
        nxt, lp_out, fused = self._emit_fused(
            last, dec_tok, emit, keys, temp, topk, idx, lp_vec, stats)
        return nxt, lp_out, fused, last, new_cache

    def _sibling_first_fn(self, tok_vec, lp_vec, row, key, temp, topk,
                          slot):
        """Park a forked sibling's FIRST token: sample from the parent's
        stashed prompt-end logits ``row`` under the child's key (branch
        index folded in at seeding) and write token + logprob into the
        device vectors — the child then rides the existing ``await``
        machinery, surfacing at the next batched fetch. Greedy children
        argmax the identical row, so every sibling's first token is
        bit-identical to an independent admission's."""
        tok_s, lp_s = self._sample_emit(
            row[None], key[None], jnp.reshape(temp, (1,)),
            jnp.reshape(topk, (1,)), jnp.zeros((1,), jnp.int32),
        )
        tok_vec = lax.dynamic_update_index_in_dim(tok_vec, tok_s[0],
                                                  slot, axis=0)
        lp_vec = lax.dynamic_update_index_in_dim(lp_vec, lp_s[0],
                                                 slot, axis=0)
        return tok_vec, lp_vec

    def _tree_first_fn(self, row, branch_ix, salt, temp, topk):
        """Sample every tree sibling's FIRST token from the parent's
        stashed prompt-end logits (ISSUE 20): branch ``b`` draws under
        fold_in(fold_in(fold_in(base, salt), b), 0) — the exact chain
        :meth:`_sibling_first_fn` evaluates for a fork-slot sibling of
        the same index, so the two family layouts' first tokens are
        bit-identical. One tiny dispatch per family start."""
        n = branch_ix.shape[0]
        keys = jax.vmap(lambda b: jax.random.fold_in(jax.random.fold_in(
            self._base_key, salt), b))(branch_ix)
        rows = jnp.broadcast_to(row, (n, row.shape[-1]))
        return sample_slots(
            rows, jnp.full((n,), temp, jnp.float32),
            jnp.full((n,), topk, jnp.int32), keys,
            jnp.zeros((n,), jnp.int32),
        )

    def _spec_step(self, params, mat, tok_vec, use_dev0, n_tok, reset,
                   reset_val, emit, depth, bits, cache, keys, temp, topk,
                   idx, lp_vec, salt, branch_m, ridx_m):
        """THE verify-tick program (speculate and/or token-tree sibling
        decode): the same mixed-Tq step as :meth:`_mixed_fn` plus the
        verify extras —

        - row 0 of each slot comes from the DEVICE token vector when
          ``use_dev0`` (an ``await`` slot's parked first token, a staged
          int8 prompt's, only exists there); every other row from the
          host-built matrix (the host knows every committed/replayed
          token);
        - ``depth``/``bits`` (tree ticks only): packed tree rows take
          RoPE position ``length + depth[row]`` and attend under the
          per-slot ancestor mask instead of row-order causal — chain
          slots ride ``arange``/lower-triangular defaults, which are the
          causal rule bit-for-bit;
        - a per-ROW sample of every logits row — the accept walk's
          input under speculation (greedy rows are pure argmax, exactly
          the legacy rule; sampled rows draw the Leviathan coupling
          sample) and the sibling tips under tree decode. Row keys are
          the reproducibility chain re-derived IN-PROGRAM:
          ``branch_m[s, r] >= 0`` (a sibling row of branch b at stream
          index ``ridx_m[s, r]``) folds (salt, branch, index) into the
          engine's base key — the fork-slot path's exact chain;
          ``branch_m[s, r] < 0`` (a spec verify row) folds the stream
          index into the slot's installed request key.

        ``reset_val`` doubles as the rollback: a verify slot always
        resets to its host-side committed length, which un-counts the
        rows a previous tick rejected (or a tree slot's replayed
        suffix).
        """
        tokens = mat.at[:, 0].set(jnp.where(use_dev0, tok_vec, mat[:, 0]))
        length = jnp.where(reset, reset_val, cache.length)
        cache = dataclasses.replace(cache, length=length)
        kw = self._step_kw()
        if depth is not None:
            kw["positions"] = length[:, None] + depth
            kw["tree_mask"] = bits
        logits, new_cache = forward_step(
            params, tokens, cache, self.cfg, n_tokens=n_tok, **kw
        )
        with jax.named_scope(scopes.HEAD):
            row = jnp.maximum(n_tok - 1, 0)
            last = jnp.take_along_axis(
                logits, row[:, None, None], axis=1)[:, 0]
        # Column 0 keeps the mixed-step emit contract verbatim (final
        # chunks sample their first token under the slot key, parked
        # tokens/logprobs ride through) — temperature-0 slots reduce to
        # the legacy greedy argmax bit-for-bit.
        tok_s, lp_s = self._sample_emit(last, keys, temp, topk, idx)
        with jax.named_scope(scopes.HEAD):
            nxt = jnp.where(emit, tok_s, tokens[:, 0])
            lp_out = jnp.where(emit, lp_s, lp_vec)

            def _row_key(key, s, b, r):
                tree_k = jax.random.fold_in(jax.random.fold_in(
                    jax.random.fold_in(self._base_key, s), b), r)
                return jnp.where(b < 0, jax.random.fold_in(key, r), tree_k)

            row_keys = jax.vmap(
                lambda key, s, bs, rs: jax.vmap(
                    lambda b, r: _row_key(key, s, b, r))(bs, rs)
            )(keys, salt, branch_m, ridx_m)
            all_tok, all_lp = sample_rows(logits, temp, topk, row_keys)
            # One fused (S, 1+Tq, 2) output = ONE host fetch per tick: lane
            # 0 tokens, lane 1 bitcast logprobs; row 0 the token/logprob
            # vectors (the awaits/parked contract), the rest the per-row
            # draws.
            col0 = jnp.stack(
                [nxt, lax.bitcast_convert_type(lp_out, jnp.int32)], axis=-1,
            )[:, None]
            rest = jnp.stack(
                [all_tok, lax.bitcast_convert_type(all_lp, jnp.int32)],
                axis=-1,
            )
            fused = jnp.concatenate([col0, rest], axis=1)
        # ``last`` rides out as a device carry exactly like the mixed
        # step's: a family admitted on a verify tick still stashes its
        # prompt-end logits row for the fork/tree start. Fetched never.
        return nxt, lp_out, fused, last, new_cache

    def _spec_lin_fn(self, params, mat, tok_vec, use_dev0, n_tok, reset,
                     reset_val, emit, cache, keys, temp, topk, idx,
                     lp_vec, salt, branch_m, ridx_m):
        """Verify tick with chain drafts only — pure causal, no mask or
        position operands (one program family shared with chunk ticks)."""
        return self._spec_step(params, mat, tok_vec, use_dev0, n_tok,
                               reset, reset_val, emit, None, None, cache,
                               keys, temp, topk, idx, lp_vec, salt,
                               branch_m, ridx_m)

    def _spec_tree_fn(self, params, mat, tok_vec, use_dev0, n_tok, reset,
                      reset_val, emit, depth, bits, cache, keys, temp,
                      topk, idx, lp_vec, salt, branch_m, ridx_m):
        """Verify tick with >= 1 packed token tree — draft trees
        (SpecInfer, arXiv:2305.09781) and/or sibling-branch bundles
        (ISSUE 20): per-slot depths and ancestor masks ride along."""
        return self._spec_step(params, mat, tok_vec, use_dev0, n_tok,
                               reset, reset_val, emit, depth, bits,
                               cache, keys, temp, topk, idx, lp_vec,
                               salt, branch_m, ridx_m)

    def _compact_fn(self, cache, start, src, n):
        """Batched commit compaction: move each verifying slot's accepted
        tree rows contiguous (see models.decode.compact_decode_window);
        slots with n=0 are bit-identically untouched."""
        return compact_decode_window(cache, start, src, n)

    def _stage_chunk_fn(self, params, tokens, n_tok, staging, reset,
                        reset_val):
        """One mid-prompt chunk into the exact staging cache (quantized
        admission). Logits are unused here, so XLA prunes the
        output head. ``reset_val`` mirrors the mixed step's: the first
        chunk sets the staged length to the prefix-hit match (0 cold)."""
        length = jnp.where(reset, reset_val, staging.length)
        staging = dataclasses.replace(staging, length=length)
        _, staging = forward_step(
            params, tokens, staging, self.cfg, n_tokens=n_tok,
            **self._prefill_kw,
        )
        return staging

    def _stage_final_fn(self, params, tokens, n_tok, staging, cache,
                        tok_vec, lp_vec, slot, plen, reset, reset_val,
                        key, temp, topk, lo=0):
        """The final chunk: finish the staged exact prefill, sample the
        first token from the last valid row, mask the stale tail, quantize
        the staged prompt (per-BLOCK scalars — the quantize-after-prefill
        contract at block granularity), and insert slot rows + scales +
        length + first token into the batch cache — one dispatch, no host
        sync (the token rides the per-tick fetch). The insert scatters
        through the slot's block table, skipping token positions below
        ``lo`` — a prefix hit's matched blocks are SHARED (their staged
        rows are the dequantized originals, which re-quantize to
        bit-identical int8, so nothing is lost by not rewriting them)."""
        length = jnp.where(reset, reset_val, staging.length)
        staging = dataclasses.replace(staging, length=length)
        logits, staging = forward_step(
            params, tokens, staging, self.cfg, n_tokens=n_tok,
            **self._prefill_kw,
        )
        idx = jnp.maximum(n_tok - 1, 0)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        tok_s, lp_s = self._sample_emit(
            last, key[None], jnp.reshape(temp, (1,)),
            jnp.reshape(topk, (1,)), jnp.zeros((1,), jnp.int32),
        )
        first, lp = tok_s[0], lp_s[0]
        lp_vec = lax.dynamic_update_index_in_dim(lp_vec, lp, slot, axis=0)
        valid = (
            jnp.arange(self.cache_len, dtype=jnp.int32) < plen
        )[None, None, None, :, None]
        k_masked = jnp.where(valid, staging.k, 0)
        v_masked = jnp.where(valid, staging.v, 0)
        kq, vq, ks, vs = quantize_paged_blocks(
            k_masked, v_masked, self.kv_block, plen
        )
        new_cache = paged_insert_slot(
            cache, slot, kq, vq, jnp.asarray(plen, jnp.int32),
            ks, vs, lo=lo,
        )
        tok_vec = lax.dynamic_update_index_in_dim(tok_vec, first,
                                                  slot, axis=0)
        return staging, new_cache, tok_vec, lp_vec, last

    # -- the tick programs, described ---------------------------------------

    def _noting(self, name: str, fn):
        """A tick program's function (``name``: the attribute its jit is
        kept under) that notes each Tq bucket it is traced for. Python runs
        the note only while jit traces a new shape, never when a tick
        dispatches: knowing which programs exist costs a tick nothing.
        The trace also opens the program's ``startup:program`` span of the
        start-up record (``obs/flight.py``); JAX's own durations for the
        dispatch fill and close it (:meth:`_program_built`)."""
        @functools.wraps(fn)
        def traced(*args):
            # Every tick program's second operand is its ``(·, Tq)`` rows.
            tq = args[1].shape[1]
            self._tick_programs.setdefault((name, tq), None)
            self._untabled = True
            build = STARTUP.building(
                name, tq,
                None if self._serve_tick is None else self._serve_tick(),
                self._program_built)
            try:
                out = fn(*args)
            except BaseException:
                STARTUP.abandon(build)
                raise
            build.stage = 1         # the body is back: JAX's turn
            return out

        return traced

    def _program_built(self, span: List[Any]) -> None:
        """A tick program's ``startup:program`` span closed, inside the
        dispatch that built it: put ``built`` (``[program, tq, seconds,
        from_cache]``) on the open ``tick:dispatch`` annotation and keep it
        for that tick's flight record."""
        _, t0, t1, f = span
        built = [f["program"], f["tq"], round(t1 - t0, 6), f["from_cache"]]
        if self._phases is not None:
            self._phases.built(built)
        if FLIGHT.enabled and f["tick"] is not None:
            self._built[f["tick"]] = built

    def _tick_operands(self, name: str, tq: int) -> Tuple[Any, ...]:
        """The abstract operands of tick program ``name`` at Tq bucket
        ``tq``, as the serve loop dispatches it, from the engine's live
        state: a device array's shape, type and, where it is committed,
        its sharding; a host operand's shape and type (``jnp.asarray`` of
        it is uncommitted). That is what jit keys a dispatch by, so
        lowering from these finds the program the loop runs (no array is
        held: nothing is kept alive)."""
        S, C = self.slots, self._chunk_group

        def dev(a, shape=None):
            return jax.ShapeDtypeStruct(
                a.shape if shape is None else shape, a.dtype,
                sharding=a.sharding if getattr(a, "committed", False)
                else None, weak_type=getattr(a, "weak_type", False))

        def host(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        i32, flag = jnp.int32, jnp.bool_
        params = jax.tree.map(dev, self.params)
        cache = jax.tree.map(dev, self.cache)
        tok = dev(self.tok)
        # n_vec, reset, reset_val, emit | keys, temp, topk, sidx, lp
        per_slot = (host((S,), i32), host((S,), flag), host((S,), i32),
                    host((S,), flag))
        sampling = (dev(self._keys), host((S,), self._temp_np.dtype),
                    host((S,), self._topk_np.dtype), host((S,), i32),
                    dev(self._lp))
        if name == "_mixed":
            return (params, dev(self.tok, (S, tq)), *per_slot, cache,
                    *sampling)
        if name == "_packed":
            return (params, host((C, tq), i32), host((C,), i32),
                    host((C,), i32), tok, *per_slot, cache, *sampling)
        if name in ("_spec_lin", "_spec_tree"):
            tree = () if name == "_spec_lin" else (
                host((S, tq), i32), host((S, tq, tq), flag))
            return (params, host((S, tq), i32), tok, host((S,), flag),
                    *per_slot, *tree, cache, *sampling,
                    host((S,), self._salt_np.dtype), host((S, tq), i32),
                    host((S, tq), i32))
        raise ValueError(f"no tick program {name!r}")

    def lower_programs(self, tq: int) -> Dict[str, Any]:
        """Lower — from the engine's live state, dispatching and donating
        nothing — the programs a tick at Tq bucket ``tq`` runs: the fused
        ``mixed`` step (``tq == 1`` is the pure-decode tick, the padded
        program; a chunk bucket is the packed one) and, where admission
        stages prompts (int8), the ``stage_chunk`` program.
        ``.compile().as_text()`` of each shows what a served tick
        executes, e.g. which Pallas kernels are in it; with the programs
        already run, the compile is the loop's own executable."""
        name = "_mixed" if tq == 1 else "_packed"
        lowered = {"mixed": self._tick_jits[name].lower(
            *self._tick_operands(name, tq))}
        if self._staged_prefill:
            zeros = lambda n, dt: jnp.zeros((n,), dt)
            lowered["stage_chunk"] = self._stage_chunk.lower(
                self.params, jnp.zeros((1, tq), jnp.int32),
                zeros(1, jnp.int32), self._staging, zeros(1, bool),
                zeros(1, jnp.int32),
            )
        return lowered

    def program_tables(self) -> List[Dict[str, Any]]:
        """One table for each tick program built so far, from its
        operations to the parts of the model (``obs/scopes.py``):
        ``program`` (``fn``: the engine's name for it, and what a flight
        record of its ticks holds: ``kind``, ``tq``, ``chunk_group``) and
        ``ops``, rows ``[op, result, scope]`` of the optimized module.
        A table is made once a program: the program is lowered from the
        operands the loop dispatches it with (:meth:`_tick_operands`),
        which finds the loop's own executable, compiling nothing, where
        jit keys them alike; where it does not (a mesh that re-lays an
        operand) that is one compile of a twin. The serve loop calls this
        only while tracing is on, at the top of a run and after a tick
        that built a new program."""
        kinds = {"_mixed": "decode", "_packed": "mixed",
                 "_spec_lin": "verify", "_spec_tree": "verify"}
        t_first, n_made = None, 0   # a ``startup:tables`` span, if any
        for (name, tq), made in sorted(self._tick_programs.items()):
            if made is not None:
                continue
            if t_first is None:
                t_first = time.monotonic()
            n_made += 1
            text = self._tick_jits[name].lower(
                *self._tick_operands(name, tq)).compile().as_text()
            self._tick_programs[name, tq] = {
                "program": {
                    "fn": name, "kind": kinds[name], "tq": tq,
                    "chunk_group": self._chunk_group
                    if name == "_packed" else 0},
                "ops": scopes.table(text),
            }
        self._untabled = False
        if t_first is not None:
            STARTUP.add("tables", t_first, programs=n_made)
        return [t for _, t in sorted(self._tick_programs.items())
                if t is not None]

    # -- ingress-facing control (thread-safe) ------------------------------

    def prefix_stats(self) -> Dict[str, Any]:
        """Lifetime radix-cache counters (hits/misses/tokens_reused/...),
        empty when the cache is off. Public so a fleet bench/test can
        diff reuse across arms of ONE live serve() run (ServeReport's
        per-run prefix block only lands when the run drains)."""
        return {} if self._prefix is None else dict(self._prefix.stats())

    def cancel(self, uid: int) -> None:
        """Cancel request ``uid`` (any thread; e.g. a client disconnect).

        Records the uid in the control mailbox; the tick loop's sweep
        applies it at the next tick start — queued-unadmitted requests
        finish unserved, in-flight requests retire mid-stream (slot
        freed, prefix pins released, paged blocks unmapped back to the
        pool). Unknown/already-finished uids are a no-op (the client
        may disconnect after its stream completed)."""
        with self._ctl_lock:
            self._cancel_uids.add(uid)

    def fork(self, uid: int) -> None:
        """Branch live request ``uid`` mid-generation (any thread).

        Records the uid in the fork mailbox; the tick loop's control
        sweep applies it — the request's newest branch gets a fresh
        slot whose block table SHARES every full ancestor block
        (refcount++, zero KV bytes) with only the partial tail block
        copied, and continues sampling under its own PRNG key. The
        branch finishes as one more indexed :class:`RequestResult`
        under the same uid ("join" = the family's results/callbacks).
        Scarce slots/blocks defer the fork a couple of sweeps; a uid
        that is not (or no longer) live ages out as a no-op."""
        self._refuse_fork("fork(uid)")
        with self._ctl_lock:
            self._fork_uids.append(uid)

    def _refuse_fork(self, what: str) -> None:
        """A fork shares its ancestor's blocks and copies the partial one;
        a recurrent state is in no block, so a branch would start from a
        state that is not its own, and an EVA model's partial summary
        block is not the partial block of positions the fork copies:
        refused by the cache kind's name."""
        if self.cfg.cache_kind in ("state", "eva", "state_window"):
            raise ValueError(
                f"a model served from the {self.cfg.cache_kind} pool "
                f"(TransformerConfig.cache_kind) does not serve with "
                f"{what}: a branch needs the recurrent state at the fork "
                f"point, which nothing holds, or its own copy of the "
                f"partial summary block (not built for that pool)")

    def _take_forks(self) -> List[int]:
        """Drain the fork mailbox (loop side), oldest first."""
        with self._ctl_lock:
            out = self._fork_uids
            self._fork_uids = []
            return out

    def request_drain(self) -> None:
        """Begin graceful drain (any thread; e.g. a SIGTERM handler).

        The loop stops admitting: visible-but-unadmitted work is shed
        (outcome ``shed``), the source is closed, in-flight requests run
        to completion, and ``serve()`` returns — the caller then flushes
        telemetry and exits."""
        with self._ctl_lock:
            self._draining = True

    @property
    def draining(self) -> bool:
        with self._ctl_lock:
            return self._draining

    @property
    def all_slots_free(self) -> bool:
        """True when no request occupies a slot (single list read —
        safe to poll from harness/monitor threads)."""
        return all(st == "free" for st in self._slot_state)

    def _take_control(self) -> Tuple[Set[int], bool]:
        """Drain the cancel mailbox and read the drain flag (loop side)."""
        with self._ctl_lock:
            cancels = self._cancel_uids
            self._cancel_uids = set()
            return cancels, self._draining

    def leak_report(self) -> Dict[str, int]:
        """The no-leak invariant, as numbers (chaos-harness contract).

        After a drained run — every request retired, however it exited —
        the engine must hold NO per-request resources: no slot-private
        blocks, no unspent reservations, no pinned radix nodes; the only
        legitimate pool occupancy is the radix tree's retained cache
        (``blocks_used == blocks_cached``). A disconnect storm that
        violates this leaked memory."""
        out = {
            "blocks_private": sum(len(s) for s in self._slot_private),
            "blocks_used": self._pool.used,
            "blocks_reserved": self._pool.reserved,
            # CoW-shared fork ancestors still refcounted by some slot
            # (ISSUE 15) — 0 after a drain, like blocks_private.
            "blocks_shared": self._pool.shared_count,
            "blocks_cached": 0,
            "pins": 0,
        }
        if self._host_pool is not None:
            # Host-tier occupancy is legitimate retained cache (like
            # blocks_cached), surfaced for the harness's accounting.
            out["host_blocks_used"] = self._host_pool.used
        if self._prefix is not None:
            out["blocks_cached"] = self._prefix.blocks_used
            out["pins"] = self._prefix.total_pins()
        if self._win is not None:
            # The window layers' pool under the same invariant: its own
            # numbers, and folded into the totals above, so that whoever
            # holds a drained engine to ``used == cached`` holds both
            # pools to it.
            w = self._win
            own = {
                "window_blocks_private": w.private(),
                "window_blocks_used": w.alloc.used,
                "window_blocks_reserved": w.alloc.reserved,
                "window_blocks_shared": w.alloc.shared_count,
                "window_blocks_cached": self._window_cached(),
                "window_blocks_held": w.held(),
            }
            for name in ("private", "used", "reserved", "shared", "cached"):
                out["blocks_" + name] += own["window_blocks_" + name]
            out.update(own)
        # With no prefix tree every used block is slot-private, so a
        # drained engine must be at used == 0 exactly.
        return out

    def slots_snapshot(self) -> List[Dict[str, Any]]:
        """Per-slot live view for the obs server's ``/slots`` endpoint
        (ISSUE 16): state, occupant uid/branch, generated length, and
        committed cache length. Called from HTTP handler threads while
        the engine thread mutates the arrays — every read here is one
        GIL-atomic list index (ints, strings, a Request ref), so the
        worst case is a snapshot one tick stale, never a torn value."""
        out: List[Dict[str, Any]] = []
        for i in range(self.slots):
            req = self._slot_req[i]
            out.append({
                "slot": i,
                "state": self._slot_state[i],
                "uid": None if req is None else req.uid,
                "index": self._slot_index[i] if req is not None else 0,
                "tokens": len(self._slot_tokens[i]),
                "clen": self._slot_clen[i],
                "nblocks": self._slot_nblocks[i],
            })
        return out

    # -- per-request callbacks (engine thread) -----------------------------

    def _deliver_token(self, req: Request, index: int, tok: int) -> None:
        """Raw token delivery: branch callback when wired (any index),
        else the legacy single-stream callback for branch 0 only."""
        cb = req.on_branch_token
        if cb is not None:
            try:
                cb(index, tok)
            except Exception:
                log.exception("on_branch_token failed (rid %s)", req.uid)
            return
        if index == 0 and req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception:
                log.exception("on_token callback failed (rid %s)", req.uid)

    def _deliver_finish(self, req: Request, index: int,
                        result: RequestResult) -> None:
        cb = req.on_branch_finish
        if cb is not None:
            try:
                cb(index, result)
            except Exception:
                log.exception("on_branch_finish failed (rid %s)", req.uid)
            return
        if index == 0 and req.on_finish is not None:
            try:
                req.on_finish(result)
            except Exception:
                log.exception("on_finish callback failed (rid %s)", req.uid)

    def _push_token(self, req: Request, tok: int, index: int = 0) -> None:
        fam = self._families.get(req.uid)
        if fam is not None and fam.best_of:
            # Server-side selection: nothing streams until the family
            # joins and _emit_best_of replays the winner.
            return
        self._deliver_token(req, index, tok)

    def _notify_finish(self, req: Request, result: RequestResult,
                       fam: Optional["_ForkFamily"] = None) -> None:
        fam = fam if fam is not None else self._families.get(req.uid)
        if fam is not None and fam.best_of:
            return  # the family join emits the one winner finish
        self._deliver_finish(req, result.index, result)

    def _finish_unadmitted(self, req: Request, tick: int, outcome: str,
                           results: List[RequestResult],
                           visible_at: float, now: float) -> None:
        """Retire a request that never reached a slot (cancelled,
        deadline-expired, or shed while queued; invalid live
        submission). No engine resources to release — only the
        result(s), the outcome counter, and the client callback. An
        n/best_of family rejects whole: one result PER requested
        completion, so a client counting n finishes always converges."""
        branches = self._branches(req)
        for index in range(branches):
            res = RequestResult(
                uid=req.uid,
                tokens=[],
                prompt_len=len(req.prompt),
                arrival_tick=req.arrival_tick,
                admit_tick=-1,
                finish_tick=tick,
                queue_wait_s=max(now - visible_at, 0.0),
                completion_s=max(now - visible_at, 0.0),
                outcome=outcome,
                ttft_s=0.0,
                index=index,
            )
            results.append(res)
            if outcome in (OUTCOME_DEADLINE, OUTCOME_SHED, OUTCOME_ERROR):
                # A categorical SLO miss: the system failed to serve it.
                # (Client cancellations are not the server's miss.)
                self.slo.observe_miss()
            if obs.REGISTRY.enabled:
                _REQUESTS.labels(outcome=outcome).inc()
            self._deliver_finish(req, index, res)
        if obs.TRACER.active:
            obs.instant("request_rejected", cat="serving", args={
                "rid": req.uid, "tick": tick, "outcome": outcome,
                "branches": branches,
            })

    # -- scheduler --------------------------------------------------------

    def _free_slots(self) -> List[int]:
        return [i for i, st in enumerate(self._slot_state) if st == "free"]

    @staticmethod
    def _branches(req: Request) -> int:
        """How many completions request ``req`` fans out to (ISSUE 15):
        ``best_of`` branches when server-side selection is on, else
        ``n`` — always >= 1."""
        bo = req.best_of if req.best_of is not None else 0
        return max(int(req.n), int(bo), 1)

    # Overridden to False on engines that cannot expand fork families
    # (the disaggregated pair's workers — a family would need slots on
    # both sides of the handoff).
    _fork_ok = True

    # -- token-tree sibling decode (ISSUE 20) -----------------------------

    def _tree_span(self, req: Request) -> int:
        """Worst-case token span of an admission tree family: the frozen
        prompt rows plus every branch's full replayed suffix (each
        branch grows to ``max_new - 1`` suffix rows before its last
        token retires it), floored at the plain single-branch span the
        slot needs after the family collapses to one survivor."""
        k = self._branches(req)
        plen = len(req.prompt)
        return max(plen + k * (req.max_new_tokens - 1),
                   plen + req.max_new_tokens)

    def _tree_sibling_ok(self, req: Request) -> bool:
        """Can this n>1 / best-of-n request decode as a token tree in
        ONE slot? Requires the tree-mask attention path and the whole
        family's worst-case row bundle fitting both the verify Tq cap (int32 bitmask: 32 rows) and the cache window.
        False falls back to the PR-15 fork-slot path — same tokens,
        k slots."""
        k = self._branches(req)
        if k <= 1 or not self._tree_sampling:
            return False
        if self._speculate or not self._tree_ok or not self._fork_ok:
            return False
        rows = k * (req.max_new_tokens - 1)
        if rows > self._spec_rows_cap:
            return False
        return len(req.prompt) + rows <= self.cache_len

    # Admission-scoped host-tier attribution scratch (ISSUE 16): counts
    # accumulated while _admit runs — prefix-path restores by
    # _paged_hit, demote flushes a dry allocator forces mid-admission —
    # and folded into the request's ledger once it opens at the end of
    # _admit. Plain ints, engine-thread only.
    _admitting = False
    _adm_restored = 0
    _adm_demoted = 0

    def _validate(self, req: Request) -> None:
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.temperature is not None and req.temperature < 0:
            raise ValueError(
                f"request {req.uid}: temperature must be >= 0"
            )
        if req.top_k is not None and req.top_k < 0:
            raise ValueError(f"request {req.uid}: top_k must be >= 0")
        if req.n < 1:
            raise ValueError(f"request {req.uid}: n must be >= 1")
        if req.best_of is not None and req.best_of > 1 and req.n != 1:
            raise ValueError(
                f"request {req.uid}: best_of runs server-side selection "
                f"and streams ONE winner — it requires n == 1"
            )
        if req.fork_at is not None and req.fork_at < 1:
            raise ValueError(f"request {req.uid}: fork_at must be >= 1")
        branches = self._branches(req)
        if branches > 1 or req.fork_at is not None:
            self._refuse_fork(
                f"request {req.uid}'s n / best_of > 1 or fork_at (forks)")
        if branches > 1:
            if self._speculate:
                raise ValueError(
                    f"request {req.uid}: n/best_of > 1 is not supported "
                    f"with speculate=True (fork branches are sampled; "
                    f"speculation is greedy-only)"
                )
            if not self._fork_ok:
                raise ValueError(
                    f"request {req.uid}: n/best_of > 1 is not supported "
                    f"on this engine (disaggregated workers cannot "
                    f"expand fork families; mid-generation fork(uid) "
                    f"on the decode pool still works)"
                )
            if branches > self.slots and not self._tree_sibling_ok(req):
                # Tree-sibling families (ISSUE 20) decode every branch
                # in ONE slot; only the fork-slot fallback needs a slot
                # per branch.
                raise ValueError(
                    f"request {req.uid}: {branches} parallel branches "
                    f"exceed the engine's {self.slots} slots (the whole "
                    f"family decodes concurrently)"
                )
        if req.max_new_tokens < 1:
            # The prefill itself samples one token, so a zero budget
            # is unservable — same contract as generate().
            raise ValueError(
                f"request {req.uid}: max_new_tokens must be >= 1, "
                f"got {req.max_new_tokens}"
            )
        if plen + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request {req.uid}: prompt {plen} + max_new "
                f"{req.max_new_tokens} exceeds slot capacity {self.cache_len}"
            )
        # The clean over-subscription failure: a request whose worst
        # case exceeds the WHOLE pool can never be admitted — reject
        # it here, in English, instead of wedging the queue (a
        # merely-scarce pool defers admission instead; see serve()).
        need = self._blocks_for(plen + req.max_new_tokens)
        if need > self.kv_blocks:
            raise ValueError(
                f"request {req.uid}: worst case needs {need} KV "
                f"blocks (prompt {plen} + max_new "
                f"{req.max_new_tokens} at --kv-block {self.kv_block}) "
                f"but the --kv-blocks pool holds {self.kv_blocks}; "
                f"raise --kv-blocks or shrink the request"
            )
        branches = self._branches(req)
        if branches > 1:
            if self._tree_sibling_ok(req):
                # Tree-sibling worst case: the ONE slot's frozen
                # ancestor rows plus every branch's packed suffix
                # window (never more than the fork-slot family
                # below — the suffix rows share every ancestor).
                fam = -(-self._tree_span(req) // self.kv_block)
            else:
                # Each sibling's worst case is its NEW blocks only —
                # everything below the fork point is shared (the CoW
                # economics this subsystem exists for).
                fam = need + (branches - 1) * (
                    need - (plen - 1) // self.kv_block
                )
            if fam > self.kv_blocks:
                raise ValueError(
                    f"request {req.uid}: a {branches}-branch family "
                    f"worst-cases at {fam} KV blocks (shared "
                    f"ancestors counted once) but the pool holds "
                    f"{self.kv_blocks}; raise --kv-blocks or shrink "
                    f"the request"
                )

    # -- paged-pool bookkeeping -------------------------------------------

    def _paged_reserve(self, req: Request) -> Optional[Tuple[int, List[Any],
                                                             int, int]]:
        """Match (pinning the path) + reserve the admission's worst-case
        private blocks; ``None`` defers the admission — the request waits
        in the queue until retires/evictions free blocks. The prefix
        hit's DEVICE-resident shared blocks subtract from the reservation
        (the sharing that lets slot-count exceed pool bytes — int8
        included now that per-block scales make its blocks shareable);
        a matched node sitting on the HOST tier still costs one
        reservation, because restoring it allocates a fresh device block
        (the restore consumes exactly that reservation in _paged_hit).

        A fork family (``n``/``best_of`` > 1, ISSUE 15) reserves
        ATOMICALLY: the parent's blocks plus each sibling's worst-case
        NEW blocks (its total minus the full ancestors it will share) —
        so sibling forks later never fail, and two half-reserved
        families can never deadlock the pool against each other. The
        family extra is returned separately and held by the family
        until the forks consume it."""
        total = self._blocks_for(len(req.prompt) + req.max_new_tokens)
        matched, nodes = 0, []
        if self._prefix is not None:
            matched, nodes = self._prefix.match(
                np.asarray(req.prompt, np.int32), record=False
            )
        win_nodes: List[Any] = []
        if self._win is not None and nodes:
            # A hit restores every kind of layer's state or none: it ends
            # at the deepest boundary whose window blocks the tree still
            # keeps, else shallower, else nowhere. The blocks it will map
            # are pinned before the reservation is asked for, as the path
            # is.
            deep = self._prefix.window_depth(nodes)
            self._prefix.release(nodes[deep:])
            nodes = nodes[:deep]
            matched = deep * self.kv_block
            win_nodes = [n for _, n in self._prefix.window_nodes(nodes)]
            self._prefix.pin_window(win_nodes)
        dev_matched = sum(1 for n in nodes if n.tier == TIER_DEVICE)
        branches = self._branches(req)
        fam_extra = 0
        if branches > 1:
            if self._tree_sibling_ok(req):
                # Token-tree sibling admission (ISSUE 20): ONE slot
                # holds the whole family — its reservation is the
                # packed window's worst case, no per-sibling extra.
                total = -(-self._tree_span(req) // self.kv_block)
            else:
                sib = total - (len(req.prompt) - 1) // self.kv_block
                fam_extra = (branches - 1) * sib
        needed = total - dev_matched
        ok = self._pool.reserve(needed + fam_extra)
        if ok and self._win is not None and not self._win.reserve():
            # By kind: full blocks by the request's length, a constant of
            # window blocks (which the window pool's size always grants).
            self._pool.unreserve(needed + fam_extra)
            ok = False
        if not ok:
            if nodes:
                self._prefix.unpin_window(win_nodes)
                self._prefix.release(nodes)
            return None
        if self._prefix is not None:
            self._prefix.record_match(matched)
        return matched, nodes, needed, fam_extra

    def _ensure_blocks(self, slot: int, tokens_needed: int,
                       rows: int = 1) -> None:
        """Map physical blocks covering ``[0, tokens_needed)`` tokens of
        ``slot`` — called before every dispatch that writes the slot.
        Allocation is backed by the admission's reservation, so it cannot
        fail; a full free list recycles LRU refcount-0 prefix leaves.
        The dispatch writes the last ``rows`` of those tokens: in the
        window layers' table the blocks they fall in are mapped and every
        block behind the window of the first of them is given back."""
        if self._win is not None:
            self._tick_wfreed += self._win.advance(
                slot, tokens_needed - rows, tokens_needed)
        need = self._blocks_for(tokens_needed)
        grew = self._slot_nblocks[slot] < need
        while self._slot_nblocks[slot] < need:
            assert self._slot_reserve[slot] > 0, (
                f"slot {slot} outgrew its block reservation"
            )
            bid = self._pool.alloc()
            self._slot_reserve[slot] -= 1
            self._host_table[slot, self._slot_nblocks[slot]] = bid
            self._slot_private[slot].add(bid)
            self._slot_nblocks[slot] += 1
            self._table_dirty = True
        if grew and obs.REQLOG.enabled:
            # Re-integrate the ledger's device-block-seconds at the new
            # block count (once per block boundary, not per token).
            rq = self._slot_req[slot]
            if rq is not None:
                obs.REQLOG.blocks(rq.uid, self._slot_nblocks[slot])

    def _blocks_for(self, tokens: int) -> int:
        """Blocks of the first table's pool a slot of ``tokens`` positions
        has rows in: a row a position, or (an EVA model: ``_row_span``) a
        summary row for every chunk those positions have closed."""
        return -(-(tokens // self._row_span) // self.kv_block)

    def _window_cached(self) -> int:
        """Window-pool blocks the prefix tree keeps (0 without one)."""
        return 0 if self._prefix is None \
            else self._prefix.window_blocks_used

    def _sync_table(self) -> None:
        """Push the host block table to the device when it changed — the
        ONE host→device transfer a table update costs (a few hundred
        int32s)."""
        if self._table_dirty:
            # A copy: ``jnp.asarray`` may alias the numpy array on the CPU
            # backend, and a program still in flight reads this table
            # while a retire clears its rows for the next one.
            self.cache = dataclasses.replace(
                self.cache, table=jnp.asarray(self._host_table.copy())
            )
            self._table_dirty = False
        if self._win is not None and self._win.dirty:
            self.cache = dataclasses.replace(
                self.cache, wtable=jnp.asarray(self._win.table.copy())
            )
            self._win.dirty = False

    def attach_host_tier(self, host_pool: HostBlockPool) -> None:
        """Wire ``host_pool`` as this engine's KV demotion tier: build
        the demote-gather / restore-scatter jits (the ONE home of their
        donation recipe) and register the staged-flush hook on the
        allocator. Called by ``__init__`` for ``host_blocks=`` and by
        ``DisaggServer`` to make the prefill worker the SHARED tree's
        tier engine (there the pool was built by the pair, the index
        already points at it, and the relayed pool arrays make this
        engine's cache the live pool whichever worker dispatched last)."""
        self._host_pool = host_pool
        self.host_blocks = host_pool.blocks
        self._demote_gather = jax.jit(gather_kv_blocks)
        self._restore_scatter = jax.jit(
            scatter_kv_blocks,
            donate_argnums=(0, 1) if not self.quantize
            else (0, 1, 5, 6),
        )
        self._pool.set_demote_flusher(self._flush_demotions)

    def _flush_demotions(self) -> int:
        """Complete every staged demotion: ONE jitted gather over the
        batch of pending device blocks, one D2H fetch, then the blocks
        free. Called at the end of each tick (off the tick's dispatch
        path — the gather queues behind the tick's step and the fetch
        happens where the loop would otherwise idle) and by a dry
        allocator mid-tick (rare; the batch amortisation is the point).
        Returns how many device blocks it freed."""
        hp = self._host_pool
        if hp is None or not hp.pending:
            return 0
        items = hp.take_pending()
        rows = [r for r, _ in items]
        bids = [b for _, b in items]
        nb = _bucket(len(bids), max(self._npb, len(bids)), floor=1)
        ids = np.zeros((nb,), np.int32)  # pad gathers block 0; ignored
        ids[:len(bids)] = bids
        if self.quantize:
            out = self._demote_gather(
                self.cache.k, self.cache.v, jnp.asarray(ids),
                self.cache.k_scale, self.cache.v_scale,
            )
        else:
            out = self._demote_gather(
                self.cache.k, self.cache.v, jnp.asarray(ids)
            )
        hp.commit(rows, *out)  # the D2H fetch happens inside commit
        for b in bids:
            self._pool.free_demoted(b)
        if self._admitting:
            # A dry allocator forced this flush mid-admission: charge
            # the demotions to the admitting request's ledger scratch.
            self._adm_demoted += len(bids)
        return len(bids)

    def _admit(self, req: Request, slot: int, tick: int,
               visible_at: float,
               resv: Tuple[int, List[Any], int, int]) -> float:
        # Queue wait ends the moment the scheduler takes the request —
        # BEFORE any prefill work runs (prefill, including a first-bucket
        # jit compile, is service time, not queueing).
        waited = max(time.monotonic() - visible_at, 0.0)
        self._admitting = True
        self._adm_restored = 0
        self._adm_demoted = 0
        self._slot_req[slot] = req
        self._slot_tokens[slot] = []
        self._slot_admit[slot] = (tick, visible_at)
        self._slot_max_tbt[slot] = 0.0
        self._slot_ttft[slot] = 0.0  # stale-occupant guard: a request
        # retired before its first token must report ttft 0, not the
        # previous occupant's
        self._slot_wait[slot] = waited
        self._chunk_k[slot] = 0
        # Sampling state (ISSUE 15): per-slot temperature/top-k (engine
        # defaults unless the request overrides) and the request's PRNG
        # key — fold_in(base, seed-or-uid) at branch 0. Pure host/np
        # writes plus one tiny key dispatch; the vectors ride every
        # dispatch as operands.
        self._slot_index[slot] = 0
        self._slot_cum_lp[slot] = 0.0
        self._slot_shared[slot] = set()
        self._temp_np[slot] = (self.temperature if req.temperature is None
                               else req.temperature)
        self._topk_np[slot] = (self.top_k if req.top_k is None
                               else req.top_k)
        salt = (req.seed if req.seed is not None else req.uid) & 0x7FFFFFFF
        self._salt_np[slot] = salt
        self._keys = self._seed_key(self._keys, jnp.int32(slot),
                                    jnp.int32(salt), jnp.int32(0))
        self.slo.observe_queue_wait(waited)
        # Prefix reuse happens FIRST: the matched length decides how much
        # prompt is left to prefill (and rides the request span below).
        self._prompt_np[slot] = np.asarray(req.prompt, np.int32)
        if self._speculate:
            plen = len(self._prompt_np[slot])
            self._hist_buf[slot, :plen] = self._prompt_np[slot]
            self._hist_len[slot] = plen
        # The reservation was taken (and the radix path pinned) by
        # _paged_reserve in the admit loop — here the slot takes
        # ownership of both.
        _, _, needed, _ = resv
        self._slot_reserve[slot] = needed
        self._slot_private[slot] = set()
        self._slot_nblocks[slot] = 0
        if self._win is not None:
            self._win.admit(slot)
        matched = self._paged_hit(req, slot, tick, resv)
        self._prefill_start[slot] = matched
        self._slot_prefix_hit[slot] = matched
        # The request's life as ONE span (admit -> retire; rid in args so
        # a Perfetto query groups every event of one request), plus an
        # admitted instant on the timeline.
        self._slot_span[slot] = obs.span(
            f"request:{req.uid}", cat="serving",
            args=None if not obs.TRACER.active else {
                "rid": req.uid, "slot": slot, "admit_tick": tick,
                "prompt_len": len(req.prompt),
                **({"prefix_hit_len": matched}
                   if self._prefix is not None else {}),
                **({"trace_id": req.trace[0]} if req.trace else {}),
            },
        )
        if obs.TRACER.active:
            obs.instant("request_admitted", cat="serving", args={
                "rid": req.uid, "slot": slot, "tick": tick,
                "queue_wait_s": round(waited, 6),
            })
            if req.trace is not None:
                # Step point of the request's cross-process flow; binds
                # to the slice enclosing this instant (ISSUE 16).
                obs.flow("t", obs.flow_id(req.trace[0]))
        if obs.REQLOG.enabled:
            obs.REQLOG.open(
                req.uid,
                trace_id=req.trace[0] if req.trace else "",
                span_id=obs.new_span_id(),
                parent_span_id=req.trace[1] if req.trace else "",
                prompt_tokens=len(req.prompt),
                prefix_hit_tokens=matched,
                arrival_tick=req.arrival_tick,
                admit_tick=tick,
                queue_wait_s=waited,
                nblocks=self._slot_nblocks[slot],
            )
            if self._adm_restored or self._adm_demoted:
                obs.REQLOG.note(req.uid,
                                host_restores=self._adm_restored,
                                host_demotes=self._adm_demoted)
        self._admitting = False
        self._prefill_pos[slot] = matched
        self._slot_state[slot] = "prefill"
        self._prefill_fifo.append(slot)
        if obs.REGISTRY.enabled:
            _QUEUE_WAIT.observe(waited)
        return waited

    def _restore_demoted(self, slot: int, nodes: List[Any]) -> int:
        """Bring a pinned path's host-tier nodes back onto the device:
        still-pending demotions cancel in place (zero copies); flushed
        ones take fresh device blocks from the slot's reservation and
        land in ONE batched H2D scatter. Returns how many blocks were
        restored (either arc — each was a device-capacity miss the host
        tier absorbed)."""
        demoted = self._prefix.demoted_in(nodes)
        if not demoted:
            return 0

        def take_one() -> int:
            assert self._slot_reserve[slot] > 0, (
                f"slot {slot} restore outgrew its block reservation"
            )
            bid = self._pool.alloc()
            self._slot_reserve[slot] -= 1
            return bid

        rows, bids = self._prefix.restore_nodes(demoted, take_one)
        if rows:
            hp = self._host_pool
            staged = hp.read(rows)
            nb = _bucket(len(bids), self._npb, floor=1)
            ids = np.full((nb,), self.kv_blocks, np.int32)  # pad: dropped
            ids[:len(bids)] = bids

            def pad(a: np.ndarray) -> jax.Array:
                out = np.zeros((nb,) + a.shape[1:], a.dtype)
                out[:len(rows)] = a
                return jnp.asarray(out)

            if self.quantize:
                hk, hv, hks, hvs = staged
                k, v, ks, vs = self._restore_scatter(
                    self.cache.k, self.cache.v, jnp.asarray(ids),
                    pad(hk), pad(hv), self.cache.k_scale,
                    self.cache.v_scale, pad(hks), pad(hvs),
                )
                self.cache = dataclasses.replace(
                    self.cache, k=k, v=v, k_scale=ks, v_scale=vs
                )
            else:
                hk, hv = staged
                k, v = self._restore_scatter(
                    self.cache.k, self.cache.v, jnp.asarray(ids),
                    pad(hk), pad(hv),
                )
                self.cache = dataclasses.replace(self.cache, k=k, v=v)
            for row in rows:
                hp.release(row, restored=True)
        return len(demoted)

    def _paged_hit(self, req: Request, slot: int, tick: int,
                   resv: Tuple[int, List[Any], int, int]) -> int:
        """The reference-in-place hit (paged serving): write the matched
        path's pool ids into the slot's table row and set the prefill
        start — pure host bookkeeping, ZERO device KV bytes moved on the
        exact tier (``bytes_moved=0`` on the instant is the measured
        claim, not a slogan: the device sees nothing until the next
        dispatch ships the updated int32 table). Demoted path nodes
        restore FIRST (one batched H2D scatter; their bytes are the
        restore cost, amortized into the admission like the suffix's
        chunks). int8 hits additionally dequant-gather the matched
        blocks into the staging cache — the suffix's exact staged
        prefill attends them as activations-grade rows — and THOSE are
        the bytes the instant reports for int8."""
        matched, nodes, _, _ = resv
        self._slot_nodes[slot] = nodes
        if not matched:
            return 0
        restored = 0
        if self._host_pool is not None:
            restored = self._restore_demoted(slot, nodes)
            self._tick_restored += restored
            self._adm_restored += restored
        for j, node in enumerate(nodes):
            self._host_table[slot, j] = node.block_id
        self._slot_nblocks[slot] = matched // self.kv_block
        self._table_dirty = True
        if self._win is not None:
            # The window layers' state at the boundary: the tree's own
            # blocks, pinned by ``_paged_reserve``.
            self._win.hit(slot, self._prefix.window_nodes(nodes))
        moved = 0
        if self.quantize:
            # Dequantize the matched int8 blocks into staging slot 0 so
            # the suffix's staged chunks see the prefix. One jitted
            # donated gather; re-quantizing at final chunk reproduces
            # the shared blocks' bytes exactly, so they are never
            # rewritten (paged_insert_slot's ``lo``). The bucket cap is
            # FLOOR-div (the staged window nb*kv_block must fit inside
            # the staging cache — ceil would overhang a cache_len that
            # is not block-divisible); a matched path is at most
            # (cache_len - 1) // kv_block blocks, so the cap holds.
            nb = _bucket(len(nodes), self.cache_len // self.kv_block,
                         floor=1)
            ids = np.zeros((nb,), np.int32)
            ids[:len(nodes)] = [n.block_id for n in nodes]
            self._staging = self._dequant_hit(
                self._staging, self.cache.k, self.cache.v,
                self.cache.k_scale, self.cache.v_scale,
                jnp.asarray(ids), jnp.int32(matched),
            )
            moved = matched * self._kv_token_bytes_q
            self._hit_bytes_moved += moved
        self._tick_prefix_hits += 1
        self._tick_prefix_reused += matched
        if obs.TRACER.active:
            obs.instant("prefix_hit", cat="serving", args={
                "rid": req.uid, "slot": slot, "tick": tick,
                "matched_tokens": matched,
                "prompt_len": len(req.prompt),
                "bytes_moved": moved,
                **({"restored_blocks": restored}
                   if self._host_pool is not None else {}),
            })
        return matched

    def _publish_prefix(self, slot: int) -> None:
        """At final-chunk completion: hand the prompt's full blocks to
        the radix tree and swap the slot's pinned path for the published
        one. Publishing is ADOPTION — ownership of the slot's private
        prompt blocks moves to the tree through the allocator's ledger,
        the KV bytes (int8 blocks with their per-block scales included)
        stay exactly where the prefill scattered them, and the slot
        keeps reading them through its unchanged table (zero device
        work)."""
        if self._prefix is None:
            return
        prompt = self._prompt_np[slot]
        nb_full = len(prompt) // self.kv_block
        private = self._slot_private[slot]
        phys = {
            j: int(self._host_table[slot, j]) for j in range(nb_full)
            if int(self._host_table[slot, j]) in private
        }
        path, adopted = self._prefix.adopt(
            prompt, phys, self._slot_nodes[slot]
        )
        for j in adopted:
            private.discard(int(self._host_table[slot, j]))
        # The admit-time pins carried over into ``path`` (plus the
        # freshly created nodes); retire releases them all at once.
        self._slot_nodes[slot] = path
        if self._win is not None:
            # The window blocks the slot still holds of its prompt's full
            # blocks (the last ones: the rest went back as the chunks
            # went by) are the tree's to keep from now on.
            for j, node in enumerate(path[:nb_full]):
                self._win.publish(slot, j, node, self._prefix)

    def _plan_chunks(
        self, max_n: Optional[int] = None
    ) -> List[Tuple[int, int, bool]]:
        """Sarathi-style budget pass: FIFO over prefilling slots, each
        taking up to a chunk, the tick taking at most ``prefill_budget``
        prompt tokens total and at most as many slots as the packed
        tick's chunk group has members. ``max_n`` clamps the per-slot
        chunk below the configured size — ticks that carry a token-tree
        sibling bundle (ISSUE 20) must keep Tq within the int32
        tree-bitmask limit, so their chunks shrink to fit. Returns
        (slot, n, is_final) triples."""
        plan: List[Tuple[int, int, bool]] = []
        budget = self.prefill_budget
        chunk = self.prefill_chunk
        if max_n is not None:
            chunk = min(chunk, max_n)
        for slot in self._prefill_fifo:
            if budget <= 0 or len(plan) == self._chunk_group:
                break
            plen = len(self._slot_req[slot].prompt)
            pos = self._prefill_pos[slot]
            n = min(chunk, plen - pos, budget)
            if n <= 0:
                continue
            budget -= n
            plan.append((slot, n, pos + n == plen))
        return plan

    def _planned_len(self, slot: int) -> int:
        """Tokens ``slot`` has sampled, the one in flight included: what
        the head plans with while a program's tail is pending (blocks to
        map, the sample index of the next draw). Counts, never values:
        the token itself stays in the device vector."""
        n = len(self._slot_tokens[slot])
        tail = self._tail
        if tail is not None and tail.flying(slot, self._slot_req[slot]):
            n += 1
        return n

    def _plan_tick(self) -> Tuple[List[Tuple[int, int, bool]], List[int],
                                  Optional[str]]:
        """What the next program holds and whether it may be dispatched
        before the pending tail lands: the chunk plan, the slots that get
        a decode row, and the reason the tick must be synchronous
        (``None``: it can look ahead).

        With a tail pending a slot's state is one tick old: a live slot
        whose token in flight is its last gets no row (it retires when
        the tail lands), and a slot whose final chunk is in flight
        decodes already, its first token read from the device vector.
        The synchronous ticks are the ones that need token VALUES on the
        host before they can be planned, told from engine state alone:
        draft-and-verify, token-tree and fork families (and a fork still
        carried), staged int8 prefill, and a tick with nothing to
        dispatch."""
        plan = self._plan_chunks(max_n=32 if self._tree_fams else None)
        tail = self._tail
        live_idx = []
        for i, st in enumerate(self._slot_state):
            req = self._slot_req[i]
            if tail is not None and tail.flying(i, req):
                # Live or awaiting its first token: a row unless the
                # token in flight is the request's last.
                if len(self._slot_tokens[i]) + 1 < req.max_new_tokens:
                    live_idx.append(i)
            elif st == "live":
                live_idx.append(i)
        if self._speculate:
            why: Optional[str] = "spec"
        elif self._tree_fams:
            why = "tree"
        elif self._families or self._live_reset or self._fork_carry:
            why = "fork"
        elif self._staged_prefill and plan:
            why = "staged"
        elif not plan and not live_idx:
            why = "awaits"
        else:
            why = None
        return plan, live_idx, why

    def _tick_counts(self) -> Dict[str, Any]:
        """The per-tick counters, as the flight record spells them."""
        out: Dict[str, Any] = {
            "prefix_hits": self._tick_prefix_hits,
            "prefix_reused": self._tick_prefix_reused,
            # Robustness arcs this tick (ISSUE 10): the black box must
            # show a storm the way it showed a wedge.
            "cancelled": self._tick_cancelled,
            "deadline_expired": self._tick_deadline,
            "shed": self._tick_shed,
            # Copy-on-write forks this tick (ISSUE 15) and the ancestor
            # blocks they shared instead of copying.
            "forks": self._tick_forks,
            "shared_blocks": self._tick_fork_shared,
            # Token-tree sibling decode this tick (ISSUE 20): branches
            # advanced in-slot, branches retired out of their bundles.
            "tree_branches": self._tick_tree_branches,
            "branch_retired": self._tick_branch_retired,
        }
        if self._win is not None:
            # Layers whose block counts differ: window-table entries
            # mapped over all slots, those given back this tick, what the
            # table would hold had none been (the full layers' entries),
            # and the kernel lists' counts by kind.
            out["window_blocks_held"] = self._win.held()
            out["window_blocks_freed"] = self._tick_wfreed
            # (An EVA model's first table counts summary blocks: its
            # positions' blocks are counted from the lengths.)
            out["window_blocks_full"] = sum(self._slot_nblocks) \
                if not self._eva_layers else int(
                    (-(-self._kv_len // self.kv_block)).sum())
            out.update(self._tick_eva)
            for kind, (k_run, k_grid) in self._tick_kv_kinds.items():
                out[f"kv_steps_run_{kind}"] = k_run
                out[f"kv_steps_grid_{kind}"] = k_grid
        if self._host_pool is not None:
            out["restored_blocks"] = self._tick_restored
        if self._speculate:
            s_slots, s_prop, s_acc = self._tick_spec
            out["spec_verify"] = {"slots": s_slots, "proposed": s_prop,
                                  "accepted": s_acc}
        return out

    # -- copy-on-write forking (ISSUE 15) ---------------------------------

    def _admit_family(self, req: Request, parent_slot: int,
                      free: List[int], resv) -> None:
        """Reserve the rest of an n>1 / best-of-n family at admission:
        one ``fpend`` slot per sibling (taken NOW so two half-admitted
        families can never deadlock waiting on each other's slots) and
        the block hold ``_paged_reserve`` already took. The siblings
        fork in the awaits pass, the tick the parent's first token
        lands."""
        branches = self._branches(req)
        _, _, _, fam_extra = resv
        sibs = [free.pop(0) for _ in range(branches - 1)]
        for s in sibs:
            self._slot_state[s] = "fpend"
        self._families[req.uid] = _ForkFamily(
            req=req, parent_slot=parent_slot, sibling_slots=sibs,
            sib_reserve=fam_extra // (branches - 1), hold=fam_extra,
            best_of=bool(req.best_of and req.best_of > 1),
            branches=branches,
        )
        self._uid_next_index[req.uid] = branches

    def _fork_family(self, fam: _ForkFamily, parent_slot: int,
                     tick: int, now2: float, results) -> int:
        """Fork every reserved sibling off the freshly-live parent —
        called from the awaits pass BEFORE the parent's EOS check, so
        even a one-token parent yields n independent samples. Each
        sibling's block budget moves from the family hold to the slot's
        reservation ledger; each sibling's first token (sampled by
        ``_sibling_first`` from the parent's exact prompt-end logits)
        surfaces through ONE extra batched fetch — a per-family
        admission-time cost, not a per-tick one — and the sibling goes
        live exactly like a final-chunk slot, EOS/budget checks
        included. Returns the number of first tokens emitted."""
        req = fam.req
        for j, child in enumerate(fam.sibling_slots):
            self._fork_child(parent_slot, child, 1 + j, [],
                             fam.sib_reserve, tick)
            fam.hold -= fam.sib_reserve
        fam.forked = True
        self._slot_logits[parent_slot] = None
        tok_h = np.asarray(self.tok)
        lp_h = np.asarray(self._lp)
        th = np.array(self._tok_host)
        emitted = 0
        for j, child in enumerate(fam.sibling_slots):
            t0 = int(tok_h[child])
            th[child] = t0
            self._slot_tokens[child] = [t0]
            self._slot_cum_lp[child] = float(lp_h[child])
            self._slot_state[child] = "live"
            self._slot_clen[child] = len(self._prompt_np[child])
            self._push_token(req, t0, 1 + j)
            _, vis = self._slot_admit[child]
            self._slot_ttft[child] = max(now2 - vis, 0.0)
            self._last_tok_t[child] = now2
            emitted += 1
            self.slo.observe_ttft(self._slot_ttft[child])
            if obs.REGISTRY.enabled:
                _TOKENS.inc()
                _TTFT.observe(self._slot_ttft[child])
            if obs.TRACER.active:
                obs.instant("first_token", cat="serving", args={
                    "rid": req.uid, "slot": child, "tick": tick,
                    "index": 1 + j,
                    "ttft_s": round(self._slot_ttft[child], 6),
                })
            if req.eos_id is not None and t0 == req.eos_id:
                self._retire(child, tick, OUTCOME_EOS, results)
            elif req.max_new_tokens <= 1:
                self._retire(child, tick, OUTCOME_BUDGET, results)
        self._tok_host = th
        return emitted

    def _fork_child(self, parent_slot: int, child_slot: int, index: int,
                    tokens_prefix: List[int], resv_blocks: int,
                    tick: int) -> None:
        """THE copy-on-write fork (vLLM's fork over PagedAttention block
        tables, arXiv:2309.06180): give ``child_slot`` the parent's
        history up to the fork point by SHARING every full ancestor
        block — radix-cached ancestors get one more pin, parent-private
        ones refcount into the allocator's ``shared`` state — and
        copying ONLY the partial tail block (one jitted dispatch; none
        when the fork point is block-aligned). Two flavors, both exact:

        - admission sibling (``tokens_prefix == []``): fork point = the
          prompt end. The child's first token samples from the parent's
          STASHED prompt-end logits under the child's own key (branch
          ``index`` folded in) — bit-identical inputs to the parent's
          own sample, so greedy siblings match an independent admission
          token-for-token — and parks in the device vectors; the child
          rides the existing ``await`` machinery from there. No KV row
          is ever recomputed.
        - mid-generation branch (``tokens_prefix`` = the parent's
          emitted stream): fork point = the last COMMITTED row; the
          shared tip token is re-consumed by parent and child alike,
          each writing its own FRESH copy of that row, and the child's
          next sample (its own key, stream index ``len(tokens_prefix)``)
          diverges.

        ``resv_blocks`` is the child's worst-case NEW-block budget,
        already reserved by the caller."""
        req = self._slot_req[parent_slot]
        prompt = self._prompt_np[parent_slot]
        plen = len(prompt)
        if tokens_prefix:
            tip = int(tokens_prefix[-1])
            L = plen + len(tokens_prefix) - 1
        else:
            tip = 0  # placeholder; _sibling_first parks the real token
            L = plen
        B = self.kv_block
        nshare = L // B
        # Shared ancestors, radix tier first: the child pins the
        # parent's matched/published path like a second admission.
        pnodes = self._slot_nodes[parent_slot]
        n_pin = min(nshare, len(pnodes))
        if n_pin:
            child_nodes = self._prefix.repin(pnodes[:n_pin])
            self._slot_nodes[child_slot] = child_nodes
        else:
            self._slot_nodes[child_slot] = []
        # ...then CoW-refcount the rest (parent-private decode blocks,
        # or unpublished prompt blocks when the prefix cache is off).
        share_bids = [int(self._host_table[parent_slot, j])
                      for j in range(n_pin, nshare)]
        self._slot_shared[child_slot] = set(
            self._pool.fork_shared(share_bids)
        )
        for bid in share_bids:
            self._slot_private[parent_slot].discard(bid)
            self._slot_shared[parent_slot].add(bid)
        self._host_table[child_slot, :nshare] = \
            self._host_table[parent_slot, :nshare]
        self._host_table[child_slot, nshare:] = 0
        self._slot_reserve[child_slot] = resv_blocks
        self._slot_private[child_slot] = set()
        self._slot_nblocks[child_slot] = nshare
        need_copy = (L % B) != 0
        if need_copy:
            src = int(self._host_table[parent_slot, nshare])
            assert self._slot_reserve[child_slot] > 0, (
                f"fork into slot {child_slot} outgrew its reservation"
            )
            dst = self._pool.alloc()
            self._slot_reserve[child_slot] -= 1
            self._host_table[child_slot, nshare] = dst
            self._slot_private[child_slot].add(dst)
            self._slot_nblocks[child_slot] = nshare + 1
        else:
            src = dst = 0  # block-aligned fork: the copy degenerates to
            # a self-write and the program only parks the tip
        self._table_dirty = True
        wcopy: Tuple[Any, ...] = ()
        if self._win is not None:
            # The window layers' half: the blocks the parent holds are
            # shared by reference like the full ones, the partial block
            # copied into one of the child's own constant.
            ok = self._win.reserve()
            assert ok, "the window pool refused a slot its constant"
            self._win.admit(child_slot)
            wcopy = tuple(jnp.int32(b) for b in self._win.fork(
                parent_slot, child_slot, nshare, need_copy))
        self.cache, self.tok = self._fork_copy(
            self.cache, self.tok, jnp.int32(src), jnp.int32(dst),
            jnp.int32(child_slot), jnp.int32(tip), *wcopy,
        )
        salt = (req.seed if req.seed is not None else req.uid) & 0x7FFFFFFF
        self._salt_np[child_slot] = salt
        self._keys = self._seed_key(self._keys, jnp.int32(child_slot),
                                    jnp.int32(salt), jnp.int32(index))
        # Host mirrors: the child is an ordinary live slot from here on.
        self._slot_req[child_slot] = req
        self._slot_index[child_slot] = index
        self._slot_tokens[child_slot] = list(tokens_prefix)
        self._prompt_np[child_slot] = prompt
        self._slot_admit[child_slot] = self._slot_admit[parent_slot]
        self._slot_wait[child_slot] = self._slot_wait[parent_slot]
        self._slot_ttft[child_slot] = (
            self._slot_ttft[parent_slot] if tokens_prefix else 0.0
        )
        self._slot_max_tbt[child_slot] = 0.0
        self._slot_prefix_hit[child_slot] = nshare * B
        self._slot_cum_lp[child_slot] = (
            self._slot_cum_lp[parent_slot] if tokens_prefix else 0.0
        )
        self._temp_np[child_slot] = self._temp_np[parent_slot]
        self._topk_np[child_slot] = self._topk_np[parent_slot]
        self._prefill_start[child_slot] = 0
        self._chunk_k[child_slot] = 0
        self._slot_clen[child_slot] = L
        self._live_reset[child_slot] = L
        if tokens_prefix:
            # Mid-generation branch: an ordinary live slot whose next
            # tick re-consumes the shared tip (a fresh row for each
            # branch) — park the tip host- and device-side.
            self._slot_state[child_slot] = "live"
            self._last_tok_t[child_slot] = self._last_tok_t[parent_slot]
            th = np.array(self._tok_host)  # the fetch view is read-only
            th[child_slot] = tip
            self._tok_host = th
        else:
            # Admission sibling: draw the child's own first token from
            # the parent's stashed prompt-end logits (exactly what an
            # independent admission's prefill would sample from) and
            # ride the await machinery — its TTFT closes at the next
            # batched fetch, like any final-chunk slot.
            row = self._slot_logits[parent_slot]
            assert row is not None, "fork family lost its logits stash"
            self.tok, self._lp = self._sibling_first(
                self.tok, self._lp, row, self._keys[child_slot],
                jnp.float32(self._temp_np[child_slot]),
                jnp.int32(self._topk_np[child_slot]),
                jnp.int32(child_slot),
            )
            self._slot_state[child_slot] = "await"
            self._last_tok_t[child_slot] = self._last_tok_t[parent_slot]
        self._slot_span[child_slot] = obs.span(
            f"request:{req.uid}", cat="serving",
            args=None if not obs.TRACER.active else {
                "rid": req.uid, "slot": child_slot, "admit_tick": tick,
                "prompt_len": len(prompt), "index": index,
                "fork_of_slot": parent_slot,
            },
        )
        self._forks_life += 1
        self._fork_shared_life += nshare
        self._tick_forks += 1
        self._tick_fork_shared += nshare
        if obs.REGISTRY.enabled:
            _FORKS.inc()
            if nshare:
                _FORK_SHARED.inc(nshare)
        if obs.TRACER.active:
            obs.instant("fork", cat="serving", args={
                "rid": req.uid, "tick": tick,
                "parent_slot": parent_slot, "child_slot": child_slot,
                "index": index, "shared_blocks": nshare,
                "copied_blocks": int(need_copy),
                "at_tokens": len(tokens_prefix),
            })
        if obs.REQLOG.enabled and nshare:
            obs.REQLOG.note(req.uid, fork_shared_blocks=nshare)

    def _fork_live(self, uid: int, tick: int,
                   pend_uids: Set[int]) -> str:
        """One mailboxed fork(uid): branch the request's lowest-index
        live slot onto a free slot. Returns ``"done"`` (forked, or a
        no-op for an unknown/finished uid), ``"wait"`` (the request
        exists but is not live yet — queued/prefilling/awaiting; the
        carry keeps the fork pending without burning retries), or
        ``"retry"`` (slot/block scarcity — bounded retries, then the
        fork expires)."""
        if self._speculate:
            log.warning(
                "fork(%d) ignored: forking needs a non-speculative "
                "engine", uid,
            )
            return "done"
        parent = None
        for i, rq in enumerate(self._slot_req):
            if rq is None or rq.uid != uid:
                continue
            if self._tail is not None and self._tail.flying(i, rq):
                return "wait"  # a token in flight: the branch point is
                # not on the host yet; the carry makes the next tick
                # synchronous and the fork applies there
            if self._slot_state[i] == "live":
                if parent is None \
                        or self._slot_index[i] < self._slot_index[parent]:
                    parent = i
            else:
                return "wait"  # still prefilling/awaiting — not yet
                # forkable; the carry holds until it goes live
        if parent is None:
            return "done" if uid not in pend_uids else "wait"
        req = self._slot_req[parent]
        toks = self._slot_tokens[parent]
        if len(toks) >= req.max_new_tokens:
            return "done"  # retiring this tick; nothing left to branch
        if parent in self._tree_fams:
            log.warning(
                "fork(%d) ignored: the slot already decodes a token "
                "tree (one conversion per request)", uid,
            )
            return "done"
        t = len(toks)
        if (self._tree_sampling and self._tree_ok and self._fork_ok
                and req.uid not in self._families
                and 2 * (req.max_new_tokens - t) <= self._spec_rows_cap
                and len(self._prompt_np[parent]) + t - 1
                + 2 * (req.max_new_tokens - t) <= self.cache_len):
            # Tree conversion: both continuations share the slot — zero
            # new slots, zero copied blocks (the partial tail block is
            # shared too; the tip re-enters as a replayed suffix row).
            return self._tree_convert_live(parent, uid, tick)
        free = self._free_slots()
        if not free:
            return "retry"
        L = len(self._prompt_np[parent]) + max(len(toks) - 1, 0)
        need = -(-(len(req.prompt) + req.max_new_tokens)
                 // self.kv_block) - L // self.kv_block
        if not self._pool.reserve(need):
            return "retry"
        idx = self._uid_next_index.get(uid, self._branches(req))
        self._uid_next_index[uid] = idx + 1
        self._fork_child(parent, free[0], idx, list(toks), need, tick)
        return "done"

    def _apply_forks(self, forks: List[int], tick: int,
                     pending) -> None:
        """The control sweep's fork arc: apply mailboxed fork(uid)s and
        re-attempt deferred ones. A fork whose request exists but is
        not live yet (queued / prefilling — "wait") stays carried at
        full TTL until the request goes live; slot/block scarcity
        ("retry") burns one of 3 retries per sweep, then the fork
        expires; genuinely unknown uids age out as no-ops."""
        for uid in forks:
            if uid not in self._fork_carry:
                self._fork_carry[uid] = 3
        pend_uids = {r.uid for r in pending}
        for uid in list(self._fork_carry):
            verdict = self._fork_live(uid, tick, pend_uids)
            if verdict == "done":
                self._fork_carry.pop(uid, None)
            elif verdict == "wait":
                self._fork_carry[uid] = 3  # still coming; keep waiting
            else:
                self._fork_carry[uid] -= 1
                if self._fork_carry[uid] <= 0:
                    del self._fork_carry[uid]
                    log.warning(
                        "fork(%d) expired unserved (slots/blocks "
                        "stayed scarce)", uid,
                    )

    def _family_branch_done(self, fam: _ForkFamily,
                            result: RequestResult) -> None:
        """A branch retired: collect it; the LAST branch completes the
        family ('join') — best-of-n selects and streams its winner."""
        fam.done.append(result)
        if len(fam.done) >= fam.branches:
            self._families.pop(fam.req.uid, None)
            if fam.best_of:
                self._emit_best_of(fam)

    def _cancel_unforked(self, fam: _ForkFamily, parent_result:
                         RequestResult, tick: int, results) -> None:
        """The parent retired BEFORE its first token (cancel/deadline
        mid-prefill): the siblings never forked — free their fpend
        slots, return the family's block hold, and finish each sibling
        unserved with the parent's outcome (one result per requested
        completion, so clients counting n finishes always converge)."""
        if fam.tree:
            # Tree families hold no sibling slots and no family hold —
            # the whole worst case is the parent slot's reservation,
            # already freed by the retire. Only the per-branch results
            # need synthesizing.
            for j in range(1, fam.branches):
                res = dataclasses.replace(
                    parent_result, index=j, tokens=[], cum_logprob=0.0,
                    ttft_s=0.0,
                )
                results.append(res)
                fam.done.append(res)
                if parent_result.outcome in (OUTCOME_DEADLINE,
                                             OUTCOME_SHED,
                                             OUTCOME_ERROR):
                    self.slo.observe_miss()
                if obs.REGISTRY.enabled:
                    _REQUESTS.labels(outcome=res.outcome).inc()
                self._notify_finish(fam.req, res, fam)
            return
        if fam.hold:
            self._pool.unreserve(fam.hold)
            fam.hold = 0
        for j, s in enumerate(fam.sibling_slots):
            self._slot_state[s] = "free"
            res = dataclasses.replace(
                parent_result, index=1 + j, tokens=[], cum_logprob=0.0,
                ttft_s=0.0,
            )
            results.append(res)
            fam.done.append(res)
            if parent_result.outcome in (OUTCOME_DEADLINE, OUTCOME_SHED,
                                         OUTCOME_ERROR):
                self.slo.observe_miss()
            if obs.REGISTRY.enabled:
                _REQUESTS.labels(outcome=res.outcome).inc()
            self._notify_finish(fam.req, res, fam)
        fam.sibling_slots = []

    def _emit_best_of(self, fam: _ForkFamily) -> None:
        """Best-of-n join: pick the winner by cumulative logprob (ties
        break to the lowest branch index) among cleanly finished
        branches — every branch failed means the parent's result stands
        — and stream it NOW as index 0 (per-branch streaming was held
        back; the winner was unknowable until the family drained)."""
        req = fam.req
        happy = [r for r in fam.done
                 if r.outcome in (OUTCOME_EOS, OUTCOME_BUDGET)]
        pool = happy or fam.done
        winner = max(pool, key=lambda r: (r.cum_logprob, -r.index))
        if obs.TRACER.active:
            obs.instant("best_of_selected", cat="serving", args={
                "rid": req.uid, "index": winner.index,
                "cum_logprob": round(winner.cum_logprob, 6),
                "branches": len(fam.done),
            })
        out = dataclasses.replace(winner, index=0)
        for t in winner.tokens:
            self._deliver_token(req, 0, t)
        self._deliver_finish(req, 0, out)

    # -- token-tree sibling decode (ISSUE 20) -----------------------------
    #
    # The family's k branches decode in ONE slot as one verify-shaped
    # row bundle per tick. The device cache freezes at ``base_len``
    # committed rows (the shared ancestor path — prompt, or prompt +
    # shared generated prefix for a mid-generation conversion); every
    # live branch's divergent suffix is REPLAYED into the window
    # [base_len, base_len + k*s) each tick under tree_mask/positions,
    # so suffix rows attend only to their own branch plus the frozen
    # ancestors. Each branch's last row draws its next token under the
    # fork-slot path's exact key chain — token-identical layouts. A
    # retiring branch shrinks the window the same tick; the last two
    # transitions are collapse (k=1: compact the survivor's suffix
    # contiguous via compact_decode_window and hand the slot back to
    # the plain decode path) and close (k=0: free the slot).

    def _admit_tree_family(self, req: Request, slot: int) -> None:
        """Register an n>1 / best-of-n family that will decode as a
        token tree in ``slot`` (reservation already taken tree-shaped
        by ``_paged_reserve``). Branches materialize at the awaits
        pass, the tick the parent's first token lands."""
        branches = self._branches(req)
        fam = _ForkFamily(
            req=req, parent_slot=slot, sibling_slots=[], sib_reserve=0,
            hold=0, best_of=bool(req.best_of and req.best_of > 1),
            branches=branches, tree=True,
            base_len=len(req.prompt), fork_len=1,
        )
        self._families[req.uid] = fam
        self._tree_fams[slot] = fam
        self._uid_next_index[req.uid] = branches
        self._tree_fams_life += 1

    def _tree_family_start(self, fam: _ForkFamily, slot: int,
                           first: int, tick: int, now2: float,
                           results) -> int:
        """Branch the freshly-live parent into its k tree siblings —
        called from the awaits pass BEFORE any EOS check, so even a
        one-token parent yields k independent samples. Siblings' first
        tokens draw from the parent's STASHED prompt-end logits under
        their own branch keys (ONE tiny dispatch + one small fetch per
        family, not per tick) — bit-identical to the fork-slot path's
        ``_sibling_first`` draws. Every branch's first token then runs
        its own EOS/budget check here, branch 0 included (the caller
        skips its generic check). Returns sibling tokens emitted."""
        req = fam.req
        fam.forked = True
        k = fam.branches
        row = self._slot_logits[slot]
        assert row is not None, "tree family lost its logits stash"
        self._slot_logits[slot] = None
        bix = np.arange(1, k, dtype=np.int32)
        tok_d, lp_d = self._tree_first(
            row, jnp.asarray(bix), jnp.int32(self._salt_np[slot]),
            jnp.float32(self._temp_np[slot]),
            jnp.int32(self._topk_np[slot]),
        )
        tok_h = np.asarray(tok_d)
        lp_h = np.asarray(lp_d)
        fam.br_tokens = [self._slot_tokens[slot]] + [
            [int(tok_h[j])] for j in range(k - 1)
        ]
        fam.br_cum_lp = [self._slot_cum_lp[slot]] + [
            float(lp_h[j]) for j in range(k - 1)
        ]
        fam.br_live = [True] * k
        fam.br_index = list(range(k))
        fam.br_ttft = [self._slot_ttft[slot]] * k
        emitted = 0
        dead: List[Tuple[int, str]] = []
        for b in range(k):
            t0 = int(fam.br_tokens[b][0])
            if b > 0:
                self._push_token(req, t0, b)
                emitted += 1
                self.slo.observe_ttft(fam.br_ttft[b])
                if obs.REGISTRY.enabled:
                    _TOKENS.inc()
                    _TTFT.observe(fam.br_ttft[b])
                if obs.TRACER.active:
                    obs.instant("first_token", cat="serving", args={
                        "rid": req.uid, "slot": slot, "tick": tick,
                        "index": b, "tree": True,
                        "ttft_s": round(fam.br_ttft[b], 6),
                    })
            if req.eos_id is not None and t0 == req.eos_id:
                dead.append((b, OUTCOME_EOS))
            elif req.max_new_tokens <= 1:
                dead.append((b, OUTCOME_BUDGET))
        self._forks_life += k - 1
        self._tick_forks += k - 1
        nshare = fam.base_len // self.kv_block
        self._fork_shared_life += (k - 1) * nshare
        self._tick_fork_shared += (k - 1) * nshare
        if obs.REGISTRY.enabled:
            _FORKS.inc(k - 1)
            if nshare:
                _FORK_SHARED.inc((k - 1) * nshare)
        if obs.TRACER.active:
            # One instant per sibling — the fork-slot path's exact
            # trace shape, so family post-mortems read identically
            # whichever layout served them.
            for b in range(1, k):
                obs.instant("fork", cat="serving", args={
                    "rid": req.uid, "tick": tick, "parent_slot": slot,
                    "child_slot": slot, "index": b, "tree": True,
                    "shared_blocks": nshare, "copied_blocks": 0,
                    "at_tokens": 0,
                })
        for b, outcome in dead:
            self._tree_finish_branch(slot, fam, b, outcome, tick, now2,
                                     results)
        self._tree_settle(slot, fam, 0, [], bool(dead), tick)
        return emitted

    def _pack_tree(
        self, fam: _ForkFamily
    ) -> Tuple[PackedSpec, List[int], int]:
        """This tick's sibling bundle: every live branch's divergent
        suffix (its tokens past the frozen ancestor rows), packed
        branch-major. All live suffixes have equal length — each branch
        gains exactly one token per tick. Returns (pack, the live
        branch ids in packed order, the suffix length)."""
        d = fam.fork_len - 1
        order = [b for b in range(fam.branches) if fam.br_live[b]]
        suffixes = [fam.br_tokens[b][d:] for b in order]
        return pack_siblings(suffixes), order, len(suffixes[0])

    def _tree_commit_all(
        self,
        tree_plan: Dict[int, Tuple[PackedSpec, List[int], int]],
        alltok: np.ndarray,
        alllp: np.ndarray,
        now: float,
        tick: int,
        results: List[RequestResult],
        tbt: List[float],
    ) -> int:
        """The host half of a tree-sibling tick: each live branch's next
        token is the draw at its LAST packed row (rows before it
        re-drew the branch's existing suffix tokens — same keys, same
        logits, bit-identical, discarded). EOS/budget checks run per
        branch; retires shrink the family the same tick (trim /
        collapse / close). Returns tokens emitted."""
        emitted_total = 0
        for slot, (pack, order, s) in tree_plan.items():
            fam = self._tree_fams.get(slot)
            if fam is None:
                continue
            req = fam.req
            self._tick_tree_branches += len(order)
            self._tree_branches_life += len(order)
            gap = max(now - self._last_tok_t[slot], 0.0)
            self._last_tok_t[slot] = now
            if gap > self._slot_max_tbt[slot]:
                self._slot_max_tbt[slot] = gap
            self.slo.observe_tbt(gap)
            dead: List[Tuple[int, str]] = []
            for rank, b in enumerate(order):
                r = rank * s + s - 1
                t_new = int(alltok[slot, r])
                fam.br_tokens[b].append(t_new)
                fam.br_cum_lp[b] += float(alllp[slot, r])
                self._push_token(req, t_new, fam.br_index[b])
                emitted_total += 1
                tbt.append(gap if rank == 0 else 0.0)
                if obs.REGISTRY.enabled:
                    _TOKENS.inc()
                    _TBT.observe(gap if rank == 0 else 0.0)
                if req.eos_id is not None and t_new == req.eos_id:
                    dead.append((b, OUTCOME_EOS))
                elif len(fam.br_tokens[b]) >= req.max_new_tokens:
                    dead.append((b, OUTCOME_BUDGET))
            for b, outcome in dead:
                self._tree_finish_branch(slot, fam, b, outcome, tick,
                                         now, results)
            self._tree_settle(slot, fam, s, order, bool(dead), tick)
        return emitted_total

    def _tree_finish_branch(self, slot: int, fam: _ForkFamily, b: int,
                            outcome: str, tick: int, now: float,
                            results) -> None:
        """One tree branch leaves the family: its per-branch result is
        final NOW (tokens, cum_logprob, its own outcome); the slot's
        resources shrink in ``_tree_settle``, not here."""
        fam.br_live[b] = False
        req = fam.req
        admit_tick, visible_at = self._slot_admit[slot]
        res = RequestResult(
            uid=req.uid,
            tokens=list(fam.br_tokens[b]),
            prompt_len=len(req.prompt),
            arrival_tick=req.arrival_tick,
            admit_tick=admit_tick,
            finish_tick=tick,
            queue_wait_s=self._slot_wait[slot],
            completion_s=max(now - visible_at, 0.0),
            outcome=outcome,
            ttft_s=fam.br_ttft[b],
            prefix_hit_tokens=self._slot_prefix_hit[slot],
            index=fam.br_index[b],
            cum_logprob=fam.br_cum_lp[b],
        )
        results.append(res)
        if outcome in (OUTCOME_EOS, OUTCOME_BUDGET):
            self.slo.observe_request(fam.br_ttft[b],
                                     self._slot_max_tbt[slot])
        elif outcome in (OUTCOME_DEADLINE, OUTCOME_SHED, OUTCOME_ERROR):
            self.slo.observe_miss()
        self._tick_branch_retired += 1
        if obs.REGISTRY.enabled:
            _REQUESTS.labels(outcome=outcome).inc()
        if obs.TRACER.active:
            obs.instant("request_retired", cat="serving", args={
                "rid": req.uid, "slot": slot, "tick": tick,
                "outcome": outcome, "index": fam.br_index[b],
                "tree": True,
            })
        self._notify_finish(req, res, fam)
        self._family_branch_done(fam, res)

    def _tree_settle(self, slot: int, fam: _ForkFamily, s: int,
                     order: List[int], retired_any: bool,
                     tick: int) -> None:
        """Normalize the slot after a tree tick (or the family start):
        k live branches keep the tree (trimming the window reservation
        when some retired — the same-tick no-leak contract), one
        survivor collapses the slot back to plain decode, zero closes
        it."""
        k_live = sum(fam.br_live)
        if k_live == 0:
            self._tree_close(slot, fam, tick)
        elif k_live == 1:
            self._tree_collapse(slot, fam, s, order)
        elif retired_any:
            span = max(
                fam.base_len
                + k_live * (fam.req.max_new_tokens - fam.fork_len),
                len(fam.req.prompt) + fam.req.max_new_tokens,
            )
            self._slot_trim(slot, -(-span // self.kv_block))

    def _slot_trim(self, slot: int, need: int) -> None:
        """Shrink ``slot`` to a ``need``-block worst case the SAME tick
        its occupant got smaller: unmap private tail blocks past the
        need (their rows belonged to retired branches; host bookkeeping
        only — any in-flight gather already dispatched against the old
        table) and return the excess reservation to the pool."""
        while self._slot_nblocks[slot] > need:
            j = self._slot_nblocks[slot] - 1
            bid = int(self._host_table[slot, j])
            if bid not in self._slot_private[slot]:
                break  # shared ancestors never sit past the need
            self._pool.unmap_private(bid)
            self._slot_private[slot].discard(bid)
            self._slot_reserve[slot] += 1
            self._host_table[slot, j] = 0
            self._slot_nblocks[slot] -= 1
            self._table_dirty = True
        excess = self._slot_nblocks[slot] + self._slot_reserve[slot] \
            - need
        if excess > 0:
            give = min(excess, self._slot_reserve[slot])
            if give:
                self._pool.unreserve(give)
                self._slot_reserve[slot] -= give
                self._pool.gen += 1

    def _tree_collapse(self, slot: int, fam: _ForkFamily, s: int,
                       order: List[int]) -> None:
        """One branch left: gather its replayed suffix contiguous
        (compact_decode_window — a no-op when it already sits at rank
        0), rebind the slot's mirrors and PRNG key to the survivor's
        stream, park its tip, and hand the slot back to the plain
        decode path. The survivor continues bit-identically: its slot
        key chain equals the in-program fold it decoded under."""
        req = fam.req
        b = fam.br_live.index(True)
        if s > 0:
            rank = order.index(b)
            if rank > 0:
                w = max(self._spec_rows_cap, 1)
                src = np.tile(np.arange(w, dtype=np.int32),
                              (self.slots, 1))
                src[slot, :s] = rank * s + np.arange(s, dtype=np.int32)
                n = np.zeros((self.slots,), np.int32)
                n[slot] = s
                start = np.zeros((self.slots,), np.int32)
                start[slot] = fam.base_len
                self.cache = self._compact(
                    self.cache, jnp.asarray(start), jnp.asarray(src),
                    jnp.asarray(n),
                )
        self._slot_clen[slot] = fam.base_len + s
        self._live_reset[slot] = fam.base_len + s
        self._slot_tokens[slot] = fam.br_tokens[b]
        self._slot_index[slot] = fam.br_index[b]
        self._slot_cum_lp[slot] = fam.br_cum_lp[b]
        self._slot_ttft[slot] = fam.br_ttft[b]
        self._keys = self._seed_key(self._keys, jnp.int32(slot),
                                    jnp.int32(self._salt_np[slot]),
                                    jnp.int32(fam.br_index[b]))
        tip = int(fam.br_tokens[b][-1])
        self.cache, self.tok = self._fork_copy(
            self.cache, self.tok, jnp.int32(0), jnp.int32(0),
            jnp.int32(slot), jnp.int32(tip),
        )
        th = np.array(self._tok_host)
        th[slot] = tip
        self._tok_host = th
        self._tree_fams.pop(slot, None)
        need = -(-(len(req.prompt) + req.max_new_tokens)
                 // self.kv_block)
        self._slot_trim(slot, need)

    def _tree_close(self, slot: int, fam: _ForkFamily,
                    tick: int) -> None:
        """Every branch finished: close the request's span/ledger and
        free the slot — prefix pins, private blocks, CoW refs, unspent
        reservation — the same tick the last branch retired."""
        self._tree_fams.pop(slot, None)
        req = fam.req
        span = self._slot_span[slot]
        if span is not None:
            if obs.TRACER.active:
                span.set(
                    tokens=sum(len(t) for t in fam.br_tokens),
                    branches=fam.branches, tree=True,
                )
            span.__exit__(None, None, None)
            self._slot_span[slot] = None
        if obs.REQLOG.enabled:
            led = obs.REQLOG.finish(
                req.uid, outcome=fam.done[-1].outcome if fam.done
                else OUTCOME_EOS, finish_tick=tick,
                tokens_decoded=sum(len(t) for t in fam.br_tokens),
                now=time.monotonic(),
            )
            if fam.done:
                fam.done[-1].ledger = led
        self._free_slot_resources(slot)
        if not any(rq is not None and rq.uid == req.uid
                   for rq in self._slot_req):
            self._uid_next_index.pop(req.uid, None)

    def _tree_convert_live(self, parent: int, uid: int,
                           tick: int) -> str:
        """Mid-generation fork(uid) as a tree conversion: keep the live
        slot, freeze its committed rows as the shared ancestors, and
        decode both continuations as a 2-branch token tree — zero new
        slots, zero copied blocks (even the partial tail block is
        shared; both branches re-consume the tip as replayed suffix
        rows). Pure host bookkeeping plus the reservation delta."""
        req = self._slot_req[parent]
        toks = self._slot_tokens[parent]
        t = len(toks)
        plen = len(self._prompt_np[parent])
        base_len = plen + t - 1
        span = max(base_len + 2 * (req.max_new_tokens - t),
                   plen + req.max_new_tokens)
        need = -(-span // self.kv_block)
        held = self._slot_nblocks[parent] + self._slot_reserve[parent]
        delta = need - held
        if delta > 0:
            if not self._pool.reserve(delta):
                return "retry"
            self._slot_reserve[parent] += delta
        idx = self._uid_next_index.get(uid, self._branches(req))
        self._uid_next_index[uid] = idx + 1
        fam = _ForkFamily(
            req=req, parent_slot=parent, sibling_slots=[],
            sib_reserve=0, hold=0, best_of=False, branches=2,
            forked=True, tree=True, base_len=base_len, fork_len=t,
            br_tokens=[toks, list(toks)],
            br_cum_lp=[self._slot_cum_lp[parent],
                       self._slot_cum_lp[parent]],
            br_live=[True, True],
            br_index=[self._slot_index[parent], idx],
            br_ttft=[self._slot_ttft[parent], self._slot_ttft[parent]],
        )
        self._families[req.uid] = fam
        self._tree_fams[parent] = fam
        self._tree_fams_life += 1
        # Tree ticks reset the device length to base_len every dispatch;
        # a pending fork/collapse reset is subsumed.
        self._live_reset.pop(parent, None)
        self._slot_clen[parent] = base_len
        nshare = base_len // self.kv_block
        self._forks_life += 1
        self._fork_shared_life += nshare
        self._tick_forks += 1
        self._tick_fork_shared += nshare
        if obs.REGISTRY.enabled:
            _FORKS.inc()
            if nshare:
                _FORK_SHARED.inc(nshare)
        if obs.TRACER.active:
            obs.instant("fork", cat="serving", args={
                "rid": req.uid, "tick": tick, "parent_slot": parent,
                "child_slot": parent, "index": idx, "tree": True,
                "shared_blocks": nshare, "copied_blocks": 0,
                "at_tokens": t,
            })
        if obs.REQLOG.enabled and nshare:
            obs.REQLOG.note(req.uid, fork_shared_blocks=nshare)
        return "done"

    # -- speculation (ISSUE 8) --------------------------------------------

    def _spec_bucket(self, n: int) -> int:
        """Tq bucket for a verify tick: power-of-two, floor 8 (shared with
        the chunk buckets so mixtures reuse programs), capped at the
        cache-window-safe rows cap."""
        b = min(8, self._spec_rows_cap)
        while b < n:
            b *= 2
        return min(b, self._spec_rows_cap)

    def _draft_slot(self, i: int, tree_ok: bool = True) -> PackedSpec:
        """Build slot ``i``'s verify chunk for this tick: ask the drafter
        for up to the clamped budget of candidates (never past the
        request's remaining token budget — the satellite contract: a
        drafter proposing past ``max_new_tokens`` is truncated here, not
        trusted), fall back to the root-path chain where the tree mask
        cannot run (the tree merge under a seq mesh, or a tick whose
        prefill chunks widen Tq past the int32 bitmask — ``tree_ok``),
        and pack with the committed tip as row 0. A ``None`` or empty
        proposal packs to one row — a plain decode tick."""
        req = self._slot_req[i]
        tip = self._slot_tokens[i][-1]
        remaining = req.max_new_tokens - len(self._slot_tokens[i])
        budget = min(self.draft_k, remaining - 1, self._spec_rows_cap - 1)
        prop: Optional[DraftProposal] = None
        if budget >= 1:
            hist = self._hist_buf[i, :self._hist_len[i]]  # view, no copy
            prop = self._drafter.propose(hist, budget)
        if prop is not None and len(prop) > 0:
            prop = prop.truncated(budget)
            if (not self._tree_ok or not tree_ok) and not prop.is_chain:
                prop = prop.chain_prefix()
        else:
            prop = DraftProposal(
                tokens=np.empty((0,), np.int32),
                parents=np.empty((0,), np.int32),
            )
        return pack_proposal(tip, prop)

    def _spec_unmap(self, slot: int) -> None:
        """Roll back the slot's block tail after a partial accept: blocks
        wholly past the committed coverage were only ever written with
        rejected rows — unmap them into the slot's reservation
        (free + re-reserved, so the later re-allocation cannot fail and
        rolled-back KV never leaks pool capacity). Runs AFTER the commit
        compaction dispatched (the device still maps the blocks for that
        gather; nothing allocates until the next tick's admissions)."""
        keep = -(-self._slot_clen[slot] // self.kv_block)
        while self._slot_nblocks[slot] > keep:
            j = self._slot_nblocks[slot] - 1
            bid = int(self._host_table[slot, j])
            if bid not in self._slot_private[slot]:
                # Shared (prefix) blocks never sit past the committed
                # tail; defensive stop if one ever did.
                break
            self._pool.unmap_private(bid)
            self._slot_private[slot].discard(bid)
            self._slot_reserve[slot] += 1
            self._host_table[slot, j] = 0
            self._slot_nblocks[slot] -= 1
            self._table_dirty = True

    def _spec_commit_all(
        self,
        spec_plan: Dict[int, PackedSpec],
        alltok: np.ndarray,
        alllp: np.ndarray,
        width: int,
        now: float,
        tick: int,
        results: List[RequestResult],
        tbt: List[float],
    ) -> int:
        """The host half of a verify tick: walk each slot's fetched
        per-row draws, emit the committed burst (EOS/budget checks in
        stream order — an EOS inside the burst truncates it, same tick),
        update the committed-length ledger (the next step's reset performs
        the device rollback), batch the tree compactions into ONE
        dispatch, and unmap rolled-back paged blocks. Returns the number
        of tokens emitted.

        Greedy slots walk the argmax path; sampled slots walk the
        STOCHASTIC path (Leviathan coupling, arXiv:2211.17192): each
        window row's fetched token was drawn from the target softmax
        under that row's deterministic stream key, so accepting a draft
        token iff the draw equals it emits exactly the target
        distribution — token-identical to non-speculative sampling
        under the same seed."""
        emitted_total = 0
        compact_src: Optional[np.ndarray] = None
        compact_n: Optional[np.ndarray] = None
        compact_start: Optional[np.ndarray] = None
        t_slots = t_prop = t_acc = 0
        t_ver = 0
        for i, pack in spec_plan.items():
            req = self._slot_req[i]
            if self._temp_np[i] > 0.0:
                kept, committed = accept_stochastic_path(pack, alltok[i])
                if obs.REGISTRY.enabled and pack.rows:
                    _SPEC_ACCEPT_SAMPLES.inc(pack.rows)
            else:
                kept, committed = accept_longest_path(pack, alltok[i])
            m = pack.rows - 1
            t_slots += 1
            t_prop += m
            t_acc += len(kept)
            if m:
                t_ver += 1
            # Truncate the burst at the request budget and at EOS — the
            # drafter was already clamped to the budget, but the contract
            # is enforced here, where it matters.
            remaining = req.max_new_tokens - len(self._slot_tokens[i])
            emit_list = committed[:remaining]
            outcome = None
            if req.eos_id is not None:
                for j, t in enumerate(emit_list):
                    if t == req.eos_id:
                        emit_list = emit_list[:j + 1]
                        outcome = OUTCOME_EOS
                        break
            n_emit = len(emit_list)
            if outcome is None and (
                len(self._slot_tokens[i]) + n_emit >= req.max_new_tokens
            ):
                outcome = OUTCOME_BUDGET
            # The burst lands at one instant: the first token carries the
            # whole inter-token gap, the rest arrive for free — the
            # honest latency shape of speculative decode.
            gap = max(now - self._last_tok_t[i], 0.0)
            self._last_tok_t[i] = now
            if gap > self._slot_max_tbt[i]:
                self._slot_max_tbt[i] = gap
            self.slo.observe_tbt(gap)
            hl = self._hist_len[i]
            rows_used = [0] + kept  # window row each committed token used
            for j, t in enumerate(emit_list):
                self._slot_tokens[i].append(int(t))
                self._slot_cum_lp[i] += float(alllp[i][rows_used[j]])
                self._hist_buf[i, hl + j] = int(t)
                self._push_token(req, int(t))
                tbt.append(gap if j == 0 else 0.0)
                if obs.REGISTRY.enabled:
                    _TOKENS.inc()
                    _TBT.observe(gap if j == 0 else 0.0)
            self._hist_len[i] = hl + n_emit
            emitted_total += n_emit
            if obs.REGISTRY.enabled and m:
                _SPEC_PROPOSED.inc(m)
                if kept:
                    _SPEC_ACCEPTED.inc(len(kept))
            if obs.TRACER.active:
                obs.instant("spec_verify", cat="serving", args={
                    "rid": req.uid, "slot": i, "tick": tick,
                    "proposed": m, "accepted": len(kept),
                    "committed": n_emit,
                })
            if obs.REQLOG.enabled and m:
                obs.REQLOG.note(req.uid, spec_proposed=m,
                                spec_accepted=len(kept))
            if outcome is not None:
                self._retire(i, tick, outcome, results)
                continue
            # Committed cache rows: the tip's (row 0) plus every accepted
            # draft row; the bonus token is the new pending tip.
            a = len(kept)
            old_clen = self._slot_clen[i]
            if kept != list(range(1, a + 1)):
                # A non-chain accepted path: its KV rows sit scattered in
                # the window — batch the gather-to-front for ONE compact
                # dispatch after the loop.
                if compact_src is None:
                    compact_src = np.tile(
                        np.arange(width, dtype=np.int32), (self.slots, 1)
                    )
                    compact_n = np.zeros((self.slots,), np.int32)
                    compact_start = np.zeros((self.slots,), np.int32)
                compact_src[i, 1:a + 1] = kept
                compact_n[i] = a + 1
                compact_start[i] = old_clen
            self._slot_clen[i] = old_clen + 1 + a
        if compact_src is not None:
            # ONE batched gather-to-front for every tree commit of the
            # tick (the device table still maps the rolled-back blocks —
            # unmapping below is host bookkeeping that only reaches the
            # device at the next tick's sync, after this gather ran).
            self.cache = self._compact(
                self.cache, jnp.asarray(compact_start),
                jnp.asarray(compact_src), jnp.asarray(compact_n),
            )
        for i in spec_plan:
            if self._slot_state[i] == "live":  # retired slots freed
                self._spec_unmap(i)
        self._spec_proposed += t_prop
        self._spec_accepted += t_acc
        self._spec_verifies += t_ver
        if t_prop:
            self._spec_ticks += 1
        if obs.REGISTRY.enabled and self._spec_proposed:
            _SPEC_ACCEPT_RATIO.set(
                self._spec_accepted / self._spec_proposed
            )
        self._tick_spec = (t_slots, t_prop, t_acc)
        return emitted_total

    def _consume_chunk(self, slot: int, n: int,
                       last: bool) -> Tuple[np.ndarray, bool]:
        """Host-side bookkeeping of one scheduled chunk — the ONE copy the
        fused and staged paths share: slice the prompt rows, advance the
        slot's running position, and on the final chunk move the slot to
        ``await`` (its first sampled token lands in this tick's batched
        fetch). Returns the token rows and whether this chunk STARTS the
        slot's prefill (pos == the admission's start offset — 0 cold, the
        matched length on a hit; the step resets the slot's length to
        that offset before the write)."""
        pos = self._prefill_pos[slot]
        rows = self._prompt_np[slot][pos:pos + n]
        self._prefill_pos[slot] = pos + n
        self._chunk_k[slot] += 1
        if last:
            self._slot_state[slot] = "await"
            self._prefill_fifo.remove(slot)
        if obs.REGISTRY.enabled:
            _PREFILL_CHUNKS.inc()
        if obs.TRACER.active:
            plen = len(self._slot_req[slot].prompt)
            obs.instant("prefill_chunk", cat="serving", args={
                "rid": self._slot_req[slot].uid, "slot": slot,
                # Nominal k/N (a tick's budget can shrink a chunk, so k
                # may run past N; the pos/plen pair is the exact truth).
                "chunk": f"{self._chunk_k[slot]}/"
                         f"{-(-plen // self.prefill_chunk)}",
                "n": int(n), "pos": pos + n, "prompt_len": plen,
            })
        return rows, pos == self._prefill_start[slot]

    def _pack_chunk_group(
        self, plan: List[Tuple[int, int, bool]], tq: int,
        reset: np.ndarray, reset_val: np.ndarray, emit: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Consume this tick's planned chunks into the packed tick's chunk
        group: member ``j`` is the plan's ``j``-th slot (blocks mapped,
        host position advanced — :meth:`_consume_chunk`), the members the
        plan leaves over stay padding (``n = 0``). The per-SLOT operands
        ``reset``/``reset_val``/``emit`` are filled in place. Returns the
        group's ``(C, tq)`` tokens, ``(C,)`` slots and ``(C,)`` counts."""
        C = self._chunk_group
        chunk_tok = np.zeros((C, tq), np.int32)
        chunk_slot = np.zeros((C,), np.int32)
        chunk_n = np.zeros((C,), np.int32)
        for j, (slot, n, last) in enumerate(plan):
            self._ensure_blocks(slot, self._prefill_pos[slot] + n, n)
            rows, first = self._consume_chunk(slot, n, last)
            chunk_tok[j, :n] = rows
            chunk_slot[j] = slot
            chunk_n[j] = n
            reset[slot] = first
            reset_val[slot] = self._prefill_start[slot]
            emit[slot] = last
        return chunk_tok, chunk_slot, chunk_n

    def _run_staged_chunk(self, slot: int, n: int, last: bool) -> None:
        """Quantized admission: advance one slot's staged exact
        prefill by ``n`` tokens; the final chunk quantizes + inserts."""
        plen = len(self._slot_req[slot].prompt)
        rows, first = self._consume_chunk(slot, n, last)
        mat = np.zeros((1, self._chunk_bucket(n)), np.int32)
        mat[0, :n] = rows
        n_vec = jnp.asarray([n], jnp.int32)
        reset = jnp.asarray([first])
        reset_val = jnp.asarray([self._prefill_start[slot]], jnp.int32)
        if last:
            # The quantized insert scatters the whole staged prompt into
            # the slot — its blocks must all be mapped first.
            self._ensure_blocks(slot, plen)
            self._sync_table()
            self._staging, self.cache, self.tok, self._lp, \
                last_row = self._stage_final(
                    self.params, jnp.asarray(mat), n_vec, self._staging,
                    self.cache, self.tok, self._lp, jnp.int32(slot),
                    jnp.int32(plen), reset, reset_val,
                    self._keys[slot], jnp.float32(self._temp_np[slot]),
                    jnp.int32(self._topk_np[slot]),
                    jnp.int32(self._prefill_start[slot]),
                )
            if self._slot_req[slot].uid in self._families:
                self._slot_logits[slot] = last_row[0]
            # The quantized blocks are in the slot: hand them to the tree.
            self._publish_prefix(slot)
        else:
            self._staging = self._stage_chunk(
                self.params, jnp.asarray(mat), n_vec, self._staging,
                reset, reset_val,
            )

    def _free_slot_resources(self, slot: int) -> None:
        """Release everything a slot holds — prefix pins, private paged
        blocks, CoW refs, unspent reservation — and mark it free.
        Shared exit arc of ``_retire`` and ``_tree_close``."""
        self._slot_req[slot] = None
        self._slot_tokens[slot] = []
        self._slot_state[slot] = "free"
        self._prompt_np[slot] = None
        self._slot_logits[slot] = None
        if self._prefix is not None and self._slot_nodes[slot]:
            # The request's pinned prefix path becomes evictable.
            self._prefix.release(self._slot_nodes[slot])
            self._slot_nodes[slot] = []
        # Blocks the tree adopted stay cached (pins just dropped);
        # the slot's remaining private blocks — decode tail, partial
        # prompt block, unpublished spans — go back to the free list,
        # along with any unspent worst-case reservation (early EOS).
        for bid in self._slot_private[slot]:
            self._pool.free_private(bid)
        self._slot_private[slot] = set()
        # CoW-shared fork ancestors (ISSUE 15): this owner's
        # refcount drops on EVERY exit arc; the last branch's
        # release frees the block.
        for bid in self._slot_shared[slot]:
            self._pool.release_shared(bid)
        self._slot_shared[slot] = set()
        self._live_reset.pop(slot, None)
        if self._slot_reserve[slot]:
            self._pool.unreserve(self._slot_reserve[slot])
            self._slot_reserve[slot] = 0
        self._host_table[slot, :] = 0  # stale ids must never be read
        self._slot_nblocks[slot] = 0
        self._table_dirty = True
        if self._win is not None:
            self._win.free_slot(slot)   # both tables are reset
        # The pin releases above can grow EVICTABILITY without
        # touching the free list — clear the admit loop's deferral
        # latch so the queue head retries.
        self._pool.gen += 1

    def _tree_retire_all(self, slot: int, fam: _ForkFamily, tick: int,
                         outcome: str,
                         results: List[RequestResult]) -> None:
        """Cancel/deadline/shed a started tree family: every live
        branch finishes with the slot's outcome, then the slot closes."""
        now = time.monotonic()
        for b in range(fam.branches):
            if fam.br_live[b]:
                self._tree_finish_branch(slot, fam, b, outcome, tick,
                                         now, results)
        self._tree_close(slot, fam, tick)

    def _retire(self, slot: int, tick: int, outcome: str,
                results: List[RequestResult]) -> None:
        """Free a slot on ANY outcome arc. The happy paths (eos/budget)
        and the robustness paths (cancelled/deadline) release the exact
        same resources — prefix pins, private paged blocks, unspent
        reservations — so retiring a request mid-prefill or mid-stream
        is just this, earlier (cancellation is cheap by construction:
        PagedAttention's unmap, arXiv:2309.06180)."""
        tfam = self._tree_fams.get(slot)
        if tfam is not None:
            if tfam.forked:
                # Started tree family: per-branch results, shared close.
                self._tree_retire_all(slot, tfam, tick, outcome, results)
                return
            # Unstarted (still prefilling / awaiting first token): the
            # plain retire below settles it — _cancel_unforked's tree
            # arm synthesizes the sibling results.
            self._tree_fams.pop(slot)
        req = self._slot_req[slot]
        admit_tick, visible_at = self._slot_admit[slot]
        now = time.monotonic()
        result = RequestResult(
            uid=req.uid,
            tokens=list(self._slot_tokens[slot]),
            prompt_len=len(req.prompt),
            arrival_tick=req.arrival_tick,
            admit_tick=admit_tick,
            finish_tick=tick,
            queue_wait_s=self._slot_wait[slot],
            completion_s=max(now - visible_at, 0.0),
            outcome=outcome,
            ttft_s=self._slot_ttft[slot],
            prefix_hit_tokens=self._slot_prefix_hit[slot],
            index=self._slot_index[slot],
            cum_logprob=self._slot_cum_lp[slot],
        )
        results.append(result)
        if outcome in (OUTCOME_EOS, OUTCOME_BUDGET):
            self.slo.observe_request(
                self._slot_ttft[slot], self._slot_max_tbt[slot]
            )
        elif outcome in (OUTCOME_DEADLINE, OUTCOME_SHED, OUTCOME_ERROR):
            # The server failed this request; a client cancellation
            # (the remaining arc) is not the server's SLO miss.
            self.slo.observe_miss()
        if slot in self._prefill_fifo:
            # Cancelled/expired mid-prefill: leave the chunk plan (and,
            # under int8, release the one-prompt-at-a-time staging
            # latch — the staged rows are garbage the next admission's
            # first-chunk reset overwrites).
            self._prefill_fifo.remove(slot)
        span = self._slot_span[slot]
        if span is not None:
            if obs.TRACER.active:
                span.set(
                    outcome=outcome, tokens=len(self._slot_tokens[slot]),
                    ttft_s=round(self._slot_ttft[slot], 6),
                )
                obs.instant("request_retired", cat="serving", args={
                    "rid": req.uid, "slot": slot, "tick": tick,
                    "outcome": outcome,
                })
                if req.trace is not None:
                    # Finish point of the cross-process flow — emitted
                    # while the request span is still open so the arrow
                    # binds to it (bp:"e").
                    obs.flow("f", obs.flow_id(req.trace[0]))
            span.__exit__(None, None, None)
            self._slot_span[slot] = None
        if obs.REQLOG.enabled:
            result.ledger = obs.REQLOG.finish(
                req.uid, outcome=outcome, finish_tick=tick,
                tokens_decoded=len(result.tokens), now=now,
            )
        self._free_slot_resources(slot)
        if obs.REGISTRY.enabled:
            _REQUESTS.labels(outcome=outcome).inc()
        # Fork-family join bookkeeping (ISSUE 15): a parent retiring
        # before its first token takes its unforked siblings with it;
        # the per-branch finish is (maybe) delivered, then the family
        # collects the branch — the LAST one joins (best-of-n selects
        # and streams its winner there).
        fam = self._families.get(req.uid)
        if fam is not None and slot == fam.parent_slot and not fam.forked:
            self._cancel_unforked(fam, result, tick, results)
        self._notify_finish(req, result, fam)
        if fam is not None:
            self._family_branch_done(fam, result)
        if not any(rq is not None and rq.uid == req.uid
                   for rq in self._slot_req):
            self._uid_next_index.pop(req.uid, None)

    def serve(self, requests: Union[Sequence[Request], RequestSource],
              max_ticks: Optional[int] = None) -> ServeReport:
        """Run the tick loop until the request source drains.

        ``requests`` is a pre-built trace (the legacy shape — admitted in
        arrival order, FIFO per arrival tick, every request validated up
        front) or a live :class:`RequestSource` (the ingress shape —
        requests appear as clients submit them, invalid ones finish with
        outcome ``error`` instead of raising, and the loop idles on
        :meth:`RequestSource.wait` between arrivals). Each tick starts
        with the control sweep: mailboxed cancellations apply, expired
        deadlines shed their requests, and a requested drain stops
        admission and sheds the queue. ``max_ticks`` bounds runaway loops
        (raises if work remains).

        The loop runs one program ahead (ISSUE 32): an iteration
        dispatches its tick program first and only then lands the TAIL
        (fetch, emit, account, flight record: ``land``) of the program
        before it, so the host's part of a tick and the fetch's round
        trip lie under the device's work. Ticks whose plan needs token
        values on the host land the pending tail first and run in the
        old order (:meth:`_plan_tick` says which, from engine state
        alone); the tail is also landed before the loop idles, breaks
        or raises."""
        live = isinstance(requests, RequestSource)
        if live:
            source: RequestSource = requests
        else:
            for r in requests:
                self._validate(r)
            source = StaticRequestSource(requests)
            with self._ctl_lock:
                # A previous run's stale mailbox must not cancel or
                # drain this fresh synthetic trace (uids recycle). Live
                # sources deliberately SKIP this reset: a drain or
                # cancel issued between spawning the engine thread and
                # the loop starting must be honored, not wiped.
                self._cancel_uids.clear()
                self._draining = False
        pending: deque = deque()  # visible, validated, unadmitted
        cancel_carry: Dict[int, int] = {}  # unmatched cancels, sweep TTL
        # A live server runs indefinitely: bound its retention (the
        # report then covers the most recent window) — a finite trace
        # keeps everything, as before.
        results: Any = deque(maxlen=4096) if live else []
        visible_wall: Dict[int, float] = {}
        tbt: Any = deque(maxlen=1 << 16) if live else []
        tick = 0
        # A program went out since the loop last idled: what a tick that
        # finds no tail pending gives as its ``sync_reason`` ("first"
        # before any, "drain" once the tail before it landed early).
        primed = False
        self._tail = None  # a run that raised may have left one
        decode_ticks = 0
        occupancy = 0
        tokens = 0
        prefix0 = self._prefix.stats() if self._prefix is not None else None
        hit_bytes0 = self._hit_bytes_moved
        spec0 = (self._spec_proposed, self._spec_accepted,
                 self._spec_ticks, self._spec_verifies)
        fork0 = (self._forks_life, self._fork_shared_life)
        tree0 = (self._tree_fams_life, self._tree_branches_life)
        self._peak_blocks_used = self._pool.used
        self._defer_gen = -1  # stale latch must not defer a fresh run
        host0 = (self._host_pool.stats()
                 if self._host_pool is not None else None)
        t0 = time.monotonic()
        # A program a tick of this loop builds reads the tick through
        # ``_serve_tick`` while it is traced (the start-up record's
        # ``startup:program`` span, obs/flight.py).
        self._serve_tick = lambda: tick
        self._built.clear()
        # The tick from inside (obs/flight.py): one stamp per phase
        # boundary, off unless the flight recorder or the span tracer is
        # on. Marks sit BETWEEN the mirror[...] regions below.
        phases = self._phases = TickPhases()
        tables: List[Dict[str, Any]] = []
        if FLIGHT.enabled or obs.TRACER.active:
            # Before the first tick: describing a program reads its text.
            tables = self.program_tables()
            if FLIGHT.enabled:
                FLIGHT.describe_programs(tables)

        def admit() -> None:
            """Admit: oldest visible request per free slot. Admission is
            pure bookkeeping (the chunks run inside the tick); the staged
            (quantized) variant holds one prompt in flight at a time, so
            admission waits for the stage. Run
            again in an iteration that had to land a pending tail
            before planning: the retirements freed slots."""
            phases.mark("admit")
            free = self._free_slots()
            while free and pending:
                if self._staged_prefill and self._prefill_fifo:
                    break
                # An n>1 / best-of-n family admits ATOMICALLY: the
                # parent's slot plus one fpend slot per sibling
                # (FIFO — the family waits rather than skip-ahead),
                # so two half-admitted families can never deadlock
                # each other's slots.
                branches = self._branches(pending[0])
                # A tree-sibling family (ISSUE 20) needs ONE slot
                # however many branches it decodes.
                tree_adm = (branches > 1
                            and self._tree_sibling_ok(pending[0]))
                if (1 if tree_adm else branches) > len(free):
                    break
                # Worst-case block reservation (minus what a
                # prefix hit shares). Failure DEFERS: the
                # request stays queued — FIFO, no skip-ahead —
                # until retires/evictions free blocks. This is
                # what lets --slots exceed what the pool holds at
                # full length instead of failing on a shape. The
                # generation latch skips the O(prompt) re-match
                # + O(tree) evictability recount on ticks where
                # availability cannot have grown since the last
                # failed attempt.
                if self._defer_gen == self._pool.gen:
                    break
                resv = self._paged_reserve(pending[0])
                if resv is None:
                    self._defer_gen = self._pool.gen
                    break
                req = pending.popleft()
                slot = free.pop(0)
                vis = visible_wall.pop(req.uid, now)
                if branches > 1:
                    # The family exists before its prompt's final
                    # chunk runs: that tick stashes the prompt-end
                    # logits the siblings sample from.
                    if tree_adm:
                        self._admit_tree_family(req, slot)
                    else:
                        self._admit_family(req, slot, free, resv)
                self._admit(req, slot, tick, vis, resv)

        # A landing in progress: the error arc must not land the other
        # pending program over a half-emitted one.
        landing = False
        # What the iteration's landings fetched, for its span's tags.
        span_sync = False
        span_tokens = 0

        def land(p: _Tail) -> float:
            """The tail of program ``p``: fetch, emit, account, flight
            record. Run at the end of ``p``'s own iteration by a
            synchronous tick; one iteration later, after the next
            program's dispatch, when the loop looks ahead; and before
            the loop idles, breaks or raises. A slot whose request is no
            longer the one ``p`` was dispatched for (cancelled, expired,
            or retired on the EOS of the program before) is skipped: its
            row was computed and is thrown away. That row's KV write
            landed at the first unwritten position of a block that was
            the slot's own when ``p``'s table was uploaded (the radix
            tree only ever holds a prompt's FULL blocks,
            ``_publish_prefix``), and whatever reuses the block is
            dispatched after ``p``, so it overwrites before it reads.
            Returns the moment the fetch came back: from then the
            program dispatched behind ``p`` has the device."""
            nonlocal tokens, decode_ticks, occupancy, landing
            nonlocal span_sync, span_tokens
            landing = True
            host_sync = bool(p.awaits or p.live)
            tokens_this_tick = 0
            expert_rows = None
            alltok_host = None
            alllp_host = None
            t_done = None
            if host_sync:
                # THE per-tick host sync: every new token of this
                # program — decode samples, fused final-chunk first
                # tokens, staged insert first tokens — in one batched
                # fetch. Only programs that produced a token pay it: a
                # fused tick of nothing but mid-prompt chunks skips the
                # fetch (like the staged path), letting consecutive
                # chunks pipeline in the dispatch queue. A verify tick
                # fetches its fused (S, 1+Tq) output instead: the token
                # vector AND every row argmax in the same sync. When the
                # loop looks ahead the NEXT program is already queued
                # behind this one, so the device works through the
                # round trip.
                phases.mark("fetch")
                if p.all_tok is not None:
                    # lint: allow[host-sync] THE one per-tick fetch (verify ticks: token/logprob vectors + every row draw, one fused array)
                    fused_host = np.asarray(p.all_tok)
                    self._tok_host = fused_host[:, 0, 0]
                    self._lp_host = np.ascontiguousarray(
                        fused_host[:, 0, 1]
                    ).view(np.float32)
                    alltok_host = fused_host[:, 1:, 0]
                    alllp_host = np.ascontiguousarray(
                        fused_host[:, 1:, 1]
                    ).view(np.float32)
                elif p.fused is not None:
                    # lint: allow[host-sync] THE one per-tick fetch (token vector + bitcast logprobs, one fused array)
                    fh = np.asarray(p.fused)
                    self._tok_host = fh[:self.slots, 0]
                    self._lp_host = np.ascontiguousarray(
                        fh[:self.slots, 1]
                    ).view(np.float32)
                    if (self._expert_rows_shape is not None
                            or self._conv_layers or self._ssm_layers
                            or self._eva_layers) and (
                            FLIGHT.enabled or obs.REGISTRY.enabled):
                        expert_rows = self._account_step_counters(
                            fh[self.slots:], p.tail_rows)
                else:
                    # Nothing stepped (a staged final chunk parked its
                    # first token while no slot was live): fetch the
                    # carried vectors directly.
                    # lint: allow[host-sync] THE one per-tick fetch (the batched token vector)
                    self._tok_host = np.asarray(self.tok)
                    # lint: allow[host-sync] rides the same sync point (the parked first-token logprobs)
                    self._lp_host = np.asarray(self._lp)
                phases.mark("emit")
                now2 = t_done = time.monotonic()
                if p.live:
                    decode_ticks += 1
                    occupancy += len(p.live)
                for i in p.awaits:
                    req = self._slot_req[i]
                    if req is not p.reqs[i]:
                        continue  # retired since: the row is thrown away
                    first = int(self._tok_host[i])
                    self._slot_tokens[i] = [first]
                    self._slot_cum_lp[i] = float(self._lp_host[i])
                    self._push_token(req, first, self._slot_index[i])
                    self._slot_state[i] = "live"
                    # Committed cache rows = the prompt; the first token
                    # is the pending tip (spec mode's rollback ledger
                    # starts here).
                    self._slot_clen[i] = len(req.prompt)
                    if self._speculate:
                        hl = self._hist_len[i]
                        self._hist_buf[i, hl] = first
                        self._hist_len[i] = hl + 1
                    _, vis = self._slot_admit[i]
                    self._slot_ttft[i] = max(now2 - vis, 0.0)
                    self._last_tok_t[i] = now2
                    tokens += 1  # the prefill-sampled first token
                    tokens_this_tick += 1
                    self.slo.observe_ttft(self._slot_ttft[i])
                    if obs.REGISTRY.enabled:
                        _TOKENS.inc()  # the prefill's first token
                        _TTFT.observe(self._slot_ttft[i])
                    if obs.TRACER.active:
                        obs.instant(
                            "first_token", cat="serving", args={
                                "rid": req.uid, "slot": i,
                                "tick": p.tick,
                                "ttft_s": round(self._slot_ttft[i], 6),
                            })
                    if obs.REQLOG.enabled:
                        obs.REQLOG.first_token(req.uid, now=now2)
                    # Family forks happen HERE — before the parent's
                    # EOS/budget check, so even a one-token parent
                    # yields n independent samples (each sibling
                    # re-consumes the last prompt token and draws its
                    # own first token under its own key).
                    fam = self._families.get(req.uid)
                    if fam is not None and not fam.forked \
                            and i == fam.parent_slot:
                        if fam.tree:
                            # Tree-sibling start (ISSUE 20): every
                            # branch's first token — branch 0's
                            # EOS/budget included — is handled inside,
                            # so the generic checks below must not run.
                            n_new = self._tree_family_start(
                                fam, i, first, p.tick, now2, results,
                            )
                            tokens += n_new
                            tokens_this_tick += n_new
                            continue
                        n_new = self._fork_family(
                            fam, i, p.tick, now2, results
                        )
                        tokens += n_new
                        tokens_this_tick += n_new
                    if req.eos_id is not None and first == req.eos_id:
                        self._retire(i, p.tick, OUTCOME_EOS, results)
                    elif req.max_new_tokens <= 1:
                        self._retire(i, p.tick, OUTCOME_BUDGET, results)
                if self._speculate:
                    # Spec mode: live-slot tokens come from the verify
                    # walk over the fetched row draws, 1..draft_k+1 of
                    # them per slot per tick.
                    if p.spec_plan:
                        n_new = self._spec_commit_all(
                            p.spec_plan, alltok_host, alllp_host,
                            p.spec_width, now2, p.tick, results, tbt,
                        )
                        tokens += n_new
                        tokens_this_tick += n_new
                else:
                    if p.tree_plan:
                        # Tree mode: each live branch's token is its
                        # last packed row's draw; retires shrink the
                        # family the same tick.
                        n_new = self._tree_commit_all(
                            p.tree_plan, alltok_host, alllp_host,
                            now2, p.tick, results, tbt,
                        )
                        tokens += n_new
                        tokens_this_tick += n_new
                    for i in p.live:
                        if p.tree_plan and i in p.tree_plan:
                            continue
                        req = self._slot_req[i]
                        if req is not p.reqs[i]:
                            continue  # retired since: thrown away
                        tok_i = int(self._tok_host[i])
                        # Every live slot enters this loop with a first
                        # token already emitted (the awaits pass of this
                        # tail or the one before, or _fork_family for
                        # siblings) — this is always an inter-token gap.
                        self._slot_tokens[i].append(tok_i)
                        self._slot_cum_lp[i] += float(self._lp_host[i])
                        self._push_token(req, tok_i, self._slot_index[i])
                        tokens += 1
                        tokens_this_tick += 1
                        gap = max(now2 - self._last_tok_t[i], 0.0)
                        tbt.append(gap)
                        self._last_tok_t[i] = now2
                        if gap > self._slot_max_tbt[i]:
                            self._slot_max_tbt[i] = gap
                        self.slo.observe_tbt(gap)
                        if obs.REGISTRY.enabled:
                            _TOKENS.inc()
                            _TBT.observe(gap)
                        if (req.fork_at is not None
                                and self._slot_index[i] == 0
                                and len(self._slot_tokens[i])
                                == req.fork_at):
                            # Replayable mid-generation branch (trace
                            # knob): the request forks itself through
                            # the same mailbox an API caller would use.
                            self.fork(req.uid)
                        if req.eos_id is not None and tok_i == req.eos_id:
                            self._retire(i, p.tick, OUTCOME_EOS, results)
                        elif (len(self._slot_tokens[i])
                                >= req.max_new_tokens):
                            self._retire(i, p.tick, OUTCOME_BUDGET,
                                         results)
            if t_done is None:
                t_done = time.monotonic()
            span_sync = span_sync or host_sync
            span_tokens += tokens_this_tick
            if obs.TRACER.active:
                tick_span.set(host_sync=span_sync, tokens=span_tokens)

            phases.mark("account")
            if self._pool.used > self._peak_blocks_used:
                self._peak_blocks_used = self._pool.used
            self._pool.publish_gauges()  # registry-guarded inside
            if self._win is not None:
                self._win.publish_gauges(self._window_cached())
            if self._host_pool is not None:
                if self._tail is None:
                    # The staged D2H flush point: demotions this tick's
                    # evictions enqueued complete as ONE batched gather,
                    # after the tick's dispatches. Never behind a program
                    # whose tail is still pending: the loop lands that
                    # one first when something is staged.
                    self._flush_demotions()
                self._host_pool.publish_gauge()  # registry-guarded

            # The flight recorder's per-tick record (the black box a
            # post-mortem replays); record dict built only when armed.
            # It describes ONE program, ``p``: what it ran as and held
            # comes from the tail, the slots' states and the pool are the
            # engine's as the record closes, ``phases``/``t_end`` are the
            # stamps of the iteration that closes it.
            if FLIGHT.enabled:
                if self._untabled:
                    # A tick built a new program (its compile was that
                    # tick's cost): describe it for the dumps.
                    tables[:] = self.program_tables()
                    FLIGHT.describe_programs(tables)
                rec = {
                    "tick": p.tick,
                    # The instant the device could start this program:
                    # the tick's top, or the landing of the program it
                    # was dispatched behind. A tick lasts to the next
                    # record's stamp (benchmark/ticks.py).
                    "t_s": round(p.t_s - t0, 6),
                    # What the tick program ran as: its kind, the Tq
                    # bucket (0: nothing dispatched; a staged tick's is
                    # its decode program's), the members of its chunk
                    # group (0: a padded program), the rows it computed
                    # (a packed tick: the chunk group's and one a slot)
                    # and those that carried a token.
                    "kind": p.kind,
                    "tq": p.tq,
                    "chunk_group": p.group,
                    "rows_computed": (
                        p.group * p.tq + self.slots
                        if p.group else self.slots * p.tq),
                    "rows_useful": p.rows_useful,
                    # Grid steps of the paged decode kernels, a layer:
                    # the entries of their work lists (no step past a
                    # slot's length) and of the whole slots x steps
                    # rectangles (``_count_kv_steps``).
                    "kv_steps_run": p.kv_steps[0],
                    "kv_steps_grid": p.kv_steps[1],
                    # Token rows x layers written into the pool, by the
                    # write they took (``_count_pool_rows``).
                    "pool_rows_row": p.pool_rows[0],
                    "pool_rows_block": p.pool_rows[1],
                    # Dispatched before the tail of the program before
                    # it landed (ISSUE 32), or why not.
                    "ahead": p.ahead,
                    "occupancy": len(p.live),
                    "states": list(self._slot_state),
                    "lengths": [self._prefill_pos[i]
                                if self._slot_state[i] == "prefill"
                                else len(self._slot_tokens[i])
                                for i in range(self.slots)],
                    "chunk_plan": [[s, int(n), bool(last)]
                                   for s, n, last in p.plan],
                    "chunk_tokens": p.chunk_tokens,
                    "tokens_emitted": tokens_this_tick,
                    "host_sync": host_sync,
                    "queue_depth": p.queue_depth,
                    "pending": p.pending,
                    "draining": p.draining,
                }
                if self._ssm_layers:
                    # Chunk rows x state-space layers, by the scan they
                    # took (``_count_scan_rows``).
                    rec["scan_rows_kernel"], rec["scan_rows_xla"] = \
                        p.scan_rows
                if self._shared_kv_calls:
                    # Rows the layers below the seam and those above it
                    # computed (a packed program cuts to one row a slot),
                    # and the calls that read the shared rows x live slots.
                    rec["rows_self"] = rec["rows_computed"]
                    rec["rows_cross"] = self._rows_cross(p.tq, p.group)
                    rec["shared_kv_calls"] = \
                        self._shared_kv_calls * len(p.live)
                    if obs.REGISTRY.enabled:
                        _ROWS_PAST_EXIT.labels(stage="self").inc(
                            rec["rows_self"])
                        _ROWS_PAST_EXIT.labels(stage="cross").inc(
                            rec["rows_cross"])
                if not p.ahead:
                    rec["sync_reason"] = p.why
                rec.update(p.counts if p.counts is not None
                           else self._tick_counts())
                # Block occupancy + internal fragmentation (the
                # fraction of mapped block capacity no written
                # token occupies) — the paged black-box truths.
                mapped = sum(self._slot_nblocks)
                written = 0
                for i in range(self.slots):
                    st = self._slot_state[i]
                    if st == "prefill":
                        written += self._prefill_pos[i] // self._row_span
                    elif st in ("await", "live"):
                        written += (
                            len(self._slot_req[i].prompt)
                            + max(len(self._slot_tokens[i]) - 1, 0)
                        ) // self._row_span
                rec["kv_blocks_used"] = self._pool.used
                rec["kv_blocks_free"] = self._pool.free_count
                rec["kv_frag"] = round(
                    1.0 - written / (mapped * self.kv_block), 4
                ) if mapped else 0.0
                if self._host_pool is not None:
                    rec["host_blocks_used"] = self._host_pool.used
                if expert_rows is not None:
                    rec.update(expert_rows)
                    if self._conv_layers:
                        # Rows that carried a token x conv layers: what
                        # the conv mixers computed.
                        rec["conv_rows"] = p.rows_useful * self._conv_layers
                if self._built:
                    # Ticks whose dispatch built a program, their records
                    # not yet written (:meth:`_program_built`).
                    built = self._built.pop(p.tick, None)
                    if built is not None:
                        rec["built"] = built
                # finish() stamps t_end as the record is built
                # and puts the iteration's phases into it.
                phases.finish(rec)
                FLIGHT.record(rec)
            else:
                phases.finish(None)  # the span trace alone
            landing = False
            return t_done

        # The start-up record's span of this call, from ``t0``: open until
        # the report is built.
        run_span = STARTUP.begin("serve", t0)
        try:
            while True:
                if max_ticks is not None and tick >= max_ticks:
                    raise RuntimeError(
                        f"serve() exceeded max_ticks={max_ticks} with "
                        f"{len(pending)} pending request(s)"
                    )
                # Created at the tick's top so that every phase nests in
                # it; an iteration that executes no tick drops it unsaid.
                tick_span = obs.span("serving:tick", cat="serving")
                span_sync, span_tokens = False, 0
                now = time.monotonic()
                phases.begin(now)  # opens 'ingest'
                self._tick_prefix_hits = 0
                self._tick_prefix_reused = 0
                self._tick_restored = 0
                self._tick_spec = (0, 0, 0)
                self._tick_cancelled = 0
                self._tick_deadline = 0
                self._tick_shed = 0
                self._tick_forks = 0
                self._tick_fork_shared = 0
                self._tick_wfreed = 0
                self._tick_kv_kinds = {}
                self._tick_eva = {}
                self._tick_tree_branches = 0
                self._tick_branch_retired = 0

                # Ingest newly visible requests. A live source's invalid
                # request must not kill the loop serving everyone else —
                # it finishes unserved with outcome 'error' (static
                # traces were validated up front and still raise).
                # lint: mirror[ingest] begin
                for r in source.poll(tick):
                    vis = r.visible_at if r.visible_at is not None else now
                    try:
                        self._validate(r)
                    except ValueError as e:
                        log.warning("rejecting request %s: %s", r.uid, e)
                        self._finish_unadmitted(
                            r, tick, OUTCOME_ERROR, results, vis, now
                        )
                        continue
                    pending.append(r)
                    visible_wall[r.uid] = vis
                    if obs.TRACER.active:
                        obs.instant("request_queued", cat="serving",
                                    args={"rid": r.uid, "tick": tick})
                # lint: mirror[ingest] end

                # Control sweep (ISSUE 10): mailboxed cancellations,
                # expired deadlines, drain — applied at tick start so
                # every mutation stays on the loop thread. Order within
                # the sweep: cancellation beats deadline beats drain-shed
                # (a disconnected client's request is 'cancelled' even if
                # its deadline also just expired); EOS/budget from the
                # PREVIOUS tick already retired, so a request finishing
                # and expiring on the same tick keeps its happy outcome.
                phases.mark("sweep")
                cancels, draining = self._take_control()
                cancels |= set(cancel_carry)
                if cancels:
                    # lint: mirror[cancel-queued] begin
                    matched = set()
                    for r in [r for r in pending if r.uid in cancels]:
                        pending.remove(r)
                        matched.add(r.uid)
                        self._tick_cancelled += 1
                        self._finish_unadmitted(
                            r, tick, OUTCOME_CANCELLED, results,
                            visible_wall.pop(r.uid, now), now,
                        )
                    # lint: mirror[cancel-queued] end
                    for i, rq in enumerate(self._slot_req):
                        if rq is not None and rq.uid in cancels:
                            matched.add(rq.uid)
                            self._tick_cancelled += 1
                            self._retire(i, tick, OUTCOME_CANCELLED,
                                         results)
                    # A cancel can race its own request's submission: the
                    # handler's submit may land AFTER this tick's poll
                    # while the cancel lands BEFORE this sweep. Carry
                    # unmatched uids for a couple of sweeps so the
                    # request is caught the moment it is ingested;
                    # genuinely unknown/finished uids age out as no-ops.
                    # lint: mirror[cancel-carry] begin
                    for uid in cancels - matched:
                        if uid not in cancel_carry:
                            cancel_carry[uid] = 2
                        else:
                            cancel_carry[uid] -= 1
                            if cancel_carry[uid] <= 0:
                                del cancel_carry[uid]
                    for uid in matched:
                        cancel_carry.pop(uid, None)
                    # lint: mirror[cancel-carry] end
                # Expired in queue: reject unserved — admitting work
                # that can no longer meet its deadline only steals
                # tick budget from requests that still can.
                # lint: mirror[deadline-queued] begin
                for r in [r for r in pending
                          if r.deadline_s is not None
                          and now >= r.deadline_s]:
                    pending.remove(r)
                    self._tick_deadline += 1
                    self._finish_unadmitted(
                        r, tick, OUTCOME_DEADLINE, results,
                        visible_wall.pop(r.uid, now), now,
                    )
                # lint: mirror[deadline-queued] end
                for i, rq in enumerate(self._slot_req):
                    if (rq is not None and rq.deadline_s is not None
                            and now >= rq.deadline_s):
                        # Expired in flight: retire mid-stream; the
                        # partial tokens already streamed stand.
                        self._tick_deadline += 1
                        self._retire(i, tick, OUTCOME_DEADLINE, results)
                if draining:
                    # Graceful drain: close the source, shed everything
                    # still queued, keep stepping the in-flight slots to
                    # completion.
                    # lint: mirror[drain-shed] begin
                    source.close()
                    while pending:
                        r = pending.popleft()
                        self._tick_shed += 1
                        self._finish_unadmitted(
                            r, tick, OUTCOME_SHED, results,
                            visible_wall.pop(r.uid, now), now,
                        )
                    # lint: mirror[drain-shed] end

                # Copy-on-write fork arc (ISSUE 15): mailboxed
                # fork(uid)s branch live requests onto free slots
                # (deferred ones retry from the carry for a few sweeps).
                # lint: mirror[fork] begin
                forks = self._take_forks()
                if forks or self._fork_carry:
                    self._apply_forks(forks, tick, pending)
                # lint: mirror[fork] end

                admit()
                # What the next program holds, and whether it can go out
                # before the pending tail lands (ISSUE 32). A tick that
                # cannot look ahead (its plan needs token values on the
                # host, or there is nothing to dispatch: every row in
                # flight was its request's last) first lands the pending
                # tail in a span of its own, then runs as a synchronous
                # tick: it admits into the slots the landing freed, plans
                # again from what the host now knows, and idles below if
                # that is nothing.
                plan, live_idx, why = self._plan_tick()
                if self._tail is not None and why is not None:
                    prev, self._tail = self._tail, None
                    if obs.TRACER.active:
                        tick_span.set(
                            tick=prev.tick, drain=True,
                            occupancy=len(prev.live), prefilling=0,
                            chunk_tokens=prev.chunk_tokens,
                            queue_depth=prev.queue_depth,
                        )
                    with tick_span:
                        land(prev)
                    tick_span = obs.span("serving:tick", cat="serving")
                    span_sync, span_tokens = False, 0
                    phases.begin(time.monotonic(), "admit")
                    admit()
                    plan, live_idx, why = self._plan_tick()
                queue_depth = len(pending)  # visible but still unadmitted

                if not pending and all(st == "free"
                                       for st in self._slot_state):
                    # Nothing to do this tick. Drained (source exhausted
                    # or draining): done. Synthetic trace: fast-forward
                    # to the next arrival instead of spinning empty
                    # decode ticks. Live feeder: report idle (the
                    # /healthz contract — an idle server is not a
                    # stalled one) and block briefly for submissions
                    # (wakes early on submit/close).
                    phases.abandon()
                    primed = False
                    if FLIGHT.enabled:
                        rec = None
                        # lint: mirror[sweep-only] begin
                        if (self._tick_cancelled or self._tick_deadline
                                or self._tick_shed):
                            # The sweep retired work and left the tick
                            # idle; without this record the counters are
                            # zeroed at the next tick top and the storm
                            # vanishes from the black box.
                            rec = {
                                "tick": tick,
                                "sweep_only": True,
                                "occupancy": 0,
                                "queue_depth": queue_depth,
                                "pending": len(pending),
                                "cancelled": self._tick_cancelled,
                                "deadline_expired": self._tick_deadline,
                                "shed": self._tick_shed,
                                "draining": draining,
                            }
                        # lint: mirror[sweep-only] end
                        if rec is not None:
                            FLIGHT.record(rec)
                    # lint: mirror[idle] begin
                    if source.exhausted or draining:
                        break
                    nxt = source.next_arrival()
                    if nxt is not None:
                        tick = max(tick + 1, nxt)
                    else:
                        if FLIGHT.enabled:
                            FLIGHT.mark_idle()
                        source.wait(0.05)
                    continue
                    # lint: mirror[idle] end

                # The tick's prefill chunks and its decode rows were
                # settled by :meth:`_plan_tick`, above the idle check:
                # ``live_idx`` is the slots that get a decode row, a token
                # in flight counted.
                phases.mark("plan")
                chunk_tokens = sum(n for _, n, _ in plan)
                # The staged path rebinds ``plan`` to []; keep the tick's
                # real chunk plan reachable for the flight record (a
                # reference, not a copy — free when the recorder is off).
                plan_rec = plan
                # Dispatched before the tail of the program before it
                # lands: the look-ahead, counted where it engages.
                ahead = self._tail is not None
                if obs.REGISTRY.enabled:
                    if ahead:
                        _TICKS_AHEAD.inc()
                    _SLOTS_OCCUPIED.set(len(live_idx))
                    _TREE_BRANCHES.set(sum(
                        sum(f.br_live) for f in self._tree_fams.values()
                        if f.forked
                    ))

                # The per-tick span: occupancy, chunk-budget spent, and
                # queue depth tagged on the one program the tick
                # dispatches; ``host_sync``/``tokens`` are what the
                # iteration fetched and emitted (the program before it
                # when the loop looks ahead), set before close. It covers
                # the iteration's every phase.
                if obs.TRACER.active:
                    tick_span.set(
                        tick=tick, occupancy=len(live_idx),
                        prefilling=len(self._prefill_fifo),
                        chunk_tokens=chunk_tokens,
                        queue_depth=queue_depth, ahead=ahead,
                        host_sync=False, tokens=0,
                    )
                with tick_span:
                    ran_staged = False
                    # What the tick program ran as, for the flight record
                    # and the profiler's tick:dispatch annotation ("none"
                    # until something goes out; no tick the loop is known
                    # to run keeps it).
                    tick_kind = "none"
                    tick_tq = 0
                    tick_group = 0  # members of a packed tick's chunk group
                    n_vec = None
                    kv_rows = kv_chunk = None   # _count_kv_steps' operands
                    if self._staged_prefill and plan:
                        # The stage programs pack and launch per chunk.
                        phases.mark("pack")
                        tick_kind = "staged"
                        for slot, n, last in plan:
                            self._run_staged_chunk(slot, n, last)
                        plan = []
                        ran_staged = True

                    stepped = False
                    spec_plan: Dict[int, PackedSpec] = {}
                    tree_plan: Dict[
                        int, Tuple[PackedSpec, List[int], int]
                    ] = {}
                    all_tok_dev = None
                    fused_dev = None
                    spec_width = 0
                    if self._tree_fams:
                        # Token-tree sibling decode (ISSUE 20): every
                        # started family's live suffixes pack into one
                        # verify-shaped bundle for its ONE slot. Packing
                        # is pure host work, same as drafting.
                        for i, tfam in self._tree_fams.items():
                            if tfam.forked \
                                    and self._slot_state[i] == "live":
                                tree_plan[i] = self._pack_tree(tfam)
                    if self._speculate and live_idx:
                        # Draft-and-verify (ISSUE 8): every live slot's
                        # tick becomes a verify chunk — tip token at row
                        # 0, up to draft_k candidates behind it (m = 0 is
                        # a plain decode row). Drafting is pure host work.
                        # A tick whose prefill chunks widen Tq past 32
                        # cannot run the int32 tree bitmasks — trees fall
                        # back to their root-path chains for that tick.
                        chunk_tq = (
                            self._chunk_bucket(max(n for _, n, _ in plan))
                            if plan else 1
                        )
                        for i in live_idx:
                            spec_plan[i] = self._draft_slot(
                                i, tree_ok=chunk_tq <= 32
                            )
                    if (self._speculate and (plan or spec_plan)) \
                            or tree_plan:
                        # THE verify tick: decode-verify rows (draft
                        # windows under speculation, sibling bundles
                        # under tree decode) and prefill chunks share
                        # one compiled program, exactly like the mixed
                        # tick — per-row draws ride back as a fused
                        # output for the accept walk / branch tips.
                        rows_all = [p.rows for p in spec_plan.values()]
                        rows_all += [pk.rows
                                     for pk, _, _ in tree_plan.values()]
                        rows_max = max(rows_all or [1])
                        # Draft-less ticks (nothing proposed anywhere)
                        # run the Tq=1 shape — low-acceptance traffic
                        # must not pay the padded verify bucket for
                        # nothing.
                        tq = (
                            self._spec_bucket(rows_max) if rows_max > 1
                            else 1
                        )
                        if plan:
                            tq = max(tq, self._chunk_bucket(
                                max(n for _, n, _ in plan)
                            ))
                        spec_width = tq
                        phases.mark("pack")
                        tick_kind, tick_tq = "verify", tq
                        mat = np.zeros((self.slots, tq), np.int32)
                        n_vec = np.zeros((self.slots,), np.int32)
                        reset = np.zeros((self.slots,), bool)
                        reset_val = np.zeros((self.slots,), np.int32)
                        emit = np.zeros((self.slots,), bool)
                        # Parked first tokens (a staged final chunk's,
                        # just above) exist only in the device token
                        # vector — their row 0 must come from there,
                        # everyone else's from the host matrix. Computed
                        # BEFORE chunk consumption flips final-chunk
                        # slots to await.
                        use_dev0 = np.asarray(
                            [st == "await" for st in self._slot_state]
                        )
                        sidx = np.asarray(
                            [len(t) for t in self._slot_tokens], np.int32
                        )
                        # Per-ROW key-chain operands (ISSUE 20): the
                        # defaults put every row on the slot's own spec
                        # chain — branch < 0 folds fold_in(slot_key,
                        # stream index); sibling rows overwrite both
                        # with the fork-slot chain's (branch, index).
                        branch_m = np.full((self.slots, tq), -1,
                                           np.int32)
                        ridx_m = sidx[:, None] + np.tile(
                            np.arange(tq, dtype=np.int32),
                            (self.slots, 1),
                        )
                        need_tree = bool(tree_plan)
                        for i, pack in spec_plan.items():
                            r = pack.rows
                            self._ensure_blocks(i, self._slot_clen[i] + r)
                            mat[i, :r] = pack.row_tokens
                            n_vec[i] = r
                            # reset_val IS the rollback: the device
                            # length over-counts by last tick's rejected
                            # rows until this reset.
                            reset[i] = True
                            reset_val[i] = self._slot_clen[i]
                            ridx_m[i, :r] = sidx[i] + pack.depth
                            if not np.array_equal(
                                pack.depth, np.arange(r, dtype=np.int32)
                            ):
                                need_tree = True
                        for i, (pack, order, s) in tree_plan.items():
                            tfam = self._tree_fams[i]
                            r = pack.rows
                            self._ensure_blocks(i, tfam.base_len + r)
                            mat[i, :r] = pack.row_tokens
                            n_vec[i] = r
                            # The replay reset: committed rows freeze at
                            # the shared ancestors; every suffix row is
                            # re-derived into the window PAST them.
                            reset[i] = True
                            reset_val[i] = tfam.base_len
                            branch_m[i, :r] = np.repeat(np.asarray(
                                [tfam.br_index[b] for b in order],
                                np.int32,
                            ), s)
                            ridx_m[i, :r] = tfam.fork_len + pack.depth
                        if not self._speculate:
                            # Plain live slots ride the tree tick as
                            # n=1 decode rows (the mixed-step contract),
                            # including a forked child's one pending
                            # length reset.
                            for i in live_idx:
                                if i in tree_plan:
                                    continue
                                self._ensure_blocks(
                                    i, len(self._slot_req[i].prompt)
                                    + len(self._slot_tokens[i])
                                )
                                mat[i, 0] = self._tok_host[i]
                                n_vec[i] = 1
                                emit[i] = True
                            for i in list(self._live_reset):
                                if self._slot_state[i] == "live" \
                                        and i not in tree_plan:
                                    reset[i] = True
                                    reset_val[i] = \
                                        self._live_reset.pop(i)
                        for slot, n, last in plan:
                            self._ensure_blocks(
                                slot, self._prefill_pos[slot] + n, n
                            )
                            rows, first = self._consume_chunk(slot, n,
                                                              last)
                            mat[slot, :n] = rows
                            n_vec[slot] = n
                            reset[slot] = first
                            reset_val[slot] = self._prefill_start[slot]
                            emit[slot] = last
                        phases.mark("table_sync")
                        self._sync_table()
                        phases.mark("dispatch", tick, tick_kind, tick_tq,
                                    ahead, self._rows_cross(
                                        tick_tq, tick_group))
                        args = (
                            self.params, jnp.asarray(mat), self.tok,
                            jnp.asarray(use_dev0), jnp.asarray(n_vec),
                            jnp.asarray(reset), jnp.asarray(reset_val),
                            jnp.asarray(emit),
                        )
                        extra = (
                            self._keys, jnp.asarray(self._temp_np),
                            jnp.asarray(self._topk_np),
                            jnp.asarray(sidx), self._lp,
                            jnp.asarray(self._salt_np),
                            jnp.asarray(branch_m), jnp.asarray(ridx_m),
                        )
                        if need_tree:
                            # Per-slot depths + ancestor bitmasks; chain
                            # slots (and prefill chunks) ride the arange/
                            # lower-triangular defaults — the causal rule
                            # bit-for-bit.
                            depth_m = np.tile(
                                np.arange(tq, dtype=np.int32),
                                (self.slots, 1),
                            )
                            bits_m = np.broadcast_to(
                                np.tril(np.ones((tq, tq), bool)),
                                (self.slots, tq, tq),
                            ).copy()
                            for i, pack in spec_plan.items():
                                r = pack.rows
                                depth_m[i, :r] = pack.depth
                                bits_m[i, :r, :r] = pack.anc
                            for i, (pack, _, _) in tree_plan.items():
                                r = pack.rows
                                depth_m[i, :r] = pack.depth
                                bits_m[i, :r, :r] = pack.anc
                            self.tok, self._lp, all_tok_dev, last_dev, \
                                self.cache = self._spec_tree(
                                    *args, jnp.asarray(depth_m),
                                    jnp.asarray(bits_m), self.cache,
                                    *extra,
                                )
                        else:
                            self.tok, self._lp, all_tok_dev, last_dev, \
                                self.cache = self._spec_lin(
                                    *args, self.cache, *extra,
                                )
                        phases.mark("publish")
                        stepped = True
                        for slot, n, last in plan:
                            # Stash prompt-end logits for slots whose
                            # fork/tree family expands at this tick's
                            # awaits pass (ISSUE 15/20).
                            if last and self._slot_req[slot].uid \
                                    in self._families:
                                self._slot_logits[slot] = last_dev[slot]
                        if self._prefix is not None:
                            for slot, n, last in plan:
                                if last:
                                    self._publish_prefix(slot)
                    elif plan:
                        # The fused mixed tick, PACKED: one decode row a
                        # slot beside a compact chunk group, in ONE
                        # compiled program; chunks write straight into
                        # each slot's blocks of the pool at its running
                        # offset.
                        tq = self._chunk_bucket(max(n for _, n, _ in plan))
                        phases.mark("pack")
                        tick_kind, tick_tq = "mixed", tq
                        tick_group = self._chunk_group
                        n_vec = np.zeros((self.slots,), np.int32)
                        reset = np.zeros((self.slots,), bool)
                        reset_val = np.zeros((self.slots,), np.int32)
                        emit = np.zeros((self.slots,), bool)
                        # Sample indices: tokens sampled so far, the
                        # one in flight counted (they also map the blocks).
                        sidx = np.asarray(
                            [self._planned_len(i)
                             for i in range(self.slots)], np.int32
                        )
                        for i in live_idx:
                            self._ensure_blocks(
                                i, len(self._slot_req[i].prompt)
                                + int(sidx[i])
                            )
                            n_vec[i] = 1
                            emit[i] = True
                        # Freshly forked children (ISSUE 15): their one
                        # device-length reset to the fork point.
                        for i in list(self._live_reset):
                            # Applied only once the slot is LIVE — an
                            # awaiting sibling keeps its pending reset
                            # until its first consuming tick.
                            if self._slot_state[i] == "live":
                                reset[i] = True
                                reset_val[i] = self._live_reset.pop(i)
                        chunk_tok, chunk_slot, chunk_n = \
                            self._pack_chunk_group(plan, tq, reset,
                                                   reset_val, emit)
                        kv_rows = (live_idx, sidx)
                        kv_chunk = (chunk_slot, chunk_n)
                        phases.mark("table_sync")
                        self._sync_table()
                        phases.mark("dispatch", tick, tick_kind, tick_tq,
                                    ahead, self._rows_cross(
                                        tick_tq, tick_group))
                        # The decode rows' tokens are the device vector:
                        # a row may consume a token the host has not
                        # fetched yet (ISSUE 32). The per-request vectors
                        # go up as copies (see _sync_table): an admission
                        # writes them while this program may be queued.
                        self.tok, self._lp, fused_dev, last_dev, \
                            self.cache = self._packed(
                                self.params, jnp.asarray(chunk_tok),
                                jnp.asarray(chunk_slot),
                                jnp.asarray(chunk_n), self.tok,
                                jnp.asarray(n_vec), jnp.asarray(reset),
                                jnp.asarray(reset_val),
                                jnp.asarray(emit), self.cache,
                                self._keys,
                                jnp.asarray(self._temp_np.copy()),
                                jnp.asarray(self._topk_np.copy()),
                                jnp.asarray(sidx), self._lp,
                            )
                        phases.mark("publish")
                        stepped = True
                        for slot, n, last in plan:
                            # Stash prompt-end logits for slots whose
                            # fork family expands at this tick's awaits
                            # pass (ISSUE 15).
                            if last and self._slot_req[slot].uid \
                                    in self._families:
                                self._slot_logits[slot] = last_dev[slot]
                        if self._prefix is not None:
                            # Final chunks just completed their prompts in
                            # the batch cache — publish the new blocks
                            # while this admission's rows are fresh.
                            for slot, n, last in plan:
                                if last:
                                    self._publish_prefix(slot)
                    elif live_idx:
                        # Pure-decode tick: the SAME program at the Tq=1
                        # bucket, tokens carried on device (awaiting slots
                        # hold their parked first token through n=0 /
                        # emit=False).
                        phases.mark("pack")
                        if not ran_staged:
                            tick_kind = "decode"
                        tick_tq = 1
                        n_vec = np.zeros((self.slots,), np.int32)
                        emit = np.zeros((self.slots,), bool)
                        reset = np.zeros((self.slots,), bool)
                        reset_val = np.zeros((self.slots,), np.int32)
                        n_vec[live_idx] = 1
                        emit[live_idx] = True
                        for i in list(self._live_reset):
                            # A forked child's device length learns the
                            # fork point at its first consuming tick
                            # (await siblings keep theirs pending).
                            if self._slot_state[i] == "live":
                                reset[i] = True
                                reset_val[i] = self._live_reset.pop(i)
                        sidx = np.asarray(
                            [self._planned_len(i)
                             for i in range(self.slots)], np.int32
                        )
                        for i in live_idx:
                            self._ensure_blocks(
                                i, len(self._slot_req[i].prompt)
                                + int(sidx[i])
                            )
                        kv_rows = (live_idx, sidx)
                        phases.mark("table_sync")
                        self._sync_table()
                        phases.mark("dispatch", tick, tick_kind, tick_tq,
                                    ahead, self._rows_cross(
                                        tick_tq, tick_group))
                        self.tok, self._lp, fused_dev, _, \
                            self.cache = self._mixed(
                                self.params, self.tok[:, None],
                                jnp.asarray(n_vec),
                                jnp.asarray(reset),
                                jnp.asarray(reset_val),
                                jnp.asarray(emit), self.cache,
                                self._keys,
                                jnp.asarray(self._temp_np.copy()),
                                jnp.asarray(self._topk_np.copy()),
                                jnp.asarray(sidx), self._lp,
                            )
                        phases.mark("publish")
                        stepped = True

                    # The program's tail, to be landed: what it ran as,
                    # the slots it holds a row for, and the request each
                    # ran for. Awaiting slots whose first token rode the
                    # PENDING program belong to that tail, not this one.
                    prev = self._tail
                    cur = _Tail(
                        tick=tick, t_s=now, ahead=ahead,
                        why=(None if ahead
                             else why or ("drain" if primed else "first")),
                        kind=tick_kind, tq=tick_tq, group=tick_group,
                        rows_useful=(
                            0 if n_vec is None else int(n_vec.sum())
                            + (chunk_tokens if tick_group else 0)),
                        chunk_tokens=chunk_tokens, plan=plan_rec,
                        live=live_idx,
                        awaits=[
                            i for i, st in enumerate(self._slot_state)
                            if st == "await" and not (
                                ahead
                                and prev.flying(i, self._slot_req[i]))
                        ],
                        reqs=list(self._slot_req),
                        queue_depth=queue_depth, pending=len(pending),
                        draining=draining,
                        fused=fused_dev, all_tok=all_tok_dev,
                        spec_plan=spec_plan, tree_plan=tree_plan,
                        spec_width=spec_width,
                        kv_steps=(
                            (0, 0) if n_vec is None
                            else self._count_kv_steps(
                                tick_tq, n_vec, reset, reset_val, kv_rows,
                                kv_chunk)),
                        pool_rows=(
                            (0, 0) if n_vec is None
                            else self._count_pool_rows(
                                tick_tq, n_vec, kv_chunk)),
                        tail_rows=(
                            0 if n_vec is None
                            else self._count_tail_rows(
                                tick_tq, n_vec, kv_chunk)),
                        scan_rows=(
                            (0, 0) if n_vec is None
                            else self._count_scan_rows(
                                tick_tq, n_vec, kv_chunk)),
                    )
                    primed = True
                    self._tail = cur
                    if ahead:
                        # THE look-ahead: this program is queued behind
                        # the one before it, and only now does the host
                        # wait for that one's tokens. From the moment
                        # they are back the device is this program's.
                        cur.t_s = land(prev)
                    if (why is not None or not self._lookahead
                            or (self._host_pool is not None
                                and self._host_pool.pending)):
                        # A synchronous tick lands its own tail as it
                        # ends; so does one that staged demotions, since
                        # their flush fetches behind every dispatch.
                        self._tail = None
                        land(cur)
                    else:
                        # Left pending for the next iteration; the head's
                        # counters freeze with it.
                        cur.counts = self._tick_counts()
                        if not ahead:
                            phases.finish(None)  # nothing landed: the
                            # stamps go to the span trace alone
                self.slo.maybe_export(now)

                # Every executed tick advances the clock by exactly one;
                # idle iterations (fast-forward, live-feeder waits, the
                # drained exit) were handled before the body, so span
                # and flight-record counts track executed ticks.
                tick += 1
        except BaseException as e:
            # The black-box contract: a wedged/crashed tick loop leaves
            # its last ticks on disk before the exception propagates,
            # the program still in flight among them: its tail lands
            # first, unless the error came out of a landing.
            last, self._tail = self._tail, None
            if last is not None and not landing:
                try:
                    land(last)
                except Exception:
                    log.exception("could not land tick %d's tail after "
                                  "%s", last.tick, type(e).__name__)
            phases.abandon()
            self._serve_tick = self._phases = None
            STARTUP.end(run_span, ticks=tick)
            FLIGHT.dump_if_armed(f"engine_error:{type(e).__name__}")
            if obs.TRACER.active:
                obs.instant("engine_error", cat="serving", args={
                    "error": type(e).__name__, "tick": tick,
                })
            raise

        if self._host_pool is not None:
            # A drained run leaves no demotion staged: the ledger's
            # _DEMOTED blocks would otherwise read as leaked capacity.
            self._flush_demotions()
            self._host_pool.publish_gauge()
        if FLIGHT.enabled:
            # Drained, not wedged: /healthz stays 200 "idle" between runs
            # however long this run's last tick ages.
            FLIGHT.mark_idle()
        if obs.REGISTRY.enabled:
            # The branch gauge is set at tick TOP, so a drained run would
            # otherwise freeze it at the last mid-run value; every family
            # closed, so the truth between runs is zero.
            _TREE_BRANCHES.set(0)
        with self._ctl_lock:
            # This run consumed its control state; the engine is reusable
            # (a drain that completed must not auto-drain the next run).
            # Entry only resets for STATIC traces, so a drain/cancel
            # issued between spawning a live engine thread and the loop
            # starting is honored, not wiped.
            self._cancel_uids.clear()
            self._draining = False
        t_end = time.monotonic()
        wall = t_end - t0
        self._serve_tick = self._phases = None
        # Final SLO publication: the gauges reflect the run's end state and
        # the report carries the windowed snapshot (goodput + percentiles).
        self.slo.export_gauges()
        slo_snap = self.slo.snapshot()
        prefix_snap: Dict[str, Any] = {}
        if self._prefix is not None:
            p1 = self._prefix.stats()
            reused = p1["tokens_reused"] - prefix0["tokens_reused"]
            prompt_tokens = sum(r.prompt_len for r in results)
            prefix_snap = {
                "hits": p1["hits"] - prefix0["hits"],
                "misses": p1["misses"] - prefix0["misses"],
                "tokens_reused": reused,
                "reused_ratio": round(reused / prompt_tokens, 4)
                if prompt_tokens else 0.0,
                "evictions": p1["evictions"] - prefix0["evictions"],
                "pool_blocks_used": p1["pool_blocks_used"],
                "pool_blocks": p1["pool_blocks"],
                # Device KV bytes the run's hits copied: 0 for exact
                # serving (reference-in-place); an int8 hit's dequant
                # gather into the staging cache.
                "hit_bytes_moved": self._hit_bytes_moved - hit_bytes0,
            }
        kv_snap: Dict[str, Any] = {
            "layout": "paged",
            "block": self.kv_block,
            "pool_blocks": self.kv_blocks,
            "blocks_used": self._pool.used,
            "blocks_free": self._pool.free_count,
            "peak_blocks_used": self._peak_blocks_used,
            # Read from the pool the model built, all layers.
            "token_bytes": self._kv_token_bytes,
            # What a block holds whatever its tokens (conv tails).
            "block_fixed_bytes": self._kv_block_fixed_bytes,
        }
        if self._win is not None:
            # Both pools' bytes: the full layers' grow with the tokens
            # held, the window layers' is the bounded one.
            wk = self.cache.wk
            wtok = 2 * wk.shape[0] * wk.shape[2] * wk.shape[4] \
                * wk.dtype.itemsize
            kv_snap.update({
                "pool_bytes": self.kv_blocks * self.kv_block
                * self._kv_token_bytes,
                "window_pool_blocks": self._win.blocks,
                "window_pool_bytes": self._win.blocks * self.kv_block
                * wtok,
                "window_token_bytes": wtok,
                "window_blocks_bound": self._win.bound,
                "window_blocks_peak_slot": self._win.peak_slot,
                "window_blocks_used": self._win.alloc.used,
                "window_blocks_freed": self._win.freed,
            })
        if self._state_pool_bytes:
            # The state beside the K/V rows: which of the two a tick
            # streams more of depends on the contexts held.
            kv_snap.update({
                "pool_bytes": self.kv_blocks * self.kv_block
                * self._kv_token_bytes,
                "state_pool_bytes": dict(self._state_pool_bytes),
            })
        if self._forks_life - fork0[0]:
            # Copy-on-write fork accounting for THIS run (ISSUE 15).
            kv_snap["forks"] = self._forks_life - fork0[0]
            kv_snap["fork_blocks_shared"] = (
                self._fork_shared_life - fork0[1]
            )
        if self._tree_fams_life - tree0[0]:
            # Token-tree sibling accounting for THIS run (ISSUE 20).
            kv_snap["tree_families"] = (
                self._tree_fams_life - tree0[0]
            )
            kv_snap["tree_branch_ticks"] = (
                self._tree_branches_life - tree0[1]
            )
        if self._host_pool is not None:
            h1 = self._host_pool.stats()
            kv_snap.update({
                "host_blocks": h1["host_blocks"],
                "host_blocks_used": h1["host_blocks_used"],
                "demotions": h1["demotions"] - host0["demotions"],
                "restores": h1["restores"] - host0["restores"],
                "host_drops": h1["host_drops"] - host0["host_drops"],
            })
        spec_snap: Dict[str, Any] = {}
        if self._speculate:
            prop = self._spec_proposed - spec0[0]
            acc = self._spec_accepted - spec0[1]
            spec_snap = {
                "drafter": type(self._drafter).__name__,
                "draft_k": self.draft_k,
                "proposed": prop,
                "accepted": acc,
                "acceptance_rate": round(acc / prop, 4) if prop else 0.0,
                "verify_ticks": self._spec_ticks - spec0[2],
                # Accepted drafts per per-SLOT verify event, plus the
                # always-free bonus token (1 = no win, draft_k + 1 =
                # perfect): the per-slot speedup lever.
                "tokens_per_verify": round(
                    1.0 + acc / (self._spec_verifies - spec0[3]), 4
                ) if self._spec_verifies - spec0[3] else 0.0,
            }
        log.info(
            "served %d request(s): %d tokens over %d decode tick(s), "
            "%.1f tok/s, mean occupancy %.2f/%d",
            len(results), tokens, decode_ticks,
            tokens / wall if wall > 0 else 0.0,
            occupancy / max(decode_ticks, 1), self.slots,
        )
        # The report holds the start-up record with this call still open
        # in it (``t1`` None, its fields as they stand); then it closes.
        run_span[3].update(
            ticks=tick, prompt_tokens=sum(r.prompt_len for r in results),
            tokens_generated=tokens)
        report = ServeReport(
            results=sorted(results, key=lambda r: r.uid),
            ticks=tick,
            wall_s=wall,
            tokens_generated=tokens,
            mean_occupancy=occupancy / max(decode_ticks, 1),
            tbt_s=list(tbt),
            slo=slo_snap,
            prefix=prefix_snap,
            kv=kv_snap,
            spec=spec_snap,
            requests=obs.aggregate_ledgers(
                [r.ledger for r in results if r.ledger is not None]
            ) or {},
            programs=tables,
            startup=STARTUP.snapshot(),
        )
        STARTUP.end(run_span, t_end)
        return report
