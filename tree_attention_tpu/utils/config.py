"""Config/flag system: one dataclass, one argparse bridge.

The reference has no config system at all — problem size is hardcoded at
``/root/reference/model.py:140-145``, rendezvous at ``model.py:20-21``, dtype
and seed inside ``make_data`` (``model.py:50-53``). SURVEY.md §5 mandates a
dataclass + flags whose **defaults reproduce the reference run**:
seq_len=64000, 16 heads, head_dim=128, B=1, q_len=1 decode.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Dict, Optional, Sequence


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse ``"seq=8"`` / ``"data=2,seq=2,model=2"`` into an ordered axis map.

    A size of -1 absorbs remaining devices (see ``mesh.make_mesh``).
    """
    axes: Dict[str, int] = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if "=" not in part:
            raise ValueError(f"bad mesh axis {part!r}; want name=size")
        name, _, size = part.partition("=")
        name = name.strip()
        if name in axes:
            raise ValueError(f"duplicate mesh axis {name!r}")
        axes[name] = int(size)
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    return axes


@dataclasses.dataclass
class RunConfig:
    """Everything the driver needs; field defaults == the reference workload."""

    # Problem size (reference: model.py:140-145, 51-53).
    batch: int = 1
    seq_len: int = 64000
    q_len: int = 1
    heads: int = 16
    kv_heads: Optional[int] = None  # None → MHA (kv_heads == heads)
    head_dim: int = 128
    causal: bool = False
    dtype: str = "bfloat16"  # TPU-native half; reference used fp16 on CPU

    # Execution.
    mode: str = "decode"  # decode | train | generate | bench | serve
    device: str = "auto"  # auto | tpu | cpu
    mesh: Optional[str] = None  # e.g. "seq=8" or "data=2,seq=2,model=2"
    n_virtual_cpu: int = 0  # >0: force N virtual CPU devices (tests/emulation)
    launch: int = 0  # >1: respawn N coordinated processes (multi-host shape)
    launch_timeout: Optional[float] = None  # seconds; kill all ranks at expiry
    heartbeat_stall: Optional[float] = None  # seconds; hang watchdog window
    restarts: int = 0  # elastic: whole-gang relaunches after a failure
    impl: str = "auto"  # auto | naive | blockwise | pallas | pallas_decode
    block_size: Optional[int] = None  # None -> impl-appropriate default
    kv_quant: str = "none"  # none | int8 (int8-MXU q8q) | int8-cast (bf16-cast q8)
    seq_layout: str = "contiguous"  # contiguous | zigzag (train mode, seq>1)
    seed: int = 0

    # Timing / bench.
    iters: int = 10
    warmup: int = 2
    comparator: str = "none"  # none | ring (train shape) | ring-decode (bench mode)

    # Training mode.
    steps: int = 3
    model_dim: int = 256
    n_layers: int = 2
    vocab_size: int = 4096
    # The model as data: a JSON file in a published config.json's keys; takes
    # the place of the model flags above (serve mode).
    model_config: Optional[str] = None

    # Generate mode.
    temperature: float = 0.8
    max_new_tokens: int = 32
    # Serve-mode sampling (ISSUE 15): per-slot top-k cutoff (0 = off);
    # --temperature is shared with generate mode. Per-request bodies on
    # the HTTP ingress override both.
    top_k: int = 0

    # Serve mode (continuous batching over a synthetic request trace).
    slots: int = 8           # concurrent cache slots (max in-flight requests)
    requests: int = 16       # synthetic trace length
    prompt_len: int = 32     # base prompt length of the trace
    prompt_jitter: int = 8   # +- jitter on prompt lengths (ragged prompts)
    arrival_every: int = 0   # ticks between arrivals (0 = all queued at start)
    prefill_chunk: int = 256  # max prompt tokens one tick writes per slot
    prefill_budget: Optional[int] = None  # per-tick prompt-token budget
    slo_ttft: float = 1.0    # TTFT target (s) for the goodput SLO
    slo_tbt: float = 0.2     # worst inter-token-gap target (s), ditto
    prefix_cache: bool = False  # radix prefix KV reuse across requests
    prefix_block: int = 64   # pool block granularity (tokens, pow2)
    prefix_share: float = 0.0  # trace: fraction of requests sharing a prefix
    prefix_len: int = 0      # trace: shared prefix length (tokens)
    kv_block: Optional[int] = None  # tokens per pool block (pow2; None ->
    #                                 prefix-block with the cache on, else 64)
    kv_blocks: Optional[int] = None  # TOTAL pool capacity in blocks (None ->
    #                                  slots * ceil(cache_len / kv_block))
    kv_shard: str = "replicated"  # replicated | seq — 'seq' range-partitions
    #                               the paged pool (and its allocator) across
    #                               the mesh's seq axis; decode merges shard
    #                               partials with the tree monoid (ISSUE 18)
    # Hierarchical KV tiering (ISSUE 13): radix eviction demotes blocks
    # onto a host-RAM tier instead of freeing them; a later prefix hit
    # restores them with one batched H2D scatter.
    host_blocks: int = 0     # host-tier capacity in blocks (0 = no tier)
    kv_tiering: str = "on"   # on | off — off ignores --host-blocks (the
    #                          bench's A/B switch at one config)
    speculate: bool = False  # draft-and-verify speculative decoding
    draft_k: int = 4         # max draft tokens per slot per verify tick
    drafter: str = "ngram"   # ngram | ngram-tree | model
    # HTTP ingress (ISSUE 10): --serve-http turns serve mode into a live
    # streaming front-end instead of a synthetic-trace run.
    serve_http: Optional[int] = None  # port (0 = OS-picked, logged)
    max_queue: int = 64      # ingress admission-queue bound (429 past it)
    default_deadline: Optional[float] = None  # seconds; None = no default
    # Fleet serving (ISSUE 11): N in-process replica engines behind the
    # cache-aware router.
    serve_fleet: bool = False
    replicas: int = 2        # replica engines under --serve-fleet
    router_port: int = 0     # router HTTP port (0 = OS-picked, logged)
    affinity: str = "on"     # prefix-affinity routing: on | off
    # Disaggregated prefill/decode (ISSUE 12): split-phase engine pools
    # over one shared block pool, zero-copy KV handoff.
    serve_disagg: bool = False
    prefill_slots: int = 1   # prefill-pool slots under --serve-disagg
    decode_slots: Optional[int] = None  # decode-pool slots (None ->
    #                                     slots - prefill_slots)

    # Host data pipeline (train mode).
    host_data: bool = False
    data: Optional[str] = None       # path to a flat binary token corpus
    data_dtype: str = "int32"        # on-disk token width: int32 | uint16

    # Checkpointing (train mode).
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 1
    resume: bool = False

    # Observability.
    log_level: str = "info"
    log_file: Optional[str] = None
    all_processes: bool = False
    profile_dir: Optional[str] = None
    metrics_out: Optional[str] = None   # JSON metrics snapshot at exit
    trace_events: Optional[str] = None  # Chrome-trace JSONL span sink
    metrics_port: Optional[int] = None  # live /metrics HTTP exporter
    flight_out: Optional[str] = None    # tick flight-recorder dump sink

    def mesh_axes(self) -> Optional[Dict[str, int]]:
        return parse_mesh_spec(self.mesh) if self.mesh else None

    def resolved_kv_heads(self) -> int:
        return self.heads if self.kv_heads is None else self.kv_heads

    def resolved_quant_kernel(self) -> Optional[str]:
        """kv_quant → q8 kernel name (the one home of that mapping):
        'int8' → 'q8q' (int8-MXU, fastest), 'int8-cast' → 'q8' (bf16-cast),
        'none' → None. Programmatic configs bypass argparse's choices, so
        an unknown value raises here rather than silently running int8."""
        kernels = {"none": None, "int8": "q8q", "int8-cast": "q8"}
        if self.kv_quant not in kernels:
            raise ValueError(
                f"kv_quant must be one of {sorted(kernels)}, "
                f"got {self.kv_quant!r}"
            )
        return kernels[self.kv_quant]


def build_arg_parser() -> argparse.ArgumentParser:
    d = RunConfig()
    p = argparse.ArgumentParser(
        prog="tree_attention_tpu",
        # No abbreviations: --launch respawns the command with the flag
        # stripped by literal match; an abbreviated form surviving the strip
        # would recurse (and ambiguous prefixes are a footgun regardless).
        allow_abbrev=False,
        description=(
            "TPU-native sequence-parallel tree attention driver. With no "
            "flags, reproduces the reference workload (decode over a "
            f"{d.seq_len}-token context, {d.heads} heads × {d.head_dim})."
        ),
    )
    p.add_argument("--mode",
                   choices=["decode", "train", "generate", "bench", "serve"],
                   default=d.mode)
    p.add_argument("--device", choices=["auto", "tpu", "cpu"], default=d.device)
    p.add_argument("--mesh", default=d.mesh, metavar="SPEC",
                   help="named mesh axes, e.g. seq=8 or data=2,seq=2,model=2")
    p.add_argument("--n-virtual-cpu", type=int, default=d.n_virtual_cpu,
                   metavar="N", help="emulate N CPU devices (forces --device=cpu)")
    p.add_argument("--launch", type=int, default=d.launch, metavar="N",
                   help="spawn N coordinated local processes (the multi-host "
                        "shape: one jax.distributed cluster, devices pooled "
                        "across processes) and run this command in each; a "
                        "rank that dies fail-fast-kills its peers")
    p.add_argument("--launch-timeout", type=float, default=d.launch_timeout,
                   metavar="SEC", help="deadline for the whole --launch run; "
                   "ranks alive at expiry are killed (status 124)")
    p.add_argument("--heartbeat-stall", type=float, default=d.heartbeat_stall,
                   metavar="SEC", help="hang watchdog for --launch: a rank "
                   "making no progress (no heartbeat; the train loop beats "
                   "once per step) for SEC seconds gets the job killed, "
                   "stalled ranks reporting status 125 — catches the "
                   "all-ranks-alive collective deadlock the fail-fast "
                   "supervisor cannot see. Size it for jit compile time.")
    p.add_argument("--restarts", type=int, default=d.restarts, metavar="K",
                   help="elastic recovery for --launch: after a failed "
                   "attempt (crash/deadline/stall) relaunch the whole gang "
                   "up to K more times; with --ckpt-dir the children resume "
                   "from the latest checkpoint, so a restart is a resume, "
                   "not a redo")
    p.add_argument("--batch", type=int, default=d.batch)
    p.add_argument("--seq-len", type=int, default=d.seq_len)
    p.add_argument("--q-len", type=int, default=d.q_len)
    p.add_argument("--heads", type=int, default=d.heads)
    p.add_argument("--kv-heads", type=int, default=d.kv_heads,
                   help="GQA KV head count (default: same as --heads)")
    p.add_argument("--head-dim", type=int, default=d.head_dim)
    p.add_argument("--causal", action="store_true", default=d.causal)
    p.add_argument("--dtype", choices=["bfloat16", "float16", "float32"],
                   default=d.dtype)
    p.add_argument("--impl",
                   choices=["auto", "naive", "blockwise", "pallas",
                            "pallas_decode"],
                   default=d.impl)
    p.add_argument("--block-size", type=int, default=d.block_size,
                   help="KV tile length (default: per-impl tuned value)")
    p.add_argument("--kv-quant", choices=["none", "int8", "int8-cast"],
                   default=d.kv_quant,
                   help="decode: int8-quantize the KV buffer; generate: "
                        "quantize the cache after prefill (per-channel "
                        "scales; halves the KV stream). 'int8' runs the "
                        "int8-MXU q8q kernel (fastest); 'int8-cast' the "
                        "bf16-cast q8 kernel (minimum int8 error)")
    p.add_argument("--seq-layout", choices=["contiguous", "zigzag"],
                   default=d.seq_layout,
                   help="train mode: sequence layout over the seq mesh axis "
                        "(zigzag balances causal work across shards)")
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--iters", type=int, default=d.iters)
    p.add_argument("--warmup", type=int, default=d.warmup)
    p.add_argument("--comparator", choices=["none", "ring", "ring-decode"],
                   default=d.comparator,
                   help="bench mode: race tree against comparators and report "
                        "ratios — 'ring' on the training shape (fwd+bwd), "
                        "'ring-decode' on the decode shape (replicated Q, "
                        "with collective counts and bytes-on-wire from the "
                        "compiled HLO)")
    p.add_argument("--steps", type=int, default=d.steps, help="train-mode steps")
    p.add_argument("--model-dim", type=int, default=d.model_dim)
    p.add_argument("--n-layers", type=int, default=d.n_layers)
    p.add_argument("--vocab-size", type=int, default=d.vocab_size)
    p.add_argument("--model-config", default=d.model_config, metavar="FILE",
                   help="serve mode: the model as data — a JSON file in a "
                        "published config.json's own keys (latent attention, "
                        "routed experts, YaRN rotary, or a Llama-style dense "
                        "decoder); replaces --model-dim/--heads/--kv-heads/"
                        "--n-layers/--vocab-size")
    p.add_argument("--temperature", type=float, default=d.temperature,
                   help="generate/serve mode: sampling temperature "
                        "(0 = greedy; serve mode threads per-slot PRNG "
                        "keys so fixed-seed runs resample bit-for-bit)")
    p.add_argument("--top-k", type=int, default=d.top_k,
                   help="serve mode: restrict sampling to the k highest "
                        "logits per step (0 = off; only applies when "
                        "--temperature > 0). Per-request bodies on "
                        "--serve-http override both knobs")
    p.add_argument("--max-new-tokens", type=int, default=d.max_new_tokens,
                   help="generate/serve mode: number of tokens to sample "
                        "per request")
    p.add_argument("--slots", type=int, default=d.slots,
                   help="serve mode: concurrent cache slots — the fixed "
                        "batch the continuous-batching engine decodes every "
                        "tick; the cache is sized from the trace "
                        "(max prompt + max-new-tokens, rounded to the "
                        "mesh's seq-shard multiple)")
    p.add_argument("--requests", type=int, default=d.requests,
                   help="serve mode: synthetic request-trace length")
    p.add_argument("--prompt-len", type=int, default=d.prompt_len,
                   help="serve mode: base prompt length of the trace")
    p.add_argument("--prompt-jitter", type=int, default=d.prompt_jitter,
                   help="serve mode: +- jitter on prompt lengths (ragged "
                        "prompts exercise per-slot cache offsets)")
    p.add_argument("--arrival-every", type=int, default=d.arrival_every,
                   help="serve mode: decode ticks between request arrivals "
                        "(0 = the whole trace is queued at start)")
    p.add_argument("--prefill-chunk", type=int, default=d.prefill_chunk,
                   help="serve mode: max prompt tokens one tick may write "
                        "for one slot — smaller chunks bound the latency "
                        "spike a long prompt inflicts on live slots")
    p.add_argument("--prefill-budget", type=int, default=d.prefill_budget,
                   help="serve mode: max TOTAL prompt tokens per tick "
                        "across prefilling slots (default: one chunk). A "
                        "tick computes the rows it carries, so this is what "
                        "a tick with prompt work costs the live slots — "
                        "the Sarathi-style stall-free token budget")
    p.add_argument("--admission", choices=["chunked"], default="chunked",
                   help="serve mode: accepted and read nowhere — serving "
                        "has ONE admission path, prefill chunks fused into "
                        "the per-tick step (benchmark/harness.py still "
                        "passes the flag from each configuration's "
                        "serving.admission)")
    p.add_argument("--slo-ttft", type=float, default=d.slo_ttft,
                   metavar="SEC",
                   help="serve mode: TTFT target of the goodput SLO — a "
                        "retired request counts as good iff its first "
                        "token arrived within SEC and no inter-token gap "
                        "exceeded --slo-tbt")
    p.add_argument("--slo-tbt", type=float, default=d.slo_tbt,
                   metavar="SEC",
                   help="serve mode: worst-inter-token-gap target of the "
                        "goodput SLO (see --slo-ttft)")
    p.add_argument("--prefix-cache", action="store_true",
                   default=d.prefix_cache,
                   help="serve mode: enable the radix prefix KV cache — "
                        "admissions reuse KV blocks of previously served "
                        "prompt prefixes in place (no prefill, no copy; "
                        "RadixAttention, arXiv:2312.07104)")
    p.add_argument("--prefix-block", type=int, default=d.prefix_block,
                   help="serve mode: prefix pool block size in tokens "
                        "(power of two; the match/publish granularity)")
    p.add_argument("--kv-layout", choices=["paged"], default="paged",
                   help="serve mode: accepted and read nowhere — serving "
                        "has ONE KV layout, the paged pool (every slot's "
                        "KV is a block table over one ref-counted pool; "
                        "PagedAttention, arXiv:2309.06180)")
    p.add_argument("--kv-block", type=int, default=d.kv_block,
                   help="serve mode: tokens per KV pool block (power of "
                        "two; default --prefix-block with the prefix "
                        "cache on, else 64)")
    p.add_argument("--kv-blocks", type=int, default=d.kv_blocks,
                   help="serve mode: TOTAL paged pool capacity in blocks "
                        "— the one KV memory budget slots and the prefix "
                        "cache share (default: slots * ceil(cache_len / "
                        "kv_block): every slot can fill its table). "
                        "Smaller over-subscribes: admissions wait for "
                        "free blocks instead of failing")
    p.add_argument("--kv-shard", choices=["replicated", "seq"],
                   default=d.kv_shard,
                   help="serve mode: 'seq' range-partitions the paged KV "
                        "pool across the mesh's sequence axis — each "
                        "shard holds blocks/W pool rows plus its own "
                        "free-list shard, decode computes per-shard "
                        "flash partials over LOCAL blocks only and "
                        "merges them with the tree monoid (one pmax + "
                        "two psum per tick). Max servable context grows "
                        "~linearly with W at fixed per-device KV bytes. "
                        "'replicated' (default) keeps the pool on every "
                        "shard")
    p.add_argument("--host-blocks", type=int, default=d.host_blocks,
                   help="serve mode: host-RAM KV tier capacity in blocks "
                        "(0 = no tier). With the prefix "
                        "cache, radix eviction DEMOTES refcount-0 blocks "
                        "into pinned host memory (async D2H, one batched "
                        "gather per tick) instead of freeing them, and a "
                        "prefix hit on a demoted path restores it with "
                        "one batched H2D scatter — the effective prefix "
                        "cache becomes host-RAM-sized (SGLang's "
                        "hierarchical cache direction)")
    p.add_argument("--kv-tiering", choices=["on", "off"],
                   default=d.kv_tiering,
                   help="serve mode: 'off' ignores --host-blocks (radix "
                        "eviction frees blocks, the pre-tiering "
                        "behavior) — the A/B switch the tiered-KV bench "
                        "flips at one otherwise-identical config")
    p.add_argument("--speculate", action="store_true", default=d.speculate,
                   help="serve mode: draft-and-verify speculative "
                        "decoding (arXiv:2211.17192) on the mixed-Tq "
                        "tick — a host drafter proposes tokens, ONE "
                        "verify step scores them all, accepted prefixes "
                        "commit in a burst, rejections roll back. Greedy "
                        "only (--temperature 0); committed tokens are "
                        "token-for-token identical to non-speculative "
                        "decode")
    p.add_argument("--draft-k", type=int, default=d.draft_k,
                   help="serve mode: max draft tokens per slot per "
                        "verify tick (1..31); one verify commits 1 to "
                        "draft_k+1 tokens")
    p.add_argument("--drafter", choices=["ngram", "ngram-tree", "model"],
                   default=d.drafter,
                   help="serve mode: 'ngram' = prompt-lookup over the "
                        "slot's own history (zero extra model); "
                        "'ngram-tree' = multi-branch token trees "
                        "verified under the tree-attention ancestor "
                        "mask (SpecInfer, arXiv:2305.09781); 'model' = "
                        "a shrunk draft transformer (half depth, same "
                        "vocab, --seed+3)")
    p.add_argument("--serve-http", type=int, default=d.serve_http,
                   metavar="PORT",
                   help="serve mode: run the streaming HTTP ingress on "
                        "localhost:PORT (0 picks a free port, logged) "
                        "instead of draining a synthetic trace — "
                        "OpenAI-compatible POST /v1/completions with SSE "
                        "token streaming, client-disconnect cancellation, "
                        "per-request deadlines, 429+Retry-After "
                        "backpressure; SIGTERM drains gracefully "
                        "(finish in-flight, flush telemetry)")
    p.add_argument("--max-queue", type=int, default=d.max_queue,
                   help="--serve-http: max requests queued ahead of "
                        "first token; submissions past it get 429 with "
                        "Retry-After derived from queue depth and the "
                        "SLO monitor's windowed TTFT")
    p.add_argument("--default-deadline", type=float,
                   default=d.default_deadline, metavar="SEC",
                   help="--serve-http: deadline for requests that do "
                        "not carry their own deadline_s — expired in "
                        "queue they are rejected, expired in flight "
                        "retired with outcome 'deadline'")
    p.add_argument("--serve-fleet", action="store_true",
                   default=d.serve_fleet,
                   help="serve mode: run --replicas in-process replica "
                        "engines behind the cache-aware HTTP router "
                        "(prefix-affinity load balancing, SGLang arXiv:"
                        "2312.07104) instead of one ingress — same "
                        "OpenAI-compatible POST /v1/completions on the "
                        "router port; SIGTERM rolls the whole fleet "
                        "down gracefully")
    p.add_argument("--replicas", type=int, default=d.replicas,
                   help="--serve-fleet: replica engine count (each gets "
                        "its own slots/cache/prefix pool; total capacity "
                        "scales linearly)")
    p.add_argument("--router-port", type=int, default=d.router_port,
                   metavar="PORT",
                   help="--serve-fleet: router HTTP port (0 picks a "
                        "free port, logged)")
    p.add_argument("--affinity", choices=["on", "off"],
                   default=d.affinity,
                   help="--serve-fleet: 'on' routes requests to the "
                        "replica whose radix cache already holds their "
                        "longest prefix (least-loaded fallback with "
                        "hysteresis); 'off' is pure least-loaded round-"
                        "robin — the dilution baseline")
    p.add_argument("--serve-disagg", action="store_true",
                   default=d.serve_disagg,
                   help="serve mode: disaggregated prefill/decode "
                        "(DistServe arXiv:2401.09670, Splitwise arXiv:"
                        "2311.18677) — a prefill pool (--prefill-slots) "
                        "runs admission + chunked prefill only and hands "
                        "finished requests to a decode pool "
                        "(--decode-slots) by zero-copy paged-block "
                        "ownership transfer over ONE shared --kv-blocks "
                        "pool; decode ticks never carry prefill rows, so "
                        "TBT stops paying for admission storms. "
                        "Composable with --serve-http (the ingress "
                        "drives the disaggregated pair unchanged)")
    p.add_argument("--prefill-slots", type=int, default=d.prefill_slots,
                   help="--serve-disagg: prefill-pool slot count "
                        "(prompts concurrently in chunked prefill or "
                        "parked for handoff)")
    p.add_argument("--decode-slots", type=int, default=d.decode_slots,
                   help="--serve-disagg: decode-pool slot count "
                        "(default: --slots minus --prefill-slots, so "
                        "--slots stays the total-capacity knob)")
    p.add_argument("--prefix-share", type=float, default=d.prefix_share,
                   help="serve mode: fraction of the synthetic trace's "
                        "requests drawing their prompt head from a shared "
                        "prefix (models shared system prompts)")
    p.add_argument("--prefix-len", type=int, default=d.prefix_len,
                   help="serve mode: length of the trace's shared prefix "
                        "in tokens (0 = no sharing)")
    p.add_argument("--host-data", action="store_true", default=d.host_data,
                   help="train mode: feed batches from the native prefetching "
                        "host pipeline instead of on-device RNG")
    p.add_argument("--data", default=d.data, metavar="PATH",
                   help="train mode: mmap'd binary token corpus to sample "
                        "batches from (overrides --host-data's synthetic "
                        "tokens; token ids must be < --vocab-size)")
    p.add_argument("--data-dtype", choices=["int32", "uint16"],
                   default=d.data_dtype,
                   help="on-disk token width of --data")
    p.add_argument("--ckpt-dir", default=d.ckpt_dir,
                   help="train mode: checkpoint directory (enables saving)")
    p.add_argument("--ckpt-every", type=int, default=d.ckpt_every,
                   help="save every N steps")
    p.add_argument("--resume", action="store_true", default=d.resume,
                   help="resume from the latest checkpoint in --ckpt-dir")
    p.add_argument("--log-level", choices=["debug", "info", "warning", "error"],
                   default=d.log_level)
    p.add_argument("--log-file", default=d.log_file,
                   help="rotating file sink (the reference's tree_attention_log.log)")
    p.add_argument("--all-processes", action="store_true", default=d.all_processes,
                   help="log from every host, not just process 0")
    p.add_argument("--profile-dir", default=d.profile_dir,
                   help="capture a jax.profiler trace into this directory")
    p.add_argument("--metrics-out", default=d.metrics_out, metavar="PATH",
                   help="write the telemetry registry (tokens decoded, "
                        "collective payload bytes, kernel builds, guard "
                        "verdicts, ...) as JSON at exit; under --launch "
                        "each rank writes PATH.pK")
    p.add_argument("--trace-events", default=d.trace_events, metavar="PATH",
                   help="emit host-side spans as Chrome-trace-format JSONL "
                        "(one JSON event per line; load in Perfetto "
                        "alongside a --profile-dir device trace)")
    p.add_argument("--metrics-port", type=int, default=d.metrics_port,
                   metavar="PORT",
                   help="serve the live telemetry HTTP endpoint on "
                        "localhost:PORT — /metrics (Prometheus text), "
                        "/metrics.json (registry snapshot), /healthz "
                        "(tick liveness), /flight (flight-recorder ring); "
                        "0 picks a free port (logged). Arms the registry "
                        "and flight recorder even without --metrics-out")
    p.add_argument("--flight-out", default=d.flight_out, metavar="PATH",
                   help="arm the serving tick flight recorder and dump "
                        "its ring (last ticks: occupancy, slot states, "
                        "chunk plan, queue depth) to PATH at exit, on "
                        "engine error, and on SIGTERM/SIGUSR1")
    return p


def parse_args(argv: Optional[Sequence[str]] = None) -> RunConfig:
    ns = build_arg_parser().parse_args(argv)
    fields = {f.name for f in dataclasses.fields(RunConfig)}
    return RunConfig(**{k: v for k, v in vars(ns).items() if k in fields})
