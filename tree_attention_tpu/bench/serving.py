"""Serving throughput record: continuous batching vs sequential decode.

Two measurements, one conclusion (aggregate tokens/sec is the serving
north star, not per-token latency):

- **Slope** — the blessed :func:`~tree_attention_tpu.utils.profiling
  .chain_slope` harness times ONE compiled ragged decode step at S slots
  (mixed per-slot lengths — the shape a live engine actually runs) and at
  1 slot. Steady-state throughput is ``S / per_step(S)`` tokens/sec against
  ``1 / per_step(1)`` for one-request-at-a-time decode; their ratio is the
  record's headline ``speedup_vs_sequential``. Chained on-device steps,
  fetch-fenced, min-over-cycles — the same protocol as every decode record.
- **Trace** — the real :class:`~tree_attention_tpu.serving.SlotServer`
  tick loop over a synthetic request trace, swept over slot counts and
  arrival rates, reporting aggregate tokens/sec, mean occupancy, and
  p50/p95 per-request completion. Run twice per cell; the second run's
  wall clock is reported (the first pays the jit compiles).

CPU proxy: the model is deliberately small so the record is about the
*batching structure* (fixed overhead amortised across slots, one dispatch
serving S requests), which transfers; absolute tokens/sec does not.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tree_attention_tpu import obs
from tree_attention_tpu.models import (
    TransformerConfig,
    forward_step,
    init_cache,
    init_params,
)
from tree_attention_tpu.serving import (
    Request,
    SlotServer,
    synthetic_trace,
)
from tree_attention_tpu.serving.engine import _bucket
from tree_attention_tpu.utils.logging import get_logger
from tree_attention_tpu.utils.profiling import chain_slope

log = get_logger("bench.serving")


def serving_model_config(
    *,
    d_model: int = 128,
    n_layers: int = 2,
    n_heads: int = 4,
    n_kv_heads: int = 2,
    vocab_size: int = 512,
    max_seq_len: int = 512,
    dtype=jnp.float32,
) -> TransformerConfig:
    """The serving bench's model: small enough that a CPU proxy run is
    minutes not hours, real enough (GQA, multi-layer) to exercise the full
    ragged stack."""
    return TransformerConfig(
        vocab_size=vocab_size,
        d_model=d_model,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        d_head=d_model // n_heads,
        d_ff=256,
        max_seq_len=max_seq_len,
        dtype=dtype,
        attn_impl="auto",
    )


def _ragged_lengths(slots: int, cache_len: int, seed: int = 7) -> np.ndarray:
    """Mixed per-slot fill levels between 25% and 75% of capacity — the
    mid-flight state of a continuously batched server."""
    rng = np.random.default_rng(seed)
    return rng.integers(cache_len // 4, 3 * cache_len // 4, size=slots).astype(
        np.int32
    )


def slope_decode_step(
    params,
    cfg: TransformerConfig,
    *,
    slots: int,
    cache_len: int,
    lengths: Optional[np.ndarray] = None,
    n_small: int = 4,
    n_large: int = 16,
    iters: int = 3,
    repeats: int = 3,
):
    """chain_slope the compiled ragged decode step at a fixed occupancy.

    The chained carry is the token vector (each step's samples feed the
    next step's queries — a real dependency, nothing overlaps); the cache
    stays at its mixed lengths, so every step prices attention over the
    live context plus the per-step fixed cost the batch amortises.
    """
    if lengths is None:
        lengths = _ragged_lengths(slots, cache_len)
    cache = init_cache(cfg, slots, cache_len)
    cache = dataclasses.replace(
        cache, length=jnp.asarray(lengths, jnp.int32)
    )
    tok0 = jnp.zeros((slots,), jnp.int32)

    def step(tok):
        logits, _ = forward_step(params, tok[:, None], cache, cfg)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    return chain_slope(
        step, tok0, n_small=n_small, n_large=n_large,
        iters=iters, repeats=repeats,
    )


def _trace_cell(
    params,
    cfg: TransformerConfig,
    *,
    slots: int,
    cache_len: int,
    trace_kw: Dict[str, Any],
) -> Dict[str, Any]:
    """One engine run over the synthetic trace.

    The jit compiles (one tick program per Tq bucket) are paid by a
    warmup serve on the SAME server — a jitted bound method caches per
    instance, so a fresh server would recompile — and the timed run then
    measures the loop, not the compiler."""
    server = SlotServer(params, cfg, slots=slots, cache_len=cache_len)
    trace = synthetic_trace(**trace_kw)
    buckets = sorted({_bucket(len(r.prompt), cache_len) for r in trace})
    # Warmup prompts stay 2 tokens under capacity so the serve() capacity
    # pre-check passes even when a trace's prompts bucket up to cache_len;
    # _bucket pads back up, so the compiled shapes are the trace's own.
    server.serve([
        Request(uid=-(i + 1),
                prompt=np.zeros(min(b, cache_len - 2), np.int32),
                max_new_tokens=2)
        for i, b in enumerate(buckets)
    ])
    report = server.serve(trace)
    d = report.as_dict()
    d["slots"] = slots
    return d


def bench_serving(
    *,
    slots: int = 8,
    slot_sweep: Sequence[int] = (1, 4, 8),
    arrival_sweep: Sequence[int] = (0, 2),
    n_requests: int = 12,
    prompt_len: int = 32,
    prompt_jitter: int = 16,
    max_new_tokens: int = 16,
    cache_len: int = 128,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The serving record: slope-timed step speedup + trace sweeps.

    ``slots=1`` in the sweep IS the sequential baseline: one request at a
    time through the identical engine, so the comparison isolates
    continuous batching (same model, same kernels, same scheduler code).
    """
    cfg = cfg or serving_model_config(max_seq_len=cache_len)
    params = init_params(jax.random.PRNGKey(seed), cfg)

    # --- slope: the blessed harness, batched vs single-request step ---
    # The single-slot baseline runs at the batched lengths' MEAN, so the
    # ratio isolates the batching structure (same attended context per
    # token on both sides), not a workload mismatch.
    lens = _ragged_lengths(slots, cache_len)
    with obs.span("bench_serving:slope", cat="bench"):
        s_batch = slope_decode_step(
            params, cfg, slots=slots, cache_len=cache_len, lengths=lens
        )
        s_one = slope_decode_step(
            params, cfg, slots=1, cache_len=cache_len,
            lengths=np.asarray([int(round(lens.mean()))], np.int32),
        )
    tps_batch = slots / s_batch.per_step
    tps_one = 1.0 / s_one.per_step
    slope_rec = {
        "slots": slots,
        "us_per_step_batched": round(s_batch.per_step * 1e6, 1),
        "us_per_step_single": round(s_one.per_step * 1e6, 1),
        "tokens_per_sec_batched": round(tps_batch, 1),
        "tokens_per_sec_sequential": round(tps_one, 1),
        "speedup_vs_sequential": round(tps_batch / tps_one, 3),
        "slope_cycles_us_batched": [
            round(s * 1e6, 2) for s in s_batch.slopes
        ],
        "slope_cycles_us_single": [round(s * 1e6, 2) for s in s_one.slopes],
        "spread_pct": round(
            max(s_batch.spread_pct, s_one.spread_pct), 1
        ),
    }

    # --- trace: the real tick loop, swept over slots and arrival rates ---
    base_trace = dict(
        n_requests=n_requests,
        prompt_len=prompt_len,
        prompt_jitter=prompt_jitter,
        max_new_tokens=max_new_tokens,
        vocab_size=cfg.vocab_size,
        seed=seed + 1,
    )
    trace_rec: Dict[str, Any] = {}
    with obs.span("bench_serving:trace", cat="bench"):
        for s in slot_sweep:
            trace_rec[f"slots_{s}"] = _trace_cell(
                params, cfg, slots=s, cache_len=cache_len,
                trace_kw=dict(base_trace, arrival_every=0),
            )
        for every in arrival_sweep:
            if every == 0:
                continue  # the slot sweep already covers the burst case
            trace_rec[f"slots_{slots}_arrival_every_{every}"] = _trace_cell(
                params, cfg, slots=slots, cache_len=cache_len,
                trace_kw=dict(base_trace, arrival_every=every),
            )
    seq = trace_rec.get("slots_1", {})
    batched = trace_rec.get(f"slots_{slots}", {})
    if seq.get("tokens_per_sec") and batched.get("tokens_per_sec"):
        trace_rec["trace_speedup_vs_sequential"] = round(
            batched["tokens_per_sec"] / seq["tokens_per_sec"], 3
        )

    log.info(
        "serving: slope %(b).1f vs %(s).1f tok/s -> %(r).2fx; trace %(t)sx",
        dict(b=tps_batch, s=tps_one, r=tps_batch / tps_one,
             t=trace_rec.get("trace_speedup_vs_sequential", "?")),
    )
    return {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "cache_len": cache_len,
            "trace": {k: v for k, v in base_trace.items() if k != "seed"},
        },
        "slope": slope_rec,
        "trace": trace_rec,
    }


# ---------------------------------------------------------------------------
# Slopes of one mixed tick and of one whole-prompt B=1 prefill
# ---------------------------------------------------------------------------


def slope_mixed_tick(
    params,
    cfg: TransformerConfig,
    *,
    slots: int,
    cache_len: int,
    chunk: int,
    lengths: np.ndarray,
    n_small: int = 4,
    n_large: int = 16,
    iters: int = 3,
    repeats: int = 3,
):
    """chain_slope ONE mixed tick: ``slots - 1`` decode rows plus one
    ``chunk``-token prefill chunk riding along (the stall-free shape) —
    the chained carry is the sampled token vector, the cache and the
    per-slot valid counts stay fixed, so the slope prices exactly the
    per-tick program the chunked engine dispatches."""
    cache = init_cache(cfg, slots, cache_len)
    cache = dataclasses.replace(cache, length=jnp.asarray(lengths, jnp.int32))
    n_vec = np.ones((slots,), np.int32)
    n_vec[-1] = chunk
    n_vec = jnp.asarray(n_vec)
    tok0 = jnp.zeros((slots,), jnp.int32)

    def step(tok):
        mat = jnp.zeros((slots, chunk), jnp.int32).at[:, 0].set(tok)
        logits, _ = forward_step(params, mat, cache, cfg, n_tokens=n_vec)
        return jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)

    return chain_slope(
        step, tok0, n_small=n_small, n_large=n_large,
        iters=iters, repeats=repeats,
    )


def slope_whole_prefill(
    params,
    cfg: TransformerConfig,
    *,
    bucket: int,
    n_small: int = 2,
    n_large: int = 8,
    iters: int = 3,
    repeats: int = 3,
):
    """chain_slope one whole-prompt B=1 prefill at its prompt bucket:
    what a prefix hit of that length saves an admission."""
    cache = init_cache(cfg, 1, bucket)
    tok0 = jnp.zeros((1,), jnp.int32)

    def step(tok):
        mat = jnp.broadcast_to(tok[:, None], (1, bucket))
        logits, _ = forward_step(params, mat, cache, cfg)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    return chain_slope(
        step, tok0, n_small=n_small, n_large=n_large,
        iters=iters, repeats=repeats,
    )


# ---------------------------------------------------------------------------
# ISSUE 5: shared-prefix flood — prefix cache on vs off
# ---------------------------------------------------------------------------


def time_paged_hit_host_update(
    *,
    prefix_len: int,
    kv_block: int,
    iters: int = 200,
    repeats: int = 3,
) -> float:
    """Microseconds for ONE paged prefix hit's entire device-visible
    cost: the radix match + pinning + writing the matched pool ids into
    a host table row (+ the release the retire path pays). This is ALL
    a hit costs in place of the matched prefix's prefill — the whole
    point of ISSUE 6 — priced min-over-repeats like the slopes. Host
    wall time: there is nothing to fetch-fence because nothing is
    dispatched."""
    import time as _time

    from tree_attention_tpu.serving.block_pool import BlockAllocator
    from tree_attention_tpu.serving.prefix_cache import PagedPrefixIndex

    nb = prefix_len // kv_block
    alloc = BlockAllocator(nb)
    idx = PagedPrefixIndex(block=kv_block, alloc=alloc)
    rng = np.random.default_rng(0)
    # One extra token so the full prefix stays matchable (the cap keeps
    # one suffix token, same as the engine).
    prompt = rng.integers(0, 512, size=prefix_len + 8).astype(np.int32)
    reserved = alloc.reserve(nb)  # side effect must survive python -O
    assert reserved
    ids = {j: alloc.alloc() for j in range(nb)}
    path, _ = idx.adopt(prompt, ids, [])
    idx.release(path)
    table = np.zeros((nb + 1,), np.int32)

    best = float("inf")
    for _ in range(repeats):
        t0 = _time.perf_counter()
        for _ in range(iters):
            matched, nodes = idx.match(prompt)
            for j, node in enumerate(nodes):
                table[j] = node.block_id
            idx.release(nodes)
        best = min(best, (_time.perf_counter() - t0) / iters)
    assert matched == prefix_len
    return best * 1e6


def _max_concurrent(report) -> int:
    """Max simultaneously in-flight requests over a run (admit→finish
    tick overlap) — the capacity truth the paged layout changes."""
    events = []
    for r in report.results:
        events.append((r.admit_tick, 1))
        events.append((r.finish_tick + 1, -1))
    cur = best = 0
    for _, d in sorted(events):
        cur += d
        best = max(best, cur)
    return best


def bench_serving_paged_flood(
    *,
    slots: int = 2,
    oversub_slots: int = 5,
    cache_len: int = 640,
    prefix_len: int = 512,
    prefix_share: float = 0.75,
    prompt_len: int = 536,
    n_requests: int = 8,
    max_new_tokens: int = 4,
    arrival_every: int = 2,
    prefill_chunk: int = 64,
    kv_block: int = 64,
    extra_pool_blocks: int = 24,
    repeats: int = 3,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The paged-KV record (ISSUE 6): the PR-5 shared-prefix flood on
    the paged pool, at ``slots`` slots and over-subscribed.

    The pool is ONE ``--kv-blocks`` budget of ``slots × cache_len``
    tokens plus ``extra_pool_blocks`` for retained prefixes. Three
    measurements:

    - **Slope** — :func:`time_paged_hit_host_update`: what a hit pays (a
      radix walk + a host table-row write); the arms'
      ``prefix.hit_bytes_moved == 0`` in the trace repeats is the same
      claim measured end-to-end.
    - **TTFT trace** — the flood at ``slots`` slots, min-over-repeats
      TTFT p50/p95.
    - **Capacity trace** — ``oversub_slots`` slots over the SAME pool
      bytes: shared prefix blocks mean concurrent hits cost one block
      each instead of a full ``cache_len`` of blocks, so
      ``max_concurrent_requests`` rises past what ``slots`` full-length
      slots hold. ``max_concurrent_improvement`` is the headline;
      all-at-start arrivals make the concurrency demand real.

    CPU proxy by design: the eager paged path re-gathers the logical
    view every tick (the Pallas kernel reads blocks in place on TPU);
    the structural wins (zero-copy hits, capacity) transfer.
    """
    cfg = cfg or serving_model_config(max_seq_len=cache_len)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    npb = -(-cache_len // kv_block)
    pool_blocks = slots * npb + extra_pool_blocks  # the equal-bytes total
    trace_kw = dict(
        n_requests=n_requests,
        prompt_len=prompt_len,
        prompt_jitter=0,
        max_new_tokens=max_new_tokens,
        arrival_every=arrival_every,
        vocab_size=cfg.vocab_size,
        seed=seed + 1,
        prefix_share=prefix_share,
        prefix_len=prefix_len,
        prefix_seed=seed + 1000,
    )

    # --- slope: the table update that is all a hit pays ---
    with obs.span("bench_serving_paged:slope", cat="bench"):
        host_us = time_paged_hit_host_update(
            prefix_len=prefix_len, kv_block=kv_block,
        )
    slope_rec = {
        "us_per_hit_host_update": round(host_us, 2),
        "prefix_len": prefix_len,
        "kv_block": kv_block,
    }

    # --- traces ---
    def run_arm(n_slots: int) -> Dict[str, Any]:
        server = SlotServer(
            params, cfg, slots=n_slots, cache_len=cache_len,
            prefill_chunk=prefill_chunk, prefix_cache=True,
            prefix_block=kv_block,
            kv_block=kv_block, kv_blocks=pool_blocks,
        )
        server.serve(synthetic_trace(**trace_kw))  # compiles + warm pool
        runs = []
        for r in range(repeats):
            report = server.serve(synthetic_trace(
                **dict(trace_kw, seed=seed + 2 + r)
            ))
            d = report.as_dict()
            d["max_concurrent_requests"] = _max_concurrent(report)
            runs.append(d)
        return {
            "slots": n_slots,
            "repeats": runs,
            "ttft_p50_s": min(r["ttft_p50_s"] for r in runs),
            "ttft_p95_s": min(r["ttft_p95_s"] for r in runs),
            "tokens_per_sec": max(r["tokens_per_sec"] for r in runs),
            "max_concurrent_requests": max(
                r["max_concurrent_requests"] for r in runs
            ),
            "hit_bytes_moved": max(
                r.get("prefix", {}).get("hit_bytes_moved", 0)
                for r in runs
            ),
        }

    trace_rec: Dict[str, Any] = {}
    with obs.span("bench_serving_paged:trace", cat="bench"):
        trace_rec["paged"] = run_arm(slots)
        # Capacity arm: more slots, SAME pool bytes, all queued at start
        # so the concurrency demand is real.
        burst = dict(trace_kw, arrival_every=0,
                     n_requests=max(n_requests, oversub_slots + 2))
        osrv = SlotServer(
            params, cfg, slots=oversub_slots, cache_len=cache_len,
            prefill_chunk=prefill_chunk, prefix_cache=True,
            prefix_block=kv_block,
            kv_block=kv_block, kv_blocks=pool_blocks,
        )
        osrv.serve(synthetic_trace(**burst))
        orep = osrv.serve(synthetic_trace(**dict(burst, seed=seed + 9)))
        trace_rec["paged_oversub"] = {
            "slots": oversub_slots,
            "pool_blocks": pool_blocks,
            "max_concurrent_requests": _max_concurrent(orep),
            "kv": orep.kv,
            "prefix": orep.prefix,
        }
    base_cc = trace_rec["paged"]["max_concurrent_requests"]
    if base_cc > 0:
        trace_rec["max_concurrent_improvement"] = round(
            trace_rec["paged_oversub"]["max_concurrent_requests"]
            / base_cc, 2
        )

    log.info(
        "paged flood: hit host update %(h).2fus; TTFT p50 %(pp).4fs; "
        "max concurrent %(mc)d vs %(mo)d at equal pool bytes",
        dict(h=slope_rec["us_per_hit_host_update"],
             pp=trace_rec["paged"]["ttft_p50_s"],
             mc=base_cc,
             mo=trace_rec["paged_oversub"]["max_concurrent_requests"]),
    )
    return {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "cache_len": cache_len,
            "kv_block": kv_block,
            "pool_blocks": pool_blocks,
            "trace": {k: v for k, v in trace_kw.items() if k != "seed"},
        },
        "slope": slope_rec,
        "trace": trace_rec,
    }


def bench_serving_prefix_flood(
    *,
    slots: int = 2,
    cache_len: int = 640,
    prefix_len: int = 512,
    prefix_share: float = 0.75,
    prompt_len: int = 536,
    prompt_jitter: int = 0,
    n_requests: int = 8,
    max_new_tokens: int = 4,
    arrival_every: int = 2,
    prefill_chunk: int = 64,
    prefix_block: int = 64,
    pool_blocks: int = 24,
    repeats: int = 3,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The prefix-reuse record: TTFT under a shared-prefix flood, prefix
    cache on vs off (ISSUE 5 / RadixAttention, arXiv:2312.07104).

    A 512-token shared prefix at >= 50% share is the production shape
    (system prompts, few-shot templates); re-prefilling it per request is
    the cost a radix KV cache deletes. Two measurements, the usual
    protocol:

    - **Slope** — chain_slope (min-over->=3-cycles) prices the whole
      ``prefix_len``-token B=1 prefill against the host table update
      that replaces it on a hit; their ratio (``prefill_avoided_ratio``)
      is the deterministic per-hit saving, independent of trace timing.
    - **Trace** — the real engine over shared-prefix traces
      (``synthetic_trace(prefix_share=..., prefix_len=...)``), cache on
      vs off, ``repeats`` timed runs on a warmed server,
      min-over-repeats TTFT p50/p95 (the latency the reuse protects) plus
      the run's tokens-reused ratio. ``ttft_p50_improvement`` is the
      headline: off-p50 over on-p50. The warmup run also warms the POOL,
      and every timed repeat draws FRESH per-request randomness while
      ``prefix_seed`` pins the shared-prefix population — so shared
      admissions hit steady-state (a long-lived server's shape) while the
      non-shared ``1 - share`` of requests stay honestly cold, and the
      reported improvement is the claimed share's, not a 100%-hit
      replay's.

    CPU proxy by design: the structure (a 512-token prefill vs a table
    update) transfers; absolute seconds do not.
    """
    cfg = cfg or serving_model_config(max_seq_len=cache_len)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    trace_kw = dict(
        n_requests=n_requests,
        prompt_len=prompt_len,
        prompt_jitter=prompt_jitter,
        max_new_tokens=max_new_tokens,
        arrival_every=arrival_every,
        vocab_size=cfg.vocab_size,
        seed=seed + 1,
        prefix_share=prefix_share,
        prefix_len=prefix_len,
        prefix_seed=seed + 1000,  # one prefix population across repeats
    )

    # --- slope: one shared-prefix prefill vs the update replacing it ---
    bucket = _bucket(prefix_len, cache_len)
    with obs.span("bench_serving_prefix:slope", cat="bench"):
        s_prefill = slope_whole_prefill(params, cfg, bucket=bucket)
        host_us = time_paged_hit_host_update(
            prefix_len=prefix_len, kv_block=prefix_block,
        )
    slope_rec = {
        "us_per_prefix_prefill": round(s_prefill.per_step * 1e6, 1),
        "us_per_hit_host_update": round(host_us, 2),
        "prefix_len": prefix_len,
        "prefix_block": prefix_block,
        "prefill_avoided_ratio": round(
            s_prefill.per_step * 1e6 / max(host_us, 1e-9), 2
        ),
        "spread_pct": round(s_prefill.spread_pct, 1),
    }

    # --- trace: the real engine, cache on vs off ---
    def run_mode(prefix_on: bool) -> Dict[str, Any]:
        server = SlotServer(
            params, cfg, slots=slots, cache_len=cache_len,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_on,
            prefix_block=prefix_block, prefix_pool_blocks=pool_blocks,
        )
        server.serve(synthetic_trace(**trace_kw))  # compiles + warm pool
        runs = []
        for r in range(repeats):
            # Fresh suffixes/cold prompts per repeat (same shared
            # prefixes): only genuinely shared tokens may hit.
            report = server.serve(synthetic_trace(
                **dict(trace_kw, seed=seed + 2 + r)
            ))
            runs.append(report.as_dict())
        out = {
            "repeats": runs,
            "ttft_p50_s": min(r["ttft_p50_s"] for r in runs),
            "ttft_p95_s": min(r["ttft_p95_s"] for r in runs),
            "tbt_p95_s": min(r["tbt_p95_s"] for r in runs),
            "tokens_per_sec": max(r["tokens_per_sec"] for r in runs),
        }
        if prefix_on:
            # Mean-over-repeats: reuse is workload composition, not a
            # noisy timing — a min/max would report a repeat whose random
            # share draw happened to run hot or cold.
            ratios = [r.get("prefix", {}).get("reused_ratio", 0.0)
                      for r in runs]
            out["tokens_reused_ratio"] = round(
                sum(ratios) / max(len(ratios), 1), 4
            )
            out["prefix"] = runs[-1].get("prefix", {})
        return out

    trace_rec: Dict[str, Any] = {}
    with obs.span("bench_serving_prefix:trace", cat="bench"):
        trace_rec["off"] = run_mode(False)
        trace_rec["on"] = run_mode(True)
    on_p50 = trace_rec["on"]["ttft_p50_s"]
    if on_p50 > 0:
        trace_rec["ttft_p50_improvement"] = round(
            trace_rec["off"]["ttft_p50_s"] / on_p50, 2
        )
    on_p95 = trace_rec["on"]["ttft_p95_s"]
    if on_p95 > 0:
        trace_rec["ttft_p95_improvement"] = round(
            trace_rec["off"]["ttft_p95_s"] / on_p95, 2
        )

    log.info(
        "prefix flood: avoided ratio %(a).1fx (slope); TTFT p50 %(o).4fs "
        "off vs %(n).4fs on -> %(i)sx; reused ratio %(r)s",
        dict(a=slope_rec["prefill_avoided_ratio"],
             o=trace_rec["off"]["ttft_p50_s"], n=on_p50,
             i=trace_rec.get("ttft_p50_improvement", "?"),
             r=trace_rec["on"].get("tokens_reused_ratio", "?")),
    )
    return {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "cache_len": cache_len,
            "pool_blocks": pool_blocks,
            "trace": {k: v for k, v in trace_kw.items() if k != "seed"},
        },
        "slope": slope_rec,
        "trace": trace_rec,
    }


def _repetitive_trace(n_requests: int, *, prompt_len: int, max_new: int,
                      vocab: int, seed: int = 0) -> List[Request]:
    """Templated/repetitive prompts (short repeating patterns): the
    workload prompt-lookup speculation exists for. The tiny bench model's
    greedy continuation settles into an attractor loop after a short
    wander, and the n-gram drafter then predicts it near-perfectly —
    the high-acceptance regime, produced honestly by the model itself
    rather than by scripting its output."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        pat = rng.integers(0, vocab, size=int(rng.integers(2, 5)))
        prompt = np.tile(pat, -(-prompt_len // len(pat)))[:prompt_len]
        reqs.append(Request(uid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=max_new))
    return reqs


def bench_serving_speculative(
    *,
    slots: int = 2,
    n_requests: int = 4,
    prompt_len: int = 24,
    max_new: int = 256,
    cache_len: int = 320,
    draft_k: int = 7,
    repeats: int = 3,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 3,
) -> Dict[str, Any]:
    """The speculative-decoding record (ISSUE 8): decode tokens/sec per
    slot with draft-and-verify on vs off, on a repetitive/templated trace
    where acceptance is high.

    Three measurements:

    - **Slope** — chain_slope prices the two per-tick programs: the plain
      decode tick (Tq=1) and the verify-shaped mixed tick at the spec
      bucket (Tq = pow2(draft_k+1)). ``verify_tick_cost_ratio`` is the
      padded verify step's cost over the decode step's — what a verify
      must amortise; at acceptance α it commits ``1 + α·draft_k`` tokens,
      so the structural speedup is ``(1 + α·draft_k) /
      verify_tick_cost_ratio``.
    - **Trace** — the real engine over the identical trace with
      ``speculate`` off, on (``ngram``), and on with token-tree drafts
      (``ngram-tree``), ``repeats`` timed runs each on a warmed server,
      best-over-repeats tokens/sec (the noise-robust larger-is-better
      sample). ``tokens_per_sec_improvement`` (the headline, >= 2x at
      high acceptance on this box) and the run's measured
      ``acceptance_rate`` / ``tokens_per_verify`` come straight from the
      engine's verify accounting.
    - **Parity** — the committed streams of all three runs are asserted
      token-identical before any number is reported: a speculative
      speedup that changed a single token would be a wrong answer fast.

    CPU proxy by design: per-tick fixed cost dominates this model, which
    is exactly the structure speculation attacks (fewer, fatter ticks);
    the acceptance machinery transfers unchanged. The default model is
    deliberately small (d=64, vocab=128): its greedy continuations
    settle into attractor loops quickly, giving the high-acceptance
    regime from the model's own honest outputs — measured ~0.86
    acceptance / 2.7x tok/s at the defaults on this box (the wider
    serving_model_config default wanders too long to accept much; real
    templated traffic is the production analogue).
    """
    import time as _time

    cfg = cfg or serving_model_config(
        max_seq_len=cache_len, vocab_size=128, d_model=64
    )
    params = init_params(jax.random.PRNGKey(seed), cfg)

    # --- slope: decode tick vs verify-shaped tick ---
    bucket = 8
    while bucket < draft_k + 1:
        bucket *= 2
    lens = _ragged_lengths(slots, cache_len)
    np.minimum(lens, cache_len - bucket, out=lens)
    with obs.span("bench_serving_speculative:slope", cat="bench"):
        s_decode = slope_decode_step(
            params, cfg, slots=slots, cache_len=cache_len, lengths=lens
        )
        s_verify = slope_mixed_tick(
            params, cfg, slots=slots, cache_len=cache_len, chunk=bucket,
            lengths=lens,
        )
    cost_ratio = (
        s_verify.per_step / s_decode.per_step if s_decode.per_step else 0.0
    )
    slope_rec = {
        "us_per_decode_tick": round(s_decode.per_step * 1e6, 1),
        "us_per_verify_tick": round(s_verify.per_step * 1e6, 1),
        "verify_bucket": bucket,
        "verify_tick_cost_ratio": round(cost_ratio, 3),
    }

    # --- trace: off vs ngram vs ngram-tree, parity-gated ---
    def run_mode(label: str, **spec_kw) -> Dict[str, Any]:
        server = SlotServer(
            params, cfg, slots=slots, cache_len=cache_len, **spec_kw
        )
        reqs = _repetitive_trace(
            n_requests, prompt_len=prompt_len, max_new=max_new,
            vocab=cfg.vocab_size, seed=seed + 1,
        )
        server.serve([dataclasses.replace(r) for r in reqs])  # warm jits
        best: Optional[Dict[str, Any]] = None
        toks = None
        for _ in range(repeats):
            t0 = _time.monotonic()
            rep = server.serve([dataclasses.replace(r) for r in reqs])
            wall = _time.monotonic() - t0
            toks = {r.uid: r.tokens for r in rep.results}
            cell = {
                "tokens_per_sec": round(rep.tokens_generated / wall, 1),
                "tokens_per_sec_per_slot": round(
                    rep.tokens_generated / wall / slots, 1
                ),
                "ticks": rep.ticks,
                "wall_s": round(wall, 4),
            }
            if rep.spec:
                cell["acceptance_rate"] = rep.spec["acceptance_rate"]
                cell["tokens_per_verify"] = rep.spec["tokens_per_verify"]
            if best is None or (cell["tokens_per_sec"]
                                > best["tokens_per_sec"]):
                best = cell
        best["label"] = label
        return best, toks

    with obs.span("bench_serving_speculative:trace", cat="bench"):
        off, toks_off = run_mode("off")
        on, toks_on = run_mode(
            "ngram", speculate=True, draft_k=draft_k, drafter="ngram"
        )
        tree, toks_tree = run_mode(
            "ngram-tree", speculate=True, draft_k=draft_k,
            drafter="ngram-tree",
        )
    for label, got in (("ngram", toks_on), ("ngram-tree", toks_tree)):
        assert got == toks_off, (
            f"PARITY VIOLATION: speculative run ({label}) changed tokens"
        )
    trace_rec: Dict[str, Any] = {"off": off, "on": on, "tree": tree,
                                 "parity": "token-identical"}
    if off["tokens_per_sec"] > 0:
        trace_rec["tokens_per_sec_improvement"] = round(
            on["tokens_per_sec"] / off["tokens_per_sec"], 2
        )
        trace_rec["tree_tokens_per_sec_improvement"] = round(
            tree["tokens_per_sec"] / off["tokens_per_sec"], 2
        )

    log.info(
        "speculative: %(i)sx tok/s (acceptance %(a)s, %(t)s tok/verify) "
        "vs verify tick cost %(c).2fx",
        dict(i=trace_rec.get("tokens_per_sec_improvement", "?"),
             a=on.get("acceptance_rate", "?"),
             t=on.get("tokens_per_verify", "?"), c=cost_ratio),
    )
    return {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "slots": slots,
            "cache_len": cache_len,
            "requests": n_requests,
            "prompt_len": prompt_len,
            "max_new_tokens": max_new,
            "draft_k": draft_k,
        },
        "slope": slope_rec,
        "trace": trace_rec,
    }


def bench_serving_forked_sampling(
    *,
    slots: int = 8,
    branches: int = 8,
    prompt_len: int = 112,
    max_new: int = 16,
    kv_block: int = 16,
    n_requests: int = 3,
    prefix_len: int = 96,
    repeats: int = 3,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 11,
) -> Dict[str, Any]:
    """The copy-on-write fork record (ISSUE 15): n>1 sampling on shared
    KV blocks vs independent requests.

    Three measurements, parity first:

    - **Parity** — greedy (temperature 0): one ``n = branches`` family
      vs ``branches`` independent requests on the same warmed engine,
      asserted token-identical per branch BEFORE any number is
      reported; and a sampled (temperature 1) family served twice,
      asserted bit-identical across serves (the per-request PRNG-key
      contract).
    - **Family economics** — ONE request at ``n = branches`` vs ``n=1``:
      ``peak_blocks_used`` from the engine's own ledger gives
      ``pool_bytes_per_completion`` (per-branch cost collapses because
      every full prompt block exists ONCE), the family-over-single
      ``pool_bytes_ratio`` (the ISSUE's <= 2x claim at this shape; a
      naive implementation pays ``branches``x), and
      ``fork_share_ratio`` — the fraction of a sibling's worst-case
      blocks served by sharing rather than allocation.
    - **Trace TTFT** — a shared-prefix trace served with ``n=1`` vs
      ``n = branches`` at equal engine/pool: per-branch TTFT p50s and
      their ratio (the prompt prefills once per family, so the family
      arm's p50 must stay within 1.3x — asserted).

    Sampled arms run at temperature 1.0 with per-request keys, so every
    number is reproducible run-to-run by construction.
    """
    import time as _time

    cache_len = prompt_len + max_new + kv_block  # one spare block's slack
    cfg = cfg or serving_model_config(
        max_seq_len=cache_len, vocab_size=128, d_model=64
    )
    params = init_params(jax.random.PRNGKey(seed), cfg)
    kv_token_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * cfg.d_head
                      * jnp.dtype(cfg.dtype).itemsize)
    block_bytes = kv_block * kv_token_bytes

    def build(temperature: float) -> SlotServer:
        return SlotServer(
            params, cfg, slots=slots, cache_len=cache_len,
            kv_block=kv_block, temperature=temperature, seed=seed,
        )

    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg.vocab_size,
                          size=prompt_len).astype(np.int32)

    # --- parity gates -----------------------------------------------------
    with obs.span("bench_serving_forked:parity", cat="bench"):
        greedy = build(0.0)
        fam = greedy.serve([Request(uid=0, prompt=prompt,
                                    max_new_tokens=max_new, n=branches)])
        got = {r.index: r.tokens for r in fam.results}
        ref = greedy.serve([
            Request(uid=100 + j, prompt=prompt, max_new_tokens=max_new)
            for j in range(branches)
        ])
        ref_toks = {r.uid: r.tokens for r in ref.results}
        for j in range(branches):
            assert got[j] == ref_toks[100 + j], (
                f"PARITY VIOLATION: fork branch {j} diverged from an "
                f"independent greedy request"
            )
        leak = greedy.leak_report()
        assert leak["blocks_used"] == leak["blocks_cached"] \
            and leak["blocks_shared"] == 0 and leak["pins"] == 0, leak
        sampled = build(1.0)
        s1 = sampled.serve([Request(uid=0, prompt=prompt,
                                    max_new_tokens=max_new, n=branches)])
        s2 = sampled.serve([Request(uid=0, prompt=prompt,
                                    max_new_tokens=max_new, n=branches)])
        assert {r.index: r.tokens for r in s1.results} \
            == {r.index: r.tokens for r in s2.results}, (
                "PARITY VIOLATION: sampled family not reproducible "
                "across serves"
            )

    # --- family economics (one request, exact ledger math) ---------------
    with obs.span("bench_serving_forked:family", cat="bench"):
        one = sampled.serve([Request(uid=1, prompt=prompt,
                                     max_new_tokens=max_new)])
        peak_one = one.kv["peak_blocks_used"]
        fam8 = sampled.serve([Request(uid=2, prompt=prompt,
                                      max_new_tokens=max_new,
                                      n=branches)])
        peak_fam = fam8.kv["peak_blocks_used"]
        total_blocks = -(-(prompt_len + max_new) // kv_block)
        family_rec = {
            "branches": branches,
            "kv_block": kv_block,
            "peak_blocks_n1": peak_one,
            "peak_blocks_family": peak_fam,
            "pool_bytes_per_completion": round(
                peak_fam * block_bytes / branches, 1
            ),
            "pool_bytes_per_completion_n1": round(
                peak_one * block_bytes, 1
            ),
            "pool_bytes_ratio": round(peak_fam / max(peak_one, 1), 3),
            "naive_pool_bytes_ratio": float(branches),
            "forks": fam8.kv.get("forks", 0),
            "fork_blocks_shared_total": fam8.kv.get(
                "fork_blocks_shared", 0),
            "fork_share_ratio": round(
                fam8.kv.get("fork_blocks_shared", 0)
                / max(fam8.kv.get("forks", 0) * total_blocks, 1), 4
            ),
        }
        assert family_rec["pool_bytes_ratio"] <= 2.0, (
            f"fork family peaked at {family_rec['pool_bytes_ratio']}x "
            f"the single-request pool bytes (claim: <= 2x at this "
            f"shape; naive is {branches}x)"
        )

    # --- shared-prefix trace TTFT -----------------------------------------
    def trace(n: int) -> List[Request]:
        # Arrivals spaced past a full generation: a family occupies all
        # ``branches`` slots, so back-to-back families would measure
        # slot queueing, not the fork's prefill economics — both arms
        # get the same spacing (the synthetic clock fast-forwards idle
        # gaps, so spacing costs no wall time).
        return synthetic_trace(
            n_requests, prompt_len=prompt_len, max_new_tokens=max_new,
            vocab_size=cfg.vocab_size, seed=seed + 2,
            arrival_every=4 * max_new,
            prefix_share=1.0, prefix_len=prefix_len,
            prefix_seed=seed + 3, n=n,
        )

    def ttft_p50(results) -> float:
        vals = sorted(r.ttft_s for r in results if r.tokens)
        return vals[len(vals) // 2] if vals else 0.0

    with obs.span("bench_serving_forked:trace", cat="bench"):
        best1 = bestn = None
        for _ in range(repeats):
            r1 = sampled.serve(trace(1))
            rn = sampled.serve(trace(branches))
            p1, pn = ttft_p50(r1.results), ttft_p50(rn.results)
            if best1 is None or p1 < best1[0]:
                best1 = (p1, r1)
            if bestn is None or pn < bestn[0]:
                bestn = (pn, rn)
        p1, r1 = best1
        pn, rn = bestn
        ratio = pn / p1 if p1 > 0 else 0.0
        trace_rec = {
            "requests": n_requests,
            "completions_n1": sum(1 for r in r1.results if r.tokens),
            "completions_family": sum(1 for r in rn.results if r.tokens),
            "ttft_p50_n1_s": round(p1, 5),
            "ttft_p50_family_s": round(pn, 5),
            "ttft_p50_ratio": round(ratio, 3),
            "tokens_family": rn.tokens_generated,
        }
        assert ratio <= 1.3, (
            f"family TTFT p50 {ratio:.2f}x the n=1 arm's (claim: the "
            f"prompt prefills once per family, so <= 1.3x)"
        )
        leak = sampled.leak_report()
        assert leak["blocks_shared"] == 0 \
            and leak["blocks_reserved"] == 0, leak

    log.info(
        "forked sampling: n=%d at %.2fx pool bytes of n=1 (naive %dx), "
        "share ratio %.2f, ttft p50 ratio %.2fx",
        branches, family_rec["pool_bytes_ratio"], branches,
        family_rec["fork_share_ratio"], trace_rec["ttft_p50_ratio"],
    )
    return {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "slots": slots,
            "cache_len": cache_len,
            "prompt_len": prompt_len,
            "max_new_tokens": max_new,
            "prefix_len": prefix_len,
            "branches": branches,
        },
        "parity": "token-identical + bit-reproducible",
        "family": family_rec,
        "trace": trace_rec,
    }


def bench_serving_tree_sampling(
    *,
    slots: int = 8,
    branches: int = 8,
    prompt_len: int = 48,
    max_new: int = 5,
    kv_block: int = 16,
    n_requests: int = 4,
    repeats: int = 3,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 13,
) -> Dict[str, Any]:
    """The token-tree sibling decode record (ISSUE 20): n>1 sampling as
    ONE tree-masked row bundle in ONE slot vs the PR-15 fork-slot path,
    at EQUAL pool bytes (identical engine shapes; only ``tree_sampling``
    differs).

    Three measurements, parity first:

    - **Parity** — a seeded temperature-1 ``n = branches`` family on the
      tree arm vs the SAME request on the fork arm, asserted
      token-identical per branch BEFORE any number is reported (both
      paths draw from the same ``fold_in(request_key, branch, index)``
      chain, so this is a pure packing/attention equivalence gate); and
      the tree family served twice, asserted bit-identical.
    - **Family economics** — one ``n = branches`` family's
      ``peak_blocks_used`` tree vs fork (``pool_bytes_ratio`` must be
      <= 1.0: the tree replays suffix rows instead of materializing
      per-branch tail blocks) and the family's slot footprint: ONE slot
      on the tree arm vs ``branches`` on the fork arm, read from the
      burst trace's ``max_concurrent_requests``.
    - **Burst trace** — ``n_requests`` families all queued at start on
      both arms at the same slot count and pool: the fork arm serializes
      (each family takes all ``branches`` slots), the tree arm runs one
      family per slot — ``max_concurrent_improvement``, tokens/sec
      ratio, and per-branch TTFT p50 ratio are the headline.

    Plus the **stochastic-acceptance distribution gate**: spec-on
    temperature-0.8 decode (Leviathan ratio test under deterministic
    stream keys, arXiv:2211.17192) asserted token-identical to the
    non-speculative sampled stream for the same seed — the point-mass
    coupling makes the distribution claim checkable as bit equality —
    and bit-reproducible across serves.

    CPU proxy by design: the slot/pool economics are ledger math and
    transfer exactly; absolute tokens/sec does not.
    """
    cache_len = prompt_len + branches * (max_new - 1) + kv_block
    cfg = cfg or serving_model_config(
        max_seq_len=cache_len, vocab_size=128, d_model=64
    )
    params = init_params(jax.random.PRNGKey(seed), cfg)
    kv_token_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * cfg.d_head
                      * jnp.dtype(cfg.dtype).itemsize)
    block_bytes = kv_block * kv_token_bytes

    def build(tree: bool, **kw) -> SlotServer:
        return SlotServer(
            params, cfg, slots=slots, cache_len=cache_len,
            kv_block=kv_block, temperature=1.0, seed=seed,
            tree_sampling=tree, **kw,
        )

    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg.vocab_size,
                          size=prompt_len).astype(np.int32)

    def fam_req(uid: int) -> Request:
        return Request(uid=uid, prompt=prompt, max_new_tokens=max_new,
                       n=branches, seed=seed + 5)

    # --- parity gates -----------------------------------------------------
    with obs.span("bench_serving_tree:parity", cat="bench"):
        tree_eng = build(True)
        fork_eng = build(False)
        t1 = tree_eng.serve([fam_req(0)])
        assert t1.kv.get("tree_families", 0) == 1, (
            f"PARITY VIOLATION: tree path did not engage: {t1.kv}"
        )
        f1 = fork_eng.serve([fam_req(0)])
        got_t = {r.index: r.tokens for r in t1.results}
        got_f = {r.index: r.tokens for r in f1.results}
        for j in range(branches):
            assert got_t[j] == got_f[j], (
                f"PARITY VIOLATION: tree branch {j} diverged from the "
                f"fork-slot path"
            )
        t2 = tree_eng.serve([fam_req(0)])
        assert {r.index: r.tokens for r in t2.results} == got_t, (
            "PARITY VIOLATION: tree family not reproducible across "
            "serves"
        )
        leak = tree_eng.leak_report()
        assert leak["blocks_used"] == leak["blocks_cached"] \
            and leak["blocks_shared"] == 0 \
            and leak["blocks_reserved"] == 0, leak

    # --- family economics (one request, exact ledger math) ---------------
    with obs.span("bench_serving_tree:family", cat="bench"):
        peak_tree = t1.kv["peak_blocks_used"]
        peak_fork = f1.kv["peak_blocks_used"]
        family_rec = {
            "branches": branches,
            "kv_block": kv_block,
            "peak_blocks_tree": peak_tree,
            "peak_blocks_fork": peak_fork,
            "pool_bytes_tree": peak_tree * block_bytes,
            "pool_bytes_fork": peak_fork * block_bytes,
            "pool_bytes_ratio": round(peak_tree / max(peak_fork, 1), 3),
        }
        assert family_rec["pool_bytes_ratio"] <= 1.0, (
            f"tree family peaked at {family_rec['pool_bytes_ratio']}x "
            f"the fork-slot pool bytes (claim: the shared-ancestor "
            f"bundle never exceeds per-branch CoW tails)"
        )

    # --- burst trace: capacity + throughput at equal pool bytes ----------
    def burst() -> List[Request]:
        return [
            Request(uid=10 + j, prompt=prompt, max_new_tokens=max_new,
                    n=branches, seed=seed + 6 + j)
            for j in range(n_requests)
        ]

    def run_arm(server: SlotServer) -> Dict[str, Any]:
        server.serve(burst())  # compile + warm
        runs = []
        for _ in range(repeats):
            report = server.serve(burst())
            d = report.as_dict()
            d["max_concurrent_requests"] = _max_concurrent(report)
            ttfts = sorted(r.ttft_s for r in report.results if r.tokens)
            d["branch_ttft_p50_s"] = (
                ttfts[len(ttfts) // 2] if ttfts else 0.0
            )
            runs.append(d)
        return {
            "tokens_per_sec": max(r["tokens_per_sec"] for r in runs),
            "branch_ttft_p50_s": min(
                r["branch_ttft_p50_s"] for r in runs
            ),
            "max_concurrent_requests": max(
                r["max_concurrent_requests"] for r in runs
            ),
        }

    with obs.span("bench_serving_tree:trace", cat="bench"):
        trace_rec = {
            "families": n_requests,
            "tree": run_arm(tree_eng),
            "fork": run_arm(fork_eng),
        }
        cc_fork = trace_rec["fork"]["max_concurrent_requests"]
        trace_rec["max_concurrent_improvement"] = round(
            trace_rec["tree"]["max_concurrent_requests"]
            / max(cc_fork, 1), 2
        )
        tps_fork = trace_rec["fork"]["tokens_per_sec"]
        if tps_fork > 0:
            trace_rec["tokens_per_sec_ratio"] = round(
                trace_rec["tree"]["tokens_per_sec"] / tps_fork, 3
            )
        p50_fork = trace_rec["fork"]["branch_ttft_p50_s"]
        if p50_fork > 0:
            trace_rec["ttft_p50_ratio"] = round(
                trace_rec["tree"]["branch_ttft_p50_s"] / p50_fork, 3
            )
        assert trace_rec["max_concurrent_improvement"] >= 1.0, (
            "tree families should never be LESS concurrent than "
            "fork-slot families at equal pool bytes"
        )

    # --- stochastic acceptance: the distribution gate ---------------------
    with obs.span("bench_serving_tree:stochastic", cat="bench"):
        from tree_attention_tpu.serving.speculation import (
            DraftModelDrafter,
        )

        # The model drafts for itself: proposals are guaranteed every
        # tick, so the ratio test actually runs (prompt-lookup only
        # fires when a sampled stream happens to loop).
        rep_prompt = np.tile(np.array([5, 6, 7, 8], np.int32), 4)
        spec = SlotServer(
            params, cfg, slots=2, cache_len=cache_len,
            kv_block=kv_block, speculate=True, draft_k=3, seed=seed,
            drafter=DraftModelDrafter(params, cfg),
        )
        plain = SlotServer(
            params, cfg, slots=2, cache_len=cache_len,
            kv_block=kv_block, seed=seed,
        )
        sreq = [Request(uid=0, prompt=rep_prompt, max_new_tokens=8,
                        temperature=0.8, seed=seed + 9)]
        s1 = spec.serve(sreq)
        p1 = plain.serve(sreq)
        assert s1.spec["proposed"] > 0, s1.spec
        assert s1.results[0].tokens == p1.results[0].tokens, (
            "DISTRIBUTION VIOLATION: spec-on temperature-0.8 stream "
            "diverged from the non-speculative sampled stream (the "
            "point-mass coupling must make them bit-equal)"
        )
        s2 = spec.serve(sreq)
        assert s2.results[0].tokens == s1.results[0].tokens, (
            "spec-on sampled stream not reproducible across serves"
        )
        stochastic_rec = {
            "temperature": 0.8,
            "proposed": s1.spec["proposed"],
            "accepted": s1.spec["accepted"],
            "acceptance_rate": s1.spec["acceptance_rate"],
            "distribution_gate": "bit-equal to non-spec sampled stream",
        }

    log.info(
        "tree sampling: n=%d in ONE slot at %.2fx fork pool bytes, "
        "max concurrent %.1fx, branch ttft p50 ratio %s, spec-on "
        "accept rate %.2f",
        branches, family_rec["pool_bytes_ratio"],
        trace_rec["max_concurrent_improvement"],
        trace_rec.get("ttft_p50_ratio"),
        stochastic_rec["acceptance_rate"],
    )
    return {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "slots": slots,
            "cache_len": cache_len,
            "prompt_len": prompt_len,
            "max_new_tokens": max_new,
            "branches": branches,
        },
        "parity": "token-identical to fork slots + bit-reproducible",
        "family": family_rec,
        "trace": trace_rec,
        "stochastic": stochastic_rec,
    }


# ---------------------------------------------------------------------------
# ISSUE 10: trace replay + chaos harness against the live HTTP ingress
# ---------------------------------------------------------------------------


def heavy_tail_trace(
    n_requests: int,
    *,
    cache_len: int,
    mean_gap_s: float = 0.02,
    prompt_base: int = 6,
    new_base: int = 3,
    tail_scale: float = 8.0,
    vocab_size: int = 128,
    seed: int = 0,
    tenants: int = 0,
    tenant_prefix_len: int = 0,
    tenant_zipf: float = 1.2,
    prefix_seed: Optional[int] = None,
    n: int = 1,
    best_of: int = 0,
    fork_at: int = 0,
) -> List[Dict[str, Any]]:
    """A production-shaped replay trace: timestamped request events with
    exponential inter-arrivals and heavy-tail (Pareto) prompt/output
    lengths — most requests are short, a few are 5-10x longer, which is
    the mixture that makes admission policy matter (a Poisson flood of
    identical requests flatters every scheduler). Lengths are clamped so
    ``prompt + max_tokens`` always fits a ``cache_len`` slot. Events are
    plain dicts (``t_s``, ``prompt``, ``max_tokens``) so they serialize
    to the JSONL trace files ``save_trace``/``load_trace`` round-trip.

    **Multi-tenant shared-prefix mixture (ISSUE 11):** with
    ``tenants > 0`` and ``tenant_prefix_len > 0``, each request draws a
    tenant from a bounded Zipf distribution (rank-k probability
    proportional to ``(k+1)^-tenant_zipf`` — a few tenants dominate, a
    long tail trickles, the skew production multi-tenancy shows) and
    prepends that tenant's fixed prefix (its "system prompt") to its
    heavy-tail random suffix. This is the workload affinity routing
    exists for: the same tenant's requests share a long prefix, and a
    router that scatters them round-robin pays the prefill N times.
    ``prefix_seed`` draws the tenant prefix *populations* from their own
    rng stream, so two arms with the same ``seed`` (identical arrivals,
    lengths, suffix randomness) can still use disjoint prefix
    populations — per-arm cold caches without rebuilding engines.
    Events carry ``tenant`` for analysis.

    **Fork-family fields (ISSUE 15):** ``n > 1`` stamps every event an
    n-completion family (copy-on-write siblings server-side),
    ``best_of > 1`` a server-side-selected one, and ``fork_at > 0`` a
    mid-generation self-fork after that many emitted tokens — so fork
    workloads replay through the same HTTP chaos harness
    (:func:`replay_trace_http` forwards the fields on the body).
    """
    rng = np.random.default_rng(seed)
    shared: List[np.ndarray] = []
    zipf_p = None
    if tenants > 0 and tenant_prefix_len > 0:
        prefix_rng = rng if prefix_seed is None else \
            np.random.default_rng(prefix_seed)
        shared = [
            prefix_rng.integers(0, vocab_size, size=tenant_prefix_len)
            .astype(np.int32)
            for _ in range(tenants)
        ]
        zipf_p = np.array([(k + 1.0) ** -tenant_zipf
                           for k in range(tenants)])
        zipf_p /= zipf_p.sum()
    head = tenant_prefix_len if shared else 0
    cap = cache_len - head - prompt_base - new_base
    if cap < 0:
        raise ValueError(
            f"cache_len {cache_len} cannot fit tenant_prefix_len {head} "
            f"plus prompt_base {prompt_base} + new_base {new_base}"
        )
    events = []
    t = 0.0
    for i in range(n_requests):
        t += float(rng.exponential(mean_gap_s))
        plen = prompt_base + int(min(rng.pareto(1.5) * tail_scale,
                                     max(cap // 2, 0)))
        new = new_base + int(min(rng.pareto(1.5) * tail_scale,
                                 cache_len - head - plen - new_base))
        suffix = rng.integers(0, vocab_size, size=plen).astype(np.int32)
        ev = {
            "t_s": round(t, 6),
            "max_tokens": int(new),
        }
        if n > 1:
            ev["n"] = int(n)
        if best_of > 1:
            ev["best_of"] = int(best_of)
        if fork_at > 0:
            ev["fork_at"] = int(fork_at)
        if shared:
            tenant = int(rng.choice(tenants, p=zipf_p))
            ev["tenant"] = tenant
            ev["prompt"] = np.concatenate(
                [shared[tenant], suffix]).tolist()
        else:
            ev["prompt"] = suffix.tolist()
        events.append(ev)
    return events


def save_trace(path: str, events: List[Dict[str, Any]]) -> None:
    """One JSON event per line — the timestamped request-trace file
    format ``bench_serving_ingress`` replays."""
    import json

    with open(path, "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")


def load_trace(path: str) -> List[Dict[str, Any]]:
    import json

    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def _iter_sse(resp):
    """Yield the payload of each ``data:`` event until EOF/[DONE]."""
    while True:
        line = resp.readline()
        if not line:
            return
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        payload = line[6:]
        if payload == b"[DONE]":
            return
        yield payload


def _replay_client(port: int, event: Dict[str, Any], start_t: float,
                   out: Dict[str, Any], chaos: Optional[Dict[str, Any]],
                   timeout_s: float) -> None:
    """One chaos-capable HTTP client: waits for its timestamp, POSTs,
    reads the SSE stream; optionally vanishes mid-stream ('disconnect'
    after k tokens — the socket closes abruptly, no goodbye) or reads
    slowly ('slow' — sleeps between events, exercising the handler-
    thread/OS-buffer backpressure isolation)."""
    import http.client
    import json as _json
    import time as _time

    _time.sleep(max(start_t + event["t_s"] - _time.monotonic(), 0.0))
    body = {"prompt": event["prompt"], "max_tokens": event["max_tokens"],
            "stream": True}
    if event.get("deadline_s") is not None:
        body["deadline_s"] = event["deadline_s"]
    if event.get("eos_id") is not None:
        body["eos_id"] = event["eos_id"]
    # Fork-family / sampling fields (ISSUE 15) replay verbatim.
    for key in ("n", "best_of", "fork_at", "temperature", "top_k", "seed"):
        if event.get(key) is not None:
            body[key] = event[key]
    t0 = _time.monotonic()
    out["submitted_s"] = t0 - start_t
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        attempts = 0
        while True:
            if event.get("deadline_s") is not None:
                # A deadline-aware client keeps retrying while its OWN
                # deadline still has air, and tells the server only the
                # time actually remaining (a retry must not reset the
                # server-side window past the client's truth).
                remaining = event["deadline_s"] - (_time.monotonic() - t0)
                if attempts and remaining <= 0:
                    return  # past its own deadline: a miss either way
                body["deadline_s"] = max(remaining, 1e-3)
            conn.request("POST", "/v1/completions", _json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out["status"] = resp.status
            out["retry_after"] = resp.getheader("Retry-After")
            if resp.status != 429 or not event.get("retry_429"):
                break
            # Honor the backpressure contract: back off as told (capped
            # so a CPU-proxy bench is not pacing itself in wall-minutes),
            # then resubmit — the client half of 429 + Retry-After.
            resp.read()
            attempts += 1
            out["retries"] = attempts
            if attempts >= 50 or event.get("deadline_s") is None:
                return  # deadline-less clients give up fast
            _time.sleep(min(float(out["retry_after"] or 1), 0.25))
        if resp.status != 200:
            resp.read()
            return
        n_seen = 0
        for payload in _iter_sse(resp):
            ch = _json.loads(payload)["choices"][0]
            if ch["token_ids"]:
                if out["ttft_s"] is None:
                    out["ttft_s"] = _time.monotonic() - t0
                out["tokens"].extend(ch["token_ids"])
                n_seen += 1
                if (chaos is not None and chaos["kind"] == "disconnect"
                        and n_seen >= chaos["after_tokens"]):
                    out["disconnected"] = True
                    resp.close()  # vanish abruptly, mid-stream
                    return
                if chaos is not None and chaos["kind"] == "slow":
                    _time.sleep(chaos["delay_s"])
            if ch["finish_reason"] is not None:
                out["finish_reason"] = ch["finish_reason"]
        out["done_s"] = _time.monotonic() - t0
    except (OSError, http.client.HTTPException) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def replay_trace_http(
    port: int,
    events: List[Dict[str, Any]],
    *,
    chaos: Optional[Dict[int, Dict[str, Any]]] = None,
    timeout_s: float = 300.0,
) -> List[Dict[str, Any]]:
    """Replay a timestamped trace against a live ingress over loopback:
    one thread per client, each firing at its event's ``t_s``. ``chaos``
    maps event index -> behavior dict (``{"kind": "disconnect",
    "after_tokens": k}`` / ``{"kind": "slow", "delay_s": d}``). Returns
    one result dict per event (status, tokens, finish_reason, ttft_s,
    done_s, disconnected)."""
    import threading
    import time as _time

    results = [
        {"i": i, "status": None, "tokens": [], "finish_reason": None,
         "ttft_s": None, "done_s": None, "disconnected": False}
        for i in range(len(events))
    ]
    start_t = _time.monotonic() + 0.05
    threads = [
        threading.Thread(
            target=_replay_client,
            args=(port, e, start_t, results[i],
                  (chaos or {}).get(i), timeout_s),
            daemon=True,
        )
        for i, e in enumerate(events)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout_s)
    return results


def _wait_engine_settled(engine, timeout_s: float = 30.0) -> Dict[str, int]:
    """Poll until every slot is free and no per-request resource is held
    (the control sweep needs a tick or two after the last client went
    away); returns the final leak report either way."""
    import time as _time

    t0 = _time.monotonic()
    while _time.monotonic() - t0 < timeout_s:
        lr = engine.leak_report()
        if (engine.all_slots_free and lr["blocks_private"] == 0
                and lr["blocks_reserved"] == 0 and lr["pins"] == 0):
            return lr
        _time.sleep(0.05)
    return engine.leak_report()


def bench_serving_ingress(
    *,
    slots: int = 2,
    cache_len: int = 96,
    n_requests: int = 16,
    disconnect_share: float = 0.3,
    slow_share: float = 0.2,
    n_overload: int = 32,
    interactive_share: float = 0.5,
    mean_gap_s: float = 0.02,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
    trace_path: Optional[str] = None,
) -> Dict[str, Any]:
    """The chaos record (ISSUE 10): a live loopback ingress under
    disconnect storms, slow readers, and deadline-heavy overload.

    Three arms against ONE warmed engine (jits paid once):

    - **baseline** — replay a heavy-tail timestamped trace clean; the
      per-request token streams are the parity reference.
    - **disconnect storm** — the same trace with ``disconnect_share`` of
      clients vanishing mid-stream (abrupt socket close) and
      ``slow_share`` reading slowly. Claims measured, not asserted-by-
      vibes: survivors' streams are token-for-token identical to the
      baseline (greedy decode per slot is independent of batch
      composition — chaos must not change anyone else's answer), and
      after the storm settles the allocator holds zero slot-private
      blocks, zero reservations, zero radix pins (cancellation leaks
      nothing).
    - **overload, shedding on vs off** — a deadline-heavy burst
      (interactive requests with tight deadlines mixed into batch
      requests with loose ones) at ~2x capacity. 'on' enforces the
      deadlines server-side (expired-in-queue rejected, expired-in-
      flight retired) + bounds the admission queue; 'off' ignores them
      (the FIFO-to-the-death baseline). Goodput-under-SLO — the
      fraction of ALL issued requests finishing within their own
      deadline, measured client-side — must be strictly better with
      shedding on: doomed work shed early is capacity the still-
      servable requests get.

    Deadlines are calibrated from the baseline arm's measured service
    rate, so the record transfers across box speeds (the structure is
    the claim; absolute seconds are not)."""
    import json as _json
    import tempfile

    from tree_attention_tpu.serving import SlotServer
    from tree_attention_tpu.serving.ingress import IngressServer

    cfg = cfg or serving_model_config(
        max_seq_len=cache_len, vocab_size=128, d_model=64
    )
    params = init_params(jax.random.PRNGKey(seed), cfg)
    engine = SlotServer(
        params, cfg, slots=slots, cache_len=cache_len,
        prefill_chunk=16, prefix_cache=True, prefix_block=16,
    )
    ingress = IngressServer(engine, max_queue=max(n_overload, n_requests),
                            default_max_tokens=8, keepalive_s=0.1)
    port = ingress.start()
    rng = np.random.default_rng(seed + 7)

    trace = heavy_tail_trace(
        n_requests, cache_len=cache_len, mean_gap_s=mean_gap_s,
        vocab_size=cfg.vocab_size, seed=seed + 1,
    )
    if trace_path is None:
        fd, trace_path = tempfile.mkstemp(suffix=".jsonl",
                                          prefix="ingress_trace_")
        import os as _os

        _os.close(fd)  # save_trace reopens by path; the file is the
        # record's replayable artifact, left in place deliberately
    # The file format is part of the record: replay what was LOADED.
    save_trace(trace_path, trace)
    trace = load_trace(trace_path)

    rec: Dict[str, Any] = {"workload": {
        "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                  "vocab": cfg.vocab_size},
        "slots": slots, "cache_len": cache_len,
        "n_requests": n_requests, "disconnect_share": disconnect_share,
        "slow_share": slow_share, "n_overload": n_overload,
        "trace_file": trace_path,
    }}

    with obs.span("bench_serving_ingress:baseline", cat="bench"):
        # Warmup: pays every jit compile inside one request's stream.
        replay_trace_http(port, trace[:2])
        _wait_engine_settled(engine)
        t0 = _time_mono()
        base = replay_trace_http(port, trace)
        base_wall = _time_mono() - t0
    served = [r for r in base if r["finish_reason"] in ("stop", "length")]
    rec["baseline"] = {
        "served": len(served),
        "wall_s": round(base_wall, 3),
        "tokens_total": sum(len(r["tokens"]) for r in base),
        "ttft_p50_s": round(sorted(
            r["ttft_s"] for r in base if r["ttft_s"] is not None
        )[len(served) // 2], 4) if served else None,
    }

    # --- disconnect storm + slow readers ---
    idx = rng.permutation(n_requests)
    n_disc = max(int(n_requests * disconnect_share), 1)
    n_slow = max(int(n_requests * slow_share), 1)
    chaos: Dict[int, Dict[str, Any]] = {}
    for i in idx[:n_disc]:
        chaos[int(i)] = {"kind": "disconnect",
                         "after_tokens": int(rng.integers(1, 3))}
    for i in idx[n_disc:n_disc + n_slow]:
        chaos[int(i)] = {"kind": "slow", "delay_s": 0.05}
    with obs.span("bench_serving_ingress:storm", cat="bench"):
        storm = replay_trace_http(port, trace, chaos=chaos)
        leak = _wait_engine_settled(engine)
    survivors = [i for i in range(n_requests) if i not in chaos
                 or chaos[i]["kind"] == "slow"]
    mismatched = [
        i for i in survivors
        if storm[i]["tokens"] != base[i]["tokens"]
    ]
    pool_clean = (leak["blocks_private"] == 0
                  and leak["blocks_reserved"] == 0 and leak["pins"] == 0
                  and leak["blocks_used"] == leak["blocks_cached"])
    rec["disconnect_storm"] = {
        "disconnected": sum(1 for r in storm if r["disconnected"]),
        "slow_readers": n_slow,
        "survivors": len(survivors),
        "survivor_streams_identical": not mismatched,
        "mismatched": mismatched,
        "pool_clean_after_storm": pool_clean,
        "leak_report": leak,
    }
    assert not mismatched, (
        f"CHAOS PARITY VIOLATION: disconnect storm changed surviving "
        f"streams {mismatched}"
    )
    assert pool_clean, f"RESOURCE LEAK after disconnect storm: {leak}"

    # --- deadline-heavy overload: shedding+backpressure on vs off ---
    # The trace is a near-simultaneous burst of LONG requests (several
    # times the engine's capacity), half "interactive" with tight
    # deadlines, half "batch" with loose ones. Deadlines are calibrated
    # from a measured dry run of this exact trace (no deadlines, FIFO to
    # completion): interactive at ~12% of the measured makespan — deep
    # inside the burst nothing can meet it — and batch at ~70%. Without
    # shedding the engine spends capacity finishing doomed interactive
    # work, pushing the FIFO tail of the batch class past ITS deadline;
    # with shedding (server-side deadlines + a bounded queue whose 429s
    # the clients honor with Retry-After retries) the doomed work dies
    # cheaply in queue and the batch class fits. Goodput-under-SLO is
    # measured client-side over ALL issued requests.
    over = heavy_tail_trace(
        n_overload, cache_len=cache_len, mean_gap_s=0.002,
        new_base=24, tail_scale=8.0,
        vocab_size=cfg.vocab_size, seed=seed + 2,
    )
    with obs.span("bench_serving_ingress:overload_calib", cat="bench"):
        ingress.max_queue = n_overload + 2
        calib = replay_trace_http(port, [dict(e) for e in over])
        _wait_engine_settled(engine)
    sub0 = min(r["submitted_s"] for r in calib)
    makespan = max(
        r["submitted_s"] + (r["done_s"] or 0.0) for r in calib
    ) - sub0
    int_deadline = max(0.12 * makespan, 0.1)
    batch_deadline = 0.70 * makespan
    for i, e in enumerate(over):
        e["deadline_s"] = int_deadline if i % 2 == 0 else batch_deadline

    def run_overload(shed: bool) -> Dict[str, Any]:
        evs = [dict(e) for e in over]
        for e in evs:
            if not shed:
                del e["deadline_s"]  # server never learns the deadline
            else:
                e["retry_429"] = True  # clients honor Retry-After
        ingress.max_queue = (max(slots * 4, 8) if shed
                             else n_overload + 2)
        res = replay_trace_http(port, evs)
        _wait_engine_settled(engine)
        met = 0
        for i, r in enumerate(res):
            dl = over[i]["deadline_s"]
            ok = (r["finish_reason"] in ("stop", "length")
                  and r["done_s"] is not None and r["done_s"] <= dl)
            met += ok
        return {
            "goodput_under_slo": round(met / n_overload, 4),
            "met": met,
            "rejected_429": sum(1 for r in res if r["status"] == 429),
            "shed_or_expired": sum(
                1 for r in res
                if r["finish_reason"] in ("deadline", "shed")
            ),
        }

    with obs.span("bench_serving_ingress:overload", cat="bench"):
        off = run_overload(shed=False)
        on = run_overload(shed=True)
    rec["overload"] = {
        "makespan_calib_s": round(makespan, 3),
        "interactive_deadline_s": round(int_deadline, 3),
        "batch_deadline_s": round(batch_deadline, 3),
        "shedding_off": off,
        "shedding_on": on,
        "goodput_improvement": round(
            on["goodput_under_slo"] / off["goodput_under_slo"], 3
        ) if off["goodput_under_slo"] else None,
    }
    # The ISSUE 10 acceptance criterion, asserted live like the storm's
    # parity/cleanliness claims: shedding+backpressure must make
    # goodput-under-SLO STRICTLY better, not just be recorded.
    assert on["goodput_under_slo"] > off["goodput_under_slo"], (
        f"SHEDDING REGRESSION: goodput-under-SLO on="
        f"{on['goodput_under_slo']} <= off={off['goodput_under_slo']}"
    )

    # --- backpressure probe: the 429 + Retry-After contract ---
    ingress.max_queue = 1
    with obs.span("bench_serving_ingress:backpressure", cat="bench"):
        burst = replay_trace_http(port, [
            dict(e, t_s=0.0) for e in trace[:6]
        ])
    n429 = [r for r in burst if r["status"] == 429]
    rec["backpressure"] = {
        "burst": len(burst),
        "rejected_429": len(n429),
        "retry_after_present": all(
            r["retry_after"] is not None and int(r["retry_after"]) >= 1
            for r in n429
        ),
    }
    _wait_engine_settled(engine)

    # --- graceful drain: stop admitting, finish in-flight ---
    ingress.drain()
    report = ingress.join(timeout=60.0)
    ingress.stop()
    rec["drain"] = {
        "engine_drained": report is not None,
        "outcomes": report.outcomes if report is not None else {},
        "final_leak": engine.leak_report(),
    }

    log.info(
        "ingress chaos: %(d)d disconnects leak-free, survivor parity OK; "
        "goodput %(off).2f off -> %(on).2f on; %(r)d/%(b)d 429s",
        dict(d=rec["disconnect_storm"]["disconnected"],
             off=off["goodput_under_slo"], on=on["goodput_under_slo"],
             r=len(n429), b=len(burst)),
    )
    return rec


def bench_serving_fleet(
    *,
    replicas: int = 4,
    slots: int = 2,
    cache_len: int = 96,
    n_requests: int = 40,
    n_parity: int = 6,
    tenants: int = 6,
    tenant_prefix_len: int = 48,
    tenant_zipf: float = 1.2,
    mean_gap_s: float = 0.01,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The fleet record (ISSUE 11): N replica engines behind the
    cache-aware router, affinity vs round-robin at EQUAL total
    slots/pool bytes (both arms run the SAME fleet — only the routing
    policy flips).

    Four claims, measured live over loopback:

    - **parity** — streams routed through the router are token-for-token
      identical to direct single-replica serving (the pass-through
      guarantee).
    - **affinity preserves the prefix win** — on a multi-tenant
      shared-prefix heavy-tail trace (Zipf tenant skew), affinity
      routing shows strictly better TTFT p50 AND strictly higher
      prefix tokens-reused ratio than round-robin over the same
      replicas: round-robin scatters each tenant's prefix across N
      trees and pays the prefill ~N times; affinity concentrates it.
      Each arm draws its own tenant prefix *population*
      (``prefix_seed``), so both start with cold caches for their own
      prefixes without rebuilding engines.
    - **rolling restart without drops** — a full rolling restart runs
      DURING a replay; every accepted request still finishes (drained
      replicas' queued work requeues onto peers), and each drained
      replica's allocator reads 0 private blocks / 0 reservations /
      0 pins at the drain point.

    Deadlines are calibrated from the parity arm's measured completion
    times (the chaos-bench lesson: absolute seconds do not transfer
    across boxes) at 10x p95 — loose enough never to bind, present so
    the fleet path carries real deadline budgets through failover.
    """
    import threading as _threading

    from tree_attention_tpu.serving import Request as _Request
    from tree_attention_tpu.serving.fleet import (
        FleetSupervisor, LocalReplica,
    )
    from tree_attention_tpu.serving.router import FleetRouter

    block = 16
    cfg = cfg or serving_model_config(
        max_seq_len=cache_len, vocab_size=128, d_model=64
    )
    params = init_params(jax.random.PRNGKey(seed), cfg)
    kv_blocks = slots * (-(-cache_len // block)) + 24  # slot worst case
    # plus prefix retention — the per-replica pool every arm shares

    def make_engine():
        return SlotServer(
            params, cfg, slots=slots, cache_len=cache_len,
            prefill_chunk=block, prefix_cache=True, prefix_block=block,
            kv_blocks=kv_blocks,
        )

    reps = [LocalReplica(f"r{i}", make_engine, max_queue=n_requests + 8,
                         default_max_tokens=8, keepalive_s=0.1)
            for i in range(replicas)]
    router = FleetRouter(block=block, affinity=True, hysteresis=2)
    sup = FleetSupervisor(reps, router=router, monitor_interval_s=0)

    def mt_trace(n, prefix_seed, gap=mean_gap_s):
        return heavy_tail_trace(
            n, cache_len=cache_len, mean_gap_s=gap,
            vocab_size=cfg.vocab_size, seed=seed + 2,
            tenants=tenants, tenant_prefix_len=tenant_prefix_len,
            tenant_zipf=tenant_zipf, prefix_seed=prefix_seed,
        )

    # --- parity: direct reference BEFORE the fleet starts (replica 0's
    # engine, same instance the fleet then reuses — no extra compiles).
    parity_trace = mt_trace(n_parity, seed + 101, gap=0.0)
    ref_engine = reps[0].engine
    with obs.span("bench_serving_fleet:reference", cat="bench"):
        ref_report = ref_engine.serve([
            _Request(uid=i, prompt=np.asarray(e["prompt"], np.int32),
                     max_new_tokens=e["max_tokens"])
            for i, e in enumerate(parity_trace)
        ])
    ref_streams = {r.uid: list(r.tokens) for r in ref_report.results}
    completions = sorted(r.completion_s for r in ref_report.results)
    deadline = max(10.0 * completions[-1], 2.0)

    port = sup.start()
    rec: Dict[str, Any] = {"workload": {
        "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                  "vocab": cfg.vocab_size},
        "replicas": replicas, "slots_per_replica": slots,
        "cache_len": cache_len, "kv_blocks_per_replica": kv_blocks,
        "n_requests": n_requests, "tenants": tenants,
        "tenant_prefix_len": tenant_prefix_len,
        "deadline_calib_s": round(deadline, 3),
    }}

    engines = sup.engines

    def settle_all():
        for eng in engines:
            _wait_engine_settled(eng)

    with obs.span("bench_serving_fleet:parity", cat="bench"):
        routed = replay_trace_http(port, parity_trace)
        settle_all()
    mismatched = [i for i, r in enumerate(routed)
                  if r["tokens"] != ref_streams[i]]
    rec["parity"] = {"requests": n_parity,
                     "identical": not mismatched,
                     "mismatched": mismatched}
    assert not mismatched, (
        f"FLEET PARITY VIOLATION: routed streams differ from direct "
        f"serving at indices {mismatched}"
    )

    # --- affinity vs round-robin, equal fleet, per-arm prefix population.
    def run_arm(affinity: bool, prefix_seed: int) -> Dict[str, Any]:
        trace = mt_trace(n_requests, prefix_seed)
        for e in trace:
            e["deadline_s"] = deadline
        router.affinity = affinity
        before = [eng.prefix_stats().get("tokens_reused", 0)
                  for eng in engines]
        routed0 = dict(router.stats()["routed"])
        res = replay_trace_http(port, trace)
        settle_all()
        reused = sum(
            eng.prefix_stats().get("tokens_reused", 0) - b
            for eng, b in zip(engines, before)
        )
        routed1 = router.stats()["routed"]
        prompt_tokens = sum(len(e["prompt"]) for e in trace)
        ttfts = sorted(r["ttft_s"] for r in res
                       if r["ttft_s"] is not None)
        served = sum(1 for r in res
                     if r["finish_reason"] in ("stop", "length"))
        assert served == n_requests, (
            f"arm affinity={affinity}: only {served}/{n_requests} served"
        )
        return {
            "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4),
            "ttft_p95_s": round(
                ttfts[min(int(len(ttfts) * 0.95), len(ttfts) - 1)], 4),
            "reused_ratio": round(reused / prompt_tokens, 4),
            "tokens_total": sum(len(r["tokens"]) for r in res),
            "served": served,
            **{f"routed_{k}": routed1[k] - routed0.get(k, 0)
               for k in routed1},
        }

    with obs.span("bench_serving_fleet:round_robin", cat="bench"):
        rr = run_arm(affinity=False, prefix_seed=seed + 202)
    with obs.span("bench_serving_fleet:affinity", cat="bench"):
        aff = run_arm(affinity=True, prefix_seed=seed + 303)
    rec["round_robin"] = rr
    rec["affinity"] = aff
    routed_total = sum(v for k, v in aff.items()
                       if k.startswith("routed_"))
    rec["fleet_affinity_gain"] = {
        "ttft_improvement": round(rr["ttft_p50_s"] / aff["ttft_p50_s"], 3)
        if aff["ttft_p50_s"] else None,
        "reused_ratio_improvement": round(
            aff["reused_ratio"] / rr["reused_ratio"], 3
        ) if rr["reused_ratio"] else None,
        "affinity_share": round(
            aff["routed_affinity"] / routed_total, 4
        ) if routed_total else 0.0,
    }
    # The acceptance criteria, asserted live like every serving record's
    # claims: affinity must PRESERVE the prefix win, not dilute it.
    assert aff["ttft_p50_s"] < rr["ttft_p50_s"], (
        f"AFFINITY REGRESSION: ttft p50 affinity={aff['ttft_p50_s']} >= "
        f"round_robin={rr['ttft_p50_s']}"
    )
    assert aff["reused_ratio"] > rr["reused_ratio"], (
        f"AFFINITY REGRESSION: reused_ratio affinity="
        f"{aff['reused_ratio']} <= round_robin={rr['reused_ratio']}"
    )

    # --- rolling restart DURING a replay: zero dropped accepted work.
    roll_trace = mt_trace(n_requests, seed + 404)
    for e in roll_trace:
        e["deadline_s"] = deadline
    roll_out: Dict[str, Any] = {}

    def do_roll():
        import time as _time

        _time.sleep(0.2)  # let the replay get some work in flight
        roll_out.update(sup.rolling_restart())

    roller = _threading.Thread(target=do_roll, daemon=True)
    with obs.span("bench_serving_fleet:rolling_restart", cat="bench"):
        roller.start()
        res = replay_trace_http(port, roll_trace)
        roller.join(timeout=120.0)
        settle_all()
    accepted = [r for r in res if r["status"] == 200]
    dropped = [r["i"] for r in accepted
               if r["finish_reason"] not in ("stop", "length")]
    leaks_clean = all(
        lk.get("leak") is not None  # a drain-timeout skip is NOT clean
        and lk["leak"]["blocks_private"] == 0
        and lk["leak"]["blocks_reserved"] == 0
        and lk["leak"]["pins"] == 0
        for lk in roll_out.values()
    ) if roll_out else False
    stats = router.stats()
    rec["rolling_restart"] = {
        "accepted": len(accepted),
        "dropped_total": len(dropped),
        "dropped": dropped,
        "requeued": stats["requeued"],
        "router_dropped_total": stats["dropped"],
        "replicas_rolled": len(roll_out),
        "drained_leak_free": leaks_clean,
    }
    assert len(accepted) == n_requests, (
        f"ROLLING RESTART: only {len(accepted)}/{n_requests} accepted "
        f"(statuses {[r['status'] for r in res]})"
    )
    assert not dropped, (
        f"ROLLING RESTART DROPPED accepted request(s) {dropped}"
    )
    assert len(roll_out) == replicas and leaks_clean, (
        f"ROLLING RESTART: drained replicas not leak-free: {roll_out}"
    )

    sup.stop()
    log.info(
        "fleet bench: parity OK; affinity ttft p50 %.4fs vs rr %.4fs "
        "(%.2fx), reused %.3f vs %.3f; rolling restart served %d/%d "
        "with %d requeue(s)",
        aff["ttft_p50_s"], rr["ttft_p50_s"],
        rec["fleet_affinity_gain"]["ttft_improvement"] or 0.0,
        aff["reused_ratio"], rr["reused_ratio"],
        len(accepted) - len(dropped), n_requests, stats["requeued"],
    )
    return rec


def _time_mono() -> float:
    import time as _time

    return _time.monotonic()


# ---------------------------------------------------------------------------
# ISSUE 12: disaggregated prefill/decode — interference under prefill flood
# ---------------------------------------------------------------------------


def _disagg_trace(
    *,
    residents: int,
    resident_prompt: int,
    resident_new: int,
    waves: int,
    wave_prompt_len: int,
    wave_new: int,
    wave_start: int,
    wave_gap: int,
    vocab_size: int,
    seed: int,
) -> List[Request]:
    """``residents`` short-prompt long-output requests queued at start
    (the steady decode population whose inter-token gaps are the
    measurement) plus ``waves`` long-prompt prefill-heavy arrivals every
    ``wave_gap`` ticks — the admission-storm shape disaggregation exists
    for. Wave requests take ``wave_new`` tokens (1 = pure prefill: they
    retire on their prefill-sampled first token and contribute nothing
    to the pooled TBT list, so ``report.tbt_s`` is the residents'
    gaps)."""
    rng = np.random.default_rng(seed)
    reqs = [
        Request(
            uid=i,
            prompt=rng.integers(0, vocab_size,
                                size=resident_prompt).astype(np.int32),
            max_new_tokens=resident_new,
            arrival_tick=0,
        )
        for i in range(residents)
    ]
    for w in range(waves):
        reqs.append(Request(
            uid=residents + w,
            prompt=rng.integers(0, vocab_size,
                                size=wave_prompt_len).astype(np.int32),
            max_new_tokens=wave_new,
            arrival_tick=wave_start + w * wave_gap,
        ))
    return reqs


def bench_serving_disagg(
    *,
    residents: int = 3,
    prefill_slots: int = 1,
    cache_len: int = 512,
    resident_prompt: int = 16,
    resident_new: int = 240,
    wave_prompt_len: int = 128,
    base_waves: int = 2,
    base_gap: int = 100,
    wave_start: int = 20,
    prefill_chunk: int = 64,
    repeats: int = 2,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The disaggregation record (ISSUE 12): decode TBT p99 under a
    prefill flood, fused engine vs split-phase pools, at equal total
    slots and equal pool bytes.

    Three load points per arm — unloaded (no arrivals), base (``base_waves``
    prefill-only prompts every ``base_gap`` ticks), and double (2x the
    waves at half the gap: the arrival rate doubles). The headline is each
    arm's ``interference_ratio`` = TBT p99 at double load over TBT p99
    unloaded:

    - **fused**: prefill chunks ride the decode program (Sarathi), so a
      storm turns decode gaps into mixed-tick gaps — the ratio grows with
      load;
    - **disagg**: decode-pool ticks are Tq=1 by construction; the ratio
      should hold ~1. ``isolation_improvement`` (fused ratio / disagg
      ratio) is the transferable structural claim.

    Parity-gated: the same mixed trace must stream token-identically
    through both arms before anything is timed. The handoff contract is
    asserted, not assumed: ``kv_bytes_moved_total`` is pinned 0 (pure
    ownership transfer) and both arms' allocators drain to zero.

    CPU-proxy caveat, stated honestly: in-process the two pools serialize
    on one device, so the disagg arm's recorded TBT is *attributed* per
    worker (the loop shifts decode clocks past the serialized prefill
    sections — what a dedicated decode device would serve); the serialized
    per-worker totals ride in the record (``prefill_tick_s`` /
    ``decode_tick_s``). Absolute seconds are proxy numbers either way;
    the structure — decode ticks never widen with prefill load — is what
    transfers to a two-pool deployment.
    """
    from tree_attention_tpu.obs.metrics import percentile
    from tree_attention_tpu.serving.disagg import DisaggServer

    cfg = cfg or serving_model_config(max_seq_len=cache_len)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    slots = residents + prefill_slots  # fused arm: equal total slots
    decode_slots = residents
    npb = -(-cache_len // 64)
    kv_blocks = slots * npb  # ONE budget for both arms: equal pool bytes
    trace_kw = dict(
        residents=residents, resident_prompt=resident_prompt,
        resident_new=resident_new, wave_prompt_len=wave_prompt_len,
        wave_new=1, wave_start=wave_start, vocab_size=cfg.vocab_size,
        seed=seed + 1,
    )
    loads = {
        "unloaded": dict(waves=0, wave_gap=base_gap),
        "base": dict(waves=base_waves, wave_gap=base_gap),
        "double": dict(waves=2 * base_waves, wave_gap=base_gap // 2),
    }

    fused = SlotServer(
        params, cfg, slots=slots, cache_len=cache_len,
        prefill_chunk=prefill_chunk, kv_blocks=kv_blocks,
    )
    disagg = DisaggServer(
        params, cfg, prefill_slots=prefill_slots,
        decode_slots=decode_slots, cache_len=cache_len,
        prefill_chunk=prefill_chunk, kv_blocks=kv_blocks,
    )

    # --- parity gate: identical streams before anything is timed ---
    parity_trace = _disagg_trace(**dict(
        trace_kw, residents=residents, resident_new=24, wave_new=4,
        waves=2, wave_gap=6,
    ))
    ref = {r.uid: r.tokens for r in fused.serve(list(parity_trace)).results}
    got = {r.uid: r.tokens
           for r in disagg.serve(list(parity_trace)).results}
    if ref != got:
        raise AssertionError(
            "disaggregated serving diverged from the fused engine on the "
            "parity trace — the zero-copy handoff corrupted a stream"
        )

    def run_arm(server) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        # Warmup: the widest-load trace pays every jit compile.
        server.serve(_disagg_trace(**trace_kw, **loads["double"]))
        for load, kw in loads.items():
            p99s, p50s = [], []
            for _ in range(repeats):
                rep = server.serve(_disagg_trace(**trace_kw, **kw))
                gaps = sorted(rep.tbt_s)
                p99s.append(percentile(gaps, 0.99))
                p50s.append(percentile(gaps, 0.50))
            # Min-over-repeats: the noise-robust estimate, same rule as
            # every latency record in this suite.
            out[load] = {
                "tbt_p99_s": round(min(p99s), 5),
                "tbt_p50_s": round(min(p50s), 5),
            }
        unloaded = out["unloaded"]["tbt_p99_s"]
        if unloaded > 0:
            out["interference_ratio"] = round(
                out["double"]["tbt_p99_s"] / unloaded, 3
            )
            out["interference_ratio_base"] = round(
                out["base"]["tbt_p99_s"] / unloaded, 3
            )
        return out

    with obs.span("bench_serving_disagg:fused", cat="bench"):
        fused_rec = run_arm(fused)
    with obs.span("bench_serving_disagg:disagg", cat="bench"):
        disagg_rec = run_arm(disagg)
        last = disagg.serve(_disagg_trace(**trace_kw, **loads["double"]))
        disagg_rec["handoffs"] = last.handoff["handoffs"]
        disagg_rec["queue_peak"] = last.handoff["queue_peak"]
        disagg_rec["kv_bytes_moved_total"] = last.handoff["kv_bytes_moved"]
        disagg_rec["prefill_tick_s"] = last.handoff["prefill_tick_s"]
        disagg_rec["decode_tick_s"] = last.handoff["decode_tick_s"]

    leaks = {"fused": fused.leak_report(), "disagg": disagg.leak_report()}
    for arm, leak in leaks.items():
        if any(leak.values()):
            raise AssertionError(
                f"disagg bench: {arm} arm leaked after drain: {leak}"
            )
    rec: Dict[str, Any] = {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "cache_len": cache_len,
            "slots": slots,
            "prefill_slots": prefill_slots,
            "decode_slots": decode_slots,
            "kv_blocks": kv_blocks,
            "residents": residents,
            "wave_prompt_len": wave_prompt_len,
            "base_waves": base_waves,
            "base_gap": base_gap,
            "prefill_chunk": prefill_chunk,
        },
        "parity": "token-identical",
        "fused": fused_rec,
        "disagg": disagg_rec,
        "leaks": leaks,
    }
    fr = fused_rec.get("interference_ratio")
    dr = disagg_rec.get("interference_ratio")
    if fr and dr:
        rec["isolation_improvement"] = round(fr / dr, 3)
    log.info(
        "disagg: interference p99(double)/p99(unloaded) fused %sx vs "
        "disagg %sx (isolation %sx); %d handoffs, 0 KV bytes moved",
        fr, dr, rec.get("isolation_improvement", "?"),
        disagg_rec.get("handoffs", 0),
    )
    return rec


def _tier_leak_check(server, arm: str) -> None:
    """The tiered bench's drain contract: device allocator clean (no
    private blocks, reservations, or pins; used == tree-retained), host
    tier with NO demotion still staged (its only legitimate occupancy is
    retained demoted prefixes — the host-sized cache is the feature)."""
    leak = server.leak_report()
    if (leak["blocks_private"] or leak["blocks_reserved"] or leak["pins"]
            or leak["blocks_used"] != leak["blocks_cached"]):
        raise AssertionError(f"tiered bench: {arm} arm leaked: {leak}")
    hp = getattr(server, "_host_pool", None)
    if hp is not None and hp.pending:
        raise AssertionError(
            f"tiered bench: {arm} arm left {len(hp.pending)} demotion(s) "
            f"staged after drain"
        )


def bench_serving_tiered_kv(
    *,
    slots: int = 2,
    cache_len: int = 320,
    kv_block: int = 32,
    prefix_len: int = 256,
    prefix_count: int = 5,
    prompt_len: int = 288,
    max_new_tokens: int = 4,
    arrival_every: int = 12,
    prefill_chunk: int = 64,
    extra_blocks: int = 4,
    host_blocks: int = 64,
    int8_slots: int = 8,
    int8_cache_len: int = 128,
    int8_prompt_len: int = 90,
    int8_new: int = 8,
    int8_pool_blocks: int = 12,
    bytes_ratio: int = 2,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The hierarchical-KV record (ISSUE 13): a host-RAM tier under the
    device pool, plus int8 per-block-scale capacity, both at fixed
    device bytes.

    **Tiering trace** — ``prefix_count`` distinct shared prefixes whose
    combined KV population (``prefix_count * prefix_len/kv_block``
    blocks) overflows the device pool. Pass 1 publishes every group;
    pass 2 revisits them in publish order — the LRU-thrash worst case.
    Three arms, identical traces, token-parity-gated:

    - **ceiling**: a device pool big enough to retain everything — the
      fits-in-device hit-rate/TTFT reference;
    - **on**: the small pool + a ``host_blocks`` tier. Radix eviction
      demotes; pass-2 hits restore via one batched H2D scatter per
      admission — hit-rate and TTFT p50 should land near the ceiling;
    - **off**: the small pool alone. Eviction FREES, so pass 2 re-pays
      cold prefill — the degradation the tier removes.

    ``restore_ratio`` (restored / demoted blocks) says how much of the
    demoted population the trace actually came back for.

    **int8 capacity** — equal device pool BYTES, all-at-start burst,
    no prefix cache: the exact arm gets ``int8_pool_blocks`` blocks, the
    int8 arm ``bytes_ratio`` times as many (per-block scales are ~1% of
    block bytes; ``bytes_ratio=2`` is the bf16 deployment story — the
    CPU proxy's float32 pools would buy 4x, so 2x is the conservative
    transferable figure). ``max_concurrent_improvement`` should track
    ``bytes_ratio``: int8 blocks now publish into the shared radix tree
    like exact ones, so the capacity doubling is real pool capacity,
    not a sidecar.

    CPU proxy: absolute TTFT seconds do not transfer; the structure —
    hit-rate held at the ceiling by the host tier, concurrency scaling
    with bytes-per-block — is the record's claim.
    """
    cfg = cfg or serving_model_config(max_seq_len=cache_len)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    npb = -(-cache_len // kv_block)
    prefix_blocks = prefix_count * (prefix_len // kv_block)
    small_pool = slots * npb + extra_blocks
    big_pool = slots * npb + prefix_blocks + extra_blocks
    assert prefix_blocks > small_pool, (
        "tiered bench misconfigured: the prefix population must "
        "overflow the small device pool"
    )
    trace_kw = dict(
        n_requests=prefix_count,
        prompt_len=prompt_len,
        prompt_jitter=0,
        max_new_tokens=max_new_tokens,
        arrival_every=arrival_every,
        vocab_size=cfg.vocab_size,
        prefix_share=1.0,
        prefix_len=prefix_len,
        prefix_count=prefix_count,
        prefix_seed=seed + 1000,
    )

    def run_arm(arm: str, pool_blocks: int, hb: int) -> Dict[str, Any]:
        server = SlotServer(
            params, cfg, slots=slots, cache_len=cache_len,
            prefill_chunk=prefill_chunk, prefix_cache=True,
            prefix_block=kv_block, kv_block=kv_block,
            kv_blocks=pool_blocks, host_blocks=hb,
        )
        # Pass 1: cold — pays the jit compiles AND publishes every
        # prefix group (round-robin assignment touches each once).
        server.serve(synthetic_trace(**trace_kw, seed=seed + 1))
        # Pass 2: revisit in publish order (the LRU-thrash worst case);
        # only this pass is measured.
        rep = server.serve(synthetic_trace(**trace_kw, seed=seed + 2))
        _tier_leak_check(server, arm)
        d = rep.as_dict()
        n = max(d["requests"], 1)
        hits = d.get("prefix", {}).get("hits", 0)
        return {
            "pool_blocks": pool_blocks,
            "host_blocks": hb,
            "revisit": d,
            "hit_rate": round(hits / n, 4),
            "ttft_p50_s": d["ttft_p50_s"],
            "tokens": {r.uid: r.tokens for r in rep.results},
        }

    tier_rec: Dict[str, Any] = {}
    with obs.span("bench_serving_tiered:trace", cat="bench"):
        arms = {
            "ceiling": run_arm("ceiling", big_pool, 0),
            "on": run_arm("on", small_pool, host_blocks),
            "off": run_arm("off", small_pool, 0),
        }
    # Parity gate: tiering is TRANSPARENT — all three arms must stream
    # the same tokens for the same trace before any number is compared.
    if not (arms["ceiling"]["tokens"] == arms["on"]["tokens"]
            == arms["off"]["tokens"]):
        raise AssertionError(
            "tiered bench: token parity broke across tiering arms"
        )
    for a in arms.values():
        del a["tokens"]
    tier_rec.update(arms)
    kv_on = arms["on"]["revisit"].get("kv", {})
    demoted = kv_on.get("demotions", 0)
    tier_rec["demotions"] = demoted
    tier_rec["restores"] = kv_on.get("restores", 0)
    if demoted:
        tier_rec["restore_ratio"] = round(
            tier_rec["restores"] / demoted, 4
        )
    off_p50 = arms["off"]["ttft_p50_s"]
    on_p50 = arms["on"]["ttft_p50_s"]
    if on_p50 > 0:
        tier_rec["ttft_p50_improvement"] = round(off_p50 / on_p50, 2)
        tier_rec["ttft_p50_vs_ceiling"] = round(
            on_p50 / max(arms["ceiling"]["ttft_p50_s"], 1e-9), 2
        )
    if arms["off"]["hit_rate"] > 0:
        tier_rec["hit_rate_improvement"] = round(
            arms["on"]["hit_rate"] / arms["off"]["hit_rate"], 2
        )

    # --- int8 per-block-scale capacity at equal device pool bytes ---
    int8_rec: Dict[str, Any] = {
        "bytes_ratio": bytes_ratio,
        "pool_blocks_exact": int8_pool_blocks,
        "pool_blocks_int8": int8_pool_blocks * bytes_ratio,
    }
    burst_kw = dict(
        n_requests=int8_slots,
        prompt_len=int8_prompt_len,
        prompt_jitter=0,
        max_new_tokens=int8_new,
        arrival_every=0,  # all queued at start: the demand is real
        vocab_size=cfg.vocab_size,
    )
    with obs.span("bench_serving_tiered:int8", cat="bench"):
        for arm, quant, blocks in (
            ("exact", False, int8_pool_blocks),
            ("int8", True, int8_pool_blocks * bytes_ratio),
        ):
            server = SlotServer(
                params, cfg, slots=int8_slots, cache_len=int8_cache_len,
                prefill_chunk=prefill_chunk, quantize=quant,
                kv_block=kv_block, kv_blocks=blocks,
            )
            server.serve(synthetic_trace(**burst_kw, seed=seed + 3))
            rep = server.serve(synthetic_trace(**burst_kw, seed=seed + 4))
            leak = server.leak_report()
            if any(leak.values()):
                raise AssertionError(
                    f"tiered bench: int8-capacity {arm} arm leaked: {leak}"
                )
            int8_rec[arm] = {
                "max_concurrent_requests": _max_concurrent(rep),
                "kv": rep.kv,
            }
    base_cc = int8_rec["exact"]["max_concurrent_requests"]
    if base_cc:
        int8_rec["max_concurrent_improvement"] = round(
            int8_rec["int8"]["max_concurrent_requests"] / base_cc, 2
        )

    log.info(
        "tiered KV: pass-2 hit-rate %.2f on vs %.2f off (ceiling %.2f); "
        "TTFT p50 %.4fs on vs %.4fs off; %d demoted / %d restored; "
        "int8 max concurrent %dx vs exact at equal bytes",
        arms["on"]["hit_rate"], arms["off"]["hit_rate"],
        arms["ceiling"]["hit_rate"], on_p50, off_p50,
        tier_rec["demotions"], tier_rec["restores"],
        int8_rec.get("max_concurrent_improvement", 0),
    )
    return {
        "workload": {
            "model": {
                "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                "vocab": cfg.vocab_size, "dtype": str(cfg.dtype),
            },
            "cache_len": cache_len,
            "kv_block": kv_block,
            "device_pool_blocks": small_pool,
            "host_blocks": host_blocks,
            "prefix_population_blocks": prefix_blocks,
            "trace": {k: v for k, v in trace_kw.items()},
        },
        "tiering": tier_rec,
        "int8_capacity": int8_rec,
    }


# ---------------------------------------------------------------------------
# ISSUE 16: end-to-end request telemetry — overhead on vs all-off
# ---------------------------------------------------------------------------


def bench_serving_request_telemetry(
    *,
    replicas: int = 2,
    slots: int = 2,
    cache_len: int = 96,
    n_requests: int = 24,
    tenants: int = 4,
    tenant_prefix_len: int = 32,
    mean_gap_s: float = 0.005,
    repeats: int = 3,
    overhead_budget: float = 0.05,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The telemetry-overhead record (ISSUE 16): the PR-11 fleet trace
    replayed through the router with request telemetry ON (tracer +
    request ledger armed, flow events and per-request cost ledgers
    recorded end to end) vs ALL OFF, on the same engines.

    Two claims, asserted live:

    - **zero-allocation disabled path** — with telemetry off, a full
      routed replay leaves the process-wide request ledger UNTOUCHED
      (no live entries, no ring growth): the seams are guarded at every
      call site (machine-checked by the obs-guard lint pass), so the
      off arm pays attribute reads only.
    - **<=5% overhead armed** — tokens/sec (on/off, best over
      ``repeats``) stays >= ``1 - overhead_budget`` and TTFT p50
      (on/off) <= ``1 + overhead_budget``. Arms interleave off/on per
      repeat so drift hits both equally; every run replays the SAME
      arrival/length schedule (one compile family, paid by a warmup)
      with its own tenant-prefix population (cold prefix caches per
      run, the fleet record's trick).

    The on arm also proves the tentpole end to end: the trace sink must
    contain the full flow chain (``s`` at the router, ``t`` at
    adoption/admission, ``f`` at retire) and the ledger ring must hold
    one finished ledger per request.
    """
    import json as _json
    import os as _os
    import tempfile as _tempfile

    from tree_attention_tpu.serving import Request as _Request
    from tree_attention_tpu.serving.fleet import (
        FleetSupervisor, LocalReplica,
    )
    from tree_attention_tpu.serving.router import FleetRouter

    if obs.TRACER.active or obs.REQLOG.enabled:
        # The overhead measurement needs a cold process: with telemetry
        # already armed process-wide there is no "off" arm to compare.
        return {"skipped": "telemetry already armed in this process"}

    block = 16
    cfg = cfg or serving_model_config(
        max_seq_len=cache_len, vocab_size=128, d_model=64
    )
    params = init_params(jax.random.PRNGKey(seed), cfg)
    kv_blocks = slots * (-(-cache_len // block)) + 24

    def make_engine():
        return SlotServer(
            params, cfg, slots=slots, cache_len=cache_len,
            prefill_chunk=block, prefix_cache=True, prefix_block=block,
            kv_blocks=kv_blocks,
        )

    reps = [LocalReplica(f"r{i}", make_engine, max_queue=n_requests + 8,
                         default_max_tokens=8, keepalive_s=0.1)
            for i in range(replicas)]
    router = FleetRouter(block=block, affinity=True, hysteresis=2)
    sup = FleetSupervisor(reps, router=router, monitor_interval_s=0)
    port = sup.start()
    engines = sup.engines

    def mt_trace(prefix_seed):
        # Fixed `seed` => identical arrivals/lengths/tenant draws every
        # run (ONE compile family, warmup pays it all); `prefix_seed`
        # redraws the tenant prefix POPULATION so each run starts with
        # a cold prefix cache for its own prefixes.
        return heavy_tail_trace(
            n_requests, cache_len=cache_len, mean_gap_s=mean_gap_s,
            vocab_size=cfg.vocab_size, seed=seed + 2,
            tenants=tenants, tenant_prefix_len=tenant_prefix_len,
            prefix_seed=prefix_seed,
        )

    def run_once(prefix_seed) -> Dict[str, Any]:
        res = replay_trace_http(port, mt_trace(prefix_seed))
        for eng in engines:
            _wait_engine_settled(eng)
        served = sum(1 for r in res
                     if r["finish_reason"] in ("stop", "length"))
        assert served == n_requests, (
            f"telemetry bench: only {served}/{n_requests} served"
        )
        ttfts = sorted(r["ttft_s"] for r in res
                       if r["ttft_s"] is not None)
        wall = max(r["done_s"] for r in res if r["done_s"] is not None)
        tokens = sum(len(r["tokens"]) for r in res)
        return {
            "tokens_per_sec": round(tokens / wall, 2) if wall else 0.0,
            "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4),
            "wall_s": round(wall, 4),
        }

    # Warmup pays every jit compile (prefill buckets + step programs on
    # each replica) before either arm is timed.
    run_once(seed + 11)

    tmp = _tempfile.mkdtemp(prefix="ta_reqlog_bench_")
    off_runs: List[Dict[str, Any]] = []
    on_runs: List[Dict[str, Any]] = []
    on_sanity: Dict[str, Any] = {}
    for rep in range(repeats):
        # -- off arm: telemetry all off; the ledger must stay untouched.
        before = obs.REQLOG.snapshot()
        with obs.span(f"bench_telemetry:off{rep}", cat="bench"):
            off_runs.append(run_once(seed + 100 + rep))
        after = obs.REQLOG.snapshot()
        assert (not after["enabled"] and after["live"] == []
                and after["recent"] == [] and after == before), (
            f"DISABLED-PATH VIOLATION: request ledger mutated with "
            f"telemetry off: {after}"
        )
        # -- on arm: tracer + ledger armed, full flow chain recorded.
        trace_path = _os.path.join(tmp, f"trace_r{rep}.jsonl")
        obs.TRACER.start(trace_path)
        obs.REQLOG.arm()
        try:
            with obs.span(f"bench_telemetry:on{rep}", cat="bench"):
                on_runs.append(run_once(seed + 200 + rep))
            snap = obs.REQLOG.snapshot()
            ledgers = snap["recent"]
            assert len(ledgers) == n_requests and snap["live"] == [], (
                f"telemetry bench: {len(ledgers)} ledger(s) recorded "
                f"for {n_requests} request(s), {len(snap['live'])} "
                f"stuck live"
            )
            agg = obs.aggregate_ledgers(ledgers)
            on_sanity = {
                "ledgers_recorded": len(ledgers),
                "tokens_decoded_ledgered":
                    agg["tokens_decoded_total"],
                "prefix_hit_ledgered": agg["prefix_hit_tokens_total"],
            }
        finally:
            obs.REQLOG.disarm()
            obs.TRACER.close()
        flows = {"s": 0, "t": 0, "f": 0}
        with open(trace_path) as fh:
            for line in fh:
                ph = _json.loads(line).get("ph")
                if ph in flows:
                    flows[ph] += 1
        assert flows["s"] and flows["t"] and flows["f"], (
            f"telemetry bench: incomplete flow chain in trace: {flows}"
        )
        on_sanity["flow_events"] = flows
    sup.stop()

    best_off = {
        "tokens_per_sec": max(r["tokens_per_sec"] for r in off_runs),
        "ttft_p50_s": min(r["ttft_p50_s"] for r in off_runs),
    }
    best_on = {
        "tokens_per_sec": max(r["tokens_per_sec"] for r in on_runs),
        "ttft_p50_s": min(r["ttft_p50_s"] for r in on_runs),
    }
    tok_ratio = round(
        best_on["tokens_per_sec"] / best_off["tokens_per_sec"], 4
    ) if best_off["tokens_per_sec"] else 0.0
    ttft_ratio = round(
        best_on["ttft_p50_s"] / best_off["ttft_p50_s"], 4
    ) if best_off["ttft_p50_s"] else 0.0
    assert tok_ratio >= 1.0 - overhead_budget, (
        f"TELEMETRY OVERHEAD: tokens/sec on/off = {tok_ratio} "
        f"< {1.0 - overhead_budget} "
        f"(on {best_on['tokens_per_sec']}, off "
        f"{best_off['tokens_per_sec']})"
    )
    assert ttft_ratio <= 1.0 + overhead_budget, (
        f"TELEMETRY OVERHEAD: TTFT p50 on/off = {ttft_ratio} "
        f"> {1.0 + overhead_budget} "
        f"(on {best_on['ttft_p50_s']}s, off {best_off['ttft_p50_s']}s)"
    )

    log.info(
        "request telemetry: tok/s on/off %.3f, ttft p50 on/off %.3f "
        "(budget %.0f%%); %d ledger(s), flows %s; disabled path "
        "allocation-free",
        tok_ratio, ttft_ratio, overhead_budget * 100,
        on_sanity.get("ledgers_recorded", 0),
        on_sanity.get("flow_events"),
    )
    return {
        "workload": {
            "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                      "vocab": cfg.vocab_size},
            "replicas": replicas, "slots_per_replica": slots,
            "cache_len": cache_len, "n_requests": n_requests,
            "tenants": tenants, "tenant_prefix_len": tenant_prefix_len,
            "repeats": repeats, "overhead_budget": overhead_budget,
        },
        "off": {**best_off, "runs": off_runs,
                "ledger_untouched": True},
        "on": {**best_on, "runs": on_runs, **on_sanity},
        "overhead": {
            "tokens_per_sec_ratio": tok_ratio,
            "ttft_p50_ratio": ttft_ratio,
        },
    }


# ---------------------------------------------------------------------------
# ISSUE 18: sequence-sharded paged pool — capacity at fixed per-device bytes
# ---------------------------------------------------------------------------


def bench_serving_seq_sharded(
    *,
    slots: int = 1,
    kv_block: int = 8,
    blocks_per_shard: int = 8,
    max_new_tokens: int = 4,
    lat_prompt_len: int = 24,
    lat_requests: int = 3,
    prefill_chunk: int = 8,
    cfg: Optional[TransformerConfig] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """The sequence-sharded serving record (ISSUE 18): max servable
    context at EQUAL per-device pool bytes, mesh=1 vs mesh=2, plus
    TTFT/TBT on a common trace — parity-gated, with the decode merge's
    collective count asserted through the accounting counters.

    **Capacity** — each arm gets ``blocks_per_shard`` pool blocks PER
    DEVICE: the mesh=1 arm a ``blocks_per_shard``-block replicated pool,
    the mesh=2 arm a ``2 * blocks_per_shard``-block pool range-
    partitioned by ``kv_shard="seq"``. Both boundaries are MEASURED, not
    computed: a single request sized to exactly fill the pool must
    stream ``max_new_tokens`` tokens, and one block more must be
    rejected by admission validation ("can never fit"). The headline
    ``max_context_ratio`` is the sharded arm's measured ceiling over the
    single-device arm's — 2.0 at W=2 by construction of the sharding,
    and the record proves the construction.

    **Latency + parity** — a small common trace through the mesh=2
    sharded arm vs a mesh=2 REPLICATED oracle: streams must be
    token-identical before TTFT/TBT p50 are reported (CPU proxy:
    absolute seconds do not transfer; the structure — capacity scaling
    with W at ~flat tick latency — is the claim).

    **Merge cost** — the sharded arm's decode dispatch must account
    EXACTLY three collectives (``pmax`` on the running max, ``psum`` on
    the weighted numerator, ``psum`` on the denominator — the tree
    monoid, arXiv:2408.04093); any fourth label in
    ``collective_payload_bytes_total{algorithm="paged_tree_decode"}``
    fails the record.
    """
    from tree_attention_tpu.parallel.accounting import PAYLOAD_BYTES
    from tree_attention_tpu.parallel.mesh import cpu_mesh

    cache_len = 2 * blocks_per_shard * kv_block
    cfg = cfg or serving_model_config(max_seq_len=cache_len)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    mesh2 = cpu_mesh(2)

    def make_server(blocks: int, *, mesh=None, kv_shard="replicated",
                    quantize=False):
        return SlotServer(
            params, cfg, slots=slots, cache_len=cache_len, mesh=mesh,
            prefill_chunk=prefill_chunk, quantize=quantize,
            kv_block=kv_block, kv_blocks=blocks,
            kv_shard=kv_shard,
        )

    def probe_max_context(blocks: int, **kw) -> Dict[str, Any]:
        """Measure the capacity boundary: a pool-filling request must
        serve; a one-block-longer one must be rejected up front."""
        fits = blocks * kv_block
        rng = np.random.default_rng(seed + 17)

        def one(total: int):
            prompt = rng.integers(
                0, cfg.vocab_size, size=total - max_new_tokens
            ).astype(np.int32)
            server = make_server(blocks, **kw)
            return server.serve([Request(
                uid=0, prompt=prompt, max_new_tokens=max_new_tokens,
                arrival_tick=0,
            )], max_ticks=600)

        rep = one(fits)
        if len(rep.results[0].tokens) != max_new_tokens:
            raise AssertionError(
                f"seq-sharded bench: the pool-filling request "
                f"({fits} tokens over {blocks} blocks) did not stream"
            )
        try:
            one(fits + kv_block)
        except ValueError:
            pass  # the measured boundary: one more block can never fit
        else:
            raise AssertionError(
                f"seq-sharded bench: a {fits + kv_block}-token request "
                f"was admitted over a {blocks}-block pool"
            )
        return {"pool_blocks": blocks,
                "max_context_tokens": fits,
                "max_new_tokens_streamed": max_new_tokens}

    with obs.span("bench_serving_seq_sharded:capacity", cat="bench"):
        mesh1 = probe_max_context(blocks_per_shard)
        mesh2_seq = probe_max_context(
            2 * blocks_per_shard, mesh=mesh2, kv_shard="seq")
        mesh2_seq["shards"] = 2

    # --- latency + parity on a common trace, mesh=2 sharded vs oracle ---
    trace_kw = dict(
        n_requests=lat_requests, prompt_len=lat_prompt_len,
        prompt_jitter=0, max_new_tokens=max_new_tokens,
        arrival_every=1, vocab_size=cfg.vocab_size,
    )
    was_enabled = obs.REGISTRY.enabled
    obs.REGISTRY.enable()
    try:
        with obs.span("bench_serving_seq_sharded:trace", cat="bench"):
            lat = {}
            for arm, kv_shard in (("seq", "seq"), ("replicated",
                                                   "replicated")):
                server = make_server(
                    2 * blocks_per_shard if kv_shard == "seq"
                    else blocks_per_shard,
                    mesh=mesh2, kv_shard=kv_shard,
                )
                server.serve(synthetic_trace(**trace_kw, seed=seed + 1))
                rep = server.serve(
                    synthetic_trace(**trace_kw, seed=seed + 2))
                leak = server.leak_report()
                if any(leak.values()):
                    raise AssertionError(
                        f"seq-sharded bench: {arm} arm leaked: {leak}")
                d = rep.as_dict()
                lat[arm] = {
                    "ttft_p50_s": d["ttft_p50_s"],
                    "tbt_p50_s": d["tbt_p50_s"],
                    "tbt_p95_s": d["tbt_p95_s"],
                    "tokens": {r.uid: r.tokens for r in rep.results},
                }
    finally:
        if not was_enabled:
            obs.REGISTRY.disable()
    if lat["seq"]["tokens"] != lat["replicated"]["tokens"]:
        raise AssertionError(
            "seq-sharded bench: token parity broke between the sharded "
            "arm and the replicated oracle at mesh=2"
        )
    for a in lat.values():
        del a["tokens"]

    # The merge monoid's wire cost: exactly one MAX and two SUMs.
    colls = sorted(
        key[1] for key in PAYLOAD_BYTES._children
        if key[0] == "paged_tree_decode"
    )
    if colls != ["pmax", "psum_den", "psum_num"]:
        raise AssertionError(
            f"seq-sharded bench: decode merge accounted collectives "
            f"{colls}, expected exactly [pmax, psum_den, psum_num]"
        )

    ratio = round(
        mesh2_seq["max_context_tokens"] / mesh1["max_context_tokens"], 2
    )
    log.info(
        "seq-sharded serving: max context %d tokens at mesh=2 vs %d at "
        "mesh=1 (%.2fx at equal per-device pool bytes); TTFT p50 %.4fs "
        "sharded vs %.4fs replicated; merge = 3 collectives",
        mesh2_seq["max_context_tokens"], mesh1["max_context_tokens"],
        ratio, lat["seq"]["ttft_p50_s"], lat["replicated"]["ttft_p50_s"],
    )
    return {
        "workload": {
            "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                      "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
                      "vocab": cfg.vocab_size},
            "slots": slots, "cache_len": cache_len,
            "kv_block": kv_block,
            "blocks_per_device": blocks_per_shard,
            "trace": trace_kw,
        },
        "mesh1": mesh1,
        "mesh2_seq": mesh2_seq,
        "max_context_ratio": ratio,
        "latency": lat,
        "merge_collectives": colls,
        "parity": "token-identical (sharded vs replicated oracle, mesh=2)",
    }
