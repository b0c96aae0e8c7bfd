"""Parameterised attention benchmarks over the framework's real entry points.

Every benchmark: generates data with the data layer (shard-local when a mesh
is given), jits the measured function, times it with compile warmup and
``block_until_ready`` fencing (:func:`tree_attention_tpu.utils.time_fn`), and
reports a JSON-serialisable :class:`BenchResult` carrying tokens/sec, achieved
FLOP/s, and peak HBM where the backend exposes allocator stats.

The comparator pair (:func:`bench_compare`) runs :func:`tree_attention
<tree_attention_tpu.parallel.tree_attention>` and :func:`ring_attention
<tree_attention_tpu.parallel.ring.ring_attention>` on identical data, shapes,
mesh and inner kernel, so the reported ratio isolates the communication
pattern — the honest-comparator requirement of SURVEY.md §7 hard part 4.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tree_attention_tpu import obs
from tree_attention_tpu.data import make_qkv, make_qkv_sharded
from tree_attention_tpu.ops import flash_attention
from tree_attention_tpu.parallel.mesh import AXIS_SEQ, prune_axes
from tree_attention_tpu.parallel.ring import ring_attention, ring_decode
from tree_attention_tpu.parallel.tree import (
    tree_attention,
    tree_decode,
    tree_decode_q8,
)
from tree_attention_tpu.parallel.ulysses import ulysses_attention, ulysses_decode
from tree_attention_tpu.utils.config import RunConfig
from tree_attention_tpu.utils.logging import get_logger
from tree_attention_tpu.utils.profiling import (
    TimingStats,
    device_memory_stats,
    record_guard_verdict,
    time_fn,
)

log = get_logger("bench")

from tree_attention_tpu.bench.ici import peaks


def _physical_floor_bw() -> Optional[float]:
    """Twice the attached chip's published HBM bandwidth (bench/ici.PEAKS):
    no honest reading streams KV faster than spec, so 2× spec is a
    conservative "the fence did not fence" threshold. None off the TPU —
    there is no published figure to hold a host reading to."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return 2 * peaks(dev.device_kind).hbm_bytes_per_s


# A median this far above the min over repeats means the measurement window
# was contended (host contention is additive): the symmetric, too-SLOW
# counterpart of the floor guard.
JITTER_MEDIAN_OVER_MIN = 1.5

# Execution-true work accounting: these count what the host loop actually
# ran (fenced iterations × the workload's static shape), complementing the
# trace-time dispatch counters in ops/ and parallel/.
_DECODE_STEPS = obs.counter(
    "decode_steps_total",
    "fenced decode steps executed by the bench/CLI host loop",
    labels=("name",),
)
_DECODE_TOKENS = obs.counter(
    "decode_tokens_total",
    "query tokens decoded by executed steps (batch x q_len per step)",
    labels=("name",),
)
_DECODE_KV_TOKENS = obs.counter(
    "decode_kv_tokens_total",
    "KV tokens scanned by executed steps (seq_len per step)",
    labels=("name",),
)


@dataclasses.dataclass
class BenchResult:
    """One benchmark record; ``as_json_line()`` is the driver-facing format."""

    name: str
    workload: Dict[str, Any]
    timing: TimingStats
    tokens_per_sec: float
    flops_per_sec: float
    n_devices: int = 1
    peak_hbm_bytes: Optional[int] = None
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        dev = jax.devices()[0]
        d = {
            "name": self.name,
            # Every record names the device it was measured on: a host
            # timing must never read as a chip's.
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "workload": self.workload,
            "tokens_per_sec": round(self.tokens_per_sec, 1),
            "tokens_per_sec_per_device": round(
                self.tokens_per_sec / self.n_devices, 1
            ),
            "flops_per_sec": self.flops_per_sec,
            "n_devices": self.n_devices,
            **self.timing.as_dict(),
        }
        if self.peak_hbm_bytes is not None:
            d["peak_hbm_bytes"] = self.peak_hbm_bytes
        d.update(self.extra)
        return d

    def as_json_line(self) -> str:
        return json.dumps(self.as_dict())


def attention_flops(
    *,
    batch: int,
    heads: int,
    q_len: int,
    kv_len: int,
    head_dim: int,
    causal: bool = False,
    backward: bool = False,
) -> float:
    """Model FLOPs of exact attention: 2 matmuls, 2 FLOPs per MAC.

    Causal halves the score matrix only in the square training shape (decode's
    single query attends to everything regardless). Backward adds the standard
    flash-attention recompute factor: dQ, dK, dV are each one QK^T-sized
    matmul pair plus the forward recompute ⇒ ~2.5× the forward FLOPs, total
    3.5× when ``backward``.
    """
    pairs = batch * heads * q_len * kv_len
    if causal and q_len == kv_len:
        pairs = batch * heads * (q_len * (q_len + 1)) // 2
    fwd = 4.0 * pairs * head_dim
    return fwd * 3.5 if backward else fwd


def _peak_hbm() -> Optional[int]:
    stats = device_memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def _workload(cfg: RunConfig, **extra: Any) -> Dict[str, Any]:
    return {
        "batch": cfg.batch,
        "heads": cfg.heads,
        "kv_heads": cfg.resolved_kv_heads(),
        "head_dim": cfg.head_dim,
        "seq_len": cfg.seq_len,
        "q_len": cfg.q_len,
        "dtype": cfg.dtype,
        "causal": cfg.causal,
        "impl": cfg.impl,
        **extra,
    }


def bench_decode(cfg: RunConfig, mesh: Optional[Mesh] = None) -> BenchResult:
    """One decode step over a ``seq_len`` KV cache; tree-merged on a mesh.

    The reference's workload (``/root/reference/model.py:140-155``) with the
    measurement done right: fenced, repeated, median.
    """
    dtype = jnp.dtype(cfg.dtype)
    key = jax.random.PRNGKey(cfg.seed)
    kw = dict(
        batch=cfg.batch, heads=cfg.heads, kv_heads=cfg.resolved_kv_heads(),
        q_len=cfg.q_len, seq_len=cfg.seq_len, head_dim=cfg.head_dim,
        dtype=dtype,
    )
    # 'int8' is the int8-MXU q8q kernel (the fastest decode path);
    # 'int8-cast' keeps the bf16-cast kernel. Validates kv_quant too.
    quant_kernel = cfg.resolved_quant_kernel()
    quant = quant_kernel is not None
    if quant and cfg.impl not in ("auto", "pallas_decode"):
        raise ValueError(
            f"--kv-quant {cfg.kv_quant} runs a pallas_decode q8 kernel; "
            f"--impl {cfg.impl} cannot serve a quantized buffer"
        )

    # One flow for exact and quantized: generate, (optionally) quantize,
    # pick the per-topology step fn and record name, then a single
    # timing/record tail.
    if mesh is None:
        q, k, v = make_qkv(key, **kw)
        n_devices = 1
    else:
        q, k, v = make_qkv_sharded(key, mesh, **kw)
        axes = prune_axes(mesh, {"data": "data", "model": "model"})
        n_devices = mesh.size

    extra = {}
    if quant:
        from tree_attention_tpu.ops.pallas_decode import (
            quantize_kv_channelwise,
            resolve_q8_kernel,
        )

        # Per-channel scales are shard-invariant, so global quantization
        # shards as-is (jnp ops run distributed on sharded inputs).
        k, v, k_s, v_s = quantize_kv_channelwise(k, v)
        extra = {"kv_quant": cfg.kv_quant}
        if mesh is None:
            name = "decode_" + quant_kernel
            kernel_fn = resolve_q8_kernel(quant_kernel)
            # block_size=None resolves inside the wrapper via the q8 tile
            # table — the bench times the production default path.
            fn = jax.jit(lambda q, k, v: kernel_fn(
                q, k, v, k_s, v_s, causal=cfg.causal,
                block_size=cfg.block_size,
            )[0])
        else:
            name = "tree_decode_" + quant_kernel
            fn = jax.jit(lambda q, k, v: tree_decode_q8(
                q, k, v, k_s, v_s, mesh=mesh, causal=cfg.causal,
                block_size=cfg.block_size, kernel=quant_kernel,
                data_axis=axes["data"], head_axis=axes["model"],
            )[0])
    elif mesh is None:
        name = "decode"
        fn = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=cfg.causal, impl=cfg.impl,
            block_size=cfg.block_size,
        )[0])
    else:
        name = "tree_decode"
        fn = jax.jit(lambda q, k, v: tree_decode(
            q, k, v, mesh=mesh, causal=cfg.causal, impl=cfg.impl,
            block_size=cfg.block_size,
            data_axis=axes["data"], head_axis=axes["model"],
        )[0])

    with obs.span("bench_decode", cat="bench",
                  args=None if not obs.TRACER.active else
                  {"name": name, "ctx": cfg.seq_len, "iters": cfg.iters}):
        stats = time_fn(fn, q, k, v, iters=cfg.iters, warmup=cfg.warmup)
    if obs.REGISTRY.enabled:
        steps = cfg.iters + max(cfg.warmup, 0)
        _DECODE_STEPS.labels(name=name).inc(steps)
        _DECODE_TOKENS.labels(name=name).inc(cfg.batch * cfg.q_len * steps)
        _DECODE_KV_TOKENS.labels(name=name).inc(cfg.seq_len * steps)
    flops = attention_flops(
        batch=cfg.batch, heads=cfg.heads, q_len=cfg.q_len, kv_len=cfg.seq_len,
        head_dim=cfg.head_dim, causal=cfg.causal,
    )
    workload = _workload(
        cfg, mesh=None if mesh is None else dict(mesh.shape), **extra
    )
    if quant:
        workload["impl"] = "pallas_decode"  # what actually ran
    # Decode must stream every KV byte, so there is a physical floor on
    # the step time. A reading below it means the completion fence did not
    # actually fence — flag it rather than report impossible tokens/sec.
    kv_bytes = (
        2 * cfg.batch * cfg.seq_len * cfg.resolved_kv_heads() * cfg.head_dim
        * (1 if quant else jnp.dtype(cfg.dtype).itemsize)
    ) // (1 if mesh is None else mesh.shape.get(AXIS_SEQ, 1))
    suspect = {}
    floor_bw = _physical_floor_bw()
    if floor_bw is not None and stats.median < kv_bytes / floor_bw:
        suspect["timing_suspect"] = (
            "median below the physical HBM floor for this workload "
            f"(>{floor_bw / 1e12:.1f} TB/s implied, 2x the chip's "
            "published bandwidth); the completion fence likely did not "
            "fence — use --mode bench / bench.py (slope protocol)"
        )
        log.warning("decode timing below the physical HBM floor: %s",
                    suspect["timing_suspect"])
        record_guard_verdict(name, "floor", suspect["timing_suspect"])
    elif (
        stats.iters >= 3
        and stats.median > JITTER_MEDIAN_OVER_MIN * stats.minimum
    ):
        # The too-slow counterpart: a clean window has median ~= min; a
        # median 1.5x the min means most repeats hit host
        # contention and the reported tokens/sec (median-based) understates
        # the chip. min_s in the record is the trustworthy bound.
        suspect["timing_suspect"] = (
            f"median {stats.median / stats.minimum:.2f}x the min over "
            f"{stats.iters} repeats — jittery measurement window; trust "
            "min_s, or use --mode bench / bench.py (repeated-slope "
            "protocol) for honest numbers"
        )
        log.warning("decode timing window jittery: %s",
                    suspect["timing_suspect"])
        record_guard_verdict(name, "jitter", suspect["timing_suspect"])
    else:
        # "clean" = every screen that could run passed; with < 3 repeats
        # the jitter screen cannot run, and the verdict says so rather
        # than overclaiming.
        record_guard_verdict(
            name, "clean",
            None if stats.iters >= 3 else
            "floor screen only (jitter screen needs >= 3 repeats)",
        )
    return BenchResult(
        name=name,
        workload=workload,
        timing=stats,
        tokens_per_sec=cfg.seq_len / stats.median,  # KV tokens scanned per step
        flops_per_sec=flops / stats.median,
        n_devices=n_devices,
        peak_hbm_bytes=_peak_hbm(),
        extra=suspect,
    )


def _train_shape_fn(
    cfg: RunConfig, mesh: Mesh, algorithm: str
) -> Callable[..., Any]:
    axes = prune_axes(mesh, {"data": "data", "model": "model"})
    extra = {}
    if algorithm == "tree_zigzag":
        # Causally balanced layout. Timing-valid on iid benchmark data
        # without re-permuting it: the layout changes which (shard, offset)
        # pairs are causally live, not what the bytes are.
        attn, extra = tree_attention, {"layout": "zigzag"}
    else:
        attn = {
            "tree": tree_attention,
            "ring": ring_attention,
            "ulysses": ulysses_attention,
        }[algorithm]

    def loss(q, k, v):
        out, _ = attn(
            q, k, v, mesh=mesh, causal=cfg.causal, impl=cfg.impl,
            block_size=cfg.block_size,
            data_axis=axes["data"], head_axis=axes["model"], **extra,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def step(q, k, v):
        _, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return grads

    return step


def bench_train_attention(
    cfg: RunConfig, mesh: Mesh, algorithm: str = "tree"
) -> BenchResult:
    """Training-shape fwd+bwd: Q/K/V all sequence-sharded (q_len = seq_len).

    Timed with a min-stat estimator, in the form the platform calls for:

    - **TPU mesh**: steps chained with ``lax.scan`` (each step's Q is the
      previous step's normalised dQ, a real data dependency),
      scalar-reduction fence, per-step cost as the slope between a short
      and a long chain, minimum over repetitions.
    - **Emulated CPU mesh**: min over ≥8 single-step repetitions. The
      slope exists to cancel fixed per-call costs; on the emulated mesh
      the noise is additive scheduling jitter (min converges), and the
      chain's price — a second long XLA compile per algorithm — buys
      nothing.
    """
    from jax import lax

    from tree_attention_tpu.ops import mesh_platforms
    from tree_attention_tpu.utils.profiling import time_per_step

    dtype = jnp.dtype(cfg.dtype)
    q, k, v = make_qkv_sharded(
        jax.random.PRNGKey(cfg.seed), mesh,
        batch=cfg.batch, heads=cfg.heads, kv_heads=cfg.resolved_kv_heads(),
        q_len=cfg.seq_len, seq_len=cfg.seq_len, head_dim=cfg.head_dim,
        dtype=dtype,
    )
    # Q must be sharded like KV in the training shape; make_qkv_sharded
    # replicates Q, so re-place it along the seq axis.
    from tree_attention_tpu.parallel.mesh import shard_along

    q = shard_along(mesh, q, AXIS_SEQ, 2)
    step = _train_shape_fn(cfg, mesh, algorithm)
    on_tpu_mesh = mesh_platforms(mesh) == {"tpu"}

    if on_tpu_mesh:
        # Long sequences get short chains: per-step work grows
        # ~quadratically, so a 1→3-step slope already rests on seconds of
        # marginal work.
        n_small, n_large = (1, 3) if cfg.seq_len >= 4096 else (2, 6)

        def mk(n):
            def f(q_, k_, v_):
                def body(qc, _):
                    dq, dk, dv = step(qc, k_, v_)
                    # Fold dK/dV into the carry too (scaled far below fp
                    # resolution): grad-wrt-q alone would let XLA dead-
                    # code-eliminate the dKV pass and the timed work would
                    # be ~5 of the 9 backward matmul passes.
                    dq = dq + 1e-30 * (jnp.sum(dk) + jnp.sum(dv))
                    qn = dq * lax.rsqrt(jnp.mean(jnp.square(dq)) + 1e-6)
                    return qn.astype(qc.dtype), None

                out = lax.scan(body, q_, None, length=n)[0]
                return jnp.sum(out.astype(jnp.float32))

            return jax.jit(f)

        iters = max(cfg.iters, 3)
        with obs.span("bench_train_attention", cat="bench",
                      args=None if not obs.TRACER.active else
                      {"algorithm": algorithm, "seq": cfg.seq_len}):
            per, _, _ = time_per_step(
                mk, q, k, v, n_small=n_small, n_large=n_large,
                iters=iters, warmup=max(cfg.warmup, 1), stat="min",
            )
        stats = TimingStats(
            median=per, mean=per, minimum=per, maximum=per,
            iters=iters, times=(per,),
        )
        protocol = {"timing_protocol": "slope_min",
                    "chain": [n_small, n_large]}
    else:
        iters = max(cfg.iters, 8)
        with obs.span("bench_train_attention", cat="bench",
                      args=None if not obs.TRACER.active else
                      {"algorithm": algorithm, "seq": cfg.seq_len}):
            stats = time_fn(
                jax.jit(step), q, k, v, iters=iters, warmup=max(cfg.warmup, 1)
            )
        per = stats.minimum
        protocol = {"timing_protocol": "single_step_min"}
    flops = attention_flops(
        batch=cfg.batch, heads=cfg.heads, q_len=cfg.seq_len,
        kv_len=cfg.seq_len, head_dim=cfg.head_dim, causal=cfg.causal,
        backward=True,
    )
    return BenchResult(
        name=f"{algorithm}_attention_fwd_bwd",
        workload=_workload(cfg, q_len=cfg.seq_len, mesh=dict(mesh.shape)),
        timing=stats,
        tokens_per_sec=cfg.batch * cfg.seq_len / per,
        flops_per_sec=flops / per,
        n_devices=mesh.size,
        peak_hbm_bytes=_peak_hbm(),
        extra=protocol,
    )


def bench_compare(cfg: RunConfig, mesh: Mesh) -> Dict[str, Any]:
    """Tree vs ring on identical data/mesh/kernel; the north-star ratio.

    Ratios compare per-step times under each record's min-stat estimator
    (``tokens_per_sec`` is derived from it, so the workload cancels) —
    not the raw medians, which differ from the estimator on the
    single-step-min path.
    """
    tree = bench_train_attention(cfg, mesh, "tree")
    ring = bench_train_attention(cfg, mesh, "ring")
    ratio = tree.tokens_per_sec / ring.tokens_per_sec
    log.info(
        "tree %.1f vs ring %.1f tokens/s -> tree is %.2fx ring",
        tree.tokens_per_sec, ring.tokens_per_sec, ratio,
    )
    record = {
        "tree": tree.as_dict(),
        "ring": ring.as_dict(),
        "tree_speedup_vs_ring": round(ratio, 3),
    }
    n = mesh.shape.get(AXIS_SEQ, 1)
    if cfg.causal and cfg.seq_len % (2 * n) == 0:
        # The causally balanced layout is the fair tree entry under masking.
        # Guarded on its stricter divisibility (2N half-blocks) so a config
        # valid for tree/ring never loses their results to a zigzag error.
        zz = bench_train_attention(cfg, mesh, "tree_zigzag")
        record["tree_zigzag"] = zz.as_dict()
        record["tree_zigzag_speedup_vs_ring"] = round(
            zz.tokens_per_sec / ring.tokens_per_sec, 3
        )
    # The third SP family joins the comparison when its head-divisibility
    # requirement holds (it re-shards the PER-SHARD head slice, so a model
    # axis divides the head count first; see parallel/ulysses). Guarded like
    # zigzag above: an inapplicable config must never lose tree/ring's
    # already-computed results.
    h_shards = mesh.shape.get("model", 1)
    hq_l, hkv_l = cfg.heads, cfg.resolved_kv_heads()
    if hq_l % h_shards == 0 and hkv_l % h_shards == 0:
        hq_l, hkv_l = hq_l // h_shards, hkv_l // h_shards
        if hq_l % n == 0 and hkv_l % n == 0:
            uly = bench_train_attention(cfg, mesh, "ulysses")
            record["ulysses"] = uly.as_dict()
            record["ulysses_speedup_vs_ring"] = round(
                uly.tokens_per_sec / ring.tokens_per_sec, 3
            )
    return record


def bench_decode_compare(cfg: RunConfig, mesh: Mesh) -> Dict[str, Any]:
    """Tree vs ring (vs Ulysses) on the DECODE shape, with communication
    accounting — VERDICT r3 item 1.

    Decode (replicated Q of ``q_len`` tokens against a sequence-sharded KV
    buffer) is the reference's entire workload
    (``/root/reference/model.py:140-145``) and the shape the tree merge
    exists for: local compute is identical across the families (same
    kernel, KV never moves), so the contest is purely the merge's
    communication. Each algorithm gets:

    - a min-stat **slope** timing (chained steps, the r3 protocol — the
      3-iter medians of the train comparator wobbled ±4%);
    - **collective counts and payload bytes per step** parsed from its
      compiled SPMD module (:func:`tree_attention_tpu.bench.comm
      .collective_stats`) — the emulated mesh can't price ICI, but it can
      count exactly what XLA will put on the wire.
    """
    from tree_attention_tpu.bench.comm import assert_loop_free, collective_stats
    from tree_attention_tpu.utils.profiling import time_per_step
    from jax import lax

    dtype = jnp.dtype(cfg.dtype)
    q, k, v = make_qkv_sharded(
        jax.random.PRNGKey(cfg.seed), mesh,
        batch=cfg.batch, heads=cfg.heads, kv_heads=cfg.resolved_kv_heads(),
        q_len=cfg.q_len, seq_len=cfg.seq_len, head_dim=cfg.head_dim,
        dtype=dtype,
    )
    axes = prune_axes(mesh, {"data": "data", "model": "model"})
    n = mesh.shape.get(AXIS_SEQ, 1)
    kw = dict(
        mesh=mesh, causal=cfg.causal, impl=cfg.impl,
        block_size=cfg.block_size,
        data_axis=axes["data"], head_axis=axes["model"],
    )

    algorithms = {"tree": tree_decode, "ring": ring_decode}
    # Ulysses re-shards the PER-SHARD head slice (a model axis divides the
    # head count first); join only when divisibility holds — an
    # inapplicable config must never lose tree/ring's results (same guard
    # shape as the train comparator).
    h_shards = mesh.shape.get("model", 1)
    hq_l, hkv_l = cfg.heads, cfg.resolved_kv_heads()
    if (
        hq_l % h_shards == 0 and hkv_l % h_shards == 0
        and (hq_l // h_shards) % n == 0 and (hkv_l // h_shards) % n == 0
    ):
        algorithms["ulysses"] = ulysses_decode

    record: Dict[str, Any] = {
        "workload": _workload(cfg, mesh=dict(mesh.shape)),
        "n_devices": mesh.size,
    }
    per_step: Dict[str, float] = {}
    for name, alg in algorithms.items():
        def step(q_, k_, v_, _alg=alg):
            return _alg(q_, k_, v_, **kw)[0]

        # Decode-step chain: the step's output has q's shape, so it feeds
        # the next step directly — n dependent steps, scalar-reduced fence.
        def mk(n_steps):
            def f(q_, k_, v_):
                def body(qc, _):
                    return step(qc, k_, v_).astype(qc.dtype), None

                out = lax.scan(body, q_, None, length=n_steps)[0]
                return jnp.sum(out.astype(jnp.float32))

            return jax.jit(f)

        with obs.span("decode_comparator", cat="bench",
                      args=None if not obs.TRACER.active else
                      {"algorithm": name, "ctx": cfg.seq_len}):
            per, _, _ = time_per_step(
                mk, q, k, v, n_small=2, n_large=max(6, cfg.iters),
                iters=max(cfg.iters, 3), warmup=max(cfg.warmup, 1), stat="min",
            )
            with obs.span("collective_stats", cat="bench",
                          args=None if not obs.TRACER.active else
                          {"algorithm": name}):
                comm = collective_stats(step, q, k, v)
        assert_loop_free(comm, f"{name}_decode")
        per_step[name] = per
        record[name] = {
            "us_per_step": round(per * 1e6, 1),
            "kv_tokens_per_sec": round(cfg.seq_len / per, 1),
            "comm": comm,
        }
    for name in per_step:
        if name != "tree":
            record[f"tree_speedup_vs_{name}"] = round(
                per_step[name] / per_step["tree"], 3
            )
    log.info(
        "decode comparator (%d-way seq, %d ctx): %s",
        n, cfg.seq_len,
        "  ".join(f"{a}={per_step[a] * 1e6:.0f}us" for a in per_step),
    )
    return record


def run_bench(cfg: RunConfig, mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """Dispatch on the config; returns the record the CLI prints as JSON."""
    if cfg.comparator == "ring-decode":
        if mesh is None:
            raise ValueError(
                "the decode comparator needs a mesh (--mesh seq=N)"
            )
        if cfg.kv_quant != "none":
            raise ValueError(
                "--kv-quant does not apply to the decode comparator "
                "(all sides run the exact decode path)"
            )
        return bench_decode_compare(cfg, mesh)
    if cfg.comparator == "ring":
        if mesh is None:
            raise ValueError("the ring comparator needs a mesh (--mesh seq=N)")
        if cfg.kv_quant != "none":
            raise ValueError(
                "--kv-quant does not apply to the tree-vs-ring comparator "
                "(both sides run the exact training-shape path)"
            )
        return bench_compare(cfg, mesh)
    return bench_decode(cfg, mesh).as_dict()
