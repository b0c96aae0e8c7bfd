#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the one model the repo supports (the Llama-style dense GQA
decoder of ``models/transformer.py`` at the published widths of 01-ai/Yi-6B:
hidden 4096, intermediate 11008, 32 heads, 4 KV heads, vocabulary 64000;
depth cut to fit one 16 GB chip beside the cache; seeded random bf16
weights), and checks what comes out by the repo's own means. One process,
one chip. It sets no platform and has no fallback: where JAX finds no TPU
it exits non-zero and prints no result.

    python chip_smoke.py             # one chip: phases a-h below
    python chip_smoke.py --chips 4   # four chips: the sequence-parallel
                                     # paths and what they are compared
                                     # with, and nothing else

Phases of the default run, one JSON object per line on stdout:

  device     a  what JAX found, versions, compile-cache directory, native lib
  kernels    b  every kernel the served/train path can select, compiled
                (``interpret=False``) at 32/4 heads x 128, against
                ``ops/reference.py`` in float32 / highest precision
  serve      c  ``cli.main(--mode serve ...)`` on its synthetic trace, paged
                layout, chunked admission, prefix cache
  ingress    c  the same engine behind ``--serve-http``: four completions
                over loopback (two sharing a prefix, one streamed, one
                cancelled mid-stream), then a drain
  serve_int8 d  the same engine with ``--kv-quant int8``
  agreement  e  logits of chunked prefill + paged decode steps against the
                plain full forward pass in float32 / highest
  train      g  ``cli.main(--mode train ...)``: three steps, loss falls
  programs   f  the compiled tick programs and the train step contain
                ``tpu_custom_call`` (run after ``train`` so its compile is
                a cache hit); the dispatch counters are printed beside
  hybrid     i  the second kind of model the repo serves whole: the conv /
                attention hybrid of ``benchmark/configs/lfm2-8b-a1b.json`` at
                its published widths through ``serve`` (K/V rows beside
                conv tails in one pool), with a prefix hit and a fork,
                its served tokens held to the plain reference's logits
  window     j  ``benchmark/configs/k-exaone-236b-a23b.json`` from its two
                pools; state: ``nemotron-3-super-120b-a12b.json`` from its
                K/V pool and its per-slot state; eva:
                ``benchmark/configs/evabyte.json`` from its exact rows' and
                its summary rows' pools (a request across three window
                boundaries, a reused slot, a slot that sits ticks out);
                parallel: ``falcon-h1-34b-instruct.json`` at 3 layers, a
                state AND K/V rows in every layer (a chunked prompt of odd
                length, a reused slot, a slot that sits ticks out),
                each held to its family's plain reference likewise
  times      h  wall time per phase and compile-cache traffic — set-up
                information only; nothing here is a rate or a benchmark

The last line is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}`` and is printed only if every phase passed.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import gc
import io
import json
import os
import signal
import sys
import tempfile
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase sizes itself from. The defaults are the real run;
    the CPU rehearsal (tests/test_chip_smoke.py) passes a tiny instance."""

    # The model: widths are Yi-6B's and are never cut; depth is.
    model_dim: int = 4096
    heads: int = 32
    kv_heads: int = 4
    vocab: int = 64000
    dtype: str = "bfloat16"
    serve_layers: int = 16   # of 32: 6.6 GB of bf16 weights beside the cache
    train_layers: int = 2    # params + grads + Adam state, twice (see train)
    # --chips 4: the CLI leaves its parameters uncommitted on device 0 and
    # every sharded call replicates them, so device 0 holds two copies.
    sharded_layers: int = 8
    # The serving trace.
    slots: int = 8
    prompt_len: int = 1280
    prompt_jitter: int = 768     # prompts span 512..2048 tokens
    max_new: int = 64
    prefix_len: int = 1024
    prefix_block: int = 64
    prefill_chunk: int = 256
    requests: int = 16
    requests_int8: int = 8
    # Agreement with the plain forward pass.
    agree_prompt: int = 1024
    agree_steps: int = 32
    # Train.
    train_seq: int = 2048
    train_steps: int = 3
    # Kernels are called with this stated: False = compiled for the chip.
    interpret: bool = False
    # Tolerances (max abs error): kernel outputs against the f32 reference,
    # by the precision the kernel carries — a few times what the first chip
    # run measured (bf16 kernels 1.0e-3..2.6e-3, int8 6e-3 (q8) and 2.9e-2
    # (q8q, Q rounded to int8 too), gradients 1.2e-2 / 3.2e-2).
    tol_kernel: float = 1e-2
    tol_kernel_int8: float = 6e-2
    tol_grad: float = 6e-2
    # Logits of the bf16 model against the f32 forward: bf16 rounding
    # through 16 layers measured 0.044 RMS on logits of RMS 1.28 (the
    # largest of 67M entries 0.26), where a wrong mask, table or chunk
    # boundary moves logits by their own magnitude. Both the largest and
    # the RMS error gate, at about twice what was measured.
    tol_logits: float = 0.5
    tol_logits_rms: float = 0.1
    # --chips 4: the tree-decode context.
    tree_heads: int = 16
    tree_ctx: int = 262144

    @property
    def d_head(self) -> int:
        return self.model_dim // self.heads

    @property
    def cache_len(self) -> int:
        return self.prompt_len + self.prompt_jitter + self.max_new

    def model_flags(self, layers: int) -> List[str]:
        return [
            "--model-dim", str(self.model_dim), "--heads", str(self.heads),
            "--kv-heads", str(self.kv_heads), "--vocab-size", str(self.vocab),
            "--n-layers", str(layers), "--dtype", self.dtype,
        ]

    def serve_flags(self, *, requests: int, int8: bool = False) -> List[str]:
        return [
            "--mode", "serve", *self.model_flags(self.serve_layers),
            "--slots", str(self.slots), "--requests", str(requests),
            "--prompt-len", str(self.prompt_len),
            "--prompt-jitter", str(self.prompt_jitter),
            "--max-new-tokens", str(self.max_new),
            "--prefill-chunk", str(self.prefill_chunk),
            "--prefix-cache", "--prefix-block", str(self.prefix_block),
            "--prefix-share", "0.5", "--prefix-len", str(self.prefix_len),
            "--temperature", "0",
            *(["--kv-quant", "int8"] if int8 else []),
        ]


class PhaseFailed(Exception):
    """A phase's own check did not hold; the message says which."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------------
# Run bookkeeping: one JSON line per phase, compile-cache traffic, wall times
# ---------------------------------------------------------------------------


class Run:
    """Prints one JSON object per phase and remembers what passed."""

    def __init__(self, cache_dir: str):
        import jax

        self.cache_dir = cache_dir
        self.phases: List[Dict[str, Any]] = []
        self._events: Dict[str, int] = {}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_: Any) -> None:
        if "compilation_cache" in event:
            key = event.rsplit("/", 1)[-1]
            self._events[key] = self._events.get(key, 0) + 1

    def _cache_entries(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.cache_dir)
                       if n.endswith("-cache"))
        except OSError:
            return 0

    def phase(self, name: str, fn: Callable[..., Dict[str, Any]],
              *args: Any, **kw: Any) -> bool:
        gc.collect()
        entries0, events0 = self._cache_entries(), dict(self._events)
        t0 = time.perf_counter()
        try:
            detail = fn(*args, **kw)
            ok = True
        except Exception as e:  # a failed phase fails the run, not the report
            traceback.print_exc()
            detail = {"error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        wall = time.perf_counter() - t0
        cache = {
            k: self._events.get(k, 0) - events0.get(k, 0)
            for k in sorted(self._events)
            if self._events.get(k, 0) != events0.get(k, 0)
        }
        cache["entries_written"] = self._cache_entries() - entries0
        line = {"phase": name, "ok": ok, "wall_s": round(wall, 2),
                **detail, "compile_cache": cache}
        self.phases.append(line)
        print(json.dumps(line), flush=True)
        return ok

    @property
    def ok(self) -> bool:
        return bool(self.phases) and all(p["ok"] for p in self.phases)


def run_cli(argv: List[str]) -> Tuple[int, Dict[str, Any]]:
    """``tree_attention_tpu.cli.main(argv)`` with its one stdout JSON record
    captured (the CLI's log lines go to stderr untouched)."""
    from tree_attention_tpu import cli

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    record: Dict[str, Any] = {}
    for line in buf.getvalue().splitlines():
        if line.startswith("{"):
            record = json.loads(line)
    return rc, record


def dispatch_counters(metrics_path: str) -> Dict[str, Any]:
    """The repo's kernel-or-reference dispatch counters out of a
    ``--metrics-out`` snapshot: which decode path, which cache kind and
    which kernel builds the run's programs resolved to (trace-time counts:
    one per program build, not per executed tick)."""
    wanted = ("decode_dispatch_total", "forward_step_dispatch_total",
              "pallas_decode_kernel_builds_total")
    try:
        with open(metrics_path) as f:
            families = json.load(f)["metrics"]
    except (OSError, ValueError, KeyError):
        return {}
    return {
        fam["name"]: {
            ",".join(f"{k}={v}" for k, v in sorted(sm["labels"].items()))
            or "_": sm["value"]
            for sm in fam["samples"]
        }
        for fam in families if fam["name"] in wanted
    }


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------


def phase_device(cache_dir: str) -> Dict[str, Any]:
    import importlib.metadata as md

    import jax

    from tree_attention_tpu.host_runtime import _so_path, native_available

    def version(dist: str) -> Optional[str]:
        try:
            return md.version(dist)
        except md.PackageNotFoundError:
            return None

    devs = jax.devices()
    native = native_available()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
        "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "native_library_loaded": native,
        "native_library": _so_path() if native else None,
    }


# ---------------------------------------------------------------------------
# b. kernels against the plain reference
# ---------------------------------------------------------------------------


def _timed(fn: Callable[[], Any]) -> Tuple[Any, float, float]:
    """``fn()`` twice behind block_until_ready: (result, first, later) —
    the first call compiles."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def _errors(got, want) -> Tuple[float, float]:
    """(max abs error, that over the reference's max magnitude), float32;
    ``-inf`` entries (empty rows of an lse) must match exactly."""
    import numpy as np

    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    finite = np.isfinite(w)
    if not np.array_equal(finite, np.isfinite(g)):
        return float("inf"), float("inf")
    if not finite.any():
        return 0.0, 0.0
    err = float(np.max(np.abs(g[finite] - w[finite])))
    return err, err / max(float(np.max(np.abs(w[finite]))), 1e-30)


def phase_kernels(s: Sizes) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tree_attention_tpu.models.decode import (
        _paged_pool_write,
        _row_targets,
    )
    from tree_attention_tpu.ops.decode import gather_paged_kv
    from tree_attention_tpu.ops.pallas_attention import attention_pallas_fwd
    from tree_attention_tpu.ops.pallas_bwd import attention_bwd_pallas
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_decode,
        attention_pallas_decode_q8,
        attention_pallas_decode_q8q,
        paged_row_write,
    )
    from tree_attention_tpu.ops.reference import (
        attention_naive,
        merge_partials,
    )
    from tree_attention_tpu.ops.tuning import (
        default_block_q,
        default_block_q_bwd,
        default_block_size,
    )

    dtype = jnp.dtype(s.dtype)
    B, Hq, Hkv, D, blk = s.slots, s.heads, s.kv_heads, s.d_head, s.prefix_block
    NB = -(-s.cache_len // blk)
    N = B * NB
    rng = np.random.default_rng(0)
    it = s.interpret

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    k_pool, v_pool = normal((N, Hkv, blk, D)), normal((N, Hkv, blk, D))
    kq = jnp.asarray(rng.integers(-127, 128, (N, Hkv, blk, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (N, Hkv, blk, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.03, (N, Hkv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.03, (N, Hkv)), jnp.float32)
    # Every slot's table is a scattered, non-monotone set of pool blocks.
    table = jnp.asarray(rng.permutation(N).reshape(B, NB), jnp.int32)

    def positions(tq: int):
        return jnp.asarray(
            rng.integers(blk, NB * blk - tq, (B,)), jnp.int32)

    @jax.jit
    def reference(q, k_p, v_p, pos, tree_mask=None):
        """ops/reference.py over the gathered logical view, f32/highest,
        one slot at a time (each at its own position)."""
        kf, vf = gather_paged_kv(
            k_p.astype(jnp.float32), v_p.astype(jnp.float32), table)

        def one(q_b, k_b, v_b, pos_b, *tm):
            o, l = attention_naive(
                q_b[None], k_b[None], v_b[None], causal=True,
                q_offset=pos_b, tree_mask=tm[0][None] if tm else None)
            return o[0], l[0]

        args = (q.astype(jnp.float32), kf, vf, pos)
        if tree_mask is not None:
            args += (tree_mask,)
        with jax.default_matmul_precision("highest"):
            return jax.vmap(one)(*args)

    def dequant(pool_q, scale):
        return pool_q.astype(jnp.float32) * scale[:, :, None, None]

    results: Dict[str, Any] = {}

    def record(name, got, want, first, later, tol):
        errs = [_errors(g, w) for g, w in zip(got, want)]
        err = max(e[0] for e in errs)
        results[name] = {
            "max_abs_err": err, "max_rel_err": max(e[1] for e in errs),
            "tol": tol, "ok": bool(err <= tol),
            "first_call_s": round(first, 3), "later_call_s": round(later, 4),
        }

    def paged(name, tq, tol, call, ref_pools, tree=False):
        q, pos = normal((B, Hq, tq, D)), positions(tq)
        tm = None
        if tree:
            tm = jnp.asarray(
                np.tril(rng.random((B, tq, tq)) < 0.6)
                | np.eye(tq, dtype=bool)[None])
        got, first, later = _timed(lambda: call(q, pos, tm))
        record(name, got, reference(q, *ref_pools, pos, tm), first, later,
               tol)

    exact = (k_pool, v_pool)
    deq = (dequant(kq, ks), dequant(vq, vs))
    kw = dict(causal=True, block_table=table, interpret=it)

    def exact_call(q, pos, tm):
        return attention_pallas_decode(
            q, k_pool, v_pool, q_offset=pos, tree_mask=tm, **kw)

    def int8_call(kernel):
        return lambda q, pos, tm: kernel(
            q, kq, vq, ks, vs, q_offset=pos, **kw)

    paged("paged_decode_tq1", 1, s.tol_kernel, exact_call, exact)
    paged("paged_chunk_tq64", min(64, s.prefill_chunk), s.tol_kernel,
          exact_call, exact)
    paged("paged_tree_verify_tq8", 8, s.tol_kernel, exact_call, exact,
          tree=True)
    paged("paged_int8_q8q_block_scales", 1, s.tol_kernel_int8,
          int8_call(attention_pallas_decode_q8q), deq)
    paged("paged_int8_q8_block_scales", 1, s.tol_kernel_int8,
          int8_call(attention_pallas_decode_q8), deq)

    # local_blocks: two shards own alternate logical blocks (signed local
    # tables); their partials, merged by the tree monoid, are the whole.
    q, pos = normal((B, Hq, 1, D)), positions(1)
    own0 = (jnp.arange(NB) % 2 == 0)[None, :]

    def partials():
        parts = [
            attention_pallas_decode(
                q, k_pool, v_pool, q_offset=pos, local_blocks=True,
                **{**kw, "block_table": jnp.where(own, table, -1)})
            for own in (own0, ~own0)
        ]
        return merge_partials(
            jnp.stack([p[0] for p in parts]), jnp.stack([p[1] for p in parts]))

    got, first, later = _timed(jax.jit(partials))
    record("paged_local_blocks_partial", got, reference(q, *exact, pos),
           first, later, s.tol_kernel)

    # The decode rows' write (ISSUE 39): one new row a slot through the
    # 8-row tile that holds it, K and V in one call, against the block
    # path's pools, bit for bit. Slot 0 is idle and (of more than two) the
    # last slot past its capacity: both write nothing.
    rows = normal((B, Hkv, 1, D)), normal((B, Hkv, 1, D))
    start = positions(1)
    if B > 2:
        start = start.at[B - 1].set(NB * blk)
    n_new = jnp.ones((B,), jnp.int32).at[0].set(0)

    def row_write():
        ids, off = _row_targets(table, start, n_new, N, blk)
        return paged_row_write(
            (k_pool, v_pool), rows, ids, off, 0, interpret=it)

    got, first, later = _timed(jax.jit(row_write))
    want = [_paged_pool_write(p[None], r, table, start, n_new, 0)[0]
            for p, r in zip((k_pool, v_pool), rows)]
    record("paged_row_write_tq1", got, want, first, later, 0.0)
    check(not np.array_equal(np.asarray(got[0]), np.asarray(k_pool)),
          "paged_row_write_tq1 wrote nothing")

    # The conv layers' decode step (ISSUE 48): a row a slot through the
    # tail pool in one launch (the read of z at p-1 and p-2, the gates, the
    # taps, the write of z at p), bf16 at the hybrid's width (2048), conv
    # layer 3 of 4. Slot 0 is idle, slot 1 at a block's first position (its
    # z before lie in the block before), the last slot (of more than two)
    # past its capacity. The POOL against the XLA path's, bit for bit. The
    # ROWS bit for bit against the XLA path's arithmetic with every
    # rounding the source states pinned (`lax.reduce_precision`): on the
    # chip XLA itself keeps `z` and `s` in float32 between a fusion's
    # operations (`xla_allow_excess_precision`: a bf16 value converted up
    # again is never rounded), so its own rows lie within one rounding of
    # these, which is checked beside; on the CPU it rounds as stated, and
    # tier-1 holds the kernel to that path's bits.
    from tree_attention_tpu.models.decode import _RowGroup
    from tree_attention_tpu.models.hybrid import _tail_rows, _tail_step
    from tree_attention_tpu.ops.pallas_conv import (
        conv_tail_plan, conv_tail_step)

    dc, bf16, f32 = min(s.model_dim, 2048), jnp.bfloat16, jnp.float32
    Nc = -(-N // 8) * 8       # a layer's blocks: a multiple of the cut
    tails = jnp.asarray(
        rng.standard_normal((4 * Nc, 2 * dc), np.float32), bf16)
    bcu = jnp.asarray(rng.standard_normal((B, 3 * dc), np.float32), bf16)
    taps = jnp.asarray(rng.standard_normal((3, dc), np.float32), bf16)
    start_c = start.at[1].set(2 * blk) if B > 1 else start

    def tail_step():
        plan = conv_tail_plan(table, start_c, n_new, Nc, blk)
        pool, out = conv_tail_step(tails, bcu, taps, plan, 3 * Nc,
                                   interpret=it)
        return pool, jnp.where((plan.ids >= 0)[:, None], out, 0)

    @jax.jit
    def tail_xla():
        """(the XLA path's pool, its rows, its rows with the roundings
        pinned)."""
        g = _RowGroup(lo=None, batch=B, tq=1, start=start_c, n=n_new,
                      table=table, tree_mask=None)
        writes = (_row_targets(table, start_c, n_new, Nc, blk)[0]
                  >= 0)[:, None]
        w32 = taps.astype(f32)
        pool, out, _ = _tail_step(tails, bcu[:, None], w32, g, 3, Nc, blk)
        out = out[:, 0]
        before = [_tail_rows(tails, g, 3, back, Nc, blk).astype(f32)
                  for back in (2, 1)]
        pin = lambda a: lax.reduce_precision(a, 8, 7)   # a bf16's bits
        zp = pin(bcu[:, :dc].astype(f32) * bcu[:, 2 * dc:].astype(f32))
        sp = pin((w32[0] * before[0] + w32[1] * before[1]) + w32[2] * zp)
        pinned = pin(bcu[:, dc:2 * dc].astype(f32) * sp).astype(bf16)
        return (pool, jnp.where(writes, out, 0),
                jnp.where(writes, pinned, 0))

    got, first, later = _timed(jax.jit(tail_step))
    want_pool, xla_rows, want_rows = tail_xla()
    record("conv_tail_step_tq1", got, (want_pool, want_rows), first, later,
           0.0)
    check(not np.array_equal(np.asarray(got[0]), np.asarray(tails)),
          "conv_tail_step_tq1 wrote nothing")
    results["conv_tail_step_tq1"]["xla_rows_max_rel_err"] = _errors(
        got[1], xla_rows)[1]
    check(results["conv_tail_step_tq1"]["xla_rows_max_rel_err"] <= 2 ** -7,
          "conv_tail_step_tq1's rows lie further from XLA's own than a "
          "rounding")

    # Prefill forward: a chunk-wide Q tile against the gathered view, each
    # slot at its own offset (what a mixed tick at the chunk bucket runs).
    tq = s.prefill_chunk
    q, pos = normal((B, Hq, tq, D)), positions(tq)
    kv_view = gather_paged_kv(k_pool, v_pool, table)
    got, first, later = _timed(lambda: attention_pallas_fwd(
        q, *kv_view, causal=True, q_offset=pos, kv_offset=0,
        block_size=512, interpret=it))
    record("prefill_fwd", got, reference(q, *exact, pos), first, later,
           s.tol_kernel)

    # Backward: dQ and dK/dV kernels at the train sequence length and the
    # tuned tiles, against autodiff of the f32 reference.
    T = s.train_seq
    bk = default_block_size("pallas", T)
    q, k, v, dout = (normal((1, Hq, T, D)), normal((1, Hkv, T, D)),
                     normal((1, Hkv, T, D)), normal((1, Hq, T, D)))

    def fwd_bwd():
        out, lse = attention_pallas_fwd(
            q, k, v, causal=True, block_size=bk,
            block_q=default_block_q(T, T), interpret=it)
        return attention_bwd_pallas(
            q, k, v, out, lse, dout, jnp.zeros_like(lse), causal=True,
            scale=None, block_size=bk,
            block_q=default_block_q_bwd(T, T, bk), interpret=it)

    (dq, dk, dv), first, later = _timed(fwd_bwd)
    @jax.jit
    def reference_grads(q_, k_, v_, dout_):
        f32 = lambda x: x.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(
                lambda *qkv: attention_naive(*qkv, causal=True)[0],
                f32(q_), f32(k_), f32(v_))
            return vjp(f32(dout_))

    rq, rk, rv = reference_grads(q, k, v, dout)
    record("bwd_dq", (dq,), (rq,), first, later, s.tol_grad)
    record("bwd_dkv", (dk, dv), (rk, rv), first, later, s.tol_grad)

    bad = sorted(n for n, r in results.items() if not r["ok"])
    check(not bad, f"kernels outside tolerance: {bad}: "
                   f"{ {n: results[n] for n in bad} }")
    return {
        "shape": {"q_heads": Hq, "kv_heads": Hkv, "head_dim": D,
                  "slots": B, "pool_blocks": N, "kv_block": blk,
                  "table_width": NB, "dtype": s.dtype,
                  "interpret": s.interpret},
        "reference": "ops/reference.py attention_naive, float32, "
                     "matmul precision highest",
        "kernels": results,
    }


# ---------------------------------------------------------------------------
# c/d. serve: the CLI's engine on its synthetic trace, then behind HTTP
# ---------------------------------------------------------------------------


def _check_serve_record(rc: int, rec: Dict[str, Any], n: int,
                        max_new: int, cancelled: int = 0,
                        want_hit: bool = True) -> None:
    check(rc == 0, f"cli.main returned {rc}")
    check(rec.get("requests") == n,
          f"served {rec.get('requests')} of {n} requests")
    want = {"budget": n - cancelled}
    if cancelled:
        want["cancelled"] = cancelled
    got = {k: v for k, v in (rec.get("outcomes") or {}).items() if v}
    check(got == want, f"outcomes {got}, wanted {want}")
    if not cancelled:
        check(rec.get("tokens_generated") == n * max_new,
              f"{rec.get('tokens_generated')} tokens generated, wanted "
              f"{n} x {max_new}")
    prefix, kv = rec.get("prefix") or {}, rec.get("kv") or {}
    check(prefix.get("hits", 0) >= 1 or not want_hit,
          f"no prefix hit: {prefix}")
    # No leak at drain: all that is left in the pool is the radix tree's
    # retained cache.
    check(kv.get("blocks_used") == prefix.get("pool_blocks_used"),
          f"pool holds {kv.get('blocks_used')} blocks but the prefix cache "
          f"retains {prefix.get('pool_blocks_used')}: a slot leaked blocks")


def _serve_summary(rec: Dict[str, Any]) -> Dict[str, Any]:
    keep = ("requests", "tokens_generated", "ticks", "outcomes", "cache_len",
            "prefill_chunk", "kv_quant", "prefix", "kv")
    out = {k: rec[k] for k in keep if k in rec}
    out["serve_wall_s"] = rec.get("wall_s")
    return out


def phase_serve(s: Sizes, *, int8: bool = False) -> Dict[str, Any]:
    n = s.requests_int8 if int8 else s.requests
    with tempfile.TemporaryDirectory() as tmp:
        metrics = os.path.join(tmp, "metrics.json")
        rc, rec = run_cli(
            s.serve_flags(requests=n, int8=int8) + ["--metrics-out", metrics])
        counters = dispatch_counters(metrics)
    _check_serve_record(rc, rec, n, s.max_new)
    return {"entry": "tree_attention_tpu.cli.main", "layers": s.serve_layers,
            **_serve_summary(rec), "dispatch_counters": counters}


class _Client:
    """The loopback client of the ingress phase; runs on its own thread
    while ``cli.main`` blocks the main one until the drain."""

    def __init__(self, s: Sizes, port: int):
        self.s, self.port = s, port
        self.results: Dict[str, Any] = {}
        self.error: Optional[str] = None
        self.thread = threading.Thread(
            target=self._run, name="smoke-client", daemon=True)

    def _conn(self, timeout: float = 900.0):
        import http.client

        return http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)

    def _post(self, body: Dict[str, Any]):
        conn = self._conn()
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        return conn, conn.getresponse()

    def _whole(self, prompt: List[int]) -> Dict[str, Any]:
        conn, resp = self._post({"prompt": prompt, "stream": False,
                                 "max_tokens": self.s.max_new})
        try:
            body = json.loads(resp.read())
        finally:
            conn.close()
        check(resp.status == 200, f"completion answered {resp.status}")
        return body

    @staticmethod
    def _events(resp):
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data: "):
                yield line[len("data: "):]

    def _ready(self, deadline_s: float) -> bool:
        t_end = time.monotonic() + deadline_s
        while time.monotonic() < t_end:
            try:
                conn = self._conn(timeout=2.0)
                conn.request("GET", "/ingress/stats")
                ready = json.loads(conn.getresponse().read()).get("ready")
                conn.close()
                if ready:
                    return True
            except (OSError, ValueError):
                pass
            time.sleep(0.2)
        return False

    def _drain(self) -> None:
        for _ in range(50):
            try:
                conn = self._conn(timeout=5.0)
                conn.request("POST", "/admin/drain", b"")
                conn.getresponse().read()
                conn.close()
                return
            except OSError:
                time.sleep(0.2)

    def _run(self) -> None:
        import numpy as np

        s, rng = self.s, np.random.default_rng(11)
        tokens = lambda n: [int(t) for t in rng.integers(0, s.vocab, n)]
        try:
            check(self._ready(600.0), "ingress never became ready")
            shared = tokens(s.prefix_len)
            tail = max(s.prefix_block, 8)
            # Two requests sharing a prefix, one after the other: the
            # second must be served from the first's published blocks.
            a = self._whole(shared + tokens(tail))
            b = self._whole(shared + tokens(tail))
            for name, r in (("shared_a", a), ("shared_b", b)):
                n_tok = len(r["choices"][0]["token_ids"])
                check(n_tok == s.max_new and
                      r["choices"][0]["finish_reason"] == "length",
                      f"{name}: {n_tok} tokens, "
                      f"{r['choices'][0]['finish_reason']}")
                self.results[name] = {
                    "completion_tokens": n_tok,
                    "prefix_hit_tokens": r["usage"]["prefix_hit_tokens"]}
            floor = s.prefix_len // s.prefix_block * s.prefix_block
            check(b["usage"]["prefix_hit_tokens"] >= floor,
                  f"second sharer hit {b['usage']['prefix_hit_tokens']} "
                  f"prefix tokens, wanted >= {floor}")
            # One streamed to the end.
            conn, resp = self._post({
                "prompt": tokens(s.prompt_len - s.prompt_jitter),
                "max_tokens": s.max_new})
            n_tok, finish, done = 0, None, False
            for data in self._events(resp):
                if data == "[DONE]":
                    done = True
                    break
                choice = json.loads(data)["choices"][0]
                n_tok += len(choice["token_ids"])
                finish = choice["finish_reason"] or finish
            conn.close()
            check(done and n_tok == s.max_new and finish == "length",
                  f"streamed: {n_tok} tokens, finish {finish}, done {done}")
            self.results["streamed"] = {"completion_tokens": n_tok,
                                        "finish_reason": finish}
            # One cancelled mid-stream: ask for all the slot can hold and
            # hang up after two tokens.
            short = s.prompt_len - s.prompt_jitter
            conn, resp = self._post({
                "prompt": tokens(short),
                "max_tokens": s.cache_len - short})
            got = 0
            for data in self._events(resp):
                got += len(json.loads(data)["choices"][0]["token_ids"])
                if got >= 2:
                    break
            resp.close()
            conn.close()
            self.results["cancelled"] = {"tokens_before_hangup": got}
            # The engine learns of the hang-up at its next write to the
            # dead socket and cancels; the drain below waits for that.
        except Exception as e:
            traceback.print_exc()
            self.error = f"{type(e).__name__}: {e}"
        finally:
            self._drain()


def phase_ingress(s: Sizes) -> Dict[str, Any]:
    """``cli.main(--mode serve --serve-http PORT)`` on this (the main)
    thread — it blocks until a drain — with the client on another; the
    client ends by POSTing /admin/drain, which is what returns the CLI."""
    from tree_attention_tpu.cli import _pick_free_port

    port = _pick_free_port()
    client = _Client(s, port)
    handlers = {sig: signal.getsignal(sig)
                for sig in (signal.SIGTERM, signal.SIGINT)}
    client.thread.start()
    try:
        with tempfile.TemporaryDirectory() as tmp:
            metrics = os.path.join(tmp, "metrics.json")
            rc, rec = run_cli(
                s.serve_flags(requests=0)
                + ["--serve-http", str(port), "--metrics-out", metrics])
            counters = dispatch_counters(metrics)
    finally:
        client.thread.join(timeout=60.0)
        for sig, handler in handlers.items():  # the CLI installed its own
            signal.signal(sig, handler)
    check(not client.thread.is_alive(), "client thread did not finish")
    check(client.error is None, f"client: {client.error}")
    _check_serve_record(rc, rec, 4, s.max_new, cancelled=1)
    return {"entry": "tree_attention_tpu.cli.main --serve-http",
            "layers": s.serve_layers, "client": client.results,
            **_serve_summary(rec), "dispatch_counters": counters}


# ---------------------------------------------------------------------------
# e. agreement with the plain forward pass
# ---------------------------------------------------------------------------


def _serve_setup(s: Sizes, mesh=None, extra: Tuple[str, ...] = ()):
    """The model and engine factory the CLI builds for the serve phases —
    through the CLI's own function, not a copy of it."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    return cli.build_serve_engine(
        parse_args(s.serve_flags(requests=0) + list(extra)), mesh)


def served_logits(s: Sizes, params, tcfg, tokens, *, mesh=None,
                  kv_shard: str = "replicated"):
    """Logits of every position of ``tokens`` the way the engine computes
    them: chunked prefill of the prompt, then one paged decode step per
    remaining token, over a paged cache whose table scatters the slot's
    blocks. Returns ``(logits (T, V) float32, cache, step wall times)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tree_attention_tpu.models.decode import forward_step, init_paged_cache

    blk = s.prefix_block
    shards = 1 if mesh is None else mesh.size
    nb = -(-tokens.shape[1] // blk)
    blocks = -(-nb // shards) * shards
    cache = init_paged_cache(
        tcfg, 1, nb * blk, blocks, block=blk, mesh=mesh, kv_shard=kv_shard)
    perm = np.random.default_rng(5).permutation(blocks)[:nb]
    table, length = jnp.asarray(perm[None], jnp.int32), cache.length
    if mesh is not None:  # placed like the step's own outputs: one compile
        from jax.sharding import NamedSharding, PartitionSpec

        table, length = jax.device_put(
            (table, length), NamedSharding(mesh, PartitionSpec()))
    cache = dataclasses.replace(cache, table=table, length=length)
    kw = {} if mesh is None else {"mesh": mesh, "kv_shard": kv_shard}

    @jax.jit
    def step(params, toks, cache):
        n = jnp.full((1,), toks.shape[1], jnp.int32)
        return forward_step(params, toks, cache, tcfg, n_tokens=n, **kw)

    rows, walls = [], {"chunk": [], "decode": []}
    P = s.agree_prompt
    spans = [(lo, min(lo + s.prefill_chunk, P), "chunk")
             for lo in range(0, P, s.prefill_chunk)]
    spans += [(i, i + 1, "decode") for i in range(P, tokens.shape[1])]
    for lo, hi, kind in spans:
        t0 = time.perf_counter()
        logits, cache = jax.block_until_ready(
            step(params, tokens[:, lo:hi], cache))
        walls[kind].append(time.perf_counter() - t0)
        rows.append(logits[0])
    # Set-up information: the first call of each shape compiles.
    timing = {
        f"{kind}_{which}_s": round(val, 4)
        for kind, w in walls.items() if w
        for which, val in (("first", w[0]),
                           ("later_median", sorted(w[1:])[len(w[1:]) // 2]
                            if len(w) > 1 else None))
        if val is not None
    }
    return jnp.concatenate(rows, axis=0), cache, timing


def _agreement_tokens(s: Sizes):
    import jax

    return jax.random.randint(
        jax.random.PRNGKey(7), (1, s.agree_prompt + s.agree_steps), 0,
        s.vocab)


REFERENCE_FORWARD = ("models/transformer.py forward, float32 activations, "
                     "matmul precision highest, naive attention")


def reference_logits(params, tcfg, tokens):
    """The plain forward pass over ``tokens`` in float32 at the highest
    matmul precision: float32 embeddings make every activation float32, and
    each layer's (bf16-valued) weights are promoted inside the layer scan,
    so no float32 copy of the whole stack is ever held. ``(T, V)``."""
    import jax
    import jax.numpy as jnp

    from tree_attention_tpu.models.transformer import forward

    ref_params = {
        **params,
        "embed": params["embed"].astype(jnp.float32),
        "wout": params["wout"].astype(jnp.float32),
    }
    ref_cfg = dataclasses.replace(tcfg, attn_impl="naive", remat=False)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: forward(p, t, ref_cfg))(
            ref_params, tokens)[0]


def _logit_errors(got, want) -> Dict[str, float]:
    import jax.numpy as jnp

    return {"max_abs_err": _errors(got, want)[0],
            "rms_err": float(jnp.sqrt(jnp.mean(jnp.square(got - want))))}


def _check_logits(s: Sizes, what: str, errs: Dict[str, float]) -> None:
    check(errs["max_abs_err"] <= s.tol_logits
          and errs["rms_err"] <= s.tol_logits_rms,
          f"{what}: {errs} (tolerance {s.tol_logits} max abs, "
          f"{s.tol_logits_rms} RMS)")


def phase_agreement(s: Sizes) -> Dict[str, Any]:
    import jax.numpy as jnp

    setup = _serve_setup(s)
    tokens = _agreement_tokens(s)
    got, _, timing = served_logits(s, setup.params, setup.tcfg, tokens)
    want = reference_logits(setup.params, setup.tcfg, tokens)
    P = s.agree_prompt
    errs = _logit_errors(got, want)
    detail = {
        "layers": s.serve_layers, "prompt_tokens": P,
        "decode_steps": s.agree_steps, "prefill_chunk": s.prefill_chunk,
        "reference": REFERENCE_FORWARD, **errs,
        "max_abs_err_prefill": _errors(got[:P], want[:P])[0],
        "max_abs_err_decode": _errors(got[P:], want[P:])[0],
        "logit_rms": float(jnp.sqrt(jnp.mean(jnp.square(want)))),
        "logit_max_abs": float(jnp.max(jnp.abs(want))),
        "tol_max_abs": s.tol_logits, "tol_rms": s.tol_logits_rms,
        "step_wall": timing,
    }
    _check_logits(s, f"logits differ from the plain forward pass "
                     f"({detail})", errs)
    return detail


# ---------------------------------------------------------------------------
# g. train
# ---------------------------------------------------------------------------


def _write_corpus(path: str, vocab: int, n_tokens: int) -> None:
    """A seeded corpus with something to learn: a short cycle of token ids
    (so the loss of a random model falls within a few steps), int32."""
    import numpy as np

    rng = np.random.default_rng(3)
    cycle = rng.integers(0, vocab, 61)
    np.resize(cycle, n_tokens).astype(np.int32).tofile(path)


def phase_train(s: Sizes) -> Dict[str, Any]:
    import math

    with tempfile.TemporaryDirectory() as tmp:
        corpus = os.path.join(tmp, "corpus.i32")
        _write_corpus(corpus, s.vocab, 64 * (s.train_seq + 1))
        rc, rec = run_cli([
            "--mode", "train", *s.model_flags(s.train_layers),
            "--seq-len", str(s.train_seq), "--batch", "1",
            "--steps", str(s.train_steps), "--iters", "1",
            "--data", corpus,
        ])
    check(rc == 0, f"cli.main returned {rc}")
    losses = rec.get("losses") or []
    check(len(losses) == s.train_steps, f"{len(losses)} losses: {losses}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {s.train_steps} steps: {losses}")
    return {"entry": "tree_attention_tpu.cli.main", "layers": s.train_layers,
            "seq_len": s.train_seq, "losses": losses,
            "later_step_s": rec.get("median_s")}


# ---------------------------------------------------------------------------
# f. the kernels really are on the path
# ---------------------------------------------------------------------------


def phase_programs(s: Sizes) -> Dict[str, Any]:
    """Compile the same jitted callables the serve and train phases ran
    (cache hits by now) and look for Pallas kernels in the optimized HLO."""
    import jax

    from tree_attention_tpu.bench.comm import pallas_kernels

    def kernels_of(lowered) -> Dict[str, int]:
        return pallas_kernels(lowered.compile().as_text())

    found: Dict[str, Dict[str, int]] = {}
    for label, extra in (("serve", ()), ("serve_int8", ("--kv-quant", "int8"))):
        engine = _serve_setup(s, extra=extra).make_engine()
        # Every Tq bucket a serve phase can have dispatched: the pure-decode
        # tick and the engine's own chunk buckets.
        buckets = sorted({1} | {engine._chunk_bucket(n)
                                for n in range(1, s.prefill_chunk + 1)})
        for tq in buckets:
            for name, lowered in engine.lower_programs(tq).items():
                if label == "serve_int8" and name == "mixed" and tq > 1:
                    continue  # int8 prompts are staged: mixed runs at Tq=1
                found[f"{label}.{name}.tq{tq}"] = kernels_of(lowered)
        del engine
        gc.collect()

    from tree_attention_tpu import cli
    from tree_attention_tpu.models import (
        default_optimizer,
        init_params,
        make_train_step,
    )
    from tree_attention_tpu.utils.config import parse_args

    cfg = parse_args(["--mode", "train", *s.model_flags(s.train_layers),
                      "--seq-len", str(s.train_seq)])
    tcfg, opt = cli._transformer_config(cfg), default_optimizer()
    p_shapes = jax.eval_shape(
        lambda k: init_params(k, tcfg), jax.random.PRNGKey(0))
    o_shapes = jax.eval_shape(opt.init, p_shapes)
    batch = {k: jax.ShapeDtypeStruct((1, s.train_seq), "int32")
             for k in ("inputs", "targets")}
    found["train.step"] = kernels_of(
        make_train_step(tcfg, opt, mesh=None, donate=True).lower(
            (p_shapes, o_shapes), batch))

    n_params = sum(int(x.size) for x in jax.tree.leaves(p_shapes))
    state_bytes = sum(
        int(x.size) * x.dtype.itemsize
        for x in jax.tree.leaves((p_shapes, o_shapes)))
    if not s.interpret:
        bare = sorted(n for n, k in found.items() if not k)
        check(not bare, f"programs with no Pallas call: {bare}")
        check({"flash_bwd_dq", "flash_bwd_dkv"} <= set(found["train.step"]),
              f"train step lacks a backward kernel: {found['train.step']}")
    return {
        "hlo": "lower(...).compile().as_text() of the engine's jitted "
               "tick programs and of make_train_step",
        "kernels_found": found,
        "train_params": n_params,
        "train_state_bytes_per_param": round(state_bytes / n_params, 2),
        "train_note": "params + Adam moments; the CLI's timing step does "
                      "not donate, so a second copy of the state plus the "
                      "gradients is resident at peak",
    }


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------


def phase_tree_decode(s: Sizes, mesh) -> Dict[str, Any]:
    """``tree_decode`` over ``seq=N``: sharded KV, one process, against the
    single-device flash decode on the same seeded data."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tree_attention_tpu.bench.comm import (
        collectives_in_hlo,
        pallas_kernels,
    )
    from tree_attention_tpu.ops import flash_attention
    from tree_attention_tpu.parallel.tree import tree_decode

    dtype = jnp.dtype(s.dtype)
    H, D, T = s.tree_heads, s.d_head, s.tree_ctx
    kv_sharding = NamedSharding(mesh, P(None, None, "seq", None))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)

    def make_kv(key):
        return jax.jit(
            lambda k: jax.random.normal(k, (1, H, T, D), jnp.float32)
            .astype(dtype), out_shardings=kv_sharding)(key)

    q = jax.random.normal(kq, (1, H, 1, D), jnp.float32).astype(dtype)
    k, v = make_kv(kk), make_kv(kv)
    homes = sorted({sh.device.id for sh in k.addressable_shards}
                   | {sh.device.id for sh in v.addressable_shards})
    check(len(homes) == mesh.size,
          f"K/V shards live on devices {homes}, wanted {mesh.size} distinct")

    fn = jax.jit(lambda q_, k_, v_: tree_decode(
        q_, k_, v_, mesh=mesh, causal=True))
    (out, lse), first, later = _timed(lambda: fn(q, k, v))
    text = fn.lower(q, k, v).compile().as_text()
    comm, kernels = collectives_in_hlo(text), pallas_kernels(text)

    one = jax.devices()[0]
    k1, v1 = jax.device_put(k, one), jax.device_put(v, one)
    want_out, want_lse = flash_attention(
        jax.device_put(q, one), k1, v1, causal=True, q_offset=T - 1,
        custom_vjp=False)
    err_out, _ = _errors(out, want_out)
    err_lse, _ = _errors(lse, want_lse)
    check(err_out <= s.tol_kernel and err_lse <= s.tol_kernel,
          f"tree_decode differs from the single-device decode: out "
          f"{err_out}, lse {err_lse} (tolerance {s.tol_kernel})")
    if not s.interpret:
        check(comm["ops"].get("all-reduce", {}).get("count", 0) >= 1,
              f"no all-reduce in the merge: {comm['ops']}")
        check("all-gather" not in comm["ops"],
              f"the compiled module gathers: {comm['ops']}")
        check(bool(kernels), "no Pallas call in the sharded decode")
    return {
        "mesh": dict(mesh.shape), "heads": H, "head_dim": D, "context": T,
        "kv_bytes": 2 * H * T * D * dtype.itemsize,
        "shard_device_ids": homes, "collectives": comm["ops"],
        "kernels_found": kernels,
        "max_abs_err_out": err_out, "max_abs_err_lse": err_lse,
        "tol": s.tol_kernel,
        "first_call_s": round(first, 3), "later_call_s": round(later, 4),
    }


def _memory_per_device() -> Dict[str, Any]:
    import jax

    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out[str(d.id)] = {k: stats.get(k)
                          for k in ("bytes_in_use", "peak_bytes_in_use")}
    return out


def phase_serve_seq_sharded(s: Sizes, mesh) -> Dict[str, Any]:
    """``--kv-shard seq`` over the mesh: the engine's step on a block-
    sharded pool agrees with the same step on one device, each shard holds
    its share of the pool, and the CLI serves a short trace on it."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = dataclasses.replace(s, serve_layers=s.sharded_layers)
    mesh_flags = ("--mesh", f"seq={mesh.size}", "--kv-shard", "seq")
    setup = _serve_setup(s, mesh=mesh, extra=mesh_flags)
    tokens = _agreement_tokens(s)
    one, _, timing_one = served_logits(s, setup.params, setup.tcfg, tokens)
    want = reference_logits(setup.params, setup.tcfg, tokens)
    params = jax.device_put(setup.params, NamedSharding(mesh, P()))
    got, cache, timing = served_logits(
        s, params, setup.tcfg, tokens, mesh=mesh, kv_shard="seq")
    # Both paths carry bf16 rounding through the depth (each shard's
    # partial is rounded before the merge), so each is held to the plain
    # float32 forward, and the two to each other, by the same tolerance.
    errors = {
        "sharded_vs_reference": _logit_errors(got, want),
        "one_device_vs_reference": _logit_errors(one, want),
        "sharded_vs_one_device": _logit_errors(got, one),
    }
    for what, errs in errors.items():
        _check_logits(s, f"seq-sharded serve, {what} ({errors})", errs)
    blocks = cache.k.shape[1]
    shard_blocks = sorted(
        (sh.device.id, sh.data.shape[1]) for sh in cache.k.addressable_shards)
    check(len({d for d, _ in shard_blocks}) == mesh.size
          and all(n == blocks // mesh.size for _, n in shard_blocks),
          f"pool shards {shard_blocks} of {blocks} blocks over "
          f"{mesh.size} devices")
    memory_after_step = _memory_per_device()
    del params, cache, got, one, want, setup
    gc.collect()

    # The CLI's own engine on the mesh: a short trace of whole-chunk
    # prompts (two tick programs), every request to its budget. All four
    # are admitted at once, so none can hit what another publishes.
    n = 4
    short = dataclasses.replace(
        s, prompt_len=s.agree_prompt, prompt_jitter=0,
        max_new=s.agree_steps // 2)
    rc, rec = run_cli(short.serve_flags(requests=n) + list(mesh_flags))
    _check_serve_record(rc, rec, n, short.max_new, want_hit=False)
    return {
        "mesh": dict(mesh.shape), "layers": s.serve_layers,
        "reference": REFERENCE_FORWARD, "errors": errors,
        "tol_max_abs": s.tol_logits, "tol_rms": s.tol_logits_rms,
        "step_wall_sharded": timing,
        "step_wall_one_device": timing_one, "pool_blocks": blocks,
        "blocks_per_shard": dict(shard_blocks),
        "memory_after_sharded_step": memory_after_step,
        "cli_serve": _serve_summary(rec),
        "memory_after_cli_serve": _memory_per_device(),
    }


def phase_fleet_placement(s: Sizes) -> Dict[str, Any]:
    """Report, do not fix: where the parameters of each in-process replica
    of ``--serve-fleet --replicas N`` live. The CLI hands every
    ``LocalReplica`` the same ``make_engine`` with no device, so this
    builds the engines the way it does and looks."""
    import jax

    n = len(jax.devices())
    small = dataclasses.replace(s, serve_layers=1)
    setup = _serve_setup(small, extra=("--serve-fleet", "--replicas", str(n)))
    homes = []
    for _ in range(n):
        engine = setup.make_engine()
        devs = set()
        for leaf in jax.tree.leaves((engine.params, engine.cache)):
            devs |= {d.id for d in leaf.devices()}
        homes.append(sorted(devs))
        del engine
    return {"replicas": n, "layers": small.serve_layers,
            "device_ids_per_replica": homes,
            "all_on_one_device": len({tuple(h) for h in homes}) == 1,
            "note": "reported, not gated: see ROADMAP.md open items"}


# ---------------------------------------------------------------------------
# hybrid: conv tails beside K/V rows, through serve, against the reference
# ---------------------------------------------------------------------------


def phase_hybrid(s: Sizes, config: Optional[Dict[str, Any]] = None, *,
                 device: str = "tpu", block: int = 64, chunk: int = 256,
                 tol_gap: float = 0.4) -> Dict[str, Any]:
    """The conv / attention hybrid (``benchmark/configs/lfm2-8b-a1b.json``:
    9 conv + 3 attention layers, 2 dense FFNs, 10 expert layers, at their
    published widths) served on one chip through ``cli.build_serve_engine``
    and ``SlotServer.serve`` with the reference's seeded weights: a cold
    request; after it retired, one that shares its first four blocks (a
    prefix hit: the conv state comes from a published block's tail) and a
    family of two forked inside a block (the copy carries the tail). Every
    served token is held to the plain reference's logits
    (``benchmark/references/lfm2_moe.py``, the full forward pass, no
    cache): a token's gap is the reference's best logit at its position
    less the reference's logit for the token served there, and every
    branch's MEAN gap lies under ``tol_gap``. Not its largest: bf16 through
    12 layers leaves the logits 0.13-0.18 rms off the float32 reference on
    logits of std 0.9, so a near-tied choice flips now and then and one
    token of a sound run reads 0.4-1.2 (0.445 of 48 tokens on the chip, PR
    33), while a branch's mean over its 12 tokens read 0.02-0.07; a stale
    tail or a wrong block leaves every later token at the logits' own
    spread, a mean of 1-3."""
    import numpy as np

    from benchmark import check as served
    from benchmark.spec import Spec
    from tree_attention_tpu import cli
    from tree_attention_tpu.serving.engine import Request
    from tree_attention_tpu.utils.config import parse_args

    # The family's two files, found as the harness finds them.
    spec = Spec(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCHMARK.json"))
    if config is None:
        config = spec.load_json("configs", "lfm2-8b-a1b.json")
    ref = spec.load_module("references", config["family"] + ".py")
    adapter = spec.load_module("adapters", config["family"] + ".py")
    w = ref.Widths.of(config)
    weights = ref.init_weights(3, w)
    rng = np.random.default_rng(17)
    vocab = int(config["vocab_size"])
    a = rng.integers(0, vocab, (5 * block + 7,)).tolist()
    b = a[:4 * block] + rng.integers(0, vocab, (block // 2 + 3,)).tolist()
    c = a[:3 * block + 20]
    new = 12
    flags = ["--mode", "serve", "--device", device,
             "--dtype", str(config["torch_dtype"]), "--slots", "4",
             "--prompt-len", str(len(a)), "--prompt-jitter", "0",
             "--max-new-tokens", str(new), "--prefill-chunk", str(chunk),
             "--prefix-cache", "--prefix-block", str(block),
             "--temperature", "0", "--seed", "1"]
    setup = cli.build_serve_engine(
        parse_args(flags), None, model=config,
        params=adapter.engine_params(weights, w))
    check(setup.tcfg.cache_kind == "hybrid", "the model caches hybrid state")
    server = setup.make_engine()
    first = server.serve([Request(uid=0, prompt=a, max_new_tokens=new)])
    second = server.serve([
        Request(uid=1, prompt=b, max_new_tokens=new),
        Request(uid=2, prompt=c, max_new_tokens=new, n=2)])
    results = list(first.results) + list(second.results)
    prompts = {0: a, 1: b, 2: c}
    check(len(results) == 4 and all(
        r.outcome == "budget" and len(r.tokens) == new for r in results),
        "four branches served to their budgets")
    hit = [r for r in results if r.uid == 1][0].prefix_hit_tokens
    check(hit == 4 * block, f"a prefix hit of four blocks (got {hit})")
    check(second.kv.get("forks") == 1, f"one fork (kv: {second.kv})")
    twins = [r.tokens for r in results if r.uid == 2]
    check(twins[0] == twins[1], "the greedy twins agree")
    leak = server.leak_report()
    check(leak["blocks_used"] == leak["blocks_cached"]
          and not (leak["blocks_private"] or leak["blocks_reserved"]
                   or leak["pins"]), f"no block leaked ({leak})")
    gaps = [served.served_gaps(ref, weights, w, np.asarray(prompts[r.uid]),
                              np.asarray(r.tokens))[0] for r in results]
    means = [float(g.mean()) for g in gaps]
    check(max(means) <= tol_gap,
          f"a branch's served tokens lie {max(means):.3f} under the "
          f"reference's best on average (limit {tol_gap}; {means})")
    return {
        "layers": list(setup.tcfg.layer_types), "prefix_hit_tokens": hit,
        "forks": second.kv["forks"],
        "kv_token_bytes": second.kv["token_bytes"],
        "kv_block_fixed_bytes": second.kv["block_fixed_bytes"],
        "tokens_compared": int(sum(len(g) for g in gaps)),
        "gap_max": float(max(g.max() for g in gaps)),
        "gap_mean": float(np.concatenate(gaps).mean()),
        "gap_mean_by_branch": means,
    }


def phase_window(s: Sizes, config: Optional[Dict[str, Any]] = None, *,
                 device: str = "tpu", block: int = 64, chunk: int = 256,
                 tol_gap: float = 0.4) -> Dict[str, Any]:
    """Sliding-window layers beside full-attention layers
    (``benchmark/configs/k-exaone-236b-a23b.json``: 6 window + 2 full
    layers, 1 dense FFN, 7 expert layers of the chip's 8 of 128 experts, at
    their published widths) served on one chip through
    ``cli.build_serve_engine`` and ``SlotServer.serve`` with the
    reference's seeded weights, from two K/V pools under two tables: a cold
    request longer than three windows and three blocks (its window blocks
    go back as it grows); after it retired, one that shares its whole
    prompt (a prefix hit at the deepest published boundary: the tree kept
    the window blocks of the prompt's last full blocks) and a family of two
    forked inside a block (window blocks shared by reference, the partial
    one copied); then a shorter request in a slot a longer one used (both
    tables reset). Every served token is held to the plain reference's
    logits (``benchmark/references/exaone_moe.py``, the full forward pass,
    the window a mask, no cache) as :func:`phase_hybrid` holds its own: a
    branch's MEAN gap lies under ``tol_gap``; a window that sees too much
    or too little, a rotated full layer or a block given back too early
    leaves every later token at the logits' own spread."""
    import numpy as np

    from benchmark import check as served
    from benchmark.spec import Spec
    from tree_attention_tpu import cli
    from tree_attention_tpu.serving.engine import Request
    from tree_attention_tpu.utils.config import parse_args

    spec = Spec(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCHMARK.json"))
    if config is None:
        config = spec.load_json("configs", "k-exaone-236b-a23b.json")
    ref = spec.load_module("references", config["family"] + ".py")
    adapter = spec.load_module("adapters", config["family"] + ".py")
    w = ref.Widths.of(config)
    weights = ref.init_weights(3, w)
    window = int(config["sliding_window"])
    rng = np.random.default_rng(19)
    vocab = int(config["vocab_size"])
    new = window // 2 + 16
    # The later requests' budgets: long enough that a branch's mean is not
    # two near-tied choices (the hit request's first 12 tokens read 0.319
    # on the chip, its first 48 0.087, served by a hit and cold alike;
    # PERF.md, PR 38).
    later = min(48, new)
    n_a = max(3 * window, 3 * block) + block + 7 - new
    a = rng.integers(0, vocab, (n_a,)).tolist()
    b = a + rng.integers(0, vocab, (block // 2 + 3,)).tolist()
    c = rng.integers(0, vocab, (2 * block + block // 3,)).tolist()
    d = rng.integers(0, vocab, (block // 2,)).tolist()
    flags = ["--mode", "serve", "--device", device,
             "--dtype", str(config["torch_dtype"]), "--slots", "3",
             "--prompt-len", str(len(b)), "--prompt-jitter", "0",
             "--max-new-tokens", str(new), "--prefill-chunk", str(chunk),
             "--prefix-cache", "--prefix-block", str(block),
             "--temperature", "0", "--seed", "1"]
    setup = cli.build_serve_engine(
        parse_args(flags), None, model=config,
        params=adapter.engine_params(weights, w))
    check(setup.tcfg.cache_kind == "window", "the model caches window state")
    server = setup.make_engine()
    first = server.serve([Request(uid=0, prompt=a, max_new_tokens=new)])
    second = server.serve([
        Request(uid=1, prompt=b, max_new_tokens=later),
        Request(uid=2, prompt=c, max_new_tokens=later, n=2)])
    third = server.serve([Request(uid=3, prompt=d, max_new_tokens=later)])
    results = list(first.results) + list(second.results) \
        + list(third.results)
    prompts = {0: a, 1: b, 2: c, 3: d}
    check(len(results) == 5 and all(
        r.outcome == "budget" for r in results),
        "five branches served to their budgets")
    check(len(a) + new > max(3 * window, 3 * block),
          "the cold request outgrew three windows and three blocks")
    hit = [r for r in results if r.uid == 1][0].prefix_hit_tokens
    check(hit == len(a) // block * block,
          f"a prefix hit at the prompt's last whole block (got {hit})")
    check(second.kv.get("forks") == 1, f"one fork (kv: {second.kv})")
    twins = [r.tokens for r in results if r.uid == 2]
    check(twins[0] == twins[1], "the greedy twins agree")
    kv = third.kv
    check(first.kv["window_blocks_peak_slot"] <= kv["window_blocks_bound"]
          and first.kv["window_blocks_freed"] > 0,
          f"a slot's window blocks stay under the bound and go back "
          f"({first.kv})")
    leak = server.leak_report()
    check(leak["blocks_used"] == leak["blocks_cached"]
          and not (leak["blocks_private"] or leak["blocks_reserved"]
                   or leak["pins"] or leak["window_blocks_held"]),
          f"no block leaked ({leak})")
    gaps = [served.served_gaps(ref, weights, w, np.asarray(prompts[r.uid]),
                              np.asarray(r.tokens))[0] for r in results]
    means = [float(g.mean()) for g in gaps]
    check(max(means) <= tol_gap,
          f"a branch's served tokens lie {max(means):.3f} under the "
          f"reference's best on average (limit {tol_gap}; {means})")
    return {
        "layers": list(setup.tcfg.layer_types), "prefix_hit_tokens": hit,
        "forks": second.kv["forks"],
        "window_blocks_bound": kv["window_blocks_bound"],
        "window_blocks_peak_slot": first.kv["window_blocks_peak_slot"],
        "window_blocks_freed": first.kv["window_blocks_freed"],
        "kv_token_bytes": kv["token_bytes"],
        "window_token_bytes": kv["window_token_bytes"],
        "tokens_compared": int(sum(len(g) for g in gaps)),
        "gap_max": float(max(g.max() for g in gaps)),
        "gap_mean": float(np.concatenate(gaps).mean()),
        "gap_mean_by_branch": means,
    }


def phase_state(s: Sizes, config: Optional[Dict[str, Any]] = None, *,
                device: str = "tpu", block: int = 64, chunk: int = 256,
                tol_gap: float = 0.4) -> Dict[str, Any]:
    """State-space mixers beside one attention layer
    (``benchmark/configs/nemotron-3-super-120b-a12b.json``: 5 Mamba-2
    layers, 1 GQA layer with no positional term, 5 LatentMoE parts at their
    published widths) served on one chip through ``cli.build_serve_engine``
    and ``SlotServer.serve`` with the reference's seeded weights, from a
    cache that is a paged K/V pool and a recurrent state a slot. In an
    engine of two slots: a long request whose prompt leaves chunks that do
    not end on a multiple of the scan's block (two whole chunks and a rest
    of 77 rows); beside it a short one; once the short one has retired, a
    third in its slot (it finds the last request's state and tail there and
    starts from zero all the same) while the long one still decodes; then a
    fourth alone, the other slot sitting every tick out. Every served token
    is held to the plain reference's logits (``benchmark/references/nemotron_h.py``: the
    token-by-token recurrence, no cache) as :func:`phase_hybrid` holds its
    own: a request's MEAN gap lies under ``tol_gap``; a stale state leaves
    every later token at the logits' own spread."""
    return _serve_from_a_state_pool(
        config or _benchmark_config("nemotron-3-super-120b-a12b.json"),
        device=device, block=block, chunk=chunk, tol_gap=tol_gap)


def phase_parallel(s: Sizes, config: Optional[Dict[str, Any]] = None, *,
                   device: str = "tpu", block: int = 64, chunk: int = 256,
                   tol_gap: float = 0.4) -> Dict[str, Any]:
    """Two mixers side by side in every layer
    (``benchmark/configs/falcon-h1-34b-instruct.json``: a Mamba-2 state of 32
    heads x 128 x 256 and rotary GQA 20 / 4 x 128 on one normed residual, the
    family's fixed multipliers, an MLP of 21,504, at the published widths and
    a REDUCED depth: 3 of the cut's 9 layers), served as :func:`phase_state`
    serves its own, from a cache whose every layer holds K/V rows AND a
    state: a long request through a chunked prompt of odd length (two whole
    chunks and a rest of 77 rows) and 96 decoded tokens, a short one beside
    it, a third in the short one's slot once it has retired, a fourth alone
    while the other slot sits every tick out; every served token held to the
    plain reference's logits (``benchmark/references/falcon_h1.py``)."""
    return _serve_from_a_state_pool(
        config or dict(_benchmark_config("falcon-h1-34b-instruct.json"),
                       num_hidden_layers=3),
        device=device, block=block, chunk=chunk, tol_gap=tol_gap)


def phase_decoder_hybrid(s: Sizes, config: Optional[Dict[str, Any]] = None,
                         *, device: str = "tpu", block: int = 64,
                         chunk: int = 256, tol_gap: float = 0.4
                         ) -> Dict[str, Any]:
    """A decoder that feeds a second decoder
    (``benchmark/configs/phi-4-mini-flash-reasoning.json``: Mamba-1 states
    and window-512 rows below the seam, ONE full-attention layer whose rows
    the cross layers read again, gated memory units, differential attention,
    at the published widths and a REDUCED depth: 8 of 32 layers, every kind
    of layer at least once), served on one chip through
    ``cli.build_serve_engine`` and ``SlotServer.serve`` with the reference's
    seeded weights, from a cache that is a state, a window and full rows at
    once. In an engine of ONE slot: a request through a chunked prompt longer
    than the window (two whole chunks and a rest of 77 rows: the prompt's
    rows leave the stack at the seam, the window layers give blocks back)
    and 48 decoded tokens; then a second in the slot it leaves (it finds the
    last request's states, window rows and shared rows there and starts from
    zero all the same). Every served token is held to the plain reference's
    logits (``benchmark/references/phi4flash.py``: the token-by-token
    recurrence, differential attention as its definition, every layer on
    every row) as :func:`phase_state` holds its own."""
    import numpy as np

    from benchmark import check as served
    from tree_attention_tpu import cli
    from tree_attention_tpu.serving.engine import Request
    from tree_attention_tpu.utils.config import parse_args

    config = config or dict(
        _benchmark_config("phi-4-mini-flash-reasoning.json"),
        num_hidden_layers=8)
    spec = _benchmark_spec()
    ref = spec.load_module("references", config["family"] + ".py")
    adapter = spec.load_module("adapters", config["family"] + ".py")
    w = ref.Widths.of(config)
    weights = ref.init_weights(3, w)
    rng = np.random.default_rng(29)
    vocab = int(config["vocab_size"])
    a = rng.integers(0, vocab, (2 * chunk + (chunk * 77 // 256 or 1),)).tolist()
    b = rng.integers(0, vocab, (block + block // 3,)).tolist()
    new_a, new = 48, block // 4 + 4
    flags = ["--mode", "serve", "--device", device,
             "--dtype", str(config["torch_dtype"]), "--slots", "1",
             "--prompt-len", str(len(a)), "--prompt-jitter", "0",
             "--max-new-tokens", str(new_a), "--prefill-chunk", str(chunk),
             "--prefix-block", str(block),
             "--temperature", "0", "--seed", "1"]
    setup = cli.build_serve_engine(
        parse_args(flags), None, model=config,
        params=adapter.engine_params(weights, w))
    check(setup.tcfg.cache_kind == "state_window" and setup.tcfg.row_cut,
          "the model caches a state, a window and shared rows, and cuts its "
          "rows at the seam")
    check(len(a) > w.window, "the prompt is longer than the window")
    server = setup.make_engine()
    report = server.serve([Request(uid=0, prompt=a, max_new_tokens=new_a),
                           Request(uid=1, prompt=b, max_new_tokens=new)])
    results = list(report.results)
    prompts = {0: a, 1: b}
    check(len(results) == 2 and all(r.outcome == "budget" for r in results),
          "two requests served to their budgets through one slot")
    leak = server.leak_report()
    check(not (leak["blocks_used"] or leak["blocks_private"]
               or leak["blocks_reserved"] or leak["pins"]),
          f"no block leaked ({leak})")
    check(report.kv["window_blocks_freed"] > 0
          and report.kv["window_blocks_used"] == 0,
          "the window layers gave the blocks behind the window back")
    gaps = [served.served_gaps(ref, weights, w, np.asarray(prompts[r.uid]),
                              np.asarray(r.tokens))[0] for r in results]
    means = [float(g.mean()) for g in gaps]
    check(max(means) <= tol_gap,
          f"a request's served tokens lie {max(means):.3f} under the "
          f"reference's best on average (limit {tol_gap}; {means})")
    cache = server.cache
    return {
        "layers": list(setup.tcfg.layer_types),
        "row_cut": setup.tcfg.row_cut,
        "shared_rows": list(cache.k.shape),
        "window_rows": list(cache.wk.shape),
        "ssm_state": list(cache.ssm_state.shape),
        "ssm_state_dtype": str(cache.ssm_state.dtype),
        "window_blocks_freed": report.kv["window_blocks_freed"],
        "tokens_compared": int(sum(len(g) for g in gaps)),
        "gap_max": float(max(g.max() for g in gaps)),
        "gap_mean": float(np.concatenate(gaps).mean()),
        "gap_mean_by_request": means,
    }


def _benchmark_spec():
    from benchmark.spec import Spec

    return Spec(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCHMARK.json"))


def _benchmark_config(name: str) -> Dict[str, Any]:
    return _benchmark_spec().load_json("configs", name)


def _serve_from_a_state_pool(config: Dict[str, Any], *, device: str,
                             block: int, chunk: int, tol_gap: float
                             ) -> Dict[str, Any]:
    """What :func:`phase_state` and :func:`phase_parallel` run, each for its
    configuration."""
    import numpy as np

    from benchmark import check as served
    from tree_attention_tpu import cli
    from tree_attention_tpu.serving.engine import Request
    from tree_attention_tpu.utils.config import parse_args

    spec = _benchmark_spec()
    ref = spec.load_module("references", config["family"] + ".py")
    adapter = spec.load_module("adapters", config["family"] + ".py")
    w = ref.Widths.of(config)
    weights = ref.init_weights(3, w)
    rng = np.random.default_rng(23)
    vocab = int(config["vocab_size"])
    rest = chunk * 77 // 256 or 1
    a = rng.integers(0, vocab, (2 * chunk + rest,)).tolist()
    b = rng.integers(0, vocab, (block // 2 + 3,)).tolist()
    c = rng.integers(0, vocab, (block + block // 3,)).tolist()
    new_a, new = 3 * block // 2, block // 4 + 4
    flags = ["--mode", "serve", "--device", device,
             "--dtype", str(config["torch_dtype"]), "--slots", "2",
             "--prompt-len", str(len(a)), "--prompt-jitter", "0",
             "--max-new-tokens", str(new_a), "--prefill-chunk", str(chunk),
             "--prefix-block", str(block),
             "--temperature", "0", "--seed", "1"]
    setup = cli.build_serve_engine(
        parse_args(flags), None, model=config,
        params=adapter.engine_params(weights, w))
    check(setup.tcfg.cache_kind == "state", "the model caches a state a slot")
    server = setup.make_engine()

    # Two slots: the third request waits for the short one's slot, and
    # the fourth, alone, leaves the other slot without a row in any tick.
    report = server.serve([
        Request(uid=0, prompt=a, max_new_tokens=new_a),
        Request(uid=1, prompt=b, max_new_tokens=new),
        Request(uid=2, prompt=c, max_new_tokens=new)])
    alone = server.serve([Request(uid=3, prompt=b[::-1], max_new_tokens=new)])
    results = list(report.results) + list(alone.results)
    prompts = {0: a, 1: b, 2: c, 3: b[::-1]}
    check(len(results) == 4 and all(r.outcome == "budget" for r in results),
          "four requests served to their budgets through two slots")
    check(len(a) % setup.tcfg.ssm.chunk != 0,
          "the long prompt does not end on a multiple of the scan's block")
    leak = server.leak_report()
    check(not (leak["blocks_used"] or leak["blocks_private"]
               or leak["blocks_reserved"] or leak["pins"]),
          f"no block leaked ({leak})")
    gaps = [served.served_gaps(ref, weights, w, np.asarray(prompts[r.uid]),
                              np.asarray(r.tokens))[0] for r in results]
    means = [float(g.mean()) for g in gaps]
    check(max(means) <= tol_gap,
          f"a request's served tokens lie {max(means):.3f} under the "
          f"reference's best on average (limit {tol_gap}; {means})")
    cache = server.cache
    return {
        "layers": list(setup.tcfg.layer_types),
        "ffn": list(setup.tcfg.ffn_kinds),
        "ssm_state": list(cache.ssm_state.shape),
        "ssm_state_dtype": str(cache.ssm_state.dtype),
        "ssm_tail": list(cache.ssm_tail.shape),
        "kv_token_bytes": report.kv["token_bytes"],
        "tokens_compared": int(sum(len(g) for g in gaps)),
        "gap_max": float(max(g.max() for g in gaps)),
        "gap_mean": float(np.concatenate(gaps).mean()),
        "gap_mean_by_request": means,
    }


def phase_eva(s: Sizes, config: Optional[Dict[str, Any]] = None, *,
              device: str = "tpu", block: int = 64, chunk: int = 256,
              tol_gap: float = 0.4) -> Dict[str, Any]:
    """EVA attention in every layer (``benchmark/configs/evabyte.json``: 8
    layers at their published widths, MHA 32 x 128, a window of 2,048 in
    chunks of 16) served on one chip through ``cli.build_serve_engine`` and
    ``SlotServer.serve`` with the reference's seeded weights, from a cache
    of two pools of every layer under two tables: exact rows of the open
    window, one summary row a chunk of every closed one. In an engine of two
    slots: a long request whose prompt (two and a half windows and one
    position: no multiple of the chunk, so decode takes over mid-chunk)
    crosses two window boundaries in chunk groups and whose output crosses a
    third a row at a time; beside it a short one that never closes a window;
    once the short one has retired, a third in its slot; then a fourth alone
    (the other slot sits every tick out), past one boundary, in a slot whose
    last request wrote more summary rows than it may see. Every served token
    is held to the plain reference's logits
    (``benchmark/references/evabyte.py``: one dense softmax over exact rows
    and summaries, no cache) as :func:`phase_hybrid` holds its own: a
    request's MEAN gap lies under ``tol_gap``; a row that reads a stale
    summary, or none, leaves every later token off."""
    import numpy as np

    from benchmark import check as served
    from benchmark.spec import Spec
    from tree_attention_tpu import cli
    from tree_attention_tpu.serving.engine import Request
    from tree_attention_tpu.utils.config import parse_args

    spec = Spec(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCHMARK.json"))
    if config is None:
        config = spec.load_json("configs", "evabyte.json")
    ref = spec.load_module("references", config["family"] + ".py")
    adapter = spec.load_module("adapters", config["family"] + ".py")
    w = ref.Widths.of(config)
    weights = ref.init_weights(3, w)
    rng = np.random.default_rng(29)
    vocab, W = int(config["vocab_size"]), int(config["window_size"])
    a = rng.integers(0, vocab, (2 * W + W // 2 + 1,)).tolist()
    b = rng.integers(0, vocab, (block // 2 + 3,)).tolist()
    c = rng.integers(0, vocab, (block + block // 3,)).tolist()
    d = rng.integers(0, vocab, (W + block // 2 + 5,)).tolist()
    new_a, new = W // 2 + W // 8, block // 4 + 4
    flags = ["--mode", "serve", "--device", device,
             "--dtype", str(config["torch_dtype"]), "--slots", "2",
             "--prompt-len", str(len(a)), "--prompt-jitter", "0",
             "--max-new-tokens", str(new_a), "--prefill-chunk", str(chunk),
             "--kv-block", str(block),
             "--temperature", "0", "--seed", "1"]
    setup = cli.build_serve_engine(
        parse_args(flags), None, model=config,
        params=adapter.engine_params(weights, w))
    check(setup.tcfg.cache_kind == "eva",
          "the model caches exact rows and summary rows")
    server = setup.make_engine()

    report = server.serve([
        Request(uid=0, prompt=a, max_new_tokens=new_a),
        Request(uid=1, prompt=b, max_new_tokens=new),
        Request(uid=2, prompt=c, max_new_tokens=new)])
    alone = server.serve([Request(uid=3, prompt=d, max_new_tokens=W // 8)])
    results = list(report.results) + list(alone.results)
    prompts = {0: a, 1: b, 2: c, 3: d}
    check(len(results) == 4 and all(r.outcome == "budget" for r in results),
          "four requests served to their budgets through two slots")
    check(len(a) % int(config["chunk_size"]) != 0
          and (len(a) + new_a) // W == 3 and len(a) // W == 2,
          "the long request crosses two boundaries in its prompt and a "
          "third in decode, its prompt no multiple of the chunk")
    leak = server.leak_report()
    check(not (leak["blocks_used"] or leak["blocks_private"]
               or leak["blocks_reserved"] or leak["pins"]
               or leak["window_blocks_held"]),
          f"no block of either pool leaked ({leak})")
    kv = report.kv
    check(kv["window_blocks_peak_slot"] <= kv["window_blocks_bound"]
          and kv["window_blocks_freed"] >= 3 * (W // block),
          f"a slot held at most its bound of exact rows' blocks and gave "
          f"three windows back ({kv})")
    gaps = [served.served_gaps(ref, weights, w, np.asarray(prompts[r.uid]),
                              np.asarray(r.tokens))[0] for r in results]
    means = [float(g.mean()) for g in gaps]
    check(max(means) <= tol_gap,
          f"a request's served tokens lie {max(means):.3f} under the "
          f"reference's best on average (limit {tol_gap}; {means})")
    cache = server.cache
    return {
        "layers": sorted(set(setup.tcfg.layer_types)),
        "summary_pool": list(cache.k.shape),
        "local_pool": list(cache.wk.shape),
        "tables": [list(cache.table.shape), list(cache.wtable.shape)],
        "window_blocks_bound": kv["window_blocks_bound"],
        "window_blocks_peak_slot": kv["window_blocks_peak_slot"],
        "window_blocks_freed": kv["window_blocks_freed"],
        "tokens_compared": int(sum(len(g) for g in gaps)),
        "gap_max": float(max(g.max() for g in gaps)),
        "gap_mean": float(np.concatenate(gaps).mean()),
        "gap_mean_by_request": means,
    }


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def run_default(run: Run, s: Sizes) -> None:
    run.phase("kernels", phase_kernels, s)
    run.phase("serve", phase_serve, s)
    run.phase("ingress", phase_ingress, s)
    run.phase("serve_int8", phase_serve, s, int8=True)
    run.phase("agreement", phase_agreement, s)
    run.phase("train", phase_train, s)
    run.phase("programs", phase_programs, s)
    run.phase("hybrid", phase_hybrid, s)
    run.phase("window", phase_window, s)
    run.phase("state", phase_state, s)
    run.phase("eva", phase_eva, s)
    run.phase("parallel", phase_parallel, s)
    run.phase("decoder_hybrid", phase_decoder_hybrid, s)


def run_four_chips(run: Run, s: Sizes) -> None:
    import jax

    from tree_attention_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"seq": len(jax.devices())})
    run.phase("tree_decode", phase_tree_decode, s, mesh)
    run.phase("serve_seq_sharded", phase_serve_seq_sharded, s, mesh)
    run.phase("fleet_placement", phase_fleet_placement, s)


def phase_times(run: Run) -> Dict[str, Any]:
    """Set-up information only: wall time per phase (first calls include
    compilation; each phase's own line splits first from later calls where
    it makes both) and what the compile cache saw."""
    cache: Dict[str, int] = {}
    for p in run.phases:
        for k, v in p["compile_cache"].items():
            cache[k] = cache.get(k, 0) + v
    return {"wall_s_by_phase": {p["phase"]: p["wall_s"] for p in run.phases},
            "compile_cache_total": cache,
            "note": "set-up information, not a benchmark"}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sequence-parallel paths on four chips "
                         "and what they are compared with, and nothing else")
    args = ap.parse_args(argv)
    # A hung phase must not hang the machine: dump every thread and exit.
    faulthandler.dump_traceback_later(1150, exit=True)

    import jax

    from tree_attention_tpu.cli import configure_compile_cache

    cache_dir = configure_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(
            f"chip_smoke.py runs on a TPU and JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}). Nothing ran; "
            "there is no CPU fallback. Tests rehearse the phases on the "
            "CPU (tests/test_chip_smoke.py)."
        )
    if len(devs) != args.chips:
        sys.exit(f"--chips {args.chips} needs exactly {args.chips} "
                 f"device(s); JAX found {len(devs)}")
    s = Sizes()
    run = Run(cache_dir)
    run.phase("device", phase_device, cache_dir)
    (run_default if args.chips == 1 else run_four_chips)(run, s)
    run.phase("times", phase_times, run)
    faulthandler.cancel_dump_traceback_later()
    if not run.ok:
        failed = [p["phase"] for p in run.phases if not p["ok"]]
        print(f"chip_smoke.py: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
