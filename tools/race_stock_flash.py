"""Race the stock JAX Pallas TPU flash kernel as an external MFU yardstick.

The claim "v5e cannot reach 70% fwd MFU at 16k with this algorithm" rested
on internal sweeps alone. This tool races the JAX-bundled reference
kernel (``jax.experimental.pallas.ops.tpu.flash_attention``) against this
repo's ``flash_attention`` on identical inputs, shapes, and measurement
protocol — either the stock kernel also sits at the same ceiling
(corroboration by an independent implementation) or it is faster (headroom
to adopt).

Fairness notes:

- identical (B, H, T, D) bf16 inputs; both kernels get the same
  ``sm_scale = 1/sqrt(D)`` (the stock kernel's default is 1.0 — passing it
  explicitly keeps the math identical);
- both time with the slope protocol (chained steps via ``lax.scan``,
  scalar-reduction fence, min-stat over cycles — see
  ``utils/profiling.slope_per_step``);
- MFU is computed for both on the SAME idealised causal model FLOPs
  (4·pairs·D fwd, ×3.5 fwd+bwd), not per-kernel launched-tile counts —
  tile-granularity differences between the kernels must not flatter either
  side. Numbers therefore differ slightly from bench.py's launched-tile
  MFU for our kernel (bench.py's basis is the right one for roofline
  accounting; the shared basis is the right one for a head-to-head).

Writes ``measurements/r5/stock_flash_race.json``; bench.py attaches it to
the suite as the ``stock_flash_race`` record.

Run ON THE CHIP HOST with nothing else on the core:
    python tools/race_stock_flash.py [--seqs 16384 32768] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tree_attention_tpu.bench.ici import peaks  # noqa: E402


def _model_flops(T: int, *, B: int = 1, H: int = 16, D: int = 128,
                 backward: bool = False) -> float:
    pairs = B * H * (T * (T + 1)) // 2  # causal
    fwd = 4.0 * pairs * D
    return fwd * 3.5 if backward else fwd


def bench_kernel(kernel: str, T: int, mode: str, n_small: int, n_large: int):
    """Per-step seconds for one (kernel, seq, mode) cell, slope protocol."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tree_attention_tpu.utils.profiling import slope_per_step

    B, H, D = 1, 16, 128
    sm = 1.0 / math.sqrt(D)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, T, D), jnp.bfloat16)

    if kernel in ("stock", "stock_default"):
        from jax.experimental.pallas.ops.tpu import flash_attention as stock

        if kernel == "stock_default":
            # Out-of-the-box: BlockSizes.get_default picks 128x128 at these
            # shapes, which measured ~7.5% MFU flat — recorded as the
            # out-of-box datapoint, not the yardstick.
            bs = None
        else:
            # A fair yardstick gets its best known configuration: 512/1024
            # tiles (measured 2026-08-01: 61.0% fwd MFU at 16k vs 7.7% with
            # the defaults on this chip), mirrored into the dq/dkv blocks.
            bs = stock.BlockSizes(
                block_q=512, block_k_major=1024, block_k=1024, block_b=1,
                block_q_major_dkv=512, block_k_major_dkv=1024,
                block_k_dkv=1024, block_q_dkv=512,
                block_k_major_dq=1024, block_k_dq=1024, block_q_dq=512,
            )

        def fwd(q_, k_, v_):
            return stock.flash_attention(
                q_, k_, v_, causal=True, sm_scale=sm, block_sizes=bs
            )
    else:
        from tree_attention_tpu.ops import flash_attention as ours_fa

        def fwd(q_, k_, v_):
            return ours_fa(
                q_, k_, v_, causal=True, scale=sm,
                custom_vjp=(mode == "fwd_bwd"),
            )[0]

    if mode == "fwd":
        step = fwd
    else:
        def loss(q_, k_, v_):
            return jnp.sum(fwd(q_, k_, v_).astype(jnp.float32) ** 2)

        def step(q_, k_, v_):
            # All three grads, folded into the carry so XLA cannot
            # dead-code-eliminate the dKV pass (same trick as bench.py).
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)
            return dq + dk + dv

    def mk(n):
        def f(q_, k_, v_):
            def body(qc, _):
                return step(qc, k_, v_).astype(qc.dtype), None

            out = lax.scan(body, q_, None, length=n)[0]
            return jnp.sum(out.astype(jnp.float32))

        return jax.jit(f)

    s = slope_per_step(
        mk, q, k, v, n_small=n_small, n_large=n_large,
        iters=5, warmup=1, stat="min", repeats=2,
    )
    flops = _model_flops(T, backward=(mode == "fwd_bwd"))
    return {
        "us_per_step": round(s.per_step * 1e6, 1),
        "mfu_pct_shared_basis": round(
            flops / s.per_step / peaks().bf16_flops_per_s * 100, 1
        ),
        "slope_spread_pct": round(s.spread_pct, 1),
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seqs", type=int, nargs="+", default=[16384, 32768])
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "measurements", "r5", "stock_flash_race.json",
    ))
    args = p.parse_args()

    commit = subprocess.run(
        ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
        text=True,
    ).stdout.strip()
    result = {
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "commit": commit,
        "protocol": "slope_min repeats=2 iters=5; shared model-FLOPs basis",
        "cells": {},
    }
    # Chain lengths per (seq, mode): sized so marginal work >~100 ms.
    chains = {
        (16384, "fwd"): (2, 16), (16384, "fwd_bwd"): (2, 8),
        (32768, "fwd"): (2, 8), (32768, "fwd_bwd"): (1, 4),
        (65536, "fwd"): (1, 3), (65536, "fwd_bwd"): (1, 3),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for T in args.seqs:
        for mode in ("fwd", "fwd_bwd"):
            n_small, n_large = chains.get((T, mode), (1, 3))
            cell = {}
            # "stock" runs with its best-known (tuned) BlockSizes — the
            # honest yardstick; "stock_default" records the out-of-box
            # 128x128 defaults once per seq (fwd only) for context.
            kernels = ["ours", "stock"]
            if mode == "fwd":
                kernels.append("stock_default")
            for kernel in kernels:
                try:
                    cell[kernel] = bench_kernel(
                        kernel, T, mode, n_small, n_large
                    )
                except Exception as e:  # record, keep racing
                    cell[kernel] = {
                        "error": f"{type(e).__name__}: {e}"[:300]
                    }
            if all("us_per_step" in cell[k] for k in ("ours", "stock")):
                cell["ours_vs_stock"] = round(
                    cell["stock"]["us_per_step"] / cell["ours"]["us_per_step"],
                    3,
                )
            result["cells"][f"seq{T}_{mode}"] = cell
            # Persist after EVERY cell: these are chip minutes, and a
            # process death (OOM, a kill at the time limit) mid-run must not erase completed cells.
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
            print(json.dumps({f"seq{T}_{mode}": cell}), flush=True)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
