"""Focused fwd-kernel tile A/B at the 16k yardstick shape.

The full grid sweep (``tools/tune_sweep.py fwd``) needs ~20 compiles;
this tool instead times a HANDFUL of candidate tiles with the protocol of
``tools/race_stock_flash.py`` (chains 2/16, iters=5, min-stat, repeats=3)
plus the shared deflation/floor screens, so a tile default change can be
judged on data that carries its own error bar.

Motivation: prefetch-zero culling (commit c00c835) removes a per-Q-row
cold fetch, which weighs ~2x heavier at bq=512 (32 rows at 16k) than at
the current default bq=1024 — the pre-fix sweep that picked 1024/2048
no longer describes the kernel.

Run on the chip host: ``python tools/ab_fwd_tiles.py``
"""

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tree_attention_tpu.bench.ici import peaks  # noqa: E402
from tree_attention_tpu.utils.profiling import (  # noqa: E402
    chain_slope,
    deflation_suspect,
)

B, H, D = 1, 16, 128


def bench_tile(T, bq, bk, mode, n_small, n_large):
    import jax
    import jax.numpy as jnp

    from tree_attention_tpu.ops import flash_attention
    from tree_attention_tpu.ops.pallas_attention import attention_pallas_fwd

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, T, D), jnp.bfloat16)

    tiles = {} if bq == 0 else {"block_q": bq, "block_size": bk}
    if mode == "fwd":
        def step(qc, k_, v_):
            if bq == 0:  # the product default path (ops/tuning.py tables)
                return flash_attention(
                    qc, k_, v_, causal=True, impl="pallas", custom_vjp=False,
                )[0]
            return attention_pallas_fwd(qc, k_, v_, causal=True, **tiles)[0]
    else:
        # Through the custom VJP and all three grads, like bench.py's
        # train record. NOTE an explicit block_q flows to BOTH passes
        # (tuning sweeps measure what they label), so a cell whose
        # bq * bk exceeds BWD_MAX_TILE_ELEMS (e.g. 1024x2048) will
        # compile-OOM in fwd_bwd mode and be recorded as an error —
        # only the 'default' cell gets the dispatcher's VMEM-capped
        # bwd Q tile.
        def step(qc, k_, v_):
            def loss(q_, k__, v__):
                o, _ = flash_attention(
                    q_, k__, v__, causal=True, impl="pallas", **tiles
                )
                return jnp.sum(o.astype(jnp.float32) ** 2)

            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(qc, k_, v_)
            return dq + dk + dv

    s = chain_slope(
        step, q, k, v, n_small=n_small, n_large=n_large, repeats=3,
    )
    flops = 4.0 * (B * H * (T * (T + 1)) // 2) * D  # shared causal basis
    if mode != "fwd":
        flops *= 3.5
    bf16_peak = peaks().bf16_flops_per_s
    rec = {
        "T": T, "mode": mode, "bq": bq, "bk": bk,
        "us_per_step": round(s.per_step * 1e6, 1),
        "mfu_pct_shared_basis": round(
            flops / s.per_step / bf16_peak * 100, 1
        ),
        "slope_cycles_us": [round(c * 1e6, 2) for c in s.slopes],
        "slope_spread_pct": round(s.spread_pct, 1),
    }
    suspect = deflation_suspect(s)
    if suspect is None and s.per_step < flops / (bf16_peak * 1.05):
        suspect = "implied MFU above the bf16 peak: fence failure"
    if suspect:
        rec["suspect"] = suspect
    return rec


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seq", type=int, default=16384)
    p.add_argument("--mode", choices=("fwd", "fwd_bwd"), default="fwd")
    p.add_argument(
        "--cells", nargs="+", default=["1024x2048", "512x1024", "512x2048",
                                       "1024x1024"],
        help="bqxbk candidates, e.g. 1024x1024; 'default' = the product "
             "default path (ops/tuning.py tables end to end)",
    )
    args = p.parse_args()
    chains = {  # >= ~100 ms marginal per cell
        ("fwd"): (2, 16) if args.seq <= 16384 else (2, 8),
        ("fwd_bwd"): (2, 8) if args.seq <= 16384 else (1, 4),
    }
    ns, nl = chains[args.mode]
    commit = subprocess.run(
        ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
        text=True,
    ).stdout.strip()
    print(json.dumps({
        "tool": "ab_fwd_tiles", "T": args.seq, "mode": args.mode,
        "commit": commit,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }), flush=True)
    for cell in args.cells:
        bq, bk = (0, 0) if cell == "default" else (
            int(x) for x in cell.split("x")
        )
        try:
            print(json.dumps(
                bench_tile(args.seq, bq, bk, args.mode, ns, nl)
            ), flush=True)
        except Exception as e:
            print(json.dumps({
                "bq": bq, "bk": bk,
                "error": f"{type(e).__name__}: {e}"[:200],
            }), flush=True)


if __name__ == "__main__":
    main()
