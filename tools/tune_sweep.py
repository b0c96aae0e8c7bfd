"""Kernel tile-size sweep on the attached TPU chip.

Prints one JSON line per measurement; the winners go into
``tree_attention_tpu/ops/tuning.py``. Run from the repo root:

    python tools/tune_sweep.py decode   # flash-decode kernel block_k sweep
    python tools/tune_sweep.py fwd      # training fwd kernel (bq, bk) sweep
    python tools/tune_sweep.py bwd      # fwd+bwd through the custom VJP

Uses the slope-timing protocol (utils.profiling.slope_per_step, min-stat
over repeated cycles), so fixed per-call costs cancel and each cell
carries its own spread.
"""

import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, ".")

from tree_attention_tpu.bench.ici import peaks  # noqa: E402
from tree_attention_tpu.utils.profiling import (  # noqa: E402
    deflation_suspect,
    record_guard_verdict,
    slope_per_step,
)


def _per_step(step, q, k, v, ns, nl, min_seconds):
    """Min-stat repeated-cycle per-step seconds (+ spread %) for a chain.

    ``min_seconds`` is the cell's physical floor (work / chip peak): a
    fence that resolves before the chained program has finished deflates
    that cycle's slope, and the min-stat estimator would then lock the
    impossible reading in. Cycles below the floor are certainly wrong and are discarded,
    symmetric with the bench harness's bandwidth-ceiling guard (if every
    cycle is impossible the cell raises rather than reporting fiction).
    A deflated cycle can also stay ABOVE the floor; that case is
    AMBIGUOUS — a min far below its siblings is equally consistent with
    the siblings being contended, and the repo's additive-noise model
    then calls the min the honest estimate — so, exactly like bench.py's
    records, the cell keeps the min and carries a ``suspect`` annotation
    (via the shared ``profiling.deflation_suspect`` rule) instead of
    silently rewriting the data.
    """
    s = slope_per_step(
        lambda n: _chain(step, n), q, k, v,
        n_small=ns, n_large=nl, iters=5, warmup=1, stat="min", repeats=4,
    )
    ok = [sl for sl in s.slopes if sl >= min_seconds]
    if not ok:
        # The TOTAL fault must file its verdict too — raising without one
        # would make the worst windows look cleanest in the guard audit.
        record_guard_verdict(
            "tune_sweep", "floor",
            f"every cycle below the physical floor {min_seconds:.2e}s",
        )
        raise RuntimeError(
            f"every cycle slope below the physical floor {min_seconds:.2e}s "
            f"({[f'{sl:.2e}' for sl in s.slopes]}): transport fault"
        )
    per = min(ok)
    spread = (max(ok) - per) / per * 100
    screened = dataclasses.replace(
        s, per_step=per, slopes=tuple(ok),
        spread_pct=spread,
    )
    dropped = len(s.slopes) - len(ok)
    deflated = deflation_suspect(screened)
    suspect = deflated
    if suspect is None and dropped:
        # Any floor-dropped cycle is hard evidence the window was faulty
        # (same invariant as profiling.deflation_suspect's non-positive
        # rule): the survivors — however clean they look — are data from
        # that same window, so the cell must not publish as clean.
        suspect = (
            f"{dropped} of {len(s.slopes)} cycles below "
            "the physical floor: faulty transport window; re-measure "
            "before trusting this cell"
        )
    # Publish the RAW cycles (incl. floor-dropped ones): a suspect cell
    # whose impossible readings were elided would carry no evidence of how
    # severe the fault was. Both guards file independently — a floor trip
    # must not mask the deflation verdict (the same one-guard-masks-
    # another shape bench.py's _train_record fix removes).
    if dropped:
        record_guard_verdict(
            "tune_sweep", "floor",
            f"{dropped} of {len(s.slopes)} cycles below the physical floor",
        )
    if deflated:
        record_guard_verdict("tune_sweep", "deflation", deflated)
    if not dropped and not deflated:
        record_guard_verdict("tune_sweep", "clean")
    return per, spread, dropped, suspect, s.slopes



def _qkv(H, Hkv, Tq, T, D=128):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    return (
        jax.random.normal(kq, (1, H, Tq, D), jnp.bfloat16),
        jax.random.normal(kk, (1, Hkv, T, D), jnp.bfloat16),
        jax.random.normal(kv, (1, Hkv, T, D), jnp.bfloat16),
    )


def _chain(step, n):
    # The chain returns a SCALAR reduction of its carry, not the carry
    # itself: slope_per_step's fetch fence copies the chain's result to
    # host, and fetching the full (1, H, T, D) tensor (~64 MB at the 16k
    # training shapes) per timing call is exactly the heavy-tailed RPC
    # jitter the hardened protocol exists to cancel — and can spuriously
    # trip the floor/deflation screens (ADVICE r5). Same contract as
    # profiling.chain_slope, which this mirrors with sweep-local knobs.
    def f(q, k, v):
        def body(qc, _):
            return step(qc, k, v).astype(qc.dtype), None

        out = lax.scan(body, q, None, length=n)[0]
        return jnp.sum(out.astype(jnp.float32))

    return jax.jit(f)


def sweep_decode():
    from tree_attention_tpu.ops.pallas_decode import attention_pallas_decode

    for H, Hkv, T, ns, nl in (
        (16, 16, 64000, 16, 64),
        (32, 4, 131072, 16, 64),
        (16, 16, 1 << 20, 2, 8),
        (32, 4, 1 << 20, 4, 16),
    ):
        q, k, v = _qkv(H, Hkv, 1, T)
        for bk in (512, 1024, 2048, 4096):
            try:
                step = lambda qc, k_, v_: attention_pallas_decode(
                    qc, k_, v_, block_size=bk
                )[0]
                kv_bytes = 2 * T * Hkv * 128 * 2
                hbm_bw = peaks().hbm_bytes_per_s
                per, spread, dropped, suspect, cycles = _per_step(
                    step, q, k, v, ns, nl,
                    min_seconds=kv_bytes / (hbm_bw * 1.05),
                )
                rec = {
                    "kernel": "decode", "H": H, "Hkv": Hkv, "T": T, "bk": bk,
                    "us": round(per * 1e6, 1),
                    "pct_roofline": round(kv_bytes / per / hbm_bw * 100, 1),
                    "spread_pct": round(spread, 1),
                    "slope_cycles_us": [round(c * 1e6, 2) for c in cycles],
                    "cycles_dropped": dropped,
                }
                if suspect:
                    rec["suspect"] = suspect
                print(json.dumps(rec), flush=True)
            except Exception as e:
                print(json.dumps({
                    "kernel": "decode", "T": T, "bk": bk,
                    "error": f"{type(e).__name__}: {e}"[:200],
                }), flush=True)


def sweep_fwd(bwd=False):
    from tree_attention_tpu.ops import flash_attention

    # Chain lengths keep the marginal work (nl - ns steps) above ~100 ms —
    # the floor below which residual per-call jitter can dominate the slope
    # (the r4 58%-of-roofline outlier sat on a 68 ms marginal).
    for T, ns, nl in ((4096, 8, 128), (16384, 4, 16)):
        q, k, v = _qkv(16, 16, T, T)
        flops = 2 * 2 * 16 * (T * T / 2) * 128 * (3.5 if bwd else 1)
        # Larger tiles cut the per-Q-row KV re-streaming (O(1/bq) HBM
        # traffic) at the cost of VMEM; the v5e has room well past these.
        for bq in (256, 512, 1024):
            for bk in (512, 1024, 2048):
                try:
                    if bwd:
                        def step(qc, k_, v_, bq=bq, bk=bk):
                            def loss(q_):
                                o, _ = flash_attention(
                                    q_, k_, v_, causal=True, impl="pallas",
                                    block_size=bk, block_q=bq,
                                )
                                return jnp.sum(o.astype(jnp.float32) ** 2)

                            return jax.grad(loss)(qc)
                    else:
                        def step(qc, k_, v_, bq=bq, bk=bk):
                            from tree_attention_tpu.ops.pallas_attention import (
                                attention_pallas_fwd,
                            )

                            return attention_pallas_fwd(
                                qc, k_, v_, causal=True, block_q=bq,
                                block_size=bk,
                            )[0]

                    per, spread, dropped, suspect, cycles = _per_step(
                        step, q, k, v, ns, nl,
                        min_seconds=flops / (peaks().bf16_flops_per_s * 1.05),
                    )
                    rec = {
                        "kernel": "bwd" if bwd else "fwd", "T": T,
                        "bq": bq, "bk": bk, "us": round(per * 1e6, 1),
                        "tflops": round(flops / per / 1e12, 1),
                        "spread_pct": round(spread, 1),
                        "slope_cycles_us": [round(c * 1e6, 2) for c in cycles],
                        "cycles_dropped": dropped,
                    }
                    if suspect:
                        rec["suspect"] = suspect
                    print(json.dumps(rec), flush=True)
                except Exception as e:
                    print(json.dumps({
                        "kernel": "bwd" if bwd else "fwd", "T": T, "bq": bq,
                        "bk": bk, "error": f"{type(e).__name__}: {e}"[:200],
                    }), flush=True)


if __name__ == "__main__":
    from tree_attention_tpu import obs

    # Env-armed like bench.py (TA_METRICS_OUT / TA_TRACE_EVENTS): without
    # this the guard verdicts filed above would hit a disabled registry.
    obs.configure()
    mode = sys.argv[1] if len(sys.argv) > 1 else "decode"
    try:
        {"decode": sweep_decode, "fwd": sweep_fwd,
         "bwd": lambda: sweep_fwd(bwd=True)}[mode]()
    finally:
        obs.shutdown()
