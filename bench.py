"""Headline benchmark suite: every README perf claim, regenerated each run.

Workloads (VERDICT round-1 item 5 — one driver-parseable record):

- ``decode_64k``   — the reference's hardcoded driver config
  (``/root/reference/model.py:140-145,51-53``): B=1, 16 heads, head_dim 128,
  64000-token context, q_len=1. The headline metric and ``vs_baseline``
  come from here (reference CPU run: 64000 tokens / 5.74 s, BASELINE.md).
- ``decode_gqa_128k`` — 32 query / 4 KV heads, 128k context.
- ``decode_gqa_1m``   — 32 query / 4 KV heads, 1M-token context.
- ``decode_mha_1m``   — 16 MHA heads, 1M-token context (the round-1
  transient-gate cliff case).
- ``train_fwd_bwd``   — causal training-shape forward and forward+backward
  through the Pallas kernels at seq 4096: TFLOP/s and MFU vs the v5e bf16
  peak, with FLOPs counted from the kernels' live-tile launches.
- ``train_fwd_bwd_16k`` — the same at seq 16384 (BASELINE config 2's shape).
- ``tree_vs_ring_decode_cpu8`` — tree vs ring vs Ulysses on the DECODE
  shape (q_len=1, the reference's 16h×128D workload) over the emulated
  8-way mesh, at two contexts (64000 and 2048), each algorithm with
  collective counts and payload bytes parsed from its compiled SPMD
  module (``bench/comm.py``). The accounting — not the emulated wall
  clock — is the number that transfers to real ICI: ``tools/ici_model.py``
  prices it (BASELINE.md north-star section).
- ``tree_vs_ring``    — tree- vs ring- (and zigzag-tree / Ulysses-)
  attention step time on an emulated 8-way sequence mesh (clean
  subprocess, CPU backend; the BASELINE.json north-star ratio's shape).
  Read it as a correctness/latency-shape check, NOT the north star: the
  emulation timeshares every "device" on the same cores, so wall clock
  tracks *total* FLOPs across shards and tree's log-depth collective
  advantage over ICI cannot appear. Since the per-run causal dispatch
  landed (r3), both algorithms cull to the same live T²/2 on every impl,
  so parity (~1.0×) is the expected emulated reading; the remaining
  tree-side costs are its merge collectives, which the emulation prices at
  memcpy cost rather than wire cost. The Ulysses entry reads LOW here for
  the same reason, amplified: its two all-to-alls move Q+K+V+O at full
  size (vs ring's KV-only rotation), and the emulation charges that as
  host memcpy with none of the ICI bisection bandwidth the family is
  designed around.

Measurement protocol:

- decode steps are chained on-device with ``lax.scan`` (each step's query
  derives from the previous output — no inter-step parallelism);
- completion is fenced by fetching a scalar reduction of the output;
- the per-step cost is the **slope** between a short and a long chain,
  cancelling every fixed cost (dispatch, fetch). See
  ``utils.profiling.chain_slope``.

The suite runs on a TPU or not at all: with no TPU attached it exits
non-zero before timing anything, and it exits non-zero after printing if
any record raised. Every record names the ``platform``, ``device_kind`` and
``device_count`` it ran on; the records that run in CPU child processes
(the emulated-mesh comparators) say ``"platform": "cpu"``. Roofline shares
divide by the attached chip's row of ``bench/ici.PEAKS``.

Prints TWO JSON lines: first the full record — top-level keys
{"metric", "value", "unit", "vs_baseline"} with the full suite in "suite" —
then a compact (<1 KB) summary as the LAST line, carrying the same headline
keys plus the device, commit, and one key figure per record (a bounded
stdout tail can truncate the first line; the summary survives).
Decode records report achieved HBM bandwidth and percent of the chip's
roofline; vs_baseline is a smoke datapoint against the reference's CPU run.
"""

import json
import os
import subprocess
import sys

from tree_attention_tpu import obs
from tree_attention_tpu.bench.ici import peaks
from tree_attention_tpu.utils.profiling import (
    deflation_suspect,
    record_guard_verdict,
)

BASELINE_TOKENS_PER_SEC = 64000 / 5.74  # reference model.py on survey CPU


def _device_fields():
    """The device this process runs on, as every record names it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _slope_record_fields(slope, kv_bytes, hbm_bw, name=""):
    """Shared tail for decode records: per-step from the min-over-cycles
    slope, the cycle slopes and spread as the record's own error bar, and
    symmetric plausibility guards against ``hbm_bw`` (the measured chip's
    published bandwidth). Verdicts also file into the telemetry registry
    under ``name`` (guard counters + trace instants) when a run armed it.
    """
    per_step = slope.per_step
    bw = kv_bytes / per_step
    fields = {
        "us_per_step": round(per_step * 1e6, 1),
        "hbm_bytes_per_sec": round(bw, 1),
        "pct_hbm_roofline": round(bw / hbm_bw * 100, 1),
        "slope_cycles_us": [round(s * 1e6, 2) for s in slope.slopes],
        "slope_spread_pct": round(slope.spread_pct, 1),
    }
    # Each screen fires (and files its verdict) independently — a ceiling
    # trip must not mask the deflation annotation; the record's
    # timing_suspect concatenates every reason.
    reasons = []
    deflated = deflation_suspect(slope)
    if bw > 1.05 * hbm_bw:
        reasons.append(
            "implied bandwidth above the HBM spec — the fetch fence did "
            "not fence; discard this record"
        )
        record_guard_verdict(name, "ceiling", reasons[-1])
    if deflated:
        reasons.append(deflated)
        record_guard_verdict(name, "deflation", deflated)
    if reasons:
        fields["timing_suspect"] = "; ".join(reasons)
    elif slope.spread_pct > 15:
        # Inflation-only noise: the min is still the honest estimate — but
        # a wide spread says the window was contended and the min may
        # itself be an upper bound.
        fields["timing_note"] = (
            f"cycle slopes spread {slope.spread_pct:.0f}%: contended "
            "window; per-step is the min cycle (noise is additive)"
        )
        record_guard_verdict(name, "jitter", fields["timing_note"])
    else:
        record_guard_verdict(name, "clean")
    return per_step, fields


def _decode_record(H, Hkv, T, n_small, n_large, block_size=None,
                   impl="auto"):
    """Time ``impl`` (default: the product dispatch) and nothing else: a
    kernel that fails on this hardware fails the record."""
    import jax
    import jax.numpy as jnp

    from tree_attention_tpu.ops import flash_attention
    from tree_attention_tpu.utils.profiling import chain_slope

    D = 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, H, 1, D), jnp.bfloat16)
    k = jax.random.normal(kk, (1, Hkv, T, D), jnp.bfloat16)
    v = jax.random.normal(kv, (1, Hkv, T, D), jnp.bfloat16)

    def step(qc, k_, v_):
        # causal=True with the newest-token position: the exact masking
        # branch the product decode runs (models/decode.py forward_step)
        # — the headline times the shipped code path, not a maskless
        # variant.
        out, _lse = flash_attention(
            qc, k_, v_, causal=True, q_offset=T - 1, impl=impl,
            block_size=block_size, custom_vjp=False,
        )
        return out

    slope = chain_slope(
        step, q, k, v, n_small=n_small, n_large=n_large, repeats=3,
    )
    kv_bytes = 2 * T * Hkv * D * 2
    per_step, fields = _slope_record_fields(
        slope, kv_bytes, peaks().hbm_bytes_per_s, name=f"decode_ctx{T}"
    )
    return {
        "workload": {"heads": H, "kv_heads": Hkv, "context": T,
                     "head_dim": D, "dtype": "bfloat16", "q_len": 1,
                     "causal": True},
        "impl": impl,
        "kv_tokens_per_sec": round(T / per_step, 1),
        **fields,
    }


def _decode_q8_record(H, Hkv, T, n_small, n_large, q_quant=False):
    """Decode over an int8-quantized KV buffer: the same slope protocol,
    half the KV bytes per step. tokens/sec is the headline gain; roofline-%
    is computed against the int8 byte count (the stream the chip actually
    reads). ``q_quant=True`` times the int8-MXU variant (Q quantized per
    row, int8 x int8 scores — no K dequant cast on the stream).

    Both records flow through the product dispatcher
    (``models.decode.decode_attention``, the same entry ``forward_step``
    uses): the bench times the path users get, not a bench-only kernel
    call."""
    import jax
    import jax.numpy as jnp

    from tree_attention_tpu.models.decode import decode_attention
    from tree_attention_tpu.ops.pallas_decode import quantize_kv_channelwise
    from tree_attention_tpu.utils.profiling import chain_slope

    quant_kernel = "q8q" if q_quant else "q8"

    D = 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, H, 1, D), jnp.bfloat16)
    k = jax.random.normal(kk, (1, Hkv, T, D), jnp.bfloat16)
    v = jax.random.normal(kv, (1, Hkv, T, D), jnp.bfloat16)
    k_q, v_q, k_s, v_s = quantize_kv_channelwise(k, v)

    def step(qc, k_q_, v_q_):
        out, _ = decode_attention(
            qc, k_q_, v_q_, k_scale=k_s, v_scale=v_s,
            q_position=T - 1, mesh=None, quant_kernel=quant_kernel,
        )
        return out

    slope = chain_slope(
        step, q, k_q, v_q, n_small=n_small, n_large=n_large, repeats=3,
    )
    kv_bytes = 2 * T * Hkv * D  # int8: one byte per element
    per_step, fields = _slope_record_fields(
        slope, kv_bytes, peaks().hbm_bytes_per_s,
        name=f"decode_{quant_kernel}_ctx{T}",
    )
    return {
        "workload": {"heads": H, "kv_heads": Hkv, "context": T,
                     "head_dim": D, "kv_dtype": "int8", "q_len": 1,
                     "causal": True,
                     "q_dtype": "int8(row)" if q_quant else "bfloat16"},
        "kv_tokens_per_sec": round(T / per_step, 1),
        **fields,
    }


def _live_tiles(Tq, Tk, bq, bk, q_off=0, kv_off=0, causal=True):
    """Causally live (Q-tile, KV-tile) pairs at the kernels' launch geometry
    — the same ``tile_live`` predicate the kernels gate compute on
    (``ops/block_utils.py``), so FLOPs derive from what is actually
    launched, not from a smooth T²/2 idealisation."""
    import numpy as np

    n_q, n_k = -(-Tq // bq), -(-Tk // bk)
    if not causal:
        return n_q * n_k
    qi = np.arange(n_q)[:, None]
    ki = np.arange(n_k)[None, :]
    return int(((q_off + qi * bq + bq - 1) >= (kv_off + ki * bk)).sum())


def _train_record(T=4096, n_small=16, n_large=64):
    """Causal training-shape fwd and fwd+bwd through the Pallas kernels.

    FLOPs are counted from the kernel launches: per live tile pair the fwd kernel runs 2 matmul passes (s = q·kᵀ,
    acc += p·v), the dQ kernel 3 (recompute s, dp = do·vᵀ, dq += ds·k) and
    the dKV kernel 4 (recompute s, dp, dk += dsᵀ·q, dv += pᵀ·do) — each
    pass 2·bq·bk·D FLOPs — so fwd+bwd is 4.5× fwd, not an assumed
    multiplier. MFU is against the attached chip's published bf16 peak.
    """
    import jax
    import jax.numpy as jnp

    from tree_attention_tpu.ops import flash_attention
    from tree_attention_tpu.ops.tuning import default_block_q, default_block_size
    from tree_attention_tpu.utils.profiling import chain_slope

    B, H, D = 1, 16, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, T, D), jnp.bfloat16)

    def fwd_step(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, custom_vjp=False)[0]

    def bwd_step(q_, k_, v_):
        def loss(q__, k__, v__):
            o, _ = flash_attention(q__, k__, v__, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        # Differentiate w.r.t. all three operands and fold every gradient
        # into the carried value: training needs dk/dv too, and grad-wrt-q
        # alone lets XLA dead-code-eliminate the dKV kernel — the timed
        # work would then be ~5 of the 9 counted passes (verified via
        # compiled cost_analysis).
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)
        return dq + dk + dv

    # repeats=3 (not 2): the deflation guard below needs >= 3 cycles to
    # tell a deflated min from one ordinarily-contended sibling.
    s_fwd = chain_slope(
        fwd_step, q, k, v, n_small=n_small, n_large=n_large, repeats=3,
    )
    s_both = chain_slope(
        bwd_step, q, k, v, n_small=n_small, n_large=n_large, repeats=3,
    )
    per_fwd, per_both = s_fwd.per_step, s_both.per_step
    bq = default_block_q(T, T)
    bk = default_block_size("pallas", T)
    pass_flops = 2 * bq * bk * D * B * H * _live_tiles(T, T, bq, bk)
    bf16_peak = peaks().bf16_flops_per_s
    fwd_flops = 2 * pass_flops
    both_flops = 9 * pass_flops  # fwd 2 + dQ 3 + dKV 4
    rec = {
        "workload": {"batch": B, "heads": H, "seq_len": T, "head_dim": D,
                     "causal": True, "dtype": "bfloat16",
                     "block_q": bq, "block_k": bk},
        "fwd": {
            "us_per_step": round(per_fwd * 1e6, 1),
            "tflops_per_sec": round(fwd_flops / per_fwd / 1e12, 1),
            "mfu_pct": round(fwd_flops / per_fwd / bf16_peak * 100, 1),
            "slope_cycles_us": [round(s * 1e6, 2) for s in s_fwd.slopes],
            "slope_spread_pct": round(s_fwd.spread_pct, 1),
        },
        "fwd_bwd": {
            "us_per_step": round(per_both * 1e6, 1),
            "tflops_per_sec": round(both_flops / per_both / 1e12, 1),
            "mfu_pct": round(both_flops / per_both / bf16_peak * 100, 1),
            "slope_cycles_us": [round(s * 1e6, 2) for s in s_both.slopes],
            "slope_spread_pct": round(s_both.spread_pct, 1),
        },
    }
    # Same physical-plausibility fences as the decode records: >100% MFU is
    # not a fast chip, it is a fence that did not fence, and a min cycle
    # far below the median cycle is a deflated fetch. The flag keeps the
    # record out of the pricing model's inputs. Both guards run
    # unconditionally: a pass tripping the MFU ceiling must not suppress the (more actionable) deflation annotation
    # for the other pass — the reasons concatenate.
    reasons = []
    if any(rec[p]["mfu_pct"] > 100 for p in ("fwd", "fwd_bwd")):
        reasons.append(
            "MFU above the bf16 peak — the fetch fence did not fence; "
            "discard this record"
        )
        record_guard_verdict(f"train_{T}", "ceiling", reasons[-1])
    deflated = deflation_suspect(s_fwd) or deflation_suspect(s_both)
    if deflated:
        reasons.append(deflated)
        record_guard_verdict(f"train_{T}", "deflation", deflated)
    if reasons:
        rec["timing_suspect"] = "; ".join(reasons)
    else:
        record_guard_verdict(f"train_{T}", "clean")
    return rec


def _cpu_child_env(n_devices):
    """Environment of a child process pinned to the CPU with ``n_devices``
    virtual devices (this process holds the chip). The child is a single
    process with no rank contract: inherited telemetry sinks would resolve
    to the PARENT's paths and truncate the trace file it still has open."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TA_METRICS_OUT", None)
    env.pop("TA_TRACE_EVENTS", None)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    return env


def _cpu_child_device(n_devices):
    """What a record from such a child says about its device."""
    return {"platform": "cpu", "device_kind": "cpu",
            "device_count": n_devices}


def _comparator_subprocess(args, timeout=900):
    """Run a CLI comparator bench on an emulated 8-way seq mesh, in a clean
    CPU subprocess (this process holds the chip; the emulated mesh needs a
    CPU-only process with the host-device-count flag set before JAX init).
    Returns the CLI's JSON record, stamped ``"platform": "cpu"``: its
    timings are host timings and never sit unlabelled beside chip
    records."""
    proc = subprocess.run(
        [sys.executable, "-m", "tree_attention_tpu", "--mode", "bench",
         "--device", "cpu", "--n-virtual-cpu", "8", "--mesh", "seq=8",
         "--causal"] + args,
        env=_cpu_child_env(8), cwd=os.path.dirname(os.path.abspath(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"comparator subprocess rc={proc.returncode}: "
            f"{proc.stderr[-500:]}"
        )
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            return {**json.loads(line), **_cpu_child_device(8)}
    raise RuntimeError("comparator subprocess printed no JSON")


def _tree_vs_ring_record():
    """Tree vs ring on the TRAINING shape (fwd+bwd, all-sharded Q/K/V).

    heads=8 (divisible by the 8-way mesh) lets the Ulysses family join
    the same record; per-head FLOPs halve via head_dim to keep the
    record's runtime in its old envelope.

    The comparator times with a min-stat estimator (see
    ``bench_train_attention`` — single-step min on the emulated mesh,
    slope on TPU meshes), runs the 4k shape TWICE in separate processes
    and reports the ratio spread, and adds a second shape — T=8192,
    GQA-4 (8 q heads / 2 KV heads) — where only tree/ring (and zigzag)
    race: Ulysses' head-divisibility (2 KV heads over an 8-way mesh)
    excludes it, which is itself the point (SURVEY §2.4 — tree serves
    GQA where Ulysses cannot)."""
    shape_4k = ["--comparator", "ring", "--seq-len", "4096",
                "--heads", "8", "--head-dim", "32", "--iters", "3",
                "--dtype", "float32"]
    rec = _comparator_subprocess(shape_4k)
    # Later sub-runs must not discard this one: each is minutes of host
    # compute, so a failed rerun/gqa subprocess is recorded as an error
    # (and fails the suite's exit code) without erasing the record.
    try:
        rerun = _comparator_subprocess(shape_4k)
        spread = abs(
            rerun["tree_speedup_vs_ring"] - rec["tree_speedup_vs_ring"]
        ) / rec["tree_speedup_vs_ring"]
        rec["second_run"] = {
            k: v for k, v in rerun.items() if k.endswith("speedup_vs_ring")
        }
        rec["ratio_spread_pct"] = round(spread * 100, 2)
    except Exception as e:
        rec["second_run"] = {"error": f"{type(e).__name__}: {e}"}
    # 8 heads GQA-4 at head_dim 16 keeps the 8k shape's serialised-CPU
    # cost in budget (a 16h×32D variant measured >30 min of 1-core time):
    # the comparison isolates the communication pattern, and head
    # count/width only scale the identical local compute both sides run.
    # kv_heads=2 still excludes Ulysses (2 % 8 != 0) — the GQA point.
    try:
        rec["gqa_8k"] = _comparator_subprocess(
            ["--comparator", "ring", "--seq-len", "8192",
             "--heads", "8", "--kv-heads", "2", "--head-dim", "16",
             "--iters", "3", "--dtype", "float32"],
            timeout=2400,
        )
    except Exception as e:
        rec["gqa_8k"] = {"error": f"{type(e).__name__}: {e}"}
    return rec


def _attach_measurement_artifacts(suite):
    """Attach this round's once-per-round measured artifacts to the suite.

    The N-scaling sweep (hours of serialized 1-core compute,
    ``tools/scaling_sweep.py``) and the stock-kernel race (chip time,
    ``tools/race_stock_flash.py``) are too expensive to regenerate on
    every bench invocation; their tools write JSON artifacts under the
    round's ``measurements/r{N}/`` and this attaches the NEWEST round's
    copy of each (so a later round that has not re-run a sweep still
    surfaces the newest one that exists), with its embedded commit +
    capture-time provenance and source path — a stale artifact is
    auditable rather than invisible."""
    import glob as _glob

    here = os.path.dirname(os.path.abspath(__file__))
    for name, fname, tool in (
        ("tree_vs_ring_decode_scaling", "decode_scaling.json",
         "scaling_sweep"),
        ("stock_flash_race", "stock_flash_race.json", "race_stock_flash"),
    ):
        paths = sorted(
            _glob.glob(os.path.join(here, "measurements", "r*", fname)),
            # r10 must sort after r9: numeric round key, not lexical.
            key=lambda p: (len(os.path.basename(os.path.dirname(p))),
                           os.path.basename(os.path.dirname(p))),
        )
        if not paths:
            suite[name] = {
                "skipped": f"no measurements/r*/{fname} artifact "
                           f"(run tools/{tool}.py)"
            }
            continue
        path = paths[-1]
        try:
            with open(path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError(f"expected a JSON object, got {type(data).__name__}")
            data["artifact_path"] = os.path.relpath(path, here)
            suite[name] = data
        except (OSError, ValueError) as e:
            suite[name] = {"error": f"unreadable artifact {path}: {e}"}


def _ici_crossover_record(suite):
    """Re-price the north-star tree÷ring crossover from THIS run's
    measurements (VERDICT r4 item 4: the falsifiable chain must rebuild its
    measured terms every run, not quote a frozen literal).

    - ``roofline_frac``: median over this run's non-suspect decode records.
    - merge payloads: the compiled-HLO comm accounting from this run's
      decode comparator for the MHA reference shape; the GQA table prices
      its 2× larger 32-query-head merge from the closed form, because the
      measured payload is a 16-head quantity (ADVICE r4 item 3).
    """
    from tree_attention_tpu.bench.ici import (
        crossover_table,
        decode_record_pcts,
        measured_roofline_frac,
        payloads_from_comm_record,
    )

    # One shared exclusion rule (ici.decode_record_pcts): decode records
    # only, nothing flagged timing_suspect.
    pcts = decode_record_pcts(suite, key="pct_hbm_roofline")
    frac = measured_roofline_frac(pcts)
    payloads = None
    for sub in (suite.get("tree_vs_ring_decode_cpu8") or {}).values():
        if isinstance(sub, dict):
            payloads = payloads_from_comm_record(sub)
            if payloads:
                break
    mha_kw = {}
    if payloads:
        mha_kw = dict(tree_payload=payloads["tree"],
                      ring_hop_payload=payloads["ring_hop"])
    return {
        "roofline_frac": round(frac, 4),
        "roofline_frac_source": (
            f"median of {len(pcts)} decode records this run" if pcts
            else "fallback constant (no decode records this run)"
        ),
        "payload_source": (
            "compiled-HLO comm accounting this run (MHA table)"
            if payloads else "closed form"
        ),
        "mha_1m": crossover_table(1 << 20, roofline_frac=frac, **mha_kw),
        "gqa4_1m": crossover_table(
            1 << 20, roofline_frac=frac, q_heads=32, kv_heads=4,
        ),
    }


def _git_commit():
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip()
    except Exception:
        return ""


def _tree_vs_ring_decode_record():
    """Tree vs ring vs Ulysses on the DECODE shape (VERDICT r3 item 1) —
    the reference's entire workload (model.py:140-145: q_len=1, 16 heads ×
    128), raced over the 8-way emulated mesh with collective counts and
    bytes-on-wire parsed from each algorithm's compiled SPMD module.

    Two contexts bracket what 1-core emulation can and cannot show:

    - ``ctx_64000`` (the reference's): per-step wall clock is dominated by
      the serialised local compute (all 8 "devices" timeshare one core),
      so the ratio reads ~1.0 — collectives priced at memcpy cannot
      surface the merge's depth difference under 1.6 s of compute.
    - ``ctx_2048``: local compute shrinks ~30×, the merge chain dominates,
      and even at memcpy pricing the ring's 14 sequential dispatches lose
      visibly to the tree's 2 fused collectives.

    The comm accounting (identical at both contexts — the merge payload is
    context-independent for tree/ring, linear for Ulysses) is the
    transferable measurement: BASELINE.md's ICI model prices it for real
    hardware, which is what makes the ≥2×-vs-ring north star falsifiable.
    """
    rec = _cpu_child_device(8)
    for ctx, iters in ((64000, 4), (2048, 6)):
        # Per-context isolation: one context's failure must not erase the
        # other's minutes of serialised host compute.
        try:
            rec[f"ctx_{ctx}"] = _comparator_subprocess(
                ["--comparator", "ring-decode", "--seq-len", str(ctx),
                 "--q-len", "1", "--heads", "16", "--head-dim", "128",
                 "--iters", str(iters), "--dtype", "float32"],
                timeout=1800,
            )
        except Exception as e:
            rec[f"ctx_{ctx}"] = {"error": f"{type(e).__name__}: {e}"}
    # The note derives from THIS run's measured ratios (a hardcoded
    # historical range goes silently stale) — the point stands on
    # its own: emulated wall clock prices collectives at memcpy cost, so
    # only the comm blocks and the N-scaling artifact transfer.
    measured = ", ".join(
        f"{ctx} tree/ring {sub['tree_speedup_vs_ring']}x"
        for ctx, sub in rec.items()
        if isinstance(sub, dict) and "tree_speedup_vs_ring" in sub
    )
    rec["wall_clock_note"] = (
        "emulated ratios are scheduling-noisy; this run measured "
        f"{measured or 'no healthy sub-run'} — read the comm blocks and "
        "the N-scaling artifact, not any single ratio"
    )
    return rec


def _serving_record():
    """Continuous batching vs sequential decode (ISSUE 2): the slot
    scheduler's one-compiled-step-per-tick throughput at 8 slots against
    one-request-at-a-time decode, slope-timed via the blessed chain_slope
    harness plus real engine trace runs swept over slots and arrival
    rates. A CPU proxy by design — the measured quantity is the batching
    structure (fixed per-step cost amortised across slots), which
    transfers; see tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving

    return bench_serving()


def _serving_prefix_record():
    """Shared-prefix flood (ISSUE 5): TTFT p50/p95 with the radix prefix
    KV cache on vs off over a trace where >= 50% of requests share a
    512-token prompt prefix (RadixAttention, arXiv:2312.07104), plus the
    chain_slope-priced shared-prefix prefill over the host table update
    that replaces it on a hit. CPU proxy; the avoided-prefill
    structure transfers. See tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_prefix_flood

    return bench_serving_prefix_flood()


def _serving_spec_record():
    """Speculative decoding (ISSUE 8): decode tokens/sec per slot with
    draft-and-verify on vs off over a repetitive/templated trace
    (arXiv:2211.17192; token-tree drafts under the tree-attention mask,
    SpecInfer arXiv:2305.09781) — plus the chain_slope-priced verify-tick
    cost the accepted bursts must amortise. Parity-gated: the committed
    streams are asserted token-identical before any number is reported.
    CPU proxy; the fewer-fatter-ticks structure transfers. See
    tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_speculative

    return bench_serving_speculative()


def _serving_paged_record():
    """Paged KV flood (ISSUE 6): the PR-5 shared-prefix flood on the
    paged pool — the host table update that is all a hit pays
    (bytes_moved == 0), TTFT p50/p95, and max concurrent requests when
    the same pool bytes are over-subscribed with more slots
    (PagedAttention, arXiv:2309.06180). CPU proxy; the zero-copy and
    capacity structure transfers. See tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_paged_flood

    return bench_serving_paged_flood()


def _serving_ingress_record():
    """Chaos harness over the live HTTP ingress (ISSUE 10): a heavy-tail
    timestamped trace replayed against a loopback SSE server — clean
    baseline, then a disconnect storm + slow readers (survivor streams
    token-identical, allocator/pin state leak-free), a deadline-heavy
    overload with shedding+backpressure on vs off (goodput-under-SLO,
    measured client-side), the 429+Retry-After contract, and a graceful
    drain. CPU proxy; the robustness structure is the claim. See
    tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_ingress

    return bench_serving_ingress()


def _serving_fleet_record():
    """Prefix-affinity fleet (ISSUE 11): four replica engines behind the
    cache-aware router on a multi-tenant shared-prefix heavy-tail trace
    (SGLang's cache-aware routing, arXiv:2312.07104) — affinity vs
    round-robin at equal total slots/pool bytes (TTFT p50 + tokens-
    reused ratio must both be strictly better with affinity), routed
    streams parity-gated against direct serving, and a full rolling
    restart DURING a replay with zero dropped accepted requests and
    leak-free drained allocators. CPU proxy; the routing structure is
    the claim. See tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_fleet

    return bench_serving_fleet()


def _serving_disagg_record():
    """Disaggregated prefill/decode (ISSUE 12): a prefill-pool + decode-
    pool pair over ONE shared paged block pool (DistServe arXiv:
    2401.09670, Splitwise arXiv:2311.18677) vs the fused engine under a
    prefill flood, at equal total slots and pool bytes. Decode TBT p99
    must hold ~flat as prefill arrival rate doubles (interference_ratio
    ~1) while the fused engine's mixed ticks degrade; handoffs are pure
    ownership transfer (kv_bytes_moved_total pinned 0), streams parity-
    gated token-identical, allocators drain to zero. CPU proxy with
    per-worker time attribution; the isolation structure is the claim.
    See tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_disagg

    return bench_serving_disagg()


def _serving_tiered_record():
    """Hierarchical KV cache (ISSUE 13): a host-RAM demotion tier under
    the device pool (SGLang's hierarchical-cache direction over
    RadixAttention arXiv:2312.07104) on a multi-prefix flood whose KV
    population overflows the device pool — pass-2 hit-rate and TTFT p50
    with tiering on must hold near the fits-in-device ceiling while
    tiering off re-pays cold prefill — plus int8 per-block-scale
    capacity: max concurrent requests at equal device pool bytes, int8
    vs exact (~the bytes ratio, now that int8 blocks share through the
    radix tree). Token-parity-gated across the tiering arms; both
    allocators (device AND host) checked drained. CPU proxy; the
    hit-rate/capacity structure transfers. See
    tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_tiered_kv

    return bench_serving_tiered_kv()


def _serving_forked_record():
    """Copy-on-write forked sampling (ISSUE 15): one prefill fans out to
    n completions whose block tables SHARE every full ancestor block
    (vLLM's CoW fork over PagedAttention tables, arXiv:2309.06180) —
    n=8 must peak at <= 2x the pool bytes of n=1 at this shape (naive
    is 8x), per-branch TTFT p50 within 1.3x (the prompt prefills once
    per family), fork_share_ratio = the fraction of a sibling's
    worst-case blocks served by refcount sharing. Parity-gated twice:
    greedy n=8 token-identical to 8 independent requests, sampled
    families bit-reproducible across serves (per-request PRNG keys).
    CPU proxy; the sharing economics are ledger math and transfer
    exactly. See tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import (
        bench_serving_forked_sampling,
    )

    return bench_serving_forked_sampling()


def _serving_tree_record():
    """Token-tree sibling decode (ISSUE 20): an n=8 family decoded as
    ONE tree-masked row bundle in ONE slot (SpecInfer's tree aimed at
    sibling futures, arXiv:2305.09781) vs the PR-15 fork-slot path at
    equal pool bytes — pool_bytes_ratio <= 1.0 asserted, burst
    max-concurrent and per-branch TTFT p50 ratios reported. Parity-gated
    both ways: tree branches token-identical to fork slots under the
    same seed, bit-reproducible across serves. Plus the stochastic
    speculative-acceptance distribution gate: spec-on temperature-0.8
    decode (Leviathan ratio test, arXiv:2211.17192) asserted bit-equal
    to the non-speculative sampled stream. CPU proxy; the slot/pool
    economics are ledger math and transfer exactly. See
    tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import bench_serving_tree_sampling

    return bench_serving_tree_sampling()


def _serving_telemetry_record():
    """Request-telemetry overhead (ISSUE 16): the fleet trace replayed
    through the router with end-to-end request telemetry ON (traceparent
    propagation, flow events, per-request cost ledgers) vs ALL OFF on
    the same engines — tokens/sec and TTFT p50 gated within 5%, the
    disabled path asserted allocation-free (ledger untouched by a full
    replay), and the on arm's trace sink checked for the complete
    router->replica flow chain. CPU proxy; the overhead structure is
    the claim. See tree_attention_tpu/bench/serving.py."""
    from tree_attention_tpu.bench.serving import (
        bench_serving_request_telemetry,
    )

    return bench_serving_request_telemetry()


def _serving_seq_sharded_record():
    """Sequence-sharded paged serving (ISSUE 18): max servable context
    at EQUAL per-device pool bytes, mesh=1 vs a mesh=2 pool range-
    partitioned by --kv-shard seq — both capacity boundaries measured
    (the pool-filling request streams, one block more is rejected),
    TTFT/TBT p50 on a common trace parity-gated against a mesh=2
    replicated oracle, and the decode merge asserted to cost EXACTLY
    three collectives (pmax + 2x psum, the tree monoid arXiv:2408.04093)
    via the accounting counters. CPU proxy on the emulated 2-device
    mesh; the capacity-scaling structure transfers. See
    tree_attention_tpu/bench/serving.py.

    Needs >= 2 CPU devices, which requires the host-device-count XLA
    flag BEFORE jax init, and this process holds the chip: the record runs
    in a clean CPU subprocess like the comparator benches and says
    ``"platform": "cpu"``."""
    code = (
        "import json\n"
        "from tree_attention_tpu.bench.serving import "
        "bench_serving_seq_sharded\n"
        "print(json.dumps(bench_serving_seq_sharded()))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=_cpu_child_env(2), cwd=os.path.dirname(os.path.abspath(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"seq-sharded subprocess rc={proc.returncode}: "
            f"{proc.stderr[-500:]}"
        )
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return {**json.loads(line), **_cpu_child_device(2)}
    raise RuntimeError("seq-sharded subprocess printed no JSON")


def main() -> None:
    # Telemetry is env-armed here (TA_METRICS_OUT / TA_TRACE_EVENTS — this
    # entry point has no flags by contract: the driver parses its stdout);
    # unarmed, every obs call below is a no-op flag check. The snapshot
    # writes in a finally: a crash (or Ctrl-C) after hours of records must
    # not lose the counters those records already filed.
    obs.configure()
    http_server = None
    port_env = os.environ.get("TA_METRICS_PORT")
    if port_env:
        # Live view of a multi-hour suite (this entry point has no flags
        # by contract): curl /metrics while the records run. The ring must
        # turn too — /healthz liveness and /flight read it (memory-only
        # unless TA_FLIGHT_OUT also armed a dump sink).
        from tree_attention_tpu.obs.http import MetricsHTTPServer

        obs.REGISTRY.enable()
        if not obs.FLIGHT.enabled:
            obs.FLIGHT.arm()
        http_server = MetricsHTTPServer(int(port_env))
        print(f"# telemetry: http://127.0.0.1:{http_server.start()}/metrics",
              file=sys.stderr)
    if obs.REGISTRY.enabled or obs.TRACER.active or obs.FLIGHT.enabled:
        # Crash-safe: a Ctrl-C / SIGTERM mid-suite still flushes the
        # armed sinks (the finally below handles the clean paths).
        obs.install_crash_handlers()
    try:
        failed = _run_suite()
    finally:
        if http_server is not None:
            http_server.stop()
        obs.shutdown()
    if failed:
        sys.exit(f"bench.py: {len(failed)} record(s) failed: {failed}")


def _nested_errors(rec):
    """True when a record carries a sub-run's ``{"error": "<message>"}``
    (the note a record leaves for a sub-run that raised)."""
    return isinstance(rec, dict) and any(
        isinstance(sub, dict)
        and (isinstance(sub.get("error"), str) or _nested_errors(sub))
        for sub in rec.values()
    )


def _run_suite():
    """Run every record on the attached TPU, print the two JSON lines, and
    return the names of the records that failed (the caller exits non-zero
    on any)."""
    import traceback

    from tree_attention_tpu.cli import configure_compile_cache

    configure_compile_cache()
    device = _device_fields()
    if device["platform"] != "tpu":
        sys.exit(
            f"bench.py measures on a TPU and found platform "
            f"{device['platform']!r} ({device['device_kind']}): nothing was "
            "timed. There is no CPU fallback; run it on a machine with a "
            "chip."
        )
    suite = {}
    failed = []

    def run(name, fn, *args, **kwargs):
        try:
            with obs.span(f"bench:{name}", cat="bench"):
                rec = fn(*args, **kwargs)
        except Exception as e:
            # Keep collecting the other records, but the failure is kept:
            # the traceback goes to stderr and the process exits non-zero.
            traceback.print_exc()
            suite[name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)
            return
        if _nested_errors(rec):
            failed.append(name)
        # Records from CPU children arrive already stamped "cpu".
        suite[name] = {**device, **rec}

    # Chain lengths are sized so the marginal work (n_large - n_small)
    # x per-step clears ~100 ms, so the slope dwarfs residual per-call
    # jitter.
    run("decode_64k", _decode_record, 16, 16, 64000, 32, 256)
    run("decode_gqa_128k", _decode_record, 32, 4, 131072, 32, 320)
    run("decode_gqa_1m", _decode_record, 32, 4, 1 << 20, 4, 40)
    run("decode_mha_1m", _decode_record, 16, 16, 1 << 20, 2, 12)
    run("decode_64k_q8", _decode_q8_record, 16, 16, 64000, 32, 320)
    run("decode_64k_q8q", _decode_q8_record, 16, 16, 64000, 32, 320,
        q_quant=True)
    # BASELINE config 4's class (GQA decode against a long cache) over
    # the quantized path: 32q/4kv at 256k ctx, int8-MXU kernel through
    # the product dispatcher.
    run("decode_gqa_256k_q8q", _decode_q8_record, 32, 4, 1 << 18, 32,
        320, q_quant=True)
    run("train_fwd_bwd", _train_record, 4096, 16, 256)
    # BASELINE config 2's shape (seq 16384): MFU progress toward the
    # north star is tracked round over round at this length too.
    run("train_fwd_bwd_16k", _train_record, 16384, 2, 16)
    # The longest single-chip-feasible causal training shapes: 32k, 64k
    # and 128k anchor the config-5 scaling trend this hardware can
    # produce. Short chains — the steps are 4x/16x/64x the 16k step's
    # work, so the slope base is already >100 ms.
    run("train_fwd_bwd_32k", _train_record, 32768, 2, 6)
    run("train_fwd_bwd_64k", _train_record, 65536, 1, 3)
    # One more doubling of the ladder. The chunked Q gather bounds the
    # transient; Q/K/V + grads at 128k are ~3.2 GB of the 16 GB HBM, and
    # flash recompute keeps activations O(T).
    run("train_fwd_bwd_128k", _train_record, 131072, 1, 3)
    # Allocator peak has no reset API, so a per-workload peak is not
    # observable in one process — record the process-lifetime peak once
    # (set by the largest workload, the 1M-context decode). Per-workload
    # peaks come from the CLI bench mode, which runs one workload per
    # process (bench/harness.py `_peak_hbm`).
    from tree_attention_tpu.bench.harness import _peak_hbm

    peak = _peak_hbm()
    if peak is not None:
        suite["peak_hbm_bytes_process"] = peak
    run("tree_vs_ring_cpu8", _tree_vs_ring_record)
    run("tree_vs_ring_decode_cpu8", _tree_vs_ring_decode_record)
    run("serving_continuous_batching", _serving_record)
    run("serving_prefix_flood", _serving_prefix_record)
    run("serving_paged_flood", _serving_paged_record)
    run("serving_speculative", _serving_spec_record)
    run("serving_ingress_chaos", _serving_ingress_record)
    run("serving_fleet", _serving_fleet_record)
    run("serving_disagg", _serving_disagg_record)
    run("serving_tiered_kv", _serving_tiered_record)
    run("serving_forked_sampling", _serving_forked_record)
    run("serving_tree_sampling", _serving_tree_record)
    run("serving_request_telemetry", _serving_telemetry_record)
    run("serving_seq_sharded", _serving_seq_sharded_record)
    run("ici_crossover", _ici_crossover_record, suite)
    _attach_measurement_artifacts(suite)

    metric = "decode_kv_tokens_per_sec_64k_ctx_1chip"
    head = suite.get("decode_64k", {})
    if "timing_suspect" in head:
        # The record says its own number is untrustworthy; a headline
        # consumer must see that without opening the suite.
        metric += "_SUSPECT"
    tokens_per_sec = head.get("kv_tokens_per_sec", 0.0)
    record = {
        "metric": metric,
        "value": tokens_per_sec,
        "unit": "tokens/sec",
        "vs_baseline": round(tokens_per_sec / BASELINE_TOKENS_PER_SEC, 2),
        **device,
        "suite": suite,
    }
    print(json.dumps(record))
    # A bounded stdout TAIL can truncate the full record above mid-object.
    # A compact summary printed LAST always survives the tail and carries
    # the headline, the device, and one key figure per record.
    print(json.dumps(_summary_line(record)))
    return failed


def _summarize_record(name, rec):
    """One key figure per suite record for the compact summary line."""
    if not isinstance(rec, dict):
        return None
    if "error" in rec:
        return "error"
    if "skipped" in rec:
        return "skipped"
    out = {}
    if "pct_hbm_roofline" in rec:
        out["pct_roofline"] = rec["pct_hbm_roofline"]
        # The record's own error bar: the summary a driver keeps must say
        # how trustworthy its headline figure is.
        if "slope_spread_pct" in rec:
            out["spread_pct"] = rec["slope_spread_pct"]
        if "timing_suspect" in rec:
            out["timing_suspect"] = True
    for pass_name in ("fwd", "fwd_bwd"):
        if pass_name in rec and "mfu_pct" in rec[pass_name]:
            out[f"{pass_name}_mfu_pct"] = rec[pass_name]["mfu_pct"]
            if "timing_suspect" in rec:
                out["timing_suspect"] = True
    for key in ("tree_speedup_vs_ring", "tree_zigzag_speedup_vs_ring",
                "ratio_spread_pct"):
        if key in rec:
            out[key] = rec[key]
    if "gqa_8k" in rec and "tree_speedup_vs_ring" in rec["gqa_8k"]:
        out["gqa_8k_vs_ring"] = rec["gqa_8k"]["tree_speedup_vs_ring"]
        if "tree_zigzag_speedup_vs_ring" in rec["gqa_8k"]:
            out["gqa_8k_zigzag_vs_ring"] = (
                rec["gqa_8k"]["tree_zigzag_speedup_vs_ring"]
            )
    if name.startswith("tree_vs_ring_decode"):
        for ctx, sub in rec.items():
            if isinstance(sub, dict) and "tree_speedup_vs_ring" in sub:
                out[f"{ctx}_vs_ring"] = sub["tree_speedup_vs_ring"]
    if name == "serving_continuous_batching":
        slope = rec.get("slope", {})
        if "speedup_vs_sequential" in slope:
            out["slope_speedup_vs_sequential"] = (
                slope["speedup_vs_sequential"]
            )
        trace = rec.get("trace", {})
        if "trace_speedup_vs_sequential" in trace:
            out["trace_speedup_vs_sequential"] = (
                trace["trace_speedup_vs_sequential"]
            )
    if name == "serving_prefix_flood":
        slope = rec.get("slope", {})
        if "prefill_avoided_ratio" in slope:
            out["prefill_avoided_ratio"] = slope["prefill_avoided_ratio"]
        trace = rec.get("trace", {})
        for key in ("ttft_p50_improvement", "ttft_p95_improvement"):
            if key in trace:
                out[key] = trace[key]
        reused = trace.get("on", {}).get("tokens_reused_ratio")
        if reused is not None:
            out["tokens_reused_ratio"] = reused
    if name == "serving_paged_flood":
        trace = rec.get("trace", {})
        if "max_concurrent_improvement" in trace:
            out["max_concurrent_improvement"] = \
                trace["max_concurrent_improvement"]
        moved = trace.get("paged", {}).get("hit_bytes_moved")
        if moved is not None:
            out["paged_hit_bytes_moved"] = moved
    if name == "serving_speculative":
        trace = rec.get("trace", {})
        for key in ("tokens_per_sec_improvement",
                    "tree_tokens_per_sec_improvement"):
            if key in trace:
                out[key] = trace[key]
        acc = trace.get("on", {}).get("acceptance_rate")
        if acc is not None:
            out["acceptance_rate"] = acc
    if name == "serving_fleet":
        gain = rec.get("fleet_affinity_gain", {})
        for key in ("ttft_improvement", "reused_ratio_improvement",
                    "affinity_share"):
            if gain.get(key) is not None:
                out[key] = gain[key]
        roll = rec.get("rolling_restart", {})
        if "dropped_total" in roll:
            out["restart_dropped"] = roll["dropped_total"]
    if name == "serving_disagg":
        for arm in ("fused", "disagg"):
            r = rec.get(arm, {}).get("interference_ratio")
            if r is not None:
                out[f"{arm}_interference_ratio"] = r
        if "isolation_improvement" in rec:
            out["isolation_improvement"] = rec["isolation_improvement"]
        moved = rec.get("disagg", {}).get("kv_bytes_moved_total")
        if moved is not None:
            out["kv_bytes_moved_total"] = moved
    if name == "serving_tiered_kv":
        tier = rec.get("tiering", {})
        for key in ("hit_rate_improvement", "ttft_p50_improvement",
                    "restore_ratio"):
            if key in tier:
                out[key] = tier[key]
        cc = rec.get("int8_capacity", {}).get("max_concurrent_improvement")
        if cc is not None:
            out["int8_max_concurrent_improvement"] = cc
    if name == "serving_forked_sampling":
        fam = rec.get("family", {})
        for key in ("pool_bytes_ratio", "fork_share_ratio",
                    "pool_bytes_per_completion"):
            if key in fam:
                out[key] = fam[key]
        ratio = rec.get("trace", {}).get("ttft_p50_ratio")
        if ratio is not None:
            out["fork_ttft_p50_ratio"] = ratio
    if name == "serving_tree_sampling":
        fam = rec.get("family", {})
        if "pool_bytes_ratio" in fam:
            out["tree_pool_bytes_ratio"] = fam["pool_bytes_ratio"]
        tr = rec.get("trace", {})
        for key in ("max_concurrent_improvement", "tokens_per_sec_ratio",
                    "ttft_p50_ratio"):
            if key in tr:
                out[key] = tr[key]
        acc = rec.get("stochastic", {}).get("acceptance_rate")
        if acc is not None:
            out["stochastic_acceptance_rate"] = acc
    if name == "serving_request_telemetry":
        ov = rec.get("overhead", {})
        for key in ("tokens_per_sec_ratio", "ttft_p50_ratio"):
            if key in ov:
                out[key] = ov[key]
        flows = rec.get("on", {}).get("flow_events")
        if flows:
            out["flow_events"] = sum(flows.values())
        if "ledgers_recorded" in rec.get("on", {}):
            out["ledgers_recorded"] = rec["on"]["ledgers_recorded"]
    if name == "serving_seq_sharded":
        if "max_context_ratio" in rec:
            out["max_context_ratio"] = rec["max_context_ratio"]
        for arm in ("mesh1", "mesh2_seq"):
            ctx = rec.get(arm, {}).get("max_context_tokens")
            if ctx is not None:
                out[f"{arm}_max_context_tokens"] = ctx
        lat = rec.get("latency", {})
        for arm in ("seq", "replicated"):
            p50 = lat.get(arm, {}).get("ttft_p50_s")
            if p50 is not None:
                out[f"ttft_p50_{arm}_s"] = p50
        if "merge_collectives" in rec:
            out["merge_collectives_count"] = len(rec["merge_collectives"])
    if name == "ici_crossover":
        out["roofline_frac"] = rec.get("roofline_frac")
        for table in ("mha_1m", "gqa4_1m"):
            if table in rec:
                out[f"{table}_first_2x"] = rec[table].get("first_n_with_2x")
    if name == "tree_vs_ring_decode_scaling" and isinstance(
        rec.get("cells"), dict
    ):
        # Compact: the summary line must stay well under the driver's
        # bounded tail, so carry only the structural headline — the
        # largest-N small-ctx cell, where ring's 2(N−1) hop chain
        # diverges hardest — plus the cell count; the full sweep stays
        # in the suite line and the artifact.
        best = None
        for key, cell in rec["cells"].items():
            if (key.startswith("ctx2048")
                    and "tree_speedup_vs_ring" in cell
                    and isinstance(cell.get("ring"), dict)):
                n = cell.get("n_devices", 0)
                if best is None or n > best[0]:
                    best = (n, cell)
        if best is not None:
            n, cell = best
            out[f"ctx2048_n{n}_vs_ring"] = cell["tree_speedup_vs_ring"]
            out[f"ctx2048_n{n}_ring_collectives"] = (
                cell["ring"]["collective_count"]
            )
        elif any(
            isinstance(c, dict) and "error" in c
            for c in rec["cells"].values()
        ):
            # No healthy small-ctx cell AND errors present: a bare cell
            # count must not read as a healthy record.
            out["cells_errored"] = True
        out["cells"] = len(rec["cells"])
    if name == "stock_flash_race" and isinstance(rec.get("cells"), dict):
        for key, cell in sorted(rec["cells"].items()):
            if "ours_vs_stock" in cell:
                out[f"{key}_ours_vs_stock"] = cell["ours_vs_stock"]
    if not out and any(
        isinstance(sub, dict) and "error" in sub for sub in rec.values()
    ):
        # All figures failed in nested sub-runs: surface that in the
        # summary rather than silently omitting the record (a missing key
        # would read as "not run").
        return "error"
    return out or None


def _summary_line(record):
    commit = _git_commit()
    records = {}
    for name, rec in record["suite"].items():
        s = _summarize_record(name, rec)
        if s is not None:
            records[name] = s
    return {
        "metric": record["metric"],
        "value": record["value"],
        "unit": record["unit"],
        "vs_baseline": record["vs_baseline"],
        "platform": record["platform"],
        "device_kind": record["device_kind"],
        "device_count": record["device_count"],
        "commit": commit,
        "records": records,
    }


if __name__ == "__main__":
    main()
