"""Kernel tile-size sweep on the attached TPU chip.

Prints one JSON line per measurement; the winners go into
``tree_attention_tpu/ops/tuning.py``. Run from the repo root:

    python tools/tune_sweep.py decode   # flash-decode kernel block_k sweep
    python tools/tune_sweep.py fwd      # training fwd kernel (bq, bk) sweep
    python tools/tune_sweep.py bwd      # fwd+bwd through the custom VJP
    python tools/tune_sweep.py --grouped  # the grouped expert product's plans
    python tools/tune_sweep.py --scan     # a chunk group's scan: kernel and XLA

Uses the slope-timing protocol (utils.profiling.slope_per_step, min-stat
over repeated cycles), so fixed per-call costs cancel and each cell
carries its own spread.
"""

import dataclasses
import functools
import json
import os
import sys
import time

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

# The grouped expert product (ops/pallas_moe.py) at the shapes the benchmark's
# five expert configurations serve: (name, hidden or latent, width, gated,
# experts held, share of a tick's pairs that land on a held expert, pairs a
# decode tick, pairs a mixed tick). A pair that lands here lands on any held
# expert alike, which gives the touched shares the cells read (ledger, PR 41:
# 99.9 / 84 / 46 / 39 / 88%).
GROUPED_SHAPES = (
    ("lfm2-8b-a1b", 2048, 1792, True, 32, 1.0, 256, 1280),
    ("k-exaone-236b-a23b", 6144, 2048, True, 8, 1 / 16, 256, 2304),
    ("deepseek-v2", 5120, 1536, True, 40, 1 / 4, 96, 1632),
    ("longcat-flash-omni", 6144, 2048, True, 16, 1 / 48, 384, 3456),
    ("nemotron-3-super-120b-a12b", 1024, 2688, False, 128, 1 / 4, 1408, 7040),
)


def grouped_candidates(m, k, n, n_rhs):
    """Today's plan first, then every plan of (whole k, the largest divisors
    of k up to 2048 and 1024) x (whole n, the largest divisors up to n / 2,
    1024, 512, 256) whose step moves 1-16 MB of weights; the rows' tile at
    whole k wherever k is tiled; both row tiles from 2,048 rows on."""
    from tree_attention_tpu.ops.tuning import (
        GroupedPlan, _divisor_tile, grouped_plan_unmeasured, row_tile)

    today = grouped_plan_unmeasured(m, k, n, n_rhs)
    plans = [today]
    if today.tk < k:
        plans.append(today._replace(rows_whole=True))
    tks = sorted({k, _divisor_tile(k, 2048), _divisor_tile(k, 1024)})
    tns = sorted({n} | {_divisor_tile(n, c)
                        for c in (n // 2, 1024, 512, 256) if c >= 128})
    tms = (row_tile(m),) if m < 2048 else (256, 128)
    for tm in tms:
        for tk in tks:
            for tn in tns:
                step = n_rhs * tk * tn * 2
                plan = GroupedPlan(tm, tk, tn, rows_whole=tk < k)
                if (1 << 20) <= step <= (16 << 20) and plan not in plans:
                    plans.append(plan)
    return plans


def _grouped_layout(rng, pairs, here, held, m):
    """Rows a held expert of one call: ``pairs`` pairs, each here with
    probability ``here`` and then on any held expert alike."""
    import numpy as np

    mine = rng.random(pairs) < here
    sizes = np.bincount(rng.integers(0, held, pairs)[mine], minlength=held)
    assert sizes.sum() <= m
    return sizes.astype(np.int32)


def _kernel_events(trace_dir, kernel):
    """Device durations (s) of the events named ``kernel`` in the newest
    xplane under ``trace_dir``, in the order they ran."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)[-1]
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    found = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                found += [(e.start_ns, e.duration_ns * 1e-9)
                          for e in line.events
                          if e.name.lstrip("%").startswith(kernel)]
    return [d for _, d in sorted(found)]


def sweep_grouped(only=None, calls=None):
    """One JSON line a (configuration, rows, product, plan). A program is
    ``calls`` expert layers in a loop, as a tick makes them: the pair rows
    gathered from the token rows, the product in, the product out on its
    result, over a stack of several layers' experts (a call's layer ``i %
    layers``). The product under test takes the candidate plan and the other
    one today's. ``kernel_us``: the mean device time of that product's
    events in one traced run of the program (what the benchmark's
    ``moe_ffn_ms_tick`` sums), ``pct_of_hbm`` the share of the memory's
    pace the cost function's bytes reach over it (the touched experts'
    matrices once, the pairs' rows in and out); ``us``: the whole layer by
    the host's clock, best of 5. Also written to
    ``chiprun_out/grouped_sweep.jsonl``."""
    import shutil

    import numpy as np

    from tree_attention_tpu.ops.pallas_moe import (
        GATED_KERNEL, UNGATED_KERNEL, grouped_matmul)
    from tree_attention_tpu.ops.tuning import (
        grouped_plan_unmeasured, row_tile)

    hbm_bw = peaks().hbm_bytes_per_s
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/grouped_sweep.jsonl", "a")
    trace_dir = "chiprun_out/.grouped_sweep_trace"
    quiet = jax.profiler.ProfileOptions()
    quiet.python_tracer_level = 0
    quiet.host_tracer_level = 0

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    for name, hidden, width, gated, held, here, dec, mix in GROUPED_SHAPES:
        if only and only not in name:
            continue
        kernel = GATED_KERNEL if gated else UNGATED_KERNEL
        n_in = 2 if gated else 1
        layers = max(2, min(8, int(3e9 // (held * hidden * width * 2
                                          * (n_in + 1)))))
        keys = jax.random.split(jax.random.PRNGKey(0), n_in + 2)
        w_in = tuple(
            jax.random.normal(kk, (layers * held, hidden, width),
                              jnp.bfloat16) * 0.02 for kk in keys[:n_in])
        w_out = jax.random.normal(
            keys[n_in], (layers * held, width, hidden), jnp.bfloat16) * 0.02
        for pairs in (dec, mix):
            m = -(-pairs // row_tile(pairs)) * row_tile(pairs)
            sizes = _grouped_layout(
                np.random.default_rng(pairs), pairs, here, held, m)
            touched, rows = int((sizes > 0).sum()), int(sizes.sum())
            gs = jnp.asarray(sizes)
            tokens = jax.random.normal(
                keys[-1], (m // 4, hidden), jnp.bfloat16)
            n_calls = calls or (32 if pairs == dec else 8)
            shapes = {"in": (hidden, width, n_in), "out": (width, hidden, 1)}
            today = {p: grouped_plan_unmeasured(m, *shapes[p])
                     for p in shapes}
            tests = [(p, plan) for p in ("in", "out")
                     for plan in grouped_candidates(m, *shapes[p])]

            def build(product, plan):
                plans = dict(today, **{product: plan})

                def layer(i, x, w_in, w_out, gs):
                    first = (i % layers) * held
                    pair_rows = (jnp.arange(m) // 4 + i) % (m // 4)
                    h = grouped_matmul(
                        x[pair_rows], w_in, gs, first_group=first,
                        relu2=not gated, name=kernel, plan=plans["in"])
                    return grouped_matmul(
                        h, (w_out,), gs, first_group=first, name=kernel,
                        plan=plans["out"])

                @jax.jit
                def program(x, w_in, w_out, gs):
                    def body(i, acc):
                        out = layer(i, x, w_in, w_out, gs)
                        return acc + out[0, :128].astype(jnp.float32)

                    acc = lax.fori_loop(0, n_calls - 1, body,
                                        jnp.zeros((128,), jnp.float32))
                    return acc, layer(n_calls - 1, x, w_in, w_out, gs)[:rows]

                return program

            args = (tokens, w_in, w_out, gs)
            ran, first = [], None
            for product, plan in tests:
                k, n, n_rhs = shapes[product]
                rec = {"kernel": kernel, "config": name, "product": product,
                       "k": k, "n": n, "n_rhs": n_rhs, "m": m, "held": held,
                       "touched": touched, "rows_here": rows,
                       "plan": plan.label,
                       "vmem_limit": plan.vmem_limit_bytes(k, n_rhs, 2)}
                try:
                    program = build(product, plan)
                    got = program(*args)[1].astype(jnp.float32)
                    if first is None:
                        first = got
                    rec["max_abs_diff_from_first"] = float(
                        jnp.max(jnp.abs(got - first))) if rows else 0.0
                    best = float("inf")
                    for _ in range(5):
                        t0 = time.perf_counter()
                        jax.block_until_ready(program(*args))
                        best = min(best, time.perf_counter() - t0)
                    rec["us"] = round(best / n_calls * 1e6, 1)
                    ran.append((rec, program))
                except Exception as e:
                    rec["error"] = f"{type(e).__name__}: {e}"[:300]
                    emit(rec)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir, profiler_options=quiet)
            for _, program in ran:
                jax.block_until_ready(program(*args))
            jax.profiler.stop_trace()
            events = _kernel_events(trace_dir, kernel)
            if len(events) != 2 * n_calls * len(ran):
                for rec, _ in ran:  # the host's clock alone
                    emit(dict(rec, error=f"{len(events)} kernel events "
                              f"for {len(ran)} programs"))
                continue
            for j, (rec, _) in enumerate(ran):
                mine = events[2 * n_calls * j:2 * n_calls * (j + 1)]
                k, n, n_rhs = shapes[rec["product"]]
                for product, part in (("in", mine[0::2]), ("out", mine[1::2])):
                    rec[f"{product}_kernel_us"] = round(
                        sum(part) / len(part) * 1e6, 1)
                rec["kernel_us"] = rec[rec["product"] + "_kernel_us"]
                nbytes = (touched * n_rhs * k * n + rows * (k + n)) * 2
                rec["pct_of_hbm"] = round(
                    nbytes / (rec["kernel_us"] * 1e-6) / hbm_bw * 100, 1)
                emit(rec)
        del w_in, w_out
    shutil.rmtree(trace_dir, ignore_errors=True)
    log.close()


# The state-space cells' (name, slots, layers, heads, d_head, groups, d_state):
# the two shapes ``ssm_chunk_scan`` serves.
SCAN_SHAPES = (
    ("nemotron-3-super-120b-a12b", 64, 5, 128, 64, 8, 128),
    ("falcon-h1-34b-instruct", 48, 9, 32, 128, 2, 256),
)


def sweep_scan(only=None, calls=16):
    """One JSON line a (configuration, chunk length, path): a chunk group
    of ONE member through ``calls`` layers in a loop, as a mixed tick makes
    them. ``xla`` is the branch ``ssm_branch`` takes off the TPU (the
    member's state gathered and unpacked, ``ssm_scan``, packed and scattered
    back); the others are ``ssm_chunk_scan`` at a block length and rows of
    heads a grid step (``rule``: ``ops/tuning.py``'s choice). ``us``: a
    layer by the host's clock, best of 6, the launch's own XLA operations
    (the per-row vectors) and the loop's (``dt`` moved a layer, ``y``
    masked and summed: not a cell's) included; ``kernel_us``: the mean
    device time of the kernel's events in one traced run, the number
    ``ops/tuning.py``'s table holds; ``max_diff`` from the XLA path's state
    and ``y``. Also written to ``chiprun_out/scan_sweep.jsonl``."""
    import shutil

    import numpy as np

    from tree_attention_tpu.models.hybrid import (
        pack_state, ssm_scan, unpack_state)
    from tree_attention_tpu.models.transformer import StateSpace
    from tree_attention_tpu.ops import tuning
    from tree_attention_tpu.ops.pallas_ssm import SCAN_KERNEL, _ssm_scan_call

    interpret = jax.default_backend() != "tpu"   # (a rehearsal off the chip)
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/scan_sweep.jsonl", "a")
    trace_dir = "chiprun_out/.scan_sweep_trace"
    quiet = jax.profiler.ProfileOptions()
    quiet.python_tracer_level = 0
    quiet.host_tracer_level = 0

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    for name, slots, layers, H, P, G, N in SCAN_SHAPES:
        if only and only not in name:
            continue
        sm = StateSpace(n_heads=H, d_head=P, n_groups=G, d_state=N, taps=4)
        per = H // sm.pack // G
        rng = np.random.default_rng(0)
        pool = jnp.asarray(rng.normal(
            size=(layers * slots,) + sm.state_shape), jnp.float32)
        A = jnp.asarray(-rng.uniform(1, 16, (H,)), jnp.float32)
        for T in (256, 128, 64):
            x = jnp.asarray(rng.normal(size=(1, T, H, P)), jnp.float32)
            B, C = (jnp.asarray(rng.normal(size=(1, T, G, N)), jnp.float32)
                    for _ in range(2))
            dt = jnp.asarray(np.exp(rng.uniform(
                np.log(1e-3), np.log(0.1), (1, T, H))), jnp.float32)
            slot = jnp.asarray([slots // 3], jnp.int32)
            n_valid = jnp.asarray([T - 5], jnp.int32)
            dt = jnp.where(jnp.arange(T)[None, :, None] < n_valid[0], dt, 0.0)

            def xla(pool, dt, m):
                at = m * slots + slot
                s0 = unpack_state(pool[at], sm)
                y, s1 = ssm_scan(x, dt, A, B, C, s0, sm.chunk)
                return pool.at[at].set(pack_state(s1, sm)), y

            wide = [t.reshape(1, T, -1) for t in (x, B, C)]

            def kernel(block, rows):
                def step(pool, dt, m):
                    pool, y = _ssm_scan_call(
                        pool, wide[0], dt, A, *wide[1:], m * slots + slot,
                        n_valid, jnp.zeros((1,), jnp.int32),
                        interpret=interpret, block=block, rows=rows)
                    return pool, y.reshape(x.shape)
                return step

            paths = [("xla", xla), ("rule", kernel(None, None))] + [
                (f"b{block}_r{rows}", kernel(block, rows))
                for block in (64, 128, 256) if block <= T
                for rows in (1, 2, 4) if per % rows == 0]

            def build(step):
                @functools.partial(jax.jit, donate_argnums=(0,))
                def program(pool):
                    def body(i, carry):
                        pool, acc = carry
                        # dt moves a layer: nothing of the scan is lifted
                        # out of the loop.
                        pool, y = step(pool, dt * (1 + 1e-3 * i), i % layers)
                        return pool, acc + y[0, :8, 0, :8]
                    return lax.fori_loop(
                        0, calls, body,
                        (pool, jnp.zeros((8, min(P, 8)), jnp.float32)))
                return program

            # Every path from the same pool first (the timed programs move
            # it), then the clocks.
            checked, first = [], None
            for label, step in paths:
                rec = {"kernel": SCAN_KERNEL, "config": name, "tq": T,
                       "path": label, "calls": calls}
                if label == "rule":
                    rec["block"] = tuning.ssm_scan_block(T)
                    rec["rows"] = tuning.ssm_scan_rows(
                        T, per, N, sm.pack * P)
                try:
                    new, y = jax.jit(step)(pool, dt, 1)
                    got = (np.asarray(new[slots:2 * slots]), np.asarray(y))
                    first = got if first is None else first
                    rec["max_diff"] = [float(np.abs(g - f).max())
                                       for g, f in zip(got, first)]
                    del new, y, got
                    checked.append((rec, build(step)))
                except Exception as e:
                    rec["error"] = f"{type(e).__name__}: {e}"[:300]
                    emit(rec)
            ran = []
            for rec, program in checked:
                best = float("inf")
                for _ in range(6):
                    t0 = time.perf_counter()
                    pool, acc = program(pool)
                    jax.block_until_ready(acc)
                    best = min(best, time.perf_counter() - t0)
                rec["us"] = round(best / calls * 1e6, 1)
                ran.append((rec, program))
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir, profiler_options=quiet)
            for _, program in ran:
                pool, acc = program(pool)
                jax.block_until_ready(acc)
            jax.profiler.stop_trace()
            events = _kernel_events(trace_dir, SCAN_KERNEL)
            mine = [r for r, _ in ran if r["path"] != "xla"]
            for j, rec in enumerate(mine):
                if len(events) == calls * len(mine):
                    part = events[calls * j:calls * (j + 1)]
                    rec["kernel_us"] = round(sum(part) / calls * 1e6, 1)
            for rec, _ in ran:
                emit(rec)
    shutil.rmtree(trace_dir, ignore_errors=True)
    log.close()


if __name__ == "__main__":
    from tree_attention_tpu import obs

    # Env-armed like bench.py (TA_METRICS_OUT / TA_TRACE_EVENTS): without
    # this the guard verdicts filed above would hit a disabled registry.
    obs.configure()
    mode = sys.argv[1].lstrip("-") if len(sys.argv) > 1 else "decode"
    try:
        {"decode": sweep_decode, "fwd": sweep_fwd,
         "bwd": lambda: sweep_fwd(bwd=True),
         "grouped": lambda: sweep_grouped(*sys.argv[2:3]),
         "scan": lambda: sweep_scan(*sys.argv[2:3])}[mode]()
    finally:
        obs.shutdown()
