"""Communication accounting (``bench/comm.py``): collective counts and
payload bytes parsed from compiled SPMD modules must match what the
programs analytically put on the wire — this is the measurement the
north-star ICI model (``tools/ici_model.py``, BASELINE.md) is priced from.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from tree_attention_tpu.bench.comm import (
    assert_loop_free,
    collective_stats,
    _shape_bytes,
)
from tree_attention_tpu.parallel import cpu_mesh


def _load_bench():
    """Load repo-root bench.py as a module (it is a script, not a package
    member); shared by every test that checks its record logic."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench.py",
    )
    spec = importlib.util.spec_from_file_location("bench_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_shape_bytes_parses_arrays_and_tuples():
    assert _shape_bytes("f32[1,16,1,128]") == 16 * 128 * 4
    assert _shape_bytes("bf16[8,128]") == 8 * 128 * 2
    assert _shape_bytes("(f32[8], f32[8,128])") == 8 * 4 + 8 * 128 * 4
    assert _shape_bytes("s8[4]") == 4
    assert _shape_bytes("token[]") == 0


def test_collective_stats_counts_psum_payload():
    mesh = cpu_mesh(4)

    def fn(x):
        return jax.shard_map(
            lambda x_l: lax.psum(x_l, "seq"),
            mesh=mesh, in_specs=P("seq"), out_specs=P(None),
        )(x)

    x = jnp.arange(64, dtype=jnp.float32)
    st = collective_stats(fn, x)
    assert st["collective_count"] >= 1
    assert not st["has_loop"]
    ar = st["ops"]["all-reduce"]
    # Per-participant payload: the 16-element local shard... all-reduce's
    # HLO output is the full reduced tensor each participant holds.
    assert ar["payload_bytes"] == 16 * 4
    assert_loop_free(st, "psum")  # must not raise


def test_collective_stats_flags_loops():
    mesh = cpu_mesh(4)

    def fn(x):
        def inner(x_l):
            def body(c, _):
                return lax.psum(c, "seq"), None

            return lax.scan(body, x_l, None, length=3)[0]

        return jax.shard_map(
            inner, mesh=mesh, in_specs=P("seq"), out_specs=P(None),
            check_vma=False,
        )(x)

    x = jnp.arange(64, dtype=jnp.float32)
    st = collective_stats(fn, x)
    assert st["has_loop"]
    with pytest.raises(AssertionError, match="while loop"):
        assert_loop_free(st, "scan-psum")


def test_decode_families_measured_payloads():
    """The three decode algorithms' wire shapes — the numbers BASELINE.md's
    model quotes: tree 2 context-independent all-reduces; ring 2(N−1)
    sequential permutes; ulysses a context-proportional all-to-all."""
    from tree_attention_tpu.parallel import ring_decode, tree_decode, ulysses_decode

    mesh = cpu_mesh(4)
    B, H, D, T = 1, 4, 32, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, 1, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, T, D), jnp.float32)

    def stats(alg):
        return collective_stats(
            lambda q_, k_, v_: alg(q_, k_, v_, mesh=mesh, causal=True)[0],
            q, k, v,
        )

    tree = stats(tree_decode)
    assert tree["ops"]["all-reduce"]["count"] == 2
    # pmax of (B,H,1) f32 + psum of num (B,H,1,D) f32 and den (B,H,1) f32.
    assert tree["payload_bytes_total"] == B * H * (D + 2) * 4

    ring = stats(ring_decode)
    n = 4
    assert ring["ops"]["collective-permute"]["count"] == 2 * (n - 1)
    # (out, lse) rotated n−1 times: per hop B·H·D f32 + B·H f32.
    assert ring["payload_bytes_total"] == (n - 1) * (B * H * (D + 1) * 4)

    uly = stats(ulysses_decode)
    # The KV reshard moves the whole buffer: per-device all-to-all output
    # is (B, H/n, T, D) per tensor — context-proportional.
    assert uly["ops"]["all-to-all"]["payload_bytes"] == (
        2 * B * (H // n) * T * D * 4
    )


def test_ici_model_table_is_monotone_and_crosses():
    """The priced model must show the claimed structure: parity at small N,
    ring degrading past the latency crossover, a >=2x point existing."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "ici_model.py",
    )
    spec = importlib.util.spec_from_file_location("ici_model", path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)

    t8 = m.step_times(8, 1 << 20)
    t256 = m.step_times(256, 1 << 20)
    assert t8["ring"] / t8["tree"] < 1.1          # HBM-bound: parity
    assert t256["ring"] / t256["tree"] >= 2.0     # latency-bound: tree wins
    # Ulysses is bandwidth-dominated (context-proportional) everywhere.
    assert t256["ulysses"] > 5 * t256["tree"]
    # GQA shrinks per-chip compute but not the merge payload, so the
    # crossover pulls in (BASELINE.md: N >~ 64 for a 4-KV-head cache).
    g64 = m.step_times(64, 1 << 20, kv_heads=4)
    assert g64["ring"] / g64["tree"] >= 2.0


def test_shape_bytes_async_start_takes_result_not_sum():
    # Async '-start' tuples alias the operand beside the result; the
    # payload is the RESULT half (positional), while sync fused tuples sum.
    assert _shape_bytes("(f32[8,128], f32[32,128])", is_start=True) == 32 * 128 * 4
    assert _shape_bytes("(f32[8,128], f32[32,128])") == (8 + 32) * 128 * 4
    assert _shape_bytes("(f32[16], f32[16], u32[], u32[])", is_start=True) == 64
    # reduce-scatter-start: the operand is the N×-larger tensor; max()
    # would pick it and overstate the transfer (ADVICE r4 item 1).
    assert _shape_bytes(
        "(f32[32,128], f32[8,128], u32[], u32[])", is_start=True
    ) == 8 * 128 * 4
    # Fused two-operand async form: first half operands, second results.
    assert _shape_bytes(
        "(f32[32,128], f32[32], f32[8,128], f32[8], u32[])", is_start=True
    ) == 8 * 128 * 4 + 8 * 4


def test_bench_summary_line_is_compact_and_parseable():
    """bench.py must end with a small self-sufficient JSON line (a
    bounded stdout tail can truncate the full record) that names the
    device the numbers were measured on."""
    import json as _json

    b = _load_bench()

    suite = {
        "decode_64k": {"pct_hbm_roofline": 88.1, "us_per_step": 711.0,
                       "kv_tokens_per_sec": 9.0e7},
        "train_fwd_bwd_16k": {"fwd": {"mfu_pct": 63.1},
                              "fwd_bwd": {"mfu_pct": 75.6}},
        "tree_vs_ring_cpu8": {"tree_speedup_vs_ring": 1.013,
                              "tree_zigzag_speedup_vs_ring": 1.248},
        "tree_vs_ring_decode_cpu8": {
            "ctx_64000": {"tree_speedup_vs_ring": 0.97},
            "ctx_2048": {"tree_speedup_vs_ring": 1.4},
        },
        "decode_gqa_1m": {"skipped": "tpu unreachable"},
        "train_fwd_bwd": {"error": "RuntimeError: boom"},
    }
    record = {"metric": "m", "value": 1.0, "unit": "tokens/sec",
              "vs_baseline": 2.0, "platform": "tpu",
              "device_kind": "TPU v5 lite", "device_count": 1,
              "suite": suite}
    line = _json.dumps(b._summary_line(record))
    assert len(line) < 2000  # survives any bounded tail
    parsed = _json.loads(line)
    assert (parsed["platform"], parsed["device_kind"],
            parsed["device_count"]) == ("tpu", "TPU v5 lite", 1)
    assert parsed["records"]["decode_64k"]["pct_roofline"] == 88.1
    assert parsed["records"]["train_fwd_bwd_16k"]["fwd_mfu_pct"] == 63.1
    assert parsed["records"]["tree_vs_ring_decode_cpu8"]["ctx_2048_vs_ring"] == 1.4
    assert parsed["records"]["decode_gqa_1m"] == "skipped"
    assert parsed["records"]["train_fwd_bwd"] == "error"
    assert {"metric", "value", "unit", "vs_baseline", "commit"} <= set(parsed)


def test_ici_measured_terms_rebuild_from_records():
    """VERDICT r4 item 4 / ADVICE item 3: the model's measured terms come
    from records (median, suspect-robust) and the payloads scale with
    QUERY heads, priced inside step_times."""
    from tree_attention_tpu.bench import ici

    # Median is robust to one noisy capture (the r4 58.1% outlier class).
    assert ici.measured_roofline_frac([58.1, 89.1, 91.7, 92.6]) == (
        (89.1 + 91.7) / 2 / 100
    )
    assert ici.measured_roofline_frac([]) == ici.DEFAULT_ROOFLINE_FRAC

    # Closed-form payloads at the reference shape match the compiled-HLO
    # measurement in the r4 comparator record (8320 / 8256 bytes).
    tree_p, ring_hop = ici.merge_payloads(16)
    assert tree_p == 8320 and ring_hop == 8256
    # Payloads scale with QUERY heads, not KV heads (ADVICE r4 item 3).
    tree_gqa, ring_gqa = ici.merge_payloads(32)
    assert tree_gqa == 2 * tree_p and ring_gqa == 2 * ring_hop

    rec = {
        "n_devices": 8,
        "tree": {"comm": {"payload_bytes_total": 8320}},
        "ring": {"comm": {"payload_bytes_total": 57792}},
    }
    p = ici.payloads_from_comm_record(rec)
    assert p == {"tree": 8320, "ring_hop": 8256}
    assert ici.payloads_from_comm_record({"n_devices": 8}) is None

    # A 32q/4kv GQA config priced at q_heads=32 must cross earlier than
    # MHA at the same context (bigger merge, smaller compute)...
    g = ici.step_times(64, 1 << 20, kv_heads=4, q_heads=32)
    assert g["ring"] / g["tree"] >= 2.0
    # ...and pricing it with the 16-head payload (the old bug) understates
    # the tree's own merge cost: the q_heads=32 tree step must be slower.
    g16 = ici.step_times(64, 1 << 20, kv_heads=4, q_heads=16)
    assert g["tree"] > g16["tree"]


def test_slope_record_fields_guards():
    """bench.py's shared decode-record tail: fast readings are suspect
    (fence failure), wide spreads get the min-cycle note, clean records
    get neither (VERDICT r4 item 1)."""
    b = _load_bench()
    from tree_attention_tpu.utils.profiling import SlopeStats, TimingStats

    ts = TimingStats(median=1, mean=1, minimum=1, maximum=1, iters=1,
                     times=(1,))

    def slope(per, spread, slopes):
        return SlopeStats(per_step=per, slopes=slopes, spread_pct=spread,
                          small=ts, large=ts)

    from tree_attention_tpu.bench.ici import peaks

    hbm = peaks("TPU v5 lite").hbm_bytes_per_s
    kv = 512 * 1024 * 1024  # 512 MB stream
    clean = kv / (0.9 * hbm)
    per, f = b._slope_record_fields(slope(clean, 1.2, (clean,)), kv, hbm)
    assert per == clean and "timing_suspect" not in f
    assert "timing_note" not in f and f["slope_spread_pct"] == 1.2

    fast = kv / (1.5 * hbm)  # 1.5x the spec: impossible
    _, f = b._slope_record_fields(slope(fast, 0.5, (fast,)), kv, hbm)
    assert "timing_suspect" in f

    _, f = b._slope_record_fields(
        slope(clean, 38.4, (clean, clean * 1.4)), kv, hbm
    )
    assert "timing_note" in f and "timing_suspect" not in f

    # Deflation fault: a min cycle far below the median cycle is an
    # early-resolved fence even when its implied bandwidth stays under the
    # spec ceiling.
    slow = kv / (0.5 * hbm)                  # contended window: 50% roofline
    deflated = 0.55 * slow                   # "faster" cycle, still sub-spec
    _, f = b._slope_record_fields(
        slope(deflated, 80.0, (deflated, slow, slow * 1.02)), kv, hbm
    )
    assert "timing_suspect" in f and "deflation" in f["timing_suspect"]
    assert f["pct_hbm_roofline"] < 105  # the ceiling guard alone misses it

    # The r5 q8q capture's shape ([359, 359, 497]): min == median, genuine
    # contention — stays a note, not a suspect flag.
    _, f = b._slope_record_fields(
        slope(clean, 38.4, (clean, clean, clean * 1.38)), kv, hbm
    )
    assert "timing_note" in f and "timing_suspect" not in f

    # With only two cycles, median == mean and the deflation test cannot
    # tell a deflated min from one contended sibling — it must stay quiet
    # (callers that want the defence run repeats >= 3).
    _, f = b._slope_record_fields(
        slope(slow, 150.0, (slow, slow * 2.5)), kv, hbm
    )
    assert "timing_suspect" not in f
