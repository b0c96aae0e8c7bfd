"""Sliding-window layers beside full-attention layers in one model: a paged
cache of two K/V pools under two tables, whose window layers hold a bounded
number of blocks a slot and give back the ones behind the window; the rotary
embedding on the window layers only; a sigmoid router with a correction bias,
a shared expert and the chip's share of the routed ones. Held against the
benchmark's plain reference (``benchmark/references/exaone_moe.py``: the full
forward pass over one sequence, the window a mask, no cache) at a small size
(window 8, block 4, chunks under and over the window), on the CPU, in float32,
with seeded weights.

Tolerances. Logits here have a standard deviation of ~1 (an untied head at
std 0.1 on a normed residual). The program and the reference add the same
float32 numbers in other orders (attention block by block over a gathered
view against one softmax over a row of the whole sequence, the experts' sum
over sorted pairs against a loop over experts): their logits agree to 1e-6
and are held to ``ATOL`` 2e-5. What a test shows to be DIFFERENT (a window
that sees everything, a rotated full layer, a block given back too early)
differs by 1e-2 or more. The kernel against ``ops/reference.py``: the same
float32 products folded tile by tile, 1e-5.
"""

import dataclasses
import importlib.util
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from tree_attention_tpu import obs
from tree_attention_tpu.models.decode import (
    PagedWindowCache,
    init_paged_cache,
)
from tree_attention_tpu.models.hybrid import layer_runs
from tree_attention_tpu.models.transformer import model_from_config
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.ops import tuning
from tree_attention_tpu.ops.pallas_decode import (
    WINDOW_KERNEL,
    attention_pallas_decode,
    decode_plan,
    paged_plan,
)
from tree_attention_tpu.ops.reference import attention_naive
from tree_attention_tpu.serving import SlotServer
from tree_attention_tpu.serving.block_pool import WindowBlocks
from tree_attention_tpu.serving.engine import Request

from tests.jitted import serve_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5
BLOCK, WINDOW = 4, 8
# The one chunk width the step helpers compile: two windows, so a step of 3,
# 4, 8 or 16 rows (under, at and over the window) is the same program.
WIDTH = 16

# The family's published keys at a small size: the period ``L L L G``, one
# leading dense FFN, the second of two shares of 4 of 8 experts, top 2.
SMALL = {
    "family": "exaone_moe", "model_type": "exaone_moe", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "sliding_window": WINDOW, "sliding_windows": [WINDOW] * 3 + [0],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "first_k_dense_replace": 1, "num_experts": 4, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "tie_word_embeddings": False, "vocab_size": 128,
    "torch_dtype": "float32",
    "deployment": {"experts_total": 8, "expert_share": 1},
    "block": {"qk_norm": True, "rotary_layers": "sliding_attention",
              "corrected_choice": True, "scale_renormed": True,
              "norm_placement": "pre"},
    "assumed": {"seeded_scales": {
        "embedding_std": 1.0, "head_std": 0.1, "attn_out_std": 0.05,
        "dense_down_std": 0.05, "expert_down_std": 0.05,
        "shared_down_std": 0.05, "qk_gain_mean": 1.5, "qk_gain_std": 0.1,
        "router_bias_std": 0.02}},
}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "benchmark", "references",
                              "exaone_moe.py"), "_references_exaone_moe")


@pytest.fixture(scope="module")
def adapter():
    return _load(os.path.join(ROOT, "benchmark", "adapters", "exaone_moe.py"),
                 "_adapters_exaone_moe")


@pytest.fixture(scope="module")
def model(ref, adapter):
    """(widths, reference weights, TransformerConfig, engine params)."""
    w = ref.Widths.of(SMALL)
    weights = ref.init_weights(7, w)
    tcfg = model_from_config(SMALL, max_seq_len=128)
    return w, weights, tcfg, adapter.engine_params(weights, w)


def _want(ref, w, weights, toks, rows=None, **kw):
    rows = np.arange(len(toks)) if rows is None else np.asarray(rows)
    return ref.logits_at(weights, w, np.asarray(toks), rows, pad_to=16, **kw)


def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "K-EXAONE-236B-A23B":
            return row["config"]
    pytest.skip("the catalog has no K-EXAONE-236B-A23B row")


# -- the model as data (f) ---------------------------------------------------


def test_the_catalogs_config_verbatim_builds_48_layers_of_the_right_kinds():
    c = _catalog_config()
    t = model_from_config(c, max_seq_len=256)
    assert t.n_layers == 48 and t.cache_kind == "window"
    assert t.layer_types == ("window", "window", "window", "attention") * 12
    assert (t.window, t.window_layers, t.cache_layers) == (128, 36, 12)
    assert (t.d_model, t.n_heads, t.n_kv_heads, t.d_head) == (6144, 64, 8, 128)
    assert (t.d_ff, t.vocab_size, t.tied_head) == (18432, 153600, False)
    assert (t.rope_theta, t.norm_eps) == (1e6, 1e-5)
    ex = t.moe
    assert (ex.n_experts, ex.held, ex.per_token, ex.width, ex.shared_width,
            ex.first_dense) == (128, 128, 8, 2048, 2048, 1)
    assert (ex.scoring, ex.renorm, ex.scale, ex.n_groups) == (
        "sigmoid", True, 2.5, 1)
    # What no published key says comes from the file's ``block`` group:
    # without it both attention kinds rotate and nothing is normed.
    assert t.rotary == ("attention", "window") and not t.qk_norm
    runs = layer_runs(t)
    assert len(runs) == 1 + 2 * 12 and runs[0][:3] == ("window", "dense", 1)


def test_the_small_files_keys_say_what_each_layer_is(model):
    _, _, t, params = model
    assert t.cache_kind == "window" and t.rotary == ("window",)
    assert t.rotates("window") and not t.rotates("attention")
    assert (t.window, t.window_layers, t.cache_layers, t.kv_pack) == (
        8, 3, 1, 1)
    assert (t.moe.held, t.moe.held_first, t.moe.n_experts) == (4, 4, 8)
    assert t.moe.corrected and t.moe.renorm_scaled and t.qk_norm
    # Five runs for two whole periods, as for the benchmark's cut.
    two = model_from_config(dict(
        SMALL, num_hidden_layers=8, layer_types=SMALL["layer_types"] * 2,
        sliding_windows=SMALL["sliding_windows"] * 2,
        mlp_layer_types=["dense"] + ["sparse"] * 7))
    assert [r[:3] for r in layer_runs(two)] == [
        ("window", "dense", 1), ("window", "expert", 2),
        ("attention", "expert", 1), ("window", "expert", 3),
        ("attention", "expert", 1)]
    assert params["wattn"]["wq"].shape[0] == 3
    assert params["attn"]["wq"].shape[0] == 1


@pytest.mark.parametrize("change, named", [
    ({"layer_types": ["sliding_attention", "linear_attention",
                      "sliding_attention", "full_attention"]},
     "linear_attention"),
    ({"n_group": 4}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"sliding_windows": [8, 8, 4, 0]}, "sliding_windows"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type"),
    ({"block": dict(SMALL["block"], norm_placement="post")},
     "norm_placement"),
    ({"block": dict(SMALL["block"], corrected_choice="noaux")},
     "corrected_choice"),
    ({"block": dict(SMALL["block"], rotary_layers="conv")}, "rotary_layers"),
    ({"num_expert_groups": 2}, "num_expert_groups"),
    ({"sliding_window": 0}, "window"),
])
def test_each_refused_key_is_refused_by_its_name(change, named):
    with pytest.raises(ValueError, match=named):
        model_from_config(dict(SMALL, **change))


def test_the_refusal_lists_the_kinds_from_the_one_table():
    from tree_attention_tpu.models.transformer import PUBLISHED_MIXERS

    with pytest.raises(ValueError) as e:
        model_from_config(dict(SMALL, layer_types=["mamba"] * 4))
    for name in PUBLISHED_MIXERS:
        assert name in str(e.value)


# -- the kernel (b) ----------------------------------------------------------


def _paged_case(rng, B, Hq, Hkv, D, NB, N, tq):
    q = jnp.asarray(rng.normal(size=(B, Hq, tq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, D)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(N)[:B * NB].reshape(B, NB), jnp.int32)
    return q, k, v, table


@pytest.mark.parametrize("tq", [1, 5, 64])
def test_the_window_kernel_against_the_reference_at_ragged_lengths(tq):
    """Interpret mode: rows of slots whose lengths lie under the window, at
    it and far past it, each row with its own lower edge; the plan built in
    the call and the plan handed in give the same bits."""
    rng = np.random.default_rng(tq)
    B, Hq, Hkv, D, NB, N = 4, 4, 2, 16, 32, 160
    q, k, v, table = _paged_case(rng, B, Hq, Hkv, D, NB, N, tq)
    cap = NB * BLOCK
    pos = jnp.asarray([0, 3, WINDOW + 1, cap - tq][:B], jnp.int32)
    out, lse = attention_pallas_decode(
        q, k, v, causal=True, q_offset=pos, block_table=table, window=WINDOW,
        interpret=True)
    plan = decode_plan(Hq, tq, k, table, pos, window=WINDOW)
    out2, _ = attention_pallas_decode(
        q, k, v, causal=True, q_offset=pos, block_table=table, window=WINDOW,
        interpret=True, step_plan=plan)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    kk = jnp.moveaxis(k[table], 1, 2).reshape(B, Hkv, cap, D)
    vv = jnp.moveaxis(v[table], 1, 2).reshape(B, Hkv, cap, D)
    for b in range(B):
        want, want_lse = attention_naive(
            q[b:b + 1], kk[b:b + 1], vv[b:b + 1], causal=True,
            q_offset=int(pos[b]), window=WINDOW)
        np.testing.assert_allclose(out[b], want[0], atol=1e-5)
        np.testing.assert_allclose(lse[b], want_lse[0], atol=1e-5)
    # A window that is the whole table is the full layer's call.
    full, _ = attention_pallas_decode(
        q, k, v, causal=True, q_offset=pos, block_table=table,
        interpret=True)
    wide, _ = attention_pallas_decode(
        q, k, v, causal=True, q_offset=pos, block_table=table, window=cap,
        interpret=True)
    np.testing.assert_allclose(wide, full, atol=1e-6)


@pytest.mark.parametrize("tq, entries", [(1, 1), (1, 2), (5, 2), (16, 4)])
def test_the_devices_list_is_the_hosts_count_with_a_first_live_step(
        tq, entries):
    """``paged_plan`` with a window: each slot's entries are the steps from
    the one that holds ``max(0, q_offset - window + 1)`` to the one that
    holds its last row, what ``tuning.paged_live_steps`` counts on the host
    (numpy) from the same offsets; one or two steps whatever the length."""
    NB, B = 32, 6
    rng = np.random.default_rng(entries)
    table = jnp.asarray(rng.permutation(B * NB).reshape(B, NB), jnp.int32)
    cap, step = NB * BLOCK, entries * BLOCK
    pos = np.asarray([0, 2, WINDOW - 1, WINDOW + 2, 57, cap - tq], np.int32)
    plan = paged_plan(jnp.asarray(pos), 0, table, tq=tq, entries=entries,
                      block=BLOCK, window=WINDOW)
    n_steps = NB // entries
    low = np.maximum(pos - (WINDOW - 1), 0)
    first = tuning.paged_first_step(low, 0, step, n_steps)
    live = tuning.paged_live_steps(pos, 0, tq, step, n_steps, low)
    count = int(plan.count)
    assert count == int(np.maximum(live, 1).sum())
    slot, stp = np.asarray(plan.slot)[:count], np.asarray(plan.step)[:count]
    want = [(b, s) for b in range(B)
            for s in range(first[b], first[b] + max(live[b], 1))]
    assert list(zip(slot.tolist(), stp.tolist())) == want
    assert live.max() <= (WINDOW - 1 + tq - 1) // step + 2
    # The table in list order names each entry's blocks.
    tbl = np.asarray(plan.table).reshape(-1, entries)[:count]
    host = np.asarray(table)
    for e, (b, s) in enumerate(want):
        assert tbl[e].tolist() == host[b, s * entries:(s + 1) * entries] \
            .tolist()
    # Without a window the list starts at step 0, as it did.
    old = paged_plan(jnp.asarray(pos), 0, table, tq=tq, entries=entries,
                     block=BLOCK)
    assert int(old.count) == int(np.maximum(tuning.paged_live_steps(
        pos, 0, tq, step, n_steps), 1).sum())
    assert WINDOW_KERNEL == "window_decode_paged" \
        and "flash_decode_paged" not in WINDOW_KERNEL


# -- the two pools against the reference (a) ---------------------------------


def _serve_rows(params, tcfg, toks, steps, slots=2, nb=16, chunk=16,
                packed=False):
    """Run ``steps`` (rows a slot a step) through the two pools with the
    window table kept by the engine's own ledger (blocks behind the window
    given back before each step, scrambled ids): the logits of the rows
    that carried a token, the ledger, and the most blocks a slot held.
    ``chunk`` is the most rows a step carries, which is what the ledger's
    bound counts; the token block is ``WIDTH`` wide whatever the chunk (or
    one row wide), two compiled programs a kind (``tests/jitted.py``), with
    the true counts in ``n_tokens`` / ``chunk_n`` as a tick carries them:
    the rows past a slot's count are written nowhere, so the ledger maps
    blocks for the rows that are real."""
    cache = init_paged_cache(tcfg, slots, nb * BLOCK, slots * nb, block=BLOCK,
                             window_blocks=64)
    assert isinstance(cache, PagedWindowCache)
    win = WindowBlocks(slots=slots, table_width=nb, block=BLOCK,
                       window=WINDOW, chunk=chunk)
    for i in range(slots):
        assert win.reserve()
        win.admit(i)
    table = jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb)[:, ::-1]
    cache = dataclasses.replace(cache, table=table)
    got, pos, peak = [[] for _ in range(slots)], [0] * slots, 0
    for ns in steps:
        for i, n in enumerate(ns):
            if n:
                win.advance(i, pos[i], pos[i] + n)
        peak = max(peak, max(win.held(i) for i in range(slots)))
        cache = dataclasses.replace(cache, wtable=jnp.asarray(win.table))
        rows, cache = serve_step(params, tcfg, cache, toks, pos, ns, WIDTH,
                                 packed=packed)
        for i, row, lg in rows:
            got[i].append((row, lg))
        for i, n in enumerate(ns):
            pos[i] += n
    return got, win, peak


@pytest.mark.parametrize("chunk", [3, 4, 8, 16])
def test_prefill_then_decode_through_both_pools_equals_the_reference(
        ref, model, chunk):
    """Chunks under the window (3, 4), at it (8) and over it (16), then
    decode, over contexts of more than three windows (40 and 31 tokens);
    the window table holds only what the ledger left mapped."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(chunk)
    toks = [rng.integers(0, 128, (40,)), rng.integers(0, 128, (31,))]
    steps = []
    for lo in range(0, 24, chunk):
        steps.append([min(chunk, 24 - lo), min(chunk, max(19 - lo, 0))])
    steps += [[1, 1]] * 12 + [[1, 0]] * 4
    got, win, peak = _serve_rows(params, tcfg, toks, steps, chunk=chunk)
    for i in range(2):
        want = _want(ref, w, weights, toks[i])
        assert len(got[i]) == len(toks[i])
        for row, lg in got[i]:
            np.testing.assert_allclose(lg, want[row], atol=ATOL)
    assert peak <= -(-(WINDOW + chunk) // BLOCK) + 1 == win.bound
    assert win.held(0) <= -(-WINDOW // BLOCK) + 1       # in decode
    assert win.freed >= (40 - WINDOW) // BLOCK - 1


def test_a_packed_tick_serves_a_chunk_beside_decode_rows(ref, model):
    w, weights, tcfg, params = model
    rng = np.random.default_rng(3)
    toks = [rng.integers(0, 128, (30,)), rng.integers(0, 128, (30,))]
    got, _, _ = _serve_rows(
        params, tcfg, toks,
        [[12, 0], [1, 10], [1, 9], [6, 1], [1, 1], [1, 1]], packed=True)
    for i in range(2):
        want = _want(ref, w, weights, toks[i])
        for row, lg in got[i]:
            np.testing.assert_allclose(lg, want[row], atol=ATOL)


@pytest.mark.parametrize("fault", ["no_window", "rotate_all"])
def test_the_two_faults_the_limits_are_held_against_show(ref, model, fault):
    """A window layer whose rows see everything, and a full layer that is
    rotated, each move the logits far beyond the tolerance once the context
    passes the window: what the cell's limits must fail (the reference's
    controls of those names)."""
    w, weights, _, _ = model
    toks = np.random.default_rng(5).integers(0, 128, (40,))
    sound = _want(ref, w, weights, toks)
    faulty = _want(ref, w, weights, toks, quant=fault)
    assert np.abs(sound - faulty)[WINDOW + 4:].max() > 1e-2
    if fault == "no_window":    # under the window the two are one model
        np.testing.assert_allclose(faulty[:WINDOW], sound[:WINDOW], atol=1e-5)


# -- the share (e) -----------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref, model):
    """Four chips share a layer of 8 routed experts, 2 each: what the four
    shares' routed parts give, with the shared expert (which every chip
    computes alike) counted once, is the uncut reference's layer; and the
    program's expert layer gives its own share's part."""
    from tree_attention_tpu.models.experts import expert_layer

    uncut = dict(SMALL, num_experts=8,
                 deployment={"experts_total": 8, "expert_share": 0})
    wu = ref.Widths.of(uncut)
    whole = ref.init_weights(11, wu)["moe"]
    layer = {n: a[1] for n, a in whole.items()}
    h = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    routed, shared = ref.ffn_parts(h, layer, w=wu)
    want = np.asarray(routed + shared)
    total = np.zeros_like(want)
    for share in range(4):
        cfg = dict(SMALL, num_experts=2,
                   deployment={"experts_total": 8, "expert_share": share})
        ws = ref.Widths.of(cfg)
        mine = dict(layer, **{n: layer[n][2 * share:2 * share + 2]
                              for n in ("we1", "we3", "we2")})
        part, sh = ref.ffn_parts(h, mine, w=ws)
        total += np.asarray(part)
        t = model_from_config(cfg, max_seq_len=64)
        y, _ = expert_layer(mine, h[None], t.moe)
        np.testing.assert_allclose(y[0], np.asarray(part + sh), atol=ATOL)
    np.testing.assert_allclose(total + np.asarray(shared), want, atol=ATOL)
    assert np.abs(want).max() > 0.01 and np.abs(total).max() > 0.005


# -- the ledger (c) ----------------------------------------------------------


def test_a_slots_window_blocks_stay_bounded_while_it_grows_to_capacity():
    """Chunks, then one row at a time to the table's end: the slot never
    holds more than its bound, what it gave back is free again, and the
    allocator is whole after it retires."""
    nb, chunk = 64, 16
    win = WindowBlocks(slots=2, table_width=nb, block=BLOCK, window=WINDOW,
                       chunk=chunk)
    assert win.bound == 7 and win.blocks == 16 and win.hit_blocks == 2
    assert win.reserve()
    win.admit(0)
    pos, peak = 0, 0
    while pos < nb * BLOCK:
        n = min(chunk, 40 - pos) if pos < 40 else 1
        win.advance(0, pos, pos + n)
        pos += n
        peak = max(peak, win.held(0))
        assert win.held(0) + win.reserved(0) == win.bound
        assert win.alloc.used == win.held(0)
        mapped = np.flatnonzero(win.table[0])
        assert mapped.size <= win.held(0)       # block id 0 may be mapped
        assert (pos - 1) // BLOCK in win._held[0]
        lo = max(pos - 1 - (WINDOW - 1), 0) // BLOCK
        assert all(j in win._held[0] for j in range(lo, (pos - 1) // BLOCK + 1))
    assert peak <= win.bound and win.held(0) <= 3
    assert win.freed == nb - win.held(0)
    win.free_slot(0)
    assert win.alloc.used == 0 and win.alloc.reserved == 0
    assert not win.table.any()


def _engine(tcfg, params, **kw):
    args = dict(slots=3, cache_len=96, prefill_chunk=8, kv_block=BLOCK,
                prefix_block=BLOCK, prefix_cache=True)
    args.update(kw)
    return SlotServer(params, tcfg, **args)


def _greedy(ref, weights, w, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        row = ref.logits_at(weights, w, np.asarray(toks),
                            np.asarray([len(toks) - 1]), pad_to=32)
        toks.append(int(row[0].argmax()))
    return toks[len(prompt):]


def test_a_long_request_never_waits_on_window_blocks(ref, model):
    """A request that fills its slot's table beside two short ones, in an
    engine whose FULL pool is too small for all three at once: the window
    pool is never why a request waits, no slot ever holds more than its
    bound, the flight record says what was held and given back, and
    nothing is leaked."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(4)
    long = rng.integers(0, 128, (21,)).tolist()
    FLIGHT.clear()
    FLIGHT.arm(capacity=4096)
    obs.REGISTRY.enable()
    try:
        eng = _engine(tcfg, params, prefix_cache=False)
        rep = eng.serve([
            Request(uid=0, prompt=long, max_new_tokens=70),
            Request(uid=1, prompt=long[:9], max_new_tokens=12),
            Request(uid=2, prompt=long[3:17], max_new_tokens=9)])
        recs = [r for r in FLIGHT.snapshot()["records"]
                if "window_blocks_held" in r]
        text = obs.REGISTRY.to_prometheus()
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()
    by_uid = {r.uid: r for r in rep.results}
    assert by_uid[0].tokens == _greedy(ref, weights, w, long, 70)
    assert by_uid[1].tokens == _greedy(ref, weights, w, long[:9], 12)
    kv = rep.kv
    assert kv["window_blocks_bound"] == 5 and kv["window_pool_blocks"] == 18
    assert kv["window_blocks_peak_slot"] <= 5
    assert kv["window_blocks_freed"] >= (91 - WINDOW) // BLOCK - 2
    assert kv["window_pool_bytes"] == 18 * BLOCK * 3 * 2 * 2 * 16 * 4
    assert kv["pool_bytes"] == kv["pool_blocks"] * BLOCK * 2 * 2 * 16 * 4
    assert recs and max(r["window_blocks_held"] for r in recs) <= 3 * 5
    # In decode a slot holds at most 3 blocks whatever its length, while
    # the full layers' table holds one a block of its length.
    late = [r for r in recs if r["occupancy"] == 1 and not r["chunk_tokens"]]
    assert late and all(r["window_blocks_held"] <= 3 for r in late)
    assert max(r["window_blocks_full"] for r in late) >= 20
    assert sum(r["window_blocks_freed"] for r in recs) \
        == kv["window_blocks_freed"]
    assert all(r["kv_steps_run"] == r["kv_steps_run_full"]
               + r["kv_steps_run_window"] for r in recs
               if "kv_steps_run_full" in r)
    leak = eng.leak_report()
    assert leak["blocks_used"] == 0 == leak["window_blocks_used"]
    assert leak["blocks_reserved"] == 0 == leak["window_blocks_held"]
    assert 'serving_kv_window_blocks{state="held"}' in text
    assert "serving_kv_window_blocks_freed_total" in text
    assert 'cache="paged_window"' in text


# -- hits, forks, reuse (d) --------------------------------------------------


def test_a_hit_a_fork_and_a_reused_slot_give_a_cold_admissions_tokens(
        ref, model):
    """Through ``SlotServer.serve``: a cold request; after it retired, a
    request that shares its whole prompt (a hit at the deepest published
    boundary: the tree kept the window blocks of the prompt's last two
    full blocks), in a slot a LONGER request used before; one that shares
    only its first 12 tokens (no boundary there keeps window blocks: no
    hit, cold, exact); a family of two forked at a prompt's end inside a
    block, sharing window blocks by reference. Every token the reference's
    greedy choice; nothing leaked."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(9)
    base = rng.integers(0, 128, (22,)).tolist()
    p_hit = base + rng.integers(0, 128, (5,)).tolist()
    p_part = base[:12] + rng.integers(0, 128, (9,)).tolist()
    p_fork = rng.integers(0, 128, (18,)).tolist()
    eng = _engine(tcfg, params)
    r0 = eng.serve([Request(uid=0, prompt=base, max_new_tokens=30)])
    assert r0.results[0].tokens == _greedy(ref, weights, w, base, 30)
    # Of 5 published blocks the tree keeps the window blocks of those the
    # slot still held when its last chunk (rows 16-21) was written.
    assert eng._prefix.window_blocks_used == 3
    r1 = eng.serve([Request(uid=1, prompt=p_hit, max_new_tokens=8),
                    Request(uid=2, prompt=p_part, max_new_tokens=8),
                    Request(uid=3, prompt=p_fork, max_new_tokens=7, n=2)])
    by_uid = {}
    for r in r1.results:
        by_uid.setdefault(r.uid, []).append(r)
    assert by_uid[1][0].prefix_hit_tokens == 20
    assert by_uid[1][0].tokens == _greedy(ref, weights, w, p_hit, 8)
    assert by_uid[2][0].prefix_hit_tokens == 0
    assert by_uid[2][0].tokens == _greedy(ref, weights, w, p_part, 8)
    assert len(by_uid[3]) == 2 and r1.kv["forks"] == 1
    for r in by_uid[3]:
        assert r.tokens == _greedy(ref, weights, w, p_fork, 7)
    leak = eng.leak_report()
    assert leak["blocks_used"] == leak["blocks_cached"]
    assert leak["window_blocks_used"] == leak["window_blocks_cached"]
    assert leak["pins"] == 0 == leak["window_blocks_held"]
    assert leak["blocks_private"] == 0 == leak["blocks_shared"]


def test_a_hit_whose_window_blocks_were_evicted_falls_back_and_is_exact(
        ref, model):
    """The window allocator takes back the tree's unmapped window blocks
    when it runs dry; here they are evicted by hand. The path is still
    matched, but no boundary keeps its window state: the admission is
    cold, and exact."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(10)
    base = rng.integers(0, 128, (22,)).tolist()
    eng = _engine(tcfg, params)
    eng.serve([Request(uid=0, prompt=base, max_new_tokens=4)])
    assert eng._prefix.window_blocks_used == 3      # blocks 2, 3, 4 of 5
    assert eng._win.alloc.evictable() == 3
    # Least recently used first: block 2's, then block 3's, which the
    # boundary at 20 tokens needs (and the one at 16 needs block 2's).
    assert eng._prefix._evict_window_one() and eng._prefix._evict_window_one()
    p = base + [7, 8, 9]
    rep = eng.serve([Request(uid=1, prompt=p, max_new_tokens=6)])
    assert rep.results[0].prefix_hit_tokens == 0
    assert rep.results[0].tokens == _greedy(ref, weights, w, p, 6)
    leak = eng.leak_report()
    assert leak["window_blocks_used"] == leak["window_blocks_cached"]
    assert leak["pins"] == 0


@pytest.mark.parametrize("kw, named", [
    (dict(quantize=True), "int8 window rows"),
    (dict(kv_shard="seq"), "sequence-sharded"),
    (dict(host_blocks=4), "host tier"),
    (dict(speculate=True), "given back"),
])
def test_engine_refuses_what_the_window_pools_do_not_carry(model, kw, named):
    _, _, tcfg, params = model
    with pytest.raises(ValueError, match=named):
        _engine(tcfg, params, **kw)


def test_disaggregation_is_refused_by_the_cache_kinds_name(model):
    from tree_attention_tpu.serving.block_pool import BlockAllocator

    _, _, tcfg, params = model
    with pytest.raises(ValueError, match="window pool.*disaggregation"):
        _engine(tcfg, params, prefix_cache=False,
                block_pool=BlockAllocator(72))


@pytest.mark.parametrize("flags, named", [
    (["--kv-quant", "int8"], "window pool is not served with --kv-quant"),
    (["--speculate"], "--speculate"),
    (["--serve-disagg"], "--serve-disagg"),
])
def test_cli_refuses_by_the_cache_kinds_name(tmp_path, flags, named):
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    path = tmp_path / "model.json"
    path.write_text(json.dumps(SMALL))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "16", "--max-new-tokens", "4",
                      "--dtype", "float32", "--model-config", str(path)]
                     + flags)
    with pytest.raises(SystemExit, match=named):
        cli.build_serve_engine(cfg, None)


def test_model_config_serves_the_family_on_its_own_weights(tmp_path):
    """``--model-config`` with this family's keys: the program draws a
    stack a kind itself and serves through ``SlotServer`` with a prefix
    hit, like the other five."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    path = tmp_path / "model.json"
    path.write_text(json.dumps(SMALL))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "24", "--max-new-tokens", "4",
                      "--dtype", "float32", "--prefix-cache",
                      "--prefix-block", "4", "--prefill-chunk", "8",
                      "--model-config", str(path)])
    setup = cli.build_serve_engine(cfg, None)
    p = setup.params
    assert p["wattn"]["q_ln"].shape == (3, 16) and "wout" in p
    assert p["layers"]["router"].shape == (3, 64, 8)
    assert p["layers"]["we1"].shape[:2] == (3, 4)
    assert p["layers"]["ws1"].shape == (3, 64, 32)
    eng = setup.make_engine()
    assert eng.cache.wk.shape[:2] == (3, 2 * 6) and eng.cache.k.shape[0] == 1
    prompt = list(range(1, 22))
    eng.serve([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    rep = eng.serve([Request(uid=1, prompt=prompt + [5, 6],
                             max_new_tokens=4)])
    assert len(rep.results[0].tokens) == 4
    assert rep.results[0].prefix_hit_tokens == 20
    leak = eng.leak_report()
    assert leak["blocks_used"] == leak["blocks_cached"]
