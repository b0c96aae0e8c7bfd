"""EVA attention in every layer of one model: exact rows inside the row's own
aligned window, one learned summary row for every chunk of every window closed
before it, one softmax over both; a paged cache of two K/V pools of every layer
under two tables (the exact rows a bounded number of blocks a slot, given back
a whole window at once; the summary rows one a chunk, kept), the softmax joined
from two partials by ``ops/reference.py`` ``merge_partials``. Held against the
benchmark's plain reference (``benchmark/references/evabyte.py``: the full
forward pass over one sequence, ONE dense softmax over ``[exact | summaries]``,
no cache) at a small size (window 32, chunk 4, block 8: windows close within
tens of tokens), on the CPU, in float32, with seeded weights.

Tolerances. Logits here have a standard deviation of ~0.5. The program and the
reference add the same float32 numbers in other orders (two partials and a
merge against one softmax over a row of the whole sequence): their logits agree
to 1e-6 and are held to ``ATOL`` 2e-5. What a test shows to be DIFFERENT (the
summaries left out, a window that slides, a stale summary row) differs by 1e-2
or more. The kernels against ``ops/reference.py``: the same float32 products
folded tile by tile, 1e-5.
"""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_attention_tpu import obs
from tree_attention_tpu.models.decode import (
    PagedWindowCache,
    chunks_closed,
    init_paged_cache,
    window_rules,
)
from tree_attention_tpu.models.transformer import (
    TransformerConfig,
    model_from_config,
)
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.ops import tuning
from tree_attention_tpu.ops.block_utils import AlignedWindow, ChunkSummaries
from tree_attention_tpu.ops.pallas_decode import (
    EVA_LOCAL_KERNEL,
    EVA_SUMMARY_KERNEL,
    attention_pallas_decode,
    decode_plan,
    paged_chunk_read,
    paged_plan,
)
from tree_attention_tpu.ops.reference import attention_naive, merge_partials
from tree_attention_tpu.serving import SlotServer
from tree_attention_tpu.serving.block_pool import WindowBlocks
from tree_attention_tpu.serving.engine import Request

from tests.jitted import serve_step_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5
BLOCK, WINDOW, CHUNK = 8, 32, 4
# The one chunk width the step helpers compile: three blocks, the widest
# chunk (the one that straddles a window boundary); a step of 3, 4 or 8 rows
# is the same program with the count in the length vector.
WIDTH = 24
LOCAL, FAR = AlignedWindow(WINDOW), ChunkSummaries(WINDOW, CHUNK)

# The family's published keys at a small size.
SMALL = {
    "family": "evabyte", "model_type": "evabyte", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "attention_class": "eva", "window_size": WINDOW, "chunk_size": CHUNK,
    "num_pred_heads": 8, "norm_add_unit_offset": True, "fp32_skip_add": True,
    "fp32_logits": True, "rms_norm_eps": 1e-5, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 320, "hidden_act": "silu",
    "torch_dtype": "float32",
    "block": {"summary_key": "weighted_plus_mu",
              "summary_logits_scaled": False, "summary_after_rotary": True,
              "window_rule": "aligned"},
    "assumed": {"torch_dtype": "float32", "seeded_scales": {
        "embedding_std": 1.0, "head_std": 0.05, "attn_out_std": 0.05,
        "dense_down_std": 0.05, "norm_gain_std": 0.1, "phi_std": 0.5,
        "mu_std": 0.5}},
}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "benchmark", "references", "evabyte.py"),
                 "_references_evabyte")


@pytest.fixture(scope="module")
def adapter():
    return _load(os.path.join(ROOT, "benchmark", "adapters", "evabyte.py"),
                 "_adapters_evabyte")


@pytest.fixture(scope="module")
def model(ref, adapter):
    """(widths, reference weights, TransformerConfig, engine params)."""
    w = ref.Widths.of(SMALL)
    weights = ref.init_weights(7, w)
    tcfg = model_from_config(SMALL, max_seq_len=256)
    return w, weights, tcfg, adapter.engine_params(weights, w)


def _want(ref, w, weights, toks, rows=None, **kw):
    rows = np.arange(len(toks)) if rows is None else np.asarray(rows)
    return ref.logits_at(weights, w, np.asarray(toks), rows, pad_to=32, **kw)


# -- the model as data -------------------------------------------------------


def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    return next(r for r in map(json.loads, open(path))
                if r["name"] == "EvaByte")["config"]


def test_the_catalogs_config_verbatim_builds_32_eva_layers():
    t = model_from_config(_catalog_config())
    assert t.layer_types == ("eva",) * 32 and t.eva_layers == 32
    assert (t.window, t.chunk, t.window_rule) == (2048, 16, "aligned")
    assert (t.n_heads, t.n_kv_heads, t.d_head, t.d_model, t.d_ff) == (
        32, 32, 128, 4096, 11008)
    assert (t.vocab_size, t.pred_heads, t.norm_offset) == (320, 8, True)
    assert t.cache_kind == "eva" and not t.dense_block
    assert t.cache_layers == 32 and t.window_layers == 0
    assert t.rope_theta == 1e5 and t.norm_eps == 1e-5
    assert window_rules(t) == (ChunkSummaries(2048, 16), AlignedWindow(2048))


def test_the_small_files_keys_say_what_each_layer_is(model):
    _, _, tcfg, params = model
    assert tcfg.layer_types == ("eva", "eva") and tcfg.ffn_kinds == (
        "dense", "dense")
    assert params["eva"]["phi"].shape == (2, 4, 16) == params["eva"][
        "mu"].shape
    # The head holds every next-position block; a norm's leaf holds the gain.
    assert params["wout"].shape == (64, 8 * 320)
    assert abs(float(params["ln_f"].mean()) - 1.0) < 0.1
    # Every default stays what the older families have.
    d = TransformerConfig()
    assert (d.window_rule, d.chunk, d.pred_heads, d.norm_offset) == (
        "sliding", 0, 1, False)
    assert window_rules(d) == (None, None)


@pytest.mark.parametrize("change, named", [
    ({"block": {"summary_key": "mean_plus_mu"}}, "summary_key"),
    ({"block": {"summary_logits_scaled": True}}, "summary_logits_scaled"),
    ({"block": {"summary_after_rotary": False}}, "summary_after_rotary"),
    ({"block": {"window_rule": "sliding"}}, "window_rule"),
    ({"attention_class": "performer"}, "attention_class 'performer'"),
    ({"fp32_skip_add": False}, "fp32_skip_add"),
    ({"fp32_logits": False}, "fp32_logits"),
    ({"layer_types": ["full_attention"] * 2}, "beside layer_types"),
    ({"chunk_size": 5}, "a whole number of chunks"),
])
def test_each_refused_key_is_refused_by_its_name(change, named):
    c = dict(SMALL, **{k: v for k, v in change.items() if k != "block"})
    if "block" in change:
        c["block"] = dict(SMALL["block"], **change["block"])
    with pytest.raises(ValueError, match=named):
        model_from_config(c)


@pytest.mark.parametrize("kw, named", [
    (dict(layer_types=("eva", "window")), "beside layers of kinds"),
    (dict(layer_types=("eva", "conv")), "beside layers of kinds"),
    (dict(window_rule="sliding"), "window_rule 'sliding'"),
    (dict(chunk=0), "chunk 0"),
    (dict(layer_types=("attention",) * 2, window=0), "without 'eva' layers"),
])
def test_an_eva_layer_beside_another_kind_is_refused_by_name(kw, named):
    args = dict(n_layers=2, layer_types=("eva", "eva"), window=32, chunk=4,
                window_rule="aligned")
    args.update(kw)
    with pytest.raises(ValueError, match=named):
        TransformerConfig(**args)


# -- the two kernels and their lists (interpret mode) -------------------------


def _paged_case(rng, B, Hq, Hkv, D, NB, N, tq):
    q = jnp.asarray(rng.normal(size=(B, Hq, tq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, D)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(N)[:B * NB].reshape(B, NB), jnp.int32)
    return q, k, v, table


@pytest.mark.parametrize("rule", [LOCAL, FAR], ids=["local", "summary"])
@pytest.mark.parametrize("tq", [1, 5, 40])
def test_each_kernel_against_the_reference_at_ragged_lengths(tq, rule):
    """Interpret mode: rows of slots at a window's first row, its last, far
    past several and (tq 5, 40) straddling a boundary, each row with its own
    window; the plan built in the call and the plan handed in give the same
    bits; a slot with no closed window gets the merge identity."""
    rng = np.random.default_rng(tq)
    B, Hq, Hkv, D, NB, N = 5, 4, 4, 16, 24, 160
    q, k, v, table = _paged_case(rng, B, Hq, Hkv, D, NB, N, tq)
    cap = NB * BLOCK
    pos = jnp.asarray([0, WINDOW - 1, WINDOW, 3 * WINDOW - 2, cap - tq],
                      jnp.int32)
    out, lse = attention_pallas_decode(
        q, k, v, causal=True, q_offset=pos, block_table=table, window=rule,
        interpret=True)
    plan = decode_plan(Hq, tq, k, table, pos, window=rule)
    out2, _ = attention_pallas_decode(
        q, k, v, causal=True, q_offset=pos, block_table=table, window=rule,
        interpret=True, step_plan=plan)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    kk = jnp.moveaxis(k[table], 1, 2).reshape(B, Hkv, cap, D)
    vv = jnp.moveaxis(v[table], 1, 2).reshape(B, Hkv, cap, D)
    for b in range(B):
        want, want_lse = attention_naive(
            q[b:b + 1], kk[b:b + 1], vv[b:b + 1], causal=True,
            q_offset=int(pos[b]), window=rule)
        np.testing.assert_allclose(out[b], want[0], atol=1e-5)
        np.testing.assert_allclose(lse[b], want_lse[0], atol=1e-5)
    if rule is FAR and tq == 1:
        # Positions 0 and W - 1 have no closed window: (0, -inf).
        assert np.isneginf(np.asarray(lse[:2])).all()
        assert not np.asarray(out[:2]).any()
    # What each row sees, from positions: row t sees [w0, t] of the exact
    # rows and the summaries c < w0 / C, and no other column.
    t = int(pos[3]) + tq - 1
    w0 = t // WINDOW * WINDOW
    seen = np.zeros(cap, bool)
    if rule is LOCAL:
        seen[w0:t + 1] = True
    else:
        seen[:w0 // CHUNK] = True
    logits = jnp.einsum("d,sd->s", q[3, 0, -1], kk[3, 0]) * D ** -0.5
    p = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf))
    np.testing.assert_allclose(out[3, 0, -1], p @ vv[3, 0], atol=1e-5)
    assert (EVA_LOCAL_KERNEL, EVA_SUMMARY_KERNEL) == (
        "eva_local_decode", "eva_summary_decode")


@pytest.mark.parametrize("rule", [LOCAL, FAR], ids=["local", "summary"])
@pytest.mark.parametrize("tq, entries", [(1, 1), (1, 2), (5, 2), (24, 4)])
def test_the_devices_list_is_the_hosts_count_under_each_rule(
        tq, entries, rule):
    """``paged_plan`` under the two rules: a slot's entries are the steps
    ``tuning.paged_rule_steps`` counts on the host (numpy) from the same
    offsets: from the step that holds the first row's window start to the
    one that holds the last row; or the steps under the last row's
    summaries, none (one dead entry) where no window has closed."""
    NB, B = 24, 6
    rng = np.random.default_rng(entries)
    table = jnp.asarray(rng.permutation(B * NB).reshape(B, NB), jnp.int32)
    cap, step = NB * BLOCK, entries * BLOCK
    pos = np.asarray([0, WINDOW - tq, WINDOW - 1, WINDOW, 4 * WINDOW + 3,
                      cap - tq], np.int32)
    plan = paged_plan(jnp.asarray(pos), 0, table, tq=tq, entries=entries,
                      block=BLOCK, window=rule)
    n_steps = NB // entries
    first, live = tuning.paged_rule_steps(pos, 0, tq, step, n_steps, rule)
    first = np.zeros_like(live) if first is None else first
    count = int(plan.count)
    assert count == int(np.maximum(live, 1).sum())
    slot, stp = np.asarray(plan.slot)[:count], np.asarray(plan.step)[:count]
    want = [(b, s) for b in range(B)
            for s in range(first[b], first[b] + max(live[b], 1))]
    assert list(zip(slot.tolist(), stp.tolist())) == want
    if rule is LOCAL:
        # A window and a group's rows past it, whatever the length.
        assert live.max() <= (WINDOW + tq - 2) // step + 2
        assert first[4] == 4 * WINDOW // step
    else:
        last = pos + tq - 1
        assert live.tolist() == [
            -(-(t // WINDOW * (WINDOW // CHUNK)) // step) for t in last]
        assert live[0] == 0 and (tq > 1 or live[2] == 0)


def test_the_merge_of_the_two_partials_is_one_softmax_over_both_sets():
    """A row's exact partial and its summary partial, each ``(out, lse)``,
    joined by ``merge_partials``, against ONE softmax over the scores of
    both sets side by side."""
    rng = np.random.default_rng(0)
    H, D, T = 4, 16, 3 * WINDOW + 5
    q = jnp.asarray(rng.normal(size=(1, H, 1, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, H, T + 1, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, H, T + 1, D)), jnp.float32)
    ks = jnp.asarray(rng.normal(size=(1, H, 64, D)), jnp.float32)
    vs = jnp.asarray(rng.normal(size=(1, H, 64, D)), jnp.float32)
    a = attention_naive(q, k, v, causal=True, q_offset=T, window=LOCAL)
    b = attention_naive(q, ks, vs, causal=True, q_offset=T, window=FAR)
    out, _ = merge_partials(jnp.stack([a[0], b[0]]), jnp.stack([a[1], b[1]]))
    w0 = T // WINDOW * WINDOW
    keys = jnp.concatenate([k[0, :, w0:T + 1], ks[0, :, :w0 // CHUNK]], 1)
    vals = jnp.concatenate([v[0, :, w0:T + 1], vs[0, :, :w0 // CHUNK]], 1)
    p = jax.nn.softmax(
        jnp.einsum("hd,hsd->hs", q[0, :, 0], keys) * D ** -0.5, axis=-1)
    np.testing.assert_allclose(
        out[0, :, 0], jnp.einsum("hs,hsd->hd", p, vals), atol=1e-5)
    # A row with no closed window: the summary partial is the identity and
    # the merge is the exact partial, bit for bit in its values.
    a0 = attention_naive(q, k, v, causal=True, q_offset=5, window=LOCAL)
    b0 = attention_naive(q, ks, vs, causal=True, q_offset=5, window=FAR)
    assert np.isneginf(np.asarray(b0[1])).all()
    both, _ = merge_partials(jnp.stack([a0[0], b0[0]]),
                             jnp.stack([a0[1], b0[1]]))
    np.testing.assert_allclose(both, a0[0], atol=1e-7)


def test_the_chunk_read_kernel_reads_what_a_gather_reads():
    """Interpret mode: ``paged_chunk_read`` against the slices themselves;
    an entry past its member's count is not read (and not compared)."""
    rng = np.random.default_rng(1)
    N, Hkv, D, B, J = 12, 4, 16, 3, 4
    pools = tuple(jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, D)),
                              jnp.float32) for _ in range(2))
    blk = jnp.asarray(rng.integers(0, N, (B, J)), jnp.int32)
    row = jnp.asarray(rng.integers(0, BLOCK // CHUNK, (B, J)) * CHUNK,
                      jnp.int32)
    count = jnp.asarray([4, 0, 2], jnp.int32)
    got = paged_chunk_read(pools, blk, row, count, CHUNK, interpret=True)
    for pool, g in zip(pools, got):
        assert g.shape == (B, J, Hkv, CHUNK, D)
        for b in range(B):
            for j in range(int(count[b])):
                r = int(row[b, j])
                np.testing.assert_array_equal(
                    g[b, j], pool[int(blk[b, j]), :, r:r + CHUNK])


# -- the two pools against the reference --------------------------------------


def _serve_rows(params, tcfg, toks, steps, slots=2, nb=24, chunk=24,
                packed=False, stats=None):
    """Run ``steps`` (rows a slot a step) through the two pools with the
    exact rows' table kept by the engine's own ledger under the aligned
    rule (blocks behind the window given back before each step, scrambled
    ids) and the summary table mapped whole: the logits of the rows that
    carried a token, the ledger, the most blocks a slot held, and the
    cache. ``chunk`` is the most rows a step carries (what the ledger's
    bound counts); the token block is ``WIDTH`` rows wide, or one, whatever
    the counts (``tests/jitted.py``)."""
    nbs = -(-nb // CHUNK)
    cache = init_paged_cache(tcfg, slots, nb * BLOCK, slots * nbs + 1,
                             block=BLOCK, window_blocks=64)
    assert isinstance(cache, PagedWindowCache)
    assert cache.table.shape == (slots, nbs) \
        and cache.wtable.shape == (slots, nb) and cache.capacity == nb * BLOCK
    win = WindowBlocks(slots=slots, table_width=nb, block=BLOCK,
                       window=WINDOW, chunk=chunk, rule="aligned")
    for i in range(slots):
        assert win.reserve()
        win.admit(i)
    table = 1 + jnp.arange(slots * nbs, dtype=jnp.int32).reshape(
        slots, nbs)[:, ::-1]
    cache = dataclasses.replace(cache, table=table)
    got, pos, peak = [[] for _ in range(slots)], [0] * slots, 0
    for ns in steps:
        for i, n in enumerate(ns):
            if n:
                win.advance(i, pos[i], pos[i] + n)
        peak = max(peak, max(win.held(i) for i in range(slots)))
        cache = dataclasses.replace(cache, wtable=jnp.asarray(win.table))
        rows, cache, st = serve_step_stats(
            params, tcfg, cache, toks, pos, ns, WIDTH, packed=packed)
        for i, row, lg in rows:
            got[i].append((row, lg))
        wrote = st["eva_summaries"]
        if stats is not None:
            due = sum(int(chunks_closed(pos[i], n, CHUNK)[1])
                      for i, n in enumerate(ns) if n)
            stats.append((int(wrote), due * tcfg.eva_layers))
        for i, n in enumerate(ns):
            pos[i] += n
    return got, win, peak, cache


def _steps(plen, total, chunk, other=0):
    """Slot 0: a prompt of ``plen`` in chunks of ``chunk``, then a row a
    step to ``total``; slot 1 rides with ``other`` rows at first and sits
    out every third step after."""
    steps, pos, i = [], 0, 0
    while pos < total:
        n = min(chunk, plen - pos) if pos < plen else 1
        steps.append([n, (1 if i % 3 else 0) if other else 0])
        pos += n
        i += 1
    return steps


@pytest.mark.parametrize("chunk", [3, 8, 24])
def test_prefill_then_decode_through_both_pools_equals_the_reference(
        ref, model, chunk):
    """A prompt of 101 positions (no multiple of the chunk of 4: decode
    takes over mid-chunk) in chunks of 3 (under a summary chunk), 8 (a
    block) and 24 (groups that straddle the window boundaries at 32,
    64, 96), then a row a tick past the fourth boundary at 128: every
    row's logits are the reference's, beside a slot that decodes its own
    sequence and sits out every third tick. A slot never holds more exact
    rows' blocks than its bound; every summary row due is written once."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(chunk)
    toks = [rng.integers(0, 320, (140,)), rng.integers(0, 320, (140,))]
    stats = []
    got, win, peak, _ = _serve_rows(
        params, tcfg, toks, _steps(101, 140, chunk, other=1), chunk=chunk,
        stats=stats)
    for i in range(2):
        rows = [p for p, _ in got[i]]
        assert rows == list(range(len(rows))) and len(rows) > (139, 20)[i]
        want = _want(ref, w, weights, toks[i][:len(rows)])
        np.testing.assert_allclose(
            np.stack([lg for _, lg in got[i]]), want, atol=ATOL)
    assert peak <= win.bound == -(-(WINDOW + chunk) // BLOCK) + 1
    assert all(wrote == due for wrote, due in stats)
    assert sum(w_ for w_, _ in stats) == 2 * (140 // CHUNK + len(got[1])
                                              // CHUNK)


def test_a_packed_tick_serves_a_straddling_chunk_beside_decode_rows(
        ref, model):
    """``forward_packed_step``: slot 0's chunks of 24 rows (28..51 and
    52..75 each straddle a window boundary: rows before it see the old
    window, rows after it the new one and the old one's summaries) beside a
    decode row of slot 1, which is itself past two windows."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(2)
    toks = [rng.integers(0, 320, (80,)), rng.integers(0, 320, (90,))]
    steps = [[0, 24], [0, 24], [0, 24], [4, 1], [24, 1], [24, 1], [24, 1]]
    got, _, _, _ = _serve_rows(params, tcfg, toks, steps, packed=False)
    # The same steps with the last four packed: one member, one decode row.
    got_p, _, _, _ = _serve_rows(params, tcfg, toks, steps[:3], packed=False)
    assert [p for p, _ in got_p[1]] == list(range(72))
    pk, _, _, _ = _serve_rows(params, tcfg, toks, steps, packed=True)
    for i, n_last in ((0, [3, 27, 51, 75]), (1, [72, 73, 74, 75])):
        rows = [p for p, _ in pk[i]][-4:]
        assert rows == n_last
        want = _want(ref, w, weights, toks[i][:rows[-1] + 1], rows=rows)
        np.testing.assert_allclose(
            np.stack([lg for _, lg in pk[i]][-4:]), want, atol=ATOL)
    # The padded steps agree with the reference too.
    want = _want(ref, w, weights, toks[0][:76])
    np.testing.assert_allclose(
        np.stack([lg for _, lg in got[0]]), want, atol=ATOL)


@pytest.mark.parametrize("fault, least", [
    ("no_summaries", 1e-2), ("sliding", 1e-3), ("int8", 1e-3)])
def test_the_controls_differ_from_the_sound_reference(ref, model, fault,
                                                      least):
    """The faults the cell's limits are held against show at this size
    too: the summaries left out, a window that slides, int8."""
    w, weights, _, _ = model
    toks = np.random.default_rng(3).integers(0, 320, (140,))
    sound = _want(ref, w, weights, toks)
    other = _want(ref, w, weights, toks, quant=fault)
    far = np.abs(other - sound).max(axis=1)
    assert far[WINDOW:].max() > 100 * ATOL and far[WINDOW:].max() > least
    if fault != "int8":
        # Inside the first window there is nothing to leave out or slide.
        assert far[:WINDOW].max() < ATOL


def test_a_summary_row_is_a_pure_function_of_the_pools_rows(ref, model):
    """The summary pool after a run holds, for every closed chunk, the
    reference's summary of that chunk (layer 0: its keys are the
    embedding's); rows of chunks not closed were never written; and the
    rows a step changed are the rows the host counted as due."""
    w, weights, tcfg, params = model
    toks = [np.random.default_rng(5).integers(0, 320, (70,)),
            np.zeros((1,), np.int64)]
    steps = [[24, 0], [24, 0], [19, 0]] + [[1, 0]] * 3
    _, _, _, cache = _serve_rows(params, tcfg, toks, steps[:3])
    before = np.asarray(cache.k)
    _, _, _, cache = _serve_rows(params, tcfg, toks, steps)
    after = np.asarray(cache.k)
    # 67 rows then 70: chunk 16 (rows 64-67) closes at row 67, one more row
    # a layer than the run of 67 rows left.
    changed = (before != after).any(axis=(2, 4))     # (layers, blocks, rows)
    assert changed.sum() == tcfg.eva_layers * 1
    p = jax.tree.map(lambda t: t[0], weights["layers"])
    x = weights["embed"][jnp.asarray(toks[0][:68])].astype(jnp.float32)
    h = ref._rms(x, p["ln1"], w)
    k = ref._rope(ref._mm(h, p["wk"], None).reshape(68, 4, 16), w)
    v = ref._mm(h, p["wv"], None).reshape(68, 4, 16)
    ks, _ = ref.summaries(k, v, p["phi"], p["mu"], w)
    table = np.asarray(cache.table)[0]
    for c in (0, 7, 8, 16):
        np.testing.assert_allclose(
            after[0, table[c // BLOCK], :, c % BLOCK], ks[c], atol=1e-5)
    # Chunk 17 (rows 68-71) has not closed: its row is as it was allocated.
    assert not after[:, table[17 // BLOCK], :, 17 % BLOCK].any()


# -- the exact rows' ledger under the aligned rule ----------------------------


def test_a_slots_blocks_go_back_a_whole_window_at_a_boundary():
    """Chunks, then one row at a time to the table's end: the slot never
    holds more than its bound, at a window boundary it gives the old
    window's ``window / block`` blocks back at once (a chunk that straddles
    it keeps them until its own dispatch has gone), and the allocator is
    whole after it retires."""
    nb, chunk = 32, 24
    win = WindowBlocks(slots=2, table_width=nb, block=BLOCK, window=WINDOW,
                       chunk=chunk, rule="aligned")
    assert win.bound == 8 and win.blocks == 18
    assert win.reserve()
    win.admit(0)
    pos, peak, gave = 0, 0, []
    while pos < nb * BLOCK:
        n = min(chunk, 60 - pos) if pos < 60 else 1
        gave.append((pos, win.advance(0, pos, pos + n)))
        pos += n
        peak = max(peak, win.held(0))
        assert win.held(0) + win.reserved(0) == win.bound
        assert win.alloc.used == win.held(0)
        w0 = (pos - n) // WINDOW * WINDOW        # the first row's window
        assert sorted(win._held[0]) == list(
            range(w0 // BLOCK, (pos - 1) // BLOCK + 1))
    assert peak <= win.bound
    # The chunk at 24..47 straddles 32 and gives nothing back; the one at
    # 48 gives window 0's four blocks back at once; in decode every
    # boundary does, and no other tick gives any.
    assert dict(gave)[24] == 0 and dict(gave)[48] == WINDOW // BLOCK
    for t, n in gave:
        if t >= 60:
            assert n == (WINDOW // BLOCK if t % WINDOW == 0 else 0), t
    assert win.freed == nb - win.held(0)
    win.free_slot(0)
    assert win.alloc.used == 0 and win.alloc.reserved == 0
    assert not win.table.any()
    with pytest.raises(ValueError, match="'sliding' or 'aligned'"):
        WindowBlocks(slots=1, table_width=4, block=BLOCK, window=WINDOW,
                     chunk=8, rule="tumbling")


# -- through the engine --------------------------------------------------------


def _engine(tcfg, params, **kw):
    args = dict(slots=3, cache_len=192, prefill_chunk=24, kv_block=BLOCK)
    args.update(kw)
    return SlotServer(params, tcfg, **args)


def _is_greedy(ref, weights, w, prompt, tokens):
    """Every served token is the reference's first choice at its position,
    given the prompt and the served tokens before it."""
    seq = np.asarray(list(prompt) + list(tokens[:-1]))
    rows = np.arange(len(prompt) - 1, len(seq))
    want = _want(ref, w, weights, seq, rows=rows)
    return want.argmax(-1).tolist() == list(tokens)


def _poison(eng):
    """Every key row of both pools NaN, every value row huge: a row read
    that its reader's mask does not hide shows in every later logit."""
    c = eng.cache
    eng.cache = dataclasses.replace(
        c, k=jnp.full_like(c.k, jnp.nan), v=jnp.full_like(c.v, 1e4),
        wk=jnp.full_like(c.wk, jnp.nan), wv=jnp.full_like(c.wv, 1e4))


def test_requests_across_windows_a_reused_slot_and_the_counters(ref, model):
    """Through ``SlotServer.serve``: a request across four window boundaries
    (prompt 101: decode takes over mid-chunk) beside two short ones; then,
    every row of both pools poisoned, a SHORTER request in each slot the
    long one could have used: it sees none of its predecessor's summary
    rows (the upper edge is its own length's, not the table's). Every
    served token is the reference's greedy choice. The flight records say
    every summary due was written, what the lists made visible and what the
    ledger held and gave back; nothing is leaked."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(4)
    long = rng.integers(0, 320, (101,)).tolist()
    FLIGHT.clear()
    FLIGHT.arm(capacity=4096)
    obs.REGISTRY.enable()
    try:
        eng = _engine(tcfg, params)
        rep = eng.serve([
            Request(uid=0, prompt=long, max_new_tokens=36),
            Request(uid=1, prompt=long[:9], max_new_tokens=12),
            Request(uid=2, prompt=long[3:40], max_new_tokens=30)])
        recs = [r for r in FLIGHT.snapshot()["records"]
                if "eva_summaries_due" in r]
        text = obs.REGISTRY.to_prometheus()
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()
    by_uid = {r.uid: r for r in rep.results}
    for uid, prompt in ((0, long), (1, long[:9]), (2, long[3:40])):
        assert _is_greedy(ref, weights, w, prompt, by_uid[uid].tokens), uid
    # Every summary due written once, a tick at a time (a tick that samples
    # nothing fetches nothing and reports no count) and, by the host's
    # count, in all: the rows written (the last sampled token of a request
    # is never written).
    told = [r for r in recs if "eva_summaries_written" in r]
    assert len(told) > 30 and all(
        r["eva_summaries_written"] == r["eva_summaries_due"] for r in told)
    assert all(r["chunk_tokens"] and not r["tokens_emitted"] for r in recs
               if "eva_summaries_written" not in r)
    chunks = sum((len(p) + n - 1) // CHUNK for p, n in (
        (long, 36), (long[:9], 12), (long[3:40], 30)))
    assert sum(r["eva_summaries_due"] for r in recs) == 2 * chunks
    # What the two lists made visible: never more exact rows than a window
    # and a chunk a live slot, summaries only past the first boundary.
    assert max(r["eva_local_rows"] for r in recs) <= 3 * (WINDOW + 24)
    assert any(r["eva_summary_rows"] for r in recs)
    late = [r for r in recs if r["occupancy"] == 1 and not r["chunk_tokens"]]
    assert late and all(r["eva_local_rows"] <= WINDOW for r in late)
    assert max(r["eva_summary_rows"] for r in late) == 128 // CHUNK
    assert all(r["kv_steps_run"] == r["kv_steps_run_summary"]
               + r["kv_steps_run_local"] for r in recs)
    kv = rep.kv
    assert kv["window_blocks_bound"] == 8 and kv["window_pool_blocks"] == 27
    assert kv["window_blocks_peak_slot"] <= 8
    assert kv["window_blocks_freed"] >= 4 * (WINDOW // BLOCK)
    assert max(r["window_blocks_held"] for r in late) <= WINDOW // BLOCK
    assert max(r["window_blocks_full"] for r in late) >= 18
    # The summary pool: a block of 8 rows for every 32 positions.
    assert kv["pool_blocks"] == 3 * 6 and kv["peak_blocks_used"] <= 5 + 1 + 3
    leak = eng.leak_report()
    assert leak["blocks_used"] == 0 == leak["window_blocks_used"]
    assert leak["blocks_reserved"] == 0 == leak["window_blocks_held"]
    assert "serving_eva_summaries_written_total" in text
    assert 'serving_kv_window_blocks{state="held"}' in text
    assert 'kernel="eva' not in text     # off the TPU no kernel is built
    assert 'cache="paged_window"' in text
    # The same engine, its pools poisoned: three shorter requests, one a
    # slot; each crosses two boundaries of its own.
    _poison(eng)
    short = [rng.integers(0, 320, (n,)).tolist() for n in (37, 50, 66)]
    rep = eng.serve([Request(uid=10 + i, prompt=p, max_new_tokens=20)
                     for i, p in enumerate(short)])
    for r in rep.results:
        assert _is_greedy(ref, weights, w, short[r.uid - 10], r.tokens), r.uid
    assert eng.leak_report()["blocks_used"] == 0


def test_a_stale_upper_edge_would_show(ref, model):
    """The fault the reuse test guards against, made by hand: a summary
    call whose rows see every MAPPED summary row (an upper edge from the
    table, not from the length) reads its slot's last request's rows."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(6)
    toks = [rng.integers(0, 320, (140,)), np.zeros((1,), np.int64)]
    _, _, _, cache = _serve_rows(params, tcfg, toks, [[24, 0]] * 5)
    rows = np.asarray(cache.k)[0, np.asarray(cache.table)[0]]
    rows = np.moveaxis(rows, 1, 0).reshape(4, -1, 16)    # (heads, rows, d)
    written = np.abs(rows).sum(axis=(0, 2)) > 0
    # 120 positions wrote 30 summary rows; a new request of 70 positions in
    # this slot may see 16 of them (two closed windows), not 30.
    assert written.sum() == 30
    assert int(tuning.paged_rule_steps(
        np.asarray([69]), 0, 1, BLOCK, 6, FAR)[1][0]) * BLOCK == 16


@pytest.mark.parametrize("kw, named", [
    (dict(quantize=True), "int8 eva rows"),
    (dict(kv_shard="seq"), "sequence-sharded"),
    (dict(host_blocks=4, prefix_cache=True), "host tier"),
    (dict(speculate=True), "summary row"),
    (dict(prefix_cache=True, prefix_block=BLOCK), "the prefix cache"),
])
def test_engine_refuses_what_the_eva_pools_do_not_carry(model, kw, named):
    _, _, tcfg, params = model
    with pytest.raises(ValueError, match=f"eva pool.*{named}"):
        _engine(tcfg, params, **kw)


def test_disaggregation_and_forks_are_refused_by_the_cache_kinds_name(model):
    from tree_attention_tpu.serving.block_pool import BlockAllocator

    _, _, tcfg, params = model
    with pytest.raises(ValueError, match="eva pool.*disaggregation"):
        _engine(tcfg, params, block_pool=BlockAllocator(72))
    eng = _engine(tcfg, params)
    with pytest.raises(ValueError, match="eva pool.*partial summary block"):
        eng.serve([Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4, n=2)])
    with pytest.raises(ValueError, match="eva pool"):
        eng.fork(0)


@pytest.mark.parametrize("flags, named", [
    (["--kv-quant", "int8"], "eva pool is not served with --kv-quant"),
    (["--speculate"], "--speculate"),
    (["--serve-disagg"], "--serve-disagg"),
    (["--prefix-cache", "--prefix-block", "8"],
     "eva pool is not served with --prefix-cache"),
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
    """``--model-config`` with this family's keys: the program draws its
    own stacks and serves through ``SlotServer`` like the other seven, no
    flag of its own."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    path = tmp_path / "model.json"
    path.write_text(json.dumps(SMALL))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "70", "--max-new-tokens", "8",
                      "--dtype", "float32", "--kv-block", str(BLOCK),
                      "--prefill-chunk", "16", "--model-config", str(path)])
    setup = cli.build_serve_engine(cfg, None)
    p = setup.params
    assert p["eva"]["phi"].shape == (2, 4, 16) and "wqkv_t" in p["eva"]
    assert p["wout"].shape == (64, 8 * 320) and "attn" not in p
    eng = setup.make_engine()
    # A block of exact rows for every 8 positions of a table row, one of
    # summaries for every 32; the exact rows' pool a constant of blocks a
    # slot (a window and a chunk: 7, and one more).
    nb = -(-eng.cache_len // BLOCK)
    assert eng.cache.wtable.shape == (2, nb) and eng.cache.table.shape == (
        2, -(-nb // CHUNK))
    assert eng.cache.wk.shape[:2] == (2, 2 * 8) and eng.cache.k.shape[:2] == (
        2, 2 * -(-nb // CHUNK))
    rep = eng.serve([Request(uid=0, prompt=list(range(1, 71)),
                             max_new_tokens=8)])
    assert len(rep.results[0].tokens) == 8
    assert eng.leak_report()["blocks_used"] == 0
