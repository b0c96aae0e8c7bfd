"""A decoder that feeds a second decoder (the ``phi4flash`` family): Mamba-1
state-space layers and sliding-window attention in turn, one more Mamba-1
layer whose scan output is the MEMORY, ONE full-attention layer whose K/V rows
are SHARED, then gated memory units and cross layers in turn; every attention
differential, on the packed rows the paged kernels read; in a packed tick the
rows no slot samples from leave the stack after the shared layer. Held
against the benchmark's plain reference (``benchmark/references/phi4flash.py``:
the full forward pass over one sequence, the recurrence a token at a time,
differential attention as its definition, every layer on every row) at a
small size, on the CPU, in float32, with seeded weights: 8 query heads over 4
KV heads of 16 (two pairs share a value pair), 256 channels x 8 states, a
window of 8, 12 layers (the split then has every kind three times below the
seam and twice above it, so that both periods are scanned).

Tolerances. Logits here have a standard deviation of ~1. The program and the
reference add the same float32 numbers in other orders (attention over a
gathered view of packed rows against two softmaxes over a row; the pair's
subtraction and the norm after it amplify a rounding by ``1 / |a0 - lam a1|``):
their logits agree to ~1e-5 and are held to ``ATOL`` 2e-4. What a test shows
to be DIFFERENT (a control, a wrong index at the seam) differs by 1e-2 or
more.
"""

import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_attention_tpu.models import decode, hybrid
from tree_attention_tpu.models.decode import (
    PagedStateWindowCache,
    init_paged_cache,
)
from tree_attention_tpu.models.hybrid import period_runs
from tree_attention_tpu.models.transformer import (
    Mamba1,
    TransformerConfig,
    init_params,
    model_from_config,
)
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.serving import SlotServer
from tree_attention_tpu.serving.engine import Request

from tests.jitted import serve_step_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-4
BLOCK = 4
WIDTH = 16      # the one chunk width the step helpers compile

BLOCK_KEYS = {"decoder_split": "sambay", "attention": "differential",
              "diff_pairing": "adjacent", "cross_attention": "differential",
              "lambda_depth": "layer_index_from_0",
              "gmu_memory": "scan_output_before_gate",
              "mlp_order": "gate_up", "window_span": 8}
SMALL = {
    "family": "phi4flash", "model_type": "phi4flash", "hidden_size": 128,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 192, "num_hidden_layers": 12, "vocab_size": 128,
    "sliding_window": 8, "mb_per_layer": 2, "layer_norm_eps": 1e-5,
    "hidden_act": "silu", "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "max_position_embeddings": 4096,
    "torch_dtype": "float32",
    "block": dict(BLOCK_KEYS),
    "assumed": {"mamba_expand": 2, "mamba_d_state": 8, "mamba_d_conv": 4,
                "mamba_dt_rank": 8, "time_step_min": 0.001,
                "time_step_max": 0.1,
                "seeded_scales": {
                    "embedding_std": 0.1, "ln_gain_std": 0.1,
                    "ln_bias_std": 0.05, "mlp_in_std": 0.09,
                    "mlp_out_std": 0.004, "ssm_in_std": 0.09,
                    "ssm_x_std": 0.2, "ssm_dt_std": 0.2,
                    "ssm_out_std": 0.004, "qkv_std": 0.12,
                    "attn_bias_std": 0.05, "attn_out_bias_std": 0.002,
                    "attn_out_std": 0.002, "sub_gain_mean": 1.5,
                    "sub_gain_std": 0.1, "gmu_in_std": 0.09,
                    "gmu_out_std": 0.004}},
}
PUBLISHED = os.path.join(ROOT, "benchmark", "configs",
                         "phi-4-mini-flash-reasoning.json")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "benchmark", "references",
                              "phi4flash.py"), "_references_phi4flash")


@pytest.fixture(scope="module")
def adapter():
    return _load(os.path.join(ROOT, "benchmark", "adapters", "phi4flash.py"),
                 "_adapters_phi4flash")


@pytest.fixture(scope="module")
def model(ref, adapter):
    """(widths, reference weights, TransformerConfig, engine params)."""
    w = ref.Widths.of(SMALL)
    weights = ref.init_weights(7, w)
    tcfg = model_from_config(SMALL, max_seq_len=128)
    adapter._hold_to_file(tcfg, SMALL, ref)
    return w, weights, tcfg, adapter.engine_params(weights, w)


def _want(ref, w, weights, toks, rows=None, **kw):
    rows = np.arange(len(toks)) if rows is None else np.asarray(rows)
    return ref.logits_at(weights, w, np.asarray(toks), rows, pad_to=16, **kw)


def _cache(tcfg, slots=2, nb=16):
    """Every block of every slot mapped under both tables: the window's
    lower edge is the mask's alone here (the engine's cases give blocks
    back)."""
    cache = init_paged_cache(tcfg, slots, nb * BLOCK, slots * nb, block=BLOCK,
                             window_blocks=slots * nb)
    assert isinstance(cache, PagedStateWindowCache)
    table = jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb)[:, ::-1]
    return dataclasses.replace(cache, table=table, wtable=table)


def _serve_rows(params, tcfg, toks, steps, packed=False, cache=None):
    """Run ``steps`` (rows a slot a step) through the cache: the logits of
    the rows that came back, the cache, and each step's counters."""
    slots = len(toks)
    cache = _cache(tcfg, slots) if cache is None else cache
    got, pos, stats = [[] for _ in range(slots)], [0] * slots, []
    for ns in steps:
        rows, cache, st = serve_step_stats(
            params, tcfg, cache, toks, pos, ns, WIDTH, packed=packed)
        stats.append(st)
        for i, row, lg in rows:
            got[i].append((row, lg))
        for i, n in enumerate(ns):
            pos[i] += n
    return got, cache, stats


# -- the model as data -------------------------------------------------------


def test_the_published_file_is_the_whole_model():
    """3,852.6M parameters by the program's own count at the published
    widths (shapes only), nothing reduced, six pools at the cell's sizes."""
    with open(PUBLISHED) as f:
        c = json.load(f)
    assert c["reduced"] == [] and c["num_hidden_layers"] == 32
    t = model_from_config(c)
    assert t.layer_types[:4] == ("ssm1", "window", "ssm1", "window")
    assert t.layer_types[16:20] == ("ssm1", "attention", "gmu", "cross")
    assert [t.layer_types.count(k) for k in (
        "ssm1", "window", "attention", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert t.ssm1 == Mamba1(inner=5120, d_state=16, taps=4, dt_rank=160)
    assert (t.cache_kind, t.cache_layers, t.ssm_layers, t.window_layers,
            t.row_cut, t.kv_pack) == ("state_window", 1, 9, 8, 18, 2)
    assert (t.d_head, t.rotary, t.norm, t.tied_head, t.norm_eps) == (
        64, (), "layer", True, 1e-5)
    assert [(tuple(s.mixer for s in period), n)
            for period, n in period_runs(t)] == [
        (("ssm1", "window"), 8), (("ssm1",), 1), (("attention",), 1),
        (("gmu", "cross"), 7)]
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), t))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 3_852_562_944 and "3,852,562,944" in c["why_reduced"]
    assert "wout" not in shapes and "wk" not in shapes["xattn"]
    s = c["serving"]
    cache = jax.eval_shape(lambda: init_paged_cache(
        t, s["slots"], s["cache_len"], s["slots"] * 144, block=64,
        window_blocks=s["slots"] * 14))
    assert cache.k.shape == cache.v.shape == (1, 6912, 10, 64, 128)
    assert cache.wk.shape == cache.wv.shape == (8, 672, 10, 64, 128)
    assert cache.ssm_state.shape == (9, 48, 16, 5120)
    assert cache.ssm_state.dtype == jnp.float32
    assert cache.ssm_tail.shape == (9, 48, 15360)


def test_the_small_files_keys_say_what_each_layer_is(model):
    _, _, t, params = model
    assert t.layer_types == ("ssm1", "window") * 3 + ("ssm1", "attention") \
        + ("gmu", "cross") * 2
    assert [(tuple(s.mixer for s in period), n)
            for period, n in period_runs(t)] == [
        (("ssm1", "window"), 3), (("ssm1",), 1), (("attention",), 1),
        (("gmu", "cross"), 2)]
    assert (t.cache_kind, t.row_cut, t.kv_pack, t.d_head) == (
        "state_window", 8, 2, 16)
    assert params["ssm1"]["A_log"].shape == (4, 8, 256)
    assert params["xattn"]["wq"].shape == (2, 128, 128)
    assert params["dense"]["w1"].shape == (12, 128, 192)


@pytest.mark.parametrize("change, named", [
    ({"mb_per_layer": 3}, "mb_per_layer"),
    ({"num_hidden_layers": 6}, "multiple of 4"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"rope_theta": 10000.0}, "rope_theta"),
    ({"rope_scaling": None}, "rope_scaling"),
    ({"layer_types": ["full_attention"] * 12}, "beside layer_types"),
    ({"num_experts": 4, "num_experts_per_tok": 2,
      "moe_intermediate_size": 8}, "experts"),
    ({"block": dict(BLOCK_KEYS, decoder_split="samba")}, "decoder_split"),
    ({"block": dict(BLOCK_KEYS, attention="softmax")}, "block.attention"),
    ({"block": dict(BLOCK_KEYS, diff_pairing="front_back")}, "diff_pairing"),
    ({"block": dict(BLOCK_KEYS, cross_attention="softmax")},
     "cross_attention"),
    ({"block": dict(BLOCK_KEYS, lambda_depth="decoder_index")},
     "lambda_depth"),
    ({"block": dict(BLOCK_KEYS, gmu_memory="gated_output")}, "gmu_memory"),
    ({"block": dict(BLOCK_KEYS, mlp_order="up_gate")}, "mlp_order"),
    ({"block": dict(BLOCK_KEYS, window_span=7)}, "window_span"),
])
def test_each_refused_key_is_refused_by_its_name(change, named):
    with pytest.raises(ValueError, match=named):
        model_from_config(dict(SMALL, **change))


def _types(t, **at):
    types = list(t.layer_types)
    for i, kind in at.items():
        types[int(i[1:])] = kind
    return tuple(types)


@pytest.mark.parametrize("kw, named", [
    (lambda t: dict(layer_types=_types(t, l7="window")), "ONE shared"),
    (lambda t: dict(layer_types=_types(t, l5="attention")), "ONE shared"),
    (lambda t: dict(layer_types=_types(t, l6="gmu")), "last layer below"),
    (lambda t: dict(layer_types=("window", "attention") + ("gmu", "cross")
                    * 5), "no Mamba-1 layer before"),
    (lambda t: dict(layer_types=_types(t, l10="ssm1")), "second memory"),
    (lambda t: dict(layer_types=_types(t, l1="conv")), "beside conv"),
    (lambda t: dict(ssm1=None), "Mamba-1 widths"),
    (lambda t: dict(diff_attn=False), "diff_attn"),
    (lambda t: dict(rotary=("attention",)), "rotary"),
    (lambda t: dict(qk_norm=True), "qk_norm"),
    (lambda t: dict(n_kv_heads=1), "both counts are even"),
    (lambda t: dict(norm="batch"), "norm"),
])
def test_what_the_loop_cannot_run_is_refused_by_name(model, kw, named):
    _, _, t, _ = model
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(t, **kw(t))


def test_differential_pieces_without_mamba1_layers_are_refused():
    for kw in (dict(diff_attn=True), dict(attn_bias=True),
               dict(norm="layer")):
        with pytest.raises(ValueError, match="without Mamba-1 layers"):
            TransformerConfig(**kw)


# -- the served path against the reference -----------------------------------


@pytest.mark.parametrize("packed", [False, True], ids=["padded", "packed"])
def test_prefill_in_chunks_then_decode_equals_the_reference(ref, model,
                                                            packed):
    """Chunks of the file's width, a prompt that ends mid-chunk (decode
    taking over from there), contexts past three windows, then decode
    through the three caches: every row that comes back is the reference's
    full forward pass. Packed: a chunk group beside the other slot's decode
    row, the rows cut to one a slot at the seam; padded: one group, every
    row through every layer."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(5)
    toks = [rng.integers(0, 128, (50,)), rng.integers(0, 128, (44,))]
    if packed:
        # Slot 1 prefills alone, then decodes while slot 0 takes chunks.
        steps = [[0, 16], [0, 13], [16, 1], [16, 1], [5, 1]] \
            + [[1, 1]] * 12 + [[1, 0]]
    else:
        steps = [[16, 16], [16, 13], [5, 1]] + [[1, 1]] * 13
    got, cache, stats = _serve_rows(params, tcfg, toks, steps, packed=packed)
    compared = 0
    for i in range(2):
        want = _want(ref, w, weights, toks[i])
        for row, lg in got[i]:
            np.testing.assert_allclose(lg, want[row], atol=ATOL)
            compared += 1
    assert compared == (sum(sum(n > 0 for n in ns) for ns in steps)
                        if packed else sum(map(sum, steps)))
    assert np.std(_want(ref, w, weights, toks[0])) > 0.3
    for ns, st in zip(steps, stats):
        assert int(st["ssm_states"]) == 4 * sum(n > 0 for n in ns)
    assert [int(n) for n in cache.length] == [sum(s[0] for s in steps),
                                              sum(s[1] for s in steps)]


def test_the_four_controls_and_int8_differ_from_the_sound_reference(
        ref, model):
    w, weights, _, _ = model
    toks = np.random.default_rng(11).integers(0, 128, (40,))
    sound = _want(ref, w, weights, toks)
    assert set(ref.CONTROLS) == {"int8", "no_diff", "own_rows",
                                 "stale_memory", "scalar_decay"}
    for control in ref.CONTROLS:
        other = _want(ref, w, weights, toks, quant=control)
        assert np.abs(other - sound)[12:].max() > 50 * ATOL, control


def test_a_wrong_index_at_the_seam_shows(ref, model, monkeypatch):
    """The memory gathered by a chunk member's FIRST row instead of its
    last valid one, and a cross row attending from ``length`` instead of its
    own position: each moves a packed tick's logits far past ``ATOL``."""
    w, weights, tcfg, params = model
    toks = [np.random.default_rng(2).integers(0, 128, (30,)),
            np.random.default_rng(3).integers(0, 128, (30,))]
    want = _want(ref, w, weights, toks[0])
    steps = [[16, 0], [9, 0]]
    sound, _, _ = _serve_rows(params, tcfg, toks, steps, packed=True)
    np.testing.assert_allclose(sound[0][-1][1], want[24], atol=ATOL)
    real = decode._step_layers

    def first_row(*a, cut=None, **kw):
        return real(*a, cut=(cut[0] * 0, cut[1]), **kw)

    def from_length(*a, cut=None, **kw):
        g = cut[1]
        return real(*a, cut=(cut[0], g._replace(start=g.start * 0
                                                + g.start.min())), **kw)

    for broken in (first_row, from_length):
        monkeypatch.setattr(decode, "_step_layers", broken)
        jax.clear_caches()
        got, _, _ = _serve_rows(params, tcfg, toks, steps, packed=True)
        assert np.abs(got[0][-1][1] - want[24]).max() > 50 * ATOL
    monkeypatch.undo()
    jax.clear_caches()


# -- differential attention on packed rows -----------------------------------


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
def test_the_packed_differential_attention_equals_its_definition(ref, model,
                                                                 window):
    """Both halves of a pair, both pairs of a value pair: the packed query
    (its 16 values in ITS half, zeros beside them) against packed rows, all
    32 lanes of the output kept, then ``diff_tail``; against the two
    softmaxes of the definition. (A cross layer is the full case over
    another layer's ``k, v``: the served-path test holds it.)"""
    w, _, tcfg, _ = model
    T, H, Hkv, d, D = 24, 8, 4, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    h = jax.random.normal(ks[0], (T, D))
    p = {"w_qkv": 0.1 * jax.random.normal(ks[1], (D, (H + 2 * Hkv) * d)),
         "b_qkv": 0.1 * jax.random.normal(ks[2], ((H + 2 * Hkv) * d,)),
         "w_o": 0.1 * jax.random.normal(ks[3], (H * d, D)),
         "b_o": 0.1 * jax.random.normal(ks[4], (D,)),
         "lam": 0.5 * jax.random.normal(ks[5], (4, d)),
         "sub_g": 1.5 + 0.1 * jax.random.normal(ks[6], (2 * d,))}
    l = 5
    k, v = ref.keys_values(h, p, w=w)
    want = ref.differential(h, k, v, p, jnp.int32(l), w=w, window=window)
    # The program's pieces.
    qkv = h @ p["w_qkv"] + p["b_qkv"]
    q = qkv[:, :H * d].reshape(1, T, H, d).transpose(0, 2, 1, 3)
    kk = k.reshape(1, T, Hkv, d).transpose(0, 2, 1, 3)
    vv = v.reshape(1, T, Hkv, d).transpose(0, 2, 1, 3)
    qp, kp, vp = decode._pack_heads(q, kk, vv, tcfg)
    assert qp.shape == (1, H, T, 2 * d) and kp.shape == (1, Hkv // 2, T, 2 * d)
    # Query head h reads packed head h // 4; its values lie in half h % 2.
    assert float(jnp.abs(qp[0, 2, :, d:]).max()) == 0.0
    assert float(jnp.abs(qp[0, 3, :, :d]).max()) == 0.0
    s = jnp.einsum("bhtd,bhsd->bhts", qp, jnp.repeat(kp, 4, axis=1)) \
        * d ** -0.5
    t = jnp.arange(T)
    see = t[None, :] <= t[:, None]
    if window is not None:
        see &= t[None, :] > t[:, None] - window
    pr = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bhsd->bhtd", pr, jnp.repeat(vp, 4, axis=1))
    layer = {"lam": p["lam"], "sub_ln": p["sub_g"]}
    o = hybrid.diff_tail(out, layer, l, tcfg)               # (1, H/2, T, 2d)
    got = o[0].transpose(1, 0, 2).reshape(T, H * d) @ p["w_o"] + p["b_o"]
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the kernel ----------------------------------------------------------------


@pytest.mark.parametrize("tq, members", [(1, 5), (24, 3)],
                         ids=["decode", "chunk"])
def test_ssm1_scan_in_interpret_mode_is_the_token_wise_recurrence(tq,
                                                                  members):
    """At a row a slot and at a chunk; a row past ``n_valid``, a slot with
    no row and a non-member are left bit for bit; a fresh member starts from
    zeros."""
    from tree_attention_tpu.ops.pallas_ssm import ssm1_scan

    N, Ch, S, layers = 8, 256, 6, 2
    ks = jax.random.split(jax.random.PRNGKey(tq), 6)
    pool = jax.random.normal(ks[0], (layers * S, N, Ch))
    x = jax.random.normal(ks[1], (members, tq, Ch))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (members, tq, Ch)) - 2)
    A = -jnp.exp(0.3 * jax.random.normal(ks[3], (N, Ch)))
    B = jax.random.normal(ks[4], (members, tq, N))
    C = jax.random.normal(ks[5], (members, tq, N))
    n_valid = jnp.asarray([tq, 0, max(tq - 5, 1), tq, 1][:members], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False][:members])
    home = S + jnp.asarray([3, 1, 0, 4, 2][:members], jnp.int32)
    valid = jnp.arange(tq)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    new, y = ssm1_scan(pool, x, dt, A, B, C, home, n_valid, fresh,
                       interpret=True)
    s0 = jnp.where(fresh[:, None, None], 0.0, pool[home])
    want_y, s1 = hybrid.ssm1_rows(s0, x, dt, A, B, C)
    want = pool.at[jnp.where(n_valid > 0, home, pool.shape[0])].set(
        s1, mode="drop")
    np.testing.assert_allclose(new, want, atol=1e-6)
    np.testing.assert_allclose(
        y, jnp.where(valid[..., None], want_y, 0.0), atol=1e-5)
    # The other layer's states, the non-member slot 5 and the member with
    # no row (slot 1): bit for bit.
    untouched = np.r_[0:S, S + 1, S + 5]
    assert np.array_equal(np.asarray(new)[untouched],
                          np.asarray(pool)[untouched])
    # Whatever lies in a row past a member's valid count (a step size of
    # one, an input of 99), its state comes out bit for bit: such a row is
    # never computed.
    junk = ~valid[..., None]
    again, _ = ssm1_scan(pool, jnp.where(junk, 99.0, x),
                         jnp.where(junk, 1.0, dt), A, B, C, home, n_valid,
                         fresh, interpret=True)
    assert np.array_equal(np.asarray(again), np.asarray(new))


# -- the engine ----------------------------------------------------------------


def _engine(model, slots=2, cache_len=64):
    _, _, tcfg, params = model
    return SlotServer(
        params, tcfg, slots=slots, cache_len=cache_len, prefill_chunk=WIDTH,
        kv_block=BLOCK, prefix_block=BLOCK)


def _greedy(ref, weights, w, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        row = _want(ref, w, weights, toks, [len(toks) - 1])
        toks.append(int(row[0].argmax()))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def served(ref, model):
    """One engine of ONE slot serving a long request and then a short one
    in the slot it leaves (no stale state, window row or shared row), with
    the flight recorder armed: (requests, their tokens, the records, the
    report)."""
    w, weights, _, _ = model
    rng = np.random.default_rng(9)
    reqs = [Request(uid=0, prompt=rng.integers(0, 128, (37,)).tolist(),
                    max_new_tokens=14),
            Request(uid=1, prompt=rng.integers(0, 128, (6,)).tolist(),
                    max_new_tokens=10)]
    server = _engine(model, slots=1)
    FLIGHT.clear()
    FLIGHT.arm(capacity=4096)
    try:
        report = server.serve(reqs)
        recs = [dict(r) for r in FLIGHT.snapshot()["records"]]
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
    return reqs, {r.uid: list(r.tokens) for r in report.results}, recs, report


def test_a_reused_slot_serves_the_reference_tokens(ref, model, served):
    w, weights, _, _ = model
    reqs, tokens, _, report = served
    for r in reqs:
        assert tokens[r.uid] == _greedy(ref, weights, w, r.prompt,
                                        r.max_new_tokens), r.uid
    assert report.kv["blocks_used"] == 0
    assert report.kv["window_blocks_used"] == 0


def test_blocks_behind_the_window_are_given_back(served):
    """A context of 51 positions past a window of 8: the window layers hold
    a bounded number of blocks a slot, the shared layer every block."""
    _, _, recs, report = served
    kv = report.kv
    assert kv["window_blocks_freed"] > 0
    assert kv["window_blocks_peak_slot"] <= kv["window_blocks_bound"] \
        == -(-(8 + WIDTH) // BLOCK) + 1
    assert kv["peak_blocks_used"] == -(-51 // BLOCK)
    held = [r["window_blocks_held"] for r in recs
            if "window_blocks_held" in r]
    assert held and max(held) <= kv["window_blocks_bound"]


def test_the_ticks_counters_are_what_a_hand_count_gives(served):
    _, _, recs, _ = served
    ticks = [r for r in recs if "rows_self" in r]
    mixed = [r for r in ticks if r["chunk_tokens"]]
    dec = [r for r in ticks if not r["chunk_tokens"] and r["occupancy"]]
    assert mixed and dec
    for r in mixed:
        # One chunk member of the bucket's rows beside one slot's row; one
        # row a slot above the seam.
        assert r["rows_self"] == r["tq"] + 1 and r["rows_cross"] == 1
    for r in dec:
        assert r["rows_self"] == r["rows_cross"] == 1
        # Four Mamba-1 layers a live slot; the shared layer and two cross
        # layers read the shared rows.
        assert r["ssm_states_advanced"] == 4 * r["occupancy"]
        assert r["shared_kv_calls"] == 3 * r["occupancy"]
    # The prompt of 37 went through in chunks of 16, 16 and 5 (bucket 8).
    assert [r["chunk_tokens"] for r in mixed][:3] == [16, 16, 5]


def test_what_the_state_window_pool_does_not_serve_is_refused(model):
    _, _, tcfg, params = model
    for kw, named in ((dict(prefix_cache=True), "prefix cache"),
                      (dict(quantize=True), "int8"),
                      (dict(speculate=True, draft_k=2), "speculation"),
                      (dict(host_blocks=4, prefix_cache=True), "host tier")):
        with pytest.raises(ValueError, match="state_window"):
            SlotServer(params, tcfg, slots=1, cache_len=32,
                       prefill_chunk=WIDTH, kv_block=BLOCK,
                       prefix_block=BLOCK, **kw)
        assert named


# -- the older families' programs --------------------------------------------

# sha256[:16] of the tiny presets' step programs as the parent commit
# (cd146df) lowered them (``jax.jit(...).lower(...).as_text()``): the layer
# loop was cut into periods and gained a carry for this family, and every
# default leaves the others' programs text for text what they were.
OLDER = {
    "test_hybrid_conv": "SMALL", "test_window_moe": "SMALL",
    "test_state_space": "SMALL", "test_eva": "SMALL",
    "test_parallel_mixer": "SMALL",
}
STEP_TEXT = {
    ("test_eva", False): "23f6c60cca99921f",
    ("test_eva", True): "e53c9594d35bbdc6",
    ("test_hybrid_conv", False): "7fea32ecf54f1270",
    ("test_hybrid_conv", True): "19ca121458f22c0f",
    ("test_parallel_mixer", False): "0e88abfeda4e2080",
    ("test_parallel_mixer", True): "5017aa0f0252b684",
    ("test_state_space", False): "acf4c8c785b4f6e0",
    ("test_state_space", True): "260bedfd51318f4a",
    ("test_window_moe", False): "271ed4a9d188c5d2",
    ("test_window_moe", True): "cff2b81a190cd7fe",
}


def _step_text(module, packed):
    mod = _load(os.path.join(ROOT, "tests", module + ".py"), "_older_" + module)
    tcfg = model_from_config(getattr(mod, OLDER[module]), max_seq_len=64)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), tcfg))
    extra = {"window_blocks": 16} if tcfg.cache_kind in ("window", "eva") \
        else {}
    cache = jax.eval_shape(lambda: init_paged_cache(
        tcfg, 2, 32, 16, block=8 if tcfg.cache_kind == "eva" else 4, **extra))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if packed:
        fn = lambda p, c, ch, m, t: decode.forward_packed_step(  # noqa: E731
            p, ch, m, m, t, t, c, tcfg)
        args = (params, cache, i32(1, 16), i32(1), i32(2))
    else:
        fn = lambda p, c, t, n: decode.forward_step(  # noqa: E731
            p, t, c, tcfg, n_tokens=n)
        args = (params, cache, i32(2, 1), i32(2))
    text = jax.jit(fn).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("packed", [False, True], ids=["decode", "packed"])
@pytest.mark.parametrize("module", sorted(OLDER))
def test_the_older_families_presets_lower_to_the_text_they_did(module,
                                                               packed):
    assert _step_text(module, packed) == STEP_TEXT[module, packed]
