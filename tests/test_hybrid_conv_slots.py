"""The second half of ``tests/test_hybrid_conv.py`` (its docstring has the
tolerances): slots at different positions and reused slots, the published
24-layer pattern, the cache's bytes, the engine. A file of its own so that two
workers share what was the slowest file of a ``--dist loadfile`` run (PR 43);
the presets, helpers and fixtures are the first half's.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_attention_tpu import obs
from tree_attention_tpu.models.decode import (
    PagedHybridCache,
    cache_block_fixed_bytes,
    cache_token_bytes,
    forward_step,
    init_paged_cache,
)
from tree_attention_tpu.models.hybrid import layer_runs
from tree_attention_tpu.models.transformer import model_from_config
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.serving import SlotServer
from tree_attention_tpu.serving.engine import Request

from tests.jitted import packed_step
from tests.test_hybrid_conv import (  # noqa: F401  (fixtures by name)
    ATOL,
    BLOCK,
    ROOT,
    SMALL,
    _cache,
    _model,
    _run,
    _want,
    adapter,
    model,
    ref,
)


# -- logits against the reference, through the cache (continued) -------------


def test_a_slot_reused_after_a_longer_request_needs_no_reset(ref, model):
    w, weights, tcfg, params = model
    rng = np.random.default_rng(9)
    long, short = rng.integers(0, 128, (37,)), rng.integers(0, 128, (12,))
    cache = _cache(tcfg, 1)
    _, cache = _run(params, tcfg, cache, [long], [[16], [16], [5]])
    cache = dataclasses.replace(cache, length=jnp.zeros((1,), jnp.int32))
    got, _ = _run(params, tcfg, cache, [short], [[7]] + [[1]] * 5)
    np.testing.assert_allclose(got[0], _want(ref, w, weights, short),
                               atol=ATOL)


def test_two_slots_at_different_positions_in_one_packed_tick(ref, model):
    """The tick with a prompt chunk: slot 2 takes rows 9..20 of its prompt
    (across a boundary) in the chunk group while slots 0 and 1 decode at
    positions 16 and 10; slot 2's decode row is inert."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(4)
    toks = rng.integers(0, 128, (3, 24))
    cache = _cache(tcfg, 3)
    _, cache = _run(params, tcfg, cache, toks, [[16, 10, 9]])
    chunk = np.zeros((1, 16), np.int32)
    chunk[0, :12] = toks[2, 9:21]
    logits, cache = packed_step(
        params, jnp.asarray(chunk), jnp.asarray([2], jnp.int32),
        jnp.asarray([12], jnp.int32),
        jnp.asarray([toks[0, 16], toks[1, 10], 0], jnp.int32),
        jnp.asarray([1, 1, 0], jnp.int32), cache, tcfg)
    assert [int(x) for x in cache.length] == [17, 11, 21]
    for i, at in enumerate([16, 10, 20]):
        np.testing.assert_allclose(
            logits[i], _want(ref, w, weights, toks[i, :at + 1], [at])[0],
            atol=ATOL)
    # And the tails it left serve the next decode tick of all three.
    got, _ = _run(params, tcfg, cache, toks, [[1, 1, 1]])
    for i, at in enumerate([17, 11, 21]):
        np.testing.assert_allclose(
            got[i][0], _want(ref, w, weights, toks[i, :at + 1], [at])[0],
            atol=ATOL)


def test_the_published_24_layer_pattern_is_expressible(ref, adapter):
    """All 24 ``layer_types`` as published, irregular end included (``... A
    c c A c c``), at a tiny width: 13 runs, and the reference's logits."""
    with open(f"{ROOT}/benchmark/configs/lfm2-8b-a1b.json") as f:
        types = json.load(f)["published"]["layer_types"]
    assert len(types) == 24 and types[-6:] == [
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
    config = dict(SMALL, num_hidden_layers=24, layer_types=types,
                  hidden_size=32, intermediate_size=64, num_attention_heads=2,
                  num_key_value_heads=1, moe_intermediate_size=16)
    w, weights, tcfg, params = _model(ref, adapter, config)
    runs = layer_runs(tcfg)
    assert len(runs) == 13 and sum(r[2] for r in runs) == 24
    assert (tcfg.cache_layers, tcfg.conv_layers) == (6, 18)
    toks = np.random.default_rng(1).integers(0, 128, (1, 14))
    got, _ = _run(params, tcfg, _cache(tcfg, 1), toks, [[9]] + [[1]] * 5)
    np.testing.assert_allclose(got[0], _want(ref, w, weights, toks[0]),
                               atol=ATOL)


def test_experts_under_rotary_gqa_without_a_conv_layer(ref, adapter):
    """Every layer attention: the hybrid pool's tail has depth 0."""
    config = dict(SMALL, num_hidden_layers=3,
                  layer_types=["full_attention"] * 3, num_dense_layers=1)
    w, weights, tcfg, params = _model(ref, adapter, config)
    cache = _cache(tcfg, 1)
    assert cache.tail.shape == (0, 8, 128) and cache.k.shape[0] == 3
    toks = np.random.default_rng(2).integers(0, 128, (1, 14))
    stats = {}      # the counters' shapes need no run: traced, not computed
    jax.eval_shape(lambda: forward_step(
        params, jnp.asarray(toks[:, :4]), cache, tcfg, stats=stats))
    assert stats["expert_rows"].shape == (2, 9) and "tail_blocks" not in stats
    got, _ = _run(params, tcfg, cache, toks, [[9]] + [[1]] * 5)
    np.testing.assert_allclose(got[0], _want(ref, w, weights, toks[0]),
                               atol=ATOL)


def test_the_qk_norms_gain_is_not_one(ref, model):
    w, weights, tcfg, params = model
    assert float(jnp.abs(params["attn"]["q_ln"] - 1).min()) > 0.05
    toks = np.random.default_rng(6).integers(0, 128, (1, 12))
    got, _ = _run(params, tcfg, _cache(tcfg, 1), toks, [[12]])
    want = _want(ref, w, weights, toks[0])
    np.testing.assert_allclose(got[0], want, atol=ATOL)
    ones = dict(params, attn=dict(
        params["attn"], q_ln=jnp.ones_like(params["attn"]["q_ln"])))
    off, _ = _run(ones, tcfg, _cache(tcfg, 1), toks, [[12]])
    assert np.abs(off[0] - want).max() > 1e-3


# -- the cache ---------------------------------------------------------------


def test_a_blocks_bytes_at_the_published_widths():
    with open(f"{ROOT}/benchmark/configs/lfm2-8b-a1b.json") as f:
        tcfg = model_from_config(json.load(f))
    cache = jax.eval_shape(
        lambda: init_paged_cache(tcfg, 2, 128, 4, block=64))
    assert isinstance(cache, PagedHybridCache)
    # Two KV heads of 64 side by side on a row's 128 lanes; a block's two
    # tail rows side by side likewise.
    assert tcfg.kv_pack == 2
    assert cache.k.shape == cache.v.shape == (3, 4, 4, 64, 128)
    assert cache.tail.shape == (9, 4, 4096)
    assert cache_token_bytes(cache) == 6144            # 3 x 2 x 8 x 64 x 2 B
    assert cache_block_fixed_bytes(cache) == 73728     # 9 x 2 x 2048 x 2 B
    # 7,296 B a token at blocks of 64.
    assert cache_token_bytes(cache) + cache_block_fixed_bytes(cache) / 64 \
        == 7296


def test_the_cache_kind_and_the_model_go_together(model):
    _, _, tcfg, params = model
    dense = dataclasses.replace(tcfg, moe=None, layer_types=None)
    wrong = init_paged_cache(dense, 1, 16, 2, block=8)
    with pytest.raises(ValueError, match="caches 'hybrid' state"):
        forward_step(params, jnp.zeros((1, 1), jnp.int32), wrong, tcfg)
    with pytest.raises(ValueError, match="int8 rows beside conv tails"):
        init_paged_cache(tcfg, 1, 16, 2, block=8, quantize=True)


# -- the engine --------------------------------------------------------------


def _engine(tcfg, params, **kw):
    args = dict(slots=3, cache_len=96, prefill_chunk=16, prefix_cache=True,
                prefix_block=BLOCK)
    args.update(kw)
    return SlotServer(params, tcfg, **args)


def _greedy(ref, weights, w, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        row = ref.logits_at(weights, w, np.asarray(toks),
                            np.asarray([len(toks) - 1]), pad_to=64)
        toks.append(int(row[0].argmax()))
    return toks[len(prompt):]


def test_the_engine_serves_a_prefix_hit_a_fork_and_a_reused_slot(ref, model):
    """Through ``SlotServer.serve``: a cold request; after it retired, a
    request with the same 19 first tokens (a hit of two whole blocks, in
    another slot's table); a family of two forked at the prompt's end
    (inside a block); every token the reference's greedy choice, the tick's
    counters in the flight record, nothing leaked."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(8)
    shared = rng.integers(0, 128, (19,)).tolist()
    p0 = shared + rng.integers(0, 128, (6,)).tolist()
    p1 = shared + rng.integers(0, 128, (9,)).tolist()
    FLIGHT.clear()
    FLIGHT.arm(capacity=4096)
    obs.REGISTRY.enable()
    try:
        eng = _engine(tcfg, params)
        r0 = eng.serve([Request(uid=0, prompt=p0, max_new_tokens=6)])
        r1 = eng.serve([Request(uid=1, prompt=p1, max_new_tokens=6),
                        Request(uid=2, prompt=p0[:21], max_new_tokens=5,
                                n=2)])
        recs = [r for r in FLIGHT.snapshot()["records"] if "conv_rows" in r]
        text = obs.REGISTRY.to_prometheus()
        # What a tick on a TPU counts (the tails' path steered to the row
        # kernel's): one row a slot x 5 conv layers by it, a chunk's by
        # the block path; a padded chunk tick takes the block path whole.
        from tree_attention_tpu.serving import engine as engine_mod
        steered = pytest.MonkeyPatch()
        steered.setattr(engine_mod, "tail_write_path",
                        lambda tq, tail: "row" if tq == 1 else "block")
        try:
            n_vec = np.asarray([1, 0, 1])
            assert eng._count_tail_rows(1, n_vec, None) == 2
            assert eng._count_tail_rows(16, n_vec, ([0], [9])) == 2
            assert eng._count_tail_rows(16, n_vec, None) == 0
            eng._account_step_counters(
                np.asarray([0] * eng._expert_rows_shape[0]
                           * eng._expert_rows_shape[1] + [20]), tail_rows=2)
            steered_text = obs.REGISTRY.to_prometheus()
        finally:
            steered.undo()
        assert eng._count_tail_rows(1, n_vec, None) == 0       # a CPU
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()
    assert r0.results[0].tokens == _greedy(ref, weights, w, p0, 6)
    by_uid = {}
    for r in r1.results:
        by_uid.setdefault(r.uid, []).append(r)
    assert by_uid[1][0].tokens == _greedy(ref, weights, w, p1, 6)
    assert by_uid[1][0].prefix_hit_tokens == 16
    assert len(by_uid[2]) == 2                       # both branches, greedy
    for r in by_uid[2]:
        assert r.tokens == _greedy(ref, weights, w, p0[:21], 5)
    assert r1.kv["forks"] == 1
    assert r1.kv["token_bytes"] == 2 * 2 * 2 * 16 * 4     # 2 layers' K and V
    assert r1.kv["block_fixed_bytes"] == 5 * 2 * 64 * 4   # 5 layers' tails
    leak = eng.leak_report()
    assert leak["blocks_used"] == leak["blocks_cached"]
    # The flight record: rows x 5 conv layers; a tick's rows fall in at
    # least one block each member, 5 tails a block.
    assert recs and all(r["conv_rows"] == 5 * r["rows_useful"] for r in recs)
    assert all(r["tail_blocks_written"] % 5 == 0 for r in recs)
    # p0's second chunk (the first a tick fetches: one that emits): rows
    # 16..24, two blocks; then a decode row a tick, one block.
    assert [(r["rows_useful"], r["tail_blocks_written"]) for r in recs[:2]] \
        == [(9, 10), (1, 5)]
    assert all("experts_touched" in r for r in recs)
    assert "serving_cache_block_fixed_bytes 2560" in text
    wrote = sum(r["tail_blocks_written"] for r in recs)
    # ... all by the block path: off the TPU no tick takes the row kernel.
    by = 'serving_conv_tail_blocks_written_total{path="%s"} '
    assert by % "block" + str(wrote) in text \
        or by % "block" + str(float(wrote)) in text
    assert by % "row" + "0" in text
    assert by % "row" + "10" in steered_text \
        and (by % "block" + str(wrote + 10) in steered_text
             or by % "block" + str(float(wrote + 10)) in steered_text)


@pytest.mark.parametrize("kw, named", [
    (dict(quantize=True), "int8 hybrid rows"),
    (dict(kv_shard="seq"), "sequence-sharded"),
    (dict(host_blocks=4), "host tier"),
    (dict(speculate=True), "cannot roll back"),
])
def test_engine_refuses_what_the_hybrid_pool_does_not_carry(model, kw, named):
    _, _, tcfg, params = model
    with pytest.raises(ValueError, match=named):
        _engine(tcfg, params, **kw)


@pytest.mark.parametrize("flags, named", [
    (["--kv-quant", "int8"], "hybrid pool is not served with --kv-quant"),
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


def test_model_config_serves_the_hybrid_on_its_own_weights(tmp_path):
    """``--model-config`` with this family's keys: the program draws a
    stack a kind itself (no ``wout``: the head is tied) and serves through
    ``SlotServer`` with a prefix hit."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    path = tmp_path / "model.json"
    path.write_text(json.dumps(SMALL))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "24", "--max-new-tokens", "4",
                      "--dtype", "float32", "--prefix-cache",
                      "--prefix-block", "8", "--model-config", str(path)])
    setup = cli.build_serve_engine(cfg, None)
    p = setup.params
    assert "wout" not in p and p["conv"]["w_conv"].shape == (5, 3, 64)
    assert p["attn"]["q_ln"].shape == (2, 16)
    assert p["layers"]["router_bias"].dtype == jnp.float32
    assert p["dense"]["w1"].shape[0] == 2 and p["layers"]["we1"].shape[:2] \
        == (5, 8)
    eng = setup.make_engine()
    assert eng.cache.tail.shape[0] == 5 and eng.cache.k.shape[0] == 2
    prompt = list(range(1, 22))
    eng.serve([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    rep = eng.serve([Request(uid=1, prompt=prompt[:17] + [5, 6],
                             max_new_tokens=4)])
    assert len(rep.results[0].tokens) == 4
    assert rep.results[0].prefix_hit_tokens == 16
    leak = eng.leak_report()
    assert leak["blocks_used"] == leak["blocks_cached"]
