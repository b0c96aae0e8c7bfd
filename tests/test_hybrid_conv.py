"""Layers of several kinds in one model: gated short-convolution mixers whose
state is a two-row tail of each pool block, beside rotary-GQA layers with
QK-norm, under a sigmoid router with a correction bias — held against the
benchmark's plain reference (``benchmark/references/lfm2_moe.py``: the full
forward pass over one sequence, no cache) at a small size, on the CPU, in
float32, with seeded weights.

Tolerances. Logits here have a standard deviation of ~0.2 (an embedding at
std 0.02 under a tied head). The program and the reference add the same
float32 numbers in other orders (the convolution as shifts inside a chunk
against a shifted sum over the sequence, attention block by block against one
softmax over the row, the experts' sum over sorted pairs against a loop over
experts): their logits agree to 2e-6 and are held to 2e-5. What a test shows
to be DIFFERENT (a dropped gain, a stale tail) differs by 1e-3 or more.
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

from tree_attention_tpu.models import experts
from tree_attention_tpu.models.decode import (
    copy_pool_block,
    forward_step,
    init_paged_cache,
)
from tree_attention_tpu.models.hybrid import layer_runs
from tree_attention_tpu.models.transformer import (
    init_params,
    model_from_config,
)

from tests.jitted import serve_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5
BLOCK = 8
# The one chunk width the step helpers compile: three blocks, the widest
# step any case takes (a first chunk of 19 rows).
WIDTH = 24

# The family's published keys at a small size: the period ``c c A c`` and
# the irregular end (``... A c A``), 2 leading dense FFNs, 8 experts top 2.
SMALL = {
    "family": "lfm2_moe", "model_type": "lfm2_moe", "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 7,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "full_attention", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 128,
    "torch_dtype": "float32", "tie_word_embeddings": True,
    "block": {"qk_norm": True, "router_scoring": "sigmoid"},
    "deployment": {"experts_total": 8, "expert_share": 0},
    "assumed": {"seeded_scales": {
        "embedding_std": 0.02, "conv_out_std": 0.02, "attn_out_std": 0.03,
        "dense_down_std": 0.02, "expert_down_std": 0.06,
        "qk_gain_mean": 1.5, "qk_gain_std": 0.1, "router_bias_std": 0.05}},
}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "benchmark", "references", "lfm2_moe.py"),
                 "_references_lfm2_moe")


@pytest.fixture(scope="module")
def adapter():
    return _load(os.path.join(ROOT, "benchmark", "adapters", "lfm2_moe.py"),
                 "_adapters_lfm2_moe")


@pytest.fixture(scope="module")
def model(ref, adapter):
    """(widths, reference weights, TransformerConfig, engine params)."""
    return _model(ref, adapter, SMALL)


def _model(ref, adapter, config, seed=7):
    w = ref.Widths.of(config)
    weights = ref.init_weights(seed, w)
    tcfg = model_from_config(config, max_seq_len=128)
    return w, weights, tcfg, adapter.engine_params(weights, w)


def _want(ref, w, weights, toks, rows=None):
    rows = np.arange(len(toks)) if rows is None else np.asarray(rows)
    return ref.logits_at(weights, w, np.asarray(toks), rows, pad_to=32)


def _cache(tcfg, slots, nb=8):
    """A pool of ``slots x nb`` blocks of 8 under a scrambled table."""
    cache = init_paged_cache(tcfg, slots, nb * BLOCK, slots * nb, block=BLOCK)
    table = jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb)[:, ::-1]
    return dataclasses.replace(cache, table=table)


def _run(params, tcfg, cache, toks, steps):
    """``steps``: per step one row count a slot; the logits of the rows
    that carried a token, per slot, and the cache. A step's token block is
    ``WIDTH`` wide (or one row, the decode step) whatever its counts: two
    compiled programs a model (``tests/jitted.py``), the true counts in
    ``n_tokens`` as a tick carries them."""
    B = len(toks)
    got, pos = [[] for _ in range(B)], [int(x) for x in cache.length]
    for ns in steps:
        rows, cache = serve_step(params, tcfg, cache, toks, pos, ns, WIDTH)
        for i, _, lg in rows:
            got[i].append(lg)
        for i, n in enumerate(ns):
            pos[i] += n
    return [np.stack(g) if g else np.zeros((0, tcfg.vocab_size), np.float32)
            for g in got], cache


# -- the model as data -------------------------------------------------------


def test_the_published_keys_say_what_each_layer_is():
    with open(f"{ROOT}/benchmark/configs/lfm2-8b-a1b.json") as f:
        c = json.load(f)
    t = model_from_config(c)
    assert (t.n_layers, t.cache_layers, t.conv_layers) == (12, 3, 9)
    assert t.layer_types == ("conv", "conv", "attention", "conv") * 3
    assert (t.d_model, t.d_ff, t.n_heads, t.n_kv_heads, t.d_head,
            t.vocab_size) == (2048, 7168, 32, 8, 64, 65536)
    assert (t.conv_taps, t.qk_norm, t.tied_head, t.cache_kind) == (
        3, True, True, "hybrid")
    assert t.rope_theta == 1e6 and t.norm_eps == 1e-5 and t.mla is None
    ex = t.moe
    assert (ex.n_experts, ex.held, ex.held_first, ex.per_token, ex.width,
            ex.first_dense) == (32, 32, 0, 4, 1792, 2)
    assert (ex.scoring, ex.corrected, ex.renorm, ex.scale) == (
        "sigmoid", True, True, 1.0)
    assert (ex.shared_width, ex.n_groups, ex.n_zero, ex.branch) == (
        0, 1, 0, None)
    # The only keys cut are the depth's; every published number is there.
    assert c["reduced"] == ["num_hidden_layers", "layer_types"]
    assert c["published"]["layer_types"][:12] == c["layer_types"]
    assert not t.dense_block and t.n_dense_layers == 2
    assert [r[:3] for r in layer_runs(t)] == [
        ("conv", "dense", 2), ("attention", "expert", 1),
        ("conv", "expert", 3), ("attention", "expert", 1),
        ("conv", "expert", 3), ("attention", "expert", 1),
        ("conv", "expert", 1)]


@pytest.mark.parametrize("name", ["yi-6b", "mistral-7b-v0.3", "deepseek-v2",
                                  "longcat-flash-omni"])
def test_the_other_configurations_are_built_as_before(name):
    """Every new field at its default: the four accepted configurations'
    models compare equal to ones made without them, and cache what they
    cached."""
    with open(f"{ROOT}/benchmark/configs/{name}.json") as f:
        c = json.load(f)
    if c["family"] == "llama_dense":
        c = dict(c, head_dim=c["assumed"]["head_dim"])
    t = model_from_config(c)
    assert t == dataclasses.replace(
        t, layer_types=None, conv_taps=3, qk_norm=False, tied_head=False)
    assert t.conv_layers == 0
    assert t.cache_layers == t.n_layers * t.sublayers
    if t.moe is not None:
        assert t.moe == dataclasses.replace(t.moe, scoring="softmax")
    assert t.cache_kind == ("kv" if c["family"] == "llama_dense"
                            else "latent")
    assert t.dense_block == (c["family"] == "llama_dense")


def test_experts_under_a_key_the_reader_does_not_know_are_refused():
    """A file with ``num_experts`` and no ``n_routed_experts`` built a
    dense Llama model in silence before this family's keys were read; a
    file that names its experts under yet another key is refused by that
    key's name."""
    dense_keys = {k: SMALL[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "vocab_size")}
    assert model_from_config(dense_keys).dense_block
    with pytest.raises(ValueError, match="num_local_experts"):
        model_from_config(dict(dense_keys, num_local_experts=8,
                               num_experts_per_tok=2))
    t = model_from_config(dict(dense_keys, num_experts=8,
                               num_experts_per_tok=2,
                               moe_intermediate_size=32))
    assert t.moe is not None and t.moe.held == 8 and not t.dense_block
    assert t.cache_kind == "hybrid" and t.conv_layers == 0


@pytest.mark.parametrize("change, named", [
    ({"conv_bias": True}, "bias"),
    ({"layer_types": ["conv"] * 6 + ["linear_attention"]},
     "linear_attention"),
    ({"layer_types": ["conv"] * 6 + ["sliding_attention"],
      "sliding_window": 8}, "beside conv layers"),
    ({"layer_types": ["conv"] * 6}, "layer_types names 6 layers"),
    ({"conv_L_cache": 4}, "4 taps"),
    ({"block": {"router_scoring": "tanh"}}, "tanh"),
    ({"kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
      "v_head_dim": 8}, "several kinds"),
    ({"topk_method": "group_limited_greedy", "n_group": 2, "topk_group": 1},
     "corrected choice inside routing groups"),
])
def test_what_the_data_cannot_say_is_refused(change, named):
    with pytest.raises(ValueError, match=named):
        model_from_config(dict(SMALL, **change))


# -- the router --------------------------------------------------------------


def test_sigmoid_router_against_a_hand_written_one_on_near_ties(ref):
    """Sigmoid scores, the top 2 of scores + bias (ties to the lowest
    index), the weights the chosen uncorrected scores over their sum."""
    ex = model_from_config(SMALL).moe
    w = ref.Widths.of(SMALL)
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(4000, 8)).astype(np.float32) * 0.9
    logits[:200] = np.round(logits[:200] * 2) / 2          # exact ties
    logits[200:400, 1] = logits[200:400, 0] + 1e-6         # near-ties
    bias = (rng.normal(size=8) * 0.05).astype(np.float32)
    bias[3] = bias[2]
    p = {"router": jnp.eye(8, dtype=jnp.float32)}
    scores = experts.router_scores(p, jnp.asarray(logits), "sigmoid")
    np.testing.assert_allclose(scores, 1 / (1 + np.exp(-logits)), rtol=1e-6)
    idx, wt = experts.route(scores, ex, jnp.asarray(bias))
    s = np.asarray(scores)
    for r in range(len(s)):
        order = sorted(range(8), key=lambda e: (-(s[r, e] + bias[e]), e))[:2]
        assert list(np.asarray(idx[r])) == order
        np.testing.assert_allclose(
            wt[r], s[r, order] / (s[r, order].sum() + 1e-20), rtol=1e-6)
    ridx, rwt = ref.route(scores, jnp.asarray(bias), w)
    np.testing.assert_array_equal(idx, ridx)
    # The published 1e-6 on the sum against the program's 1e-20: under
    # float32's own rounding of a sum of ~1.3.
    np.testing.assert_allclose(wt, rwt, rtol=2e-6)
    plain, _ = experts.route(scores, ex, jnp.zeros((8,)))
    moved = (np.sort(idx, -1) != np.sort(plain, -1)).any(-1).mean()
    assert 0.05 < moved < 0.9


def _equations(jaxpr) -> int:
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    n += _equations(j)
    return n


@pytest.mark.parametrize("preset, tq, equations", [
    ("SMALL", 1, 740), ("SMALL", 16, 740),
    ("SMALL_SC", 1, 726), ("SMALL_SC", 16, 726),
])
def test_the_softmax_families_trace_the_programs_they_did(preset, tq,
                                                          equations):
    """The scoring is a Python branch on the model: with ``softmax`` both
    latent families trace the step they traced on the commit before this
    family came (equations counted there, nested jaxprs included)."""
    from tests import test_latent_moe

    tcfg = model_from_config(getattr(test_latent_moe, preset),
                             max_seq_len=128)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), tcfg))
    cache = jax.eval_shape(lambda: init_paged_cache(tcfg, 3, 64, 24, block=8))

    def step(params, toks, cache, n):
        stats = {}
        logits, cache = forward_step(params, toks, cache, tcfg, n_tokens=n,
                                     stats=stats)
        return logits, cache, stats

    assert _equations(jax.make_jaxpr(step)(
        params, jax.ShapeDtypeStruct((3, tq), jnp.int32), cache,
        jax.ShapeDtypeStruct((3,), jnp.int32)).jaxpr) == equations


# -- logits against the reference, through the cache -------------------------


@pytest.mark.parametrize("first", [BLOCK, BLOCK + 1, BLOCK + 2, BLOCK - 1, 3])
def test_chunked_prefill_then_decode_equals_the_reference(ref, model, first):
    """A first chunk that ends at ``p % block`` in {0, 1, 2, block - 1} (and
    inside a block), a second chunk across two block boundaries, then decode
    one token a step across the next boundary: every row's logits."""
    w, weights, tcfg, params = model
    toks = np.random.default_rng(first).integers(0, 128, (2, 40))
    lens = [first + 13 + 9, first + 13 + 6]
    steps = [[first, first], [13, 13]] + [[1, 1]] * 6 + [[1, 0]] * 3
    got, cache = _run(params, tcfg, _cache(tcfg, 2), toks, steps)
    assert [int(x) for x in cache.length] == lens
    for i in range(2):
        np.testing.assert_allclose(
            got[i], _want(ref, w, weights, toks[i, :lens[i]]), atol=ATOL)


def test_a_prefix_hit_of_whole_blocks_gives_the_cold_admissions_logits(
        ref, model):
    """Slot 0 serves a prompt and retires; slot 1 then maps the prompt's two
    first blocks (a table update and a length, nothing else) and goes on
    from position 16 with another suffix: the conv state it needs is the
    published block's tail."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(3)
    a = rng.integers(0, 128, (30,))
    b = np.concatenate([a[:16], rng.integers(0, 128, (14,))])
    cache = _cache(tcfg, 2)
    _, cache = _run(params, tcfg, cache, [a, b], [[11, 0], [19, 0]])
    # The first request retires; its blocks stay (published). The second
    # slot's table names them and its length says 16.
    table = cache.table.at[1, :2].set(cache.table[0, :2])
    cache = dataclasses.replace(
        cache, table=table, length=jnp.asarray([0, 16], jnp.int32))
    got, _ = _run(params, tcfg, cache, [a, b], [[0, 9]] + [[0, 1]] * 5)
    want = _want(ref, w, weights, b)[16:30]
    np.testing.assert_allclose(got[1], want, atol=ATOL)
    # With the tails of those blocks zeroed the same rows come out wrong.
    stale = dataclasses.replace(cache, tail=jnp.zeros_like(cache.tail))
    bad, _ = _run(params, tcfg, stale, [a, b], [[0, 9]])
    assert np.abs(bad[1] - want[:9]).max() > 1e-3


@pytest.mark.parametrize("at", [11, 16, 17])
def test_a_fork_inside_a_block_carries_the_tail_with_the_copy(ref, model,
                                                              at):
    """The parent stands at ``at`` (inside a block, at a boundary, one
    past it); the child shares the full blocks, copies the partial one
    (``copy_pool_block``: K, V and tail) and both go on with tokens of their
    own."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(at)
    a = rng.integers(0, 128, (at + 8,))
    b = np.concatenate([a[:at], rng.integers(0, 128, (8,))])
    cache = _cache(tcfg, 2)
    _, cache = _run(params, tcfg, cache, [a, b], [[at, 0]])
    full = at // BLOCK
    table = cache.table.at[1, :full].set(cache.table[0, :full])
    cache = dataclasses.replace(
        cache, table=table, length=jnp.asarray([at, at], jnp.int32))
    if at % BLOCK:
        cache = copy_pool_block(cache, cache.table[0, full],
                                cache.table[1, full])
    got, _ = _run(params, tcfg, cache, [a, b], [[1, 1]] * 8)
    np.testing.assert_allclose(
        got[0], _want(ref, w, weights, a)[at:], atol=ATOL)
    np.testing.assert_allclose(
        got[1], _want(ref, w, weights, b)[at:], atol=ATOL)
