"""The layout an engine serves from (ISSUE 34).

``served_layout`` re-lays the attention input projections out-major under
names of their own (``wq`` / ``wk`` / ``wv`` as one ``wqkv_t``, a latent
layer's ``wqb`` as ``wqb_t``); ``gqa_qkv`` and ``latent_qkv`` contract over
whichever form the layer holds. What the chip's compiler makes of either form
is ``tests/test_chip_compile.py``'s; here, on the CPU in float32 at small
sizes: both forms give the same logits in every layer body at Tq 1 and in the
packed tick, the re-laying is idempotent and touches nothing else, every
front end re-lays a model once, and the gauge reads the bytes.

Tolerance. The two forms multiply the same numbers; the CPU's product adds
them in the order the operand's layout gives, so float32 logits of a standard
deviation of 0.2-1 agree to ~1e-6 and are held to the 2e-5 the step tests of
these bodies use (``tests/test_hybrid_conv.py``).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.jitted import packed_step, step
from tests.test_hybrid_conv import SMALL as SMALL_HYBRID
from tests.test_latent_moe import SMALL as SMALL_LATENT, SMALL_SC

from tree_attention_tpu import obs
from tree_attention_tpu.models.decode import (
    forward_packed_step,
    forward_step,
    init_paged_cache,
)
from tree_attention_tpu.models.transformer import (
    GQA_SERVED,
    LATENT_SERVED,
    TransformerConfig,
    forward,
    init_params,
    model_from_config,
    served_layout,
)
from tree_attention_tpu.serving import DisaggServer, SlotServer
from tree_attention_tpu.serving.engine import (
    _WEIGHTS_RELAID,
    Request,
    serving_params,
)

ATOL = 2e-5
BLOCK, NB, SLOTS = 8, 4, 3

DENSE = TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
    d_ff=128, n_layers=3, max_seq_len=64, dtype=jnp.float32,
    attn_block_size=BLOCK)

# body -> (the model, the served leaf its layers hold, stacks that hold it)
BODIES = {
    "dense": (lambda: DENSE, GQA_SERVED),
    "hybrid": (lambda: model_from_config(SMALL_HYBRID, max_seq_len=64),
               GQA_SERVED),
    "latent": (lambda: model_from_config(SMALL_LATENT, max_seq_len=64),
               LATENT_SERVED),
    "latent_double_layer": (
        lambda: model_from_config(SMALL_SC, max_seq_len=64), LATENT_SERVED),
}


def _leaves_named(tree, name):
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if getattr(path[-1], "key", None) == name]


@functools.lru_cache(maxsize=None)
def _model(body):
    make, leaf = BODIES[body]
    cfg = make()
    return cfg, init_params(jax.random.PRNGKey(3), cfg), leaf


def _cache(cfg):
    """Every slot some rows in: a scrambled table, lengths 5, 0 and 11."""
    cache = init_paged_cache(cfg, SLOTS, NB * BLOCK, SLOTS * NB, block=BLOCK)
    table = jnp.arange(SLOTS * NB, dtype=jnp.int32).reshape(SLOTS, NB)[:, ::-1]
    return dataclasses.replace(cache, table=table)


def _tick_logits(params, cfg, program, eager=False):
    """A prefill step that leaves the slots at different lengths, then the
    program under test on top of it: a decode step of every slot (``tq1``)
    or a packed tick (slot 1 takes a chunk of 6, the others a row each).
    Each step is one compiled program a (body, form) (``tests/jitted.py``),
    so the two programs' cases share the prefill's; ``eager`` dispatches
    primitive by primitive and traces anew every call."""
    rng = np.random.default_rng(11)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (SLOTS, 12)), jnp.int32)
    run, run_packed = (_eager_step, forward_packed_step) if eager \
        else (step, packed_step)
    _, cache = run(params, toks, _cache(cfg),
                   jnp.asarray([5, 0, 11], jnp.int32), cfg)
    if program == "tq1":
        logits, cache = run(params, toks[:, :1], cache,
                            jnp.ones((SLOTS,), jnp.int32), cfg)
    else:
        chunk = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 8)), jnp.int32)
        logits, cache = run_packed(
            params, chunk, jnp.asarray([1], jnp.int32),
            jnp.asarray([6], jnp.int32), toks[:, 0],
            jnp.asarray([1, 0, 1], jnp.int32), cache, cfg)
    return np.asarray(logits), cache


def _eager_step(params, tokens, cache, n_tokens, cfg):
    return forward_step(params, tokens, cache, cfg, n_tokens=n_tokens)


@pytest.mark.parametrize("program", ["tq1", "packed"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_served_layout_gives_the_outer_formats_logits(body, program):
    cfg, params, leaf = _model(body)
    served = served_layout(params)
    assert _leaves_named(served, leaf)
    for gone in ("wq", "wk", "wv", "wqb"):
        assert not _leaves_named(served, gone), gone
    want, cache_o = _tick_logits(params, cfg, program)
    got, cache_s = _tick_logits(served, cfg, program)
    assert np.abs(want).max() > 0.1            # logits, not zeros
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    # And the rows the tick wrote into the pool (K, V, latent rows, tails).
    for a, b in zip(jax.tree.leaves(cache_s), jax.tree.leaves(cache_o)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=ATOL)


LATENT_BODIES = sorted(b for b, (_, leaf) in BODIES.items()
                       if leaf == LATENT_SERVED)


@pytest.mark.parametrize("program", ["tq1", "packed"])
@pytest.mark.parametrize("body", LATENT_BODIES)
def test_the_barrier_before_the_reshape_changes_no_bit(
        body, program, monkeypatch):
    """``latent_qkv``'s served branch holds its reshape to heads behind a
    ``lax.optimization_barrier`` (ISSUE 41: what makes the chip's compiler
    read ``wqb_t`` in place). With the barrier taken out the program is the
    one served before it: the same logits and pool rows, to the bit."""
    from jax import lax

    cfg, params, _ = _model(body)
    served = served_layout(params)
    # Eagerly: a compiled program would be found again, not traced again,
    # and the patched barrier never called.
    got, cache = _tick_logits(served, cfg, program, eager=True)
    barriers = []

    def no_barrier(operand):
        barriers.append(operand)
        return operand

    monkeypatch.setattr(lax, "optimization_barrier", no_barrier)
    want, cache_before = _tick_logits(served, cfg, program, eager=True)
    # The products of both steps of ``_tick_logits`` went through it: flat
    # ``(B, T, H x (nope + rope))``, every latent sublayer.
    width = cfg.n_heads * (cfg.mla.nope + cfg.mla.rope)
    flat = [b for b in barriers
            if not isinstance(b, tuple) and b.shape[-1] == width]
    assert len(flat) >= 2 and all(b.ndim == 3 for b in flat)
    assert np.abs(want).max() > 0.1
    np.testing.assert_array_equal(got, want)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_before)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("body", LATENT_BODIES)
def test_a_served_latent_tree_differentiates_as_the_outer_one(body):
    """Every function of the model takes either tree (ISSUE 34), the
    gradient included: the loss of a prefill step's logits (``forward()``
    builds the dense block only; ``forward_step`` is the full-sequence pass a
    latent model has) differentiates through the served branch's barrier,
    and the served tree's gradient is the outer tree's, re-laid."""
    from tree_attention_tpu.models.transformer import cross_entropy_loss

    cfg, params, leaf = _model(body)
    rng = np.random.default_rng(5)
    toks, targets = (
        jnp.asarray(rng.integers(1, cfg.vocab_size, (SLOTS, 12)), jnp.int32)
        for _ in range(2))
    n_tokens = jnp.asarray([5, 0, 11], jnp.int32)

    @jax.jit
    @jax.grad
    def grad(p):
        logits, _ = forward_step(p, toks, _cache(cfg), cfg, n_tokens=n_tokens)
        return cross_entropy_loss(
            logits, targets, jnp.arange(12)[None] < n_tokens[:, None])

    want, got = served_layout(grad(params)), grad(served_layout(params))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for name in (leaf, "wqa", "wkb"):       # the product's and its neighbours'
        assert all(np.abs(np.asarray(g)).max() > 1e-5
                   for g in _leaves_named(got, name)), name
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


def test_the_plain_forward_takes_either_form():
    """``gqa_qkv`` also serves ``forward()`` (generate, train, the smoke's
    reference logits): one function, told by the leaves it is given."""
    params = init_params(jax.random.PRNGKey(3), DENSE)
    toks = jnp.asarray(
        np.random.default_rng(2).integers(1, 128, (2, 24)), jnp.int32)
    want = forward(params, toks, DENSE)[0]
    got = forward(served_layout(params), toks, DENSE)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_served_leaf_is_the_outer_ones_transposed(body):
    cfg, params, leaf = _model(body)
    served = served_layout(params)
    if leaf == LATENT_SERVED:
        outer = _leaves_named(params, "wqb")
        want = [np.swapaxes(np.asarray(w), -1, -2) for w in outer]
    else:
        want = [np.concatenate([np.swapaxes(np.asarray(w), -1, -2)
                                for w in qkv], axis=-2)
                for qkv in zip(*(_leaves_named(params, n)
                                 for n in ("wq", "wk", "wv")))]
        assert want[0].shape[-2:] == (cfg.q_dim + 2 * cfg.kv_dim, cfg.d_model)
    got = _leaves_named(served, leaf)
    assert len(got) == len(want) >= 1
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("body", sorted(BODIES))
def test_applied_twice_it_changes_nothing_and_it_touches_no_other_leaf(body):
    _, params, leaf = _model(body)
    once = served_layout(params)
    twice = served_layout(once)
    assert jax.tree.structure(once) == jax.tree.structure(twice)
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(twice)):
        assert a is b
    # Every leaf that is not a projection is the caller's own array.
    mine = {id(a) for a in jax.tree.leaves(params)}
    relaid = {id(a) for a in _leaves_named(once, leaf)}
    for a in jax.tree.leaves(once):
        assert (id(a) in mine) != (id(a) in relaid)


def test_a_tree_without_the_leaves_passes_through():
    tree = {"embed": jnp.ones((4, 2)), "layers": {"w1": jnp.ones((2, 2, 3))},
            "sub": [{"wo": jnp.ones((2, 2))}], "n": 3}
    out = served_layout(tree)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a is b


def test_the_abstract_tree_is_relaid_as_the_real_one():
    """``jax.eval_shape`` through the engine's own function is what the
    compile tests hand the chip's compiler."""
    cfg, params, _ = _model("latent_double_layer")
    abstract = jax.eval_shape(
        lambda: served_layout(init_params(jax.random.PRNGKey(0), cfg)))
    real = served_layout(params)
    assert jax.tree.structure(abstract) == jax.tree.structure(real)
    for a, b in zip(jax.tree.leaves(abstract), jax.tree.leaves(real)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.fixture
def registry():
    """The registry on for one test, and left as it was found: zeroed (other
    modules' tests read totals they take to be their own) and off."""
    was = obs.REGISTRY.enabled
    obs.REGISTRY.enable()
    yield
    obs.REGISTRY.reset()
    if not was:
        obs.REGISTRY.disable()


def _gauge(leaf):
    return _WEIGHTS_RELAID.labels(leaf=leaf).value()


@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_gauge_reads_the_bytes_the_engine_holds_relaid(registry, body):
    cfg, params, leaf = _model(body)
    want = sum(a.size * a.dtype.itemsize for n in ("wq", "wk", "wv", "wqb")
               for a in _leaves_named(params, n))
    assert want > 0
    eng = SlotServer(params, cfg, slots=2, cache_len=32, kv_block=BLOCK,
                     prefill_chunk=16)
    assert _gauge(leaf) == want
    other = ({GQA_SERVED, LATENT_SERVED} - {leaf}).pop()
    assert _gauge(other) == 0
    # The engine serves from the re-laid tree and the caller's is untouched.
    assert _leaves_named(eng.params, leaf) and not _leaves_named(params, leaf)
    rep = eng.serve([Request(uid=0, prompt=list(range(1, 12)),
                             max_new_tokens=3)])
    assert len(rep.results[0].tokens) == 3


def test_the_gauge_is_zero_for_a_model_with_none_of_the_leaves(registry):
    _WEIGHTS_RELAID.labels(leaf=GQA_SERVED).set(7)
    tree = {"embed": jnp.ones((4, 2)), "layers": {"w1": jnp.ones((2, 2, 3))}}
    assert serving_params(tree)["embed"] is tree["embed"]
    assert _gauge(GQA_SERVED) == 0 and _gauge(LATENT_SERVED) == 0


def test_a_disaggregated_pair_shares_one_relaid_tree():
    params = init_params(jax.random.PRNGKey(3), DENSE)
    pair = DisaggServer(params, DENSE, prefill_slots=1, decode_slots=2,
                        cache_len=32, kv_block=BLOCK, prefill_chunk=16)
    held = [w.params["layers"][GQA_SERVED]
            for w in (pair, pair.prefill, pair.decode)]
    assert held[0] is held[1] is held[2]


def test_the_cli_relays_once_for_every_engine_it_builds():
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "16", "--max-new-tokens", "4",
                      "--model-dim", "64", "--heads", "4", "--vocab-size",
                      "128", "--dtype", "float32"])
    outer = init_params(jax.random.PRNGKey(0), cli._transformer_config(cfg))
    setup = cli.build_serve_engine(cfg, None, params=outer)
    served = setup.params["layers"][GQA_SERVED]
    assert "wq" in outer["layers"] and "wq" not in setup.params["layers"]
    assert setup.params["layers"]["w1"] is outer["layers"]["w1"]
    a, b = setup.make_engine(), setup.make_engine()   # a fleet's replicas
    assert a.params["layers"][GQA_SERVED] is served
    assert b.params["layers"][GQA_SERVED] is served
