"""What only the shortcut-connected double layer has: a routed branch that
leaves after the first attention and rejoins after the second FFN,
zero-compute (identity) experts in the router's width, and a top taken of
bias-corrected scores — held against ``benchmark/references/longcat_scmoe.py``
at ``tests/test_latent_moe.py``'s small preset (``SMALL_SC``). What the two
latent families share is tested there, one case a family.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_latent_moe import (
    PRESETS,
    ROOT,
    SMALL,
    SMALL_SC,
    _model,
    _serve_chunks,
    load_family,
)
from tree_attention_tpu.models import experts
from tree_attention_tpu.models.decode import forward_step, init_paged_cache
from tree_attention_tpu.models.transformer import (
    init_params,
    model_from_config,
)
from tree_attention_tpu.serving.engine import Request


@pytest.fixture(scope="module")
def fam():
    return load_family("longcat_scmoe")


def _layer0(fam, seed=11, **config):
    """Widths and layer 0's reference leaves (``we*``: that layer's)."""
    w = fam.ref.Widths.of(dict(SMALL_SC, **config))
    stack = fam.ref.init_weights(seed, w)["layers"]
    return w, jax.tree.map(lambda a: a[0], stack)


# -- the model as data -------------------------------------------------------


def test_the_published_keys_say_what_a_layer_is():
    with open(f"{ROOT}/benchmark/configs/longcat-flash-omni.json") as f:
        t = model_from_config(json.load(f))
    la, ex = t.mla, t.moe
    assert (t.n_layers, t.sublayers, t.cache_layers) == (4, 2, 8)
    assert (t.d_model, t.d_ff, t.n_heads, t.vocab_size) == (
        6144, 12288, 64, 16384)
    assert (la.q_rank, la.kv_rank, la.nope, la.rope, la.v_head, la.row) == (
        1536, 512, 128, 64, 128, 640)
    assert la.q_scale == 2.0 and la.kv_scale == pytest.approx(12 ** 0.5)
    assert la.yarn is None and t.rope_theta == 1e7 and t.norm_eps == 1e-5
    assert (ex.n_experts, ex.n_routed, ex.n_zero, ex.held, ex.held_first) == (
        768, 512, 256, 16, 0)
    assert (ex.per_token, ex.scale, ex.width, ex.shared_width) == (
        12, 6.0, 2048, 0)
    assert ex.corrected and not ex.renorm and ex.branch == (0, 1)
    assert ex.n_groups == 1 and ex.first_dense == 0


def test_a_model_without_the_new_keys_is_built_as_before():
    """Every new field at its default: the other latent family's model
    compares equal to one made without them."""
    with open(f"{ROOT}/benchmark/configs/deepseek-v2.json") as f:
        t = model_from_config(json.load(f))
    assert t.sublayers == 1 and t.cache_layers == t.n_layers == 5
    assert t.mla == dataclasses.replace(t.mla, q_scale=1.0, kv_scale=1.0)
    assert t.moe == dataclasses.replace(
        t.moe, n_zero=0, corrected=False, branch=None)
    assert t.moe.n_routed == t.moe.n_experts == 160


@pytest.mark.parametrize("change, named", [
    ({"zero_expert_type": "copy"}, "identity"),
    ({"block": {"sublayers": 2}}, "routed branch"),
    ({"block": {"sublayers": 2, "routed_branch": [1, 2]}}, "routed branch"),
    ({"block": dict(SMALL_SC["block"]), "first_k_dense_replace": 1},
     "leading dense"),
    ({"deployment": {"experts_total": 16, "expert_share": 4}}, "lie outside"),
])
def test_what_the_data_cannot_say_is_refused(change, named):
    with pytest.raises(ValueError, match=named):
        model_from_config(dict(SMALL_SC, **change))


# -- the router --------------------------------------------------------------


def test_corrected_choice_equals_the_reference_on_10000_rows(fam):
    w = fam.ref.Widths.of(SMALL_SC)
    ex = model_from_config(SMALL_SC).moe
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(10_000, 24)).astype(np.float32) * 1.4
    logits[:100] = np.round(logits[:100])          # ties: lowest index wins
    scores = jax.nn.softmax(jnp.asarray(logits), -1)
    bias = jnp.asarray(rng.normal(size=24) * 0.01, jnp.float32)
    idx, wt = experts.route(scores, ex, bias)
    ridx, rwt = fam.ref.route(scores, bias, w)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(wt, rwt, rtol=1e-6)
    # The bias moves the choice in a good share of rows, never the weights:
    # those are the chosen experts' own scores times 6.
    plain, _ = experts.route(scores, ex, jnp.zeros_like(bias))
    moved = (np.sort(idx, -1) != np.sort(plain, -1)).any(-1).mean()
    assert 0.1 < moved < 0.9
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), 1)
    np.testing.assert_allclose(wt, picked * 6, rtol=1e-6)


def test_a_bias_changes_the_choice_and_not_the_weights():
    ex = model_from_config(SMALL_SC).moe
    scores = jnp.asarray([[0.30, 0.25, 0.20, 0.15] + [0.1 / 20] * 20])
    bias = jnp.zeros((24,)).at[3].set(0.06)         # lifts 3 over 2
    idx, wt = experts.route(scores, ex, bias)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 3]
    by_id = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(wt[0])))
    np.testing.assert_allclose(
        [by_id[0], by_id[1], by_id[3]], [1.8, 1.5, 0.9], rtol=1e-6)


@pytest.mark.parametrize("lifted, zero_pairs, real", [
    (range(16, 24), 3, 0),       # every choice a zero-compute expert
    (range(4, 8), 0, 3),         # none: three held routed experts
])
def test_a_row_of_only_zero_compute_experts_and_one_with_none(
        fam, lifted, zero_pairs, real):
    w, p = _layer0(fam)
    ex = model_from_config(SMALL_SC).moe
    p = dict(p, router_bias=jnp.zeros((24,)).at[jnp.asarray(lifted)].set(1.0))
    h = jax.random.normal(jax.random.PRNGKey(3), (20, 64))
    y, chosen = experts.expert_layer(p, h[None], ex)
    np.testing.assert_allclose(
        y[0], fam.ref.routed_branch(h, p, w=w), atol=1e-5)
    zero = np.asarray(chosen[0]) >= 16
    assert (zero.sum(-1) == zero_pairs).all()
    if zero_pairs:
        # No weights read: the branch is the row itself, times the three
        # chosen scores and 6.
        s = np.asarray(experts.router_scores(p, h))
        wsum = 6 * np.take_along_axis(s, np.asarray(chosen[0]), 1).sum(-1)
        np.testing.assert_allclose(y[0], wsum[:, None] * h, rtol=1e-5)
    counts = np.asarray(experts.held_counts(
        chosen, jnp.ones((1, 20), bool), ex))
    assert counts.tolist() == (
        [0, 0, 0, 0, 0, 60, 0] if zero_pairs else
        np.bincount(np.asarray(chosen).reshape(-1) - 4,
                    minlength=4).tolist() + [0, 0, 3])
    assert counts[-1] == real


def test_the_shares_and_the_identity_term_once_add_up_to_the_uncut_layer(fam):
    """The four shares' routed sums plus the zero-compute experts counted
    once = the uncut reference layer; the program's layer = the reference's
    share, identity term included (every chip computes it for its rows)."""
    uncut = {"n_routed_experts": 16,
             "deployment": {"experts_total": 16, "expert_share": 0}}
    w_all, p_all = _layer0(fam, **uncut)
    h = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
    whole = fam.ref.routed_branch(h, p_all, w=w_all)
    total = fam.ref.routed_branch(h, p_all, w=w_all, held=0)   # identity once
    assert float(jnp.abs(total).max()) > 0.1
    ex = model_from_config(SMALL_SC).moe
    for share in range(4):
        cut = dict(p_all, **{n: p_all[n][4 * share:4 * share + 4]
                             for n in ("we1", "we3", "we2")})
        part = fam.ref.routed_branch(h, cut, w=w_all, held_first=4 * share,
                                     held=4, identity=False)
        total = total + part
        mine, _ = experts.expert_layer(
            cut, h[None], dataclasses.replace(ex, held_first=4 * share))
        np.testing.assert_allclose(
            mine[0], part + fam.ref.routed_branch(h, p_all, w=w_all, held=0),
            atol=1e-5)
    np.testing.assert_allclose(total, whole, atol=1e-5)


# -- the shortcut ------------------------------------------------------------


def test_the_branch_is_of_the_first_sublayers_input_not_the_seconds(fam):
    _, w, weights, tcfg, params = _model(fam)
    ref = fam.ref
    toks = np.random.default_rng(1).integers(0, 128, (1, 24))
    x = weights["embed"][jnp.asarray(toks[0])].astype(jnp.float32)
    wrong = x
    for l in range(w.layers):
        p = jax.tree.map(lambda a: a[l], weights["layers"])
        parts = ref.layer_parts(x, p, w=w)
        first = ref.routed_branch(parts["h"], p, w=w)
        second = ref.routed_branch(
            ref._rms(parts["c"], p["sub"][1]["ln2"], w.norm_eps), p, w=w)
        np.testing.assert_allclose(parts["out"] - parts["d"], first,
                                   atol=1e-5)
        assert float(jnp.abs(first - second).max()) > 1e-3
        x = parts["out"]
        # A layer with the branch taken at the second sublayer instead.
        pw = ref.layer_parts(wrong, p, w=w)
        wrong = pw["d"] + ref.routed_branch(
            ref._rms(pw["c"], p["sub"][1]["ln2"], w.norm_eps), p, w=w)
    head = lambda r: np.asarray(ref._head(            # noqa: E731
        r, weights["ln_f"], weights["wout"], w=w, quant=None))
    got, _, _ = _serve_chunks(params, tcfg, toks, [24])
    np.testing.assert_allclose(got[0], head(x), atol=2e-6)
    assert np.abs(got[0] - head(wrong)).max() > 2e-4    # 100 x the limit


# -- counters ----------------------------------------------------------------


def test_held_counts_against_a_hand_count():
    ex = model_from_config(SMALL_SC).moe       # holds 4-7 of 16, zero 16-23
    idx = jnp.asarray([[[4, 5, 17], [0, 16, 20], [7, 5, 18], [8, 9, 10]]])
    valid = jnp.asarray([[True, True, True, False]])   # the last is padding
    counts = np.asarray(experts.held_counts(idx, valid, ex))
    assert experts.counts_width(ex) == counts.shape[0] == 4 + 3
    # experts 4, 5, 6, 7; held elsewhere; zero-compute; most real in a row
    assert counts.tolist() == [1, 2, 0, 1, 1, 4, 2]
    assert counts[:6].sum() == 3 * 3                   # 3 a decision
    # Without zero-compute experts the layout is the older one.
    plain = model_from_config(SMALL).moe
    assert experts.counts_width(plain) == plain.held + 1
    assert experts.held_counts(idx % 16, valid, plain).shape == (5,)


def test_the_tick_records_count_every_pair_once(fam):
    _, _, _, tcfg, params = _model(fam)
    toks = np.random.default_rng(2).integers(0, 128, (2, 21))
    _, _, stats = _serve_chunks(params, tcfg, toks, [21, 13], chunk=32)
    rows = np.asarray(stats["expert_rows"])            # the last step: Tq 32
    assert rows.shape == (2, 4 + 3)
    # 21 + 13 rows carry a token, 3 pairs each, in each of the 2 layers.
    assert (rows[:, :6].sum(-1) == 34 * 3).all()
    assert (rows[:, 6] <= 3).all() and (rows[:, 5] > 0).all()


# -- one program for the model without the new parts -------------------------


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


@pytest.mark.parametrize("tq", [1, 16])
def test_the_other_latent_family_traces_the_program_it_did(tq):
    """Zero-compute experts, the correction and the scales are Python
    branches on the model: a model without them traces the step it traced
    before they existed, 740 equations at this preset (counted on the
    commit before PR 31, nested jaxprs included); the same model with
    zero-compute experts in its router's width traces a larger one."""

    def count(preset):
        tcfg = model_from_config(preset, max_seq_len=128)
        params = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), tcfg))
        cache = jax.eval_shape(
            lambda: init_paged_cache(tcfg, 3, 64, 24, block=8))

        def step(params, toks, cache, n):
            stats = {}
            logits, cache = forward_step(params, toks, cache, tcfg,
                                         n_tokens=n, stats=stats)
            return logits, cache, stats

        return _equations(jax.make_jaxpr(step)(
            params, jax.ShapeDtypeStruct((3, tq), jnp.int32), cache,
            jax.ShapeDtypeStruct((3,), jnp.int32)).jaxpr)

    assert count(SMALL) == 740
    assert count(dict(SMALL, zero_expert_num=4)) > 740


# -- the entry point ---------------------------------------------------------


def test_model_config_serves_the_double_layer_on_its_own_weights(tmp_path):
    """``--model-config`` with this family's keys: the program draws the
    branch layout's leaves itself and serves through ``SlotServer``."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    assert sorted(PRESETS) == ["deepseek_mla_moe", "longcat_scmoe"]
    path = tmp_path / "model.json"
    path.write_text(json.dumps(SMALL_SC))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "16", "--max-new-tokens", "4",
                      "--dtype", "float32", "--prefix-cache",
                      "--prefix-block", "8", "--model-config", str(path)])
    setup = cli.build_serve_engine(cfg, None)
    layers = setup.params["layers"]
    assert len(layers["sub"]) == 2 and layers["sub"][1]["wo"].shape[0] == 2
    assert layers["router"].shape == (2, 64, 24)
    assert layers["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(layers["router_bias"]).min()) > 0
    eng = setup.make_engine()
    assert eng.cache.kv.shape[0] == 4
    rep = eng.serve([Request(uid=0, prompt=list(range(1, 20)),
                             max_new_tokens=4)])
    assert len(rep.results[0].tokens) == 4
    leak = eng.leak_report()
    assert leak["blocks_used"] == leak["blocks_cached"]
