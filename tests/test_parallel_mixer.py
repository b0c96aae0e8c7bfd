"""A layer whose mixer is TWO mixers side by side: a Mamba-2 state-space mixer
and rotary GQA read ONE normed residual and add into it together, each under
its own fixed scale, every layer; the family's fixed multipliers as data; a
dense SwiGLU behind them (the ``falcon_h1`` family). Held against the
benchmark's plain reference (``benchmark/references/falcon_h1.py``: the full
forward pass over one sequence, the state-space branch the token-by-token
recurrence, one dense softmax, every multiplier an explicit scalar) at a small
size, on the CPU, in float32, with seeded weights: 5 query heads over 1 KV
head (the family's group of five), 4 state heads of 16 x 32 in 2 groups, 3
layers, NO multiplier at 1.

Tolerances. Logits here have a standard deviation of ~1. The program and the
reference add the same float32 numbers in other orders (the chunked scan's
matrix products against the recurrence, attention over a gathered view
against one softmax over a row): their logits agree to ~2e-6 and are held to
``ATOL`` 2e-5. What a test shows to be DIFFERENT (a multiplier moved, a
control, a slot that moved against one that sat out) differs by 1e-3 or more.
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
from tree_attention_tpu.models import decode, hybrid, transformer
from tree_attention_tpu.models.decode import (
    PagedStateCache,
    forward_step,
    init_paged_cache,
)
from tree_attention_tpu.models.hybrid import layer_runs
from tree_attention_tpu.models.transformer import (
    Multipliers,
    StateSpace,
    TransformerConfig,
    init_params,
    model_from_config,
    rms_norm,
)
from tree_attention_tpu.obs import scopes
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.serving import SlotServer
from tree_attention_tpu.serving.engine import Request

from tests.jitted import serve_step_stats, step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5
BLOCK = 4
WIDTH = 16      # the one chunk width the step helpers compile (two scan blocks)

BLOCK_KEYS = {"mixer_arrangement": "parallel_shared_norm",
              "mup_segments": ["z", "x", "B", "C", "dt"],
              "rotary_convention": "half_split"}
SMALL = {
    "family": "falcon_h1", "model_type": "falcon_h1", "hidden_size": 64,
    "num_attention_heads": 5, "num_key_value_heads": 1, "head_dim": 16,
    "intermediate_size": 96, "num_hidden_layers": 3, "vocab_size": 128,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_ssm": 64,
    "mamba_n_groups": 2, "mamba_d_state": 32, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_expand": 2, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "attn_layer_indices": None, "attention_bias": False, "mlp_bias": False,
    "projectors_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_theta": 1e11, "rope_scaling": None, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "embedding_multiplier": 5.657, "lm_head_multiplier": 0.25,
    "attention_in_multiplier": 0.8, "attention_out_multiplier": 0.3,
    "key_multiplier": 0.2, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.4, "ssm_multipliers": [0.7, 0.5, 0.6, 1.5, 0.8],
    "mlp_multipliers": [0.6, 0.3],
    "block": dict(BLOCK_KEYS),
    "assumed": {"time_step_min": 0.001, "time_step_max": 0.1,
                "seeded_scales": {
                    "embedding_std": 0.18, "head_std": 0.5, "qk_std": 0.4,
                    "v_std": 0.1, "attn_out_std": 0.2, "ssm_in_std": 0.25,
                    "ssm_out_std": 0.1, "mlp_gate_std": 0.2,
                    "mlp_up_std": 0.1, "mlp_down_std": 0.3,
                    "gain_mean": 1.5, "gain_std": 0.1}},
}
SCALARS = [f.name for f in dataclasses.fields(Multipliers)
           if not isinstance(f.default, tuple)]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "benchmark", "references",
                              "falcon_h1.py"), "_references_falcon_h1")


@pytest.fixture(scope="module")
def adapter():
    return _load(os.path.join(ROOT, "benchmark", "adapters", "falcon_h1.py"),
                 "_adapters_falcon_h1")


@pytest.fixture(scope="module")
def model(ref, adapter):
    """(widths, reference weights, TransformerConfig, engine params)."""
    w = ref.Widths.of(SMALL)
    weights = ref.init_weights(7, w)
    tcfg = model_from_config(SMALL, max_seq_len=128)
    adapter._hold_to_file(tcfg, SMALL)
    return w, weights, tcfg, adapter.engine_params(weights, w)


def _want(ref, w, weights, toks, rows=None, **kw):
    rows = np.arange(len(toks)) if rows is None else np.asarray(rows)
    return ref.logits_at(weights, w, np.asarray(toks), rows, pad_to=16, **kw)


def _greedy(ref, weights, w, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        row = _want(ref, w, weights, toks, [len(toks) - 1])
        toks.append(int(row[0].argmax()))
    return toks[len(prompt):]


def _cache(tcfg, slots=2, nb=16):
    cache = init_paged_cache(tcfg, slots, nb * BLOCK, slots * nb, block=BLOCK)
    assert isinstance(cache, PagedStateCache)
    table = jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb)[:, ::-1]
    return dataclasses.replace(cache, table=table)


def _serve_rows(params, tcfg, toks, steps, packed=False, cache=None):
    """Run ``steps`` (rows a slot a step) through the cache: the logits of
    the rows that carried a token, the cache, and each step's counters."""
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


def test_the_catalogs_config_verbatim_builds_72_two_branch_layers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in open(path)]
    c = next((r["config"] for r in rows
              if r["name"] == "Falcon-H1-34B-Instruct"), None)
    if c is None:
        pytest.skip("the catalog has no Falcon-H1-34B-Instruct row")
    t = model_from_config(c, max_seq_len=256)
    assert t.layer_types == ("parallel",) * 72 and t.cache_kind == "state"
    assert (t.ssm_layers, t.cache_layers, t.n_dense_layers) == (72, 72, 72)
    assert t.ssm == StateSpace(n_heads=32, d_head=128, n_groups=2,
                               d_state=256, taps=4, chunk=128)
    assert (t.ssm.inner, t.ssm.conv_dim, t.ssm.in_dim) == (4096, 5120, 9248)
    # A head of 128 fills a row of lanes.
    assert (t.ssm.pack, t.ssm.state_shape) == (1, (32, 256, 128))
    assert (t.d_model, t.n_heads, t.n_kv_heads, t.d_head, t.d_ff) == (
        5120, 20, 4, 128, 21504)
    assert (t.q_dim, t.kv_dim, t.vocab_size) == (2560, 512, 261120)
    assert (t.rope_theta, t.norm_eps, t.tied_head) == (1e11, 1e-5, False)
    assert [r[:3] for r in layer_runs(t)] == [("parallel", "dense", 72)]
    m = t.mup
    assert (m.embedding_multiplier, m.lm_head_multiplier) == (
        c["embedding_multiplier"], 0.0078125)
    assert (m.ssm_in_multiplier, m.ssm_out_multiplier,
            m.attention_in_multiplier, m.attention_out_multiplier,
            m.key_multiplier) == (0.25, c["ssm_out_multiplier"], 1.0,
                                  0.0375, c["key_multiplier"])
    assert m.ssm_multipliers == tuple(c["ssm_multipliers"])
    assert m.mlp_multipliers == tuple(c["mlp_multipliers"])
    assert not m.unit and Multipliers().unit
    # A configuration read back from a checkpoint's JSON sidecar holds the
    # record as a dict.
    dense = TransformerConfig(mup=dataclasses.asdict(Multipliers()))
    assert dense.mup == Multipliers() and hash(dense) == hash(
        TransformerConfig())


@pytest.mark.parametrize("layers, total", [(1, None), (9, 4205.3)])
def test_the_programs_own_count_at_the_published_widths(layers, total):
    """430.12M a layer; 4,205.3M for the benchmark's cut (9 layers, an
    eighth of the vocabulary). Shapes only."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-instruct.json")) as f:
        c = json.load(f)
    assert (c["num_hidden_layers"], c["vocab_size"]) == (9, 32640)
    t = model_from_config(dict(c, num_hidden_layers=layers))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), t))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    per_layer = sum(count(shapes[k]) for k in ("attn", "ssm", "dense"))
    assert per_layer == layers * 430_120_032
    assert abs(per_layer / layers / 1e6 - 430.12) < 0.005
    assert "ln1" in shapes["attn"] and "ln1" not in shapes["ssm"]
    if total is not None:
        whole = count(shapes)
        assert whole == per_layer + 2 * 32640 * 5120 + 5120
        assert abs(whole / 1e6 - total) < 0.05 and "4,205.3M" in \
            c["why_reduced"]
        cache = jax.eval_shape(lambda: init_paged_cache(
            t, 48, 2560, 48 * 40, block=64))
        assert cache.k.shape == cache.v.shape == (9, 1920, 4, 64, 128)
        assert cache.ssm_state.shape == (9, 48, 32, 256, 128)
        assert cache.ssm_state.dtype == jnp.float32
        assert cache.ssm_tail.shape == (9, 48, 15360)


def test_the_small_files_keys_say_what_each_layer_is(model):
    _, _, t, params = model
    assert t.layer_types == ("parallel",) * 3
    assert t.ffn_kinds == ("dense",) * 3
    assert layer_runs(t) == [("parallel", "dense", 3, 0, 0)]
    assert t.cache_kind == "state" and not t.dense_block
    assert (t.ssm_layers, t.cache_layers, t.n_expert_layers) == (3, 3, 0)
    assert (t.n_heads // t.n_kv_heads, t.q_dim, t.d_model) == (5, 80, 64)
    assert params["ssm"]["w_in"].shape == (3, 64, 64 + 64 + 64 + 64 + 4)
    assert params["ssm"]["conv_w"].shape == (3, 4, 192)
    assert params["attn"]["wq"].shape == (3, 64, 80)
    assert all(v != 1.0 for v in dataclasses.astuple(t.mup)
               if isinstance(v, float))
    assert all(v != 1.0 for v in t.mup.ssm_multipliers + t.mup.mlp_multipliers)


@pytest.mark.parametrize("change, named", [
    ({"mamba_rms_norm": False}, "mamba_rms_norm"),
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
    ({"attn_layer_indices": [0, 2]}, "attn_layer_indices"),
    ({"mamba_use_mlp": False}, "mamba_use_mlp"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"projectors_bias": True}, "projectors_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"mamba_d_ssm": 128}, "mamba_d_ssm"),
    ({"mamba_n_groups": 3}, "groups"),
    ({"ssm_multipliers": [1, 2, 3]}, "ssm_multipliers"),
    ({"layer_types": ["full_attention"] * 3}, "beside layer_types"),
    ({"num_experts": 4, "num_experts_per_tok": 2,
      "moe_intermediate_size": 8}, "experts"),
    ({"block": dict(BLOCK_KEYS, mixer_arrangement="serial")},
     "mixer_arrangement"),
    ({"block": dict(BLOCK_KEYS, mup_segments=["x", "z", "B", "C", "dt"])},
     "mup_segments"),
    ({"block": dict(BLOCK_KEYS, rotary_convention="interleaved")},
     "rotary_convention"),
])
def test_each_refused_key_is_refused_by_its_name(change, named):
    with pytest.raises(ValueError, match=named):
        model_from_config(dict(SMALL, **change))


@pytest.mark.parametrize("kw, named", [
    (dict(layer_types=("parallel", "ssm", "parallel")), "beside layers"),
    (dict(layer_types=("parallel", "attention", "parallel")),
     "beside layers"),
    (dict(layer_types=("parallel", "conv", "parallel")), "conv"),
    (dict(layer_types=("parallel", "window", "parallel"), window=8),
     "window"),
    (dict(qk_norm=True), "qk_norm"),
    (dict(rotary=()), "rotary"),
    (dict(layer_types=("ssm",) * 3), "mup"),
    (dict(layer_types=None, ssm=None), "mup"),
])
def test_the_two_branch_kind_beside_another_is_refused_by_name(model, kw,
                                                               named):
    _, _, t, _ = model
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(t, **kw)


# -- the served path against the reference -----------------------------------


@pytest.mark.parametrize("chunk", [3, 8, 13, 16])
def test_prefill_in_chunks_then_decode_equals_the_reference(ref, model,
                                                            chunk):
    """Chunks under the scan's block of 8 (3), at it, off its multiples (13:
    a prompt of odd length, decode taking over mid-chunk) and of two blocks,
    ragged between the slots, then decode through the cache: every row's
    logits are the reference's full forward pass; every step writes a state a
    slot with a row a layer."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(chunk)
    toks = [rng.integers(0, 128, (40,)), rng.integers(0, 128, (31,))]
    steps = []
    for lo in range(0, 26, chunk):
        steps.append([min(chunk, 26 - lo), min(chunk, max(19 - lo, 0))])
    steps += [[1, 1]] * 12 + [[1, 0]] * 2
    got, _, stats = _serve_rows(params, tcfg, toks, steps)
    for i in range(2):
        want = _want(ref, w, weights, toks[i])
        assert len(got[i]) == len(toks[i])
        for row, lg in got[i]:
            np.testing.assert_allclose(lg, want[row], atol=ATOL)
    assert np.std(_want(ref, w, weights, toks[0])) > 0.3
    for ns, st in zip(steps, stats):
        assert int(st["ssm_states"]) == 3 * sum(n > 0 for n in ns)


def test_a_packed_tick_serves_a_chunk_group_beside_decode_rows(ref, model):
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


@pytest.mark.parametrize("packed", [False, True])
def test_a_slot_that_sits_out_keeps_its_state_and_its_rows_bit_for_bit(
        model, packed):
    _, _, tcfg, params = model
    rng = np.random.default_rng(8)
    toks = [rng.integers(0, 128, (20,)), rng.integers(0, 128, (20,))]
    _, c0, _ = _serve_rows(params, tcfg, toks, [[7, 9]])
    _, c1, _ = _serve_rows(params, tcfg, [toks[0][7:], toks[1][9:]],
                           [[5, 0], [1, 0]], packed=packed, cache=c0)
    for name in ("ssm_state", "ssm_tail"):
        a, b = np.asarray(getattr(c0, name)), np.asarray(getattr(c1, name))
        np.testing.assert_array_equal(a[:, 1], b[:, 1])
        assert np.abs(a[:, 0] - b[:, 0]).max() > 1e-3
    own = np.asarray(c0.table[1])       # the blocks of the slot that sat out
    for name in ("k", "v"):
        a, b = np.asarray(getattr(c0, name)), np.asarray(getattr(c1, name))
        np.testing.assert_array_equal(a[:, own], b[:, own])
        assert np.abs(a - b).max() > 1e-3
    assert int(c1.length[1]) == 9


@pytest.mark.parametrize("first", [1, 5, 11])
def test_a_reused_slot_reads_no_stale_state_and_no_stale_row(ref, model,
                                                             first):
    """A slot whose last request was LONGER: its state, its tail and its K/V
    rows stay in the arrays (the rows poisoned here, so that one read would
    show). A member whose first position is 0 reads none of them: logits
    from its first row on, whether its first step is a row or a chunk."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(first)
    old = [rng.integers(0, 128, (23,)), rng.integers(0, 128, (17,))]
    _, cache, _ = _serve_rows(params, tcfg, old, [[16, 9], [7, 8]])
    assert float(jnp.abs(cache.ssm_state[:, 0]).max()) > 1e-3
    new = rng.integers(0, 128, (first + 6,))
    own = cache.table[0]
    cache = dataclasses.replace(
        cache, length=cache.length.at[0].set(0),
        k=cache.k.at[:, own].multiply(50.0), v=cache.v.at[:, own].add(7.0))
    want = _want(ref, w, weights, new)
    got, _, _ = _serve_rows(params, tcfg, [new, old[1]],
                            [[first, 0]] + [[1, 0]] * 6, cache=cache)
    assert [row for row, _ in got[0]] == list(range(first + 6))
    np.testing.assert_allclose(np.stack([lg for _, lg in got[0]]), want,
                               atol=ATOL)


def test_the_controls_differ_from_the_sound_reference(ref, model):
    """The three faults of this mechanism (the attention branch fed the
    residual after the SSM branch's add; the SSM term left out; every
    multiplier at one) and int8 everywhere each move the logits by far more
    than the program's distance from the reference."""
    w, weights, _, _ = model
    assert ref.CONTROLS == ("int8", "serial", "no_ssm_branch",
                            "unit_multipliers")
    toks = np.random.default_rng(5).integers(0, 128, (40,))
    sound = _want(ref, w, weights, toks)
    for fault, least in (("int8", 1e-2), ("serial", 0.1),
                         ("no_ssm_branch", 0.1), ("unit_multipliers", 1.0)):
        moved = np.abs(sound - _want(ref, w, weights, toks, quant=fault))
        assert moved.max() > least >= 500 * ATOL, fault


# -- the multipliers ---------------------------------------------------------


@pytest.fixture(scope="module")
def one_layer(model):
    """One two-branch layer with every multiplier at 1, its parameters, a
    step's tokens and its logits: what each multiplier is moved against."""
    _, _, tcfg, params = model
    t = dataclasses.replace(tcfg, n_layers=1, layer_types=("parallel",),
                            mup=Multipliers())
    p = jax.tree.map(lambda a: a, params)
    for kind in ("attn", "ssm", "dense"):
        p[kind] = jax.tree.map(lambda a: a[:1], params[kind])
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 128, (1, 8)))
    n = jnp.asarray([8], jnp.int32)
    return t, p, toks, n, np.asarray(step(p, toks, _cache(t, 1), n, t)[0])


@pytest.mark.parametrize("name, at", [(n, None) for n in SCALARS] + [
    ("ssm_multipliers", i) for i in range(5)] + [
    ("mlp_multipliers", i) for i in range(2)])
def test_each_multiplier_alone_moves_the_logits(one_layer, name, at):
    t, p, toks, n, base = one_layer
    value = 1.7 if at is None else tuple(
        1.7 if i == at else 1.0
        for i in range(len(getattr(t.mup, name))))
    moved = dataclasses.replace(
        t, mup=dataclasses.replace(t.mup, **{name: value}))
    got = np.asarray(step(p, toks, _cache(moved, 1), n, moved)[0])
    assert np.abs(got - base).max() > 1e-3, name


def test_multipliers_at_one_leave_a_serial_models_step_as_it_was(
        monkeypatch):
    """Every default is 1.0 and a 1.0 emits no multiply: the step of a model
    of SERIAL mixers (``nemotron_h``'s small preset) traces to the same
    program whether the multipliers' hook is the real one or a stub that
    hands its operand back; and every call it sees is at 1.0."""
    from tests.test_state_space import SMALL as SERIAL

    t = model_from_config(SERIAL, max_seq_len=64)
    assert t.mup.unit
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), t))
    cache = jax.eval_shape(lambda: init_paged_cache(t, 2, 32, 16, block=4))

    def text():
        return str(jax.make_jaxpr(
            lambda p, c: forward_step(p, jnp.zeros((2, 8), jnp.int32), c, t,
                                      n_tokens=jnp.full((2,), 8)))(
            shapes, cache))

    real, seen = text(), []

    def stub(x, m):
        seen.append(m)
        return x

    for mod in (transformer, hybrid):
        monkeypatch.setattr(mod, "times", stub)
    assert text() == real
    assert seen and all(m == 1.0 for m in seen)


# -- the branch form under the wrappers --------------------------------------


def test_gqa_mixer_is_its_norm_its_branch_and_the_add_bit_for_bit(
        monkeypatch):
    """``llama_dense``'s tiny preset: the dense block's mixer, as it was
    before it was cut into a branch and a wrapper (the norm, the projections,
    the groups, the output projection and the add in ONE function), gives the
    wrapper's logits bit for bit."""
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_head=16, d_ff=96,
                            dtype=jnp.float32, attn_block_size=BLOCK)
    params = init_params(jax.random.PRNGKey(3), cfg)
    cache = init_paged_cache(cfg, 2, 32, 16, block=BLOCK)
    cache = dataclasses.replace(
        cache, table=jnp.arange(16, dtype=jnp.int32).reshape(2, 8))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 8)))
    n = jnp.asarray([8, 5], jnp.int32)

    def as_it_was(attend, layer, x, positions, k, v, k_s, v_s, views, l,
                  base):
        groups = attend.groups
        with jax.named_scope(scopes.ATTN_IN):
            h = rms_norm(x, layer["ln1"], cfg.norm_eps)
            q, k_new, v_new = transformer.gqa_qkv(
                layer, h, positions, cfg, rotary=attend.rotary)
        outs = []
        for gi in range(len(groups)):
            out, k, v, k_s, v_s = attend(
                gi, q, k_new, v_new, k, v, k_s, v_s, views, l, base)
            outs.append(out)
        out = decode._join_rows(groups, outs)
        x = x + decode._unheads(out) @ layer["wo"]
        return x, k, v, k_s, v_s

    run = lambda: jax.jit(  # noqa: E731
        lambda p, c: forward_step(p, toks, c, cfg, n_tokens=n)[0])(
        params, cache)
    new = np.asarray(run())
    monkeypatch.setattr(decode, "gqa_mixer", as_it_was)
    np.testing.assert_array_equal(np.asarray(run()), new)
    assert np.abs(new).max() > 1e-3


def test_ssm_mixer_is_its_norm_its_branch_and_the_add_bit_for_bit():
    """``nemotron_h``'s tiny preset: a serial state-space layer's wrapper
    (``ssm_mixer``) gives, bit for bit, the residual plus what the branch
    returns for the normed residual, the pools the branch returns, and
    nothing else: no scale, no second norm."""
    from tests.test_state_space import SMALL as SERIAL

    t = model_from_config(SERIAL, max_seq_len=64)
    params = init_params(jax.random.PRNGKey(1), t)
    layer = jax.tree.map(lambda a: a[1], params["ssm"])
    cache = init_paged_cache(t, 2, 32, 16, block=4)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 8, 64)),
                    jnp.float32)
    state = cache.ssm_state + 0.25
    group = decode._RowGroup(
        lo=None, batch=2, tq=8, start=jnp.asarray([3, 0], jnp.int32),
        n=jnp.asarray([8, 5], jnp.int32), table=cache.table, tree_mask=None,
        wtable=None, slot=jnp.arange(2, dtype=jnp.int32))
    got = hybrid.ssm_mixer(layer, x, state, cache.ssm_tail, 1, (group,), t)
    y, *pools = hybrid.ssm_branch(
        layer, rms_norm(x, layer["ln1"], t.norm_eps), state, cache.ssm_tail,
        1, (group,), t)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(x + y))
    for a, b in zip(got[1:], pools):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(y).max()) > 1e-3 and int(got[3]) == 2


# -- through SlotServer ------------------------------------------------------


def test_a_reused_slot_serves_the_references_greedy_choice(ref, model):
    """Three requests through one slot, one after another, beside a long one
    in the other slot: every token the reference's greedy choice; the flight
    record counts a state a live slot a LAYER in decode ticks (every layer
    holds one) and the K/V rows beside them; the gauges weigh the state pool
    beside the K/V pool; nothing leaked."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 128, (n,)).tolist() for n in (21, 9, 13, 30)]
    FLIGHT.clear()
    FLIGHT.arm(capacity=4096)
    obs.REGISTRY.enable()
    try:
        eng = SlotServer(params, tcfg, slots=2, cache_len=96,
                         prefill_chunk=8, kv_block=BLOCK)
        rep = eng.serve([
            Request(uid=0, prompt=prompts[3], max_new_tokens=30),
            Request(uid=1, prompt=prompts[0], max_new_tokens=6),
            Request(uid=2, prompt=prompts[1], max_new_tokens=7),
            Request(uid=3, prompt=prompts[2], max_new_tokens=5)])
        recs = [r for r in FLIGHT.snapshot()["records"]
                if "ssm_states_advanced" in r]
        text = obs.REGISTRY.to_prometheus()
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()
    by_uid = {r.uid: r.tokens for r in rep.results}
    assert by_uid[0] == _greedy(ref, weights, w, prompts[3], 30)
    for uid, p, n in ((1, 0, 6), (2, 1, 7), (3, 2, 5)):
        assert by_uid[uid] == _greedy(ref, weights, w, prompts[p], n)
    dec = [r for r in recs if not r.get("chunk_tokens") and r["occupancy"]]
    assert dec and all(r["ssm_states_advanced"] == 3 * r["occupancy"]
                       for r in dec)
    # The state pool beside the K/V pool: 3 layers x 2 slots of 4 x 16 x 32
    # float32 states, and of 3 conv rows of 192.
    want = {"ssm_state": 3 * 2 * 4 * 16 * 32 * 4, "ssm_tail": 3 * 2 * 576 * 4}
    kv = rep.as_dict()["kv"]
    assert kv["state_pool_bytes"] == want
    assert kv["pool_bytes"] == kv["pool_blocks"] * BLOCK * kv["token_bytes"]
    for pool, size in want.items():
        assert f'serving_state_pool_bytes{{pool="{pool}"}} {size}' in text \
            or f'serving_state_pool_bytes{{pool="{pool}"}} {float(size)}' \
            in text
    assert 'cache="paged_state"' in text
    leak = eng.leak_report()
    assert leak["blocks_used"] == 0 == leak["blocks_reserved"]


def test_model_config_serves_the_family_on_its_own_weights(tmp_path):
    """``--model-config`` with this family's keys: the program draws a stack
    a kind itself and serves through ``SlotServer``, like the others."""
    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    path = tmp_path / "model.json"
    path.write_text(json.dumps(SMALL))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "24", "--max-new-tokens", "4",
                      "--dtype", "float32", "--prefix-block", "4",
                      "--prefill-chunk", "8", "--model-config", str(path)])
    setup = cli.build_serve_engine(cfg, None)
    p = setup.params
    assert p["ssm"]["A_log"].shape == (3, 4) and "wout" in p
    assert "ln1" not in p["ssm"] and p["attn"]["ln1"].shape == (3, 64)
    eng = setup.make_engine()
    assert eng.cache.ssm_state.shape == (3, 2, 2, 32, 32)   # pack 2 at 16
    assert eng.cache.ssm_tail.shape == (3, 2, 3 * 192)
    assert eng.cache.k.shape[0] == 3
    rep = eng.serve([Request(uid=0, prompt=list(range(1, 22)),
                             max_new_tokens=4)])
    assert len(rep.results[0].tokens) == 4
    with pytest.raises(ValueError, match="state pool.*prefix cache"):
        SlotServer(p, setup.tcfg, slots=2, cache_len=32, kv_block=4,
                   prefix_cache=True, prefix_block=4)
