"""Latent attention, the expert layer that is told which experts it holds,
and the model handed in as data — held against the benchmark's plain
references (``benchmark/references/deepseek_mla_moe.py``,
``longcat_scmoe.py``) at a small size, on the CPU, with seeded weights and the
Pallas kernels in interpret mode. What both latent families share is one test
with a case a family (the ``fam`` fixture); what only the shortcut-connected
double layer has is in ``tests/test_shortcut_moe.py``.
"""

import dataclasses
import functools
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tree_attention_tpu import obs
from tree_attention_tpu.models import experts, latent
from tree_attention_tpu.models.decode import (
    PagedLatentCache,
    cache_token_bytes,
    init_paged_cache,
)
from tree_attention_tpu.models.transformer import (
    model_from_config,
    rms_norm,
)
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.serving import SlotServer
from tree_attention_tpu.serving.engine import Request

from tests.jitted import step_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The published keys at a small size: 16 routed experts in 4 groups (best 2,
# top 3), this share holds experts 4-7; YaRN with an original length of 64.
SMALL = {
    "family": "deepseek_mla_moe", "model_type": "deepseek_v2",
    "hidden_size": 64, "intermediate_size": 128, "kv_lora_rank": 32,
    "q_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 4,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "topk_method": "group_limited_greedy",
    "routed_scaling_factor": 16, "norm_topk_prob": False,
    "scoring_func": "softmax", "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "num_hidden_layers": 3, "vocab_size": 128,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "torch_dtype": "float32",
    "deployment": {"experts_total": 16, "expert_share": 1},
}


# The second latent family's published keys at a small size: a double layer
# (2 latent sublayers + 2 dense FFNs, the routed branch beside them), 16
# routed experts + 8 zero-compute ones, a corrected top 3; this share holds
# routed experts 4-7.
SMALL_SC = {
    "family": "longcat_scmoe", "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 48, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 4, "zero_expert_num": 8,
    "zero_expert_type": "identity", "moe_topk": 3, "vocab_size": 128,
    "rms_norm_eps": 1e-5, "rope_theta": 10000000, "torch_dtype": "float32",
    "deployment": {"experts_total": 16, "expert_share": 1},
    "block": {"sublayers": 2, "routed_branch": [0, 1],
              "corrected_choice": True},
    "assumed": {"norm_topk_prob": False,
                "seeded_scales": {"embedding_std": 1.0,
                                  "router_bias_std": 0.01}},
}

PRESETS = {"deepseek_mla_moe": SMALL, "longcat_scmoe": SMALL_SC}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(eq=False)     # hashed by identity: ``_model``'s key
class Family:
    """A latent family's small preset and its two benchmark files."""

    name: str
    config: dict
    ref: object
    adapter: object


def load_family(name):
    return Family(name, PRESETS[name], *(
        _load(os.path.join(ROOT, "benchmark", d, name + ".py"),
              f"_{d}_{name}") for d in ("references", "adapters")))


@pytest.fixture(scope="module", params=sorted(PRESETS))
def fam(request):
    return load_family(request.param)


@pytest.fixture(scope="module")
def ref():
    return load_family("deepseek_mla_moe").ref


@functools.lru_cache(maxsize=None)
def _model(fam, dtype="float32", seed=7):
    config = dict(fam.config, torch_dtype=dtype)
    w = fam.ref.Widths.of(config)
    weights = fam.ref.init_weights(seed, w)
    tcfg = model_from_config(config, max_seq_len=128)
    return config, w, weights, tcfg, fam.adapter.engine_params(weights, w)


def _serve_chunks(params, tcfg, toks, lens, *, block=8, nb=8, chunk=16,
                  quantize_rows=False):
    """Prefill in chunks of ``chunk`` then decode one token a step through
    the paged latent pool (a scrambled block table); the logits of every
    real row, per slot. Two widths, ``chunk`` and one, each one compiled
    program a model (``tests/jitted.py``)."""
    B = len(lens)
    cache = init_paged_cache(tcfg, B, nb * block, B * nb, block=block)
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)[:, ::-1]
    cache = dataclasses.replace(cache, table=table)
    got, pos, stats = [[] for _ in lens], [0] * B, {}
    while any(pos[i] < lens[i] for i in range(B)):
        left = [lens[i] - pos[i] for i in range(B)]
        tq = chunk if all(x >= 4 or x == 0 for x in left) else 1
        t = np.zeros((B, tq), np.int32)
        n = np.zeros((B,), np.int32)
        for i in range(B):
            n[i] = min(tq, left[i])
            t[i, :n[i]] = toks[i, pos[i]:pos[i] + n[i]]
        logits, cache, stats = step_stats(
            params, jnp.asarray(t), cache, jnp.asarray(n), tcfg)
        if quantize_rows:            # an int8 latent row, re-read as such
            kv = cache.kv.astype(jnp.float32)
            s = jnp.max(jnp.abs(kv), axis=-1, keepdims=True) / 127.0
            s = jnp.where(s > 0, s, 1.0)
            cache = dataclasses.replace(
                cache, kv=(jnp.round(kv / s) * s).astype(cache.kv.dtype))
        for i in range(B):
            got[i].append(np.asarray(logits[i, :n[i]]))
            pos[i] += int(n[i])
    return [np.concatenate(g) for g in got], cache, stats


# -- attention ---------------------------------------------------------------


def _expanded_attention(layer, h, positions, tcfg):
    """The published order, written out: per-head keys and values are
    up-projections of every cached ``c_kv``; q.k over ``nope + rope``,
    p.v over ``v_head``. ``(B, H, T, v_head)``, causal."""
    la, (B, T, _) = tcfg.mla, h.shape
    freqs = latent.rope_frequencies(la.rope, tcfg.rope_theta, la.yarn)
    c_q = la.q_scale * rms_norm(h @ layer["wqa"], layer["q_ln"],
                                tcfg.norm_eps)
    q = (c_q @ layer["wqb"]).reshape(B, T, tcfg.n_heads, la.nope + la.rope)
    q = jnp.concatenate([
        q[..., :la.nope],
        latent.rope_halves(q[..., la.nope:], positions, freqs)], -1)
    kva = h @ layer["wkva"]
    c_kv = la.kv_scale * rms_norm(kva[..., :la.kv_rank], layer["kv_ln"],
                                  tcfg.norm_eps)
    k_rope = latent.rope_halves(kva[..., la.kv_rank:], positions, freqs)
    k_nope = jnp.einsum("btc,hnc->bthn", c_kv, layer["wkb"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None], k_nope.shape[:3] + (la.rope,))], -1)
    v = jnp.einsum("btc,hcv->bhtv", c_kv, layer["wvb"])
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   precision="highest") * latent.softmax_scale(la)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhts,bhsv->bhtv", jax.nn.softmax(s, -1), v,
                      precision="highest")


def test_absorbed_equals_expanded_attention(fam):
    """Both orders of the arithmetic, with the latents' scales where the
    family has them (2 on ``c_q``, 1.41 on ``c_kv`` at the small preset's
    ranks)."""
    _, _, _, tcfg, params = _model(fam)
    la = tcfg.mla
    if tcfg.sublayers > 1:
        assert la.q_scale == pytest.approx((64 / 48) ** 0.5)
        assert la.kv_scale == pytest.approx(2 ** 0.5)
        layer = jax.tree.map(lambda a: a[0], params["layers"]["sub"][0])
    else:
        assert la.q_scale == la.kv_scale == 1.0
        layer = jax.tree.map(lambda a: a[0], params["dense"])
    B, T = 2, 24
    h = jax.random.normal(jax.random.PRNGKey(0), (B, T, tcfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    rows, q_abs = latent.latent_qkv(layer, h, positions, tcfg)
    pool = rows.reshape(B * 3, 8, la.row)            # 3 blocks a slot
    table = jnp.arange(B * 3, dtype=jnp.int32).reshape(B, 3)
    out_lat, _ = latent.latent_attention_reference(
        q_abs, pool, table, q_offset=jnp.zeros((B,), jnp.int32),
        scale=latent.softmax_scale(la), rank=la.kv_rank)
    absorbed = jnp.einsum("bhtc,hcv->bhtv", out_lat, layer["wvb"])
    np.testing.assert_allclose(
        absorbed, _expanded_attention(layer, h, positions, tcfg), atol=1e-5)


def test_yarn_rotary_matches_the_written_formula(ref):
    w = ref.Widths.of(SMALL)
    tcfg = model_from_config(SMALL)
    yarn, dim, base = tcfg.mla.yarn, tcfg.mla.rope, tcfg.rope_theta

    def corr(rot):
        return dim * math.log(yarn.original_len / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    i = np.arange(dim // 2)
    plain = base ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = plain / 40 * ramp + plain * (1 - ramp)
    got = np.asarray(latent.rope_frequencies(dim, base, yarn))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_frequencies(w), want, rtol=1e-6)
    assert 0 < ramp.sum() < dim // 2, "the ramp must blend, not switch"
    assert latent.rope_amplitude(yarn) == 1.0
    assert latent.softmax_scale(tcfg.mla) == pytest.approx(
        24 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    # Across (and past) the original length: rotation by position.
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 96, dim))
    pos = jnp.arange(96)[None]
    rot = np.asarray(latent.rope_halves(x, pos, jnp.asarray(want)))
    ang = np.arange(96)[:, None] * want
    x1, x2 = np.asarray(x[0, :, :dim // 2]), np.asarray(x[0, :, dim // 2:])
    np.testing.assert_allclose(
        rot[0], np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                x1 * np.sin(ang) + x2 * np.cos(ang)], -1),
        atol=1e-5)


@pytest.mark.parametrize("tq", [1, 5, 40])
def test_paged_kernel_equals_the_gathered_reference(tq):
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_mla_paged,
    )

    rng = np.random.default_rng(tq)
    B, H, W, rank, block, NB, N = 3, 8, 48, 32, 8, 8, 40
    pool = jnp.asarray(rng.normal(size=(N, block, W)), jnp.float32)
    table = jnp.asarray(rng.permutation(N)[:B * NB].reshape(B, NB), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, tq, W)), jnp.float32)
    kw = dict(q_offset=jnp.asarray([0, 17, 23], jnp.int32), scale=0.2,
              rank=rank)
    o1, l1 = attention_pallas_mla_paged(q, pool, table, interpret=True, **kw)
    o2, l2 = latent.latent_attention_reference(q, pool, table, **kw)
    np.testing.assert_allclose(o1, o2, atol=1e-5)
    np.testing.assert_allclose(l1, l2, atol=1e-5)


# (slots, table width, block, rows a slot, heads, where each slot's first row
# sits: None = ragged over the capacity): the table widths take 4, 2 and 1
# blocks a grid step; lengths at 0, a step's edge and the capacity; Tq 1 and
# a chunk of rows; one Q tile and several.
MLA_KERNEL = {
    "ragged_width12_tq5": (3, 12, 4, 5, 4, None),
    "ragged_width6_tq1": (5, 6, 4, 1, 4, None),
    "ragged_width7_one_block_a_step_tq1": (4, 7, 8, 1, 4, None),
    "edges_width12_tq1": (6, 12, 4, 1, 4, [0, 15, 16, 31, 32, 47]),
    "full_width8_tq1": (3, 8, 4, 1, 4, [31, 31, 31]),
    "chunk_two_q_tiles_width8_tq300": (2, 8, 64, 300, 4, [0, 130]),
    "chunk_tail_reaches_a_step_width12_tq9": (4, 12, 4, 9, 8, [7, 8, 23, 39]),
}


@pytest.mark.parametrize("name", sorted(MLA_KERNEL))
def test_paged_kernel_walks_its_live_steps(name):
    """``mla_decode_paged`` on its list of live steps (ISSUE 37): held to
    the plain reference, and to the bits of the rectangular grid it launched
    before (``tests/paged_rectangle.py``), with the plan built in the call
    and handed in."""
    from tests import paged_rectangle
    from tree_attention_tpu.ops.pallas_decode import (
        attention_pallas_mla_paged, mla_plan,
    )

    B, NB, block, Tq, H, starts = MLA_KERNEL[name]
    rng = np.random.default_rng(sorted(MLA_KERNEL).index(name))
    rank, rope = 32, 8
    W = rank + rope
    N = B * NB + 2
    q = jnp.asarray(rng.normal(size=(B, H, Tq, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(N, block, W)), jnp.float32)
    table = jnp.asarray(rng.permutation(N)[:B * NB].reshape(B, NB), jnp.int32)
    if starts is None:
        starts = rng.integers(0, NB * block - Tq + 1, size=B)
    q_offset = jnp.asarray(starts, jnp.int32)
    kw = dict(q_offset=q_offset, scale=0.3, rank=rank)
    o1, l1 = attention_pallas_mla_paged(q, pool, table, interpret=True, **kw)
    o2, l2 = latent.latent_attention_reference(q, pool, table, **kw)
    np.testing.assert_allclose(o1, o2, atol=2e-5)
    np.testing.assert_allclose(l1, l2, atol=2e-5)
    o3, l3 = paged_rectangle.attention_pallas_mla_paged(
        q, pool, table, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o3))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l3))
    # A layer loop's call: the plan built once, shifted with the table.
    o4, l4 = attention_pallas_mla_paged(
        q, jnp.concatenate([jnp.zeros_like(pool), pool]), N + table,
        interpret=True, step_plan=mla_plan(Tq, pool, table, q_offset)
        .shifted(N), **kw)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o4))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l4))


# -- the whole model through the paged latent pool ---------------------------


def test_chunked_prefill_then_decode_equals_the_reference_forward(fam):
    _, w, weights, tcfg, params = _model(fam)
    toks = np.random.default_rng(0).integers(0, 128, (2, 40))
    lens = [40, 29]
    got, cache, _ = _serve_chunks(params, tcfg, toks, lens)
    assert isinstance(cache, PagedLatentCache)
    # One layer of rows for every attention: the pool's depth is the
    # model's, not its layer count.
    assert cache.kv.shape[0] == tcfg.cache_layers \
        == tcfg.n_layers * tcfg.sublayers
    for i, n in enumerate(lens):
        want = fam.ref.logits_at(weights, w, toks[i, :n], np.arange(n),
                                 pad_to=64)
        np.testing.assert_allclose(got[i], want, atol=2e-5)


def test_bfloat16_within_tolerance_and_an_int8_latent_row_fails(fam):
    """In float32 the program reads the reference's logits to 2e-5; with the
    cached rows rounded to int8 (per token) it misses that twentyfold
    (4e-4): the tight comparison is the one an int8 latent row fails. In bfloat16
    (weights and activations; the reference in float32 on the same rounded
    weights) the logits differ by rounding, a near-tied router choice that
    falls the other way included: 0.004-0.022 at most over seeds here, held
    to 0.04. At this width an int8 row, 8 bits a value like bfloat16's
    mantissa, lies inside that; at the published widths the cell's
    ``correct`` gate tells them apart (PERF.md section 6, PR 27).

    The double-layer family's preset draws its embedding at std 1 (its
    configuration file's ``assumed.residual_scale``), so a sublayer's
    attention is a smaller part of the residual and of the logits: float32
    reads 1.0-1.5e-7 over seeds, an int8 row 1.3-1.8e-5 (a hundredfold, and
    over the tight limit of 2e-6), bfloat16 0.0030-0.0033, held to 0.01."""
    tight, int8_over, bf16 = {"deepseek_mla_moe": (2e-5, 2e-4, 0.04),
                              "longcat_scmoe": (2e-6, 1e-5, 0.01)}[fam.name]
    toks = np.random.default_rng(3).integers(0, 128, (2, 40))
    lens = [40, 32]

    def worst(dtype, **kw):
        _, w, weights, tcfg, params = _model(fam, dtype)
        got, _, _ = _serve_chunks(params, tcfg, toks, lens, **kw)
        return max(np.abs(g - fam.ref.logits_at(
            weights, w, toks[i, :n], np.arange(n), pad_to=64)).max()
            for i, (g, n) in enumerate(zip(got, lens)))

    assert worst("float32") < tight
    assert worst("float32", quantize_rows=True) > int8_over
    assert worst("bfloat16") < bf16


# -- the expert layer --------------------------------------------------------


def test_router_choice_equals_the_reference_on_10000_rows(ref):
    w = ref.Widths.of(SMALL)
    ex = model_from_config(SMALL).moe
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(10_000, 16)).astype(np.float32) * 1.4
    logits[:100] = np.round(logits[:100])          # ties: lowest index wins
    scores = jax.nn.softmax(jnp.asarray(logits), -1)
    idx, wt = experts.route(scores, ex)
    ridx, rwt = ref.route(scores, w)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(wt, rwt, rtol=1e-6)
    # Group limit: every choice lies in one of the 2 best groups; scale 16.
    groups = np.asarray(idx) // 4
    assert all(len(set(g)) <= 2 for g in groups)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), 1)
    np.testing.assert_allclose(wt, picked * 16, rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The four shares' routed sums plus the shared experts counted once =
    the uncut reference layer; the program's layer = the reference's share."""
    config = dict(SMALL, n_routed_experts=16,
                  deployment={"experts_total": 16, "expert_share": 0})
    w_all = ref.Widths.of(config)
    p_all = jax.tree.map(lambda a: a[0], ref.init_weights(11, w_all)["moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
    uncut = ref.expert_ffn(h, p_all, w=w_all, quant=None)
    total = ref.expert_ffn(h, p_all, w=w_all, quant=None, held=0)  # shared
    for share in range(4):
        cut = dict(p_all, **{n: p_all[n][4 * share:4 * share + 4]
                             for n in ("we1", "we3", "we2")})
        part = ref.expert_ffn(h, cut, w=w_all, quant=None, shared=False,
                              held_first=4 * share, held=4)
        total = total + part
        ex = dataclasses.replace(model_from_config(SMALL).moe,
                                 held_first=4 * share)
        mine, _ = experts.expert_layer(
            {**cut, "router": p_all["router"]}, h[None],
            dataclasses.replace(ex, shared_width=0))
        np.testing.assert_allclose(mine[0], part, atol=1e-5)
    np.testing.assert_allclose(total, uncut, atol=1e-5)


# The branches of the kernel's block plan (``ops/tuning.py`` ``GroupedPlan``)
# at a small shape, k 256 x n 384: ``None`` is the plan function's own.
# (tk, tn, the rows' tile at whole k), the row tile being the layout's.
_PLANS = {
    "plan_function": None,
    "k_tiles_and_strips": (128, 128, False),
    "k_tiles_rows_whole": (128, 384, True),
    "whole_k_whole_n": (256, 384, False),
    "whole_k_strips": (256, 128, False),
}
# (rows, row tile, rows an expert): today's three layouts first.
_LAYOUTS = {
    "an_empty_expert": (128, 128, [3, 0, 10, 5]),
    "tile_256_straddled": (2048, 256, [700, 0, 0, 900]),
    "every_pair_past_the_held": (128, 128, [0, 0, 0, 0]),
    "tile_128_straddled_twice": (384, 128, [100, 60, 0, 150]),
    "tile_128_of_chunk_rows": (2048, 128, [700, 0, 1, 900]),
}


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("plan", sorted(_PLANS))
@pytest.mark.parametrize("product", ["one_rhs", "two_rhs", "relu2"])
def test_grouped_matmul_kernel_equals_ragged_dot(product, plan, layout):
    """Interpret mode against ``lax.ragged_dot``. An expert whose rows
    straddle a row tile has two entries: with no ``k`` dimension the second
    finds the first's strip resident, and its rows must still be its own."""
    from tree_attention_tpu.ops.pallas_moe import grouped_matmul
    from tree_attention_tpu.ops.tuning import GroupedPlan

    rng = np.random.default_rng(0)
    k, n, G = 256, 384, 4
    m, tm, sizes = _LAYOUTS[layout]
    rhs = tuple(jnp.asarray(rng.normal(size=(2 * G, k, n)) * 0.1, jnp.float32)
                for _ in range(2 if product == "two_rhs" else 1))
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    gs, tot = jnp.asarray(sizes, jnp.int32), sum(sizes)
    blocks = _PLANS[plan]  # the plan function takes its own row tile
    chosen = None if blocks is None else GroupedPlan(tm, *blocks)
    kw = dict(first_group=G, relu2=product == "relu2")
    x = grouped_matmul(lhs, rhs, gs, interpret=True, plan=chosen, **kw)
    y = grouped_matmul(lhs, rhs, gs, **kw)
    np.testing.assert_allclose(x[:tot], y[:tot], atol=1e-4)


# -- the engine --------------------------------------------------------------


def _engine(tcfg, params, **kw):
    args = dict(slots=3, cache_len=96, prefill_chunk=16, prefix_cache=True,
                prefix_block=8)
    args.update(kw)
    return SlotServer(params, tcfg, **args)


def _greedy(ref, weights, w, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        row = ref.logits_at(weights, w, np.asarray(toks),
                            np.asarray([len(toks) - 1]), pad_to=64)
        toks.append(int(row[0].argmax()))
    return toks[len(prompt):]


def test_slot_server_serves_the_small_preset_like_the_reference(fam):
    """Chunked admission, a prefix hit (every attention's blocks come back:
    the tokens after it are the reference's), a fork and a cancel."""
    from tests.test_serving_fork import ScriptedSource

    ref = fam.ref
    _, w, weights, tcfg, params = _model(fam)
    eng = _engine(tcfg, params)
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 128, 24).tolist()
    prompts = {0: shared + rng.integers(0, 128, 13).tolist(),
               1: rng.integers(0, 128, 21).tolist(),
               2: shared + rng.integers(0, 128, 5).tolist(),   # a prefix hit
               3: rng.integers(0, 128, 18).tolist()}
    reqs = [Request(uid=0, prompt=prompts[0], max_new_tokens=6),
            Request(uid=1, prompt=prompts[1], max_new_tokens=6, fork_at=2),
            Request(uid=2, prompt=prompts[2], max_new_tokens=6,
                    arrival_tick=12),
            Request(uid=3, prompt=prompts[3], max_new_tokens=40,
                    arrival_tick=12)]
    rep = eng.serve(ScriptedSource(eng, reqs, cancels={20: [3]}))
    by = {}
    for r in rep.results:
        by.setdefault(r.uid, []).append(r)
    for uid in (0, 1, 2):
        want = _greedy(ref, weights, w, prompts[uid], 6)
        for r in by[uid]:
            assert r.tokens == want, (uid, r.index)
    assert len(by[1]) == 2                      # the fork's two branches
    assert by[2][0].prefix_hit_tokens >= 16     # whole shared blocks
    assert by[3][0].outcome == "cancelled" and len(by[3][0].tokens) < 40
    want3 = _greedy(ref, weights, w, prompts[3], len(by[3][0].tokens))
    assert by[3][0].tokens == want3
    leak = eng.leak_report()
    assert leak["blocks_used"] == leak["blocks_cached"]
    assert not (leak["blocks_private"] or leak["blocks_reserved"]
                or leak["pins"])


@pytest.mark.parametrize("name, depth", [
    ("deepseek-v2", 5),             # one attention a layer, 5 layers
    ("longcat-flash-omni", 8),      # two a layer, 4 layers
])
def test_pool_bytes_a_token_a_layer_are_the_row(name, depth):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        import json
        config = json.load(f)
    tcfg = model_from_config(config)
    cache = init_paged_cache(tcfg, 2, 128, 2, block=64)
    L, N, block, row = cache.kv.shape
    # 576 values and the 64 zero lanes the file declares under ``assumed``.
    assert "64 zero lanes" in config["assumed"]["row_padding"]
    assert tcfg.mla.row_pad == 64
    assert (L, N, block, row) == (depth, 2, 64, 576 + 64)
    assert L == tcfg.cache_layers
    assert cache.kv.nbytes == L * N * block * (576 + 64) * 2
    assert cache_token_bytes(cache) == depth * (576 + 64) * 2
    assert not hasattr(cache, "k") and not hasattr(cache, "v")


@pytest.mark.parametrize("rank, rope, lanes", [
    (512, 64, 640), (32, 8, 128), (96, 32, 128), (120, 16, 256)])
def test_a_latent_row_is_rounded_up_to_whole_lane_groups(rank, rope, lanes):
    """No option sets the pad: a published ``config.json`` handed to
    ``--model-config`` gets the row the kernel wants."""
    config = dict(SMALL, kv_lora_rank=rank, qk_rope_head_dim=rope)
    tcfg = model_from_config(config)
    assert tcfg.mla.row == lanes and tcfg.mla.row_pad == lanes - rank - rope
    cache = init_paged_cache(tcfg, 1, 16, 2, block=8)
    assert cache.kv.shape[-1] == lanes


def test_expert_counters_ride_the_fetch_and_stay_off_when_off(fam):
    _, _, _, tcfg, params = _model(fam)
    ex = tcfg.moe
    reqs = [Request(uid=i, prompt=list(range(3 + i, 20 + i)),
                    max_new_tokens=5) for i in range(3)]
    eng = _engine(tcfg, params, prefix_cache=False)
    assert not FLIGHT.enabled and not obs.REGISTRY.enabled
    FLIGHT.clear()
    eng.serve(reqs)
    assert obs.REGISTRY.counter("moe_pairs_here").value() == 0
    assert obs.REGISTRY.counter("moe_pairs_zero").value() == 0
    assert not FLIGHT.snapshot()["records"]
    FLIGHT.clear()
    FLIGHT.arm(capacity=256)
    obs.REGISTRY.enable()
    try:
        eng.serve([dataclasses.replace(r, uid=r.uid + 10) for r in reqs])
        recs = [r for r in FLIGHT.snapshot()["records"]
                if "expert_pairs" in r]
        here = obs.REGISTRY.counter("moe_pairs_here").value()
        total = obs.REGISTRY.counter("moe_pairs_total").value()
        zero = obs.REGISTRY.counter("moe_pairs_zero").value()
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()
    assert recs and here == sum(r["expert_pairs"] for r in recs)
    assert zero == sum(r["zero_pairs"] for r in recs)
    assert total == sum(r["routed_pairs"] for r in recs)
    # Every fetched row routes 3 pairs in each of the 2 expert layers.
    assert total % (3 * 2) == 0 and 0 < here < total
    assert (zero > 0) == bool(ex.n_zero)
    for r in recs:
        assert 0 <= r["experts_touched"] <= 2 * 4
        assert r["expert_rows_max"] <= r["expert_pairs"]
        assert r["routed_pairs"] == 3 * r["routed_rows"] > 0
        assert r["routed_rows"] % 2 == 0          # rows x 2 expert layers
        assert r["zero_pairs"] + r["expert_pairs"] <= r["routed_pairs"]
        # Without zero-compute experts every decision's 3 are real ones.
        assert r["real_row_max"] <= 3
        assert ex.n_zero or (r["zero_pairs"], r["real_row_max"]) == (0, 3)


# -- what is refused at build ------------------------------------------------


@pytest.mark.parametrize("kw, named", [
    (dict(quantize=True), "int8 latent rows"),
    (dict(kv_shard="seq"), "sequence-sharded"),
    (dict(host_blocks=4), "host tier"),
    (dict(speculate=True), "tree_mask"),
])
def test_engine_refuses_what_a_latent_pool_does_not_carry(fam, kw, named):
    _, _, _, tcfg, params = _model(fam)
    with pytest.raises(ValueError, match=named):
        _engine(tcfg, params, **kw)


@pytest.mark.parametrize("flags, named", [
    (["--kv-quant", "int8"], "--kv-quant"),
    (["--kv-shard", "seq"], "--kv-shard seq"),
    (["--host-blocks", "4", "--prefix-cache", "--prefix-block", "8"],
     "--host-blocks"),
    (["--speculate"], "--speculate"),
    (["--serve-disagg"], "--serve-disagg"),
])
@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_cli_refuses_by_name_with_a_system_exit(tmp_path, preset, flags,
                                                named):
    import json

    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    path = tmp_path / "model.json"
    path.write_text(json.dumps(PRESETS[preset]))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "16", "--max-new-tokens", "4",
                      "--dtype", "float32", "--model-config", str(path)]
                     + flags)
    with pytest.raises(SystemExit, match=named):
        cli.build_serve_engine(cfg, None)


def test_model_config_builds_the_dense_block_too(tmp_path):
    """``--model-config`` with a Llama-style file builds the dense block at
    the file's widths (what the flags cannot say: the MLP width, the rotary
    base, the norm's epsilon)."""
    import json

    from tree_attention_tpu import cli
    from tree_attention_tpu.utils.config import parse_args

    path = tmp_path / "dense.json"
    path.write_text(json.dumps({
        "hidden_size": 64, "intermediate_size": 160,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 96, "rope_theta": 5e6,
        "rms_norm_eps": 1e-5}))
    cfg = parse_args(["--mode", "serve", "--device", "cpu", "--slots", "2",
                      "--prompt-len", "16", "--max-new-tokens", "4",
                      "--dtype", "float32", "--model-config", str(path)])
    setup = cli.build_serve_engine(cfg, None)
    t = setup.tcfg
    assert t.dense_block and (t.d_ff, t.n_kv_heads, t.d_head, t.rope_theta,
                              t.norm_eps) == (160, 2, 16, 5e6, 1e-5)
    rep = setup.make_engine().serve(
        [Request(uid=0, prompt=[1, 2, 3, 4, 5], max_new_tokens=4)])
    assert len(rep.results[0].tokens) == 4
