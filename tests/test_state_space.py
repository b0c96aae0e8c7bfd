"""State-space (Mamba-2) mixers beside one attention layer with no positional
term and LatentMoE feed-forward parts (routed experts in a latent, ungated,
relu squared, a shared expert on the full width), a layer that is a mixer or
a mixer and a feed-forward half: a cache that is a paged K/V pool AND a
recurrent state a slot, which every token rewrites whole. Held against the
benchmark's plain reference (``benchmark/references/nemotron_h.py``: the full
forward pass over one sequence, the state-space layer the token-by-token
recurrence, no cache) at a small size, on the CPU, in float32, with seeded
weights.

Tolerances. Logits here have a standard deviation of ~0.8. The program and
the reference add the same float32 numbers in other orders (the chunked scan's
matrix products against the recurrence, attention over a gathered view against
one softmax over a row, the experts' sum over sorted pairs against a loop over
experts): their logits agree to ~1e-6 and are held to ``ATOL`` 2e-5. What a
test shows to be DIFFERENT (a slot that moved against one that sat out, the
int8 control) differs by 1e-3 or more. The scan against a float64 recurrence
on inputs of order 1: 2e-4.
"""

import dataclasses
import functools
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tree_attention_tpu import obs
from tree_attention_tpu.models.decode import (
    PagedStateCache,
    forward_step,
    init_paged_cache,
)
from tree_attention_tpu.models.hybrid import (
    layer_runs,
    pack_state,
    scan_path,
    ssm_scan,
    ssm_step,
    unpack_state,
)
from tree_attention_tpu.models.transformer import (
    StateSpace,
    model_from_config,
)
from tree_attention_tpu.obs.flight import FLIGHT
from tree_attention_tpu.ops.pallas_moe import UNGATED_KERNEL, grouped_matmul
from tree_attention_tpu.ops.pallas_ssm import (
    SCAN_KERNEL,
    SSM_KERNEL,
    _ssm_scan_call,
    _ssm_update_call,
    live_list,
    ssm_chunk_scan,
    ssm_decode_update,
)
from tree_attention_tpu.serving import SlotServer
from tree_attention_tpu.serving.engine import Request

from tests.jitted import serve_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5
BLOCK = 4
# The one chunk width the step helpers compile: two blocks of the scan (its
# ``chunk_size`` is 8), so a step of 3, 8, 13 or 16 rows is the same program
# and its raggedness rides in the length vector.
WIDTH = 16

# The family's published keys at a small size: four of this repo's layers in
# four runs (an ssm layer with experts, one with no feed-forward half, the
# attention layer with experts, an ssm layer alone), the second of two shares
# of 4 of 8 experts, top 3, in a latent of 32 under a residual of 64.
SMALL = {
    "family": "nemotron_h", "model_type": "nemotron_h", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 48, "num_hidden_layers": 6,
    "hybrid_override_pattern": "MEM*EM",
    "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "use_conv_bias": True, "mamba_proj_bias": False,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 5,
    "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
    "time_step_min": 0.001, "time_step_max": 0.1,
    "tie_word_embeddings": False, "vocab_size": 128,
    "torch_dtype": "float32",
    "deployment": {"experts_total": 8, "expert_share": 1},
    "block": {"rotary_layers": "none", "router_scoring": "sigmoid",
              "corrected_choice": True, "scale_renormed": True,
              "latent_proj_plain": True, "gate_before_norm": True},
    "assumed": {"seeded_scales": {
        "embedding_std": 1.0, "head_std": 0.1, "ssm_out_std": 0.03,
        "attn_out_std": 0.05, "expert_down_std": 0.5,
        "latent_up_std": 0.3, "shared_down_std": 0.02, "gain_mean": 1.5,
        "gain_std": 0.1, "router_bias_std": 0.02}},
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
                              "nemotron_h.py"), "_references_nemotron_h")


@pytest.fixture(scope="module")
def adapter():
    return _load(os.path.join(ROOT, "benchmark", "adapters", "nemotron_h.py"),
                 "_adapters_nemotron_h")


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


def _greedy(ref, weights, w, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        row = _want(ref, w, weights, toks, [len(toks) - 1])
        toks.append(int(row[0].argmax()))
    return toks[len(prompt):]


def _engine(tcfg, params, **kw):
    args = dict(slots=3, cache_len=96, prefill_chunk=8, kv_block=BLOCK)
    args.update(kw)
    return SlotServer(params, tcfg, **args)


def _cache(tcfg, slots=2, nb=16):
    cache = init_paged_cache(tcfg, slots, nb * BLOCK, slots * nb, block=BLOCK)
    assert isinstance(cache, PagedStateCache)
    table = jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb)[:, ::-1]
    return dataclasses.replace(cache, table=table)


# -- the model as data (e) ---------------------------------------------------


def test_the_catalogs_config_verbatim_builds_48_layers_of_the_right_kinds():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in open(path)]
    c = next((r["config"] for r in rows
              if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"), None)
    if c is None:
        pytest.skip("the catalog has no Nemotron 3 Super row")
    t = model_from_config(dict(c, block=SMALL["block"]), max_seq_len=256)
    # 88 published parts: 40 M + 8 * open a layer each, 40 E are halves.
    assert t.n_layers == 48 and t.cache_kind == "state"
    assert (t.ssm_layers, t.cache_layers, t.n_expert_layers) == (40, 8, 40)
    assert t.ssm == StateSpace(n_heads=128, d_head=64, n_groups=8,
                               d_state=128, taps=4, chunk=128)
    assert (t.ssm.inner, t.ssm.conv_dim, t.ssm.in_dim) == (8192, 10240, 18560)
    assert (t.ssm.pack, t.ssm.state_shape) == (2, (64, 128, 128))
    assert (t.d_model, t.n_heads, t.n_kv_heads, t.d_head) == (4096, 32, 2, 128)
    assert (t.vocab_size, t.tied_head, t.norm_eps) == (131072, False, 1e-5)
    ex = t.moe
    assert (ex.n_experts, ex.per_token, ex.width, ex.latent, ex.shared_width,
            ex.gated, ex.scale, ex.renorm) == (
        512, 22, 2688, 1024, 5376, False, 5.0, True)
    assert t.rotary == () and not t.rotates("attention")
    # The benchmark's cut: the model's first 11 parts, 6 layers in 4 runs.
    cut = model_from_config(dict(c, block=SMALL["block"], num_hidden_layers=11,
                                 hybrid_override_pattern="MEMEMEM*EME"))
    assert [r[:3] for r in layer_runs(cut)] == [
        ("ssm", "expert", 3), ("ssm", "none", 1), ("attention", "expert", 1),
        ("ssm", "expert", 1)]


def test_the_small_files_keys_say_what_each_layer_is(model):
    _, _, t, params = model
    assert t.layer_types == ("ssm", "ssm", "attention", "ssm")
    assert t.ffn_kinds == ("expert", "none", "expert", "none")
    assert [r[:2] for r in layer_runs(t)] == [
        ("ssm", "expert"), ("ssm", "none"), ("attention", "expert"),
        ("ssm", "none")]
    assert t.cache_kind == "state" and not t.dense_block
    assert (t.ssm_layers, t.cache_layers, t.n_expert_layers) == (3, 1, 2)
    assert (t.moe.held, t.moe.held_first, t.moe.n_experts) == (4, 4, 8)
    assert t.moe.leaves == ("we1", "we2") and t.moe.latent == 32
    assert params["ssm"]["w_in"].shape == (3, 64, 128 + 192 + 8)
    assert params["ssm"]["conv_w"].shape == (3, 4, 192)
    assert params["layers"]["we1"].shape == (2, 4, 32, 48)
    assert "we3" not in params["layers"] and "ws3" not in params["layers"]


@pytest.mark.parametrize("change, named", [
    ({"hybrid_override_pattern": "EMM*EM"}, "no mixer before it"),
    ({"hybrid_override_pattern": "MEEM*E"}, "no mixer before it"),
    ({"hybrid_override_pattern": "MEMXEM"}, "character 'X'"),
    ({"hybrid_override_pattern": "MEM*E"}, "num_hidden_layers"),
    ({"layer_types": ["conv"] * 6}, "beside layer_types"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
    ({"mlp_hidden_act": "gelu"}, "mlp_hidden_act"),
    ({"expand": 3}, "expand"),
    ({"n_groups": 3}, "groups"),
    ({"n_group": 4}, "n_group"),
    ({"moe_expert_bias": True}, "moe_expert_bias"),
    ({"block": dict(SMALL["block"], gate_before_norm=False)},
     "gate_before_norm"),
    ({"block": dict(SMALL["block"], latent_proj_plain="norm")},
     "latent_proj_plain"),
    ({"block": dict(SMALL["block"], rotary_layers="mamba")}, "rotary_layers"),
])
def test_each_refused_key_is_refused_by_its_name(change, named):
    with pytest.raises(ValueError, match=named):
        model_from_config(dict(SMALL, **change))


# -- the chunked scan against the recurrence (b) -----------------------------


def _recurrence(x, dt, A, B, C, s0):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t . C_t, one
    token after another, in float64."""
    x, dt, A, B, C, s = (np.asarray(t, np.float64)
                         for t in (x, dt, A, B, C, s0))
    rep = x.shape[2] // B.shape[2]
    ys = np.zeros_like(x)
    for t in range(x.shape[1]):
        Bh, Ch = np.repeat(B[:, t], rep, 1), np.repeat(C[:, t], rep, 1)
        s = np.exp(dt[:, t] * A)[..., None, None] * s \
            + (dt[:, t, :, None] * x[:, t])[..., None] * Bh[:, :, None, :]
        ys[:, t] = np.sum(s * Ch[:, :, None, :], -1)
    return ys, s


@pytest.mark.parametrize("T, chunk, n_valid", [
    (8, 8, (8, 8)), (13, 8, (13, 5)), (16, 8, (16, 0)), (24, 8, (9, 17)),
    (5, 8, (5, 1)), (16, 4, (7, 16)),
])
def test_the_chunked_scan_equals_the_recurrence(T, chunk, n_valid):
    """Chunk ends off the multiples of ``chunk`` (the scan pads with rows
    of dt 0) and rows past a member's valid count (dt 0, masked by the
    caller): the scan's ``y`` on the valid rows and its final state are the
    recurrence's over the valid rows alone."""
    rng = np.random.default_rng(T * 31 + chunk)
    b, H, P, G, N = 2, 4, 8, 2, 16
    x = rng.normal(size=(b, T, H, P)).astype(np.float32)
    B = rng.normal(size=(b, T, G, N)).astype(np.float32)
    C = rng.normal(size=(b, T, G, N)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (b, T, H)))
    valid = np.arange(T)[None, :] < np.asarray(n_valid)[:, None]
    dt = np.where(valid[..., None], dt, 0.0).astype(np.float32)
    A = -rng.uniform(1, 16, (H,)).astype(np.float32)
    s0 = rng.normal(size=(b, H, P, N)).astype(np.float32)
    y, s1 = ssm_scan(*(jnp.asarray(t) for t in (x, dt, A, B, C, s0)), chunk)
    for i, n in enumerate(n_valid):
        wy, ws = _recurrence(x[i:i + 1, :n], dt[i:i + 1, :n], A,
                             B[i:i + 1, :n], C[i:i + 1, :n], s0[i:i + 1])
        np.testing.assert_allclose(y[i, :n], wy[0], atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(s1[i], ws[0], atol=2e-4, rtol=1e-4)
        if n == 0:      # no valid row: the state bit for bit
            np.testing.assert_array_equal(np.asarray(s1[i]), s0[i])


def test_the_pool_layout_packs_heads_side_by_side_and_back():
    sm = StateSpace(n_heads=8, d_head=16, n_groups=2, d_state=16, taps=4)
    assert (sm.pack, sm.state_shape) == (4, (2, 16, 64))
    s = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8, 16, 16)),
                    jnp.float32)
    packed = pack_state(s, sm)
    assert packed.shape == (3, 2, 16, 64)
    # S[h, p, n] lies at [h // pack, n, (h % pack) * d_head + p].
    assert float(packed[1, 1, 5, 2 * 16 + 3]) == float(s[1, 6, 3, 5])
    np.testing.assert_array_equal(np.asarray(unpack_state(packed, sm)),
                                  np.asarray(s))


# -- the kernel --------------------------------------------------------------


def _kernel_case(live, Hp, G, slots, seed, N=16):
    """One call of the kernel on layer 1 of 3 in interpret mode, at ``slots``
    slots a phase (``None``: the rule's), against ``ssm_step`` under the
    same ``jit`` (XLA's CPU backend fuses ``a * S + b * x`` there as it does
    in the interpreted kernel): ``(pool, new pool, y, wanted states, wanted
    y)``."""
    rng = np.random.default_rng(seed)
    S, layers, L, m = len(live), 3, 128, 1
    state = jnp.asarray(rng.normal(size=(layers * S, Hp, N, L)), jnp.float32)
    x, a = (jnp.asarray(rng.normal(size=(S, Hp, L)), jnp.float32)
            for _ in range(2))
    b, c = (jnp.asarray(rng.normal(size=(S, G, N)), jnp.float32)
            for _ in range(2))
    ids, count = live_list(jnp.asarray(live, jnp.int32))
    assert int(count[0]) == sum(live)
    assert ids[:sum(live)].tolist() == [i for i, v in enumerate(live) if v]
    if slots is None:
        new, y = ssm_decode_update(state, x, a, b, c, ids, count, m * S,
                                   interpret=True)
    else:
        new, y = _ssm_update_call(state, x, a, b, c, ids, count,
                                  jnp.full((1,), m * S, jnp.int32),
                                  interpret=True, slots=slots)
    want, wy = jax.jit(ssm_step)(state[m * S:(m + 1) * S], x, a, b, c)
    return tuple(np.asarray(t) for t in (state, new, y, want, wy))


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0), (0, 0, 0, 0, 0),
                                  (1, 1, 1, 1, 1)])
def test_the_decode_kernel_advances_the_live_slots_in_place(live):
    """Interpret mode against ``ssm_step``: the slots in the list get the
    recurrence's next state and ``S . C``; a slot with no row keeps its
    state bit for bit; so does every other layer's; an empty list changes
    nothing."""
    S, m = len(live), 1
    state, new, y, want, wy = _kernel_case(live, Hp=4, G=2, slots=None,
                                           seed=sum(live))
    for s in range(S):
        if live[s]:
            np.testing.assert_allclose(new[m * S + s], want[s], atol=1e-5)
            np.testing.assert_allclose(y[s], wy[s], atol=1e-4)
        else:
            np.testing.assert_array_equal(new[m * S + s], state[m * S + s])
    for other in (0, 2):
        np.testing.assert_array_equal(new[other * S:(other + 1) * S],
                                      state[other * S:(other + 1) * S])
    assert SSM_KERNEL == "ssm_decode_update"


# Slots a phase against the live count: phases that divide the list and one
# that does not, one phase that holds it all, a list shorter than a phase,
# an empty list, a list with gaps; several groups of heads and one. (The
# list is data: the cases of one shape and one phase size share a compile,
# three in all: tier-1 runs close to its time limit.)
@pytest.mark.parametrize("live,Hp,G,slots", [
    (live, Hp, G, slots)
    for Hp, G, sizes in ((4, 2, (1, 4)), (2, 1, (2,)))
    for slots in sizes
    for live in ((1, 1, 1, 1, 1, 1, 1, 1),      # whole phases at 1, 2, 4
                 (1, 0, 1, 1, 0, 1, 1, 0),      # 5 live, gaps: a short last
                 (0, 0, 0, 0, 0, 1, 0, 0),      # 1 live: under a phase
                 (0, 0, 0, 0, 0, 0, 0, 0),      # an empty list
                 (1, 1, 1, 0, 1, 1, 1, 1))      # 7 live: a last of 1 or 3
], ids=lambda v: "".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_the_decode_kernel_in_phases_writes_ssm_steps_bits(live, Hp, G, slots):
    """Whatever the phases: the state of a live slot is BIT-EQUAL to
    ``ssm_step``'s, a slot off the list holds the bits it held, ``y`` agrees
    to 1e-6."""
    S, m = len(live), 1
    state, new, y, want, wy = _kernel_case(live, Hp, G, slots,
                                           seed=slots + Hp)
    on = np.asarray(live, bool)
    mine = new[m * S:(m + 1) * S]
    np.testing.assert_array_equal(mine[on], want[on])
    np.testing.assert_allclose(y[on], wy[on], atol=1e-6, rtol=0)
    np.testing.assert_array_equal(mine[~on], state[m * S:(m + 1) * S][~on])
    np.testing.assert_array_equal(new[:m * S], state[:m * S])
    np.testing.assert_array_equal(new[(m + 1) * S:], state[(m + 1) * S:])


@pytest.mark.parametrize("live", [(1, 1, 1, 1, 1), (1, 0, 1, 1, 0),
                                  (0, 0, 0, 1, 0), (0, 0, 0, 0, 0)],
                         ids=lambda v: "".join(map(str, v)))
def test_the_decode_kernel_at_a_head_a_row_in_two_groups(live):
    """The kernel's second served shape at a small copy (the two-branch
    configuration's: ``pack`` 1, a head of 128 fills a row; G 2, so ``B`` and
    ``C`` are a ``(2, N)`` tile turned to columns; ``N`` = 2 x ``L``; two
    rows a group): the live slots' states bit-equal to ``ssm_step``'s, a
    slot off the list holds the bits it held, at the rule's phase size and
    at two slots a phase."""
    from tree_attention_tpu.ops import tuning

    S, m = len(live), 1
    on = np.asarray(live, bool)
    assert tuning.ssm_phase_slots(32, 256, 128, 2) == 4 \
        == tuning.ssm_phase_slots(64, 128, 128, 8)
    for slots in (None, 2):
        state, new, y, want, wy = _kernel_case(live, Hp=4, G=2, slots=slots,
                                               seed=7, N=256)
        mine = new[m * S:(m + 1) * S]
        np.testing.assert_array_equal(mine[on], want[on])
        np.testing.assert_allclose(y[on], wy[on], atol=1e-5, rtol=0)
        np.testing.assert_array_equal(mine[~on],
                                      state[m * S:(m + 1) * S][~on])
        np.testing.assert_array_equal(new[:m * S], state[:m * S])
        np.testing.assert_array_equal(new[(m + 1) * S:], state[(m + 1) * S:])


def test_the_phase_rule_follows_the_shapes_and_fits_its_limit():
    """``ops/tuning.py`` ``ssm_phase_slots``: the served shape takes the
    measured four slots a phase; a state too large for that takes fewer,
    down to one; the limit the call asks for holds the buffers."""
    from tree_attention_tpu.ops import tuning

    assert tuning.ssm_phase_slots(64, 128, 128, 8) == 4
    assert tuning.ssm_phase_slots(128, 128, 128, 8) == 2
    assert tuning.ssm_phase_slots(256, 128, 128, 8) == 1
    assert tuning.ssm_phase_slots(4096, 128, 128, 8) == 1
    assert tuning.ssm_phase_slots(4, 16, 128, 2) == 4
    for hp in (4, 64, 128, 256):
        q = tuning.ssm_phase_slots(hp, 128, 128, 8)
        need = tuning.ssm_phase_vmem_bytes(q, hp, 128, 128, 8)
        assert need >= 2 * q * hp * 128 * 128 * 4
        assert need < tuning.ssm_phase_vmem_limit(q, hp, 128, 128, 8)
        assert q == 1 or need <= tuning.SSM_PHASE_VMEM_BYTES


# -- a chunk group's scan as one kernel (ISSUE 51) ---------------------------

# The two served shapes at a small copy, ``(heads, d_head, groups, d_state)``:
# two heads of 64 side by side on a row's lanes (``pack`` 2, the state
# configuration's), and a head of 128 a row under a state wider than the row
# (``pack`` 1, the two-branch configuration's); four rows of heads in two
# groups both.
SCAN_SHAPES = {"two_heads_a_row": (8, 64, 2, 16),
               "a_head_a_row": (4, 128, 2, 256)}
SCAN_CASES = [(shape, tq) for shape in SCAN_SHAPES for tq in (64, 128, 256)]
SCAN_SLOTS, SCAN_LAYER = 5, 1


@functools.lru_cache(maxsize=None)
def _scan_case(shape, tq, none=False):
    """One launch of ``ssm_chunk_scan`` in interpret mode on layer 1 of 3 of
    a pool of five slots, a chunk group of three members: a whole chunk from
    the state slot 3 holds; NO row, pointed at slot 3 too (a member that
    sits a tick out names whatever slot); ``tq - 27`` rows from position 0
    into slot 0, whose old state is poisoned. ``none``: no member has a row.
    Returns the arrays a test compares, as numpy's."""
    H, P, G, N = SCAN_SHAPES[shape]
    sm = StateSpace(n_heads=H, d_head=P, n_groups=G, d_state=N, taps=4)
    rng = np.random.default_rng(tq + H)
    b, S, m = 3, SCAN_SLOTS, SCAN_LAYER
    n_valid = np.asarray((0, 0, 0) if none else (tq, 0, tq - 27), np.int32)
    fresh = np.asarray((0, 0, 1), np.int32)
    home = m * S + np.asarray((3, 3, 0), np.int32)
    pool = rng.normal(size=(3 * S,) + sm.state_shape).astype(np.float32)
    pool[home[2]] = np.nan
    x = rng.normal(size=(b, tq, H, P)).astype(np.float32)
    B, C = (rng.normal(size=(b, tq, G, N)).astype(np.float32)
            for _ in range(2))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (b, tq, H)))
    valid = np.arange(tq)[None, :] < n_valid[:, None]
    dt = np.where(valid[..., None], dt, 0.0).astype(np.float32)
    A = -rng.uniform(1, 16, (H,)).astype(np.float32)
    new, y = ssm_chunk_scan(
        *(jnp.asarray(t) for t in (pool, _wide(x), dt, A, _wide(B), _wide(C),
                                   home, n_valid, fresh)), interpret=True)
    return dict(sm=sm, pool=pool, new=np.asarray(new),
                y=np.asarray(y).reshape(x.shape), x=x, dt=dt, A=A, B=B, C=C,
                home=home, n_valid=n_valid, fresh=fresh)


def _wide(t):
    """``(b, T, heads or groups, width)`` as the projection lays it, the
    last two axes one: the kernel's operands."""
    return t.reshape(t.shape[:2] + (-1,))


def _scan_start(case):
    """The states the members start from, unpacked: zeros for a fresh one."""
    s0 = unpack_state(jnp.asarray(case["pool"][case["home"]]), case["sm"])
    return jnp.where(jnp.asarray(case["fresh"], bool)[:, None, None, None],
                     0.0, s0)


@pytest.mark.parametrize("shape, tq", SCAN_CASES)
def test_the_scan_kernel_equals_the_chunked_scan(shape, tq):
    """``ssm_chunk_scan`` against ``ssm_scan`` from the same states: the
    valid rows' ``y`` and the state a member with a row leaves, at the
    tolerance the chunked scan is held to the recurrence at."""
    case = _scan_case(shape, tq)
    wy, ws = ssm_scan(*(jnp.asarray(case[k]) for k in "x dt A B C".split()),
                      _scan_start(case), case["sm"].chunk)
    for i, n in enumerate(case["n_valid"]):
        if n:
            np.testing.assert_allclose(case["y"][i, :n], wy[i, :n],
                                       atol=2e-4, rtol=1e-4)
            np.testing.assert_allclose(
                case["new"][case["home"][i]], pack_state(ws[i], case["sm"]),
                atol=2e-4, rtol=1e-4)
    assert SCAN_KERNEL == "ssm_chunk_scan" and not any(
        other in SCAN_KERNEL for other in (
            "ssm_decode_update", "flash_decode_paged", "moe_grouped_matmul",
            "moe_ungated_matmul"))


@pytest.mark.parametrize("shape, tq", SCAN_CASES)
def test_the_scan_kernel_equals_the_recurrence_row_by_row(shape, tq):
    """... and against ``ssm_step`` taken ``n_valid`` times on the packed
    state, the rows past a member's count left out of it: what a decode
    tick a token would have left in the pool."""
    case = _scan_case(shape, tq)
    sm = case["sm"]
    rows = (sm.n_heads // sm.pack, sm.pack * sm.d_head)

    @jax.jit
    def walk(s, x, dt, B, C):
        def one(s, t):
            x, dt, b, c = t
            a = jnp.repeat(jnp.exp(dt * case["A"]), sm.d_head).reshape(rows)
            s, y = ssm_step(s[None], (dt[:, None] * x).reshape(rows)[None],
                            a[None], b[None], c[None])
            return s[0], y[0]
        return lax.scan(one, s, (x, dt, B, C))

    start = pack_state(_scan_start(case), sm)
    for i, n in enumerate(case["n_valid"]):
        if n:
            ws, wy = walk(start[i], *(jnp.asarray(case[k][i, :n])
                                      for k in "x dt B C".split()))
            np.testing.assert_allclose(
                case["y"][i, :n].reshape(n, *rows), wy, atol=2e-4, rtol=1e-4)
            np.testing.assert_allclose(case["new"][case["home"][i]], ws,
                                       atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("shape, tq", SCAN_CASES)
def test_the_scan_kernel_writes_the_members_rows_and_no_other(shape, tq):
    """Every pool row of another slot or layer holds the bits it held; the
    member with no row wrote nothing (it names the slot the first member
    writes: that slot holds the first member's state, once) and its ``y`` is
    zeros; the member from position 0 read nothing of the poison its slot
    held."""
    case = _scan_case(shape, tq)
    mine = {int(h) for h, n in zip(case["home"], case["n_valid"]) if n}
    assert len(mine) == 2
    for row in range(case["pool"].shape[0]):
        if row not in mine:
            np.testing.assert_array_equal(case["new"][row],
                                          case["pool"][row])
        else:
            assert np.isfinite(case["new"][row]).all()
            assert np.abs(case["new"][row] - case["pool"][row]).max() > 1e-3 \
                or np.isnan(case["pool"][row]).all()
    assert not case["y"][1].any() and np.isfinite(case["y"]).all()


@pytest.mark.parametrize("shape", sorted(SCAN_SHAPES))
def test_the_scan_kernel_with_no_member_that_has_a_row_changes_nothing(shape):
    """An empty list: the pool bit for bit (the poison too), ``y`` zeros."""
    case = _scan_case(shape, 64, none=True)
    np.testing.assert_array_equal(case["new"], case["pool"])
    assert not case["y"].any()


@pytest.mark.parametrize("block, rows", [(64, 1), (128, 2), (256, 1)])
def test_the_scan_kernels_blocks_are_tuning_not_mathematics(block, rows):
    """Any block length and any rows of heads a grid step: the same state
    and ``y`` as the rule's choice, to rounding."""
    case = _scan_case("two_heads_a_row", 256)
    c = case
    new, y = _ssm_scan_call(
        *(jnp.asarray(t) for t in (
            c["pool"], _wide(c["x"]), c["dt"], c["A"], _wide(c["B"]),
            _wide(c["C"]), c["home"], c["n_valid"], c["fresh"])),
        interpret=True, block=block, rows=rows)
    y = np.asarray(y).reshape(case["y"].shape)
    for i in (0, 2):
        np.testing.assert_allclose(y[i], case["y"][i], atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(new[case["home"][i]],
                                   case["new"][case["home"][i]],
                                   atol=2e-4, rtol=1e-4)


def test_the_scan_path_follows_what_the_program_observes(monkeypatch):
    """``scan_path``: the XLA scan off the TPU whatever the shape; on one,
    the kernel for both served shapes at the cells' three chunk lengths, and
    the XLA scan for a group it cannot cut (rows no multiple of 8, one row)
    and a pool it cannot (not float32, a row of heads that is no whole lane
    tile)."""
    served = [StateSpace(128, 64, 8, 128, 4), StateSpace(32, 128, 2, 256, 4)]
    small = StateSpace(8, 16, 2, 16, 4)
    f32 = lambda sm, dt=jnp.float32: jax.ShapeDtypeStruct(
        (5, 4) + sm.state_shape, dt)
    assert small.pack * small.d_head == 64
    for sm in served:
        assert all(scan_path(tq, sm, f32(sm)) == "xla"
                   for tq in (64, 128, 256))                    # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for sm in served:
        assert sm.pack * sm.d_head == 128
        assert all(scan_path(tq, sm, f32(sm)) == "kernel"
                   for tq in (8, 64, 128, 256))
        assert all(scan_path(tq, sm, f32(sm)) == "xla" for tq in (1, 13, 100))
        assert scan_path(256, sm, f32(sm, jnp.bfloat16)) == "xla"
    assert scan_path(256, small, f32(small)) == "xla"


def test_the_scan_rule_follows_the_shapes_and_fits_its_limit():
    """``ops/tuning.py`` ``ssm_scan_block`` / ``ssm_scan_rows``: the block
    is the measured 128 rows (a shorter chunk is one block), the rows of
    heads a grid step divide a group, and the limit the call asks for holds
    its blocks twice, under the ceiling the other kernels' plans keep to."""
    from tree_attention_tpu.ops import tuning

    assert [tuning.ssm_scan_block(t) for t in (8, 64, 128, 256, 512)] \
        == [8, 64, 128, 128, 128]
    for hp, n, l, g in ((64, 128, 128, 8), (32, 256, 128, 2),
                        (4, 16, 128, 2), (4096, 128, 128, 8)):
        for tq in (64, 128, 256):
            blk = tuning.ssm_scan_block(tq)
            r = tuning.ssm_scan_rows(tq, hp // g, n, l)
            assert (hp // g) % r == 0
            need = tuning.ssm_scan_vmem_bytes(tq, blk, r, n, l)
            assert need >= 2 * r * (2 * n * l + 2 * tq * l) * 4
            assert need < tuning.ssm_scan_vmem_limit(tq, blk, r, n, l) \
                <= tuning.GROUPED_VMEM_CEILING_BYTES


# -- the engine's steps against the reference (a), (d) -----------------------


def _serve_rows(params, tcfg, toks, steps, packed=False, cache=None):
    """Run ``steps`` (rows a slot a step) through the state cache (a new one,
    or ``cache`` from where its lengths stand): the logits of the rows that
    carried a token, and the cache. Every step is one of two compiled
    programs a kind (``tests/jitted.py``): ``WIDTH`` tokens a slot with the
    true counts in ``n_tokens`` / ``chunk_n``, as the engine's tick carries
    them, or one."""
    slots = len(toks)
    cache = _cache(tcfg, slots) if cache is None else cache
    got, pos = [[] for _ in range(slots)], [0] * slots
    for ns in steps:
        rows, cache = serve_step(params, tcfg, cache, toks, pos, ns, WIDTH,
                                 packed=packed)
        for i, row, lg in rows:
            got[i].append((row, lg))
        for i, n in enumerate(ns):
            pos[i] += n
    return got, cache


@pytest.mark.parametrize("chunk", [3, 8, 13, 16])
def test_prefill_in_chunks_then_decode_equals_the_reference(ref, model, chunk):
    """Chunks under the scan's block of 8 (3), at it, off its multiples (13)
    and of two blocks (16), ragged between the slots, then decode: every
    row's logits are the reference's full forward pass. The chunk is how
    many rows a step CARRIES (``n_tokens``): the state leaves a step at
    positions 3, 6, ... or 13, 26 and the next step takes it up there; the
    token block is ``WIDTH`` wide for all four, as an engine's tick is.
    (What the scan does with a block of 5 or 13 rows that it must pad itself
    is ``test_the_chunked_scan_equals_the_recurrence``'s.)"""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(chunk)
    toks = [rng.integers(0, 128, (40,)), rng.integers(0, 128, (31,))]
    steps = []
    for lo in range(0, 26, chunk):
        steps.append([min(chunk, 26 - lo), min(chunk, max(19 - lo, 0))])
    steps += [[1, 1]] * 12 + [[1, 0]] * 2
    got, _ = _serve_rows(params, tcfg, toks, steps)
    for i in range(2):
        want = _want(ref, w, weights, toks[i])
        assert len(got[i]) == len(toks[i])
        for row, lg in got[i]:
            np.testing.assert_allclose(lg, want[row], atol=ATOL)
    assert np.std(_want(ref, w, weights, toks[0])) > 0.3


def test_a_packed_tick_serves_a_chunk_beside_decode_rows(ref, model):
    w, weights, tcfg, params = model
    rng = np.random.default_rng(3)
    toks = [rng.integers(0, 128, (30,)), rng.integers(0, 128, (30,))]
    got, _ = _serve_rows(
        params, tcfg, toks,
        [[12, 0], [1, 10], [1, 9], [6, 1], [1, 1], [1, 1]], packed=True)
    for i in range(2):
        want = _want(ref, w, weights, toks[i])
        for row, lg in got[i]:
            np.testing.assert_allclose(lg, want[row], atol=ATOL)


@pytest.mark.parametrize("packed", [False, True])
def test_a_slot_with_no_row_keeps_its_state_and_tail_bit_for_bit(
        model, packed):
    _, _, tcfg, params = model
    rng = np.random.default_rng(8)
    toks = [rng.integers(0, 128, (20,)), rng.integers(0, 128, (20,))]
    _, c0 = _serve_rows(params, tcfg, toks, [[7, 9]])
    _, c1 = _serve_rows(params, tcfg, [toks[0][7:], toks[1][9:]],
                        [[5, 0], [1, 0]], packed=packed, cache=c0)
    for name in ("ssm_state", "ssm_tail"):
        a, b = np.asarray(getattr(c0, name)), np.asarray(getattr(c1, name))
        np.testing.assert_array_equal(a[:, 1], b[:, 1])
        assert np.abs(a[:, 0] - b[:, 0]).max() > 1e-3
    assert int(c1.length[1]) == 9


@pytest.mark.parametrize("first", [1, 5, 11])
def test_a_slot_whose_length_goes_back_to_0_starts_from_a_zero_state(
        ref, model, first):
    """What the engine does to reuse a slot is reset its length with the
    next request's first chunk; the state and the tail the last request
    left stay in the arrays. A member whose first position is 0 must read
    neither: its logits are the reference's from the first row on, whether
    its first step is one row (the decode step) or a chunk."""
    w, weights, tcfg, params = model
    rng = np.random.default_rng(first)
    old = [rng.integers(0, 128, (23,)), rng.integers(0, 128, (17,))]
    _, cache = _serve_rows(params, tcfg, old, [[16, 9], [7, 8]])
    assert float(jnp.abs(cache.ssm_state[:, 0]).max()) > 1e-3
    new = rng.integers(0, 128, (first + 6,))
    cache = dataclasses.replace(cache, length=cache.length.at[0].set(0))
    want = _want(ref, w, weights, new)
    got, _ = _serve_rows(params, tcfg, [new, old[1]],
                         [[first, 0]] + [[1, 0]] * 6, cache=cache)
    assert [row for row, _ in got[0]] == list(range(first + 6))
    np.testing.assert_allclose(np.stack([lg for _, lg in got[0]]), want,
                               atol=ATOL)


@pytest.mark.parametrize("fault, least", [("state_bf16", 3e-6),
                                          ("int8", 1e-2)])
def test_the_controls_the_limits_are_held_against_show(ref, model, fault,
                                                       least):
    """A state rounded to bfloat16 after every token drifts from the
    float32 one by a rounding a token (little over 40 tokens, more with
    every token a head remembers); int8 everywhere moves the logits at
    once."""
    w, weights, _, _ = model
    toks = np.random.default_rng(5).integers(0, 128, (40,))
    sound = _want(ref, w, weights, toks)
    assert np.abs(sound - _want(ref, w, weights, toks, quant=fault)).max() \
        > least


# -- through SlotServer (a), (c) ---------------------------------------------


@pytest.fixture(scope="module")
def reused(model):
    """Three requests through one slot, one after another, beside a long one
    in another slot, with the flight recorder and the registry on: the
    engine, the prompts, its report, the flight records and the registry's
    text."""
    _, _, tcfg, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 128, (n,)).tolist() for n in (21, 9, 13, 30)]
    FLIGHT.clear()
    FLIGHT.arm(capacity=4096)
    obs.REGISTRY.enable()
    try:
        eng = _engine(tcfg, params, slots=2)
        rep = eng.serve([
            Request(uid=0, prompt=prompts[3], max_new_tokens=40),
            Request(uid=1, prompt=prompts[0], max_new_tokens=6),
            Request(uid=2, prompt=prompts[1], max_new_tokens=7),
            Request(uid=3, prompt=prompts[2], max_new_tokens=5)])
        recs = FLIGHT.snapshot()["records"]
        text = obs.REGISTRY.to_prometheus()
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
        obs.REGISTRY.disable()
        obs.REGISTRY.reset()
    return eng, prompts, rep, recs, text


def test_a_reused_slot_serves_what_a_fresh_engine_serves(ref, model, reused):
    """Three requests through one slot, one after another (each finds the
    last one's state and tail in its slot and starts from zero all the
    same), beside a long one in another slot; prompts that leave a chunk
    of every size. Every token the reference's greedy choice; the flight
    record counts a state a live slot a state-space layer in decode ticks;
    nothing leaked."""
    w, weights, tcfg, params = model
    eng, prompts, rep, recs, text = reused
    recs = [r for r in recs if "ssm_states_advanced" in r]
    by_uid = {r.uid: r.tokens for r in rep.results}
    assert by_uid[0] == _greedy(ref, weights, w, prompts[3], 40)
    for uid, p, n in ((1, 0, 6), (2, 1, 7), (3, 2, 5)):
        assert by_uid[uid] == _greedy(ref, weights, w, prompts[p], n)
    # The last of the three found two requests' leavings in its slot: a
    # fresh engine's first request, in a slot nothing has touched, is served
    # the same. (One fresh engine, not one a request: every new engine
    # compiles its tick programs again, and the first two are held to the
    # reference's choice above like the third.)
    fresh = _engine(tcfg, params, slots=1).serve(
        [Request(uid=9, prompt=prompts[2], max_new_tokens=5)])
    assert fresh.results[0].tokens == by_uid[3]
    dec = [r for r in recs if not r.get("chunk_tokens") and r["occupancy"]]
    assert dec and all(r["ssm_states_advanced"] == 3 * r["occupancy"]
                       for r in dec)
    assert all("expert_pairs" in r for r in recs)
    assert "serving_ssm_states_advanced_total" in text
    assert 'cache="paged_state"' in text
    leak = eng.leak_report()
    assert leak["blocks_used"] == 0 == leak["blocks_reserved"]


def test_a_tick_with_a_chunk_counts_its_scan_rows_by_their_path(
        reused, monkeypatch):
    """The flight record of a tick that carries a chunk: the chunk's rows
    times the three state-space layers, under the scan they took (on a CPU
    all ``xla``; the two fields sum to the rows on any backend), nothing in
    a tick with no chunk; the registry's counter follows. The count is the
    host's, from the rows it packed and ``scan_path``'s answer: steered to
    the kernel, the same rows stand under ``kernel``."""
    from tree_attention_tpu.serving import engine as engine_mod

    eng, prompts, _, recs, text = reused
    assert eng._ssm_layers == 3
    for r in recs:
        assert r["scan_rows_kernel"] + r["scan_rows_xla"] \
            == 3 * r["chunk_tokens"]
        assert r["scan_rows_kernel"] == 0                       # a CPU
    rows = sum(r["scan_rows_xla"] for r in recs)
    assert rows == 3 * sum(len(p) for p in prompts)
    assert f'ssm_scan_rows_total{{path="xla"}} {rows}\n' in text
    assert 'ssm_scan_rows_total{path="kernel"} 0\n' in text
    n_vec = np.asarray([1, 0])
    assert eng._count_scan_rows(8, n_vec, ([0], [5])) == (0, 15)
    assert eng._count_scan_rows(1, n_vec, None) == (0, 0)
    monkeypatch.setattr(engine_mod, "scan_path", lambda *a: "kernel")
    assert eng._count_scan_rows(8, n_vec, ([0], [5])) == (15, 0)
    assert eng._count_scan_rows(8, np.asarray([3, 4]), None) == (21, 0)


def test_a_packed_tick_through_the_scan_kernel_serves_the_xla_scans_rows(
        model, monkeypatch):
    """``forward_packed_step`` with the chunk group's scan steered to the
    kernel (``scan_path``, as on a TPU; interpret mode) against the XLA
    scan, tick by tick from the same cache: a chunk of two of the kernel's
    blocks from position 0 into a slot whose old state is poisoned, ragged
    chunks after it, a decode row beside them, a tick whose chunk member has
    NO row. The logits and the state pool agree to the rounding of two
    orders of one float32 sum; a slot with no row holds its bits."""
    from tree_attention_tpu.models import decode, hybrid

    _, _, tcfg, params = model
    rng = np.random.default_rng(12)
    toks = [rng.integers(0, 128, (60,)), rng.integers(0, 128, (20,))]
    cache = _cache(tcfg, 2)
    cache = dataclasses.replace(
        cache, ssm_state=cache.ssm_state.at[:, 0].set(jnp.nan))

    def tick(params, chunk, slot, n, dec, dn, cache):
        return decode.forward_packed_step(
            params, chunk, slot, n, dec, dn, cache, tcfg)

    by_xla = jax.jit(tick)
    assert hybrid.scan_path(WIDTH, tcfg.ssm, cache.ssm_state) == "xla"
    monkeypatch.setattr(hybrid, "scan_path", lambda *a: "kernel")
    by_kernel = jax.jit(tick)
    pos = 0
    for n, dn in ((16, 0), (13, 1), (0, 1), (16, 1), (5, 0)):
        chunk = np.zeros((1, WIDTH), np.int32)
        chunk[0, :n] = toks[0][pos:pos + n]
        args = (jnp.asarray(chunk), jnp.asarray([0], jnp.int32),
                jnp.asarray([n], jnp.int32),
                jnp.asarray([0, toks[1][pos % 20]], jnp.int32),
                jnp.asarray([0, dn], jnp.int32))
        got, kcache = by_kernel(params, *args, cache)
        logits, cache = by_xla(params, *args, cache)
        served = np.asarray([n > 0, dn > 0])
        np.testing.assert_allclose(np.asarray(got)[served],
                                   np.asarray(logits)[served], atol=ATOL)
        np.testing.assert_allclose(np.asarray(kcache.ssm_state),
                                   np.asarray(cache.ssm_state), atol=ATOL)
        np.testing.assert_array_equal(np.asarray(kcache.ssm_tail),
                                      np.asarray(cache.ssm_tail))
        pos += n
    assert int(cache.length[0]) == 50 and np.isfinite(
        np.asarray(cache.ssm_state)).all()


def test_model_config_serves_the_family_on_its_own_weights(tmp_path):
    """``--model-config`` with this family's keys: the program draws a
    stack a kind itself and serves through ``SlotServer``, like the
    others."""
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
    assert p["ssm"]["A_log"].shape == (3, 8) and "wout" in p
    assert p["layers"]["w_down"].shape == (2, 64, 32)
    assert float(jnp.exp(p["ssm"]["A_log"]).min()) >= 1.0
    eng = setup.make_engine()
    assert eng.cache.ssm_state.shape == (3, 2, 2, 16, 64)
    assert eng.cache.ssm_tail.shape == (3, 2, 3 * 192)
    rep = eng.serve([Request(uid=0, prompt=list(range(1, 22)),
                             max_new_tokens=4)])
    assert len(rep.results[0].tokens) == 4


# -- LatentMoE (f), (g) ------------------------------------------------------


@pytest.mark.parametrize("relu2", [True, False])
def test_the_ungated_product_in_interpret_mode_against_ragged_dot(relu2):
    rng = np.random.default_rng(int(relu2))
    m, k, n, G, first = 256, 128, 256, 5, 2
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(G + first + 1, k, n)) * 0.1,
                      jnp.float32)
    sizes = jnp.asarray([40, 0, 100, 3, 57], jnp.int32)     # 200 of 256 rows
    got = grouped_matmul(lhs, (rhs,), sizes, first_group=first,
                         interpret=True, relu2=relu2, name=UNGATED_KERNEL)
    want = lax.ragged_dot(lhs, rhs[first:first + G], sizes)
    if relu2:
        want = jnp.square(jnp.maximum(want, 0.0))
    np.testing.assert_allclose(got[:200], want[:200], atol=1e-4, rtol=1e-5)
    assert "moe_grouped_matmul" not in UNGATED_KERNEL


def test_top_22_of_512_in_a_latent_against_the_reference(ref):
    """The published router (22 of 512, corrected, renormed then scaled by
    5) over a share of 128, at small widths: the program's expert layer is
    the reference's routed part + its shared part."""
    from tree_attention_tpu.models.experts import expert_layer

    cfg = dict(SMALL, n_routed_experts=128, num_experts_per_tok=22,
               deployment={"experts_total": 512, "expert_share": 2})
    w = ref.Widths.of(cfg)
    layer = {n: a[0] for n, a in ref.init_weights(3, w)["moe"].items()}
    h = jnp.asarray(np.random.default_rng(4).normal(size=(24, 64)),
                    jnp.float32)
    routed, shared = ref.ffn_parts(h, layer, w=w)
    t = model_from_config(cfg, max_seq_len=64)
    assert (t.moe.per_token, t.moe.n_experts, t.moe.held_first) == (
        22, 512, 256)
    y, chosen = expert_layer(layer, h[None], t.moe)
    np.testing.assert_allclose(y[0], np.asarray(routed + shared), atol=ATOL)
    assert chosen.shape == (1, 24, 22)
    here = (np.asarray(chosen) >= 256) & (np.asarray(chosen) < 384)
    assert 0 < here.sum() < here.size and np.abs(routed).max() > 1e-3


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref, model):
    """Four chips share a layer of 8 routed experts, 2 each: what the four
    shares' routed parts give (each brought up through ``W_up`` from its
    own partial sum in the latent), with the shared expert (which every
    chip computes alike) counted once, is the uncut reference's layer; and
    the program's expert layer gives its own share's part."""
    from tree_attention_tpu.models.experts import expert_layer

    uncut = dict(SMALL, n_routed_experts=8,
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
        cfg = dict(SMALL, n_routed_experts=2,
                   deployment={"experts_total": 8, "expert_share": share})
        ws = ref.Widths.of(cfg)
        mine = dict(layer, **{n: layer[n][2 * share:2 * share + 2]
                              for n in ("we1", "we2")})
        part, sh = ref.ffn_parts(h, mine, w=ws)
        total += np.asarray(part)
        t = model_from_config(cfg, max_seq_len=64)
        y, _ = expert_layer(mine, h[None], t.moe)
        np.testing.assert_allclose(y[0], np.asarray(part + sh), atol=ATOL)
    np.testing.assert_allclose(total + np.asarray(shared), want, atol=ATOL)
    assert np.abs(want).max() > 0.01 and np.abs(total).max() > 0.005


# -- what the state pool does not carry (h) ----------------------------------


@pytest.mark.parametrize("kw, named", [
    (dict(quantize=True), "state pool.*int8 state rows"),
    (dict(kv_shard="seq"), "state pool.*sequence-sharded"),
    (dict(host_blocks=4, prefix_cache=True, prefix_block=BLOCK),
     "state pool.*host tier"),
    (dict(speculate=True), "state pool.*recurrent state"),
    (dict(prefix_cache=True, prefix_block=BLOCK),
     "state pool.*prefix cache"),
])
def test_engine_refuses_what_the_state_pool_does_not_carry(model, kw, named):
    _, _, tcfg, params = model
    with pytest.raises(ValueError, match=named):
        _engine(tcfg, params, **kw)


def test_disaggregation_and_forks_are_refused_by_the_cache_kinds_name(model):
    from tree_attention_tpu.serving.block_pool import BlockAllocator

    _, _, tcfg, params = model
    with pytest.raises(ValueError, match="state pool.*disaggregation"):
        _engine(tcfg, params, block_pool=BlockAllocator(72))
    eng = _engine(tcfg, params)
    with pytest.raises(ValueError, match="state pool.*fork"):
        eng.fork(3)
    with pytest.raises(ValueError, match="state pool.*n / best_of"):
        eng.serve([Request(uid=0, prompt=[1, 2, 3], max_new_tokens=2, n=2)])
    with pytest.raises(ValueError, match="state.*tree_mask"):
        forward_step(params, jnp.zeros((2, 2), jnp.int32), _cache(tcfg),
                     tcfg, tree_mask=jnp.ones((2, 2, 2), bool))
    with pytest.raises(ValueError, match="int8 rows beside a recurrent"):
        init_paged_cache(tcfg, 2, 32, 16, block=BLOCK, quantize=True)


@pytest.mark.parametrize("flags, named", [
    (["--kv-quant", "int8"], "state pool is not served with --kv-quant"),
    (["--speculate"], "--speculate"),
    (["--serve-disagg"], "--serve-disagg"),
    (["--prefix-cache", "--prefix-block", "4"], "state pool.*--prefix-cache"),
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
