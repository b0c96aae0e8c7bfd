"""A routed-expert feed-forward layer that is told which experts it holds.

The router scores every expert at its published width in float32 (a softmax
over the experts, or each expert's own sigmoid); the top
``per_token`` are chosen (group-limited: the best ``top_groups`` of
``n_groups`` equal groups first, a group scored by its best expert; or
corrected: the top of ``scores + bias``, weighted by the scores themselves;
ties to the lowest index); the layer computes ``Σ w_i · SwiGLU_i(x)`` over the
chosen experts IT HOLDS (``[held_first, held_first + held)``) and adds the
shared experts. Pairs routed to an expert held elsewhere add nothing here:
that partial sum is what goes on, in this program and in the benchmark's plain
reference alike, and the shares of all holders plus the shared experts counted
once add up to the uncut layer (``tests/test_latent_moe.py``). A pair on a
zero-compute expert (the router's last ``n_zero`` ids) adds ``w · x``: no
weights, no matmul, computed by every holder for its own rows, so it too
counts once over the shares.

The product over held experts is a grouped matmul over the (row, expert)
pairs sorted by expert (``ops/pallas_moe.py``): no expert is computed for a
row that did not choose it, no pair is dropped at any load, and an expert no
row chose is not read.

Also here: the parameters of a model whose block is not the dense one
(:func:`init_block_params`): a leading stack of dense-FFN layers and a stack of
expert layers, each scanned on its own; or, where the routed experts are a
branch beside several attention + dense-FFN sublayers, one stack of such
layers with each sublayer's leaves named apart under ``sub`` (a list).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tree_attention_tpu.models.latent import init_latent_layer
from tree_attention_tpu.obs import scopes
from tree_attention_tpu.models.transformer import (
    ExpertLayer,
    TransformerConfig,
)

Params = Dict[str, Any]


def route(scores: jax.Array, ex: ExpertLayer,
          bias: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """``scores`` ``(R, n_experts)`` float32 (:func:`router_scores`) ->
    chosen experts ``(R, per_token)`` int32 and their weights
    ``(R, per_token)`` float32. With ``bias`` ``(n_experts,)`` the choice
    is the top of ``scores + bias``; the weights are the chosen experts'
    uncorrected scores."""
    R, E = scores.shape
    if bias is not None:
        _, idx = lax.top_k(scores + bias, ex.per_token)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        return idx.astype(jnp.int32), _weigh(w, ex)
    masked = scores
    if ex.n_groups > 1:
        per = E // ex.n_groups
        best = scores.reshape(R, ex.n_groups, per).max(axis=-1)
        _, top_g = lax.top_k(best, ex.top_groups)           # lowest index first
        keep = jnp.zeros((R, ex.n_groups), bool).at[
            jnp.arange(R)[:, None], top_g].set(True)
        masked = jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)
    w, idx = lax.top_k(masked, ex.per_token)
    return idx.astype(jnp.int32), _weigh(w, ex)


def _weigh(w: jax.Array, ex: ExpertLayer) -> jax.Array:
    if ex.renorm and ex.per_token > 1:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * ex.scale if ex.renorm_scaled else w
    return w * ex.scale


def router_scores(p: Params, x: jax.Array,
                  scoring: str = "softmax") -> jax.Array:
    """The router's scores by ``ExpertLayer.scoring``: a softmax over the
    router's logits, or each logit's sigmoid. Float32 at the highest
    matmul precision either way: a near-tie decided otherwise changes
    which expert a row visits."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array,
           w2: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def relu2_ffn(x: jax.Array, w1: jax.Array, w2: jax.Array) -> jax.Array:
    """An ungated feed-forward part: one matrix in, relu squared, one
    out."""
    return jnp.square(jax.nn.relu(x @ w1)) @ w2


EXPERT_LEAVES = ("we1", "we3", "we2")


def expert_layer(p: Params, x: jax.Array, ex: ExpertLayer, *,
                 experts=None, first=0,
                 router_input=None) -> Tuple[jax.Array, jax.Array]:
    """``x`` ``(B, T, D)`` normed residual. Returns the layer's output
    ``(B, T, D)`` and, for the counters, the chosen experts ``(B, T,
    per_token)`` (global ids) — what rows of them count is the caller's to
    say (a tick's padding rows are routed too). ``experts`` (default: the
    layer's own ``we1``/``we3``/``we2``) may be a stack of many layers'
    experts with this layer's at ``first`` on: a layer loop hands every
    layer the one stack and never slices it. ``router_input`` is ``x``
    before it was rounded to the served type (float32), where the caller
    has it: one rounding fewer before the near-ties are decided, and
    before a zero-compute expert hands it back.

    Where ``ex.latent``, the routed experts live in a latent narrower than
    the residual: a row goes down (``w_down``) once before the gather, the
    experts' matrices are of the latent's width, and the weighed sum of
    what the experts held HERE give comes up (``w_up``) once; the router
    and the shared expert see the full width. Where not ``ex.gated``, an
    expert is ``w2 . relu(w1 . u)^2`` (``experts`` holds two stacks, no
    gate) and its products carry the ungated kernel's name."""
    from tree_attention_tpu.ops.pallas_moe import (
        UNGATED_KERNEL, grouped_matmul,
    )
    from tree_attention_tpu.ops.tuning import row_tile

    if experts is None:
        experts = tuple(p[n] for n in ex.leaves)
    B, T, D = x.shape
    R, K = B * T, ex.per_token
    xf = x.reshape(R, D)
    x_in = xf if router_input is None else router_input.reshape(R, D)
    with jax.named_scope(scopes.ROUTE):
        idx, w = route(router_scores(p, x_in, ex.scoring), ex,
                       p["router_bias"] if ex.corrected else None)
        local = idx - ex.held_first
        here = (local >= 0) & (local < ex.held)
        # Pairs sorted by held expert; a pair whose expert lives elsewhere
        # sorts past every held one and belongs to no group.
        key = jnp.where(here, local, ex.held).reshape(-1)
        m = R * K
        pad = -m % row_tile(m)
        if pad:
            key = jnp.concatenate(
                [key, jnp.full((pad,), ex.held, jnp.int32)])
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros(
            (ex.held + 1,), jnp.int32).at[key].add(1)[:ex.held]
        rows = jnp.minimum(order // K, R - 1)
        gathered = (xf @ p["w_down"] if ex.latent else xf)[rows]
    with jax.named_scope(scopes.EXPERTS):
        if ex.gated:
            hidden = grouped_matmul(
                gathered, experts[:2], sizes, first_group=first)
            out = grouped_matmul(hidden, experts[2:], sizes,
                                 first_group=first)
        else:
            hidden = grouped_matmul(
                gathered, experts[:1], sizes, first_group=first,
                relu2=True, name=UNGATED_KERNEL)
            out = grouped_matmul(hidden, experts[1:], sizes,
                                 first_group=first, name=UNGATED_KERNEL)
    with jax.named_scope(scopes.ROUTE):
        # Back to (row, choice) order: pair j sits at sorted position
        # inv[j].
        inv = jnp.zeros((m + pad,), jnp.int32).at[order].set(
            jnp.arange(m + pad, dtype=jnp.int32))[:m]
        pairs = out[inv].reshape(R, K, out.shape[-1])
        y = jnp.sum(
            jnp.where(here[..., None],
                      pairs.astype(jnp.float32) * w[..., None], 0.0),
            axis=1,
        )
        if ex.n_zero:
            # Identity experts: one weighted sum a row, no weights to read.
            w_zero = jnp.sum(jnp.where(idx >= ex.n_routed, w, 0.0), axis=-1)
            y = y + w_zero[:, None] * x_in.astype(jnp.float32)
        y = y.astype(x.dtype)
        if ex.latent:
            y = y @ p["w_up"]
    if ex.shared_width:
        with jax.named_scope(scopes.FFN):
            y = y + (swiglu(xf, p["ws1"], p["ws3"], p["ws2"]) if ex.gated
                     else relu2_ffn(xf, p["ws1"], p["ws2"]))
    return y.reshape(B, T, D), idx.reshape(B, T, K)


def counts_width(ex: ExpertLayer) -> int:
    """Entries :func:`held_counts` gives for one layer."""
    return ex.held + (3 if ex.n_zero else 1)


def held_counts(idx: jax.Array, valid: jax.Array,
                ex: ExpertLayer) -> jax.Array:
    """Rows on each held expert ``(held,)`` int32, then the pairs on
    routed experts held elsewhere; where the layer has zero-compute
    experts two entries more: the pairs on those, and the most routed
    (real) experts any one row chose. ``idx`` ``(B, T, per_token)`` chosen
    experts, ``valid`` ``(B, T)`` the rows that carry a token."""
    local = idx - ex.held_first
    slot = jnp.where((local >= 0) & (local < ex.held), local, ex.held)
    ones = jnp.repeat(valid.reshape(-1), ex.per_token).astype(jnp.int32)
    counts = jnp.zeros((counts_width(ex),), jnp.int32)
    if not ex.n_zero:
        return counts.at[slot.reshape(-1)].add(ones)
    zero = idx >= ex.n_routed
    slot = jnp.where(zero, ex.held + 1, slot)
    real = jnp.sum(~zero, axis=-1, dtype=jnp.int32)
    return counts.at[slot.reshape(-1)].add(ones).at[ex.held + 2].set(
        jnp.max(jnp.where(valid, real, 0)))


# ---------------------------------------------------------------------------
# Parameters of a latent-attention / expert model
# ---------------------------------------------------------------------------


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _dense_layer(key, cfg: TransformerConfig, res_std: float) -> Params:
    k_a, k1, k2, k3 = jax.random.split(key, 4)
    D, F = cfg.d_model, cfg.d_ff
    return {
        "ln1": jnp.ones((D,), jnp.float32), "ln2": jnp.ones((D,), jnp.float32),
        **init_latent_layer(k_a, cfg, res_std),
        "w1": _normal(k1, (D, F), 0.02, cfg.dtype),
        "w3": _normal(k2, (D, F), 0.02, cfg.dtype),
        "w2": _normal(k3, (F, D), res_std, cfg.dtype),
    }


# The std of a seeded correction on the choice: of the order of the gaps
# between neighbouring scores at the top, so it changes some choices and
# not most.
ROUTER_BIAS_STD = 1e-3


def _expert_layer_leaves(key, cfg: TransformerConfig,
                         res_std: float) -> Params:
    ex, D = cfg.moe, cfg.d_model
    k_a, k_r, k_e, k_s = jax.random.split(key, 4)

    def one_expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (_normal(k1, (D, ex.width), 0.02, cfg.dtype),
                _normal(k2, (D, ex.width), 0.02, cfg.dtype),
                _normal(k3, (ex.width, D), res_std, cfg.dtype))

    # Expert by expert: the float32 draw of the stacked tensor never exists.
    we1, we3, we2 = lax.map(one_expert, jax.random.split(k_e, ex.held))
    out = {
        "router": _normal(k_r, (D, ex.n_experts), 0.02, cfg.dtype),
        "we1": we1, "we3": we3, "we2": we2,
    }
    if ex.corrected:
        out["router_bias"] = _normal(
            jax.random.fold_in(k_r, 1), (ex.n_experts,), ROUTER_BIAS_STD,
            jnp.float32)
    if ex.branch is not None:
        out["sub"] = [_dense_layer(k, cfg, res_std)
                      for k in jax.random.split(k_a, cfg.sublayers)]
    else:
        out.update(
            ln1=jnp.ones((D,), jnp.float32), ln2=jnp.ones((D,), jnp.float32),
            **init_latent_layer(k_a, cfg, res_std))
    if ex.shared_width:
        s1, s2, s3 = jax.random.split(k_s, 3)
        out.update(
            ws1=_normal(s1, (D, ex.shared_width), 0.02, cfg.dtype),
            ws3=_normal(s2, (D, ex.shared_width), 0.02, cfg.dtype),
            ws2=_normal(s3, (ex.shared_width, D), res_std, cfg.dtype),
        )
    return out


def init_block_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    """Parameters of a model whose block is chosen by ``cfg.mla`` /
    ``cfg.moe``: ``dense`` (the leading dense-FFN layers) and ``layers``
    (the expert layers; all of them dense where ``cfg.moe`` is None), each
    stacked on a leading layer axis. A layer with a routed branch
    (``cfg.moe.branch``) holds the router and the experts as any expert
    layer does, and under ``sub`` a list of its attention + dense-FFN
    sublayers' leaves, each on the layer axis alone (a second axis would
    have the layer loop copy both sublayers' weights out of the stack
    before either is read). One jitted call; every leaf is drawn
    layer by layer (expert by expert) in float32 and rounded at once to
    the served type, so the peak is the weights themselves."""
    import functools

    @functools.partial(jax.jit, static_argnums=(1,))
    def make(key, cfg):
        k_embed, k_dense, k_moe, k_out = jax.random.split(key, 4)
        res_std = 0.02 / (2 * cfg.n_layers) ** 0.5
        n_dense = cfg.n_dense_layers
        out = {
            "embed": _normal(k_embed, (cfg.vocab_size, cfg.d_model), 0.02,
                             cfg.dtype),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
            "wout": _normal(k_out, (cfg.d_model, cfg.vocab_size), 0.02,
                            cfg.dtype),
        }
        if n_dense:
            out["dense"] = lax.map(
                lambda k: _dense_layer(k, cfg, res_std),
                jax.random.split(k_dense, n_dense))
        if cfg.n_layers > n_dense:
            out["layers"] = lax.map(
                lambda k: _expert_layer_leaves(k, cfg, res_std),
                jax.random.split(k_moe, cfg.n_layers - n_dense))
        return out

    return make(key, cfg)
