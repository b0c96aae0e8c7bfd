"""A routed-expert feed-forward layer that is told which experts it holds.

The router scores every expert at its published width in float32; the top
``per_token`` are chosen (group-limited: the best ``top_groups`` of
``n_groups`` equal groups first, a group scored by its best expert; ties to
the lowest index); the layer computes ``Σ w_i · SwiGLU_i(x)`` over the chosen
experts IT HOLDS (``[held_first, held_first + held)``) and adds the shared
experts. Pairs routed to an expert held elsewhere add nothing here: that
partial sum is what goes on, in this program and in the benchmark's plain
reference alike, and the shares of all holders plus the shared experts counted
once add up to the uncut layer (``tests/test_latent_moe.py``).

The product over held experts is a grouped matmul over the (row, expert)
pairs sorted by expert (``ops/pallas_moe.py``): no expert is computed for a
row that did not choose it, no pair is dropped at any load, and an expert no
row chose is not read.

Also here: the parameters of a model whose block is not the dense one
(:func:`init_block_params`): a leading stack of dense-FFN layers and a stack of
expert layers, each scanned on its own.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tree_attention_tpu.models.latent import init_latent_layer
from tree_attention_tpu.models.transformer import (
    ExpertLayer,
    TransformerConfig,
)

Params = Dict[str, Any]


def route(scores: jax.Array, ex: ExpertLayer) -> Tuple[jax.Array, jax.Array]:
    """``scores`` ``(R, n_experts)`` float32 (the softmax of the router's
    logits) -> chosen experts ``(R, per_token)`` int32 and their weights
    ``(R, per_token)`` float32."""
    R, E = scores.shape
    masked = scores
    if ex.n_groups > 1:
        per = E // ex.n_groups
        best = scores.reshape(R, ex.n_groups, per).max(axis=-1)
        _, top_g = lax.top_k(best, ex.top_groups)           # lowest index first
        keep = jnp.zeros((R, ex.n_groups), bool).at[
            jnp.arange(R)[:, None], top_g].set(True)
        masked = jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)
    w, idx = lax.top_k(masked, ex.per_token)
    if ex.renorm and ex.per_token > 1:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    else:
        w = w * ex.scale
    return idx.astype(jnp.int32), w


def router_scores(p: Params, x: jax.Array) -> jax.Array:
    """Softmax over the router's logits, float32 at the highest matmul
    precision: a near-tie decided otherwise changes which expert a row
    visits."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array,
           w2: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


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
    has it: one rounding fewer before the near-ties are decided."""
    from tree_attention_tpu.ops.pallas_moe import grouped_matmul, row_tile

    if experts is None:
        experts = tuple(p[n] for n in EXPERT_LEAVES)
    we1, we3, we2 = experts
    B, T, D = x.shape
    R, K = B * T, ex.per_token
    xf = x.reshape(R, D)
    idx, w = route(router_scores(
        p, xf if router_input is None else router_input.reshape(R, D)), ex)
    local = idx - ex.held_first
    here = (local >= 0) & (local < ex.held)
    # Pairs sorted by held expert; a pair whose expert lives elsewhere
    # sorts past every held one and belongs to no group.
    key = jnp.where(here, local, ex.held).reshape(-1)
    m = R * K
    pad = -m % row_tile(m)
    if pad:
        key = jnp.concatenate([key, jnp.full((pad,), ex.held, jnp.int32)])
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((ex.held + 1,), jnp.int32).at[key].add(1)[:ex.held]
    rows = jnp.minimum(order // K, R - 1)
    hidden = grouped_matmul(xf[rows], (we1, we3), sizes, first_group=first)
    out = grouped_matmul(hidden, (we2,), sizes, first_group=first)
    # Back to (row, choice) order: pair j sits at sorted position inv[j].
    inv = jnp.zeros((m + pad,), jnp.int32).at[order].set(
        jnp.arange(m + pad, dtype=jnp.int32))[:m]
    pairs = out[inv].reshape(R, K, D)
    y = jnp.sum(
        jnp.where(here[..., None],
                  pairs.astype(jnp.float32) * w[..., None], 0.0),
        axis=1,
    ).astype(x.dtype)
    if ex.shared_width:
        y = y + swiglu(xf, p["ws1"], p["ws3"], p["ws2"])
    return y.reshape(B, T, D), idx.reshape(B, T, K)


def held_counts(idx: jax.Array, valid: jax.Array,
                ex: ExpertLayer) -> jax.Array:
    """Rows on each held expert ``(held,)`` int32, and in the last entry
    the pairs routed elsewhere: ``idx`` ``(B, T, per_token)`` chosen
    experts, ``valid`` ``(B, T)`` the rows that carry a token."""
    local = idx - ex.held_first
    slot = jnp.where((local >= 0) & (local < ex.held), local, ex.held)
    return jnp.zeros((ex.held + 1,), jnp.int32).at[slot.reshape(-1)].add(
        jnp.repeat(valid.reshape(-1), ex.per_token).astype(jnp.int32))


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
        "ln1": jnp.ones((D,), jnp.float32), "ln2": jnp.ones((D,), jnp.float32),
        **init_latent_layer(k_a, cfg, res_std),
        "router": _normal(k_r, (D, ex.n_experts), 0.02, cfg.dtype),
        "we1": we1, "we3": we3, "we2": we2,
    }
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
    stacked on a leading layer axis. One jitted call; every leaf is drawn
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
