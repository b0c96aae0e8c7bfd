"""The plain reference of the ``nemotron_h`` family: a decoder whose layers
are ONE part each (a Mamba-2 state-space mixer, grouped-query attention with
no positional term, or a LatentMoE feed-forward part: routed experts in a
latent narrower than the residual, ungated, relu squared, beside one shared
expert on the full width), an untied head. The full forward pass over one
sequence: the state-space layer as the TOKEN-BY-TOKEN recurrence under
``lax.scan`` (never the chunked form the program runs: the two must not share
a mistake), the convolution a plain shifted sum, attention over the whole
sequence, every held expert a loop; float32 at the highest matmul precision;
no kernels, no cache, no batching. What every family's file gives is in
``README.md`` beside this file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
nothing of the harness.

Equations (NVIDIA-Nemotron-3-Super-120B-A12B,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``model_type`` ``nemotron_h``). ``hybrid_override_pattern`` names every layer
of the published depth, a character each; every layer is ``x += Part(rms(x;
g))`` with an RMSNorm of ``layer_norm_epsilon`` and a learned gain, and one
part:

- ``M``, a Mamba-2 mixer (``mamba_num_heads`` heads of ``mamba_head_dim``,
  inner width their product; ``n_groups`` groups; ``ssm_state_size`` N;
  ``conv_kernel`` taps). ``[z | xBC | dt] = h W_in`` (hidden -> inner +
  (inner + 2 x groups x N) + heads; ``xBC`` is ``x``, then ``B``, then
  ``C``). ``xBC_t <- silu(sum_k w_k * xBC_{t-taps+1+k} + b)``: depthwise,
  causal, zero before the sequence, with a bias (``use_conv_bias``). For
  head ``i`` of group ``i // (heads / groups)``: ``D_t = softplus(dt_t +
  dt_bias_i)`` (not clipped: the config has no ``time_step_limit``), ``a_t =
  exp(D_t A_i)`` with ``A_i = -exp(A_log_i)``, ``S_t = a_t S_{t-1} + D_t x_t
  (x) B_t`` (``head_dim x N``, zero before the sequence), ``y_t = S_t C_t +
  D_i x_t``. Then ``y <- rms_groups(y * silu(z); gain)`` over groups of
  ``inner / n_groups`` values (``block.gate_before_norm``) and the part is
  ``y W_out``. ``chunk_size`` blocks a scan and is not mathematics.
- ``*``, attention: ``q = h W_q`` -> ``heads`` of ``head_dim``, ``k = h
  W_k``, ``v = h W_v`` -> KV heads (query head ``i`` reads KV head ``i //
  (heads / kv_heads)``); causal softmax of ``q . k / head_dim^1/2``;
  ``concat(heads) W_o``. No bias, no QK-norm, NO positional term
  (``block.rotary_layers`` ``"none"``; ``rope_theta`` is a key the family's
  modelling code never reads).
- ``E``, LatentMoE: ``s = sigmoid(h W_r)`` over the
  ``deployment.experts_total`` routed experts; the ``num_experts_per_tok``
  chosen are those with the largest ``s + b`` (``block.corrected_choice``;
  ties to the lowest index; ``n_group`` = ``topk_group`` = 1: no group
  limit), their weights the uncorrected ``s`` of the chosen over their sum
  (``norm_topk_prob``) times ``routed_scaling_factor``
  (``block.scale_renormed``); ``u = h W_down`` (hidden ->
  ``moe_latent_size``; no norm, no bias: ``block.latent_proj_plain``); ``r =
  sum_e w_e W2_e relu(W1_e u)^2`` (latent -> ``moe_intermediate_size`` ->
  latent, no gate matrix: ``mlp_hidden_act`` ``relu2``); the part is ``r
  W_up + W2_s relu(W1_s h)^2`` (the shared expert on the full width, of
  ``moe_shared_expert_intermediate_size``). The router and the shared expert
  see ``h``, only the routed experts see ``u``.
- ends: logits ``= rms(x; norm) W_out`` (``tie_word_embeddings`` false).

**The chip's share** (``deployment``): the router scores all
``experts_total`` experts and chooses among all of them; this file computes
the ``n_routed_experts`` experts held here, ``[expert_share x held,
(expert_share + 1) x held)``, leaves out what the absent ones would add, as
the program does, and brings THAT partial sum up through ``W_up``; the shared
expert is computed whole (every chip computes it for its own rows); the
vocabulary is the rows held. :func:`ffn_parts` gives the routed and the
shared part apart, for the test that adds the shares up.

Departures, each noted where it is made: (1) the chosen scores' sum is
divided with ``1e-20`` added (the DeepSeek-V3 router's constant, which the
program's ``_weigh`` has too). (2) Depth is the parts the file keeps. (3) The
multi-token-prediction module (``num_nextn_predict_layers``) is not built
(``not_built``): it adds nothing to these logits.

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands, the cached keys and values and the state-space layers' carried
state and conv rows to int8 (symmetric, per row / per output channel / per
token / per head), the precision below the bf16 the configuration states;
``"state_int8"`` rounds what is cached or carried alone; ``"router_bf16"``
computes the router's logits from bfloat16 operands. One more is a FAULT of
the program's kind, for the limits to be held against
(``benchmark/calibrate.py --control``): ``"state_bf16"`` rounds the carried
state to bfloat16 after every token.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
CONTROLS = ("int8", "state_int8", "router_bf16", "state_bf16")
_PARTS = {"M": "ssm", "*": "attn", "E": "moe"}
ROW_BLOCK = 512      # rows of the attention computed at once


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    hidden: int
    parts: Tuple[str, ...]            # "ssm" | "attn" | "moe", one a layer
    m_heads: int
    m_head: int
    m_groups: int
    m_state: int
    taps: int
    heads: int
    kv_heads: int
    head: int
    experts: int                      # the router's width
    held: int
    held_first: int
    per_tok: int
    latent: int
    expert_ffn: int
    shared_ffn: int
    route_scale: float
    renorm: bool
    scale_renormed: bool
    corrected: bool
    norm_eps: float
    dt_min: float
    dt_max: float
    dtype: str
    scales: Tuple[Tuple[str, float], ...]   # assumed.seeded_scales, sorted

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.m_groups * self.m_state

    def scale(self, name: str) -> float:
        return dict(self.scales)[name]

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        pattern = str(config["hybrid_override_pattern"])
        if len(pattern) != int(config["num_hidden_layers"]):
            raise ValueError(
                f"{len(pattern)} parts for {config['num_hidden_layers']} "
                f"layers")
        block = config.get("block") or {}
        for key, want in (("rotary_layers", "none"),
                          ("latent_proj_plain", True),
                          ("gate_before_norm", True),
                          ("router_scoring", "sigmoid")):
            if block.get(key) != want:
                raise ValueError(f"block.{key} other than {want!r}: not "
                                 f"this family as it is built")
        if int(config.get("n_group", 1)) != 1 \
                or int(config.get("topk_group", 1)) != 1 \
                or config.get("mlp_hidden_act") != "relu2" \
                or not config.get("use_conv_bias") \
                or config.get("mamba_proj_bias") \
                or config.get("mamba_hidden_act") != "silu":
            raise ValueError("a group limit, a gated expert, no conv bias "
                             "or a projection bias: not this family")
        hidden = int(config["hidden_size"])
        m_heads, m_head = (int(config["mamba_num_heads"]),
                           int(config["mamba_head_dim"]))
        if int(config["expand"]) * hidden != m_heads * m_head:
            raise ValueError("expand x hidden_size is not heads x head_dim")
        dep = config.get("deployment") or {}
        held = int(config["n_routed_experts"])
        return cls(
            vocab=int(config["vocab_size"]), hidden=hidden,
            parts=tuple(_PARTS[ch] for ch in pattern),
            m_heads=m_heads, m_head=m_head,
            m_groups=int(config["n_groups"]),
            m_state=int(config["ssm_state_size"]),
            taps=int(config["conv_kernel"]),
            heads=int(config["num_attention_heads"]),
            kv_heads=int(config["num_key_value_heads"]),
            head=int(config["head_dim"]),
            experts=int(dep.get("experts_total", held)), held=held,
            held_first=int(dep.get("expert_share", 0)) * held,
            per_tok=int(config["num_experts_per_tok"]),
            latent=int(config["moe_latent_size"]),
            expert_ffn=int(config["moe_intermediate_size"]),
            shared_ffn=int(config["moe_shared_expert_intermediate_size"]),
            route_scale=float(config["routed_scaling_factor"]),
            renorm=bool(config["norm_topk_prob"]),
            scale_renormed=bool(block.get("scale_renormed", False)),
            corrected=bool(block.get("corrected_choice", False)),
            norm_eps=float(config["layer_norm_epsilon"]),
            dt_min=float(config["time_step_min"]),
            dt_max=float(config["time_step_max"]),
            dtype=str(config.get("torch_dtype", "bfloat16")),
            scales=tuple(sorted(
                (k, float(v))
                for k, v in config["assumed"]["seeded_scales"].items())),
        )


# -- weights -----------------------------------------------------------------


def _leaf(key, shape, stddev: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w",))
def _init_weights(seed: jax.Array, w: Widths) -> Dict[str, Any]:
    dtype = jnp.dtype(w.dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    D = w.hidden
    ones = lambda n: jnp.ones((n,), jnp.float32)   # noqa: E731

    def leaves(key, shapes):
        kk = jax.random.split(key, len(shapes))
        return {n: _leaf(k, shape, sd, dtype)
                for k, (n, (shape, sd)) in zip(kk, shapes.items())}

    def ssm(key):
        k_w, k_a, k_dt, k_g = jax.random.split(key, 4)
        # The published init: -A uniform over 1-16; the step log-uniform
        # over [time_step_min, time_step_max], dt_bias its inverse softplus.
        dt = jnp.exp(jax.random.uniform(
            k_dt, (w.m_heads,), jnp.float32,
            jnp.log(w.dt_min), jnp.log(w.dt_max)))
        return {"ln": ones(D),
                "A_log": jnp.log(jax.random.uniform(
                    k_a, (w.m_heads,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": ones(w.m_heads),
                "conv_b": jnp.zeros((w.conv_dim,), dtype),
                "norm": w.scale("gain_mean") + w.scale("gain_std")
                * jax.random.normal(k_g, (w.inner,), jnp.float32),
                **leaves(k_w, {
                    "w_in": ((D, 2 * w.inner + 2 * w.m_groups * w.m_state
                              + w.m_heads), 0.02),
                    "conv_w": ((w.conv_dim, w.taps), w.taps ** -0.5),
                    "w_out": ((w.inner, D), w.scale("ssm_out_std"))})}

    def attn(key):
        return {"ln": ones(D), **leaves(key, {
            "wq": ((D, w.heads * w.head), 0.02),
            "wk": ((D, w.kv_heads * w.head), 0.02),
            "wv": ((D, w.kv_heads * w.head), 0.02),
            "wo": ((w.heads * w.head, D), w.scale("attn_out_std"))})}

    def moe(key):
        k_r, k_b, k_e, k_s = jax.random.split(key, 4)

        def one_expert(k):
            return leaves(k, {
                "we1": ((w.latent, w.expert_ffn), 0.02),
                "we2": ((w.expert_ffn, w.latent),
                        w.scale("expert_down_std"))})

        out = {"ln": ones(D),
               "router": _leaf(k_r, (D, w.experts), 0.02, dtype),
               **lax.map(one_expert, jax.random.split(k_e, w.held)),
               **leaves(k_s, {
                   "w_down": ((D, w.latent), 0.02),
                   "w_up": ((w.latent, D), w.scale("latent_up_std")),
                   "ws1": ((D, w.shared_ffn), 0.02),
                   "ws2": ((w.shared_ffn, D), w.scale("shared_down_std"))})}
        if w.corrected:
            out["router_bias"] = _leaf(
                k_b, (w.experts,), w.scale("router_bias_std"), jnp.float32)
        return out

    out = {"embed": _leaf(ks[0], (w.vocab, D), w.scale("embedding_std"),
                          dtype),
           "wout": _leaf(ks[4], (D, w.vocab), w.scale("head_std"), dtype),
           "ln_f": ones(D)}
    for name, make, k in (("ssm", ssm, ks[1]), ("attn", attn, ks[2]),
                          ("moe", moe, ks[3])):
        n = w.parts.count(name)
        if n:
            out[name] = lax.map(make, jax.random.split(k, n))
    return out


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: a stack a kind of part (``ssm``, ``attn``, ``moe``, each on a
    leading axis of that kind's layers in depth order; an ``moe`` layer
    holds the ``held`` experts of the chip's share, the router's ``experts``
    columns, the correction bias, the latent projections and the shared
    expert), ``embed``, ``wout`` and ``ln_f``. Normal; every projection INTO
    a part at std 0.02 (the router too); the taps at ``taps^-1/2``, their
    bias zero; ``A_log`` so that ``-A`` spans 1-16 and ``dt_bias`` so that
    the step spans ``time_step_min``-``time_step_max`` log-uniformly (the
    published init: a state's memory then runs from under a token to ~1,000
    tokens, so a stale state shows); ``D`` at one; the pre-norms' gains at
    one, the state-space layers' gated norm's at ``gain_mean`` +-
    ``gain_std`` (never one: a dropped gain shows). The scales that decide
    how much a part adds to the residual it joins are the configuration
    file's (``assumed.seeded_scales``, with the reckoning that chose them).
    Shapes are the published ones: ``conv_w`` is ``(conv_dim, taps)``, tap
    ``k`` on ``xBC_{t-taps+1+k}``."""
    return _init_weights(jnp.uint32(int(seed) % (2 ** 32)), w)


# -- the forward pass --------------------------------------------------------


def _fake_int8(x: jax.Array, axis) -> jax.Array:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _mm(x: jax.Array, wt: jax.Array, quant: Optional[str]) -> jax.Array:
    wt = wt.astype(jnp.float32)
    if quant == "int8":
        x, wt = _fake_int8(x, -1), _fake_int8(wt, 0)
    return jnp.matmul(x, wt, precision=HIGHEST)


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _cached(rows: jax.Array, quant: Optional[str]) -> jax.Array:
    return _fake_int8(rows, -1) if quant in ("int8", "state_int8") else rows


def _carried(state: jax.Array, quant: Optional[str]) -> jax.Array:
    """A head's state ``(heads, head, N)`` as a lower precision would hand
    it to the next token."""
    if quant in ("int8", "state_int8"):
        return _fake_int8(state, (-2, -1))
    if quant == "state_bf16":
        return state.astype(jnp.bfloat16).astype(jnp.float32)
    return state


def ssm_mixer(h, p, *, w: Widths, quant: Optional[str] = None):
    """The Mamba-2 mixer of the normed rows ``h`` ``(T, hidden)``: the
    convolution a plain sum of shifted copies, the state-space layer the
    recurrence itself, one token after another."""
    T, H, P, G, N = h.shape[0], w.m_heads, w.m_head, w.m_groups, w.m_state
    zxd = _mm(h, p["w_in"], quant)
    z, xbc, dt = (zxd[:, :w.inner], zxd[:, w.inner:w.inner + w.conv_dim],
                  zxd[:, w.inner + w.conv_dim:])
    xbc = _cached(xbc, quant)
    taps = p["conv_w"].astype(jnp.float32)                # (conv_dim, taps)
    pad = jnp.concatenate([jnp.zeros((w.taps - 1, w.conv_dim), xbc.dtype),
                           xbc])
    conv = sum(taps[:, k] * pad[k:k + T] for k in range(w.taps))
    conv = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    x = conv[:, :w.inner].reshape(T, H, P)
    B = conv[:, w.inner:w.inner + G * N].reshape(T, G, N)
    C = conv[:, w.inner + G * N:].reshape(T, G, N)
    delta = jax.nn.softplus(dt + p["dt_bias"])            # (T, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    rep = H // G

    def token(S, xs):
        x_t, B_t, C_t, d_t = xs
        Bh, Ch = jnp.repeat(B_t, rep, axis=0), jnp.repeat(C_t, rep, axis=0)
        S = jnp.exp(d_t * A)[:, None, None] * S \
            + (d_t[:, None] * x_t)[:, :, None] * Bh[:, None, :]
        y = jnp.sum(S * Ch[:, None, :], axis=-1)          # (H, P)
        return _carried(S, quant), y

    _, y = lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                    (x, B, C, delta))
    y = y + p["D"][:, None] * x
    y = (y.reshape(T, w.inner) * jax.nn.silu(z)).reshape(T, G, w.inner // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + w.norm_eps)
    return _mm(y.reshape(T, w.inner) * p["norm"], p["w_out"], quant)


def attention(h, p, *, w: Widths, quant: Optional[str] = None):
    """Grouped-query attention of the normed rows ``h`` with no positional
    term, over the whole sequence, ``ROW_BLOCK`` rows at a time."""
    T, H, G = h.shape[0], w.heads, w.heads // w.kv_heads
    q = _mm(h, p["wq"], quant).reshape(T, H, w.head)
    k = _cached(_mm(h, p["wk"], quant).reshape(T, w.kv_heads, w.head), quant)
    v = _cached(_mm(h, p["wv"], quant).reshape(T, w.kv_heads, w.head), quant)
    rb = min(ROW_BLOCK, T)
    n_rb = -(-T // rb)
    qp = jnp.pad(q, ((0, n_rb * rb - T), (0, 0), (0, 0)))
    col = jnp.arange(T)

    def one(args):
        qh, i, r0 = args                      # (rb, d), head, first row
        see = col[None, :] <= (r0 + jnp.arange(rb))[:, None]
        s = jnp.einsum("td,sd->ts", qh, k[:, i // G],
                       precision=HIGHEST) * w.head ** -0.5
        pr = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", pr, v[:, i // G], precision=HIGHEST)

    qb = qp.reshape(n_rb, rb, H, w.head).transpose(2, 0, 1, 3).reshape(
        H * n_rb, rb, w.head)
    o = lax.map(one, (qb, jnp.repeat(jnp.arange(H), n_rb),
                      jnp.tile(jnp.arange(n_rb) * rb, H)))
    o = o.reshape(H, n_rb * rb, w.head)[:, :T]
    return _mm(o.transpose(1, 0, 2).reshape(T, H * w.head), p["wo"], quant)


def _relu2(h, w1, w2, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(h, w1, quant))), w2, quant)


def router_scores(h, router, quant):
    if quant == "router_bf16":
        logits = jnp.matmul(h.astype(jnp.bfloat16),
                            router.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
    else:
        logits = _mm(h, router, quant)
    return jax.nn.sigmoid(logits)


def route(scores: jax.Array, bias: Optional[jax.Array],
          w: Widths) -> Tuple[jax.Array, jax.Array]:
    """The ``per_tok`` experts with the largest ``scores + bias`` of
    ``scores`` (T, experts), the lower index first among equals, and their
    weights: their own scores over their sum (departure 1), times the
    scale."""
    ranked = scores if bias is None else scores + bias
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    idx = order[:, :w.per_tok]
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if w.renorm:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
        return idx, wt * w.route_scale if w.scale_renormed else wt
    return idx, wt * w.route_scale


def ffn_parts(h, p, *, w: Widths, quant: Optional[str] = None, layer=None,
              held_first: Optional[int] = None):
    """A LatentMoE layer's two parts for normed rows ``h``: what the
    ``held`` routed experts from ``held_first`` on give, brought up from the
    latent (the router scoring and choosing among all ``experts``; a plain
    loop over the held ones in the latent, one expert's weights in float32
    at a time; ``W_up`` on their weighed sum), and what the shared expert
    gives on the full width. ``p`` holds one layer's leaves; with ``layer``
    its ``we*`` are the whole stack's and an expert is cut out of it by
    (layer, expert)."""
    first = w.held_first if held_first is None else held_first
    idx, weight = route(router_scores(h, p["router"], quant),
                        p.get("router_bias") if w.corrected else None, w)
    u = _mm(h, p["w_down"], quant)

    def of(name, e):
        return p[name][e] if layer is None else p[name][layer, e]

    def one_expert(r, e):
        we = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        return r + we[:, None] * _relu2(u, of("we1", e), of("we2", e),
                                        quant), None

    r, _ = lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(w.held))
    return (_mm(r, p["w_up"], quant),
            _relu2(h, p["ws1"], p["ws2"], quant))


_EXPERT_LEAVES = ("we1", "we2")


@functools.partial(jax.jit, static_argnames=("w", "quant", "kind"))
def _part(x, stack, i, *, w: Widths, quant: Optional[str], kind: str):
    if kind == "moe":
        p = {n: (a if n in _EXPERT_LEAVES else a[i])
             for n, a in stack.items()}
        routed, shared = ffn_parts(_rms(x, p["ln"], w.norm_eps), p, w=w,
                                   quant=quant, layer=i)
        return x + routed + shared
    p = jax.tree.map(lambda t: t[i], stack)
    fn = ssm_mixer if kind == "ssm" else attention
    return x + fn(_rms(x, p["ln"], w.norm_eps), p, w=w, quant=quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, wout, *, w: Widths, quant: Optional[str]):
    return _mm(_rms(x, ln_f, w.norm_eps), wout, quant)


def residuals(weights: Dict[str, Any], w: Widths, x: jax.Array, *,
              quant: Optional[str] = None):
    """The residual after every part, from the embedded rows ``x``: yields
    ``(layer, kind, x)`` in depth order."""
    seen = {"ssm": 0, "attn": 0, "moe": 0}
    for l, kind in enumerate(w.parts):
        x = _part(x, weights[kind], jnp.int32(seen[kind]), w=w, quant=quant,
                  kind=kind)
        seen[kind] += 1
        yield l, kind, x


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 512) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    so that few shapes compile; every part is causal, so the padding reaches
    no row that is read, and a row's experts are its own."""
    T = len(tokens)
    padded = -(-T // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:T] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for _, _, x in residuals(weights, w, x, quant=quant):
        pass
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["wout"],
                w=w, quant=quant)
    return np.asarray(out)
