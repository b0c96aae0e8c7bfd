"""The plain reference of the ``exaone_moe`` family: a decoder whose
attention layers are of two kinds (a sliding window of the last ``window``
positions with the rotary embedding, or the whole context with no positional
term), an RMSNorm over each query and key head in both, over two kinds of
feed-forward half (a dense SwiGLU in the leading layer, then routed experts
under a sigmoid router with a per-expert correction of the choice, beside one
shared expert), an untied head. The full forward pass over one sequence: the
window a mask, attention in blocks of rows over the whole sequence (so that a
9k-token context fits), every held expert a loop; float32 at the highest
matmul precision; no kernels, no cache, no batching. What every family's file
gives is in ``README.md`` beside this file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
nothing of the harness.

Equations (K-EXAONE-236B-A23B,
https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B, ``model_type``
``exaone_moe``), one layer ``l``, residual ``x``, every norm an RMSNorm with
a learned gain and ``rms_norm_eps``; ``x += Attn_kind(norm1(x))``, ``x +=
FFN(norm2(x))`` (``block.norm_placement`` ``"pre"``):

- ``Attn``, ``h = norm1(x)``: ``q = h W_q`` -> ``heads`` of ``head_dim``,
  ``k = h W_k``, ``v = h W_v`` -> KV heads (query head ``i`` reads KV head
  ``i // (heads / kv_heads)``); where ``block.qk_norm``, ``q = rms_head(q;
  q_norm)``, ``k = rms_head(k; k_norm)`` over each head's values (one gain
  of ``head_dim`` a layer). By ``layer_types[l]``:
  - ``sliding_attention``: row ``t`` sees positions ``(t - sliding_window,
    t]``;
  - ``full_attention``: row ``t`` sees ``[0, t]``.
  A layer whose kind ``block.rotary_layers`` names (``"sliding_attention"``;
  ``"all"``: both) rotates ``q`` and ``k`` (base
  ``rope_parameters.rope_theta``, every dimension, no scaling); the other
  kind has no positional term. Softmax of ``q . k / head_dim^1/2``, ``y =
  concat(heads) W_o``.
- ``FFN``, ``g = norm2(x)``: layers below ``first_k_dense_replace``
  ``W_2(silu(W_1 g) * W_3 g)`` at ``intermediate_size``; the others ``s =
  sigmoid(g W_r)`` over the ``deployment.experts_total`` routed experts; the
  ``num_experts_per_tok`` chosen are those with the largest ``s + b``
  (``block.corrected_choice``; else of ``s``; ties to the lowest index),
  their weights the uncorrected ``s`` of the chosen over their sum
  (``norm_topk_prob``) times ``routed_scaling_factor``
  (``block.scale_renormed``); the output ``sum_e w_e E_e(g) + E_shared(g)``,
  every ``E`` a SwiGLU of ``moe_intermediate_size`` (the shared one of
  ``num_shared_experts`` times that). ``n_group`` = ``topk_group`` = 1: no
  group limit.
- ends: logits ``= rms(x; norm) W_out`` (``tie_word_embeddings`` false).

**The chip's share** (``deployment``): the router scores all
``experts_total`` experts and chooses among all of them; this file computes
the ``num_experts`` experts held here, ``[expert_share * num_experts,
(expert_share + 1) * num_experts)``, and leaves out what the absent ones
would add, as the program does; the shared expert is computed whole (every
chip computes it for its own rows); the vocabulary is the rows held.
:func:`ffn_parts` gives the routed and the shared part apart, for the test
that adds the shares up.

Departures, each noted where it is made: (1) rotary pairs are taken as halves
``(i, i + head/2)``: with seeded weights a relabelling of columns. (2) The
chosen scores' sum is divided with ``1e-20`` added (the DeepSeek-V3 router's
own constant, which the program's ``_weigh`` has too). (3) Depth is the
layers the file keeps. (4) The multi-token-prediction module
(``num_nextn_predict_layers``) is not built (``not_built``): it adds nothing
to these logits.

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands and the cached keys and values to int8 (symmetric, per row / per
output channel / per token), the precision below the bf16 the configuration
states; ``"state_int8"`` rounds the cached rows alone; ``"router_bf16"``
computes the router's logits from bfloat16 operands. Two more are FAULTS of
the program's kind, for the limits to be held against
(``benchmark/calibrate.py --control``): ``"no_window"`` lets a window layer's
rows see their whole context, ``"rotate_all"`` rotates the full layers too.
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
CONTROLS = ("int8", "state_int8", "router_bf16", "no_window", "rotate_all")
_MIXERS = {"sliding_attention": "window", "full_attention": "attention"}
ROW_BLOCK = 512      # rows of the attention computed at once


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    hidden: int
    layer_types: Tuple[str, ...]      # "window" | "attention", one a layer
    window: int
    rotary: Tuple[str, ...]           # the kinds that rotate q and k
    qk_norm: bool
    heads: int
    kv_heads: int
    head: int
    ffn: int
    n_dense: int
    experts: int                      # the router's width
    held: int
    held_first: int
    per_tok: int
    expert_ffn: int
    shared_ffn: int
    route_scale: float
    renorm: bool
    scale_renormed: bool
    corrected: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    scales: Tuple[Tuple[str, float], ...]   # assumed.seeded_scales, sorted

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    def scale(self, name: str) -> float:
        return dict(self.scales)[name]

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        heads = int(config["num_attention_heads"])
        hidden = int(config["hidden_size"])
        layers = int(config["num_hidden_layers"])
        types = tuple(_MIXERS[t] for t in config["layer_types"])
        if len(types) != layers:
            raise ValueError(f"{len(types)} layer_types for {layers} layers")
        block = config.get("block") or {}
        if block.get("norm_placement", "pre") != "pre":
            raise ValueError("norm_placement other than 'pre': not built")
        if int(config.get("n_group", 1)) != 1 \
                or int(config.get("topk_group", 1)) != 1 \
                or config.get("scoring_func") != "sigmoid":
            raise ValueError("a group limit / another scoring: not this family")
        said = block.get("rotary_layers", "all")
        rotary = ("window", "attention") if said == "all" else tuple(
            _MIXERS[t] for t in ([said] if isinstance(said, str) else said))
        dep = config.get("deployment") or {}
        held = int(config["num_experts"])
        ffn = int(config["moe_intermediate_size"])
        return cls(
            vocab=int(config["vocab_size"]), hidden=hidden,
            layer_types=types, window=int(config["sliding_window"]),
            rotary=rotary, qk_norm=bool(block.get("qk_norm", False)),
            heads=heads, kv_heads=int(config["num_key_value_heads"]),
            head=int(config.get("head_dim") or hidden // heads),
            ffn=int(config["intermediate_size"]),
            n_dense=int(config["first_k_dense_replace"]),
            experts=int(dep.get("experts_total", held)), held=held,
            held_first=int(dep.get("expert_share", 0)) * held,
            per_tok=int(config["num_experts_per_tok"]),
            expert_ffn=ffn,
            shared_ffn=int(config.get("num_shared_experts") or 0) * ffn,
            route_scale=float(config["routed_scaling_factor"]),
            renorm=bool(config["norm_topk_prob"]),
            scale_renormed=bool(block.get("scale_renormed", False)),
            corrected=bool(block.get("corrected_choice", False)),
            rope_theta=float(config["rope_parameters"]["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
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
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    D = w.hidden
    ones = lambda n: jnp.ones((n,), jnp.float32)   # noqa: E731

    def leaves(key, shapes):
        kk = jax.random.split(key, len(shapes))
        return {n: _leaf(k, shape, sd, dtype)
                for k, (n, (shape, sd)) in zip(kk, shapes.items())}

    def attn(key):
        k_w, k_q, k_k = jax.random.split(key, 3)
        gain = lambda k: w.scale("qk_gain_mean") + w.scale(   # noqa: E731
            "qk_gain_std") * jax.random.normal(k, (w.head,), jnp.float32)
        out = {"ln1": ones(D), **leaves(k_w, {
            "wq": ((D, w.heads * w.head), 0.02),
            "wk": ((D, w.kv_heads * w.head), 0.02),
            "wv": ((D, w.kv_heads * w.head), 0.02),
            "wo": ((w.heads * w.head, D), w.scale("attn_out_std"))})}
        if w.qk_norm:
            out.update(q_ln=gain(k_q), k_ln=gain(k_k))
        return out

    def dense(key):
        return {"ln2": ones(D), **leaves(key, {
            "w1": ((D, w.ffn), 0.02), "w3": ((D, w.ffn), 0.02),
            "w2": ((w.ffn, D), w.scale("dense_down_std"))})}

    def moe(key):
        k_r, k_b, k_e, k_s = jax.random.split(key, 4)

        def one_expert(k):
            return leaves(k, {
                "we1": ((D, w.expert_ffn), 0.02),
                "we3": ((D, w.expert_ffn), 0.02),
                "we2": ((w.expert_ffn, D), w.scale("expert_down_std"))})

        out = {"ln2": ones(D),
               "router": _leaf(k_r, (D, w.experts), 0.02, dtype),
               **lax.map(one_expert, jax.random.split(k_e, w.held))}
        if w.corrected:
            out["router_bias"] = _leaf(
                k_b, (w.experts,), w.scale("router_bias_std"), jnp.float32)
        if w.shared_ffn:
            out.update(leaves(k_s, {
                "ws1": ((D, w.shared_ffn), 0.02),
                "ws3": ((D, w.shared_ffn), 0.02),
                "ws2": ((w.shared_ffn, D), w.scale("shared_down_std"))}))
        return out

    n_win = w.layer_types.count("window")
    out = {"embed": _leaf(ks[0], (w.vocab, D), w.scale("embedding_std"),
                          dtype),
           "wout": _leaf(ks[5], (D, w.vocab), w.scale("head_std"), dtype),
           "ln_f": ones(D)}
    for name, make, n, k in (("wattn", attn, n_win, ks[1]),
                             ("attn", attn, w.layers - n_win, ks[2]),
                             ("dense", dense, min(w.n_dense, w.layers), ks[3]),
                             ("moe", moe, max(w.layers - w.n_dense, 0),
                              ks[4])):
        if n:
            out[name] = lax.map(make, jax.random.split(k, n))
    return out


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: a stack a kind of part (``wattn``: the window layers' attention,
    ``attn``: the full layers', each on a leading axis of that kind's layers
    in depth order; ``dense``, ``moe``: the feed-forward halves, likewise;
    an expert layer holds the ``held`` experts of the chip's share, the
    router's ``experts`` columns, the correction bias and the shared
    expert), ``embed``, ``wout`` and ``ln_f``. Normal; every projection INTO
    a part at std 0.02 (the router too); the norms' gains at one but the
    QK-norm's (``qk_gain_mean`` +- ``qk_gain_std``: never one, a dropped
    gain shows). The scales that decide how much a part adds to the residual
    it joins are the configuration file's (``assumed.seeded_scales``, with
    the reckoning that chose them)."""
    return _init_weights(jnp.uint32(int(seed) % (2 ** 32)), w)


# -- the forward pass --------------------------------------------------------


def _fake_int8(x: jax.Array, axis: int) -> jax.Array:
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


def _rope(x: jax.Array, w: Widths) -> jax.Array:
    """``x`` is (T, heads, d); position t is row t. Pairs are halves
    (departure 1)."""
    T, half = x.shape[0], x.shape[-1] // 2
    freqs = w.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, p, *, w: Widths, kind: str, quant: Optional[str] = None):
    """Grouped-query attention of the normed rows ``h`` ``(T, hidden)`` of a
    layer of ``kind``: the norm over each query and key head, the rotation
    where the kind has one, the window as a mask, over the whole sequence,
    ``ROW_BLOCK`` rows at a time."""
    T, H, G = h.shape[0], w.heads, w.heads // w.kv_heads
    q = _mm(h, p["wq"], quant).reshape(T, H, w.head)
    k = _mm(h, p["wk"], quant).reshape(T, w.kv_heads, w.head)
    v = _mm(h, p["wv"], quant).reshape(T, w.kv_heads, w.head)
    if w.qk_norm:
        q, k = _rms(q, p["q_ln"], w.norm_eps), _rms(k, p["k_ln"], w.norm_eps)
    if kind in w.rotary or quant == "rotate_all":
        q, k = _rope(q, w), _rope(k, w)
    k, v = _cached(k, quant), _cached(v, quant)
    window = w.window if kind == "window" and quant != "no_window" else None
    rb = min(ROW_BLOCK, T)
    n_rb = -(-T // rb)
    qp = jnp.pad(q, ((0, n_rb * rb - T), (0, 0), (0, 0)))
    col = jnp.arange(T)

    def one(args):
        qh, i, r0 = args                      # (rb, d), head, first row
        row = r0 + jnp.arange(rb)
        see = col[None, :] <= row[:, None]
        if window is not None:
            see &= col[None, :] > row[:, None] - window
        s = jnp.einsum("td,sd->ts", qh, k[:, i // G],
                       precision=HIGHEST) * w.head ** -0.5
        pr = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", pr, v[:, i // G], precision=HIGHEST)

    qb = qp.reshape(n_rb, rb, H, w.head).transpose(2, 0, 1, 3).reshape(
        H * n_rb, rb, w.head)
    heads = jnp.repeat(jnp.arange(H), n_rb)
    firsts = jnp.tile(jnp.arange(n_rb) * rb, H)
    o = lax.map(one, (qb, heads, firsts))     # (H * n_rb, rb, d)
    o = o.reshape(H, n_rb * rb, w.head)[:, :T]
    return _mm(o.transpose(1, 0, 2).reshape(T, H * w.head), p["wo"], quant)


def _swiglu(h, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(h, w1, quant)) * _mm(h, w3, quant), w2, quant)


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
    weights: their own scores over their sum (departure 2), times the
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
    """An expert layer's two parts for normed rows ``h``: what the ``held``
    routed experts from ``held_first`` on give (the router scoring and
    choosing among all ``experts``; a plain loop over the held ones, one
    expert's weights in float32 at a time), and what the shared expert
    gives. ``p`` holds one layer's leaves; with ``layer`` its ``we*`` are
    the whole stack's and an expert is cut out of it by (layer, expert)."""
    first = w.held_first if held_first is None else held_first
    idx, weight = route(router_scores(h, p["router"], quant),
                        p.get("router_bias") if w.corrected else None, w)

    def of(name, e):
        return p[name][e] if layer is None else p[name][layer, e]

    def one_expert(y, e):
        we = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        out = _swiglu(h, of("we1", e), of("we3", e), of("we2", e), quant)
        return y + we[:, None] * out, None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(w.held))
    shared = jnp.zeros_like(h)
    if w.shared_ffn:
        shared = _swiglu(h, p["ws1"], p["ws3"], p["ws2"], quant)
    return routed, shared


_EXPERT_LEAVES = ("we1", "we3", "we2")


@functools.partial(jax.jit, static_argnames=("w", "quant", "kind"))
def _mixer(x, stack, i, *, w: Widths, quant: Optional[str], kind: str):
    p = jax.tree.map(lambda t: t[i], stack)
    return x + attention(_rms(x, p["ln1"], w.norm_eps), p, w=w, kind=kind,
                         quant=quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _dense_ffn(x, stack, i, *, w: Widths, quant: Optional[str]):
    p = jax.tree.map(lambda t: t[i], stack)
    return x + _swiglu(_rms(x, p["ln2"], w.norm_eps),
                       p["w1"], p["w3"], p["w2"], quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _moe_ffn(x, stack, i, *, w: Widths, quant: Optional[str]):
    p = {n: (a if n in _EXPERT_LEAVES else a[i]) for n, a in stack.items()}
    routed, shared = ffn_parts(_rms(x, p["ln2"], w.norm_eps), p, w=w,
                               quant=quant, layer=i)
    return x + routed + shared


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, wout, *, w: Widths, quant: Optional[str]):
    return _mm(_rms(x, ln_f, w.norm_eps), wout, quant)


def residuals(weights: Dict[str, Any], w: Widths, x: jax.Array, *,
              quant: Optional[str] = None):
    """The residual after every half layer, from the embedded rows ``x``:
    yields ``(layer, "mixer" | "ffn", x)`` in depth order."""
    seen = {"window": 0, "attention": 0}
    for l, kind in enumerate(w.layer_types):
        stack = weights["wattn" if kind == "window" else "attn"]
        x = _mixer(x, stack, jnp.int32(seen[kind]), w=w, quant=quant,
                   kind=kind)
        seen[kind] += 1
        yield l, "mixer", x
        if l < w.n_dense:
            x = _dense_ffn(x, weights["dense"], jnp.int32(l), w=w,
                           quant=quant)
        else:
            x = _moe_ffn(x, weights["moe"], jnp.int32(l - w.n_dense), w=w,
                         quant=quant)
        yield l, "ffn", x


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 512) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    so that few shapes compile; attention is causal, so the padding reaches
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
