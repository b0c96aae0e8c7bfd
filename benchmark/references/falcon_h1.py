"""The plain reference of the ``falcon_h1`` family: a decoder whose every
layer's mixer is TWO mixers side by side, a Mamba-2 state-space mixer and
rotary grouped-query attention, which read ONE normed residual and add into
it together, each under its own fixed scale, with a dense SwiGLU behind them;
the family's fixed scalar multipliers, every one an explicit scalar at the
place the equations give it; an untied head. The full forward pass over one
sequence: the state-space branch as the TOKEN-BY-TOKEN recurrence under
``lax.scan`` (never the chunked form the program runs: the two must not share
a mistake), the convolution a plain shifted sum, attention one dense causal
softmax over the whole sequence (computed a block of rows at a time so that
it fits at the published widths); float32 at the highest matmul precision; no
kernels, no cache, no batching. What every family's file gives is in
``README.md`` beside this file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
nothing of the harness, and nothing of another family's file (its recurrence
is written out here, with the multipliers ``nemotron_h.py``'s has no place
for).

Equations (Falcon-H1-34B-Instruct,
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json,
``model_type`` ``falcon_h1``). Write ``e`` = ``embedding_multiplier``,
``s_in`` / ``s_out`` = ``ssm_in_multiplier`` / ``ssm_out_multiplier``, ``m``
= ``ssm_multipliers`` (five), ``a_in`` / ``a_out`` =
``attention_in_multiplier`` / ``attention_out_multiplier``, ``kappa`` =
``key_multiplier``, ``g1, g2`` = ``mlp_multipliers``, ``lam`` =
``lm_head_multiplier``; RMSNorm of ``rms_norm_eps`` with a learned gain; no
bias on any projection, the convolution alone has one.

- ends: ``x_0 = e E[token]``; ``logits = lam (rms(x; norm) W_head)``
  (``tie_word_embeddings`` false).
- a layer: ``h = rms(x; ln1)``; ``x <- x + s_out SSM(s_in h) + a_out
  Attn(a_in h)`` (ONE norm, two branches, one sum:
  ``block.mixer_arrangement`` ``"parallel_shared_norm"``); then ``x <- x +
  MLP(rms(x; ln2))``.
- the SSM branch (Mamba-2; ``mamba_n_heads`` heads of ``mamba_d_head``,
  inner width ``mamba_d_ssm`` their product, NOT ``mamba_expand`` x hidden;
  ``mamba_n_groups``; ``mamba_d_state`` N; ``mamba_d_conv`` taps): ``[z | x
  | B | C | dt] = (u W_in) * mu``, ``mu`` the vector that is ``m_0`` over
  ``z``'s columns, ``m_1`` over ``x``'s, ``m_2`` over ``B``'s, ``m_3`` over
  ``C``'s, ``m_4`` over ``dt``'s (``block.mup_segments``), BEFORE the
  convolution's bias and the step's bias; ``xBC_t <- silu(sum_k w_k *
  xBC_{t-taps+1+k} + b)``, depthwise, causal, zero before the sequence; for
  head ``i`` of group ``i // (heads / groups)``: ``D_t = softplus(dt_t +
  dt_bias_i)`` (not clipped), ``a_t = exp(D_t A_i)``, ``A_i = -exp(A_log_i)``,
  ``S_t = a_t S_{t-1} + D_t x_t (x) B_t`` (``d_head x N``, zero before the
  sequence), ``y_t = S_t C_t + D_i x_t``; ``y <- rms_groups(y * silu(z);
  gain)`` over ``mamba_n_groups`` groups (``mamba_rms_norm`` true,
  ``mamba_norm_before_gate`` false: gate, then norm); the branch is ``y
  W_out``. ``mamba_chunk_size`` blocks a scan and is not mathematics.
- the attention branch: ``q = u W_q``, ``k = kappa (u W_k)``, ``v = u W_v``
  (``num_attention_heads`` x ``head_dim`` is not the hidden size); the rotary
  embedding over all of ``head_dim`` on ``q`` and ``k``, dimension ``i``
  paired with ``i + head_dim / 2`` (``block.rotary_convention``
  ``"half_split"``), the angles ``t . rope_theta^(-2i / head_dim)`` in
  float32, ``rope_scaling`` null; causal softmax of ``q . k / head_dim^1/2``;
  ``concat(heads) W_o``.
- the MLP: ``g2 (W_d (silu(g1 (W_g h)) * (W_u h)))`` (``g1`` inside the
  activation).

**The chip's share** (``deployment``): depth is the layers the file keeps;
the vocabulary is the rows held (the embedding's rows and the head's columns
of the slice: the logits are over the slice).

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands, the cached keys and values and the state-space branch's carried
state and conv rows to int8 (symmetric, per row / per output channel / per
token / per head), the precision below the bf16 the configuration states.
Three more are FAULTS of this mechanism's kind, for the limits to be held
against: ``"serial"`` (the attention branch reads the residual AFTER the SSM
branch was added, under a norm of its own input: two mixers in series),
``"no_ssm_branch"`` (the SSM term left out of the sum) and
``"unit_multipliers"`` (every multiplier 1).
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
CONTROLS = ("int8", "serial", "no_ssm_branch", "unit_multipliers")
ROW_BLOCK = 512      # rows of the attention computed at once
_SCALARS = ("embedding_multiplier", "lm_head_multiplier",
            "attention_in_multiplier", "attention_out_multiplier",
            "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")
_BLOCK = {"mixer_arrangement": "parallel_shared_norm",
          "mup_segments": ["z", "x", "B", "C", "dt"],
          "rotary_convention": "half_split"}


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    hidden: int
    layers: int
    ffn: int
    m_heads: int
    m_head: int
    m_groups: int
    m_state: int
    taps: int
    heads: int
    kv_heads: int
    head: int
    rope_theta: float
    norm_eps: float
    scalars: Tuple[Tuple[str, float], ...]   # the seven single multipliers
    ssm_mult: Tuple[float, ...]              # z, x, B, C, dt
    mlp_mult: Tuple[float, float]            # gate, down
    dt_min: float
    dt_max: float
    dtype: str
    scales: Tuple[Tuple[str, float], ...]    # assumed.seeded_scales, sorted

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.m_groups * self.m_state

    def scale(self, name: str) -> float:
        return dict(self.scales)[name]

    def mult(self, name: str) -> float:
        return dict(self.scalars)[name + "_multiplier"]

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        block = config.get("block") or {}
        for key, want in _BLOCK.items():
            if block.get(key) != want:
                raise ValueError(f"block.{key} other than {want!r}: not "
                                 f"this family as it is built")
        if not config.get("mamba_rms_norm") \
                or config.get("mamba_norm_before_gate") \
                or not config.get("mamba_use_mlp") \
                or not config.get("mamba_conv_bias") \
                or config.get("mamba_proj_bias") \
                or config.get("attn_layer_indices") is not None \
                or config.get("rope_scaling") is not None \
                or config.get("hidden_act") != "silu":
            raise ValueError(
                "no gated norm, the norm before the gate, no MLP, no conv "
                "bias, a projection bias, attention in some layers only, a "
                "scaled rotary or another activation: not this family")
        m_heads, m_head = (int(config["mamba_n_heads"]),
                           int(config["mamba_d_head"]))
        if int(config["mamba_d_ssm"]) != m_heads * m_head:
            raise ValueError("mamba_d_ssm is not heads x head")
        assumed = config["assumed"]
        return cls(
            vocab=int(config["vocab_size"]),
            hidden=int(config["hidden_size"]),
            layers=int(config["num_hidden_layers"]),
            ffn=int(config["intermediate_size"]),
            m_heads=m_heads, m_head=m_head,
            m_groups=int(config["mamba_n_groups"]),
            m_state=int(config["mamba_d_state"]),
            taps=int(config["mamba_d_conv"]),
            heads=int(config["num_attention_heads"]),
            kv_heads=int(config["num_key_value_heads"]),
            head=int(config["head_dim"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            scalars=tuple((k, float(config[k])) for k in _SCALARS),
            ssm_mult=tuple(float(v) for v in config["ssm_multipliers"]),
            mlp_mult=tuple(float(v) for v in config["mlp_multipliers"]),
            dt_min=float(assumed["time_step_min"]),
            dt_max=float(assumed["time_step_max"]),
            dtype=str(config.get("torch_dtype", "bfloat16")),
            scales=tuple(sorted(
                (k, float(v))
                for k, v in assumed["seeded_scales"].items())),
        )


# -- weights -----------------------------------------------------------------


def _leaf(key, shape, stddev: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w",))
def _init_weights(seed: jax.Array, w: Widths) -> Dict[str, Any]:
    dtype = jnp.dtype(w.dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    D = w.hidden
    ones = lambda n: jnp.ones((n,), jnp.float32)   # noqa: E731

    def layer(key):
        k_w, k_a, k_dt, k_g = jax.random.split(key, 4)
        shapes = {
            "wq": ((D, w.heads * w.head), w.scale("qk_std")),
            "wk": ((D, w.kv_heads * w.head), w.scale("qk_std")),
            "wv": ((D, w.kv_heads * w.head), w.scale("v_std")),
            "wo": ((w.heads * w.head, D), w.scale("attn_out_std")),
            "w_in": ((D, 2 * w.inner + 2 * w.m_groups * w.m_state
                      + w.m_heads), w.scale("ssm_in_std")),
            "conv_w": ((w.conv_dim, w.taps), w.taps ** -0.5),
            "w_out": ((w.inner, D), w.scale("ssm_out_std")),
            "wg": ((D, w.ffn), w.scale("mlp_gate_std")),
            "wu": ((D, w.ffn), w.scale("mlp_up_std")),
            "wd": ((w.ffn, D), w.scale("mlp_down_std")),
        }
        kk = jax.random.split(k_w, len(shapes))
        # The Mamba-2 init: -A uniform over 1-16; the step log-uniform over
        # [time_step_min, time_step_max], dt_bias its inverse softplus.
        dt = jnp.exp(jax.random.uniform(
            k_dt, (w.m_heads,), jnp.float32,
            jnp.log(w.dt_min), jnp.log(w.dt_max)))
        return {
            "ln1": ones(D), "ln2": ones(D),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (w.m_heads,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": ones(w.m_heads),
            "conv_b": jnp.zeros((w.conv_dim,), dtype),
            "norm": w.scale("gain_mean") + w.scale("gain_std")
            * jax.random.normal(k_g, (w.inner,), jnp.float32),
            **{n: _leaf(k, shape, sd, dtype)
               for k, (n, (shape, sd)) in zip(kk, shapes.items())}}

    return {"embed": _leaf(ks[0], (w.vocab, D), w.scale("embedding_std"),
                           dtype),
            "wout": _leaf(ks[2], (D, w.vocab), w.scale("head_std"), dtype),
            "ln_f": ones(D),
            "layers": lax.map(layer, jax.random.split(ks[1], w.layers))}


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: ``layers`` (every leaf of a layer on a leading axis of the
    layers), ``embed``, ``wout`` and ``ln_f``. Normal; the taps at
    ``taps^-1/2``, their bias zero; ``A_log`` so that ``-A`` spans 1-16 and
    ``dt_bias`` so that the step spans ``time_step_min``-``time_step_max``
    log-uniformly (Mamba-2's init: a state's memory then runs from under a
    token to ~1,000 tokens, so a stale state shows); ``D`` at one; the
    pre-norms' gains at one, the gated norm's at ``gain_mean`` +-
    ``gain_std`` (never one: a dropped gain shows). Every projection's
    scale is the configuration file's (``assumed.seeded_scales``, with the
    reckoning that chose them): a model trained under these multipliers
    holds weights that are large where a multiplier is small, so that AFTER
    the multipliers every part is of the size it has in any decoder.
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
    return _fake_int8(rows, -1) if quant == "int8" else rows


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """The rotary embedding of ``x`` ``(T, heads, d)`` at positions 0..T-1,
    dimension ``i`` paired with ``i + d / 2``, the angles in float32."""
    T, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs     # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mults(w: Widths, quant: Optional[str]):
    """``(the seven scalars by short name, ssm_multipliers,
    mlp_multipliers)``; all ones under the ``unit_multipliers`` fault."""
    if quant == "unit_multipliers":
        return (lambda name: 1.0), (1.0,) * 5, (1.0, 1.0)
    return w.mult, w.ssm_mult, w.mlp_mult


def ssm_branch(u, p, *, w: Widths, quant: Optional[str] = None):
    """The Mamba-2 branch of the rows ``u`` ``(T, hidden)`` (the normed
    residual times ``s_in``): the convolution a plain sum of shifted copies,
    the state-space layer the recurrence itself, one token after another.
    What it adds BEFORE ``s_out``."""
    T, H, P, G, N = u.shape[0], w.m_heads, w.m_head, w.m_groups, w.m_state
    gn = G * N
    _, m, _ = _mults(w, quant)
    mu = jnp.concatenate([jnp.full((n,), v, jnp.float32) for n, v in zip(
        (w.inner, w.inner, gn, gn, H), m)])
    zxd = _mm(u, p["w_in"], quant) * mu
    z, xbc, dt = (zxd[:, :w.inner], zxd[:, w.inner:w.inner + w.conv_dim],
                  zxd[:, w.inner + w.conv_dim:])
    xbc = _cached(xbc, quant)
    taps = p["conv_w"].astype(jnp.float32)                # (conv_dim, taps)
    pad = jnp.concatenate([jnp.zeros((w.taps - 1, w.conv_dim), xbc.dtype),
                           xbc])
    conv = sum(taps[:, k] * pad[k:k + T] for k in range(w.taps))
    conv = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    x = conv[:, :w.inner].reshape(T, H, P)
    B = conv[:, w.inner:w.inner + gn].reshape(T, G, N)
    C = conv[:, w.inner + gn:].reshape(T, G, N)
    delta = jax.nn.softplus(dt + p["dt_bias"])            # (T, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    rep = H // G

    def token(S, xs):
        x_t, B_t, C_t, d_t = xs
        Bh, Ch = jnp.repeat(B_t, rep, axis=0), jnp.repeat(C_t, rep, axis=0)
        S = jnp.exp(d_t * A)[:, None, None] * S \
            + (d_t[:, None] * x_t)[:, :, None] * Bh[:, None, :]
        y = jnp.sum(S * Ch[:, None, :], axis=-1)          # (H, P)
        if quant == "int8":
            S = _fake_int8(S, (-2, -1))
        return S, y

    _, y = lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                    (x, B, C, delta))
    y = y + p["D"][:, None] * x
    y = (y.reshape(T, w.inner) * jax.nn.silu(z)).reshape(T, G, w.inner // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + w.norm_eps)
    return _mm(y.reshape(T, w.inner) * p["norm"], p["w_out"], quant)


def attention_branch(u, p, *, w: Widths, quant: Optional[str] = None):
    """Rotary grouped-query attention of the rows ``u`` (the normed residual
    times ``a_in``), one causal softmax over the whole sequence, computed
    ``ROW_BLOCK`` rows at a time. What it adds BEFORE ``a_out``."""
    T, H, G = u.shape[0], w.heads, w.heads // w.kv_heads
    mult, _, _ = _mults(w, quant)
    q = _mm(u, p["wq"], quant).reshape(T, H, w.head)
    k = mult("key") * _mm(u, p["wk"], quant).reshape(T, w.kv_heads, w.head)
    v = _mm(u, p["wv"], quant).reshape(T, w.kv_heads, w.head)
    q, k = _rope(q, w.rope_theta), _cached(_rope(k, w.rope_theta), quant)
    v = _cached(v, quant)
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


def mlp(h, p, *, w: Widths, quant: Optional[str] = None):
    _, _, (g1, g2) = _mults(w, quant)
    gate = jax.nn.silu(g1 * _mm(h, p["wg"], quant))
    return g2 * _mm(gate * _mm(h, p["wu"], quant), p["wd"], quant)


def layer_parts(x, p, *, w: Widths, quant: Optional[str] = None):
    """One layer from the residual ``x``: what the SSM branch, the attention
    branch and the MLP each add (after their multipliers), and the residual
    the MLP joins."""
    mult, _, _ = _mults(w, quant)
    h = _rms(x, p["ln1"], w.norm_eps)
    add_s = mult("ssm_out") * ssm_branch(mult("ssm_in") * h, p, w=w,
                                         quant=quant)
    if quant == "no_ssm_branch":
        add_s = jnp.zeros_like(add_s)
    if quant == "serial":
        h = _rms(x + add_s, p["ln1"], w.norm_eps)
    add_a = mult("attention_out") * attention_branch(
        mult("attention_in") * h, p, w=w, quant=quant)
    mid = x + add_s + add_a
    return add_s, add_a, mlp(_rms(mid, p["ln2"], w.norm_eps), p, w=w,
                             quant=quant), mid


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _layer(x, stack, i, *, w: Widths, quant: Optional[str]):
    p = jax.tree.map(lambda t: t[i], stack)
    _, _, add_m, mid = layer_parts(x, p, w=w, quant=quant)
    return mid + add_m


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _embed(embed, ids, *, w: Widths, quant: Optional[str]):
    return _mults(w, quant)[0]("embedding") * embed[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, wout, *, w: Widths, quant: Optional[str]):
    return _mults(w, quant)[0]("lm_head") * _mm(
        _rms(x, ln_f, w.norm_eps), wout, quant)


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 512) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    so that few shapes compile; every part is causal, so the padding reaches
    no row that is read."""
    T = len(tokens)
    padded = -(-T // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:T] = tokens
    x = _embed(weights["embed"], jnp.asarray(ids), w=w, quant=quant)
    for i in range(w.layers):
        x = _layer(x, weights["layers"], jnp.int32(i), w=w, quant=quant)
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["wout"],
                w=w, quant=quant)
    return np.asarray(out)
