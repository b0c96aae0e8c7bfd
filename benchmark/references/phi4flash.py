"""The plain reference of the ``phi4flash`` family: a decoder that feeds a
second decoder. Below the seam Mamba-1 state-space layers and sliding-window
attention layers in turn; then one more Mamba-1 layer, whose scan output is
kept as the MEMORY, and ONE full-attention layer, whose keys and values are
the SHARED rows; above it gated memory units (a gate on the memory of the
same position) and cross layers (queries of their own over the shared rows)
in turn. Every attention is differential. The full forward pass over one
sequence: the recurrence TOKEN BY TOKEN under ``lax.scan``, the convolution a
plain shifted sum, differential attention AS ITS DEFINITION (the two
softmaxes of a pair over 64-wide heads, each times the pair's two value heads
side by side; no padded query, no packed row), one dense causal or banded
mask, every layer on every row (no row leaves the stack early); float32 at
the highest matmul precision; no kernels, no cache, no batching. What every
family's file gives is in ``README.md`` beside this file.

Independent of the program: it imports nothing of ``tree_attention_tpu``,
nothing of the harness and nothing of another family's file.

Equations (Phi-4-mini-flash-reasoning,
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json,
``model_type`` ``phi4flash``; the arrangement and the differential form from
arXiv:2507.06607 and arXiv:2410.05258, written from memory: every rule the
published keys do not carry is one key of the file's ``block`` group or a line
under ``assumed``, and any other value is refused). ``LN`` is LayerNorm with
mean, gain and bias (``layer_norm_eps``). No positional encoding anywhere.

- ends: ``x_0 = E[token]``; after the last layer ``LN``; ``logits = h E^T``
  (tied, no bias).
- a layer ``l``: ``x <- x + Mixer_l(LN_1(x))``; ``x <- x + W_2 (silu(g) * u)``
  with ``[g | u] = LN_2(x) W_1`` (one matrix, split in that order:
  ``block.mlp_order`` ``"gate_up"``).
- which mixer (``n`` layers, ``block.decoder_split`` ``"sambay"``): ``l < n /
  2``: even Mamba-1, odd window attention; ``l = n / 2``: Mamba-1, whose scan
  output is the memory ``m``; ``l = n / 2 + 1``: full attention, whose ``k,
  v`` are the shared rows; above: even a gated memory unit, odd a cross
  layer.
- Mamba-1 (``assumed``: ``mamba_expand`` x hidden channels ``I``,
  ``mamba_d_state`` ``N``, ``mamba_d_conv`` taps, ``mamba_dt_rank`` ``R``):
  ``[x | z] = h W_in``; ``x <- silu(conv(x) + b)`` depthwise, causal, zero
  before the sequence; ``[d | B | C] = x W_x``; ``D_t = softplus(d W_dt +
  b_dt)``; ``A = -exp(A_log)`` ``(I, N)``; ``S_t[c, n] = exp(D_t[c] A[c, n])
  S_{t-1}[c, n] + D_t[c] B_t[n] x_t[c]``; ``y_t[c] = sum_n C_t[n] S_t[c, n] +
  D[c] x_t[c]``; the mixer adds ``(y * silu(z)) W_out``. ``m = y`` of the
  memory layer (``block.gmu_memory`` ``"scan_output_before_gate"``).
- a gated memory unit: the mixer adds ``(silu(h W_in) * m) W_out``.
- differential attention (``block.attention`` ``"differential"``,
  ``block.diff_pairing`` ``"adjacent"``; ``s = head^-1/2``): ``q = h W_q +
  b``, and in a window or full layer ``k, v = h W_k + b, h W_v + b`` (one
  matrix ``W_qkv``). Query head ``2p + sig`` reads key head ``2j + sig`` and
  the value PAIR ``V_j = [v_2j | v_2j+1]`` with ``j = p // (heads /
  kv_heads)``: ``a_{p,sig} = softmax(s q k^T + mask) V_j``; ``lam =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)``, ``lam0(l) = 0.8 - 0.6
  e^(-0.3 l)`` (``block.lambda_depth`` ``"layer_index_from_0"``); ``o_p =
  RMSNorm(a_{p,0} - lam a_{p,1}) (1 - lam0(l))`` (a gain of ``2 x head``);
  the pairs' lanes through ``W_o + b``. Mask: causal; a window layer's row
  at ``t`` sees ``(t - sliding_window, t]``. A cross layer has ``W_q``, its
  bias, the lambdas, the norm and ``W_o`` only and reads the full layer's
  ``k, v`` over the whole context (``block.cross_attention``
  ``"differential"``).

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands, the cached keys and values and the carried state to int8, the
precision below the bf16 the configuration states. Four more are FAULTS of
this model's kind: ``"no_diff"`` (``a_{p,1}``'s term left out),
``"own_rows"`` (a cross layer reads the nearest window layer's rows, under
its window, instead of the shared rows), ``"stale_memory"`` (a gated memory
unit multiplies by the PREVIOUS position's ``m``) and ``"scalar_decay"``
(``A[c, n]`` replaced by its mean over ``n``: Mamba-2's form).
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
CONTROLS = ("int8", "no_diff", "own_rows", "stale_memory", "scalar_decay")
ROW_BLOCK = 512      # rows of the attention computed at once
_BLOCK = {"decoder_split": "sambay", "attention": "differential",
          "diff_pairing": "adjacent", "cross_attention": "differential",
          "lambda_depth": "layer_index_from_0",
          "gmu_memory": "scan_output_before_gate", "mlp_order": "gate_up"}


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    hidden: int
    layers: int
    ffn: int
    heads: int
    kv_heads: int
    window: int
    inner: int
    state: int
    taps: int
    dt_rank: int
    norm_eps: float
    dt_min: float
    dt_max: float
    dtype: str
    scales: Tuple[Tuple[str, float], ...]    # assumed.seeded_scales, sorted

    @property
    def head(self) -> int:
        return self.hidden // self.heads

    def scale(self, name: str) -> float:
        return dict(self.scales)[name]

    def kind(self, l: int) -> str:
        """Layer ``l``'s mixer."""
        half = self.layers // 2
        if l < half:
            return "mamba" if l % 2 == 0 else "swa"
        if l <= half + 1:
            return "mamba" if l == half else "full"
        return "gmu" if l % 2 == 0 else "cross"

    def count(self, kind: str) -> int:
        return sum(self.kind(l) == kind for l in range(self.layers))

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        block = config.get("block") or {}
        for key, want in _BLOCK.items():
            if block.get(key) != want:
                raise ValueError(f"block.{key} other than {want!r}: not "
                                 f"this family as it is built")
        layers = int(config["num_hidden_layers"])
        if int(config.get("mb_per_layer", 2)) != 2 or layers % 4 \
                or config.get("mlp_bias") or config.get("lm_head_bias") \
                or not config.get("tie_word_embeddings") \
                or config.get("hidden_act") != "silu" \
                or int(block.get("window_span", config["sliding_window"])) \
                != int(config["sliding_window"]):
            raise ValueError(
                "mb_per_layer other than 2, a depth that is no multiple of "
                "4, an MLP or head bias, an untied head, another activation "
                "or another window span: not this family")
        assumed = config["assumed"]
        hidden = int(config["hidden_size"])
        return cls(
            vocab=int(config["vocab_size"]), hidden=hidden, layers=layers,
            ffn=int(config["intermediate_size"]),
            heads=int(config["num_attention_heads"]),
            kv_heads=int(config["num_key_value_heads"]),
            window=int(config["sliding_window"]),
            inner=int(assumed["mamba_expand"]) * hidden,
            state=int(assumed["mamba_d_state"]),
            taps=int(assumed["mamba_d_conv"]),
            dt_rank=int(assumed["mamba_dt_rank"]),
            norm_eps=float(config["layer_norm_eps"]),
            dt_min=float(assumed["time_step_min"]),
            dt_max=float(assumed["time_step_max"]),
            dtype=str(config.get("torch_dtype", "bfloat16")),
            scales=tuple(sorted(
                (k, float(v))
                for k, v in assumed["seeded_scales"].items())),
        )


# -- weights -----------------------------------------------------------------


def _leaf(key, shape, stddev, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w",))
def _init_weights(seed: jax.Array, w: Widths) -> Dict[str, Any]:
    dtype = jnp.dtype(w.dtype)
    D, I, N, R, d = w.hidden, w.inner, w.state, w.dt_rank, w.head
    f32 = jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def ln(key):
        kg, kb = jax.random.split(key)
        return {"ln_g": 1.0 + w.scale("ln_gain_std")
                * jax.random.normal(kg, (D,), f32),
                "ln_b": w.scale("ln_bias_std")
                * jax.random.normal(kb, (D,), f32)}

    def leaves(key, shapes):
        kk = jax.random.split(key, len(shapes))
        return {n: _leaf(k, shape, sd, dtype)
                for k, (n, (shape, sd)) in zip(kk, shapes.items())}

    def mlp(key):
        k_n, k_w = jax.random.split(key)
        return {**ln(k_n), **leaves(k_w, {
            "w1": ((D, 2 * w.ffn), w.scale("mlp_in_std")),
            "w2": ((w.ffn, D), w.scale("mlp_out_std"))})}

    def mamba(key):
        k_n, k_w, k_dt = jax.random.split(key, 3)
        # The family's init: -A is 1..N on every channel; the step
        # log-uniform over [time_step_min, time_step_max], its bias the
        # inverse softplus; D at one.
        dt = jnp.exp(jax.random.uniform(
            k_dt, (I,), f32, jnp.log(w.dt_min), jnp.log(w.dt_max)))
        return {**ln(k_n), **leaves(k_w, {
            "w_in": ((D, 2 * I), w.scale("ssm_in_std")),
            "conv_w": ((I, w.taps), w.taps ** -0.5),
            "w_x": ((I, R + 2 * N), w.scale("ssm_x_std")),
            "w_dt": ((R, I), w.scale("ssm_dt_std")),
            "w_out": ((I, D), w.scale("ssm_out_std"))}),
            "conv_b": jnp.zeros((I,), dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=f32), (I, N))),
            "D": jnp.ones((I,), f32)}

    def attention(key, l, own_kv=True):
        """``l``: the layer's index, for the output's scale: the pair's
        norm leaves rows of rms ``gain x (1 - lam0(l))``, so ``W_o`` is
        drawn larger by ``1 / (1 - lam0(l))`` and every layer's attention
        adds the same share."""
        k_n, k_w, k_l, k_g = jax.random.split(key, 4)
        wide = w.heads * d + (2 * w.kv_heads * d if own_kv else 0)
        out = leaves(k_w, {
            "w_qkv": ((D, wide), w.scale("qkv_std")),
            "b_qkv": ((wide,), w.scale("attn_bias_std")),
            "b_o": ((D,), w.scale("attn_out_bias_std"))})
        lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * l.astype(f32))
        return {**ln(k_n), **out,
                "w_o": (jax.random.normal(jax.random.fold_in(k_w, 7),
                                          (w.heads * d, D), f32)
                        * w.scale("attn_out_std") / (1.0 - lam0)
                        ).astype(dtype),
                # The published init of the four lambda vectors.
                "lam": 0.1 * jax.random.normal(k_l, (4, d), f32),
                "sub_g": w.scale("sub_gain_mean") + w.scale("sub_gain_std")
                * jax.random.normal(k_g, (2 * d,), f32)}

    def gmu(key):
        k_n, k_w = jax.random.split(key)
        return {**ln(k_n), **leaves(k_w, {
            "w_in": ((D, I), w.scale("gmu_in_std")),
            "w_out": ((I, D), w.scale("gmu_out_std"))})}

    def stack(make, key, kind, with_index=False):
        n = w.count(kind)
        keys = jax.random.split(key, n)
        if not with_index:
            return lax.map(make, keys)
        at = jnp.asarray([l for l in range(w.layers) if w.kind(l) == kind],
                         jnp.int32)
        return lax.map(lambda a: make(a[0], a[1]), (keys, at))

    return {
        "embed": _leaf(ks[0], (w.vocab, D), w.scale("embedding_std"), dtype),
        "ln_f": ln(ks[1]),
        "mlp": lax.map(mlp, jax.random.split(ks[2], w.layers)),
        "mamba": stack(mamba, ks[3], "mamba"),
        "swa": stack(attention, ks[4], "swa", True),
        "full": stack(attention, ks[5], "full", True),
        "cross": stack(functools.partial(attention, own_kv=False), ks[6],
                       "cross", True),
        "gmu": stack(gmu, ks[7], "gmu"),
    }


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: a stack a kind of part (``mlp`` every layer's; ``mamba``, ``swa``,
    ``full``, ``cross``, ``gmu`` the layers of that mixer, in depth order),
    ``embed`` (the head too) and ``ln_f``. Normal; every projection's scale
    is the configuration file's (``assumed.seeded_scales``, with the
    reckoning that chose them): each mixer and each MLP adds a tenth to a
    quarter of the residual it joins, the ``q . k`` logits spread near 1,
    the step spans ``time_step_min``-``time_step_max``, ``-A`` is 1..N a
    channel (the family's init), the four lambda vectors normal(0, 0.1) as
    published so that ``lam`` lies near ``lam0(l)``, the pair's norm's gain
    never one, every LayerNorm a gain off one and a bias off zero (a dropped
    one shows). Shapes are the published ones: ``conv_w`` ``(I, taps)``, tap
    ``k`` on ``x_{t-taps+1+k}``; ``A_log`` ``(I, N)``; ``w1`` ``[gate |
    up]``."""
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


def _ln(x: jax.Array, p: Dict[str, Any], eps: float) -> jax.Array:
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["ln_g"] + p["ln_b"]


def _cached(rows: jax.Array, quant: Optional[str]) -> jax.Array:
    return _fake_int8(rows, -1) if quant == "int8" else rows


def mamba(h, p, *, w: Widths, quant: Optional[str] = None):
    """The Mamba-1 mixer of the normed rows ``h`` ``(T, hidden)``: the
    convolution a plain sum of shifted copies, the state-space layer the
    recurrence itself, one token after another. Returns what the mixer adds
    and the scan output ``y`` (with the skip, before the gate)."""
    T, I, N, R = h.shape[0], w.inner, w.state, w.dt_rank
    xz = _mm(h, p["w_in"], quant)
    x, z = _cached(xz[:, :I], quant), xz[:, I:]
    taps = p["conv_w"].astype(jnp.float32)                      # (I, taps)
    pad = jnp.concatenate([jnp.zeros((w.taps - 1, I), x.dtype), x])
    x = sum(taps[:, k] * pad[k:k + T] for k in range(w.taps))
    x = jax.nn.silu(x + p["conv_b"].astype(jnp.float32))
    dbc = _mm(x, p["w_x"], quant)
    B, C = dbc[:, R:R + N], dbc[:, R + N:]
    delta = jax.nn.softplus(_mm(dbc[:, :R], p["w_dt"], quant) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                # (I, N)
    if quant == "scalar_decay":
        A = jnp.broadcast_to(jnp.mean(A, axis=1, keepdims=True), A.shape)

    def token(S, xs):
        x_t, B_t, C_t, d_t = xs
        S = jnp.exp(d_t[:, None] * A) * S \
            + (d_t * x_t)[:, None] * B_t[None, :]
        y = jnp.sum(S * C_t[None, :], axis=-1)                  # (I,)
        if quant == "int8":
            S = _fake_int8(S, -1)
        return S, y

    _, y = lax.scan(token, jnp.zeros((I, N), jnp.float32),
                    (x, B, C, delta))
    y = y + p["D"] * x
    return _mm(y * jax.nn.silu(z), p["w_out"], quant), y


def gated_memory(h, m, p, *, quant: Optional[str] = None):
    """A gated memory unit's mixer: a gate on the memory ``m`` ``(T,
    inner)`` of the same positions."""
    if quant == "stale_memory":
        m = jnp.concatenate([jnp.zeros_like(m[:1]), m[:-1]])
    return _mm(jax.nn.silu(_mm(h, p["w_in"], quant)) * m, p["w_out"], quant)


def keys_values(h, p, *, w: Widths, quant: Optional[str] = None):
    """A window or full layer's keys and values ``(T, kv_heads, head)``."""
    T, d, H, Hkv = h.shape[0], w.head, w.heads, w.kv_heads
    kv = _mm(h, p["w_qkv"][:, H * d:], quant) \
        + p["b_qkv"][H * d:].astype(jnp.float32)
    k, v = kv[:, :Hkv * d], kv[:, Hkv * d:]
    return (_cached(k.reshape(T, Hkv, d), quant),
            _cached(v.reshape(T, Hkv, d), quant))


def differential(h, k, v, p, l, *, w: Widths, window: Optional[int],
                 quant: Optional[str] = None):
    """Differential attention of the normed rows ``h`` over keys and values
    ``k, v`` ``(T, kv_heads, head)``, by its definition: for pair ``p`` the
    softmax of head ``2p`` and the softmax of head ``2p + 1``, each over its
    own 64-wide key head and each times the pair's two value heads side by
    side, subtracted under ``lam``, normed over the pair's lanes. One dense
    mask (causal, or the band of ``window``), ``ROW_BLOCK`` rows at a time.
    ``l``: the layer's index."""
    T, d, H = h.shape[0], w.head, w.heads
    G = H // w.kv_heads
    q = (_mm(h, p["w_qkv"][:, :H * d], quant)
         + p["b_qkv"][:H * d].astype(jnp.float32)).reshape(T, H, d)
    rb = min(ROW_BLOCK, T)
    n_rb = -(-T // rb)
    qp = jnp.pad(q, ((0, n_rb * rb - T), (0, 0), (0, 0)))
    col = jnp.arange(T)

    def one(args):
        qh, i, r0 = args                      # (rb, d), head, first row
        row = (r0 + jnp.arange(rb))[:, None]
        see = col[None, :] <= row
        if window is not None:
            see &= col[None, :] > row - window
        pair = i // 2
        j = pair // G                         # the pair's value pair
        s = jnp.einsum("td,sd->ts", qh, k[:, 2 * j + i % 2],
                       precision=HIGHEST) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        vj = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], axis=-1)
        return jnp.einsum("ts,sd->td", pr, vj, precision=HIGHEST)

    qb = qp.reshape(n_rb, rb, H, d).transpose(2, 0, 1, 3).reshape(
        H * n_rb, rb, d)
    a = lax.map(one, (qb, jnp.repeat(jnp.arange(H), n_rb),
                      jnp.tile(jnp.arange(n_rb) * rb, H)))
    a = a.reshape(H // 2, 2, n_rb * rb, 2 * d)[:, :, :T]
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * l.astype(jnp.float32))
    lam = jnp.exp(jnp.sum(p["lam"][0] * p["lam"][1])) \
        - jnp.exp(jnp.sum(p["lam"][2] * p["lam"][3])) + lam0
    o = a[:, 0] if quant == "no_diff" else a[:, 0] - lam * a[:, 1]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + w.norm_eps) \
        * p["sub_g"] * (1.0 - lam0)
    return _mm(o.transpose(1, 0, 2).reshape(T, H * d), p["w_o"], quant) \
        + p["b_o"].astype(jnp.float32)


def mlp(h, p, *, w: Widths, quant: Optional[str] = None):
    gu = _mm(h, p["w1"], quant)
    return _mm(jax.nn.silu(gu[:, :w.ffn]) * gu[:, w.ffn:], p["w2"], quant)


@functools.partial(jax.jit, static_argnames=("w", "quant", "kind"))
def mixer_part(x, stack, i, l, carry, *, w: Widths, kind: str,
               quant: Optional[str]):
    """What layer ``l``'s mixer (the ``i``-th of its ``kind``) adds to the
    residual ``x``, and the carry after it: ``(memory, shared k, shared v,
    the last window layer's k, its v)``, a part None until a layer made
    it."""
    p = jax.tree.map(lambda t: t[i], stack)
    h = _ln(x, p, w.norm_eps)
    m, sk, sv, wk, wv = carry
    if kind == "mamba":
        add, m = mamba(h, p, w=w, quant=quant)
    elif kind == "gmu":
        add = gated_memory(h, m, p, quant=quant)
    elif kind == "cross":
        k, v, window = sk, sv, None
        if quant == "own_rows":
            k, v, window = wk, wv, w.window
        add = differential(h, k, v, p, l, w=w, window=window, quant=quant)
    else:
        k, v = keys_values(h, p, w=w, quant=quant)
        if kind == "full":
            sk, sv, window = k, v, None
        else:
            wk, wv, window = k, v, w.window
        add = differential(h, k, v, p, l, w=w, window=window, quant=quant)
    return add, (m, sk, sv, wk, wv)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def mlp_part(x, stack, l, *, w: Widths, quant: Optional[str]):
    p = jax.tree.map(lambda t: t[l], stack)
    return mlp(_ln(x, p, w.norm_eps), p, w=w, quant=quant)


def layer_parts(weights, w: Widths, x, l: int, carry, *,
                quant: Optional[str] = None):
    """Layer ``l`` from the residual ``x``: what its mixer and its MLP add,
    the residual the MLP joins, and the carry."""
    kind = w.kind(l)
    i = sum(w.kind(j) == kind for j in range(l))
    add_m, carry = mixer_part(x, weights[kind], jnp.int32(i), jnp.int32(l),
                              carry, w=w, kind=kind, quant=quant)
    mid = x + add_m
    return add_m, mlp_part(mid, weights["mlp"], jnp.int32(l), w=w,
                           quant=quant), mid, carry


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, embed, *, w: Widths, quant: Optional[str]):
    return _mm(_ln(x, ln_f, w.norm_eps), embed.T, quant)


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 4608) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    so that few shapes compile; every part is causal, so the padding reaches
    no row that is read."""
    T = len(tokens)
    padded = -(-T // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:T] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    carry = (None,) * 5
    for l in range(w.layers):
        _, add, mid, carry = layer_parts(weights, w, x, l, carry, quant=quant)
        x = mid + add
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["embed"],
                w=w, quant=quant)
    return np.asarray(out)
