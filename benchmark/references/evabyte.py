"""The plain reference of the ``evabyte`` family: a byte-level decoder whose
every layer is EVA attention (exact inside the row's own aligned window, one
learned summary row for every chunk of every window closed before it, ONE
softmax over both) over a dense SwiGLU, norms whose gain is ``1 + g``, an
untied head of several next-position blocks. The full forward pass over one
sequence: the summaries from their definition over the whole sequence, the
scores of a row against ``[exact | summaries]`` concatenated and masked from
positions, one dense softmax, in blocks of rows (so that a 15k-position
context fits); float32 at the highest matmul precision; no kernels, no
cache, no batching, and never two partials and a merge (the program's way:
the two must not share a mistake). What every family's file gives is in
``README.md`` beside this file.

Independent of the program: it imports nothing of ``tree_attention_tpu`` and
nothing of the harness.

Equations (EvaByte, https://huggingface.co/EvaByte/EvaByte, ``model_type``
``evabyte``, ``attention_class`` ``eva``; EVA: Zheng et al., ICLR 2023, in
the form the byte-level model ships), one layer, residual ``x``; every norm
an RMSNorm with gain ``(1 + g)`` (``norm_add_unit_offset``) and
``rms_norm_eps``; ``x += Attn(norm1(x))``, ``x += MLP(norm2(x))``:

- ``Attn``, ``h = norm1(x)``: ``q, k, v = h W_q, h W_k, h W_v`` ->
  ``heads`` of ``head_dim`` (``num_key_value_heads`` = ``heads``), the rotary
  embedding on ``q`` and ``k`` (base ``rope_theta``, every dimension), ``s =
  head_dim^-1/2``. ``W = window_size``, ``C = chunk_size``, two learned
  vectors a head ``phi``, ``mu``:
  - chunk ``c`` = positions ``[C c, C c + C)``: ``alpha_m = softmax_{m in
    c}(k_m . phi)`` (``block.summary_logits_scaled`` false: no ``s``; ``k``
    after the rotary, ``block.summary_after_rotary``), ``k~_c = sum_m
    alpha_m k_m + mu`` (``block.summary_key`` ``"weighted_plus_mu"``), ``v~_c
    = sum_m alpha_m v_m``;
  - row ``t``, ``w0 = (t // W) W`` (``block.window_rule`` ``"aligned"``):
    ``o_t = softmax over [s q_t . k_j for j in w0..t | s q_t . k~_c for c <
    w0 / C] . [v_j | v~_c]``; then ``W_o``.
- ``MLP``, ``g = norm2(x)``: ``W_d (silu(W_g g) * W_u g)``.
- ends: logits ``= norm(x) W_out[:, :vocab]``: the head holds
  ``num_pred_heads`` blocks of ``vocab`` columns, block ``i`` scores the byte
  at ``t + 1 + i``, and the served logits are block 0's.

Departures, each noted where it is made: (1) rotary pairs are halves ``(i, i
+ head/2)``: with seeded weights a relabelling of columns. (2) Depth is the
layers the file keeps. (3) Blocks 1-7 of the head are held and not computed:
they are a drafter's (``not_built``).

Controls (``quant``), not references: ``"int8"`` rounds every matmul's
operands and the cached keys, values and summaries to int8 (symmetric, per
row / per output channel), the precision below the bf16 the configuration
states. Two more are FAULTS of this mechanism, for the limits to be held
against (``benchmark/calibrate.py --control``): ``"sliding"`` lets a row see
the last ``W`` positions exactly in place of its aligned window,
``"no_summaries"`` leaves the second sum out.
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
CONTROLS = ("int8", "sliding", "no_summaries")
ROW_BLOCK = 512      # rows of the attention computed at once
# The one value built of each rule that only the modelling code says.
_RULES = {"summary_key": "weighted_plus_mu", "summary_logits_scaled": False,
          "summary_after_rotary": True, "window_rule": "aligned"}


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    pred_heads: int
    hidden: int
    layers: int
    heads: int
    head: int
    ffn: int
    window: int
    chunk: int
    norm_offset: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    scales: Tuple[Tuple[str, float], ...]   # assumed.seeded_scales, sorted

    def scale(self, name: str) -> float:
        return dict(self.scales)[name]

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Widths":
        heads = int(config["num_attention_heads"])
        hidden = int(config["hidden_size"])
        block = config.get("block") or {}
        if config.get("attention_class") != "eva" \
                or int(config["num_key_value_heads"]) != heads:
            raise ValueError("attention_class 'eva' over as many KV heads "
                             "as heads is this family")
        for key, built in _RULES.items():
            if block.get(key, built) != built:
                raise ValueError(
                    f"block.{key} {block[key]!r}: only {built!r} is built")
        window, chunk = int(config["window_size"]), int(config["chunk_size"])
        if window % chunk:
            raise ValueError(f"a window of {window} in chunks of {chunk}")
        return cls(
            vocab=int(config["vocab_size"]),
            pred_heads=int(config.get("num_pred_heads", 1)),
            hidden=hidden, layers=int(config["num_hidden_layers"]),
            heads=heads, head=int(config.get("head_dim") or hidden // heads),
            ffn=int(config["intermediate_size"]),
            window=window, chunk=chunk,
            norm_offset=bool(config.get("norm_add_unit_offset", False)),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
            dtype=str((config.get("assumed") or {}).get(
                "torch_dtype", "bfloat16")),
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
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    D, hd = w.hidden, (w.heads, w.head)

    def gain(key):
        return _leaf(key, (D,), w.scale("norm_gain_std"), jnp.float32)

    def layer(key):
        kk = jax.random.split(key, 11)
        shapes = {
            "wq": ((D, w.heads * w.head), 0.02),
            "wk": ((D, w.heads * w.head), 0.02),
            "wv": ((D, w.heads * w.head), 0.02),
            "wo": ((w.heads * w.head, D), w.scale("attn_out_std")),
            "phi": (hd, w.scale("phi_std")),
            "mu": (hd, w.scale("mu_std")),
            "w1": ((D, w.ffn), 0.02), "w3": ((D, w.ffn), 0.02),
            "w2": ((w.ffn, D), w.scale("dense_down_std")),
        }
        out = {n: _leaf(k, shape, sd, dtype)
               for k, (n, (shape, sd)) in zip(kk, shapes.items())}
        out.update(ln1=gain(kk[9]), ln2=gain(kk[10]))
        return out

    return {
        "embed": _leaf(ks[0], (w.vocab, D), w.scale("embedding_std"), dtype),
        "wout": _leaf(ks[1], (D, w.pred_heads * w.vocab),
                      w.scale("head_std"), dtype),
        "ln_f": gain(ks[2]),
        "layers": lax.map(layer, jax.random.split(ks[3], w.layers)),
    }


def init_weights(seed: int, w: Widths) -> Dict[str, Any]:
    """Seeded weights in the served type, made on the device in one jitted
    call: ``layers`` (every leaf on a leading axis of the layers), ``embed``,
    ``wout`` (all ``pred_heads`` blocks) and ``ln_f``. Normal; every
    projection INTO a part at std 0.02; a norm's leaf is its published
    parameter ``g`` (the gain is ``1 + g``), drawn at 0 +- ``norm_gain_std``
    in float32 (never 0: a dropped offset shows); ``phi`` at ``phi_std`` (``k
    . phi`` of the order of 1, so that a chunk's weights are far from
    uniform: a mean pool shows), ``mu`` at ``mu_std`` (of a pooled key's
    size: a dropped ``mu`` shows). The scales are the configuration file's
    (``assumed.seeded_scales``, with the reckoning that chose them)."""
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


def _rms(x: jax.Array, g: jax.Array, w: Widths) -> jax.Array:
    gain = 1.0 + g if w.norm_offset else g
    return x * lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + w.norm_eps) * gain


def _cached(rows: jax.Array, quant: Optional[str]) -> jax.Array:
    return _fake_int8(rows, -1) if quant == "int8" else rows


def _rope(x: jax.Array, w: Widths) -> jax.Array:
    """``x`` is (T, heads, d); position t is row t. Pairs are halves
    (departure 1)."""
    T, half = x.shape[0], x.shape[-1] // 2
    freqs = w.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def summaries(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
              w: Widths) -> Tuple[jax.Array, jax.Array]:
    """One key row and one value row for every chunk of ``chunk`` positions
    of rotated ``k`` / ``v`` ``(T, heads, d)``, ``T`` a multiple of the
    chunk: ``(T / chunk, heads, d)`` each, from the definition."""
    T, H, d = k.shape
    kc = k.reshape(T // w.chunk, w.chunk, H, d)
    vc = v.reshape(T // w.chunk, w.chunk, H, d)
    alpha = jax.nn.softmax(
        jnp.einsum("cmhd,hd->cmh", kc, phi, precision=HIGHEST), axis=1)
    ks = jnp.einsum("cmh,cmhd->chd", alpha, kc, precision=HIGHEST) + mu
    vs = jnp.einsum("cmh,cmhd->chd", alpha, vc, precision=HIGHEST)
    return ks, vs


def attention(h, p, *, w: Widths, quant: Optional[str] = None):
    """EVA attention of the normed rows ``h`` ``(T, hidden)``, ``T`` a
    multiple of the chunk: every row's scores against the whole sequence's
    exact rows and against every chunk's summary, side by side, masked from
    positions, under one softmax; ``ROW_BLOCK`` rows at a time."""
    T, H = h.shape[0], w.heads
    W, C = w.window, w.chunk
    q = _rope(_mm(h, p["wq"], quant).reshape(T, H, w.head), w)
    k = _rope(_mm(h, p["wk"], quant).reshape(T, H, w.head), w)
    v = _mm(h, p["wv"], quant).reshape(T, H, w.head)
    k, v = _cached(k, quant), _cached(v, quant)
    ks, vs = summaries(k, v, p["phi"].astype(jnp.float32),
                       p["mu"].astype(jnp.float32), w)
    ks, vs = _cached(ks, quant), _cached(vs, quant)
    keys = jnp.concatenate([k, ks], axis=0)          # (T + T / C, H, d)
    vals = jnp.concatenate([v, vs], axis=0)
    rb = min(ROW_BLOCK, T)
    n_rb = -(-T // rb)
    qp = jnp.pad(q, ((0, n_rb * rb - T), (0, 0), (0, 0)))
    col, chunk = jnp.arange(T), jnp.arange(T // C)

    def one(args):
        qh, i, r0 = args                      # (rb, d), head, first row
        row = (r0 + jnp.arange(rb))[:, None]
        w0 = row // W * W
        exact = col[None, :] <= row
        if quant == "sliding":
            exact &= col[None, :] > row - W
        else:
            exact &= col[None, :] >= w0
        far = chunk[None, :] * C < w0
        if quant == "no_summaries":
            far = jnp.zeros_like(far)
        see = jnp.concatenate([exact, far], axis=1)
        s = jnp.einsum("td,sd->ts", qh, keys[:, i],
                       precision=HIGHEST) * w.head ** -0.5
        pr = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", pr, vals[:, i], precision=HIGHEST)

    qb = qp.reshape(n_rb, rb, H, w.head).transpose(2, 0, 1, 3).reshape(
        H * n_rb, rb, w.head)
    heads = jnp.repeat(jnp.arange(H), n_rb)
    firsts = jnp.tile(jnp.arange(n_rb) * rb, H)
    o = lax.map(one, (qb, heads, firsts))     # (H * n_rb, rb, d)
    o = o.reshape(H, n_rb * rb, w.head)[:, :T]
    return _mm(o.transpose(1, 0, 2).reshape(T, H * w.head), p["wo"], quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _layer(x, stack, i, *, w: Widths, quant: Optional[str]):
    p = jax.tree.map(lambda t: t[i], stack)
    x = x + attention(_rms(x, p["ln1"], w), p, w=w, quant=quant)
    g = _rms(x, p["ln2"], w)
    return x + _mm(jax.nn.silu(_mm(g, p["w1"], quant))
                   * _mm(g, p["w3"], quant), p["w2"], quant)


@functools.partial(jax.jit, static_argnames=("w", "quant"))
def _head(x, ln_f, wout, *, w: Widths, quant: Optional[str]):
    # Block 0 of the head: the next byte's (departure 3).
    return _mm(_rms(x, ln_f, w), wout[:, :w.vocab], quant)


def logits_at(weights: Dict[str, Any], w: Widths, tokens: np.ndarray,
              rows: np.ndarray, *, quant: Optional[str] = None,
              pad_to: int = 512) -> np.ndarray:
    """Logits, ``(len(rows), vocab)`` float32, at positions ``rows`` of one
    sequence. The sequence is padded at its end to a multiple of ``pad_to``
    (and of the chunk) so that few shapes compile; attention is causal and
    a chunk's summary is seen only from a later window, so the padding
    reaches no row that is read."""
    T = len(tokens)
    step = int(np.lcm(pad_to, w.chunk))
    padded = -(-T // step) * step
    ids = np.zeros((padded,), np.int32)
    ids[:T] = tokens
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(w.layers):
        x = _layer(x, weights["layers"], jnp.int32(i), w=w, quant=quant)
    out = _head(x[jnp.asarray(rows)], weights["ln_f"], weights["wout"],
                w=w, quant=quant)
    return np.asarray(out)
