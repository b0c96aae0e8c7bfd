"""Decoder-only transformer LM built on the tree-attention ops layer.

The reference repo has no model — its driver calls the attention op on random
tensors (``/root/reference/model.py:129-155``). A framework needs a flagship
model family to exercise the kernel the way users will: this module provides a
Llama-style decoder-only LM (RMSNorm, rotary embeddings, SwiGLU, grouped-query
attention) written as pure functions over a pytree of parameters.

TPU-first design choices:

- **Layers are stacked and scanned** (``lax.scan`` over a leading layer axis)
  so the program XLA sees is O(1) in depth — one compiled layer body — with
  ``jax.checkpoint`` on the body for rematerialised activations (HBM ↔ FLOPs
  trade, SURVEY.md §7).
- **Attention routes through the tree layer when a mesh is given**: activations
  stay sequence-sharded end-to-end (embeddings/norms/FFN are pointwise over
  sequence, so GSPMD shards them for free) and only the attention inner loop
  uses explicit collectives via :func:`tree_attention
  <tree_attention_tpu.parallel.tree.tree_attention>`.
- **bf16 params / fp32 norms & softmax**: the TPU-native half precision, with
  reductions carried in float32 (the reference uses fp16 throughout,
  ``model.py:51-53``; see SURVEY.md §7 numerics policy).
- **Sharding is data, not code**: :func:`param_specs` returns a
  ``PartitionSpec`` pytree mirroring :func:`init_params` — megatron-style
  tensor parallelism over the ``model`` axis, batch over ``data``, sequence
  over ``seq`` — and the same forward runs unsharded on one chip.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tree_attention_tpu.ops import flash_attention
from tree_attention_tpu.parallel.mesh import AXIS_DATA, AXIS_MODEL, AXIS_SEQ

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN rotary scaling: see :func:`~.latent.rope_frequencies`."""

    factor: float
    original_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """A latent-attention (MLA) block: low-rank query and key/value
    projections, ``rope`` rotary dimensions beside ``nope`` plain ones in
    every query/key head, and a cache of one ``[c_kv | k_rope]`` row a token
    (``models/latent.py``). In the pool the row is followed by zero lanes
    up to the next multiple of the chip's 128: short of one, the TPU
    compiler copies the whole pool into a layout of its own before every
    launch of the kernel. ``q_scale`` / ``kv_scale`` multiply the normed
    low-rank latents ``c_q`` / ``c_kv`` (the cached row holds the scaled
    ``c_kv``; the rotary key is not scaled)."""

    q_rank: int            # 0: queries are projected directly
    kv_rank: int
    nope: int
    rope: int
    v_head: int
    yarn: Optional[YarnRope] = None
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @property
    def row_pad(self) -> int:
        return -(self.kv_rank + self.rope) % 128

    @property
    def row(self) -> int:
        """Lanes a cached token takes in one layer, padding included."""
        return self.kv_rank + self.rope + self.row_pad


@dataclasses.dataclass(frozen=True)
class ExpertLayer:
    """A routed-expert feed-forward layer (``models/experts.py``) and the
    share of it held here: the router scores all ``n_experts``, this
    program computes experts ``[held_first, held_first + held)``. The
    router's last ``n_zero`` ids are zero-compute experts: they have no
    weights, give back the row they are handed, and are computed where the
    row lives, so every holder computes them for its own rows."""

    n_experts: int         # the router's width, zero-compute experts included
    held: int
    held_first: int
    per_token: int
    width: int             # one routed expert's SwiGLU width
    shared_width: int      # the always-on shared experts, as one SwiGLU; 0: none
    n_groups: int = 1
    top_groups: int = 1
    scale: float = 1.0     # on the chosen scores when they are not renormed
    renorm: bool = False
    # The scale multiplies the renormed weights too (the later routers'
    # rule: renorm, then scale), not only scores that were not renormed.
    renorm_scaled: bool = False
    first_dense: int = 0   # leading layers that keep the dense SwiGLU
    n_zero: int = 0
    # The top ``per_token`` are taken of ``scores + bias`` (a per-expert
    # correction held with the weights); the weights stay the scores.
    corrected: bool = False
    # What the router makes of its logits: a softmax over the experts, or
    # each expert's own sigmoid.
    scoring: str = "softmax"
    # None: the expert layer stands in the layer's FFN's place. ``(leaves,
    # rejoins)``: a branch beside the dense FFNs, computed from the normed
    # residual that sublayer ``leaves``'s FFN reads and added to the
    # residual after sublayer ``rejoins``'s FFN.
    branch: Optional[Tuple[int, int]] = None
    # The routed experts live in a latent of this width, narrower than the
    # residual: a row goes down (``w_down``) before the gather and the
    # weighed sum comes up (``w_up``) after it; the router and the shared
    # expert see the full width. 0: the experts see the residual's width.
    latent: int = 0
    # False: an expert (the shared one too) is one matrix in, relu squared,
    # one matrix out; there is no gate matrix (``we3`` / ``ws3``).
    gated: bool = True

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router scoring {self.scoring!r}: 'softmax' and 'sigmoid' "
                f"are built")
        if self.n_experts % self.n_groups:
            raise ValueError(
                f"{self.n_experts} experts do not split into "
                f"{self.n_groups} routing groups")
        if not 0 <= self.held_first <= self.held_first + self.held \
                <= self.n_routed:
            raise ValueError(
                f"experts held [{self.held_first}, "
                f"{self.held_first + self.held}) lie outside the router's "
                f"{self.n_routed} routed experts")
        if self.corrected and self.n_groups > 1:
            raise ValueError(
                "a corrected choice inside routing groups is not built")
        if self.branch is not None and self.first_dense:
            raise ValueError(
                "leading dense layers before layers with a routed branch "
                "are not built")

    @property
    def n_routed(self) -> int:
        """Experts with weights: the router's ids below this."""
        return self.n_experts - self.n_zero

    @property
    def leaves(self) -> Tuple[str, ...]:
        """The routed experts' stacked matrices, in the order the grouped
        products take them: in (``we1``, and ``we3`` where gated), out."""
        return ("we1", "we3", "we2") if self.gated else ("we1", "we2")


@dataclasses.dataclass(frozen=True)
class StateSpace:
    """A state-space (Mamba-2) mixer's widths (``models/hybrid.py``
    ``ssm_mixer``): ``n_heads`` heads of ``d_head``, in ``n_groups`` groups
    that share their ``B`` and ``C`` rows of ``d_state``; a depthwise causal
    convolution of ``taps`` taps with a bias over ``[x | B | C]``; a scan
    blocked in ``chunk`` rows."""

    n_heads: int
    d_head: int
    n_groups: int
    d_state: int
    taps: int
    chunk: int = 128

    def __post_init__(self):
        if self.n_heads % self.n_groups or self.taps < 2:
            raise ValueError(
                f"a state-space mixer of {self.n_heads} heads in "
                f"{self.n_groups} groups with {self.taps} taps: the groups "
                f"divide the heads and the convolution has a tail (>= 2 "
                f"taps)")

    @property
    def inner(self) -> int:
        """The mixer's inner width, ``heads x head``."""
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        """The convolved width: ``x`` and the groups' ``B`` and ``C``."""
        return self.inner + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        """``W_in``'s output: ``[z | xBC | dt]``."""
        return self.inner + self.conv_dim + self.n_heads

    @property
    def pack(self) -> int:
        """Heads the state pool lays side by side on a row's 128 lanes
        (``d_head x pack`` lanes, as ``TransformerConfig.kv_pack`` does for
        KV heads): as many as divide both the lanes and a group, so that a
        row's heads share their ``B`` and ``C`` (two heads of 64; a head of
        128 fills a row by itself, ``pack`` 1). No option."""
        if 128 % self.d_head:
            return 1
        return math.gcd(128 // self.d_head, self.n_heads // self.n_groups)

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """One slot's state in one layer as the pool holds it: ``(heads /
        pack, d_state, pack x d_head)``, ``S[h, p, n]`` at ``[h // pack, n,
        (h % pack) x d_head + p]``."""
        return (self.n_heads // self.pack, self.d_state,
                self.pack * self.d_head)


@dataclasses.dataclass(frozen=True)
class Mamba1:
    """A Mamba-1 mixer's widths (``models/hybrid.py`` ``ssm1_branch``):
    ``inner`` channels, each with a state of ``d_state`` numbers and a
    decay of its own for every one of them (``A`` is ``(inner, d_state)``,
    where Mamba-2's is a scalar a head); ``Δ`` a vector over the channels,
    up from a ``dt_rank``-wide row; a depthwise causal convolution of
    ``taps`` taps with a bias over ``x`` alone."""

    inner: int
    d_state: int
    taps: int
    dt_rank: int

    def __post_init__(self):
        if self.taps < 2 or min(self.inner, self.d_state, self.dt_rank) < 1:
            raise ValueError(
                f"a Mamba-1 mixer of {self.inner} channels x {self.d_state} "
                f"states, dt_rank {self.dt_rank}, {self.taps} taps: every "
                f"width is positive and the convolution has a tail (>= 2 "
                f"taps)")

    @property
    def x_dim(self) -> int:
        """``W_x``'s output: ``[δ | B | C]``."""
        return self.dt_rank + 2 * self.d_state

    @property
    def state_shape(self) -> Tuple[int, int]:
        """One slot's state in one layer as the pool holds it: ``(d_state,
        inner)``, ``S[c, n]`` at ``[n, c]``: the channels on the lanes, as
        ``x`` and ``Δ`` come out of their projections."""
        return (self.d_state, self.inner)


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """Fixed scalars a family multiplies its activations by, under the names
    the ``falcon_h1`` family publishes them (``models/hybrid.py`` says where
    each lands): on the embedded rows and on the logits; on the two
    branches' inputs (the one normed residual) and outputs; on the keys; on
    ``W_in``'s output, a scalar a segment of ``[z | x | B | C | dt]``
    (``ssm_multipliers``); on the MLP's gate inside its activation and on
    its output (``mlp_multipliers``). Data, applied to activations and never
    folded into a stored weight; every default is 1.0, and a 1.0 emits no
    multiply (:func:`times`)."""

    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5
    mlp_multipliers: Tuple[float, ...] = (1.0,) * 2

    def __post_init__(self):
        # (A published file holds whole numbers and lists.)
        for f in dataclasses.fields(self):
            value = getattr(self, f.name)
            if isinstance(f.default, tuple):
                value = tuple(float(v) for v in value)
                if len(value) != len(f.default):
                    raise ValueError(
                        f"{f.name} {value}: {len(f.default)} scalars")
            else:
                value = float(value)
            object.__setattr__(self, f.name, value)

    @property
    def unit(self) -> bool:
        return self == Multipliers()


def times(x: jax.Array, m: float) -> jax.Array:
    """``x`` times the fixed scalar ``m``: the product in float32, rounded
    once to ``x``'s type; ``x`` itself, and no operation, where ``m`` is
    1.0."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


# A layer's mixer kind, by the names published configurations give it in
# ``layer_types``: the one table :func:`model_from_config` reads kinds from
# and lists in its refusals.
PUBLISHED_MIXERS = {
    "full_attention": "attention", "attention": "attention",
    "sliding_attention": "window", "conv": "conv",
}
# ``"ssm"`` has no name in ``layer_types``: its family says what a layer is
# in ``hybrid_override_pattern``, a character a part (:func:`_pattern_layers`).
# Nor has ``"eva"``: its family says every layer's in ``attention_class``.
# Nor ``"parallel"``: ``model_type`` ``falcon_h1`` says every layer's.
# Nor ``"ssm1"`` / ``"gmu"`` / ``"cross"``: ``model_type`` ``phi4flash``
# derives every layer's from the depth (:func:`decoder_hybrid_layers`).
MIXER_KINDS = frozenset(PUBLISHED_MIXERS.values()) | {
    "ssm", "eva", "parallel", "ssm1", "gmu", "cross"}
# The kinds of a decoder that feeds a second decoder (below the seam, then
# above it), and the one arrangement of them built.
DECODER_HYBRID_KINDS = frozenset({"ssm1", "window", "attention", "gmu",
                                  "cross"})
NORM_KINDS = ("rms", "layer")
# The published ``attention_class`` values built, by the mixer kind they give
# every layer.
ATTENTION_CLASSES = {"eva": "eva"}
WINDOW_RULES = ("sliding", "aligned")
FFN_KINDS = frozenset({"dense", "expert", "none"})


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Static architecture hyperparameters (hashable: usable as a jit static).

    Every layer is a mixer and a feed-forward half, and the data says
    which. The mixer: ``mla`` set means latent attention in every layer;
    else ``layer_types`` names each layer's (``"attention"``: rotary GQA,
    with an RMSNorm over each query and key head where ``qk_norm``;
    ``"window"``: the same attention over the last ``window`` positions
    only, a row at ``t`` sees ``(t - window, t]``; ``"conv"``: a gated
    short convolution of ``conv_taps`` taps; ``"ssm"``: a state-space
    mixer of ``ssm``'s widths; ``"eva"``: rotary attention that is exact
    inside the row's own ALIGNED window, ``[(t // window) * window, t]``
    (``window_rule`` ``"aligned"``), and sees every window closed before it
    as one learned summary row a ``chunk`` of positions, both under one
    softmax; ``"parallel"``: TWO mixers side by side, the state-space
    mixer and rotary GQA over the whole context on ONE normed residual,
    their outputs added to it together, each under its own fixed scale:
    every layer of such a model, ``models/hybrid.py``; ``"ssm1"``: a
    Mamba-1 mixer of ``ssm1``'s widths; ``"gmu"``: a gated memory unit, a
    gate on the scan output the LAST ``"ssm1"`` layer made for the same row
    in the same step; ``"cross"``: attention whose queries alone are the
    layer's own, over the K/V rows the ONE ``"attention"`` layer before it
    wrote: the upper half of a decoder that feeds a second decoder), None
    meaning rotary GQA throughout. ``diff_attn``: every attention of the
    model is differential (two softmaxes of a pair of heads subtracted
    under a learned ``λ``, an RMSNorm over the pair's ``2 x d_head`` lanes:
    ``models/hybrid.py`` ``diff_branch``); ``attn_bias``: the attention
    projections have biases; ``norm``: ``"rms"``, or ``"layer"`` for a
    LayerNorm with mean, gain and bias in every norm's place. ``mup``: the fixed scalars a family multiplies
    its activations by (:class:`Multipliers`; built with ``"parallel"``
    layers). ``rotary`` names the attention kinds whose queries and
    keys take the rotary embedding (both by default; a model whose full
    layers carry no positional term names ``("window",)``, one with no
    positional term at all ``()``). The
    feed-forward half: ``moe`` set means
    every layer after its ``first_dense`` is a routed-expert layer (else
    the dense SwiGLU); or ``ffn_types`` says it a layer (``"dense"`` |
    ``"expert"`` | ``"none"``: a layer that is its mixer alone). A latent
    layer may be ``sublayers`` pairs of one
    attention and one dense FFN; with more than one, the routed experts are
    a branch beside them (``moe.branch``). ``tied_head``: the head is the
    embedding's transpose and the parameters hold no ``wout``.

    What is cached follows (``cache_kind``): K/V rows for every attention
    (``cache_layers`` of them, not ``n_layers``), one latent row where
    ``mla`` is set, and for every conv layer (``conv_layers``) the last
    ``conv_taps - 1`` gated inputs, held as a tail of each pool block; a
    window layer's (``window_layers``) K/V rows live in a pool of their
    own under a table of their own, a bounded number of blocks a slot; a
    state-space layer's (``ssm_layers``) state and conv tail are one array
    a slot, which no table indexes; an EVA layer's (``eva_layers``) exact
    rows live in the window pool as a window layer's do and its summary
    rows, one a ``chunk`` positions and kept for the request's life, in the
    pool under the first table. A ``"parallel"`` layer is an attention
    layer AND a state-space layer: it counts in ``cache_layers`` and in
    ``ssm_layers`` (``cache_kind`` ``"state"``, the two depths equal).

    ``pred_heads``: the head scores that many next positions a row
    (``wout`` ``(D, pred_heads x vocab)``); the served logits are the
    first's. ``norm_offset``: the published norms' gain is ``1 + g``; a
    norm leaf of these parameters holds the GAIN (float32, so ``1 + g``
    loses nothing), and whoever loads published weights adds the one.
    """

    vocab_size: int = 32768
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    n_kv_heads: int = 8          # < n_heads for GQA/MQA
    d_head: int = 64
    d_ff: int = 1408             # ~8/3 · d_model, rounded to a lane multiple
    max_seq_len: int = 65536
    rope_theta: float = 10000.0
    # "zigzag" permutes the sequence so causal work balances across the
    # mesh's seq shards (parallel.tree.zigzag_perm); positions ride RoPE so
    # the model is exactly equivalent to contiguous order. Ignored without a
    # >1-way seq axis.
    seq_layout: str = "contiguous"
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16    # activation/param compute dtype
    attn_impl: str = "auto"      # flash_attention impl selector
    attn_block_size: Optional[int] = None  # None -> impl-appropriate
    remat: bool = True           # checkpoint each layer body under scan
    mla: Optional[LatentAttention] = None
    moe: Optional[ExpertLayer] = None
    sublayers: int = 1
    layer_types: Optional[Tuple[str, ...]] = None
    conv_taps: int = 3
    qk_norm: bool = False
    tied_head: bool = False
    window: int = 0
    rotary: Tuple[str, ...] = ("attention", "window")
    ssm: Optional[StateSpace] = None
    ffn_types: Optional[Tuple[str, ...]] = None
    window_rule: str = "sliding"
    chunk: int = 0
    pred_heads: int = 1
    norm_offset: bool = False
    mup: Multipliers = Multipliers()
    ssm1: Optional[Mamba1] = None
    diff_attn: bool = False
    attn_bias: bool = False
    norm: str = "rms"

    def __post_init__(self):
        if isinstance(self.mup, dict):      # read back from JSON
            object.__setattr__(self, "mup", Multipliers(**self.mup))
        if isinstance(self.ssm1, dict):
            object.__setattr__(self, "ssm1", Mamba1(**self.ssm1))
        if self.norm not in NORM_KINDS:
            raise ValueError(
                f"norm {self.norm!r}: one of {NORM_KINDS}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be a multiple of "
                f"n_kv_heads ({self.n_kv_heads})"
            )
        if self.layer_types is not None:
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.n_layers \
                    or not kinds <= set(MIXER_KINDS):
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers of "
                    f"kinds {sorted(kinds)}: one of {sorted(MIXER_KINDS)} "
                    f"for each of the {self.n_layers} layers")
            if bool(kinds & {"window", "eva"}) != (self.window > 0) or (
                    "window" in kinds and "conv" in kinds):
                raise ValueError(
                    f"window layers {bool(kinds & {'window', 'eva'})} with "
                    f"window={self.window}: a model with sliding-window "
                    f"layers states their window (> 0), a model without "
                    f"states none, and window layers beside conv layers "
                    f"are not built")
            if "eva" in kinds and (
                    kinds != {"eva"} or self.moe is not None
                    or self.window_rule != "aligned" or self.chunk < 1
                    or self.window % self.chunk):
                raise ValueError(
                    f"an 'eva' layer beside layers of kinds "
                    f"{sorted(kinds - {'eva'})} (experts: "
                    f"{self.moe is not None}), window_rule "
                    f"{self.window_rule!r}, window {self.window}, chunk "
                    f"{self.chunk}: EVA attention is built in every layer "
                    f"of a model, over the dense FFN, under the 'aligned' "
                    f"window rule, its window a whole number of chunks")
            if "eva" not in kinds and (
                    self.window_rule != "sliding" or self.chunk):
                raise ValueError(
                    f"window_rule {self.window_rule!r}, chunk {self.chunk} "
                    f"without 'eva' layers: a sliding-window layer's "
                    f"window slides and has no chunk summaries")
            if self.mla is not None or self.sublayers > 1:
                raise ValueError(
                    "layers of several kinds are built over rotary-GQA "
                    "attention, one mixer a layer: not with latent "
                    "attention or several sublayers")
            if "conv" in kinds and self.conv_taps != 3:
                raise ValueError(
                    f"a short convolution of {self.conv_taps} taps: its "
                    f"state is built as the last 2 gated inputs, a two-row "
                    f"tail of each pool block (3 taps)")
            has_state = bool(kinds & {"ssm", "parallel"})
            if has_state != (self.ssm is not None) or (
                    has_state and kinds & {"conv", "window"}):
                raise ValueError(
                    f"state-space layers {has_state} with ssm="
                    f"{self.ssm}: a model with state-space layers states "
                    f"their widths, a model without states none, and a "
                    f"recurrent state beside conv or sliding-window layers "
                    f"is not built")
            upper = kinds & {"ssm1", "gmu", "cross"}
            if bool(upper) != (self.ssm1 is not None):
                raise ValueError(
                    f"layers of kinds {sorted(upper)} with ssm1="
                    f"{self.ssm1}: a model with Mamba-1, gated-memory or "
                    f"cross layers states the Mamba-1 widths, a model "
                    f"without states none")
            if upper:
                self._check_decoder_hybrid(kinds)
            if "parallel" in kinds and (
                    kinds != {"parallel"} or self.moe is not None
                    or self.qk_norm or not self.rotates("attention")):
                raise ValueError(
                    f"a 'parallel' layer (a state-space mixer and rotary "
                    f"GQA on one normed residual) beside layers of kinds "
                    f"{sorted(kinds - {'parallel'})} (experts: "
                    f"{self.moe is not None}, qk_norm: {self.qk_norm}, "
                    f"rotary: {self.rotary}): it is built in every layer of "
                    f"a model, its attention rotated and without QK-norm, "
                    f"over the dense feed-forward half or none")
        elif self.ssm is not None or self.ssm1 is not None:
            raise ValueError(
                "state-space widths without layer_types: which layers are "
                "state-space layers is said a layer")
        elif self.window or self.chunk or self.window_rule != "sliding":
            raise ValueError(
                f"window={self.window} (rule {self.window_rule!r}, chunk "
                f"{self.chunk}) without layer_types: which layers are "
                f"sliding-window or EVA layers is said a layer")
        if not self.mup.unit and "parallel" not in (self.layer_types or ()):
            raise ValueError(
                f"mup {self.mup}: fixed activation multipliers are built "
                f"with 'parallel' layers (layer_types), where every one of "
                f"them has its place; any other model's are all 1.0")
        if (self.diff_attn or self.attn_bias or self.norm != "rms") \
                and self.ssm1 is None:
            raise ValueError(
                f"diff_attn {self.diff_attn}, attn_bias {self.attn_bias}, "
                f"norm {self.norm!r} without Mamba-1 layers: differential "
                f"attention, projection biases and LayerNorm are built in "
                f"the decoder-hybrid layer loop ('ssm1' / 'gmu' / 'cross' "
                f"layers); any other model's attention is one softmax, "
                f"without biases, under RMSNorm")
        if self.window_rule not in WINDOW_RULES or self.pred_heads < 1:
            raise ValueError(
                f"window_rule {self.window_rule!r} (one of {WINDOW_RULES}) "
                f"with pred_heads {self.pred_heads} (>= 1)")
        # A configuration read back from JSON (a checkpoint's sidecar) holds
        # a list: the dataclass stays hashable, a jit static.
        object.__setattr__(self, "rotary", tuple(self.rotary))
        if not set(self.rotary) <= {"attention", "window"}:
            raise ValueError(
                f"rotary {self.rotary}: names of attention kinds "
                f"('attention', 'window'), or none")
        if self.ffn_types is not None:
            object.__setattr__(self, "ffn_types", tuple(self.ffn_types))
            kinds = set(self.ffn_types)
            if len(self.ffn_types) != self.n_layers \
                    or not kinds <= set(FFN_KINDS) \
                    or ("expert" in kinds) != (self.moe is not None) \
                    or self.layer_types is None \
                    or (self.moe is not None and self.moe.first_dense):
                raise ValueError(
                    f"ffn_types names {len(self.ffn_types)} layers' "
                    f"feed-forward kinds {sorted(kinds)}: one of "
                    f"{sorted(FFN_KINDS)} for each of the {self.n_layers} "
                    f"layers of a model that says its mixers a layer too "
                    f"(layer_types), 'expert' where the model has experts "
                    f"(moe, with no first_dense beside it)")
        branch = self.moe.branch if self.moe is not None else None
        if (self.sublayers > 1 and branch is None) or (
                branch is not None and (
                    self.mla is None
                    or not 0 <= branch[0] <= branch[1] < self.sublayers)):
            raise ValueError(
                f"{self.sublayers} sublayers a layer with routed branch "
                f"{branch}: several sublayers are built as latent "
                f"attention + dense FFN pairs with the routed experts a "
                f"branch that leaves and rejoins inside the layer")

    def _check_decoder_hybrid(self, kinds) -> None:
        """What the layer loop cannot run of a model with Mamba-1, gated
        memory or cross layers, refused by name."""
        types = self.layer_types
        if not kinds <= DECODER_HYBRID_KINDS or self.moe is not None \
                or self.ffn_types is not None:
            raise ValueError(
                f"'ssm1' / 'gmu' / 'cross' layers beside layers of kinds "
                f"{sorted(kinds - DECODER_HYBRID_KINDS)} (experts: "
                f"{self.moe is not None}, ffn_types: "
                f"{self.ffn_types is not None}): they are built beside "
                f"'window' and 'attention' layers over the dense "
                f"feed-forward half, without experts or latent attention")
        upper = [i for i, t in enumerate(types) if t in ("gmu", "cross")]
        seam = upper[0] if upper else len(types)
        shared = [i for i, t in enumerate(types) if t == "attention"]
        if "cross" in kinds and len(shared) != 1:
            raise ValueError(
                f"cross layers with {len(shared)} full-attention "
                f"('attention') layers: the cross layers read the rows of "
                f"ONE shared layer; none and two are not built")
        if "cross" in kinds and (shared[0] != seam - 1):
            raise ValueError(
                f"the shared full-attention layer at {shared} and the "
                f"first gated-memory or cross layer at {seam}: a cross "
                f"layer has the shared layer before it, and the shared "
                f"layer is the last layer below the seam (the rows that "
                f"no slot samples from leave the stack after it)")
        if "gmu" in kinds and "ssm1" not in types[:seam]:
            raise ValueError(
                "a gated memory unit with no Mamba-1 layer before it: its "
                "memory is the scan output of the last 'ssm1' layer below")
        below = set(types[seam:]) - {"gmu", "cross"}
        if below:
            raise ValueError(
                f"layers of kinds {sorted(below)} after the first "
                f"gated-memory or cross layer (at {seam}): a second memory "
                f"layer or a second shared layer is not built; above the "
                f"seam every layer is 'gmu' or 'cross'")
        if self.qk_norm or self.rotary or not self.diff_attn:
            raise ValueError(
                f"qk_norm {self.qk_norm}, rotary {self.rotary}, diff_attn "
                f"{self.diff_attn} with Mamba-1 layers: the decoder-hybrid "
                f"layers' attention is differential, with no rotary "
                f"embedding (rotary ()) and no QK-norm")
        if self.n_kv_heads % 2:
            raise ValueError(
                f"differential attention of {self.n_heads} / "
                f"{self.n_kv_heads} heads: a pair of neighbouring query "
                f"heads reads a pair of neighbouring KV heads, so both "
                f"counts are even")

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.d_head

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is the short convolution: the tail pool's
        depth."""
        return (self.layer_types or ()).count("conv")

    @property
    def window_layers(self) -> int:
        """Layers whose mixer is sliding-window attention: the window
        pool's depth."""
        return (self.layer_types or ()).count("window")

    @property
    def ssm_layers(self) -> int:
        """Layers that hold a recurrent state, the state-space mixer alone
        (Mamba-2's ``"ssm"`` or Mamba-1's ``"ssm1"``; a model has one of
        the two) or beside attention: the state pool's depth."""
        types = self.layer_types or ()
        return types.count("ssm") + types.count("parallel") \
            + types.count("ssm1")

    @property
    def row_cut(self) -> Optional[int]:
        """The layers every row of a step runs through, where the model is
        a decoder that feeds a second decoder: the layers up to and with
        the shared full-attention layer. Above it nothing carries anything
        from one position to the next (a cross layer reads the shared
        layer's rows, a gated memory unit the same row's memory), so only
        a row some slot samples from goes on. None: every other model."""
        types = self.layer_types or ()
        if "cross" not in types:
            return None
        return types.index("attention") + 1

    @property
    def eva_layers(self) -> int:
        """Layers whose mixer is EVA attention: the depth of both of their
        pools (exact rows under the second table, summary rows under the
        first)."""
        return (self.layer_types or ()).count("eva")

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        """Each layer's feed-forward kind: ``ffn_types`` where the data says
        it a layer, else ``moe.first_dense`` leading dense layers and expert
        layers after them."""
        if self.ffn_types is not None:
            return self.ffn_types
        n = self.n_dense_layers
        return ("dense",) * n + ("expert",) * (self.n_layers - n)

    @property
    def n_expert_layers(self) -> int:
        """Layers whose feed-forward half is the routed experts."""
        return self.ffn_kinds.count("expert")

    def rotates(self, kind: str) -> bool:
        """Whether an attention layer of ``kind`` rotates its queries and
        keys."""
        return kind in self.rotary

    @property
    def dense_block(self) -> bool:
        """The Llama-style block this module's ``forward`` computes."""
        return self.mla is None and self.moe is None \
            and not self.conv_layers and not self.window_layers \
            and not self.ssm_layers and not self.eva_layers \
            and self.ffn_types is None and self.rotates("attention")

    @property
    def cache_kind(self) -> str:
        """What serving caches: ``"kv"`` (K/V rows, the dense block),
        ``"latent"`` (one latent row a token), ``"hybrid"`` (K/V rows
        for the attention layers beside a two-row tail a block for the
        conv layers; also rotary-GQA attention over expert layers) or
        ``"window"`` (two K/V pools under two tables: the full-attention
        layers' rows a token, the sliding-window layers' a bounded number
        of blocks a slot) or ``"state"`` (K/V rows for the attention layers
        beside a recurrent state and a conv tail a slot for the
        state-space layers, which every token rewrites whole; in a model
        of ``"parallel"`` layers the attention layers and the state-space
        layers are the same layers) or ``"eva"``
        (two K/V pools of every layer under two tables: exact rows of the
        open window, a bounded number of blocks a slot, and one summary row
        a chunk of every closed window, kept)."""
        if self.mla is not None:
            return "latent"
        if self.eva_layers:
            return "eva"
        if self.ssm1 is not None:
            return "state_window"
        if self.window_layers:
            return "window"
        if self.ssm_layers:
            return "state"
        return "kv" if self.dense_block else "hybrid"

    @property
    def kv_pack(self) -> int:
        """KV heads the hybrid pool lays side by side on a row's lanes: a
        head of fewer than the chip's 128 lanes is packed with its
        neighbours, ``d_head x kv_pack`` lanes a row (a query is then its
        head's values in its KV head's lanes and zeros beside them). A
        pool whose rows are 64 lanes wide the TPU compiler holds in a
        layout of its own and copies, whole, to the kernel's and back in
        every tick (PERF.md, PR 33). No option: 1 for every other cache."""
        if self.diff_attn:
            # Differential attention's value PAIR is the packed row: two
            # heads, whatever their width (128 lanes at a head of 64).
            return 2
        if self.cache_kind != "hybrid" or 128 % self.d_head:
            return 1
        return math.gcd(128 // self.d_head, self.n_kv_heads)

    @property
    def cache_layers(self) -> int:
        """The cache's depth: one layer of rows for every attention that
        sees its whole context (a window layer's rows: ``window_layers``
        deep, in the window pool)."""
        types = self.layer_types or ()
        return (self.n_layers - self.conv_layers - self.window_layers
                - sum(types.count(t) for t in ("ssm", "ssm1", "gmu", "cross"))
                ) * self.sublayers

    @property
    def n_dense_layers(self) -> int:
        if self.ffn_types is not None:
            return self.ffn_types.count("dense")
        if self.moe is None:
            return self.n_layers
        return min(self.moe.first_dense, self.n_layers)


def _key(c: Dict[str, Any], *names: str) -> Any:
    """The first of a quantity's published names that ``c`` has."""
    for n in names:
        if n in c:
            return c[n]
    raise KeyError(" / ".join(names))


# The published keys with "expert" in their name that ``model_from_config``
# reads (a family's alias beside the first family's name).
_EXPERT_KEYS = frozenset({
    "n_routed_experts", "num_experts", "n_shared_experts",
    "num_shared_experts", "num_experts_per_tok", "expert_ffn_hidden_size",
    "zero_expert_num", "zero_expert_type", "use_expert_bias",
    "moe_shared_expert_intermediate_size",
})

# ``hybrid_override_pattern``'s characters: a layer of the published count
# is ONE part.
_PATTERN_PARTS = {"M": "ssm", "*": "attention", "E": "expert", "-": "dense"}


def _pattern_layers(pattern: str) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """``hybrid_override_pattern`` as this repo's layers, a mixer and a
    feed-forward half each: ``(layer_types, ffn_types)``. A mixer part
    (``M`` state-space, ``*`` attention) opens a layer; a feed-forward part
    (``E`` experts, ``-`` dense) right after it is that layer's half, and a
    mixer followed by another mixer is a layer with no feed-forward half.
    A feed-forward part with no mixer before it cannot be said so and is
    refused."""
    mixers, ffns = [], []
    for i, ch in enumerate(pattern):
        part = _PATTERN_PARTS.get(ch)
        if part is None:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: character {ch!r} at "
                f"{i}; the parts built are {sorted(_PATTERN_PARTS)}")
        if part in ("ssm", "attention"):
            mixers.append(part)
            ffns.append("none")
        elif not mixers or ffns[-1] != "none":
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: the {ch!r} at {i} "
                f"has no mixer before it; a layer here is a mixer with at "
                f"most one feed-forward part after it")
        else:
            ffns[-1] = part
    return tuple(mixers), tuple(ffns)


def _state_space_from_config(c: Dict[str, Any], block: Dict[str, Any],
                             hidden: int) -> StateSpace:
    """The ``nemotron_h`` family's state-space keys, each refused by name
    where it says what is not built."""
    for key, built in (("use_conv_bias", True), ("mamba_proj_bias", False),
                       ("mamba_hidden_act", "silu"), ("use_bias", False),
                       ("mlp_bias", False), ("attention_bias", False)):
        if c.get(key, built) != built:
            raise ValueError(
                f"{key} {c[key]!r}: the state-space block is built with "
                f"{key} {built!r}")
    for key in ("gate_before_norm", "latent_proj_plain"):
        if block.get(key, True) is not True:
            raise ValueError(
                f"block.{key} {block[key]!r}: only true is built (silu(z) "
                f"gates before the grouped norm; no norm and no bias on "
                f"the latent projections)")
    ssm = StateSpace(
        n_heads=int(c["mamba_num_heads"]), d_head=int(c["mamba_head_dim"]),
        n_groups=int(c["n_groups"]), d_state=int(c["ssm_state_size"]),
        taps=int(c["conv_kernel"]), chunk=int(c.get("chunk_size", 128)))
    if "expand" in c and int(c["expand"]) * hidden != ssm.inner:
        raise ValueError(
            f"expand {c['expand']} x hidden_size {hidden} is not "
            f"mamba_num_heads x mamba_head_dim ({ssm.inner})")
    return ssm


# What the ``falcon_h1`` family's modelling code says and no published key
# does, each rule with the one value built (the file's ``block`` group).
_FALCON_H1_BLOCK = {
    "mixer_arrangement": "parallel_shared_norm",
    "mup_segments": ["z", "x", "B", "C", "dt"],
    "rotary_convention": "half_split",
}


def _falcon_h1_from_config(c: Dict[str, Any], block: Dict[str, Any]
                           ) -> Tuple[StateSpace, Multipliers]:
    """The ``falcon_h1`` family's state-space widths and fixed multipliers,
    every key that says what is not built refused by name."""
    for key, built in (
            ("mamba_rms_norm", True), ("mamba_norm_before_gate", False),
            ("attn_layer_indices", None), ("mamba_use_mlp", True),
            ("mamba_conv_bias", True), ("mamba_proj_bias", False),
            ("projectors_bias", False), ("attention_bias", False),
            ("mlp_bias", False), ("hidden_act", "silu"),
            ("rope_scaling", None)):
        if c.get(key, built) != built:
            raise ValueError(
                f"{key} {c[key]!r}: the falcon_h1 layer is built with "
                f"{key} {built!r}")
    for key, built in _FALCON_H1_BLOCK.items():
        if block.get(key, built) != built:
            raise ValueError(
                f"block.{key} {block[key]!r}: only {built!r} is built for "
                f"model_type 'falcon_h1'")
    ssm = StateSpace(
        n_heads=int(c["mamba_n_heads"]), d_head=int(c["mamba_d_head"]),
        n_groups=int(c["mamba_n_groups"]), d_state=int(c["mamba_d_state"]),
        taps=int(c["mamba_d_conv"]),
        chunk=int(c.get("mamba_chunk_size", 128)))
    if int(c["mamba_d_ssm"]) != ssm.inner:
        raise ValueError(
            f"mamba_d_ssm {c['mamba_d_ssm']} is not mamba_n_heads x "
            f"mamba_d_head ({ssm.inner})")
    mup = Multipliers(**{
        f.name: c[f.name] for f in dataclasses.fields(Multipliers)
        if f.name in c})
    return ssm, mup


# What the ``phi4flash`` family's paper and modelling code say and no
# published key does, each rule with the one value built (the file's
# ``block`` group).
_PHI4FLASH_BLOCK = {
    "decoder_split": "sambay",
    "attention": "differential",
    "diff_pairing": "adjacent",
    "cross_attention": "differential",
    "lambda_depth": "layer_index_from_0",
    "gmu_memory": "scan_output_before_gate",
    "mlp_order": "gate_up",
}


def decoder_hybrid_layers(n: int) -> Tuple[str, ...]:
    """The ``"sambay"`` split of ``n`` layers (a multiple of 4), two mixers
    a period: below the middle a Mamba-1 layer and a window layer in turn;
    layer ``n / 2`` one more Mamba-1 layer, whose scan output is the
    MEMORY; layer ``n / 2 + 1`` the ONE full-attention layer, whose rows
    are shared; above it a gated memory unit and a cross layer in turn."""
    half = n // 2
    return tuple(
        ("ssm1" if l % 2 == 0 else "window") if l < half
        else "ssm1" if l == half else "attention" if l == half + 1
        else ("gmu" if l % 2 == 0 else "cross") for l in range(n))


def _phi4flash_from_config(c: Dict[str, Any], block: Dict[str, Any]
                           ) -> Tuple[Tuple[str, ...], Mamba1, int]:
    """The ``phi4flash`` family's layers, Mamba-1 widths and window, every
    key that says what is not built refused by name."""
    for key, built in (
            ("mb_per_layer", 2), ("mlp_bias", False),
            ("lm_head_bias", False), ("hidden_act", "silu"),
            ("tie_word_embeddings", True)):
        if c.get(key, built) != built:
            raise ValueError(
                f"{key} {c[key]!r}: the phi4flash layer is built with "
                f"{key} {built!r}")
    rope = sorted(k for k in c if k.startswith("rope_") or k == "rotary_dim")
    if rope:
        raise ValueError(
            f"{rope}: the phi4flash family has no positional encoding; a "
            f"file that names one is not this model")
    for key, built in _PHI4FLASH_BLOCK.items():
        if block.get(key, built) != built:
            raise ValueError(
                f"block.{key} {block[key]!r}: only {built!r} is built for "
                f"model_type 'phi4flash'")
    n, hidden = int(c["num_hidden_layers"]), int(c["hidden_size"])
    if n % 4 or n < 4:
        raise ValueError(
            f"num_hidden_layers {n}: the 'sambay' split is built for a "
            f"multiple of 4 (two decoders of whole periods of 2)")
    window = int(c["sliding_window"])
    if int(block.get("window_span", window)) != window:
        raise ValueError(
            f"block.window_span {block['window_span']} beside "
            f"sliding_window {window}: a window layer's row at t sees "
            f"(t - sliding_window, t], itself included; no other span is "
            f"built")
    a = c.get("assumed") or {}
    ssm1 = Mamba1(
        inner=int(a.get("mamba_expand", 2)) * hidden,
        d_state=int(a.get("mamba_d_state", 16)),
        taps=int(a.get("mamba_d_conv", 4)),
        dt_rank=int(a.get("mamba_dt_rank", -(-hidden // 16))))
    return decoder_hybrid_layers(n), ssm1, window


def _gated(c: Dict[str, Any]) -> bool:
    """Whether the feed-forward parts have a gate matrix, by
    ``mlp_hidden_act``: ``relu2`` is one matrix in, relu squared, one out;
    ``silu`` (or no key) the SwiGLU."""
    act = str(c.get("mlp_hidden_act", "silu"))
    if act not in ("silu", "relu2"):
        raise ValueError(
            f"mlp_hidden_act {act!r}: 'silu' (a gated SwiGLU) and 'relu2' "
            f"(ungated, relu squared) are built")
    return act == "silu"


def model_from_config(config: Dict[str, Any], *, dtype: Any = None,
                      max_seq_len: int = 65536,
                      **overrides: Any) -> TransformerConfig:
    """The model as data: a :class:`TransformerConfig` from a model's
    published ``config.json`` keys, under the names its family publishes
    them. What the data chooses, and the keys read for it:

    - depth and widths: ``num_hidden_layers`` / ``num_layers``,
      ``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
      ``head_dim``, ``vocab_size``; the dense FFN's ``intermediate_size`` /
      ``ffn_hidden_size``; ``rms_norm_eps`` / ``norm_eps``; ``rope_theta``
      (or ``rope_parameters.rope_theta``); ``tie_word_embeddings`` /
      ``tie_embedding`` (the head is the embedding's transpose).
    - each layer's mixer: ``kv_lora_rank`` selects latent attention in
      every layer (``q_lora_rank``, ``qk_nope_head_dim``,
      ``qk_rope_head_dim``, ``v_head_dim``, ``rope_scaling``;
      ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: the normed latents
      times ``(hidden / rank)^1/2``). Else ``layer_types`` names every
      layer's (:data:`PUBLISHED_MIXERS`): ``full_attention`` /
      ``attention`` (rotary GQA), ``sliding_attention`` (the same over the
      last ``sliding_window`` positions; ``sliding_windows``, a layer's
      own, must agree) or ``conv`` (a gated short convolution:
      ``conv_L_cache`` taps, ``conv_bias`` false); without it every layer
      is rotary GQA. ``rope_parameters.rope_type`` other than ``default``
      is refused. Or ``hybrid_override_pattern`` (the ``nemotron_h``
      family) names every PART of the published depth, a character each:
      ``M`` a state-space mixer (``mamba_num_heads``, ``mamba_head_dim``,
      ``n_groups``, ``ssm_state_size``, ``conv_kernel``, ``chunk_size``;
      ``expand`` must agree; ``use_conv_bias`` true, ``mamba_proj_bias`` /
      ``use_bias`` / ``mlp_bias`` / ``attention_bias`` false,
      ``mamba_hidden_act`` ``silu``: any other refused by name), ``*``
      attention, ``E`` routed experts, ``-`` a dense FFN. A mixer and the
      feed-forward part right after it are one of this repo's layers, a
      mixer followed by a mixer a layer with no feed-forward half
      (:func:`_pattern_layers`). Or ``attention_class`` (the ``evabyte``
      family; :data:`ATTENTION_CLASSES`, any other value refused by name)
      gives every layer one kind: ``eva``, with ``window_size`` and
      ``chunk_size``; the family's ``num_pred_heads`` (next-position blocks
      of the head; the served logits are the first's),
      ``norm_add_unit_offset`` (``TransformerConfig.norm_offset``), and
      ``fp32_skip_add`` / ``fp32_logits``, which must be true where given:
      the residual's add rounds once from the exact sum and the logits are
      float32 in every program here.
      Or ``model_type`` ``falcon_h1`` gives every layer the two-branch
      mixer (``"parallel"``: a state-space mixer of ``mamba_n_heads`` x
      ``mamba_d_head`` = ``mamba_d_ssm``, ``mamba_n_groups``,
      ``mamba_d_state``, ``mamba_d_conv`` taps, ``mamba_chunk_size``,
      beside rotary GQA on one normed residual) over the dense SwiGLU, and
      the family's fixed multipliers (:class:`Multipliers`, by their
      published names); ``mamba_rms_norm`` true, ``mamba_norm_before_gate``
      false, ``mamba_use_mlp`` true, ``mamba_conv_bias`` true,
      ``attn_layer_indices`` / ``rope_scaling`` null, every projection bias
      false, ``hidden_act`` ``silu``: any other refused by name.
      Or ``model_type`` ``phi4flash`` gives the depth the ``"sambay"``
      split (:func:`decoder_hybrid_layers`: Mamba-1 and window layers in
      turn, one more Mamba-1 layer whose scan output is the memory, ONE
      full-attention layer whose rows the cross layers read again, then
      gated memory units and cross layers in turn), differential attention
      with projection biases and no positional term, LayerNorm with bias
      (``layer_norm_eps``), a tied head; ``sliding_window``; the Mamba-1
      widths from the file's ``assumed`` group (``mamba_expand`` 2,
      ``mamba_d_state`` 16, ``mamba_d_conv`` 4, ``mamba_dt_rank`` hidden /
      16: the family's convention where absent); ``mb_per_layer`` 2,
      ``mlp_bias`` / ``lm_head_bias`` false, ``hidden_act`` ``silu``,
      ``tie_word_embeddings`` true, no ``rope_*`` key and a depth that is
      a multiple of 4: any other refused by name.
    - each layer's feed-forward half: ``n_routed_experts`` /
      ``num_experts`` select the expert layer after the first
      ``first_k_dense_replace`` / ``num_dense_layers`` layers (an expert's
      width ``moe_intermediate_size`` / ``expert_ffn_hidden_size``; experts
      a token ``num_experts_per_tok`` / ``moe_topk``; ``n_shared_experts``
      / ``num_shared_experts``; ``mlp_layer_types``, ``dense`` | ``sparse``
      a layer, which must say what ``first_k_dense_replace`` says: leading
      dense layers, expert layers after;
      ``zero_expert_num`` identity experts after the routed ones in the
      router's width; ``topk_method`` with ``n_group`` / ``topk_group``
      (without ``topk_method: group_limited_greedy`` only 1 / 1, no group
      limit, is accepted);
      ``routed_scaling_factor``, ``norm_topk_prob``; the scoring
      ``scoring_func``: ``softmax`` | ``sigmoid``; ``use_expert_bias``: the
      top is taken of scores + a per-expert bias;
      ``moe_shared_expert_intermediate_size``: the shared expert's width,
      said outright; ``moe_latent_size``: the routed experts live in a
      latent of that width; ``mlp_hidden_act`` ``relu2``: the experts have
      no gate matrix). A key that names
      experts and is none of these is refused by name: a file whose
      experts this reader cannot see is never built dense.

    Without any of them the keys are those of a Llama-style dense decoder.

    What no published key says is said by two groups of this repo's own. A
    file cut to one chip's share says so under ``deployment``:
    ``experts_total`` (the routed experts of the whole layer;
    ``n_routed_experts`` is then how many are held here) and
    ``expert_share`` (which share of them, 0-based; consecutive ranges).
    ``vocab_size`` is the rows held. What only the modelling code says is
    under ``block``: ``sublayers`` (attention + dense-FFN pairs a layer),
    ``routed_branch`` ``[leaves, rejoins]`` (the routed experts are
    computed beside the dense FFNs from the normed residual sublayer
    ``leaves``'s FFN reads, and added after sublayer ``rejoins``'s FFN),
    ``corrected_choice`` (as ``use_expert_bias``), ``router_scoring`` (as
    ``scoring_func``), ``qk_norm`` (an RMSNorm with a learned gain over
    each query and key head, before the rotary embedding),
    ``rotary_layers`` (the published name of the one attention kind whose
    queries and keys are rotated, ``"sliding_attention"`` for a model
    whose full layers carry no positional term; ``"none"``: no attention
    layer has one; ``"all"`` or absent: every
    attention layer), ``gate_before_norm`` and ``latent_proj_plain`` (a
    state-space mixer's ``silu(z)`` gates before its grouped norm; the
    latent projections have no norm and no bias: true, the only rule built,
    any other refused by name), ``scale_renormed`` (``routed_scaling_factor``
    multiplies the renormed weights too: renorm, then scale),
    ``mixer_arrangement`` / ``mup_segments`` / ``rotary_convention`` (the
    ``falcon_h1`` family's: both branches read the one ``input_layernorm``,
    ``"parallel_shared_norm"``; ``ssm_multipliers``' five scalars lie over
    ``W_in``'s columns in the order ``["z", "x", "B", "C", "dt"]``; the
    rotary pairs dimension ``i`` with ``i + d / 2``, ``"half_split"``: the
    one value built each, any other refused by name), ``decoder_split`` /
    ``attention`` / ``diff_pairing`` / ``cross_attention`` /
    ``lambda_depth`` / ``gmu_memory`` / ``mlp_order`` / ``window_span`` (the
    ``phi4flash`` family's: ``"sambay"``; ``"differential"``; query head
    ``2p + σ`` is half ``σ`` of pair ``p``, ``"adjacent"``; the cross layers
    are differential too; ``λ₀(l)`` takes the layer's index from 0; a gated
    memory unit multiplies by the memory layer's scan output with the
    ``D·x`` skip and before ``silu(z)``, ``"scan_output_before_gate"``;
    ``W₁``'s columns are ``[gate | up]``; the window's span is
    ``sliding_window``: the one value built each, any other refused by
    name) and
    ``norm_placement`` (``"pre"``, the only one built: any other is
    refused by name)."""
    c = config
    heads = int(c["num_attention_heads"])
    hidden = int(c["hidden_size"])
    mla = moe = None
    d_head = int(c.get("head_dim") or hidden // heads)
    kv_heads = int(c.get("num_key_value_heads") or heads)
    block = c.get("block") or {}
    if c.get("kv_lora_rank"):
        rs = c.get("rope_scaling") or None
        yarn = None
        if rs is not None:
            if rs.get("type", rs.get("rope_type")) != "yarn":
                raise ValueError(
                    f"rope_scaling type {rs.get('type')!r}: only 'yarn' "
                    f"is built")
            yarn = YarnRope(
                factor=float(rs["factor"]),
                original_len=int(rs["original_max_position_embeddings"]),
                beta_fast=float(rs.get("beta_fast", 32)),
                beta_slow=float(rs.get("beta_slow", 1)),
                mscale=float(rs.get("mscale", 1.0)),
                mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
            )
        q_rank, kv_rank = int(c.get("q_lora_rank") or 0), int(c["kv_lora_rank"])
        mla = LatentAttention(
            q_rank=q_rank, kv_rank=kv_rank,
            nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
            v_head=int(c["v_head_dim"]),
            yarn=yarn,
            q_scale=(hidden / q_rank) ** 0.5
            if c.get("mla_scale_q_lora") and q_rank else 1.0,
            kv_scale=(hidden / kv_rank) ** 0.5
            if c.get("mla_scale_kv_lora") else 1.0,
        )
        d_head, kv_heads = mla.nope + mla.rope, heads
    unknown = sorted(k for k in c if "expert" in k and c[k]
                     and k not in _EXPERT_KEYS)
    if unknown:
        raise ValueError(
            f"the file names experts under {unknown}, which this reader "
            f"does not know (it reads {sorted(_EXPERT_KEYS)}): refused, "
            f"never built as a dense model")
    n_held = c.get("n_routed_experts") or c.get("num_experts")
    if n_held:
        if int(c.get("moe_layer_freq", 1)) != 1:
            raise ValueError("moe_layer_freq other than 1 is not built")
        n_zero = int(c.get("zero_expert_num") or 0)
        if n_zero and c.get("zero_expert_type", "identity") != "identity":
            raise ValueError(
                f"zero-compute experts of type {c['zero_expert_type']!r}: "
                f"only 'identity' is built")
        grouped = c.get("topk_method", "greedy") == "group_limited_greedy"
        if not grouped:
            for name in ("n_group", "topk_group"):
                if int(c.get(name) or 1) != 1:
                    raise ValueError(
                        f"{name} {c[name]} without topk_method "
                        f"'group_limited_greedy': a group limit is built "
                        f"for that method only")
        if not isinstance(block.get("corrected_choice", False), bool):
            raise ValueError(
                f"block.corrected_choice {block['corrected_choice']!r}: "
                f"true (the top is taken of scores + a per-expert bias) "
                f"or false")
        mlp_types = c.get("mlp_layer_types")
        if mlp_types is not None:
            n_dense = int(c.get("first_k_dense_replace")
                          or c.get("num_dense_layers") or 0)
            said = [str(t) for t in mlp_types]
            if said != ["dense"] * min(n_dense, len(said)) \
                    + ["sparse"] * max(len(said) - n_dense, 0):
                raise ValueError(
                    f"mlp_layer_types {sorted(set(said))}: {n_dense} "
                    f"leading 'dense' layers (first_k_dense_replace) and "
                    f"'sparse' after them is what is built")
        dep = c.get("deployment") or {}
        held = int(n_held)
        total = int(dep.get("experts_total", held))
        width = int(_key(c, "moe_intermediate_size",
                         "expert_ffn_hidden_size"))
        branch = block.get("routed_branch")
        moe = ExpertLayer(
            n_experts=total + n_zero, held=held,
            held_first=int(dep.get("expert_share", 0)) * held,
            per_token=int(_key(c, "num_experts_per_tok", "moe_topk")),
            width=width,
            shared_width=int(
                c.get("moe_shared_expert_intermediate_size")
                or int(c.get("n_shared_experts")
                       or c.get("num_shared_experts") or 0) * width),
            n_groups=int(c["n_group"]) if grouped else 1,
            top_groups=int(c["topk_group"]) if grouped else 1,
            scale=float(c.get("routed_scaling_factor", 1.0)),
            renorm=bool(c.get("norm_topk_prob", False)),
            renorm_scaled=bool(block.get("scale_renormed", False)),
            first_dense=int(c.get("first_k_dense_replace")
                            or c.get("num_dense_layers") or 0),
            n_zero=n_zero,
            corrected=bool(block.get("corrected_choice")
                           or c.get("use_expert_bias")),
            scoring=str(c.get("scoring_func")
                        or block.get("router_scoring") or "softmax"),
            branch=None if branch is None else (int(branch[0]),
                                                int(branch[1])),
            latent=int(c.get("moe_latent_size") or 0),
            gated=_gated(c),
        )
    if dtype is None:
        dtype = jnp.dtype(str(c.get("torch_dtype", "bfloat16")))
    layer_types, window, ssm, ffn_types = None, 0, None, None
    if c.get("hybrid_override_pattern") is not None:
        if c.get("layer_types") is not None:
            raise ValueError(
                "hybrid_override_pattern beside layer_types: a file says "
                "its layers' kinds one way")
        pattern = str(c["hybrid_override_pattern"])
        if len(pattern) != int(c["num_hidden_layers"]):
            raise ValueError(
                f"hybrid_override_pattern names {len(pattern)} parts for "
                f"num_hidden_layers {c['num_hidden_layers']}")
        layer_types, ffn_types = _pattern_layers(pattern)
        if "ssm" in layer_types:
            ssm = _state_space_from_config(c, block, hidden)
        if "dense" in ffn_types and not _gated(c):
            raise ValueError(
                "a dense feed-forward part ('-') with mlp_hidden_act "
                "relu2 is not built")
    elif c.get("layer_types") is not None:
        names = PUBLISHED_MIXERS
        other = sorted({str(t) for t in c["layer_types"]} - set(names))
        if other:
            raise ValueError(
                f"layer_types {other}: the layer kinds built are "
                f"{sorted(names)}")
        layer_types = tuple(names[str(t)] for t in c["layer_types"])
        if c.get("conv_bias"):
            raise ValueError("a short convolution with a bias is not built")
        if "window" in layer_types:
            window = int(c.get("sliding_window") or 0)
            own = c.get("sliding_windows")
            if own is not None and [int(w) for w in own] != [
                    window if t == "window" else 0 for t in layer_types]:
                raise ValueError(
                    f"sliding_windows {sorted(set(own))}: every "
                    f"sliding_attention layer at sliding_window "
                    f"({window}) and every other at 0 is what is built")
    mup = Multipliers()
    if c.get("model_type") == "falcon_h1":
        if layer_types is not None or n_held:
            raise ValueError(
                "model_type 'falcon_h1' beside layer_types, "
                "hybrid_override_pattern or experts: every layer of the "
                "family is the two-branch mixer over a dense feed-forward "
                "half")
        ssm, mup = _falcon_h1_from_config(c, block)
        layer_types = ("parallel",) * int(c["num_hidden_layers"])
    ssm1, decoder_hybrid = None, {}
    if c.get("model_type") == "phi4flash":
        if layer_types is not None or n_held or c.get("kv_lora_rank"):
            raise ValueError(
                "model_type 'phi4flash' beside layer_types, "
                "hybrid_override_pattern, experts or latent attention: the "
                "family's layers follow from its depth alone")
        layer_types, ssm1, window = _phi4flash_from_config(c, block)
        block = dict(block, rotary_layers="none")
        decoder_hybrid = dict(ssm1=ssm1, diff_attn=True, attn_bias=True,
                              norm="layer")
    window_rule, chunk = "sliding", 0
    if c.get("attention_class") is not None:
        said = str(c["attention_class"])
        if said not in ATTENTION_CLASSES or layer_types is not None:
            raise ValueError(
                f"attention_class {said!r} (beside layer_types or "
                f"hybrid_override_pattern: {layer_types is not None}): the "
                f"classes built are {sorted(ATTENTION_CLASSES)}, each "
                f"every layer's kind")
        # What only the modelling code says, each rule with the one value
        # built; another reading is refused by name, never built wrong.
        for key, built in (("summary_key", "weighted_plus_mu"),
                           ("summary_logits_scaled", False),
                           ("summary_after_rotary", True),
                           ("window_rule", "aligned")):
            if block.get(key, built) != built:
                raise ValueError(
                    f"block.{key} {block[key]!r}: only {built!r} is built "
                    f"for attention_class {said!r}")
        for key in ("fp32_skip_add", "fp32_logits"):
            if c.get(key, True) is not True:
                raise ValueError(
                    f"{key} {c[key]!r}: the residual's add and the logits "
                    f"are float32 in every program here; only true is "
                    f"built")
        n = int(_key(c, "num_hidden_layers", "num_layers"))
        layer_types = (ATTENTION_CLASSES[said],) * n
        window, chunk = int(c["window_size"]), int(c["chunk_size"])
        window_rule = "aligned"
    rp = c.get("rope_parameters") or {}
    if not c.get("kv_lora_rank") and str(
            rp.get("rope_type", "default")) != "default":
        raise ValueError(
            f"rope_parameters.rope_type {rp['rope_type']!r}: only "
            f"'default' (no scaling) is built for rotary GQA")
    if block.get("norm_placement", "pre") != "pre":
        raise ValueError(
            f"block.norm_placement {block['norm_placement']!r}: only 'pre' "
            f"(a norm before each half, on the residual) is built")
    rotary = ("attention", "window")
    if block.get("rotary_layers", "all") == "none":
        rotary = ()
    elif block.get("rotary_layers", "all") != "all":
        said = block["rotary_layers"]
        said = [said] if isinstance(said, str) else list(said)
        other = sorted(set(map(str, said)) - set(PUBLISHED_MIXERS))
        if other or any(PUBLISHED_MIXERS[str(t)] == "conv" for t in said):
            raise ValueError(
                f"block.rotary_layers {said}: 'all', 'none' or attention "
                f"kinds of {sorted(PUBLISHED_MIXERS)}")
        rotary = tuple(sorted({PUBLISHED_MIXERS[str(t)] for t in said}))
    kw = dict(
        vocab_size=int(c["vocab_size"]), d_model=hidden,
        n_layers=len(layer_types) if ffn_types is not None
        else int(_key(c, "num_hidden_layers", "num_layers")),
        n_heads=heads, n_kv_heads=kv_heads, d_head=d_head,
        d_ff=int(_key(c, "intermediate_size", "ffn_hidden_size")),
        max_seq_len=max_seq_len,
        rope_theta=float(
            c.get("rope_theta") or rp.get("rope_theta", 10000.0)),
        norm_eps=float(c.get("rms_norm_eps") or c.get("norm_eps")
                       or c.get("layer_norm_epsilon")
                       or c.get("layer_norm_eps") or 1e-6),
        dtype=dtype,
        mla=mla, moe=moe, sublayers=int(block.get("sublayers", 1)),
        layer_types=layer_types,
        conv_taps=int(c.get("conv_L_cache", 3)),
        qk_norm=bool(block.get("qk_norm", False)),
        tied_head=bool(c.get("tie_word_embeddings")
                       or c.get("tie_embedding")),
        window=window, rotary=rotary, ssm=ssm, ffn_types=ffn_types,
        window_rule=window_rule, chunk=chunk,
        pred_heads=int(c.get("num_pred_heads", 1)),
        norm_offset=bool(c.get("norm_add_unit_offset", False)),
        mup=mup, **decoder_hybrid,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# Parameter initialisation and sharding specs (two pytrees, one shape)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    """Initialise the parameter pytree.

    Per-layer weights carry a leading ``n_layers`` axis so the forward pass can
    ``lax.scan`` over depth. Residual-output projections (``wo``, ``w2``) are
    scaled by ``(2·n_layers)^-1/2`` so the residual stream's variance stays O(1)
    at init regardless of depth.
    """
    if cfg.cache_kind in ("hybrid", "window", "state", "eva",
                          "state_window"):
        from tree_attention_tpu.models.hybrid import init_hybrid_params

        return init_hybrid_params(key, cfg)
    if not cfg.dense_block:
        from tree_attention_tpu.models.experts import init_block_params

        return init_block_params(key, cfg)
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    L, D = cfg.n_layers, cfg.d_model
    std = 0.02
    res_std = std / (2 * cfg.n_layers) ** 0.5

    def normal(key, shape, stddev):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 6)
    layers = {
        "ln1": jnp.ones((L, D), jnp.float32),
        "wq": normal(ks[0], (L, D, cfg.q_dim), std),
        "wk": normal(ks[1], (L, D, cfg.kv_dim), std),
        "wv": normal(ks[2], (L, D, cfg.kv_dim), std),
        "wo": normal(ks[3], (L, cfg.q_dim, D), res_std),
        "ln2": jnp.ones((L, D), jnp.float32),
        "w1": normal(ks[4], (L, D, cfg.d_ff), std),
        "w3": normal(ks[5], (L, D, cfg.d_ff), std),
        "w2": normal(jax.random.fold_in(ks[5], 1), (L, cfg.d_ff, D), res_std),
    }
    if cfg.qk_norm:
        layers["q_ln"] = jnp.ones((L, cfg.d_head), jnp.float32)
        layers["k_ln"] = jnp.ones((L, cfg.d_head), jnp.float32)
    out = {
        "embed": normal(k_embed, (cfg.vocab_size, D), std),
        "layers": layers,
        "ln_f": jnp.ones((D,), jnp.float32),
    }
    if not cfg.tied_head:
        out["wout"] = normal(k_out, (D, cfg.vocab_size), std)
    return out


def param_specs(
    cfg: TransformerConfig,
    *,
    data_axis: Optional[str] = AXIS_DATA,
    model_axis: Optional[str] = AXIS_MODEL,
) -> Params:
    """``PartitionSpec`` pytree mirroring :func:`init_params`.

    Megatron-style tensor parallelism: column-parallel in-projections
    (``wq/wk/wv/w1/w3`` shard their output features over ``model_axis``),
    row-parallel out-projections (``wo/w2`` shard their input features), so the
    only TP collective per block is the psum XLA inserts after the row-parallel
    matmul. Embedding/unembedding shard the vocab-orthogonal feature dim.
    ``data_axis`` is accepted for signature symmetry (params are never
    batch-sharded).
    """
    del data_axis
    if not cfg.dense_block:
        raise NotImplementedError(
            "param_specs: sharding the parameters of a model with latent "
            "attention, experts or conv layers is not built")
    m = model_axis
    if cfg.qk_norm or cfg.tied_head:
        raise NotImplementedError(
            "param_specs: sharding a model with QK-norm or a tied head is "
            "not built")
    return {
        "embed": P(None, m),
        "layers": {
            "ln1": P(None, None),
            "wq": P(None, None, m),
            "wk": P(None, None, m),
            "wv": P(None, None, m),
            "wo": P(None, m, None),
            "ln2": P(None, None),
            "w1": P(None, None, m),
            "w3": P(None, None, m),
            "w2": P(None, m, None),
        },
        "ln_f": P(None),
        "wout": P(None, m),
    }


def param_shardings(cfg: TransformerConfig, mesh: Mesh, **kw) -> Params:
    specs = param_specs(cfg, **kw)

    def to_sharding(spec: P) -> NamedSharding:
        # Drop axis names the mesh doesn't carry, so the same spec tree works
        # on a seq-only mesh and a full data×seq×model mesh.
        pruned = P(*(a if a in mesh.shape else None for a in spec))
        return NamedSharding(mesh, pruned)

    return jax.tree.map(to_sharding, specs, is_leaf=lambda x: isinstance(x, P))


def count_params(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * rms) * w).astype(x.dtype)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float) -> jax.Array:
    """LayerNorm with mean, gain and bias, reduced in float32."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * inv) * w + b).astype(x.dtype)


def norm_rows(cfg: "TransformerConfig", x: jax.Array, p: Params,
              name: str) -> jax.Array:
    """The model's norm of ``x`` under leaf ``name`` of ``p``: RMSNorm, or
    (``cfg.norm`` ``"layer"``) LayerNorm with the bias under ``name_b``."""
    if cfg.norm == "layer":
        return layer_norm(x, p[name], p[name + "_b"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding on ``(B, H, T, D)``; ``positions`` is
    ``(T,)`` shared across the batch or ``(B, T)`` per-row (the ragged
    decode shape: every cache slot sits at its own global offset).

    Positions are *global* sequence indices: under sequence parallelism each
    shard passes its own offset slice, so rotations agree across the mesh.
    """
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if angles.ndim == 3:  # (B, T, half): broadcast over the head dim
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rotated.astype(x.dtype)


def embed(params: Params, tokens: jax.Array,
          mup: Multipliers = Multipliers()) -> jax.Array:
    """The embedded rows of ``tokens``, times ``embedding_multiplier``."""
    return times(jnp.take(params["embed"], tokens, axis=0),
                 mup.embedding_multiplier)


def unembed(params: Params, x: jax.Array,
            mup: Multipliers = Multipliers()) -> jax.Array:
    """The head: ``x`` times ``wout``, or, where the parameters hold none
    (``TransformerConfig.tied_head``), times the embedding's transpose.
    Float32 logits, times ``lm_head_multiplier``. A head of several next-position blocks
    (``TransformerConfig.pred_heads``: ``wout`` wider than the vocabulary)
    serves its first block, the next position's."""
    if "wout" in params:
        wout, vocab = params["wout"], params["embed"].shape[0]
        if wout.shape[-1] != vocab:
            wout = wout[:, :vocab]
        logits = (x @ wout).astype(jnp.float32)
    else:
        logits = jnp.einsum(
            "...d,vd->...v", x, params["embed"]).astype(jnp.float32)
    return times(logits, mup.lm_head_multiplier)


# The attention input projections as a serving engine holds them
# (:func:`served_layout`): out-major, the contracted axis minor, under a name
# of their own, since a square ``wq`` says nothing by its shape.
GQA_SERVED, LATENT_SERVED = "wqkv_t", "wqb_t"


def served_layout(params: Params) -> Params:
    """``params`` with every attention input projection re-laid as the tick
    programs want it. A layer's ``wq``, ``wk`` and ``wv`` ``(..., D, out)``
    become one ``wqkv_t`` ``(..., q_dim + 2 x kv_dim, D)`` (a layer that
    holds ``wq`` alone, a cross layer: ``(..., q_dim, D)``); a latent layer's
    ``wqb`` ``(..., rank, out)`` becomes ``wqb_t`` ``(..., out, rank)``.

    The compiler for the chip multiplies by these with the contracted axis
    minor. Handed the outer format, every layer of every tick slices the
    weight out of its stack and transposes it in a copy of its own before
    the product; handed this one, the product reads the stack where it lies
    (``tests/test_chip_compile.py`` holds the compiled programs to that).
    For ``wqb_t`` that also takes the product's result left flat: with the
    reshape to heads folded in, the compiler stages the layer's whole weight
    in fast memory first, so ``latent_qkv`` holds the reshape behind a
    barrier.
    Every other leaf is handed on untouched, and so is a tree that holds none
    of the three or holds them re-laid already. One jitted call a leaf: no
    second copy of the model is ever made, and the outer leaves are the
    caller's to drop."""
    if isinstance(params, (list, tuple)):
        return type(params)(served_layout(v) for v in params)
    if not isinstance(params, dict):
        return params
    out = {k: served_layout(v) for k, v in params.items()}
    if "wq" in out:
        # (A cross layer projects queries only: its ``wqkv_t`` is ``wq``'s.)
        out[GQA_SERVED] = _out_major(
            *(out.pop(n) for n in ("wq", "wk", "wv") if n in out))
    if "wqb" in out:
        out[LATENT_SERVED] = _out_major(out.pop("wqb"))
    return out


@jax.jit
def _out_major(*weights: jax.Array) -> jax.Array:
    """``(..., in, out_i)`` weights of one input as one ``(..., sum of
    out_i, in)``."""
    return jnp.concatenate([jnp.swapaxes(w, -1, -2) for w in weights],
                           axis=-2)


def times_out_major(x: jax.Array, w_t: jax.Array) -> jax.Array:
    """``x`` ``(..., in)`` times an out-major weight ``(out, in)``."""
    return jnp.einsum("...i,oi->...o", x, w_t)


def gqa_qkv(p: Params, h: jax.Array, positions: jax.Array,
            cfg: TransformerConfig, rotary: bool = True,
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The rotary-GQA projections of the normed residual ``h`` ``(B, T,
    D)``: queries ``(B, H, T, d)`` and new keys and values ``(B, Hkv, T,
    d)``, the rotary embedding applied (not where ``rotary`` is false: a
    layer kind with no positional term, ``TransformerConfig.rotates``);
    the keys times ``cfg.mup.key_multiplier``; where ``cfg.qk_norm``, an RMSNorm over each query and key head (one
    learned gain of ``d`` a layer) before it."""
    if GQA_SERVED in p:      # the served form: one product, cut in three
        q, k, v = jnp.split(times_out_major(h, p[GQA_SERVED]),
                            [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
    else:
        q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    k = times(k, cfg.mup.key_multiplier)    # the cached row holds it scaled
    q = _heads(q, cfg.n_heads, cfg.d_head)
    k = _heads(k, cfg.n_kv_heads, cfg.d_head)
    v = _heads(v, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_ln"], cfg.norm_eps)
        k = rms_norm(k, p["k_ln"], cfg.norm_eps)
    if not rotary:
        return q, k, v
    return (rope(q, positions, cfg.rope_theta),
            rope(k, positions, cfg.rope_theta), v)


def _heads(x: jax.Array, n_heads: int, d_head: int) -> jax.Array:
    """(B, T, H·D) -> (B, H, T, D) — the ops-layer layout."""
    B, T, _ = x.shape
    return x.reshape(B, T, n_heads, d_head).transpose(0, 2, 1, 3)


def _unheads(x: jax.Array) -> jax.Array:
    """(B, H, T, D) -> (B, T, H·D)."""
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _attention_block(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    mesh: Optional[Mesh],
    axes: Dict[str, Optional[str]],
    layout: str = "contiguous",
) -> jax.Array:
    q, k, v = gqa_qkv(p, x, positions, cfg)

    if mesh is not None and mesh.shape.get(axes["seq"], 1) > 1:
        from tree_attention_tpu.parallel.tree import tree_attention

        out, _ = tree_attention(
            q, k, v,
            mesh=mesh,
            seq_axis=axes["seq"],
            data_axis=axes["data"],
            head_axis=axes["model"],
            causal=True,
            impl=cfg.attn_impl,
            block_size=cfg.attn_block_size,
            layout=layout,
        )
    else:
        out, _ = flash_attention(
            q, k, v,
            causal=True,
            impl=cfg.attn_impl,
            block_size=cfg.attn_block_size,
        )
    return _unheads(out) @ p["wo"]


def _mlp_block(p: Params, x: jax.Array,
               mult: Tuple[float, float] = (1.0, 1.0)) -> jax.Array:
    """The SwiGLU; ``mult`` (``Multipliers.mlp_multipliers``): the gate
    scaled inside its activation, and the output."""
    gate = jax.nn.silu(times(x @ p["w1"], mult[0]))
    return times((gate * (x @ p["w3"])) @ p["w2"], mult[1])


def _resolved_layout(cfg, mesh, axes) -> str:
    """zigzag only matters (and only type-checks) on a >1-way seq axis."""
    if (
        cfg.seq_layout == "zigzag"
        and mesh is not None
        and axes.get("seq")
        and mesh.shape.get(axes["seq"], 1) > 1
    ):
        return "zigzag"
    return "contiguous"


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    data_axis: Optional[str] = AXIS_DATA,
    seq_axis: str = AXIS_SEQ,
    model_axis: Optional[str] = AXIS_MODEL,
) -> jax.Array:
    """Token ids ``(B, T)`` -> logits ``(B, T, vocab)`` (float32).

    With ``mesh``, activations are constrained to ``P(data, seq, None)`` so
    the residual stream stays sequence-sharded between tree-attention calls;
    without it, this is a plain single-device forward.
    """
    from tree_attention_tpu.parallel.mesh import prune_axes

    if not cfg.dense_block:
        raise NotImplementedError(
            "forward: the full-sequence (training) pass builds the dense "
            "block only; a model with latent attention, experts or conv "
            "layers is served through models.decode.forward_step")
    axes = prune_axes(
        mesh, {"data": data_axis, "seq": seq_axis, "model": model_axis}
    )
    if mesh is not None:
        act_spec = P(axes["data"], axes["seq"], None)

    def constrain(x):
        if mesh is None:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(mesh, act_spec))

    T = tokens.shape[1]
    if T > cfg.max_seq_len:
        raise ValueError(f"sequence length {T} exceeds max_seq_len={cfg.max_seq_len}")
    layout = _resolved_layout(cfg, mesh, axes)
    if layout == "zigzag":
        # Permute the (tiny, int32) token array once; every later op is
        # position-pointwise, RoPE reads the true global positions, and the
        # zigzag tree_attention handles cross-shard causality. Model output
        # is row-for-row the contiguous model's output, permuted.
        from tree_attention_tpu.parallel.tree import zigzag_perm

        perm, _ = zigzag_perm(T, mesh.shape[axes["seq"]])
        perm = jnp.asarray(perm)
        tokens = jnp.take(tokens, perm, axis=1)
        positions = perm.astype(jnp.int32)
    else:
        positions = jnp.arange(T, dtype=jnp.int32)
    x = constrain(jnp.take(params["embed"], tokens, axis=0))

    def body(x, layer):
        x = x + constrain(
            _attention_block(
                layer, rms_norm(x, layer["ln1"], cfg.norm_eps),
                positions, cfg, mesh, axes, layout,
            )
        )
        x = x + constrain(_mlp_block(layer, rms_norm(x, layer["ln2"], cfg.norm_eps)))
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["layers"])

    return unembed(params, rms_norm(x, params["ln_f"], cfg.norm_eps))


def cross_entropy_loss(
    logits: jax.Array, targets: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean next-token cross entropy in float32. ``targets``/``mask``: (B, T)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: TransformerConfig,
    **fwd_kw,
) -> jax.Array:
    """Batch = {"inputs": (B,T) ids, "targets": (B,T) ids, optional "mask"}.

    Inputs/targets are pre-shifted at the data layer so both have length T —
    keeping T divisible by the sequence-parallel shard count (a ``T-1`` shift
    inside the model would break the mesh divisibility contract).
    """
    logits = forward(params, batch["inputs"], cfg, **fwd_kw)
    targets, mask = batch["targets"], batch.get("mask")
    mesh = fwd_kw.get("mesh")
    axes = {
        "seq": fwd_kw.get("seq_axis", AXIS_SEQ),
        "data": fwd_kw.get("data_axis", AXIS_DATA),
        "model": fwd_kw.get("model_axis", AXIS_MODEL),
    }
    from tree_attention_tpu.parallel.mesh import prune_axes

    if _resolved_layout(cfg, mesh, prune_axes(mesh, axes)) == "zigzag":
        # Logits come back in zigzag row order; align the labels. The mean
        # is permutation-invariant, so the loss equals the contiguous one.
        from tree_attention_tpu.parallel.tree import zigzag_perm

        perm, _ = zigzag_perm(targets.shape[1], mesh.shape[axes["seq"]])
        perm = jnp.asarray(perm)
        targets = jnp.take(targets, perm, axis=1)
        if mask is not None:
            mask = jnp.take(mask, perm, axis=1)
    return cross_entropy_loss(logits, targets, mask)
