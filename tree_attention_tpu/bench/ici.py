"""Price the decode-merge communication on real ICI: the north-star model.

The ≥2×-vs-ring north star (BASELINE.json: tree ≥2× ring tokens/sec/chip at
1M context) cannot be *measured* on this hardware (one chip; the emulated
mesh prices collectives at memcpy). This model makes it *falsifiable*
instead (VERDICT r3 item 1): every term is either measured in this repo or
a published hardware constant, so anyone with a pod can check the
prediction — and any term they refute, refutes the claim.

Terms:

- **Per-chip compute** t_comp = KV_shard_bytes / (roofline_frac · HBM_BW).
  Decode is HBM-bound; ``roofline_frac`` is MEASURED on the v5e chip —
  :func:`measured_roofline_frac` takes the median over a bench run's
  decode records (robust to one noisy capture; VERDICT r4 weak item 4:
  the constant must track the latest measurement, not a frozen literal),
  and :func:`load_bench_roofline_fracs` pulls those records out of the
  newest ``BENCH_r*.json`` on disk.
- **Merge payloads** — MEASURED from each algorithm's compiled SPMD module
  (``bench.py`` record ``tree_vs_ring_decode_cpu8``, parsed by
  :mod:`tree_attention_tpu.bench.comm`): tree = one pmax (B·Hq·Tq·4 B) +
  one psum (B·Hq·Tq·(D+1)·4 B); ring = N−1 sequential hops of
  B·Hq·Tq·(D+1)·4 B each; Ulysses = all-to-all of the whole KV shard
  (context-proportional). :func:`merge_payloads` computes the closed form
  — parameterised by the QUERY head count, which is what the payload
  scales with (ADVICE r4 item 3: a GQA config's KV head count shrinks
  t_comp but NOT the merge payload) — and
  :func:`payloads_from_comm_record` extracts the same quantities from a
  live comm-accounting record, so the closed form is checkable against
  the compiled HLO every bench run.
- **ICI constants** — the modelled chip's row of :data:`PEAKS`
  (assumptions, stated so they can be attacked): per-hop latency ≈ 1 µs,
  per-link one-way bandwidth ≈ 45 GB/s (2D torus). Parametric throughout.

Cost model (latency-dominated regime — the payloads are KB-scale):

    t_tree  = t_comp + ceil(log2 N) · (2·ALPHA + tree_payload/BETA)
    t_ring  = t_comp + (N−1) · (ALPHA + hop_payload/BETA)
    t_uly   = t_comp + (N−1)·ALPHA + kv_shard_bytes·(N−1)/N / BETA

(tree: the pmax and psum each run a log-depth stage chain; ring: the hop
chain is sequential by construction; Ulysses: bandwidth-dominated by the
KV reshard.) ``python tools/ici_model.py`` prints the table BASELINE.md's
north-star section quotes, re-priced from the records on disk.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip, as ``jax.Device.device_kind`` names it."""

    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    ici_hop_latency_s: float      # an assumption of the merge-cost model
    ici_link_bytes_per_s: float   # per link, one way
    source: str


# The package's ONE table of hardware peaks, keyed by ``device_kind``
# (bench.py, the harness fence guard and the tile tools all read it). A
# roofline share is only ever computed against the row of the device the
# number was measured on: a kind with no row is an error, never a default.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        hbm_bytes_per_s=819e9,
        bf16_flops_per_s=197e12,
        ici_hop_latency_s=1e-6,
        ici_link_bytes_per_s=4.5e10,
        source=(
            "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect per chip "
            "(four links, 45 GB/s a link one way); the ~1 us hop latency "
            "is an assumption of the merge-cost model"
        ),
    ),
}


def peaks(device_kind: Optional[str] = None) -> ChipPeaks:
    """The :data:`PEAKS` row for ``device_kind`` (default: the first
    attached device's). Raises for a kind the table does not list."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a "
            f"row with its source to bench/ici.PEAKS (known: {sorted(PEAKS)})"
        ) from None


# The merge-cost model below prices a pod of one NAMED chip; its defaults
# are that chip's row, and every entry point takes overrides.
MODEL_CHIP = "TPU v5 lite"
HBM_BW = PEAKS[MODEL_CHIP].hbm_bytes_per_s
ALPHA = PEAKS[MODEL_CHIP].ici_hop_latency_s
BETA = PEAKS[MODEL_CHIP].ici_link_bytes_per_s

# Fallback for the measured term when no bench records are available
# (e.g. a fresh checkout before any bench run): the r3/r4 chip campaigns
# consistently measured 0.88-0.93 across 64k-1M contexts. Anything that
# HAS records should use measured_roofline_frac instead.
DEFAULT_ROOFLINE_FRAC = 0.88

# Reference decode shape (/root/reference/model.py:140-145), bf16 cache.
REF_BATCH, REF_HEADS, REF_TQ, REF_HEAD_DIM = 1, 16, 1, 128
CACHE_BYTES = 2  # bf16
_MERGE_STATE_BYTES = 4  # the merge collective carries f32 (num, den)


def merge_payloads(
    q_heads: int = REF_HEADS,
    *,
    batch: int = REF_BATCH,
    tq: int = REF_TQ,
    head_dim: int = REF_HEAD_DIM,
) -> Tuple[int, int]:
    """(tree_payload, ring_hop_payload) bytes for one decode-merge step.

    Both scale with the QUERY head count only — a GQA cache shrinks t_comp
    4×–8× while the merge payload is unchanged, which pulls the
    tree-vs-ring crossover to smaller N (the merge's relative weight
    grows). Tree: one pmax of the lse row + one fused psum of (num, den).
    Ring: each hop carries the running (out, lse) pair.
    """
    row = batch * q_heads * tq * _MERGE_STATE_BYTES
    tree = row + row * (head_dim + 1)        # pmax + fused psum
    ring_hop = row * (head_dim + 1)          # (out, lse) per hop
    return tree, ring_hop


def payloads_from_comm_record(rec: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """Extract measured merge payloads from one ``bench_decode_compare``
    record (a ``ctx_*`` entry of ``tree_vs_ring_decode_cpu8``).

    Returns ``{"tree": bytes_per_step, "ring_hop": bytes_per_hop}`` or
    None if the record lacks the comm accounting. The tree payload is its
    whole per-step collective traffic; the ring hop payload is the total
    divided by the N−1 hops the unrolled chain executes (each hop may be
    several collective-permutes — e.g. out and lse ride separately).
    """
    try:
        n = rec["n_devices"]
        tree_total = rec["tree"]["comm"]["payload_bytes_total"]
        ring_total = rec["ring"]["comm"]["payload_bytes_total"]
    except (KeyError, TypeError):
        return None
    if n < 2:
        return None
    return {"tree": int(tree_total), "ring_hop": int(ring_total) // (n - 1)}


def decode_record_pcts(
    records: Dict[str, Any], key: str = "pct_roofline"
) -> List[float]:
    """The one exclusion rule for "chip decode records worth pricing a TPU
    model from", shared by the in-run path (bench.py, full records under
    ``pct_hbm_roofline``) and the on-disk capture path (summary records
    under ``pct_roofline``): decode records only, and nothing the capture
    flagged ``timing_suspect``.
    """
    return [
        rec[key]
        for name, rec in records.items()
        if name.startswith("decode")
        and isinstance(rec, dict)
        and isinstance(rec.get(key), (int, float))
        and "timing_suspect" not in rec
    ]


def measured_roofline_frac(pcts: List[float]) -> float:
    """Median achieved-roofline fraction over a run's decode records.

    The median — not the max — is the mechanical rule (VERDICT r4 weak
    item 4: the model must not keep a flattering constant while the
    measurement underneath it moves; a single noisy capture, high or low,
    must not move the model either).
    """
    if not pcts:
        return DEFAULT_ROOFLINE_FRAC
    s = sorted(pcts)
    mid = len(s) // 2
    med = s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2
    return med / 100.0


def load_bench_roofline_fracs(
    repo_root: Optional[str] = None,
) -> Tuple[List[float], Optional[str]]:
    """Decode-record roofline percentages from the newest ``BENCH_r*.json``.

    Driver captures store the parsed summary under ``parsed.records`` with
    one ``pct_roofline`` per decode record. Returns ``(pcts, source_path)``
    — empty list when no capture is on disk (fresh checkout).
    """
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )))
    def round_key(p: str) -> Tuple[int, str]:
        # BENCH_r10 must sort after BENCH_r9 (and after BENCH_r04):
        # numeric round key, not lexical.
        stem = os.path.basename(p)[len("BENCH_r"):-len(".json")]
        try:
            return (int(stem), stem)
        except ValueError:
            return (-1, stem)

    paths = sorted(glob.glob(os.path.join(repo_root, "BENCH_r*.json")),
                   key=round_key)
    for path in reversed(paths):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = data.get("parsed") or {}
        pcts = decode_record_pcts(parsed.get("records") or {})
        if pcts:
            return pcts, path
    return [], None


def step_times(
    n: int,
    ctx: int,
    *,
    alpha: float = ALPHA,
    beta: float = BETA,
    hbm_bw: float = HBM_BW,
    roofline_frac: float = DEFAULT_ROOFLINE_FRAC,
    kv_heads: int = REF_HEADS,
    q_heads: int = REF_HEADS,
    head_dim: int = REF_HEAD_DIM,
    cache_bytes: int = CACHE_BYTES,
    tree_payload: Optional[int] = None,
    ring_hop_payload: Optional[int] = None,
) -> Dict[str, float]:
    """Predicted per-decode-step seconds for each family at N chips.

    Payloads default to the closed form at ``q_heads`` (ADVICE r4 item 3:
    payloads scale with query heads, so a 32q/4kv GQA config prices a 2×
    larger merge than the 16-head reference); pass measured values (e.g.
    from :func:`payloads_from_comm_record`) to pin them to compiled HLO.
    """
    if tree_payload is None or ring_hop_payload is None:
        t_p, r_p = merge_payloads(q_heads, head_dim=head_dim)
        tree_payload = t_p if tree_payload is None else tree_payload
        ring_hop_payload = r_p if ring_hop_payload is None else ring_hop_payload
    kv_shard = 2 * (ctx // n) * kv_heads * head_dim * cache_bytes
    t_comp = kv_shard / (roofline_frac * hbm_bw)
    stages = math.ceil(math.log2(n))
    t_tree = t_comp + stages * (2 * alpha + tree_payload / beta)
    t_ring = t_comp + (n - 1) * (alpha + ring_hop_payload / beta)
    t_uly = t_comp + (n - 1) * alpha + kv_shard * (n - 1) / n / beta
    return {"comp": t_comp, "tree": t_tree, "ring": t_ring, "ulysses": t_uly}


def crossover_table(
    ctx: int,
    *,
    ns: Tuple[int, ...] = (8, 16, 32, 64, 128, 256, 512),
    **kwargs: Any,
) -> Dict[str, Any]:
    """Rows of :func:`step_times` over ``ns`` plus the first N with ≥2×
    tree-vs-ring — the falsifiable chain BASELINE.md quotes, with the
    assumptions embedded so every printed table carries its own terms."""
    rows = []
    crossover = None
    for n in ns:
        t = step_times(n, ctx, **kwargs)
        ratio = t["ring"] / t["tree"]
        rows.append({
            "chips": n,
            "t_comp_us": round(t["comp"] * 1e6, 1),
            "t_tree_us": round(t["tree"] * 1e6, 1),
            "t_ring_us": round(t["ring"] * 1e6, 1),
            "t_ulysses_us": round(t["ulysses"] * 1e6, 1),
            "tree_vs_ring": round(ratio, 2),
        })
        if crossover is None and ratio >= 2.0:
            crossover = n
    q_heads = kwargs.get("q_heads", REF_HEADS)
    head_dim = kwargs.get("head_dim", REF_HEAD_DIM)
    tree_p, ring_p = merge_payloads(q_heads, head_dim=head_dim)
    return {
        "ctx": ctx,
        "assumptions": {
            "alpha_s": kwargs.get("alpha", ALPHA),
            "beta_Bps": kwargs.get("beta", BETA),
            "hbm_Bps": kwargs.get("hbm_bw", HBM_BW),
            "roofline_frac": round(
                kwargs.get("roofline_frac", DEFAULT_ROOFLINE_FRAC), 4
            ),
            "q_heads": q_heads,
            "kv_heads": kwargs.get("kv_heads", REF_HEADS),
            "tree_payload_B": kwargs.get("tree_payload", tree_p),
            "ring_hop_payload_B": kwargs.get("ring_hop_payload", ring_p),
        },
        "rows": rows,
        "first_n_with_2x": crossover,
    }
