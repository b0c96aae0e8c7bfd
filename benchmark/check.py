"""How ``correct`` is decided: served tokens against the plain reference.

After the window has closed and the engine is freed, a sample of the
requests the window finished, drawn from the seed with the longest in it, is
run once each through the plain reference of the configuration's family
(``references/<family>.py``, handed in as ``ref``). For every served token
the gap by which its reference logit lies below the reference's best is
read; the widest and the mean of those gaps are each held to a limit the
configuration file states (set from chip readings, see PERF.md). Exact
counts (full lengths, no leaked blocks, no compile in the window) have the
limit 0.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def sample(recs, seed: int, min_tokens: int, max_requests: int) -> List[Any]:
    """Requests that finished with all their tokens: the longest, then
    others drawn from the seed until ``min_tokens`` served tokens are held
    or ``max_requests`` requests."""
    done = sorted((r for r in recs
                   if r.outcome == "budget" and len(r.tokens) == r.output),
                  key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.output)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x636865636b])
    picked = [longest]
    for i in rng.permutation(len(rest)):
        if len(picked) >= max_requests \
                or sum(r.output for r in picked) >= min_tokens:
            break
        picked.append(rest[i])
    return picked


def served_gaps(ref, weights: Dict[str, Any], w, prompt: np.ndarray,
                served: np.ndarray, *, control: Optional[str] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position, in one pass over the prompt and the
    served tokens. With ``control`` the token judged at each position is not
    the served one but the one the lower precision puts first there.
    Returns ``(gaps, judged tokens)``."""
    n = len(served)
    tokens = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    logits = ref.logits_at(weights, w, tokens, rows)
    judged = np.asarray(served)
    if control is not None:
        judged = ref.logits_at(weights, w, tokens, rows,
                               quant=control).argmax(-1)
    gaps = logits.max(-1) - logits[np.arange(n), judged]
    return gaps, judged


def gap_numbers(ref, config: Dict[str, Any], picked, weights: Dict[str, Any],
                control: Optional[str] = None) -> Dict[str, Any]:
    """The compared numbers over ``picked``: widest and mean gap."""
    w = ref.Widths.of(config)
    gaps = [served_gaps(ref, weights, w, r.prompt, np.asarray(r.tokens),
                        control=control)[0]
            for r in picked]
    allg = np.concatenate(gaps) if gaps else np.zeros((0,))
    return {
        "gap_max": float(allg.max()) if allg.size else float("inf"),
        "gap_mean": float(allg.mean()) if allg.size else float("inf"),
        "tokens_compared": int(allg.size),
        "requests_compared": len(picked),
        "longest_compared": max(
            (len(r.prompt) + r.output for r in picked), default=0),
    }


def verdict(numbers: Dict[str, float],
            limits: Dict[str, float]) -> Dict[str, Any]:
    """Each number beside its limit; correct only if every one holds."""
    rows = []
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and value <= limit
        rows.append({"number": name, "value": value, "limit": limit,
                     "ok": bool(ok)})
    return {"correct": all(r["ok"] for r in rows) and bool(rows),
            "compared": rows}
