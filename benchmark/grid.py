"""The one general traffic generator: blocks that permute a fixed grid.

A traffic file gives grids of prompt lengths, output lengths and gaps. A
block is one pass through the grids; the seed shuffles each block's prompts,
outputs and gaps independently and draws the token ids. So every seed offers
the same multiset of lengths, the same total of prompt and output tokens and
the same mean rate in every block, in another order (PR 22 drew lengths per
seed and its tokens per second followed the draw). A grid may be written in
balanced groups, which keeps the work even inside a block too.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class Shape:
    """One request before it has token ids: lengths and the gap, in units of
    the mean gap, that separates it from the request before it."""

    index: int
    prompt: int
    output: int
    gap: float


def _groups(grid_: List[Any]) -> List[List[Any]]:
    """A grid is a list of groups; a flat list is one group."""
    if grid_ and isinstance(grid_[0], list):
        return [list(g) for g in grid_]
    return [list(grid_)]


def values(grid_: List[Any]) -> List[Any]:
    """Every value of a grid, grouped or flat."""
    return [x for g in _groups(grid_) for x in g]


def _fit(groups: List[List[Any]], n_groups: int, size: int,
         what: str) -> List[List[Any]]:
    """Repeat a smaller grid until it fills ``n_groups`` groups of ``size``."""
    if len(groups) == 1 and n_groups > 1:
        flat = groups[0]
        if len(flat) == n_groups * size:     # cut in consecutive groups
            groups = [flat[i * size:(i + 1) * size] for i in range(n_groups)]
        elif size % len(flat):
            raise ValueError(f"a flat grid of {len(flat)} {what} fits "
                             f"neither {size} nor {n_groups} x {size}")
    out = []
    for g in groups:
        if size % len(g):
            raise ValueError(
                f"a group of {len(g)} {what} does not divide {size}")
        out.append(g * (size // len(g)))
    if n_groups % len(out):
        raise ValueError(
            f"{len(out)} groups of {what} do not divide {n_groups}")
    return out * (n_groups // len(out))


def _layout(traffic: Dict[str, Any]):
    grids = {k: _groups(traffic.get(k, [1.0]))
             for k in ("prompts", "outputs", "gaps")}
    n_groups = max(len(g) for g in grids.values())
    size = max(len(g[0]) for g in grids.values())
    return {k: _fit(g, n_groups, size, k) for k, g in grids.items()}, \
        n_groups, size


def block_size(traffic: Dict[str, Any]) -> int:
    _, n_groups, size = _layout(traffic)
    return n_groups * size


def shapes(traffic: Dict[str, Any], seed: int) -> Iterator[Shape]:
    """Endless requests: block after block of the permuted grids. Where a
    grid is written in groups (of equal size, balanced so that each group
    carries about the same work), the seed shuffles the order of the groups
    and the order inside each, independently for prompts, outputs and gaps:
    every run of ``size`` requests then holds one group of each."""
    grids, n_groups, size = _layout(traffic)
    rng = np.random.default_rng([int(seed), 0x67726964])
    index = 0

    def one_pass(groups):
        return [groups[g][i] for g in rng.permutation(n_groups)
                for i in rng.permutation(size)]

    while True:
        p = one_pass(grids["prompts"])
        o = one_pass(grids["outputs"])
        g = one_pass(grids["gaps"])
        for i in range(n_groups * size):
            yield Shape(index, int(p[i]), int(o[i]), float(g[i]))
            index += 1


def balanced_groups(values: List[Any], size: int) -> List[List[Any]]:
    """Deal sorted values into groups of ``size`` back and forth, so that the
    groups' sums come out close: how a mix's grouped grids were written."""
    n = len(values) // size
    v = sorted(values, reverse=True)
    groups: List[List[Any]] = [[] for _ in range(n)]
    for r in range(size):
        row = v[r * n:(r + 1) * n]
        for i, x in enumerate(row[::-1] if r % 2 else row):
            groups[i].append(x)
    return groups


def midlife(shape: Shape, k: int, of: int, multiple: int) -> Shape:
    """Request ``k`` of the ``of`` that the window catches mid-life: ``k/of``
    of its output is behind it, so its prompt is lengthened by that many
    tokens (rounded down to ``multiple``, which keeps the chunk buckets the
    grid's own) and its output cut by the same. Retirements then spread over
    the first requests' lives and slots do not start, prefill and retire in
    lockstep."""
    done = (shape.output * k // of) // multiple * multiple
    done = min(done, shape.output - 1)
    return Shape(shape.index, shape.prompt + done, shape.output - done,
                 shape.gap)


def token_ids(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """Random ids for request ``index``: no shared prefix between requests."""
    rng = np.random.default_rng([int(seed), 0x746f6b73, int(index)])
    return rng.integers(0, vocab, size=n, dtype=np.int32)


def exponential_quantiles(n: int) -> List[float]:
    """``n`` mid-quantiles of a unit exponential, scaled to mean exactly 1:
    the numbers kept in a paced mix's ``gaps``."""
    q = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return [float(x) for x in q / q.mean()]
