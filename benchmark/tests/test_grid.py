"""The grid generator: every seed the same work per block, another order."""

import collections
import itertools
import json
import os

import pytest

from benchmark import grid

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def load(mix):
    with open(os.path.join(TRAFFIC, mix + ".json")) as f:
        return json.load(f)


def blocks(traffic, seed, n_blocks):
    n = grid.block_size(traffic)
    shapes = list(itertools.islice(grid.shapes(traffic, seed), n * n_blocks))
    return [shapes[i * n:(i + 1) * n] for i in range(n_blocks)]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work_in_every_block(mix):
    traffic = load(mix)
    seen = []
    for seed in (0, 7, 2 ** 31 + 11):
        for block in blocks(traffic, seed, 3):
            seen.append((
                collections.Counter(s.prompt for s in block),
                collections.Counter(s.output for s in block),
                collections.Counter(s.gap for s in block),
                sum(s.prompt for s in block), sum(s.output for s in block),
                round(sum(s.gap for s in block), 9),
            ))
    assert all(s == seen[0] for s in seen)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_in_order_and_in_pairing(mix):
    traffic = load(mix)
    a, b = blocks(traffic, 1, 1)[0], blocks(traffic, 2, 1)[0]
    assert [s.prompt for s in a] != [s.prompt for s in b]
    assert [(s.prompt, s.output) for s in a] != \
        [(s.prompt, s.output) for s in b]
    again = blocks(traffic, 1, 1)[0]
    assert a == again            # the same seed, the same inputs


def test_paced_gaps_have_mean_one_so_the_rate_is_the_files():
    traffic = load("chat_paced")
    gaps = sorted(x for g in traffic["gaps"] for x in g)
    assert abs(sum(gaps) / len(gaps) - 1.0) < 1e-5
    assert gaps == pytest.approx(grid.exponential_quantiles(len(gaps)),
                                 abs=1e-6)
    # Arrivals of one block take block / rate seconds whatever the seed.
    for seed in (3, 4):
        block = blocks(traffic, seed, 2)[1]
        assert sum(s.gap for s in block) / traffic["rate_per_s"] == \
            pytest.approx(len(block) / traffic["rate_per_s"], rel=1e-5)


def test_token_ids_depend_on_seed_and_request_and_fit_the_vocabulary():
    a = grid.token_ids(5, 0, 64, 1000)
    assert (a == grid.token_ids(5, 0, 64, 1000)).all()
    assert (a != grid.token_ids(5, 1, 64, 1000)).any()
    assert (a != grid.token_ids(6, 0, 64, 1000)).any()
    assert a.min() >= 0 and a.max() < 1000
    assert grid.token_ids(2 ** 31 + 5, 3, 8, 50).shape == (8,)


def test_midlife_keeps_the_total_and_spreads_what_is_left():
    shape = grid.Shape(0, 512, 384, 1.0)
    left = []
    for k in range(8):
        m = grid.midlife(shape, k, 8, 128)
        assert m.prompt + m.output == shape.prompt + shape.output
        assert (m.prompt - shape.prompt) % 128 == 0 and m.output >= 1
        left.append(m.output)
    assert left[0] == 384 and len(set(left)) >= 3 and left == sorted(
        left, reverse=True)
    # A one-token answer cannot be cut to nothing.
    assert grid.midlife(grid.Shape(0, 8, 1, 1.0), 7, 8, 1).output == 1


@pytest.mark.parametrize("mix", [m for m in MIXES if "backlog" in m])
def test_midlife_start_spreads_retirements_over_the_first_lives(mix):
    """Slots that all start together retire in the order of their outputs
    only; caught mid-life, request k has k/S of its output behind it, so
    what is left to emit differs from the plain outputs and is spread."""
    traffic = load(mix)
    n = 8
    plain = list(itertools.islice(grid.shapes(traffic, 9), n))
    caught = [grid.midlife(s, k, n, traffic["midlife_multiple"])
              for k, s in enumerate(plain)]
    assert sum(c.output for c in caught) < sum(s.output for s in plain)
    assert len({c.output for c in caught}) >= n // 2
    assert [c.output for c in caught] != [s.output for s in plain]


def test_grouped_grids_put_one_group_of_each_in_every_run_of_its_size():
    traffic = load("chat_backlog")
    groups = [sorted(g) for g in traffic["prompts"]]
    assert traffic["prompts"] == grid.balanced_groups(
        [p for g in traffic["prompts"] for p in g], 4)
    sums = [sum(g) for g in groups]
    assert max(sums) - min(sums) <= 0.1 * max(sums)
    block = blocks(traffic, 11, 1)[0]
    for i in range(0, 16, 4):
        assert sorted(s.prompt for s in block[i:i + 4]) in groups
    with pytest.raises(ValueError):
        next(grid.shapes({"prompts": [[1, 2], [3, 4]], "outputs": [1, 2, 3]},
                         0))
    flat = list(itertools.islice(
        grid.shapes({"prompts": [[1, 2], [3, 4]], "outputs": [5, 6, 7, 8]},
                    0), 4))
    assert sorted(s.output for s in flat) == [5, 6, 7, 8]
