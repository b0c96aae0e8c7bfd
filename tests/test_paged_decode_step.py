"""The paged decode kernels' grid step (ISSUE 28): every KV head of several
table entries a step; and their grid (ISSUE 37): a list of the steps that
hold a live token, none past a slot's length.

``ops/tuning.paged_decode_step`` reads a call's shapes and says how many KV
heads and table entries one grid step of ``flash_decode_paged`` /
``flash_decode_paged_q8q`` takes. The kernels here run in interpret mode at
tiny shapes, where a step never reaches the rule's byte target, so the rule
hands out the most entries that divide the table width: widths 5 and 7 take
one entry a step, 6 two, 12 four, 40 and 64 eight. Every case is held to
``ops/reference.py`` (a signed table to ``paged_local_partial``'s reference
route, which masks the blocks another shard owns), over contexts that end at
0, 1, a block's edge, a step's edge and ragged across slots, and to the bits
of the rectangular grid the kernels launched before the list
(``tests/paged_rectangle.py``, the old call kept for this). The list itself
(``paged_step_plan``) is held to the old body's ``live`` test as a function.
Whether the TPU's compiler takes the same kernels is
``tests/test_chip_compile.py``'s to say.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tests import paged_rectangle
from tree_attention_tpu.ops import pallas_decode, tuning
from tree_attention_tpu.ops.decode import gather_paged_kv, paged_local_partial
from tree_attention_tpu.ops.pallas_decode import (
    attention_pallas_decode,
    attention_pallas_decode_q8,
    attention_pallas_decode_q8q,
    paged_plan,
    paged_step_plan,
)
from tree_attention_tpu.ops.reference import attention_naive

D = 16


def _case(kind="f32", *, hkv=4, group=2, nb=40, blk=4, tq=1, tree=False,
          local=None, heads=None, per_channel=False):
    """One kernel case: ``kind`` f32 / bf16 / q8 / q8q; ``local`` "mixed"
    or "remote" (a signed table with remote entries inside live steps and
    whole remote steps; "remote" also has slots that own nothing);
    ``heads`` the heads a step must be cut to (by a lower VMEM ceiling);
    ``per_channel`` int8 with the contiguous kernels' ``(B, Hkv, 1, D)``
    scales instead of per-block scalars."""
    return dict(kind=kind, hkv=hkv, group=group, nb=nb, blk=blk, tq=tq,
                tree=tree, local=local, heads=heads, per_channel=per_channel)


CASES = {
    # KV heads 1 / 4 / 8 x table widths the entries divide and do not.
    **{f"heads{h}_width{nb}": _case(hkv=h, nb=nb, group=2 if h < 8 else 1)
       for h in (1, 4, 8) for nb in (5, 7, 40, 64)},
    "width6_two_entries": _case(nb=6),
    "width12_four_entries": _case(nb=12),
    "bf16_width40": _case("bf16", nb=40),
    # Packed rows: a ragged pack, a chunk's tail, more than one Q tile.
    "tq17_width5": _case(nb=5, tq=17),
    "tq17_width40": _case(nb=40, tq=17),
    "tq17_heads8_width64": _case(hkv=8, group=1, nb=64, tq=17),
    "tq64_width40": _case(nb=40, tq=64, blk=8),
    "tq64_heads1_width7": _case(hkv=1, nb=7, tq=64, blk=16),
    "tq64_two_q_tiles_width40": _case(nb=40, tq=64, group=4, blk=8),
    # Token trees (ancestor bitmasks in the window).
    "tree_tq8_width7": _case(nb=7, tq=8, tree=True),
    "tree_tq8_width40": _case(nb=40, tq=8, tree=True),
    "tree_tq17_width40": _case(nb=40, tq=17, tree=True),
    "tree_tq17_width12": _case(nb=12, tq=17, tree=True),
    "tree_tq8_heads8_width64": _case(hkv=8, group=1, nb=64, tq=8, tree=True),
    # The sequence-sharded pool's signed table.
    "local_mixed_width5": _case(nb=5, local="mixed"),
    "local_mixed_width6": _case(nb=6, local="mixed"),
    "local_mixed_width40": _case(nb=40, local="mixed"),
    "local_remote_width40": _case(nb=40, local="remote"),
    "local_remote_heads2_width64": _case(hkv=2, nb=64, local="remote"),
    "local_mixed_tq17_width40": _case(nb=40, tq=17, local="mixed"),
    # int8 pools, per-block scales that differ inside one step.
    **{f"{kind}_block_scales_width{nb}": _case(kind, nb=nb)
       for kind in ("q8", "q8q") for nb in (5, 12, 40, 64)},
    "q8_block_scales_tree_tq8_width40": _case("q8", nb=40, tq=8, tree=True),
    "q8q_block_scales_tree_tq8_width40": _case("q8q", nb=40, tq=8, tree=True),
    "q8q_block_scales_tq17_width40": _case("q8q", nb=40, tq=17),
    "q8_channel_scales_width40": _case("q8", nb=40, per_channel=True),
    "q8q_channel_scales_width40": _case("q8q", nb=40, per_channel=True),
    # More heads than fit a step: the rule cuts them into groups.
    "heads8_cut_to_2_width40": _case(hkv=8, group=1, nb=40, heads=2),
    "heads4_cut_to_1_width7_tq17": _case(nb=7, tq=17, heads=1),
    "heads8_cut_to_4_q8q_width64": _case("q8q", hkv=8, group=1, nb=64,
                                         heads=4),
    # The list over more than one head group and Q tile at once, with what
    # else indexes by the entry's slot or step: block scales, tree bits, a
    # signed table.
    "heads8_cut_to_2_q8_block_scales_tq17_width40": _case(
        "q8", hkv=8, group=1, nb=40, tq=17, heads=2),
    "heads4_cut_to_2_tree_tq8_width40": _case(nb=40, tq=8, tree=True,
                                              heads=2),
    "heads4_cut_to_2_local_remote_width40": _case(nb=40, local="remote",
                                                  heads=2),
    "heads4_cut_to_1_two_q_tiles_tq64_width12": _case(
        nb=12, tq=64, group=4, blk=8, heads=1),
    "bf16_chunk_tail_tq17_width64": _case("bf16", nb=64, tq=17),
}


def _entries(nb):
    return max(p for p in tuning.PAGED_STEP_ENTRIES if nb % p == 0)


def _offsets(nb, blk, tq, rng):
    """Where each slot's first query row sits: 0, 1, the last row of a
    block and the first of the next, the same at a step's edge, ragged, and
    the last position the capacity allows."""
    step = _entries(nb) * blk
    cap = nb * blk - tq
    fixed = [0, 1, blk - 1, blk, step - 1, step, 2 * step - 1]
    ragged = list(rng.integers(0, cap + 1, size=3))
    return np.minimum(np.array(fixed + ragged + [cap]), cap).astype(np.int32)


def _naive(q, k, v, offsets, tree_mask):
    """``attention_naive`` a slot at a time (it takes one offset a call)."""
    outs, lses = [], []
    for b, off in enumerate(np.asarray(offsets)):
        o, l = attention_naive(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], causal=True,
            q_offset=int(off),
            tree_mask=None if tree_mask is None else tree_mask[b:b + 1])
        outs.append(o)
        lses.append(l)
    return np.concatenate(outs).astype(np.float32), np.concatenate(lses)


def _rectangle(monkeypatch, fn, *args, **kw):
    """``fn`` (a paged kernel's wrapper) on the rectangular grid it launched
    before the list: every step of every slot's table."""
    with monkeypatch.context() as m:
        m.setattr(pallas_decode, "_paged_decode_call",
                  paged_rectangle._paged_decode_call)
        static = [n for n in ("causal", "local_blocks") if n in kw]
        return jax.jit(fn.__wrapped__, static_argnames=static)(*args, **kw)


def _same_bits(got, want):
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", sorted(CASES))
def test_paged_step_matches_reference(name, monkeypatch):
    c = CASES[name]
    kind, hkv, nb, blk, tq = c["kind"], c["hkv"], c["nb"], c["blk"], c["tq"]
    rng = np.random.default_rng(sorted(CASES).index(name))
    offsets = _offsets(nb, blk, tq, rng)
    B, hq = len(offsets), hkv * c["group"]
    n = B * nb + 3
    # Fragmented: each slot's blocks are scattered over the pool.
    table = rng.permutation(n)[:B * nb].reshape(B, nb).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(B, hq, tq, D)), jnp.float32)
    tree_mask = None
    if c["tree"]:
        tm = np.tril(rng.random((B, tq, tq)) < 0.6) | np.eye(tq, dtype=bool)
        tree_mask = jnp.asarray(tm)

    if c["heads"] is not None:
        # A ceiling under which only ``heads`` of the KV heads fit a step.
        entry = 2 * blk * D * (1 if kind in ("q8", "q8q") else 4)
        bq = min(-(-c["group"] * tq // 8) * 8, 128)
        rows = bq * 4 * (5 * D + 8 * 128)
        monkeypatch.setattr(tuning, "PAGED_STEP_VMEM_BYTES",
                            c["heads"] * (2 * entry + rows))
        assert tuning.paged_decode_step(
            hkv, blk, D, entry // (2 * blk * D), nb, bq)[0] == c["heads"]

    if kind in ("q8", "q8q"):
        fn = (attention_pallas_decode_q8 if kind == "q8"
              else attention_pallas_decode_q8q)
        k_q = rng.integers(-127, 128, size=(n, hkv, blk, D)).astype(np.int8)
        v_q = rng.integers(-127, 128, size=(n, hkv, blk, D)).astype(np.int8)
        if c["per_channel"]:
            ks = rng.uniform(0.005, 0.03, (B, hkv, 1, D)).astype(np.float32)
            vs = rng.uniform(0.005, 0.03, (B, hkv, 1, D)).astype(np.float32)
            kg, vg = gather_paged_kv(jnp.asarray(k_q), jnp.asarray(v_q),
                                     jnp.asarray(table))
            k_ref = kg.astype(jnp.float32) * ks
            v_ref = vg.astype(jnp.float32) * vs
        else:
            # One scalar a block a head, a sixfold range: neighbours in a
            # step differ, so a scale read from the wrong row shows.
            ks = rng.uniform(0.005, 0.03, (n, hkv)).astype(np.float32)
            vs = rng.uniform(0.005, 0.03, (n, hkv)).astype(np.float32)
            k_ref, v_ref = gather_paged_kv(
                jnp.asarray(k_q * ks[:, :, None, None]),
                jnp.asarray(v_q * vs[:, :, None, None]), jnp.asarray(table))
        args = (q, jnp.asarray(k_q), jnp.asarray(v_q), jnp.asarray(ks),
                jnp.asarray(vs))
        kw = dict(causal=True, q_offset=offsets,
                  block_table=jnp.asarray(table), tree_mask=tree_mask)
        out, lse = fn(*args, **kw)
        _same_bits((out, lse), _rectangle(monkeypatch, fn, *args, **kw))
        ref_o, ref_l = _naive(q, k_ref, v_ref, offsets, tree_mask)
        # int8 resolution (q8q rounds the queries to int8 too).
        tol = dict(atol=6e-2, rtol=6e-2)
        np.testing.assert_allclose(np.asarray(out, np.float32), ref_o, **tol)
        np.testing.assert_allclose(np.asarray(lse), ref_l, **tol)
        return

    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    q = q.astype(dtype)
    pool_k = jnp.asarray(rng.normal(size=(n, hkv, blk, D)), dtype)
    pool_v = jnp.asarray(rng.normal(size=(n, hkv, blk, D)), dtype)
    tol = dict(atol=3e-2, rtol=3e-2) if kind == "bf16" \
        else dict(atol=1e-5, rtol=1e-5)

    if c["local"] is not None:
        per = _entries(nb)
        signed = table.copy()
        signed[:, 1::3] = -1                 # remote entries inside steps
        signed[2, :per] = -1                 # a slot's first step all remote
        signed[5, per:2 * per] = -1          # a middle step all remote
        if c["local"] == "remote":
            signed[3, :] = -1                # slots that own nothing
            signed[-1, :] = -1
        ref_o, ref_l = paged_local_partial(
            q, pool_k, pool_v, jnp.asarray(signed), q_position=offsets)
        kw = dict(causal=True, q_offset=offsets,
                  block_table=jnp.asarray(signed), local_blocks=True)
        out, lse = attention_pallas_decode(q, pool_k, pool_v, **kw)
        _same_bits((out, lse), _rectangle(
            monkeypatch, attention_pallas_decode, q, pool_k, pool_v, **kw))
        ref_l, lse = np.asarray(ref_l), np.asarray(lse)
        empty = np.isneginf(ref_l)
        if c["local"] == "remote":
            assert empty[3].all() and empty[-1].all()
        # Rows with no local key: the merge identity, exactly.
        assert (np.isneginf(lse) == empty).all()
        assert (np.asarray(out)[empty] == 0).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o), **tol)
        np.testing.assert_allclose(lse[~empty], ref_l[~empty], **tol)
        return

    kw = dict(causal=True, q_offset=offsets, block_table=jnp.asarray(table),
              tree_mask=tree_mask)
    out, lse = attention_pallas_decode(q, pool_k, pool_v, **kw)
    _same_bits((out, lse), _rectangle(
        monkeypatch, attention_pallas_decode, q, pool_k, pool_v, **kw))
    kg, vg = gather_paged_kv(pool_k, pool_v, jnp.asarray(table))
    ref_o, ref_l = _naive(q, kg, vg, offsets, tree_mask)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref_o, **tol)
    np.testing.assert_allclose(np.asarray(lse), ref_l, **tol)


# (KV heads, block, head dim, bytes a value, table width, packed rows a Q
# tile) -> (heads, entries) a step.
RULE = {
    # The benchmark's cells: 1 MB of K + V a step.
    "mistral7b_bf16_decode": ((8, 64, 128, 2, 40, 8), (8, 4)),
    "yi6b_bf16_decode": ((4, 64, 128, 2, 64, 8), (4, 8)),
    # int8 halves an entry's bytes: twice the entries, where they divide.
    "mistral7b_int8_decode": ((8, 64, 128, 1, 40, 8), (8, 8)),
    "yi6b_int8_decode": ((4, 64, 128, 1, 64, 8), (4, 8)),
    # A chunk's tail at 128 packed rows a head: the heads' state still fits.
    "mistral7b_bf16_chunk_rows": ((8, 64, 128, 2, 40, 128), (8, 4)),
    "yi6b_bf16_chunk_rows": ((4, 64, 128, 2, 64, 128), (4, 8)),
    # Table widths the entries do not divide.
    "smoke_width33": ((4, 64, 128, 2, 33, 8), (4, 1)),
    "width6": ((4, 64, 128, 2, 6, 8), (4, 2)),
    "width12": ((4, 64, 128, 2, 12, 8), (4, 4)),
    # 32 KV heads: a block is 1 MB already; at 128 rows a head their state
    # does not fit, and the heads are cut.
    "mha32_decode": ((32, 64, 128, 2, 64, 8), (32, 1)),
    "mha32_chunk_rows": ((32, 64, 128, 2, 64, 128), (8, 4)),
    # A block that is itself past the target.
    "block1024": ((8, 1024, 128, 2, 16, 8), (8, 1)),
}


@pytest.mark.parametrize("name", sorted(RULE))
def test_step_rule(name):
    args, want = RULE[name]
    heads, entries = tuning.paged_decode_step(*args)
    assert (heads, entries) == want
    hkv, block, d, itemsize, nb, bq = args
    assert hkv % heads == 0 and nb % entries == 0
    assert entries in tuning.PAGED_STEP_ENTRIES and 8 % entries == 0
    # Double-buffered K and V of the step, under the stated ceiling.
    assert 2 * heads * entries * 2 * block * d * itemsize \
        <= tuning.PAGED_STEP_VMEM_BYTES


def test_builds_counter_says_what_a_step_takes():
    """The registry's line for a paged build carries the tiling."""
    from tree_attention_tpu import obs

    was = obs.REGISTRY.enabled
    obs.REGISTRY.enable()
    try:
        rng = np.random.default_rng(0)
        pool = jnp.asarray(rng.normal(size=(13, 2, 4, D)), jnp.float32)
        # A shape no other test of this process builds (the wrappers are
        # jitted: a cached program counts nothing).
        q = jnp.asarray(rng.normal(size=(3, 6, 1, D)), jnp.float32)
        table = jnp.asarray(rng.permutation(13)[:12].reshape(3, 4), jnp.int32)
        attention_pallas_decode(q, pool, pool, causal=True,
                                q_offset=jnp.asarray([0, 7, 15], jnp.int32),
                                block_table=table)
        text = obs.REGISTRY.to_prometheus()
    finally:
        if not was:
            obs.REGISTRY.disable()
    line = [l for l in text.splitlines()
            if l.startswith("pallas_decode_kernel_builds_total{")
            and 'kernel="paged"' in l and 'entries="4"' in l]
    assert line and 'heads="2"' in line[0], text


# -- the work list (ISSUE 37) -------------------------------------------------


def _old_live(q_off, kv_off, tq, bk, n_steps):
    """Steps the rectangle's body computed for a slot: its ``live`` test."""
    return [si for si in range(n_steps)
            if kv_off + si * bk <= q_off + tq - 1]


def _check_plan(q_off, kv_off, tq, bk, n_steps):
    B = len(q_off)
    live = tuning.paged_live_steps(
        np.asarray(q_off), np.asarray(kv_off), tq, bk, n_steps)
    slot, step, flags, count = (np.asarray(a) for a in paged_step_plan(
        jnp.asarray(live), n_steps))
    # Static capacity: the rectangle, which every slot full fills.
    assert slot.shape == step.shape == flags.shape == (B * n_steps,)
    want = []
    for b in range(B):
        steps = _old_live(int(q_off[b]), int(kv_off[b]), tq, bk, n_steps)
        assert len(steps) == live[b]
        assert steps == list(range(len(steps)))     # a prefix of the table
        # A slot with nothing to attend to still gets an entry: its rows
        # are initialised and written out, and nothing is computed.
        for si in steps or [0]:
            want.append((b, si,
                         (si == 0) * pallas_decode._PLAN_FIRST
                         | (si == max(len(steps), 1) - 1)
                         * pallas_decode._PLAN_LAST
                         | bool(steps) * pallas_decode._PLAN_LIVE))
    assert count == len(want) and B <= count <= B * n_steps
    got = list(zip(slot[:count], step[:count], flags[:count]))
    assert got == want          # slots in order, a slot's steps in order
    return int(count)


@pytest.mark.parametrize("seed", range(8))
def test_plan_holds_the_steps_the_live_test_kept(seed):
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 17))
    n_steps = int(rng.integers(1, 12))
    bk = int(rng.choice([4, 32, 256]))
    tq = int(rng.choice([1, 8, 17, 64]))
    cap = n_steps * bk
    q_off = rng.integers(0, max(cap - tq, 0) + 1, size=B)
    # Mostly the serving shape (kv_offset 0); some slots whose pool starts
    # past their rows, which see nothing.
    kv_off = np.where(rng.random(B) < 0.2, rng.integers(0, cap, size=B), 0)
    _check_plan(q_off, kv_off, tq, bk, n_steps)


PLAN_EDGES = {
    # (q_offset, tq, step tokens, steps of the table) -> entries of a slot
    "length_0": ((0, 1, 256, 10), 1),
    "last_row_of_a_step": ((255, 1, 256, 10), 1),
    "first_row_of_the_next": ((256, 1, 256, 10), 2),
    "chunk_tail_reaches_the_edge": ((240, 17, 256, 10), 2),
    "chunk_tail_stops_short": ((239, 17, 256, 10), 1),
    "full_capacity": ((2559, 1, 256, 10), 10),
    "one_step_table": ((5, 1, 64, 1), 1),
}


@pytest.mark.parametrize("name", sorted(PLAN_EDGES))
def test_plan_at_the_edges(name):
    (q_off, tq, bk, n_steps), want = PLAN_EDGES[name]
    assert _check_plan([q_off], [0], tq, bk, n_steps) == want
    # Beside a full slot and an empty one the count is the sum.
    assert _check_plan([n_steps * bk - tq, q_off, 0], [0, 0, 0], tq, bk,
                       n_steps) == n_steps + want + 1


def test_full_slots_are_the_rectangle_and_the_table_lies_in_list_order():
    rng = np.random.default_rng(0)
    B, NB, entries, blk = 5, 12, 4, 8
    table = jnp.asarray(rng.permutation(B * NB).reshape(B, NB), jnp.int32)
    full = paged_plan(jnp.full((B,), NB * blk - 1, jnp.int32), 0, table,
                      tq=1, entries=entries, block=blk)
    assert int(full.count) == B * NB // entries
    np.testing.assert_array_equal(full.table, np.asarray(table).reshape(-1))
    np.testing.assert_array_equal(
        full.slot, np.repeat(np.arange(B), NB // entries))
    # Ragged: entry e holds its slot's blocks step * entries ...; a layer's
    # shift moves the table and nothing else.
    pos = jnp.asarray([0, 31, 32, 70, 95], jnp.int32)
    plan = paged_plan(pos, 0, table, tq=1, entries=entries, block=blk)
    assert int(plan.count) == 1 + 1 + 2 + 3 + 3
    for e in range(int(plan.count)):
        b, si = int(plan.slot[e]), int(plan.step[e])
        np.testing.assert_array_equal(
            plan.table[e * entries:(e + 1) * entries],
            np.asarray(table)[b, si * entries:(si + 1) * entries])
    moved = plan.shifted(1000)
    np.testing.assert_array_equal(moved.table, np.asarray(plan.table) + 1000)
    assert all(a is b for a, b in zip(moved[2:], plan[2:]))
    # Not causal: nothing is past a slot's length.
    assert int(paged_plan(pos, 0, table, tq=1, entries=entries, block=blk,
                          causal=False).count) == B * NB // entries


def test_a_plan_handed_in_is_the_plan_built_in_the_call():
    """What a step program does: one plan a group of rows, shifted with the
    table to each layer's blocks. A plan of another call's shapes is
    refused."""
    rng = np.random.default_rng(1)
    B, hkv, nb, blk, layers = 6, 2, 12, 4, 3
    n = B * nb
    pool = jnp.asarray(rng.normal(size=(layers * n, hkv, blk, D)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 2 * hkv, 1, D)), jnp.float32)
    table = jnp.asarray(rng.permutation(n).reshape(B, nb), jnp.int32)
    pos = jnp.asarray(rng.integers(0, nb * blk, size=B), jnp.int32)
    plan = pallas_decode.decode_plan(2 * hkv, 1, pool, table, pos)
    for l in range(layers):
        kw = dict(causal=True, q_offset=pos, block_table=l * n + table)
        _same_bits(
            attention_pallas_decode(q, pool, pool, **kw,
                                    step_plan=plan.shifted(l * n)),
            attention_pallas_decode(q, pool, pool, **kw))
    with pytest.raises(ValueError, match="a plan for 6 slots of 12 blocks"):
        attention_pallas_decode(
            q[:3], pool, pool, causal=True, q_offset=pos[:3],
            block_table=table[:3], step_plan=plan)
