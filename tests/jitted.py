"""One jit around a call, for the tests that compare it with an oracle: a
sharded call against an unsharded one, and the model's step programs at the
engine's shapes.

Called eagerly, ``tree_attention`` under ``shard_map`` on the 8 virtual CPU
devices is dispatched primitive by primitive, every chunk of every shard a
launch of its own: 20-50 times slower than the one program a user's jitted
step runs (62 s against 3 s for one fuzz case, alone on this machine), and
slower still beside five other workers. The numbers compared are the same.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tree_attention_tpu.models.decode import forward_packed_step, forward_step


def jitted(fn):
    """``fn(*arrays, **static)`` as one compiled program a call; keyword
    arguments (mesh, layout, chunk sizes, positions) are closed over."""

    @functools.wraps(fn)
    def call(*args, **kw):
        return jax.jit(functools.partial(fn, **kw))(*args)

    return call


# -- the step programs at the engine's shapes --------------------------------
#
# ``forward_step`` and ``forward_packed_step`` are not jitted themselves:
# called eagerly with ``Tq = max(rows)`` of every step, a test that walks a
# small model through steps of 12, 10, 9 and 6 rows traces and compiles every
# scan, every interpreted kernel and every primitive at four token widths for
# one assertion (110 s of one case under six workers). The engine never does
# that: a tick's chunk is one of its few static widths and the true lengths
# ride in ``n_tokens`` / ``chunk_n``. So do the tests: a file pads every step's
# token block to ONE chunk width (``step_width``; a decode step is width 1)
# and runs it through the programs below, compiled once a (model, width) and
# kept until ``conftest.py`` clears JAX's caches at the module's end.


@functools.partial(jax.jit, static_argnames=("cfg",))
def step_stats(params, tokens, cache, n_tokens, cfg):
    """``forward_step`` on ``(slots, width)`` tokens of which slot ``i``'s
    first ``n_tokens[i]`` are real: ``(logits, cache, the step's counters)``."""
    stats = {}
    logits, cache = forward_step(params, tokens, cache, cfg,
                                 n_tokens=n_tokens, stats=stats)
    return logits, cache, stats


@functools.partial(jax.jit, static_argnames=("cfg",))
def packed_step_stats(params, chunk_tokens, chunk_slot, chunk_n, tokens,
                      n_tokens, cache, cfg):
    """``forward_packed_step``: a chunk group of ``(members, width)`` tokens
    beside one decode row a slot: ``(logits, cache, the step's counters)``."""
    stats = {}
    logits, cache = forward_packed_step(
        params, chunk_tokens, chunk_slot, chunk_n, tokens, n_tokens, cache,
        cfg, stats=stats)
    return logits, cache, stats


def step(*args):
    """``step_stats`` (the same compiled program) without the counters."""
    return step_stats(*args)[:2]


def packed_step(*args):
    """``packed_step_stats`` without the counters."""
    return packed_step_stats(*args)[:2]


def step_width(ns, chunk):
    """The token width of a step of ``ns`` rows a slot: 1 for a decode step,
    else the file's one chunk width."""
    assert max(ns) <= chunk, (ns, chunk)
    return 1 if max(ns) <= 1 else chunk


def padded_rows(toks, pos, ns, width):
    """``(slots, width)`` int32: slot ``i``'s rows ``toks[i][pos[i]:pos[i] +
    ns[i]]`` at the left, zeros after them (the step reads none of those:
    ``n_tokens`` says where they start)."""
    t = np.zeros((len(ns), width), np.int32)
    for i, n in enumerate(ns):
        t[i, :n] = toks[i][pos[i]:pos[i] + n]
    return t


def serve_step_stats(params, cfg, cache, toks, pos, ns, chunk, packed=False):
    """One step of ``ns`` rows a slot, slot ``i``'s from ``toks[i][pos[i]]``
    on, at the width ``step_width`` gives it: ``[(slot, row, logits)]`` for
    the rows the step returns logits for, the cache, and the step's
    counters. ``packed``: the slot with most rows is the one member of the
    chunk group, the others ride the decode group (a row or none each), and
    a slot's last row alone comes back, as from a tick."""
    slots, tq = len(ns), step_width(ns, chunk)
    if not packed:
        logits, cache, stats = step_stats(
            params, jnp.asarray(padded_rows(toks, pos, ns, tq)), cache,
            jnp.asarray(ns, jnp.int32), cfg)
        logits = np.asarray(logits)
        return [(i, pos[i] + j, logits[i, j])
                for i, n in enumerate(ns) for j in range(n)], cache, stats
    c = int(np.argmax(ns))
    assert all(n <= 1 for i, n in enumerate(ns) if i != c), ns
    dec = [toks[i][pos[i]] if i != c and ns[i] else 0 for i in range(slots)]
    dn = [int(i != c and ns[i] > 0) for i in range(slots)]
    logits, cache, stats = packed_step_stats(
        params, jnp.asarray(padded_rows([toks[c]], [pos[c]], [ns[c]], tq)),
        jnp.asarray([c], jnp.int32), jnp.asarray([ns[c]], jnp.int32),
        jnp.asarray(dec, jnp.int32), jnp.asarray(dn, jnp.int32), cache, cfg)
    logits = np.asarray(logits)
    return [(i, pos[i] + n - 1, logits[i])
            for i, n in enumerate(ns) if n], cache, stats


def serve_step(*args, **kw):
    """``serve_step_stats`` without the counters."""
    return serve_step_stats(*args, **kw)[:2]
