"""One jit around a sharded call, for the tests that compare it with an
unsharded oracle.

Called eagerly, ``tree_attention`` under ``shard_map`` on the 8 virtual CPU
devices is dispatched primitive by primitive, every chunk of every shard a
launch of its own: 20-50 times slower than the one program a user's jitted
step runs (62 s against 3 s for one fuzz case, alone on this machine), and
slower still beside five other workers. The numbers compared are the same.
"""

import functools

import jax


def jitted(fn):
    """``fn(*arrays, **static)`` as one compiled program a call; keyword
    arguments (mesh, layout, chunk sizes, positions) are closed over."""

    @functools.wraps(fn)
    def call(*args, **kw):
        return jax.jit(functools.partial(fn, **kw))(*args)

    return call
