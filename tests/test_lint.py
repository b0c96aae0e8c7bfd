"""The invariant linter (tools/lint.py + tools/lintlib).

Fixture-level contracts per pass — a known-bad snippet fires, the
matching known-good idiom (lifted from the real call sites) stays clean,
and the ``# lint: allow[rule] reason`` grammar suppresses — plus the
package-wide runs: the WHOLE repo is lint-clean against an EMPTY
baseline, and the runner exits nonzero the moment a new violation
appears.

Pure AST: importing tools.lintlib (and this file) must not import jax —
pinned by a test, and what keeps the suite's share of the tier-1 budget
in the milliseconds.
"""

from __future__ import annotations

import json
import os
import sys

from tools import lintlib
from tools.lint import main as lint_main

ENGINE = "tree_attention_tpu/serving/engine.py"
OPS_DECODE = "tree_attention_tpu/ops/decode.py"
PALLAS = "tree_attention_tpu/ops/pallas_decode.py"
OBS_FLIGHT = "tree_attention_tpu/obs/flight.py"
INGRESS = "tree_attention_tpu/serving/ingress.py"
DISAGG = "tree_attention_tpu/serving/disagg.py"
HOST_POOL = "tree_attention_tpu/serving/host_pool.py"


def run(rule, text, path=ENGINE):
    return lintlib.run_source(rule, text, path)


def messages(findings):
    return [f.message for f in findings]


# ---------------------------------------------------------------------------
# obs-guard


class TestObsGuard:
    def test_unguarded_instant_args_flagged(self):
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "def f(x):\n"
            "    obs.instant('evt', cat='serving', args={'x': x})\n"
        ))
        assert len(fs) == 1 and "TRACER.active" in fs[0].message

    def test_guarded_instant_clean(self):
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "def f(x):\n"
            "    if obs.TRACER.active:\n"
            "        obs.instant('evt', cat='serving', args={'x': x})\n"
        ))
        assert fs == []

    def test_span_args_ifexp_idiom_clean(self):
        # The repo's canonical form: allocation only on the else branch.
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "def f(tick):\n"
            "    with obs.span('serving:tick', cat='serving',\n"
            "                  args=None if not obs.TRACER.active else\n"
            "                  {'tick': tick}):\n"
            "        pass\n"
        ))
        assert fs == []

    def test_span_args_dict_unguarded_flagged(self):
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "def f(tick):\n"
            "    with obs.span('t', cat='serving', args={'tick': tick}):\n"
            "        pass\n"
        ))
        assert len(fs) == 1

    def test_labels_chain_needs_guard(self):
        base = (
            "from tree_attention_tpu import obs\n"
            "_REQS = obs.counter('reqs_total', 'h', labels=('outcome',))\n"
            "def f(outcome):\n"
            "{body}"
        )
        bad = base.format(
            body="    _REQS.labels(outcome=outcome).inc()\n")
        good = base.format(body=(
            "    if obs.REGISTRY.enabled:\n"
            "        _REQS.labels(outcome=outcome).inc()\n"))
        assert len(run("obs-guard", bad)) == 1
        assert run("obs-guard", good) == []

    def test_bare_inc_is_free_when_disabled(self):
        # metrics.py's documented unconditional-record design: no
        # allocation before the internal flag check.
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "_T = obs.counter('toks_total', 'h')\n"
            "def f(n):\n"
            "    _T.inc()\n"
            "    _T.inc(n * 4)\n"
        ))
        assert fs == []

    def test_early_return_guard_dominates(self):
        # ops/decode.py:_account_dispatch shape.
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "_D = obs.counter('d_total', 'h', labels=('path',))\n"
            "def account(path):\n"
            "    if not obs.REGISTRY.enabled:\n"
            "        return\n"
            "    _D.labels(path=path).inc()\n"
        ))
        assert fs == []

    def test_or_combined_guard_accepted(self):
        # cli.py's crash-handler arm: any instrument on => not the
        # disabled path, allocation is paid by an enabled run.
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "_T = obs.counter('t_total', 'h', labels=('k',))\n"
            "def f(k):\n"
            "    if obs.REGISTRY.enabled or obs.TRACER.active:\n"
            "        _T.labels(k=k).inc()\n"
        ))
        assert fs == []

    def test_flight_record_guarded_vs_not(self):
        base = (
            "from tree_attention_tpu.obs.flight import FLIGHT\n"
            "def tick(n):\n"
            "{body}"
        )
        bad = base.format(body="    FLIGHT.record({'tick': n})\n")
        good = base.format(body=(
            "    if FLIGHT.enabled:\n"
            "        FLIGHT.record({'tick': n})\n"))
        assert len(run("obs-guard", bad)) == 1
        assert run("obs-guard", good) == []

    def test_span_set_needs_tracer_guard(self):
        base = (
            "from tree_attention_tpu import obs\n"
            "def f(tok):\n"
            "    tick_span = obs.span('t', cat='serving')\n"
            "    with tick_span:\n"
            "{body}"
        )
        bad = base.format(body="        tick_span.set(tokens=tok)\n")
        good = base.format(body=(
            "        if obs.TRACER.active:\n"
            "            tick_span.set(tokens=tok)\n"))
        assert len(run("obs-guard", bad)) == 1
        assert run("obs-guard", good) == []

    def test_or_with_non_guard_disjunct_rejected(self):
        # Review finding: `REGISTRY.enabled or DEBUG` runs with all
        # telemetry off whenever DEBUG is true — it guards nothing.
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "DEBUG = True\n"
            "_T = obs.counter('t_total', 'h', labels=('k',))\n"
            "def f(k):\n"
            "    if obs.REGISTRY.enabled or DEBUG:\n"
            "        _T.labels(k=k).inc()\n"
        ))
        assert len(fs) == 1

    def test_and_with_non_guard_operand_still_guards(self):
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "_T = obs.counter('t_total', 'h', labels=('k',))\n"
            "def f(k, m):\n"
            "    if obs.REGISTRY.enabled and m:\n"
            "        _T.labels(k=k).inc()\n"
        ))
        assert fs == []

    def test_match_case_bodies_are_walked(self):
        # Review finding: ast.Match case bodies are stmt lists, not
        # exprs — the walker must descend or emissions hide under match.
        base = (
            "from tree_attention_tpu import obs\n"
            "_T = obs.counter('t_total', 'h', labels=('k',))\n"
            "def f(mode, k):\n"
            "    match mode:\n"
            "        case 1:\n"
            "{body}"
        )
        bad = base.format(body="            _T.labels(k=k).inc()\n")
        good = base.format(body=(
            "            if obs.REGISTRY.enabled:\n"
            "                _T.labels(k=k).inc()\n"))
        assert len(run("obs-guard", bad)) == 1
        assert run("obs-guard", good) == []

    def test_obs_internals_out_of_scope(self):
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "def f(x):\n"
            "    obs.instant('evt', cat='x', args={'x': x})\n"
        ), path=OBS_FLIGHT)
        assert fs == []

    def test_unguarded_reqlog_seam_flagged(self):
        # ISSUE 16: ledger accumulation rides the engine's hot seams —
        # same machine-checked discipline as counters and spans.
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "def retire(uid, n):\n"
            "    obs.REQLOG.finish(uid, outcome='completed', tick=n)\n"
        ))
        assert len(fs) == 1 and "REQLOG.finish" in fs[0].message

    def test_guarded_reqlog_seam_clean(self):
        fs = run("obs-guard", (
            "from tree_attention_tpu import obs\n"
            "def retire(uid, n):\n"
            "    if obs.REQLOG.enabled:\n"
            "        obs.REQLOG.finish(uid, outcome='completed', tick=n)\n"
        ))
        assert fs == []

    def test_reqlog_module_in_scope_unlike_obs_peers(self):
        # obs/reqlog.py is the ONE obs/ module inside the guard scope:
        # its finish() emits a tracer instant, so it carries the same
        # burden as engine code. Its siblings stay exempt.
        snippet = (
            "from tree_attention_tpu import obs\n"
            "def f(x):\n"
            "    obs.instant('evt', cat='serving', args={'x': x})\n"
        )
        assert run("obs-guard", snippet, path=OBS_FLIGHT) == []
        fs = run("obs-guard", snippet,
                 path="tree_attention_tpu/obs/reqlog.py")
        assert len(fs) == 1


# ---------------------------------------------------------------------------
# host-sync


class TestHostSync:
    BAD_SERVE = (
        "import numpy as np\n"
        "class SlotServer:\n"
        "    def serve(self, requests):\n"
        "        toks = np.asarray(self.tok)\n"
    )

    def test_device_asarray_in_serve_flagged(self):
        fs = run("host-sync", self.BAD_SERVE)
        assert len(fs) == 1 and "np.asarray" in fs[0].message

    def test_allow_with_reason_suppresses(self):
        fs = run("host-sync", self.BAD_SERVE.replace(
            "        toks = np.asarray(self.tok)\n",
            "        # lint: allow[host-sync] THE per-tick fetch\n"
            "        toks = np.asarray(self.tok)\n",
        ))
        assert fs == []

    def test_allow_without_reason_is_a_finding(self):
        fs = run("host-sync", self.BAD_SERVE.replace(
            "        toks = np.asarray(self.tok)\n",
            "        # lint: allow[host-sync]\n"
            "        toks = np.asarray(self.tok)\n",
        ))
        assert len(fs) == 1 and "needs a reason" in fs[0].message

    def test_wrong_rule_allow_does_not_suppress(self):
        fs = run("host-sync", self.BAD_SERVE.replace(
            "        toks = np.asarray(self.tok)\n",
            "        # lint: allow[obs-guard] not this rule\n"
            "        toks = np.asarray(self.tok)\n",
        ))
        assert len(fs) == 1

    def test_list_literal_asarray_clean(self):
        fs = run("host-sync", (
            "import numpy as np\n"
            "class SlotServer:\n"
            "    def serve(self, requests):\n"
            "        use = np.asarray([s == 'await' for s in self.st])\n"
        ))
        assert fs == []

    def test_item_and_block_until_ready_flagged(self):
        fs = run("host-sync", (
            "class SlotServer:\n"
            "    def serve(self, requests):\n"
            "        x = self.tok.item()\n"
            "        self.cache.k.block_until_ready()\n"
        ))
        assert len(fs) == 2

    def test_int_on_tainted_local_flagged_param_exempt(self):
        fs = run("host-sync", (
            "import jax.numpy as jnp\n"
            "class SlotServer:\n"
            "    def serve(self, requests, q_position):\n"
            "        dev = jnp.zeros((4,))\n"
            "        a = int(dev[0])\n"          # tainted local -> flag
            "        b = int(q_position)\n"      # param -> exempt
        ))
        assert len(fs) == 1 and "dev" in fs[0].message

    def test_ops_dispatch_scope(self):
        fs = run("host-sync", (
            "import jax\n"
            "def flash_decode(q, k, v):\n"
            "    return jax.device_get(q)\n"
        ), path=OPS_DECODE)
        assert len(fs) == 1

    def test_other_files_unscoped(self):
        fs = run("host-sync", self.BAD_SERVE,
                 path="tree_attention_tpu/bench/serving.py")
        assert fs == []

    def test_disagg_serve_and_tick_helpers_scoped(self):
        # ISSUE 12: the disaggregated loop joins the host-sync scope —
        # DisaggServer.serve and any *_tick helper pay exactly one
        # annotated fetch per worker; adoption/relay helpers are host
        # bookkeeping on request data and stay out of scope, like the
        # fused engine's admission helpers.
        bad = (
            "import numpy as np\n"
            "class DisaggServer:\n"
            "    def serve(self, requests):\n"
            "        return np.asarray(self.decode.tok)\n"
            "    def _decode_tick(self):\n"
            "        return np.asarray(self.decode.tok)\n"
            "    def _adopt(self, p, d):\n"
            "        return np.asarray(self.decode.tok)\n"
        )
        fs = run("host-sync", bad, path=DISAGG)
        assert len(fs) == 2
        assert {f.line for f in fs} == {4, 6}  # serve + _decode_tick

    def test_host_pool_every_method_scoped(self):
        # ISSUE 13: the host KV tier is the ONE intended home of host
        # sync (the staged D2H batch lands in commit()), so EVERY
        # HostBlockPool method is in scope and each landing fetch needs
        # its annotated reason — a bare fetch anywhere in the file is a
        # staging-discipline bug, not background noise.
        bad = (
            "import numpy as np\n"
            "class HostBlockPool:\n"
            "    def commit(self, rows, k_rows):\n"
            "        self.k[rows] = np.asarray(k_rows)\n"
            "    def read(self, rows):\n"
            "        return np.asarray(self.k[rows])\n"
        )
        fs = run("host-sync", bad, path=HOST_POOL)
        assert len(fs) == 2
        fs = run("host-sync", bad.replace(
            "        self.k[rows] = np.asarray(k_rows)\n",
            "        # lint: allow[host-sync] the staged D2H batch "
            "lands here\n"
            "        self.k[rows] = np.asarray(k_rows)\n",
        ), path=HOST_POOL)
        assert len(fs) == 1 and fs[0].line == 7  # only the bare read

    def test_tree_dispatch_scope(self):
        # ISSUE 18: the sharded decode dispatch layer joins the scope —
        # a sync in paged_tree_decode stalls every shard of every tick.
        fs = run("host-sync", (
            "import jax\n"
            "def paged_tree_decode(q, k, v, tbl):\n"
            "    return jax.device_get(q)\n"
        ), path="tree_attention_tpu/parallel/tree.py")
        assert len(fs) == 1

    def test_models_decode_only_seq_writers_scoped(self):
        # ISSUE 18: the *_seq pool writers run under shard_map inside
        # jitted families — no sync allowed.  forward_step converts
        # request metadata (host lists) with np.asarray by design and
        # stays out of scope.
        body = (
            "import numpy as np\n"
            "def _paged_pool_write_seq(pool, rows):\n"
            "    return np.asarray(pool)\n"
            "def forward_step(params, cache, start):\n"
            "    return np.asarray(start)\n"
        )
        fs = run("host-sync", body,
                 path="tree_attention_tpu/models/decode.py")
        assert len(fs) == 1 and fs[0].line == 3

    def test_host_pool_bookkeeping_clean(self):
        # The real class's sync-free surface (alloc/enqueue/drop is pure
        # host bookkeeping) must stay clean without annotations.
        fs = run("host-sync", (
            "import numpy as np\n"
            "class HostBlockPool:\n"
            "    def alloc(self):\n"
            "        return self._free.pop() if self._free else None\n"
            "    def enqueue(self, row, bid):\n"
            "        self.pending[row] = bid\n"
        ), path=HOST_POOL)
        assert fs == []


# ---------------------------------------------------------------------------
# recompile-hygiene


class TestRecompileHygiene:
    def test_raw_length_shape_var_flagged(self):
        fs = run("recompile-hygiene", (
            "class S:\n"
            "    def f(self, plen):\n"
            "        tq = plen\n"
        ))
        assert len(fs) == 1 and "tq" in fs[0].message

    def test_bucketed_shape_vars_clean(self):
        fs = run("recompile-hygiene", (
            "class S:\n"
            "    def f(self, plan, rows_max, prompt):\n"
            "        tq = self._spec_bucket(rows_max) if rows_max > 1 else 1\n"
            "        tq = max(tq, self._chunk_bucket(8))\n"
            "        bucket = _bucket(plan, self.cache_len)\n"
            "        bucket = prompt.shape[1]\n"
        ))
        assert fs == []

    def test_disagg_shape_vars_scoped(self):
        # ISSUE 12: the disagg loop builds its own tick matrices — its
        # tq assignments must flow through the pow2 bucket helpers too.
        fs = run("recompile-hygiene", "tq = raw_len\n", path=DISAGG)
        assert len(fs) == 1 and "tq" in fs[0].message
        assert run("recompile-hygiene",
                   "tq = dc._chunk_bucket(raw_len)\n", path=DISAGG) == []

    def test_shard_var_from_traced_value_flagged(self):
        # ISSUE 18: shard geometry slices the pool — a traced shard
        # count (lax.axis_index looks like a host int inside shard_map)
        # makes the slice shape dynamic.
        fs = run("recompile-hygiene", (
            "from jax import lax\n"
            "def merge(pool, mesh):\n"
            "    n_shards = lax.axis_index('seq') + 1\n"
            "    return pool.shape[0] // n_shards\n"
        ), path="tree_attention_tpu/parallel/tree.py")
        assert len(fs) == 1 and "n_shards" in fs[0].message \
            and "mesh.shape" in fs[0].message

    def test_shard_var_via_tainted_local_flagged(self):
        fs = run("recompile-hygiene", (
            "import jax.numpy as jnp\n"
            "def merge(tbl, mesh):\n"
            "    hi = jnp.max(tbl)\n"
            "    n_local = hi + 1\n"
        ), path="tree_attention_tpu/models/decode.py")
        assert len(fs) == 1 and "n_local" in fs[0].message

    def test_shard_var_from_mesh_clean(self):
        # The real idiom: counts from mesh.shape (host-side), divisions
        # of array .shape over them, attribute form included.
        fs = run("recompile-hygiene", (
            "class S:\n"
            "    def _setup(self, mesh, pool):\n"
            "        self._seq_shards = max(mesh.shape.get('seq', 1), 1)\n"
            "        n_sh = mesh.shape['seq']\n"
            "        n_local = pool.shape[0] // n_sh\n"
        ))
        assert fs == []

    def test_shard_var_check_scoped_to_dispatch_files(self):
        fs = run("recompile-hygiene", (
            "from jax import lax\n"
            "def f():\n"
            "    n_shards = lax.axis_index('seq') + 1\n"
        ), path="tree_attention_tpu/bench/serving.py")
        assert fs == []

    def test_module_scope_jnp_flagged(self):
        fs = run("recompile-hygiene", (
            "import jax.numpy as jnp\n"
            "_TABLE = jnp.arange(128)\n"
        ), path=OPS_DECODE)
        assert len(fs) == 1 and "module-scope" in fs[0].message

    def test_function_scope_jnp_clean(self):
        fs = run("recompile-hygiene", (
            "import jax.numpy as jnp\n"
            "def f():\n"
            "    return jnp.arange(128)\n"
        ), path=OPS_DECODE)
        assert fs == []

    def test_python_if_on_traced_value_flagged(self):
        fs = run("recompile-hygiene", (
            "import jax\n"
            "def _step_fn(x, n):\n"
            "    if n > 0:\n"
            "        return x\n"
            "    return x * 2\n"
            "_step = jax.jit(_step_fn)\n"
        ), path=OPS_DECODE)
        assert len(fs) == 1 and "'n'" in fs[0].message

    def test_static_trace_time_tests_clean(self):
        fs = run("recompile-hygiene", (
            "import jax\n"
            "def _step_fn(x, mask=None):\n"
            "    if mask is None:\n"
            "        return x\n"
            "    if x.shape[0] > 8:\n"
            "        return x\n"
            "    return x * 2\n"
            "_step = jax.jit(_step_fn)\n"
        ), path=OPS_DECODE)
        assert fs == []

    def test_static_argname_param_may_branch(self):
        fs = run("recompile-hygiene", (
            "import jax\n"
            "def _step_fn(x, n):\n"
            "    if n > 0:\n"
            "        return x\n"
            "    return x * 2\n"
            "_step = jax.jit(_step_fn, static_argnames=('n',))\n"
        ), path=OPS_DECODE)
        assert fs == []

    def test_unhashable_static_arg_at_call_site(self):
        fs = run("recompile-hygiene", (
            "import jax\n"
            "def _step_fn(x, sizes):\n"
            "    return x\n"
            "_step = jax.jit(_step_fn, static_argnames=('sizes',))\n"
            "def caller(x):\n"
            "    return _step(x, sizes=[1, 2, 3])\n"
        ), path=OPS_DECODE)
        assert len(fs) == 1 and "unhashable" in fs[0].message


# ---------------------------------------------------------------------------
# pallas-contract


class TestPallasContract:
    def test_lambda_capturing_array_flagged(self):
        fs = run("pallas-contract", (
            "import jax.numpy as jnp\n"
            "def build(table):\n"
            "    tbl = jnp.asarray(table, jnp.int32)\n"
            "    spec = pl.BlockSpec((1, 8, 8),\n"
            "                        lambda b, i: (tbl[b, i], 0, 0))\n"
        ), path=PALLAS)
        assert len(fs) == 1 and "tbl" in fs[0].message

    def test_factory_int_closure_clean(self):
        # The _paged_kv_map idiom: static int baked at trace time.
        fs = run("pallas-contract", (
            "def _paged_kv_map(n_kv_heads):\n"
            "    def index_map(bh, qi, si, offs_ref, tbl_ref):\n"
            "        return (tbl_ref[bh // n_kv_heads, si],\n"
            "                bh % n_kv_heads, 0, 0)\n"
            "    return index_map\n"
        ), path=PALLAS)
        assert fs == []

    def test_index_map_mutation_flagged(self):
        fs = run("pallas-contract", (
            "_STATE = {}\n"
            "def build():\n"
            "    def index_map(bh, qi, si):\n"
            "        _STATE['last'] = si\n"
            "        return (bh, qi, 0)\n"
            "    spec = pl.BlockSpec((1, 8, 8), index_map)\n"
        ), path=PALLAS)
        assert any("pure" in m for m in messages(fs))

    def test_scalar_prefetch_not_int32_flagged(self):
        code = (
            "import jax.numpy as jnp\n"
            "def paged_call(kernel, offs_raw, table, q):\n"
            "    tbl = jnp.asarray(table{dtype})\n"
            "    grid_spec = pltpu.PrefetchScalarGridSpec(\n"
            "        num_scalar_prefetch=2, grid=(1,))\n"
            "    return pl.pallas_call(kernel, grid_spec=grid_spec)(\n"
            "        offsets_smem(0, 0, 4), tbl, q)\n"
        )
        bad = run("pallas-contract", code.format(dtype=""), path=PALLAS)
        good = run("pallas-contract",
                   code.format(dtype=", jnp.int32"), path=PALLAS)
        assert len(bad) == 1 and "int32" in bad[0].message
        assert good == []

    def test_tree_bits_needs_limit_check(self):
        base = (
            "def kernel_entry(tree_mask, G, Hkv, bq, n_q):\n"
            "{guard}"
            "    tb = _tree_bits_rows(tree_mask, G, Hkv, bq, n_q)\n"
            "    return tb\n"
        )
        bad = base.format(guard="")
        good = base.format(guard=(
            "    if tree_mask.shape[1] > 32:\n"
            "        raise ValueError('Tq exceeds 32')\n"))
        assert len(run("pallas-contract", bad, path=PALLAS)) == 1
        assert run("pallas-contract", good, path=PALLAS) == []

    def test_only_pallas_files_scoped(self):
        fs = run("pallas-contract", (
            "import jax.numpy as jnp\n"
            "def build(table):\n"
            "    tbl = jnp.asarray(table, jnp.int32)\n"
            "    spec = pl.BlockSpec((1, 8), lambda b: (tbl[b], 0))\n"
        ), path=OPS_DECODE)
        assert fs == []

    def test_sibling_packer_needs_limit_check(self):
        # ISSUE 20: the sibling-row packer feeds the same int32 tree
        # bitmasks — it must carry its own rows <= 32 guard.
        spec_path = "tree_attention_tpu/serving/speculation.py"
        base = (
            "def pack_siblings(suffixes):\n"
            "{guard}"
            "    return _pack(suffixes)\n"
        )
        bad = base.format(guard="")
        good = base.format(guard=(
            "    rows = sum(len(s) for s in suffixes)\n"
            "    assert rows <= 32, 'sibling bundle too wide'\n"))
        fs = run("pallas-contract", bad, path=spec_path)
        assert len(fs) == 1 and "pack_siblings" in fs[0].message
        assert run("pallas-contract", good, path=spec_path) == []
        # The packer rule is scoped to speculation.py; engine callers
        # ride the eligibility gates instead of per-call checks.
        assert run("pallas-contract", bad, path=ENGINE) == []


# ---------------------------------------------------------------------------
# lock-safety


class TestLockSafety:
    def test_unlocked_mutation_flagged(self):
        fs = run("lock-safety", (
            "import threading\n"
            "class Rec:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._ring = []\n"
            "    def record(self, rec):\n"
            "        self._ring.append(rec)\n"
        ), path=OBS_FLIGHT)
        assert len(fs) == 1 and "self._ring" in fs[0].message

    def test_locked_mutation_and_flag_attr_clean(self):
        fs = run("lock-safety", (
            "import threading\n"
            "class Rec:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._ring = []\n"
            "        self.enabled = False\n"
            "    def arm(self):\n"
            "        with self._lock:\n"
            "            self._ring.append(0)\n"
            "        self.enabled = True\n"  # the lock-free fast-path flag
        ), path=OBS_FLIGHT)
        assert fs == []

    def test_host_pool_in_lock_scope(self):
        # ISSUE 13: host_pool.py joins the lock-safety scope. The real
        # HostBlockPool is single-threaded (engine-loop only) and owns
        # no lock — vacuously clean — but the moment anyone gives it one
        # (say, a background flusher thread), every self._* mutation
        # must move under it.
        locked = (
            "import threading\n"
            "class HostBlockPool:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._free = []\n"
            "    def release(self, row):\n"
            "        self._free.append(row)\n"
        )
        fs = run("lock-safety", locked, path=HOST_POOL)
        assert len(fs) == 1 and "self._free" in fs[0].message
        lockless = (
            "class HostBlockPool:\n"
            "    def __init__(self):\n"
            "        self._free = []\n"
            "    def release(self, row):\n"
            "        self._free.append(row)\n"
        )
        assert run("lock-safety", lockless, path=HOST_POOL) == []

    def test_plain_lock_on_crash_path_flagged(self):
        base = (
            "import threading\n"
            "class Sink:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.{lock}()\n"
            "    def flush(self):\n"
            "        with self._lock:\n"
            "            pass\n"
        )
        bad = run("lock-safety", base.format(lock="Lock"),
                  path=OBS_FLIGHT)
        good = run("lock-safety", base.format(lock="RLock"),
                   path=OBS_FLIGHT)
        assert len(bad) == 1 and "RLock" in bad[0].message
        assert good == []

    def test_plain_lock_via_from_import_still_flagged(self):
        # Review finding: `from threading import Lock` must not dodge
        # the RLock requirement.
        fs = run("lock-safety", (
            "from threading import Lock\n"
            "class Sink:\n"
            "    def __init__(self):\n"
            "        self._lock = Lock()\n"
            "    def flush(self):\n"
            "        with self._lock:\n"
            "            pass\n"
        ), path=OBS_FLIGHT)
        assert len(fs) == 1 and "RLock" in fs[0].message

    def test_non_crash_class_may_use_plain_lock(self):
        # slo.py's monitor: not on the signal path, Lock is fine.
        fs = run("lock-safety", (
            "import threading\n"
            "class Mon:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def observe(self, v):\n"
            "        with self._lock:\n"
            "            pass\n"
        ), path="tree_attention_tpu/obs/slo.py")
        assert fs == []

    def test_reqlog_ring_mutation_needs_lock(self):
        # ISSUE 16: the request ledger is written by ingress handler
        # threads (open/finish) and read by the obs HTTP thread
        # (snapshot) — obs/ scope applies unchanged: every container
        # mutation under the RLock, the lock-free `enabled` flag stays
        # the sanctioned fast path.
        base = (
            "import threading\n"
            "class ReqLog:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._live = {{}}\n"
            "        self.enabled = False\n"
            "    def open(self, uid, led):\n"
            "{body}"
        )
        path = "tree_attention_tpu/obs/reqlog.py"
        bad = run("lock-safety",
                  base.format(body="        self._live[uid] = led\n"),
                  path=path)
        good = run("lock-safety", base.format(body=(
            "        with self._lock:\n"
            "            self._live[uid] = led\n")), path=path)
        assert len(bad) == 1 and "self._live" in bad[0].message
        assert good == []

    def test_signal_path_emission_flagged(self):
        fs = run("lock-safety", (
            "def flush():\n"
            "    _FLUSHES.inc()\n"
            "    return None\n"
        ), path="tree_attention_tpu/obs/__init__.py")
        assert len(fs) == 1 and "signal-path" in fs[0].message

    def test_signal_path_reaches_callees(self):
        fs = run("lock-safety", (
            "def flush():\n"
            "    _write_all()\n"
            "def _write_all():\n"
            "    obs.instant('flushed', cat='obs')\n"
        ), path="tree_attention_tpu/obs/__init__.py")
        assert len(fs) == 1

    def test_outside_obs_unscoped(self):
        fs = run("lock-safety", (
            "import threading\n"
            "class Rec:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def flush(self):\n"
            "        self._x = 1\n"
        ), path=ENGINE)
        assert fs == []

    def test_ingress_in_scope_unlocked_mutation_flagged(self):
        # ISSUE 10: the ingress's handler threads share state with the
        # engine thread — serving/ingress.py joins the lock-safety scope.
        snippet = (
            "import threading\n"
            "class Ingress:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._queued = 0\n"
            "    def submit(self):\n"
            "        self._queued += 1\n"
        )
        fs = run("lock-safety", snippet, path=INGRESS)
        assert len(fs) == 1 and "self._queued" in fs[0].message
        # The engine module itself stays out of scope: handler threads
        # reach it only through the mailbox seams.
        assert run("lock-safety", snippet, path=ENGINE) == []

    def test_router_and_fleet_in_scope(self):
        # ISSUE 11: the fleet tier's handler/monitor threads share the
        # replica registry, approximate trees, and restart budgets —
        # serving/router.py and serving/fleet.py join the lock-safety
        # scope with the same mutate-under-self._lock contract.
        snippet = (
            "import threading\n"
            "class Router:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._inflight = {}\n"
            "    def choose(self, name):\n"
            "        self._inflight[name] = 1\n"
        )
        for path in ("tree_attention_tpu/serving/router.py",
                     "tree_attention_tpu/serving/fleet.py"):
            fs = run("lock-safety", snippet, path=path)
            assert len(fs) == 1 and "self._inflight" in fs[0].message, path
        # ...and the engine module still is NOT in scope.
        assert run("lock-safety", snippet, path=ENGINE) == []

    def test_router_locked_mutation_clean(self):
        fs = run("lock-safety", (
            "import threading\n"
            "class Router:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._trees = {}\n"
            "    def rejoin(self, name):\n"
            "        with self._lock:\n"
            "            self._trees.pop(name, None)\n"
        ), path="tree_attention_tpu/serving/router.py")
        assert fs == []

    def test_disagg_in_scope_unlocked_mailbox_flagged(self):
        # ISSUE 12: DisaggServer's cancel/drain mailboxes are its only
        # thread-safe seams — serving/disagg.py joins the lock-safety
        # scope (handoff-queue run state lives in loop-locals by design;
        # whatever shared state DOES live on self mutates under the
        # RLock).
        snippet = (
            "import threading\n"
            "class DisaggServer:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._cancel_uids = set()\n"
            "    def cancel(self, uid):\n"
            "        self._cancel_uids.add(uid)\n"
        )
        fs = run("lock-safety", snippet, path=DISAGG)
        assert len(fs) == 1 and "self._cancel_uids" in fs[0].message
        # ...and the engine module still is NOT in scope.
        assert run("lock-safety", snippet, path=ENGINE) == []

    def test_disagg_locked_mailbox_clean(self):
        fs = run("lock-safety", (
            "import threading\n"
            "class DisaggServer:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._draining = False\n"
            "    def request_drain(self):\n"
            "        with self._lock:\n"
            "            self._draining = True\n"
        ), path=DISAGG)
        assert fs == []

    def test_ingress_locked_mutation_and_condition_lock_clean(self):
        # The live feeder's Condition doubles as its lock; mutations
        # under `with self._lock:` pass, and Condition() on a class with
        # a crash-path method name (close) is not a plain-Lock finding.
        fs = run("lock-safety", (
            "import threading\n"
            "class Feeder:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Condition()\n"
            "        self._queue = []\n"
            "        self._closed = False\n"
            "    def submit(self, r):\n"
            "        with self._lock:\n"
            "            self._queue.append(r)\n"
            "    def close(self):\n"
            "        with self._lock:\n"
            "            self._closed = True\n"
        ), path=INGRESS)
        assert fs == []


# ---------------------------------------------------------------------------
# lock-order (ISSUE 14)


ROUTER = "tree_attention_tpu/serving/router.py"
FLEET = "tree_attention_tpu/serving/fleet.py"


class TestLockOrder:
    def test_unbounded_wait_under_lock_flagged(self):
        fs = run("lock-order", (
            "import threading\n"
            "class Router:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._evt = threading.Event()\n"
            "    def route(self):\n"
            "        with self._lock:\n"
            "            self._evt.wait()\n"
        ), path=ROUTER)
        assert len(fs) == 1 and "no timeout" in fs[0].message

    def test_timeout_wait_and_own_condition_clean(self):
        # Condition.wait on the HELD lock releases it (the feeder's
        # idiom); a timeout-bounded wait on anything is bounded.
        fs = run("lock-order", (
            "import threading\n"
            "class Feeder:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Condition()\n"
            "        self._evt = threading.Event()\n"
            "    def wait_work(self, t):\n"
            "        with self._lock:\n"
            "            self._lock.wait(t)\n"
            "            self._evt.wait()\n"  # own-lock exempt does NOT
        ), path=ROUTER)                        # cover a foreign no-arg wait
        assert len(fs) == 1 and "_evt" in fs[0].message

    def test_multi_item_with_records_acquisition_edges(self):
        # Review finding: `with self._a, self._b:` acquires left to
        # right like the nested spelling, but _held_locks only walks
        # ancestors — same-With siblings saw no edge, so the one-line
        # idiom's AB/BA cycle passed clean.
        fs = run("lock-order", (
            "import threading\n"
            "class Sup:\n"
            "    def __init__(self):\n"
            "        self._op_lock = threading.RLock()\n"
            "        self._lock = threading.RLock()\n"
            "    def a(self):\n"
            "        with self._op_lock, self._lock:\n"
            "            pass\n"
            "    def b(self):\n"
            "        with self._lock:\n"
            "            with self._op_lock:\n"
            "                pass\n"
        ), path=FLEET)
        assert len(fs) == 2 \
            and all("cycle" in f.message for f in fs)

    def test_acquire_on_held_lock_not_exempt(self):
        # Review finding: the held-lock exemption keyed on the receiver
        # alone, which also whitelisted a no-arg .acquire() on the held
        # lock — the one guaranteed self-deadlock. Only wait() RELEASES
        # the lock while parked.
        fs = run("lock-order", (
            "import threading\n"
            "class Router:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            self._lock.acquire()\n"
        ), path=ROUTER)
        assert len(fs) == 1 and "no timeout" in fs[0].message

    def test_blocking_io_under_lock_flagged(self):
        fs = run("lock-order", (
            "import threading\n"
            "from urllib.request import urlopen\n"
            "class Sup:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def poll(self):\n"
            "        with self._lock:\n"
            "            return urlopen('http://x/healthz')\n"
        ), path=FLEET)
        assert len(fs) == 1 and "blocking I/O" in fs[0].message

    def test_blocking_reached_through_helper_flagged(self):
        # Inter-procedural: the lock holder calls a same-class helper
        # whose body blocks — flagged at the call site.
        fs = run("lock-order", (
            "import threading, time\n"
            "class Sup:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def _settle(self):\n"
            "        time.sleep(0.2)\n"
            "    def roll(self):\n"
            "        with self._lock:\n"
            "            self._settle()\n"
        ), path=FLEET)
        assert len(fs) == 1 and "_settle" in fs[0].message

    def test_lock_cycle_flagged(self):
        # AB/BA: op->state in one method, state->op in another.
        fs = run("lock-order", (
            "import threading\n"
            "class Sup:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._op_lock = threading.Lock()\n"
            "    def a(self):\n"
            "        with self._op_lock:\n"
            "            with self._lock:\n"
            "                pass\n"
            "    def b(self):\n"
            "        with self._lock:\n"
            "            with self._op_lock:\n"
            "                pass\n"
        ), path=FLEET)
        assert len(fs) == 2 and all("cycle" in f.message for f in fs)

    def test_nested_order_without_cycle_clean(self):
        # The fleet's real shape: state lock nests under the op lock,
        # never the reverse.
        fs = run("lock-order", (
            "import threading\n"
            "class Sup:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._op_lock = threading.Lock()\n"
            "    def a(self):\n"
            "        with self._op_lock:\n"
            "            with self._lock:\n"
            "                pass\n"
        ), path=FLEET)
        assert fs == []

    def test_allow_with_reason_suppresses(self):
        fs = run("lock-order", (
            "import threading, time\n"
            "class Sup:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def roll(self):\n"
            "        with self._lock:\n"
            "            # lint: allow[lock-order] bounded by grace esc\n"
            "            time.sleep(0.2)\n"
        ), path=FLEET)
        assert fs == []

    def test_out_of_scope_files_skipped(self):
        fs = run("lock-order", (
            "import threading, time\n"
            "class X:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1)\n"
        ), path="tree_attention_tpu/host_runtime.py")
        assert fs == []

    def test_fleet_recovery_sites_annotated_not_bare(self):
        # The supervisor's serialized recovery path is the ONE deliberate
        # blocking-under-lock region — every site carries its reason.
        path = os.path.join(lintlib.REPO_ROOT, FLEET)
        with open(path) as fh:
            text = fh.read()
        assert text.count("lint: allow[lock-order]") == 4


# ---------------------------------------------------------------------------
# donation-safety (ISSUE 14)


class TestDonationSafety:
    def test_read_after_donate_flagged(self):
        fs = run("donation-safety", (
            "import jax\n"
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._step = jax.jit(self._step_fn,\n"
            "                             donate_argnums=(0,))\n"
            "    def serve(self):\n"
            "        out = self._step(self.cache, 1)\n"
            "        return self.cache.k\n"
        ))
        assert len(fs) == 1 and "self.cache" in fs[0].message

    def test_same_statement_rebind_clean(self):
        fs = run("donation-safety", (
            "import jax\n"
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._step = jax.jit(self._step_fn,\n"
            "                             donate_argnums=(0,))\n"
            "    def serve(self):\n"
            "        self.cache = self._step(self.cache, 1)\n"
            "        return self.cache.k\n"
        ))
        assert fs == []

    def test_missing_relay_between_aliased_engines_flagged(self):
        base = (
            "import jax\n"
            "class Pair:\n"
            "    def _relay_pool(self, src, dst):\n"
            "        dst.cache = src.cache\n"
            "    def serve(self, pf, dc):\n"
            "        # lint: donated-alias[pf.cache, dc.cache]\n"
            "        pf.tok, pf.cache = pf._mixed(0, 1, 2, 3, 4, 5,\n"
            "                                     pf.cache, pf._key)\n"
            "{relay}"
            "        dc.tok, dc.cache = dc._mixed(0, 1, 2, 3, 4, 5,\n"
            "                                     dc.cache, dc._key)\n"
        )
        bad = base.format(relay="")
        good = base.format(relay="        self._relay_pool(pf, dc)\n")
        fs = run("donation-safety", bad, path=DISAGG)
        assert len(fs) == 1 and "dc.cache" in fs[0].message
        assert run("donation-safety", good, path=DISAGG) == []

    def test_direct_rebind_also_relays(self):
        fs = run("donation-safety", (
            "import jax, dataclasses\n"
            "class Pair:\n"
            "    def serve(self, pf, dc):\n"
            "        # lint: donated-alias[pf.cache, dc.cache]\n"
            "        pf.tok, pf.cache = pf._mixed(0, 1, 2, 3, 4, 5,\n"
            "                                     pf.cache, pf._key)\n"
            "        dc.cache = dataclasses.replace(dc.cache,\n"
            "                                       k=pf.cache.k)\n"
            "        dc.tok, dc.cache = dc._mixed(0, 1, 2, 3, 4, 5,\n"
            "                                     dc.cache, dc._key)\n"
        ), path=DISAGG)
        # dataclasses.replace(dc.cache, ...) READS the stale dc.cache
        # container (legal: only .k/.v fields died) and the assignment
        # rebinds it — the direct-relay idiom stays clean.
        assert fs == []

    def test_dispatch_in_while_condition_consumes(self):
        # Review finding: the While handler checked reads in the loop
        # test but never ran the call handler on it, so a donating
        # dispatch in a while-CONDITION was invisible — the loop's own
        # re-evaluation and any read after the loop see a dead buffer.
        fs = run("donation-safety", (
            "import jax\n"
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._step = jax.jit(self._step_fn,\n"
            "                             donate_argnums=(0,))\n"
            "    def serve(self):\n"
            "        while self._step(self.cache, 1):\n"
            "            pass\n"
            "        return self.cache.k\n"
        ))
        assert fs and all("self.cache" in f.message for f in fs)

    def test_while_condition_dispatch_with_body_rebind_clean(self):
        fs = run("donation-safety", (
            "import jax\n"
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._step = jax.jit(self._step_fn,\n"
            "                             donate_argnums=(0,))\n"
            "    def serve(self):\n"
            "        while self._step(self.cache, 1):\n"
            "            self.cache = self._refresh()\n"
        ))
        assert fs == []

    def test_allow_with_reason_suppresses(self):
        fs = run("donation-safety", (
            "import jax\n"
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._step = jax.jit(self._step_fn,\n"
            "                             donate_argnums=(0,))\n"
            "    def serve(self):\n"
            "        out = self._step(self.cache, 1)\n"
            "        # lint: allow[donation-safety] CPU-only debug path\n"
            "        return self.cache.k\n"
        ))
        assert fs == []

    def test_lambda_body_reads_not_flagged(self):
        # Review fix: ast.walk used to descend into lambda bodies — but
        # a callback's reads happen when it is CALLED, after this
        # statement's successor rebinds the binding.
        fs = run("donation-safety", (
            "import jax\n"
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._step = jax.jit(self._step_fn,\n"
            "                             donate_argnums=(0,))\n"
            "    def serve(self):\n"
            "        out = self._step(self.cache, 1)\n"
            "        cb = lambda: self.cache.k\n"
            "        self.cache = out\n"
            "        return cb\n"
        ))
        assert fs == []

    def test_table_matches_engine(self):
        # The cross-file donation table is pinned against engine.py by
        # the pass itself — a drifted edit is a finding on engine.py.
        from tools.lintlib import donation
        path = os.path.join(lintlib.REPO_ROOT, ENGINE)
        with open(path) as fh:
            src = lintlib.Source(ENGINE, fh.read())
        discovered = donation._discover_donations(src.tree)
        for name, pos in donation.SLOTSERVER_DONATIONS.items():
            assert name in discovered, name
            if discovered[name] is not None:
                assert tuple(discovered[name]) == tuple(pos), name

    def test_out_of_scope_files_skipped(self):
        fs = run("donation-safety", (
            "import jax\n"
            "class X:\n"
            "    def __init__(self):\n"
            "        self._step = jax.jit(f, donate_argnums=(0,))\n"
            "    def g(self):\n"
            "        out = self._step(self.cache)\n"
            "        return self.cache\n"
        ), path="tree_attention_tpu/serving/router.py")
        assert fs == []


# ---------------------------------------------------------------------------
# handoff-transfer (ISSUE 16)


class TestHandoffTransfer:
    @staticmethod
    def _adopt_src(skip=()):
        from tools.lintlib.handoff import ADOPTED_SLOT_FIELDS
        lines = [
            "class DisaggServer:\n",
            "    def _adopt(self, req, d):\n",
            "        pf, dc = self.prefill, self.decode\n",
        ]
        for name in sorted(ADOPTED_SLOT_FIELDS - set(skip)):
            lines.append(f"        dc.{name}[d] = pf.{name}[0]\n")
        return "".join(lines)

    def test_untabled_engine_slot_field_flagged(self):
        fs = run("handoff-transfer", (
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._slot_req = [None]\n"
            "        self._slot_frobnicate = [0]\n"
        ))
        assert len(fs) == 1 and "_slot_frobnicate" in fs[0].message

    def test_tabled_and_exempt_fields_clean(self):
        # Plain stores, subscripted rows, and AugAssign rebinds of
        # tabled (or exempt) fields all resolve to the same attribute.
        fs = run("handoff-transfer", (
            "class SlotServer:\n"
            "    def __init__(self):\n"
            "        self._slot_req = [None]\n"
            "        self._slot_logits = None\n"
            "    def tick(self, s):\n"
            "        self._slot_clen[s] += 1\n"
        ))
        assert fs == []

    def test_complete_adopt_clean(self):
        assert run("handoff-transfer", self._adopt_src(),
                   path=DISAGG) == []

    def test_dropped_transfer_flagged(self):
        fs = run("handoff-transfer",
                 self._adopt_src(skip=("_slot_span",)), path=DISAGG)
        assert len(fs) == 1 and "_slot_span" in fs[0].message
        assert "ADOPT_EXEMPT" in fs[0].message

    def test_missing_decode_binding_flagged(self):
        fs = run("handoff-transfer", (
            "class DisaggServer:\n"
            "    def _adopt(self, req, d):\n"
            "        self.decode._slot_req[d] = req\n"
        ), path=DISAGG)
        assert len(fs) == 1 and "decode receiver" in fs[0].message

    def test_tables_match_real_tree(self):
        # Reverse drift (a tabled name engine.py no longer builds) is
        # pinned HERE against the real tree — the donation pass's
        # convention — so the fixture snippets above stay usable.
        from tools.lintlib import handoff
        path = os.path.join(lintlib.REPO_ROOT, ENGINE)
        with open(path) as fh:
            src = lintlib.Source(ENGINE, fh.read())
        discovered = handoff._engine_slot_fields(src.tree)
        tabled = handoff.ADOPTED_SLOT_FIELDS | set(handoff.ADOPT_EXEMPT)
        assert tabled == discovered
        # And the real _adopt covers the full table (re-checked here so
        # the suite fails even if a lint baseline grandfathers it).
        dis = os.path.join(lintlib.REPO_ROOT, DISAGG)
        with open(dis) as fh:
            assert lintlib.run_source("handoff-transfer", fh.read(),
                                      DISAGG) == []

    def test_out_of_scope_files_skipped(self):
        fs = run("handoff-transfer", (
            "class X:\n"
            "    def __init__(self):\n"
            "        self._slot_mystery = 0\n"
        ), path="tree_attention_tpu/serving/router.py")
        assert fs == []


# ---------------------------------------------------------------------------
# ledger-leak (ISSUE 14)


class TestLedgerLeak:
    def test_pins_dropped_on_failure_arc_flagged(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def _paged_reserve(self, req):\n"
            "        matched, nodes = self._prefix.match(req)\n"
            "        if not self._pool.reserve(4):\n"
            "            return None\n"
            "        return matched, nodes, 4\n"
        ))
        assert len(fs) == 1 and "nodes" in fs[0].message \
            and "return" in fs[0].message

    def test_release_on_failure_arc_clean(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def _paged_reserve(self, req):\n"
            "        matched, nodes = self._prefix.match(req)\n"
            "        if not self._pool.reserve(4):\n"
            "            if nodes:\n"
            "                self._prefix.release(nodes)\n"
            "            return None\n"
            "        return matched, nodes, 4\n"
        ))
        assert fs == []

    def test_alloc_then_early_loop_exit_flagged(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def _ensure_blocks(self, slot, need):\n"
            "        while self._slot_nblocks[slot] < need:\n"
            "            bid = self._pool.alloc()\n"
            "            if self._table_dirty:\n"
            "                continue\n"
            "            self._slot_private[slot].add(bid)\n"
        ))
        assert len(fs) == 1 and "bid" in fs[0].message

    def test_ledger_store_and_none_guard_clean(self):
        # host-row alloc with the evict_one retry idiom: a None row is
        # absence, not a leak; an enqueued row is transferred.
        fs = run("ledger-leak", (
            "class Idx:\n"
            "    def evict_one(self):\n"
            "        row = self.host.alloc()\n"
            "        while row is None and self._drop_host_lru():\n"
            "            row = self.host.alloc()\n"
            "        if row is not None:\n"
            "            self.host.enqueue(row, 7)\n"
            "            return True\n"
            "        return False\n"
        ), path="tree_attention_tpu/serving/prefix_cache.py")
        assert fs == []

    def test_unchecked_reserve_flagged(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def admit(self, n):\n"
            "        self._pool.reserve(n)\n"
        ))
        assert len(fs) == 1 and "unchecked" in fs[0].message

    def test_reserve_success_arc_must_store_count(self):
        bad = (
            "class S:\n"
            "    def admit(self, n):\n"
            "        if not self._pool.reserve(n):\n"
            "            return None\n"
            "        self.go()\n"
        )
        good = bad.replace("        self.go()\n",
                           "        self._slot_reserve[0] = n\n")
        assert len(run("ledger-leak", bad)) == 1
        assert run("ledger-leak", good) == []

    def test_allow_with_reason_suppresses(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def probe(self, n):\n"
            "        # lint: allow[ledger-leak] capacity probe, no claim\n"
            "        self._pool.reserve(n)\n"
        ))
        assert fs == []

    def test_preloop_acquire_survives_continue(self):
        # Review finding: continue/break leaked EVERYTHING pending —
        # including resources acquired BEFORE the loop whose sink sits
        # right after it — forcing bogus allow[]s on a common idiom.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self, items):\n"
            "        bid = self._pool.alloc()\n"
            "        for it in items:\n"
            "            if it is None:\n"
            "                continue\n"
            "            self.note(it)\n"
            "        self._table[0] = bid\n"
        ))
        assert fs == []

    def test_inloop_acquire_still_leaks_on_continue(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self, items):\n"
            "        for it in items:\n"
            "            bid = self._pool.alloc()\n"
            "            if it is None:\n"
            "                continue\n"
            "            self._table[it] = bid\n"
        ))
        assert len(fs) == 1 and "bid" in fs[0].message

    def test_reserve_in_while_test_tracked(self):
        # Review finding: _reserve_in_test was wired only for If — the
        # eviction-retry idiom (`while not pool.reserve(n): evict()`)
        # exits holding a reservation nobody tracked.
        bad = (
            "class S:\n"
            "    def admit(self, n):\n"
            "        while not self._pool.reserve(n):\n"
            "            self._evict()\n"
            "        self.go()\n"
        )
        good = bad.replace("        self.go()\n",
                           "        self._slot_reserve[0] = n\n")
        assert len(run("ledger-leak", bad)) == 1
        assert run("ledger-leak", good) == []

    def test_conditional_release_in_loop_body_is_not_a_sink(self):
        # Review finding: _apply_sinks scanned the WHOLE For/With
        # subtree up front, so a release buried in the body sank the
        # resource before branch analysis — a conditional (or
        # zero-iteration) release arc read as clean.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self, items, ok):\n"
            "        bid = self._pool.alloc()\n"
            "        for it in items:\n"
            "            if ok:\n"
            "                self._pool.free_private(bid)\n"
            "        return None\n"
        ))
        assert len(fs) == 1 and "bid" in fs[0].message

    def test_release_under_with_body_still_sinks(self):
        # The with BODY walks inline — an unconditional release there
        # stays a sink (only the up-front whole-subtree credit is gone).
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self):\n"
            "        bid = self._pool.alloc()\n"
            "        with self._lock:\n"
            "            self._table[0] = bid\n"
        ))
        assert fs == []

    def test_raise_caught_and_released_locally_clean(self):
        # Review finding: a raise caught by a LOCAL handler that
        # releases the resource on that arc still flagged at the raise
        # — the caught arc belongs to the handler, not the exit.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self):\n"
            "        bid = self._pool.alloc()\n"
            "        try:\n"
            "            raise ValueError()\n"
            "        except ValueError:\n"
            "            self._pool.free_private(bid)\n"
            "            return None\n"
        ))
        assert fs == []

    def test_raise_with_unreleasing_handler_still_flags(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self):\n"
            "        bid = self._pool.alloc()\n"
            "        try:\n"
            "            raise ValueError()\n"
            "        except ValueError:\n"
            "            return None\n"
        ))
        assert len(fs) == 1 and "bid" in fs[0].message

    def test_caught_raise_does_not_mask_later_leak(self):
        # Review fix: a Raise the handler catches used to mark the
        # WHOLE function terminated, skipping every statement after the
        # try — the rare-arc leak class this pass exists for.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self, slot):\n"
            "        try:\n"
            "            self.go()\n"
            "            raise ValueError()\n"
            "        except ValueError:\n"
            "            self.note()\n"
            "        bid = self._pool.alloc()\n"
            "        return None\n"
        ))
        assert len(fs) == 1 and "bid" in fs[0].message

    def test_acquire_released_after_caught_raise_clean(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self):\n"
            "        bid = self._pool.alloc()\n"
            "        try:\n"
            "            self.go(1)\n"
            "        except ValueError:\n"
            "            self.note()\n"
            "        self._pool.free(bid)\n"
            "        return None\n"
        ))
        assert fs == []

    def test_try_finally_with_terminating_body_terminates(self):
        # try/finally whose body returns on every arc has no catching
        # arc — the fall-off-end after it is unreachable, not a leak.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self):\n"
            "        bid = self._pool.alloc()\n"
            "        try:\n"
            "            return bid\n"
            "        finally:\n"
            "            self.note()\n"
        ))
        assert fs == []

    def test_router_tree_match_not_a_pin(self):
        # ReplicaTree.match returns an int score — receiver-scoped so
        # the router never false-fires (and the file is out of scope).
        fs = run("ledger-leak", (
            "class S:\n"
            "    def choose(self, prompt):\n"
            "        m = self._trees.match(prompt)\n"
            "        return None\n"
        ))
        assert fs == []

    def test_out_of_scope_files_skipped(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self):\n"
            "        bid = self._pool.alloc()\n"
            "        return None\n"
        ), path="tree_attention_tpu/serving/block_pool.py")
        assert fs == []

    def test_fork_shared_unledgered_flagged(self):
        # fork_shared refcounts blocks into a child's table — the bid
        # list must land in a per-slot shared ledger so BOTH retires
        # release (ISSUE 15); dropping it on any arc is the leak.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def _fork_child(self, parent, child, bids):\n"
            "        shared = self._pool.fork_shared(bids)\n"
            "        self._host_table[child, 0] = 0\n"
        ))
        assert len(fs) == 1 and "shared" in fs[0].message \
            and "fork_shared" in fs[0].message

    def test_fork_shared_stored_in_ledger_clean(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def _fork_child(self, parent, child, bids):\n"
            "        self._slot_shared[child] = set(\n"
            "            self._pool.fork_shared(bids)\n"
            "        )\n"
        ))
        assert fs == []

    def test_repin_dropped_on_exit_arc_flagged(self):
        # repin takes one MORE pin per node of the parent's path — the
        # child's pins must be ledgered (released at ITS retire), and
        # inspecting them is not releasing them.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def _fork_child(self, parent, child, nshare):\n"
            "        nodes = self._prefix.repin(self._slot_nodes[parent])\n"
            "        if nshare == 0:\n"
            "            return None\n"
            "        self._slot_nodes[child] = nodes\n"
        ))
        assert len(fs) == 1 and "nodes" in fs[0].message \
            and "repin" in fs[0].message

    def test_repin_ledgered_clean(self):
        fs = run("ledger-leak", (
            "class S:\n"
            "    def _fork_child(self, parent, child):\n"
            "        nodes = self._prefix.repin(self._slot_nodes[parent])\n"
            "        self._slot_nodes[child] = nodes\n"
        ))
        assert fs == []

    def test_repin_receiver_scoped_like_match(self):
        # A non-prefix receiver's repin (some future cache with the same
        # verb) is not a radix pin and must not fire.
        fs = run("ledger-leak", (
            "class S:\n"
            "    def f(self):\n"
            "        x = self._scores.repin([1, 2])\n"
            "        return None\n"
        ))
        assert fs == []


# ---------------------------------------------------------------------------
# mirror-drift (ISSUE 14)


class TestMirrorDrift:
    ENGINE_SIDE = (
        "class SlotServer:\n"
        "    def serve(self, source, pending, results):\n"
        "        while True:\n"
        "            # lint: mirror[ingest] begin\n"
        "            for r in source.poll(0):\n"
        "                self._validate(r)\n"
        "                pending.append(r)\n"
        "            # lint: mirror[ingest] end\n"
    )
    DISAGG_SIDE = (
        "class DisaggServer:\n"
        "    def serve(self, source, pending, results):\n"
        "        pf = self.prefill\n"
        "        while True:\n"
        "            # lint: mirror[ingest] begin\n"
        "            for req in source.poll(0):\n"
        "                pf._validate(req)\n"
        "                pending.append(req)\n"
        "            # lint: mirror[ingest] end\n"
    )

    def _fake(self, tmp_path, engine_text, disagg_text):
        pkg = tmp_path / "tree_attention_tpu" / "serving"
        pkg.mkdir(parents=True)
        (tmp_path / "tools").mkdir()
        (pkg / "engine.py").write_text(engine_text)
        (pkg / "disagg.py").write_text(disagg_text)
        return str(tmp_path)

    def test_renamed_identifiers_compare_equal(self, tmp_path, capsys):
        root = self._fake(tmp_path, self.ENGINE_SIDE, self.DISAGG_SIDE)
        rc = lint_main(["--root", root, "--rules", "mirror-drift",
                        "--baseline", str(tmp_path / "b.json")])
        capsys.readouterr()
        assert rc == 0

    def test_one_sided_edit_fails_both_directions(self, tmp_path,
                                                  capsys):
        drifted = self.DISAGG_SIDE.replace(
            "                pending.append(req)\n",
            "                pending.append(req)\n"
            "                self._count += 1\n",
        )
        root = self._fake(tmp_path, self.ENGINE_SIDE, drifted)
        for f in ("tree_attention_tpu/serving/engine.py",
                  "tree_attention_tpu/serving/disagg.py"):
            rc = lint_main(["--root", root, "--rules", "mirror-drift",
                            "--baseline", str(tmp_path / "b.json"), f])
            out = capsys.readouterr().out
            assert rc == 1 and "mirror[ingest]" in out, f

    def test_screaming_case_rename_is_drift(self, tmp_path, capsys):
        # Swapping one outcome constant for another is semantics, not
        # renaming — the normalizer keeps SCREAMING_CASE literal.
        eng = self.ENGINE_SIDE.replace(
            "                pending.append(r)\n",
            "                results.append(OUTCOME_SHED)\n",
        )
        dis = self.DISAGG_SIDE.replace(
            "                pending.append(req)\n",
            "                results.append(OUTCOME_CANCELLED)\n",
        )
        root = self._fake(tmp_path, eng, dis)
        rc = lint_main(["--root", root, "--rules", "mirror-drift",
                        "--baseline", str(tmp_path / "b.json")])
        capsys.readouterr()
        assert rc == 1

    def test_missing_twin_tag_flagged(self, tmp_path, capsys):
        dis = self.DISAGG_SIDE.replace("mirror[ingest]", "mirror[other]")
        root = self._fake(tmp_path, self.ENGINE_SIDE, dis)
        rc = lint_main(["--root", root, "--rules", "mirror-drift",
                        "--baseline", str(tmp_path / "b.json")])
        out = capsys.readouterr().out
        assert rc == 1 and "lost its twin" in out

    def test_region_deleted_on_one_side_caught_from_either_file(
            self, tmp_path, capsys):
        # Review finding: compare_sources only walked the LINTED file's
        # tags, so deleting a whole begin/end pair passed a --changed
        # run that linted only the edited file — the drift was caught
        # only when a full run happened to lint the twin.
        eng = self.ENGINE_SIDE.replace(
            "            # lint: mirror[ingest] begin\n", "").replace(
            "            # lint: mirror[ingest] end\n", "")
        root = self._fake(tmp_path, eng, self.DISAGG_SIDE)
        rc = lint_main(["--root", root, "--rules", "mirror-drift",
                        "--baseline", str(tmp_path / "b.json"),
                        "tree_attention_tpu/serving/engine.py"])
        out = capsys.readouterr().out
        assert rc == 1 and "lost its twin" in out

    def test_unpaired_marker_flagged(self, tmp_path, capsys):
        eng = self.ENGINE_SIDE.replace(
            "            # lint: mirror[ingest] end\n", "")
        dis = self.DISAGG_SIDE.replace(
            "            # lint: mirror[ingest] end\n", "")
        root = self._fake(tmp_path, eng, dis)
        rc = lint_main(["--root", root, "--rules", "mirror-drift",
                        "--baseline", str(tmp_path / "b.json")])
        out = capsys.readouterr().out
        assert rc == 1 and "without end" in out

    def test_current_tree_regions_paired_and_clean(self):
        from tools.lintlib import mirror
        eng = lintlib.Source(ENGINE, open(
            os.path.join(lintlib.REPO_ROOT, ENGINE)).read())
        dis = lintlib.Source(DISAGG, open(
            os.path.join(lintlib.REPO_ROOT, DISAGG)).read())
        regs_e, errs_e = mirror.regions(eng)
        regs_d, errs_d = mirror.regions(dis)
        assert errs_e == [] and errs_d == []
        # >= 7: the six sweep regions plus sweep-only (the idle-path
        # flight record is itself a mirrored block — review finding).
        assert sorted(regs_e) == sorted(regs_d) and len(regs_e) >= 7
        assert "sweep-only" in regs_e
        assert mirror.compare_sources(eng, dis) == []
        assert mirror.compare_sources(dis, eng) == []

    def test_pass_leaves_the_shared_tree_unmutated(self):
        # Review fix: normalization used to rename identifiers in the
        # Source's tree IN PLACE, corrupting the names every later pass
        # on the same Source analyzed.
        import ast
        dis = lintlib.Source(DISAGG, open(
            os.path.join(lintlib.REPO_ROOT, DISAGG)).read())
        before = ast.dump(dis.tree)
        lintlib.PASSES["mirror-drift"](dis)
        assert ast.dump(dis.tree) == before

    def test_singleton_token_nodes_carry_no_parent(self):
        # Perf fix (ISSUE 20): Load/Store/operator nodes are PARSER
        # SINGLETONS shared module-wide; stamping _lint_parent on one
        # aims it at the module's last user, and the region deepcopy
        # follows the pointer into an arbitrary module-sized parent
        # chain (the whole-repo lint blew its 10 s budget as engine.py
        # grew). Source must leave them unannotated.
        import ast
        src = lintlib.Source("x.py", "a = b + c\nd = [e for e in f]\n")
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.expr_context, ast.boolop,
                                 ast.operator, ast.unaryop, ast.cmpop)):
                assert not hasattr(node, "_lint_parent"), type(node)


# ---------------------------------------------------------------------------
# reintroducing burned-down bugs must fail lint (ISSUE 14 acceptance)


class TestReintroduction:
    def _copy_tree(self, tmp_path):
        import shutil
        pkg = tmp_path / "tree_attention_tpu" / "serving"
        pkg.mkdir(parents=True)
        (tmp_path / "tools").mkdir()
        for name in ("engine.py", "disagg.py"):
            shutil.copy(
                os.path.join(lintlib.REPO_ROOT,
                             "tree_attention_tpu", "serving", name),
                pkg / name,
            )
        return str(tmp_path)

    def test_deleting_a_relay_fails_lint(self, tmp_path, capsys):
        root = self._copy_tree(tmp_path)
        dis = tmp_path / "tree_attention_tpu" / "serving" / "disagg.py"
        lines = dis.read_text().splitlines(True)
        idx = [i for i, ln in enumerate(lines)
               if ln.strip() == "self._relay_pool(pf, dc)"]
        assert idx, "the relay sites moved; update this test"
        del lines[idx[-1]]
        dis.write_text("".join(lines))
        rc = lint_main(["--root", root, "--rules", "donation-safety",
                        "--baseline", str(tmp_path / "b.json"),
                        "tree_attention_tpu/serving/disagg.py"])
        out = capsys.readouterr().out
        assert rc == 1 and "donation-safety" in out

    def test_deleting_failure_arc_release_fails_lint(self, tmp_path,
                                                     capsys):
        root = self._copy_tree(tmp_path)
        eng = tmp_path / "tree_attention_tpu" / "serving" / "engine.py"
        text = eng.read_text()
        needle = (
            "        if not ok:\n"
            "            if nodes:\n"
            "                self._prefix.unpin_window(win_nodes)\n"
            "                self._prefix.release(nodes)\n"
            "            return None\n"
        )
        assert needle in text, "the reserve idiom moved; update this test"
        eng.write_text(text.replace(needle, (
            "        if not ok:\n"
            "            return None\n"
        ), 1))
        rc = lint_main(["--root", root, "--rules", "ledger-leak",
                        "--baseline", str(tmp_path / "b.json"),
                        "tree_attention_tpu/serving/engine.py"])
        out = capsys.readouterr().out
        assert rc == 1 and "ledger-leak" in out and "nodes" in out

    def test_editing_cancel_carry_ttl_one_side_fails_lint(self, tmp_path,
                                                          capsys):
        root = self._copy_tree(tmp_path)
        eng = tmp_path / "tree_attention_tpu" / "serving" / "engine.py"
        text = eng.read_text()
        assert "cancel_carry[uid] = 2" in text
        eng.write_text(text.replace("cancel_carry[uid] = 2",
                                    "cancel_carry[uid] = 3", 1))
        rc = lint_main(["--root", root, "--rules", "mirror-drift",
                        "--baseline", str(tmp_path / "b.json"),
                        "tree_attention_tpu/serving/disagg.py"])
        out = capsys.readouterr().out
        assert rc == 1 and "mirror[cancel-carry]" in out

    def test_editing_fork_sweep_one_side_fails_lint(self, tmp_path,
                                                    capsys):
        # The fork control-sweep arc (ISSUE 15) is a mirrored region:
        # growing the engine's side (an extra statement) without the
        # hand-port to disagg.py must fail lint from EITHER file.
        root = self._copy_tree(tmp_path)
        eng = tmp_path / "tree_attention_tpu" / "serving" / "engine.py"
        text = eng.read_text()
        needle = (
            "                forks = self._take_forks()\n"
            "                if forks or self._fork_carry:\n"
        )
        assert needle in text, "the fork sweep moved; update this test"
        eng.write_text(text.replace(needle, (
            "                forks = self._take_forks()\n"
            "                forks = sorted(forks)\n"
            "                if forks or self._fork_carry:\n"
        ), 1))
        for target in ("engine.py", "disagg.py"):
            rc = lint_main([
                "--root", root, "--rules", "mirror-drift",
                "--baseline", str(tmp_path / "b.json"),
                f"tree_attention_tpu/serving/{target}",
            ])
            out = capsys.readouterr().out
            assert rc == 1 and "mirror[fork]" in out, (target, out)


# ---------------------------------------------------------------------------
# the package itself + runner semantics


class TestFullPackage:
    def test_whole_repo_is_clean_against_empty_baseline(self):
        files = lintlib.discover_files()
        findings = lintlib.run_passes(files)
        assert [f.format() for f in findings] == []
        # and the committed baseline really is empty
        baseline = lintlib.load_baseline(
            os.path.join(lintlib.REPO_ROOT, "tools", "lint_baseline.json"))
        assert baseline == {}

    def test_lintlib_never_imports_jax_and_stays_cheap(self):
        # A fresh interpreter importing every pass and linting the WHOLE
        # repo must pull in neither jax nor numpy and finish well under
        # 10 s — the two properties that keep the linter tier-1-cheap
        # (the suite already runs near the 870 s ceiling) and usable as
        # a sub-second pre-commit hook via --changed.  Timed inside the
        # subprocess so interpreter startup is included but pytest
        # overhead is not.
        import subprocess
        code = (
            "import sys, time; sys.path.insert(0, {root!r})\n"
            "t0 = time.monotonic()\n"
            "from tools import lintlib\n"
            "findings = lintlib.run_passes(lintlib.discover_files())\n"
            "wall = time.monotonic() - t0\n"
            "heavy = [m for m in sys.modules\n"
            "         if m.split('.')[0] in ('jax', 'jaxlib', 'numpy')]\n"
            "assert not heavy, heavy\n"
            "assert findings == [], [f.format() for f in findings]\n"
            "assert wall < 10.0, f'whole-repo lint took {{wall:.1f}}s'\n"
        ).format(root=lintlib.REPO_ROOT)
        subprocess.run([sys.executable, "-c", code], check=True,
                       cwd=lintlib.REPO_ROOT)

    def test_engine_tick_fetch_is_annotated(self):
        # The per-tick host syncs are allow[]-annotated, not unscoped:
        # the verify-tick fused fetch, the mixed tick's token+logprob
        # fused fetch (ISSUE 15), and the token + logprob pair of
        # a tick that stepped nothing (a staged first token alone).
        path = os.path.join(lintlib.REPO_ROOT, ENGINE)
        with open(path) as fh:
            text = fh.read()
        assert text.count("lint: allow[host-sync]") == 4

    def test_disagg_tick_fetches_are_annotated(self):
        # One fetch point per worker per tick, all annotated: the
        # prefill worker's await fetch (token + logprob, ISSUE 15), the
        # decode worker's fused-verify fetch, and the decode worker's
        # fused token+logprob fetch.
        path = os.path.join(lintlib.REPO_ROOT, DISAGG)
        with open(path) as fh:
            text = fh.read()
        assert text.count("lint: allow[host-sync]") == 4


class TestRunner:
    BAD_ENGINE = (
        "import numpy as np\n"
        "class SlotServer:\n"
        "    def serve(self, requests):\n"
        "        return np.asarray(self.tok)\n"
    )

    def _fake_repo(self, tmp_path, bad=True):
        pkg = tmp_path / "tree_attention_tpu" / "serving"
        pkg.mkdir(parents=True)
        (tmp_path / "tools").mkdir()
        (pkg / "engine.py").write_text(
            self.BAD_ENGINE if bad else "x = 1\n")
        return str(tmp_path)

    def test_exit_1_on_new_violation(self, tmp_path, capsys):
        root = self._fake_repo(tmp_path)
        bl = tmp_path / "baseline.json"
        rc = lint_main(["--root", root, "--baseline", str(bl)])
        out = capsys.readouterr().out
        assert rc == 1 and "host-sync" in out and "FAIL" in out

    def test_exit_0_when_clean(self, tmp_path, capsys):
        root = self._fake_repo(tmp_path, bad=False)
        rc = lint_main(["--root", root,
                        "--baseline", str(tmp_path / "b.json")])
        assert rc == 0
        assert "OK" in capsys.readouterr().out

    def test_baseline_grandfathers_exactly_once(self, tmp_path, capsys):
        root = self._fake_repo(tmp_path)
        bl = tmp_path / "baseline.json"
        rc = lint_main(["--root", root, "--baseline", str(bl),
                        "--write-baseline"])
        assert rc == 0 and bl.exists()
        # same single finding -> baselined, exit 0
        rc = lint_main(["--root", root, "--baseline", str(bl)])
        capsys.readouterr()
        assert rc == 0
        # a SECOND identical violation exceeds the multiplicity
        eng = (tmp_path / "tree_attention_tpu" / "serving" / "engine.py")
        eng.write_text(self.BAD_ENGINE
                       + "        y = np.asarray(self.cache)\n")
        rc = lint_main(["--root", root, "--baseline", str(bl)])
        assert rc == 1

    def test_json_output_shape(self, tmp_path, capsys):
        root = self._fake_repo(tmp_path)
        rc = lint_main(["--root", root, "--json",
                        "--baseline", str(tmp_path / "b.json")])
        data = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert data["new"] and data["findings"]
        f = data["new"][0]
        assert {"rule", "path", "line", "col", "message"} <= set(f)

    def test_unknown_rule_errors(self, capsys):
        rc = lint_main(["--rules", "no-such-pass"])
        assert rc == 2

    def test_absolute_file_paths_normalized_into_scope(self, tmp_path,
                                                       capsys):
        # Review finding: an absolute path spelling must not lint as
        # out-of-scope-everything and report OK.
        root = self._fake_repo(tmp_path)
        abs_engine = os.path.join(root, "tree_attention_tpu", "serving",
                                  "engine.py")
        rc = lint_main(["--root", root,
                        "--baseline", str(tmp_path / "b.json"),
                        abs_engine])
        out = capsys.readouterr().out
        assert rc == 1 and "host-sync" in out

    def test_write_baseline_refuses_subset_runs(self, tmp_path, capsys):
        # Review finding: a subset run sees a subset of findings —
        # writing it would erase every other entry in the baseline.
        root = self._fake_repo(tmp_path)
        bl = tmp_path / "baseline.json"
        rc = lint_main(["--root", root, "--baseline", str(bl),
                        "--rules", "obs-guard", "--write-baseline"])
        assert rc == 2 and not bl.exists()
        rc = lint_main(["--root", root, "--baseline", str(bl),
                        "tree_attention_tpu/serving/engine.py",
                        "--write-baseline"])
        assert rc == 2 and not bl.exists()

    def test_rules_filter(self, tmp_path, capsys):
        root = self._fake_repo(tmp_path)
        rc = lint_main(["--root", root, "--rules", "obs-guard",
                        "--baseline", str(tmp_path / "b.json")])
        assert rc == 0  # the host-sync finding is filtered out

    def _git(self, root, *argv):
        import subprocess
        subprocess.run(
            ["git", "-C", root, "-c", "user.email=l@l", "-c",
             "user.name=lint", *argv],
            check=True, capture_output=True,
        )

    def test_changed_lints_only_files_differing_vs_head(self, tmp_path,
                                                        capsys):
        # Pre-commit loop: a clean tree lints 0 files; dirtying the
        # engine (unstaged) or adding an untracked in-scope file brings
        # exactly those files into the run.
        root = self._fake_repo(tmp_path, bad=False)
        self._git(root, "init", "-q")
        self._git(root, "add", "-A")
        self._git(root, "commit", "-qm", "seed")
        bl = str(tmp_path / "b.json")
        rc = lint_main(["--root", root, "--changed", "--baseline", bl])
        out = capsys.readouterr().out
        assert rc == 0 and "0 files changed" in out
        # unstaged edit vs HEAD
        eng = tmp_path / "tree_attention_tpu" / "serving" / "engine.py"
        eng.write_text(self.BAD_ENGINE)
        rc = lint_main(["--root", root, "--changed", "--baseline", bl])
        out = capsys.readouterr().out
        assert rc == 1 and "host-sync" in out and "1 files" in out
        # untracked in-scope file joins; out-of-scope untracked doesn't
        (tmp_path / "tree_attention_tpu" / "serving"
         / "extra.py").write_text("x = 1\n")
        (tmp_path / "notes.txt").write_text("not python\n")
        rc = lint_main(["--root", root, "--changed", "--baseline", bl])
        out = capsys.readouterr().out
        assert rc == 1 and "2 files" in out

    def test_changed_intersects_explicit_files(self, tmp_path, capsys):
        # --changed plus explicit files = the intersection (lint just
        # the file I'm editing, but only if it actually changed).
        root = self._fake_repo(tmp_path, bad=False)
        self._git(root, "init", "-q")
        self._git(root, "add", "-A")
        self._git(root, "commit", "-qm", "seed")
        eng = tmp_path / "tree_attention_tpu" / "serving" / "engine.py"
        eng.write_text(self.BAD_ENGINE)
        bl = str(tmp_path / "b.json")
        rc = lint_main(["--root", root, "--changed", "--baseline", bl,
                        "tools/lint.py"])  # changed ∩ {lint.py} = ∅
        out = capsys.readouterr().out
        assert rc == 0 and "0 files changed" in out
        rc = lint_main(["--root", root, "--changed", "--baseline", bl,
                        "tree_attention_tpu/serving/engine.py"])
        assert rc == 1

    def test_changed_normalizes_absolute_file_args(self, tmp_path,
                                                   capsys):
        # Review fix: the intersection/fallback branches skipped the
        # relpath normalization the plain files branch has — an
        # absolute spelling intersected to nothing and reported OK for
        # a file that DID change.
        root = self._fake_repo(tmp_path, bad=False)
        self._git(root, "init", "-q")
        self._git(root, "add", "-A")
        self._git(root, "commit", "-qm", "seed")
        eng = tmp_path / "tree_attention_tpu" / "serving" / "engine.py"
        eng.write_text(self.BAD_ENGINE)
        bl = str(tmp_path / "b.json")
        rc = lint_main(["--root", root, "--changed", "--baseline", bl,
                        str(eng)])
        out = capsys.readouterr().out
        assert rc == 1 and "host-sync" in out

    def test_changed_zero_files_respects_json(self, tmp_path, capsys):
        # Review fix: the clean-tree fast path printed a human line,
        # crashing machine consumers of --json.
        root = self._fake_repo(tmp_path, bad=False)
        self._git(root, "init", "-q")
        self._git(root, "add", "-A")
        self._git(root, "commit", "-qm", "seed")
        rc = lint_main(["--root", root, "--changed", "--json",
                        "--baseline", str(tmp_path / "b.json")])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert data == {"files": 0, "findings": [], "new": [],
                        "baselined": 0}

    def test_changed_root_below_git_toplevel(self, tmp_path, capsys):
        # Review finding: `git diff --name-only` emits TOPLEVEL-relative
        # names; with --root a subdir of the git repo they never
        # intersected the root-relative scope, so a dirty tree reported
        # '0 files changed OK'. --relative rebases them against root.
        inner = tmp_path / "inner"
        inner.mkdir()
        root = self._fake_repo(inner, bad=False)
        self._git(str(tmp_path), "init", "-q")
        self._git(str(tmp_path), "add", "-A")
        self._git(str(tmp_path), "commit", "-qm", "seed")
        eng = inner / "tree_attention_tpu" / "serving" / "engine.py"
        eng.write_text(self.BAD_ENGINE)
        bl = str(tmp_path / "b.json")
        rc = lint_main(["--root", root, "--changed", "--baseline", bl])
        out = capsys.readouterr().out
        assert rc == 1 and "host-sync" in out and "1 files" in out

    def test_changed_without_git_falls_back_to_explicit_args(
            self, tmp_path, capsys):
        # No .git under --root: explicit file args keep working, and a
        # bare --changed is a usage error (exit 2), not a silent OK.
        root = self._fake_repo(tmp_path)
        bl = str(tmp_path / "b.json")
        rc = lint_main(["--root", root, "--changed", "--baseline", bl])
        err = capsys.readouterr().err
        assert rc == 2 and "--changed needs git" in err
        rc = lint_main(["--root", root, "--changed", "--baseline", bl,
                        "tree_attention_tpu/serving/engine.py"])
        out = capsys.readouterr().out
        assert rc == 1 and "host-sync" in out
