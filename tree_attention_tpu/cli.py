"""CLI driver: the reference's ``python3 model.py``, grown into a real tool.

The reference entrypoint (``/root/reference/model.py:129-169``) hardcodes one
workload, times one un-fenced call, and prints nothing checkable. Here
``python -m tree_attention_tpu`` with no flags reproduces that workload —
single-query decode over a 64k-token context, 16 heads × 128 — but measured
honestly (fenced, repeated, median) and steered by real flags (SURVEY.md §5):

    python -m tree_attention_tpu                       # reference workload
    python -m tree_attention_tpu --mesh seq=4          # sequence-parallel
    python -m tree_attention_tpu --device cpu --n-virtual-cpu 8 --mesh seq=8
    python -m tree_attention_tpu --mode train --seq-len 2048 --mesh seq=4
    python -m tree_attention_tpu --mode bench --comparator ring ...
    python -m tree_attention_tpu --mode generate --seq-len 128

Modes: ``decode`` (one attention step over a KV cache), ``train`` (LM steps on
the flagship transformer), ``generate`` (prefill + autoregressive decode),
``serve`` (continuous batching: a slot scheduler drains a synthetic request
trace), ``bench`` (the harness; prints one JSON record on stdout).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import sys
import time
from typing import Any, Callable, Optional

from tree_attention_tpu import obs
from tree_attention_tpu.utils.config import RunConfig, parse_args
from tree_attention_tpu.utils.logging import get_logger, setup_logging

log = get_logger("cli")


def _report_record(report) -> dict:
    """``ServeReport.as_dict()`` for the one-line record: of the tick
    programs' tables (there while tracing is on) the labels alone; the
    rows are in the flight recorder's dump. Likewise of the start-up
    record."""
    rec = report.as_dict()
    if "programs" in rec:
        rec["programs"] = [dict(t["program"], ops=len(t["ops"]))
                           for t in rec["programs"]]
    if "startup" in rec:
        # Of the start-up record the phases' seconds alone; the spans are
        # in the flight recorder's dump and under /healthz.
        rec["startup"] = rec["startup"]["seconds"]
    return rec


def _pick_free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _relaunch(cfg: RunConfig, argv: Optional[list]) -> int:
    """``--launch N``: respawn this command as N coordinated processes.

    The multi-host shape (``jax.distributed`` cluster, device pool spanning
    processes) on one machine — the working version of the reference's
    ``mp.spawn`` + hardcoded rendezvous (``model.py:20-21,165``). Uses the
    native fork/exec launcher; ranks and the coordinator address travel by
    environment (see :func:`initialize_distributed
    <tree_attention_tpu.parallel.mesh.initialize_distributed>`).
    """
    from tree_attention_tpu.host_runtime import launch_local

    args = list(sys.argv[1:] if argv is None else argv)
    # Strip --launch so children run the command directly.
    child_args = []
    skip = False
    for a in args:
        if skip:
            skip = False
            continue
        # --metrics-port is parent-only too: N children binding one port
        # would race; the parent keeps the live endpoint.
        parent_only = (
            "--launch", "--launch-timeout", "--heartbeat-stall",
            "--restarts", "--metrics-port",
        )
        if a in parent_only:
            skip = True
            continue
        if a.startswith(tuple(f + "=" for f in parent_only)):
            continue
        child_args.append(a)
    # Elastic restart is only a *resume* if the children restore their
    # latest checkpoint. When the user did NOT pass --resume themselves,
    # the parent adds it and threads the absolute step target, so a
    # restarted child COMPLETES the original --steps budget (an empty
    # --ckpt-dir makes --resume a fresh start, so adding it is safe —
    # though note a --restarts run against a ckpt-dir with prior state
    # declares that state resumable and will continue it). When the user
    # passed --resume explicitly, its documented continuation contract
    # ("run --steps MORE") is kept: each restart attempt then runs --steps
    # from its own restore point, so a crash can extend the total run —
    # bounded, since checkpoints only move forward.
    elastic_resume = bool(
        cfg.restarts and cfg.ckpt_dir and "--resume" not in child_args
    )
    if elastic_resume:
        child_args.append("--resume")
    elif cfg.restarts and cfg.ckpt_dir:
        log.warning(
            "--restarts with explicit --resume keeps continuation "
            "semantics: each restart runs --steps more from its restore "
            "point rather than completing one fixed budget"
        )
    cmd = [sys.executable, "-m", "tree_attention_tpu", *child_args]
    from tree_attention_tpu.host_runtime import require_cpu_children

    try:
        require_cpu_children(f"--launch {cfg.launch}", child_args)
    except RuntimeError as e:
        raise SystemExit(str(e)) from None
    log.info("launching %d coordinated processes: %s", cfg.launch, cmd)
    # The coordinator address travels to the children via inherited env;
    # restore the parent's env afterwards so a later in-process run doesn't
    # find a stale coordinator.
    prev = {
        k: os.environ.get(k) for k in ("TA_COORDINATOR", "TA_TRAIN_TOTAL_STEPS")
    }
    os.environ["TA_COORDINATOR"] = f"localhost:{_pick_free_port()}"
    if elastic_resume and cfg.mode == "train":
        # A restarted child must COMPLETE the original budget, not run
        # --steps more from its restored point (_run_train reads this).
        os.environ["TA_TRAIN_TOTAL_STEPS"] = str(cfg.steps)
    try:
        failures, statuses = launch_local(
            cmd, cfg.launch, timeout=cfg.launch_timeout,
            heartbeat_stall=cfg.heartbeat_stall, restarts=cfg.restarts,
        )
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if cfg.restarts and not failures:
        from tree_attention_tpu.host_runtime import last_launch_attempts

        attempts = last_launch_attempts()
        if attempts > 1:
            log.warning("launch: recovered after %d attempt(s)", attempts)
    if failures:
        log.error("launch: %d/%d ranks failed: %s", failures, cfg.launch,
                  statuses)
    return 1 if failures else 0


def _emit(record: dict) -> None:
    """Print the run's one JSON record — from process 0 only."""
    import jax

    if jax.process_index() == 0:
        print(json.dumps(record))


#: Where the persistent compile cache lives when the environment does not
#: place it: a fixed path inside the checkout (the path is part of the
#: cache's key, so a directory that moves never hits).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Call before the first compile (the CLI, ``bench.py`` and
    ``chip_smoke.py`` do). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and this sets no other directory; otherwise the cache
    goes to :data:`COMPILE_CACHE_DIR`. The serving engine compiles one
    small program per chunk/verify/table-width bucket, lazily, so the
    minimum compile time for keeping an entry is lowered to keep them all.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return cache_dir


def _configure_backend(cfg: RunConfig) -> None:
    """Pick the platform before any JAX backend initialises.

    Must run before the first device query. ``--n-virtual-cpu`` implies the
    CPU platform (the virtual-device flag only affects the CPU client).
    ``--device auto`` takes what JAX finds; the startup log line names it.
    """
    device = cfg.device
    if cfg.n_virtual_cpu > 0:
        device = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cfg.n_virtual_cpu}"
            ).strip()
    import jax

    if device != "auto":
        jax.config.update("jax_platforms", device)
    configure_compile_cache()


def _require_devices(cfg: RunConfig) -> None:
    """First device query: a platform that was asked for by name and is
    not there is a flag error with a message, not a traceback."""
    import jax

    try:
        jax.devices()
    except RuntimeError as e:
        raise SystemExit(
            f"--device {cfg.device}: JAX could not initialise that "
            f"platform on this machine ({str(e).splitlines()[0]}). "
            "Nothing ran; pass --device cpu to run on the host on purpose."
        ) from None


def _build_mesh(cfg: RunConfig):
    from tree_attention_tpu.parallel.mesh import make_mesh

    axes = cfg.mesh_axes()
    if axes is None:
        return None
    return make_mesh(axes)


def _dtype(cfg: RunConfig):
    import jax.numpy as jnp

    return jnp.dtype(cfg.dtype)


def _run_decode(cfg: RunConfig, mesh) -> int:
    """The reference workload: one decode step, timed; parity with
    ``main()`` at ``/root/reference/model.py:129-155``."""
    import jax

    from tree_attention_tpu.bench.harness import bench_decode

    res = bench_decode(cfg, mesh)
    log.info(
        "decode: %d KV tokens, %d heads x %d, %s, %d device(s)",
        cfg.seq_len, cfg.heads, cfg.head_dim, cfg.dtype,
        1 if mesh is None else mesh.size,
    )
    log.info(
        "median %.4fs per step  (%.0f KV tokens/s, %.2e FLOP/s)",
        res.timing.median, res.tokens_per_sec, res.flops_per_sec,
    )
    if res.peak_hbm_bytes:
        log.info("peak HBM: %.1f MiB", res.peak_hbm_bytes / 2**20)
    _emit(res.as_dict())
    return 0


def _run_bench(cfg: RunConfig, mesh) -> int:
    from tree_attention_tpu.bench.harness import run_bench

    record = run_bench(cfg, mesh)
    _emit(record)
    return 0


def _transformer_config(cfg: RunConfig):
    import jax.numpy as jnp

    from tree_attention_tpu.models import TransformerConfig

    d_head = cfg.model_dim // cfg.heads
    return TransformerConfig(
        vocab_size=cfg.vocab_size,
        d_model=cfg.model_dim,
        n_layers=cfg.n_layers,
        n_heads=cfg.heads,
        n_kv_heads=cfg.resolved_kv_heads(),
        d_head=d_head,
        d_ff=int(8 * cfg.model_dim / 3 + 127) // 128 * 128,
        max_seq_len=max(cfg.seq_len, 128),
        dtype=_dtype(cfg),
        attn_impl=cfg.impl,
        attn_block_size=cfg.block_size,
        seq_layout=cfg.seq_layout,
    )


def _run_train(cfg: RunConfig, mesh) -> int:
    """LM training steps on the flagship model (the capability the reference
    lacks entirely — no loss, no backward, no optimizer)."""
    import jax

    import jax.numpy as jnp

    from tree_attention_tpu.data import make_lm_batch
    from tree_attention_tpu.models import (
        count_params, default_optimizer, init_train_state, make_train_step,
        shard_batch,
    )
    from tree_attention_tpu.utils.profiling import time_fn

    if cfg.steps < 1:
        # Throughput timing below reuses the last training batch; with no
        # steps there is neither a batch nor anything meaningful to time.
        raise SystemExit("train mode requires --steps >= 1")
    tcfg = _transformer_config(cfg)
    opt = default_optimizer()
    state = init_train_state(jax.random.PRNGKey(cfg.seed), tcfg, opt, mesh=mesh)
    # Donation reuses the old state's buffers — unsafe while an async
    # checkpoint save may still be reading them, so it's off when saving.
    step = make_train_step(tcfg, opt, mesh=mesh, donate=not cfg.ckpt_dir)
    log.info(
        "transformer: %d params, %d layers, d_model %d, seq %d",
        count_params(state[0]), tcfg.n_layers, tcfg.d_model, cfg.seq_len,
    )
    if cfg.resume and not cfg.ckpt_dir:
        raise SystemExit("--resume requires --ckpt-dir")
    ckpt = start_step = None
    if cfg.ckpt_dir:
        import contextlib

        from tree_attention_tpu.checkpoint import Checkpointer, load_model_config

        ckpt = Checkpointer(cfg.ckpt_dir, save_interval_steps=cfg.ckpt_every)
        if cfg.resume and ckpt.latest_step() is not None:
            with contextlib.suppress(FileNotFoundError):
                saved_cfg = load_model_config(cfg.ckpt_dir)
                if saved_cfg != tcfg:
                    raise SystemExit(
                        f"checkpoint config in {cfg.ckpt_dir} disagrees with "
                        f"the CLI flags:\n  saved: {saved_cfg}\n  flags: {tcfg}"
                    )
            state, start_step = ckpt.restore(state)
            log.info("resumed from step %d", start_step)
    start = 0 if start_step is None else start_step + 1
    # Plain --resume keeps its documented continuation semantics: run
    # --steps MORE steps from the restored point. An elastic restart
    # (--launch --restarts) instead completes the ORIGINAL budget — a
    # restart is a resume, not a redo — so the parent threads the absolute
    # target through the environment alongside the rank protocol.
    end = start + cfg.steps
    total = os.environ.get("TA_TRAIN_TOTAL_STEPS")
    if total is not None:
        end = max(int(total), start)
    key = jax.random.PRNGKey(cfg.seed + 1)
    pipe = None
    corpus = None
    if cfg.data:
        from tree_attention_tpu.host_runtime import (
            HostCorpusPipeline, TokenCorpus, native_available,
        )

        # Real data: mmap'd token corpus, same resume contract as the
        # synthetic pipeline (batch k is a pure function of (seed, k)).
        corpus = TokenCorpus(cfg.data, dtype=cfg.data_dtype)
        pipe = HostCorpusPipeline(
            corpus, cfg.batch, cfg.seq_len, cfg.seed + 1, start=start,
        )
        log.info(
            "corpus pipeline: %s (%d tokens, native=%s)",
            cfg.data, len(corpus), native_available(),
        )
    elif cfg.host_data:
        from tree_attention_tpu.host_runtime import HostDataPipeline, native_available

        # Batch content is a pure function of (seed, step index), so resume
        # starts the pipeline at `start` — no replayed training data.
        pipe = HostDataPipeline(
            (cfg.batch, cfg.seq_len + 1), tcfg.vocab_size, cfg.seed + 1,
            start=start,
        )
        log.info("host data pipeline (native=%s)", native_available())

    def next_batch(i):
        if pipe is None:
            return make_lm_batch(
                jax.random.fold_in(key, i), batch=cfg.batch,
                seq_len=cfg.seq_len, vocab_size=tcfg.vocab_size, mesh=mesh,
            )
        toks = pipe.next()  # numpy; slice as host views, one transfer each
        if corpus is not None:
            # XLA's gather clamps out-of-range ids, which would silently
            # train on garbage; fail loudly instead. Cheap: a host max over
            # one batch.
            hi = int(toks.max())
            if hi >= tcfg.vocab_size:
                raise SystemExit(
                    f"corpus token id {hi} >= --vocab-size "
                    f"{tcfg.vocab_size} (step {i}); retokenize or raise "
                    f"--vocab-size"
                )
        b = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
        if mesh is not None:
            return shard_batch(mesh, b)
        return {k: jnp.asarray(v) for k, v in b.items()}

    losses = []
    saved_last = True
    try:
        from tree_attention_tpu.host_runtime import heartbeat, maybe_inject_fault

        for i in range(start, end):
            maybe_inject_fault(i)  # env-armed test crash (supervision/elastic)
            batch = next_batch(i)
            state, loss = step(state, batch)
            losses.append(float(loss))
            heartbeat()  # after the fetch: real per-step progress, not dispatch
            log.info("step %d: loss %.4f", i, losses[-1])
            if ckpt is not None:
                saved_last = ckpt.save(i, state, cfg=tcfg)
        if ckpt is not None and not saved_last and end > start:
            # The save interval skipped the final step; the resumable state
            # must include all completed work.
            ckpt.save(end - 1, state, cfg=tcfg, force=True)
        if end == start:
            # Restarted after the budget was already complete: nothing to
            # train this attempt (losses stays empty), but the record still
            # needs a batch to time the compiled step against — fetched
            # here, while the data pipeline/corpus are still open.
            batch = next_batch(start)
    finally:
        if pipe is not None:
            pipe.close()
        if corpus is not None:
            corpus.close()
        if ckpt is not None:
            ckpt.close()
    # Throughput of the compiled step (last batch, post-compile). Timing
    # re-runs with the same state, so a donating step can't be reused —
    # with --ckpt-dir the step is already non-donating.
    step_t = step if cfg.ckpt_dir else make_train_step(
        tcfg, opt, mesh=mesh, donate=False
    )
    stats = time_fn(step_t, state, batch, iters=max(cfg.iters, 1), warmup=1)
    toks = cfg.batch * cfg.seq_len
    log.info(
        "train step: median %.4fs (%.0f tokens/s)",
        stats.median, toks / stats.median,
    )
    _emit({
        "mode": "train",
        "losses": losses,
        "tokens_per_sec": round(toks / stats.median, 1),
        **stats.as_dict(),
    })
    return 0


def _run_generate(cfg: RunConfig, mesh) -> int:
    import jax

    from tree_attention_tpu.models import generate, init_params

    if cfg.temperature < 0:
        raise SystemExit("--temperature must be >= 0 (0 = greedy)")
    if cfg.max_new_tokens < 1:
        raise SystemExit("--max-new-tokens must be >= 1")
    if cfg.kv_quant != "none" and cfg.impl not in ("auto", "pallas_decode"):
        # Same rejection the bench surface gives this flag pair.
        raise SystemExit(
            f"--kv-quant {cfg.kv_quant} runs a pallas_decode q8 kernel; "
            f"--impl {cfg.impl} cannot serve a quantized buffer"
        )
    tcfg = _transformer_config(cfg)
    params = init_params(jax.random.PRNGKey(cfg.seed), tcfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(cfg.seed + 1), (cfg.batch, max(cfg.q_len, 1)),
        0, tcfg.vocab_size,
    )
    from tree_attention_tpu.host_runtime import heartbeat

    n_new = cfg.max_new_tokens
    # Generation is one dispatch: progress granularity is the whole call,
    # so a watchdog stall window must cover it.
    heartbeat()
    toks = generate(
        params, prompt, n_new, tcfg,
        temperature=cfg.temperature, key=jax.random.PRNGKey(cfg.seed + 2),
        mesh=mesh,
        quantize_after_prefill=cfg.kv_quant != "none",
        quant_kernel=cfg.resolved_quant_kernel() or "q8q",
    )
    toks = jax.block_until_ready(toks)
    heartbeat()
    log.info(
        "generated %s tokens from a %s prompt%s",
        toks.shape, prompt.shape,
        f" ({cfg.kv_quant} KV cache)" if cfg.kv_quant != "none" else "",
    )
    _emit({
        "mode": "generate",
        "tokens": toks.tolist(),
        **({"kv_quant": cfg.kv_quant} if cfg.kv_quant != "none" else {}),
    })
    return 0


@dataclasses.dataclass
class ServeSetup:
    """What ``--mode serve`` builds from its flags before any traffic: the
    model, the slot capacity, and the engine factory (one call per engine —
    the fleet tier makes several)."""

    tcfg: Any                       # TransformerConfig at the slot capacity
    params: Any
    cache_len: int
    host_blocks: int
    decode_slots: Optional[int]     # --serve-disagg only
    make_engine: Callable[[], Any]  # -> SlotServer | DisaggServer


def load_model_config(path: str) -> dict:
    """``--model-config``'s file: a JSON object in a published
    ``config.json``'s own keys. ``models.transformer.model_from_config``
    reads it, and its docstring lists the keys a family at a time: a
    Llama-style dense decoder's (``hidden_size``, ``num_hidden_layers``,
    ``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
    ``vocab_size``, ``rms_norm_eps``, ``rope_theta``); a latent-attention
    expert model's (``kv_lora_rank``, ``q_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``n_routed_experts``,
    ``n_shared_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``first_k_dense_replace``, ``topk_method``, ``n_group``, ``topk_group``,
    ``routed_scaling_factor``, ``norm_topk_prob``, ``scoring_func``,
    ``rope_scaling``); the shortcut-connected double layer's (``num_layers``,
    ``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``,
    ``zero_expert_num``, ``mla_scale_q_lora``, ``mla_scale_kv_lora``); a
    conv / attention hybrid's (``layer_types``, ``conv_L_cache``,
    ``conv_bias``, ``num_experts``, ``num_dense_layers``, ``norm_eps``,
    ``use_expert_bias``, ``tie_word_embeddings``); and this repo's own two
    groups, ``deployment`` (a file cut to one chip's share) and ``block``
    (what only the modelling code says)."""
    import json

    try:
        with open(path) as f:
            model = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"--model-config {path}: {e}") from None
    if not isinstance(model, dict) or "hidden_size" not in model:
        raise SystemExit(
            f"--model-config {path}: not a model configuration (a JSON "
            f"object in a published config.json's keys: hidden_size, the "
            f"layer count, ...)")
    return model


# What a model served from the latent pool, the hybrid pool or the window
# pools (``TransformerConfig.cache_kind``) is not served with: the flag's test and
# the mechanism's name, refused at build and never served wrong.
_POOL_REFUSALS = (
    (lambda c: c.kv_quant != "none", "--kv-quant (int8 rows)"),
    (lambda c: c.kv_shard == "seq",
     "--kv-shard seq (a sequence-sharded pool)"),
    (lambda c: c.kv_tiering == "on" and c.host_blocks > 0,
     "--host-blocks (the host tier)"),
    (lambda c: c.speculate,
     "--speculate (the latent kernel takes no tree_mask; a conv layer's "
     "tail or a recurrent state cannot roll a rejected draft back; a window "
     "layer's freed blocks cannot come back, nor can a summary row an EVA "
     "layer wrote be unwritten)"),
    (lambda c: c.serve_disagg,
     "--serve-disagg (the handoff of that pool's arrays)"),
)


def build_serve_engine(cfg: RunConfig, mesh, *, model=None,
                       params=None) -> ServeSetup:
    """Validate the serve flags and build the model and the engine factory
    — the ONE construction every serve front end shares (the synthetic
    trace, ``--serve-http``, ``--serve-fleet``) and ``chip_smoke.py`` calls
    to inspect the engine the CLI serves with.

    ``model`` is the model as data: a dict in a published ``config.json``'s
    keys (what ``--model-config <file>`` loads); it takes the place of the
    model flags (``--model-dim``, ``--heads``, ...), which build the
    Llama-style block as before. ``params`` are the weights to serve, in
    the served type and the block's own layout (``init_params``'); without
    them the program draws its own from ``--seed``, leaf by leaf in the
    served type. Either way ``ServeSetup.params`` is what the engines serve
    from: the same tree with the attention input projections re-laid
    (``serving.engine.serving_params``), which every function of the model
    takes as it takes the outer format."""
    import jax

    # The start-up record (obs/flight.py): this call is four spans end to
    # end. The device is resolved first; where nothing asked before (a
    # caller that is not ``main``), that is the backend's client starting.
    t_enter = time.monotonic()
    devices = jax.devices()
    t_devices = time.monotonic()
    obs.STARTUP.add("backend", t_enter, t_devices,
                    platform=devices[0].platform, devices=len(devices))

    from tree_attention_tpu.models import init_params
    from tree_attention_tpu.serving import SlotServer
    from tree_attention_tpu.serving.engine import serving_params

    if model is None and cfg.model_config:
        model = load_model_config(cfg.model_config)

    if cfg.max_new_tokens < 1:
        raise SystemExit("--max-new-tokens must be >= 1")
    if cfg.slots < 1:
        raise SystemExit("--slots must be >= 1")
    if cfg.prompt_len - cfg.prompt_jitter < 1:
        raise SystemExit("--prompt-jitter must leave prompts >= 1 token")
    if cfg.prefill_chunk < 1:
        raise SystemExit("--prefill-chunk must be >= 1")
    if cfg.prefill_budget is not None and cfg.prefill_budget < 1:
        raise SystemExit("--prefill-budget must be >= 1")
    if cfg.kv_quant != "none" and cfg.impl not in ("auto", "pallas_decode"):
        raise SystemExit(
            f"--kv-quant {cfg.kv_quant} runs a pallas_decode q8 kernel; "
            f"--impl {cfg.impl} cannot serve a quantized buffer"
        )
    if cfg.max_queue < 1:
        raise SystemExit("--max-queue must be >= 1")
    if cfg.serve_fleet and cfg.serve_http is not None:
        raise SystemExit(
            "--serve-fleet and --serve-http are exclusive: the router "
            "IS the fleet's HTTP front door (it listens on --router-port)"
        )
    if cfg.serve_fleet and cfg.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    decode_slots: Optional[int] = None
    if cfg.serve_disagg:
        if cfg.serve_fleet:
            raise SystemExit(
                "--serve-disagg and --serve-fleet are exclusive (a "
                "disaggregated fleet tier is not built yet; run one "
                "disaggregated pair per process)"
            )
        if cfg.prefill_slots < 1:
            raise SystemExit("--prefill-slots must be >= 1")
        decode_slots = (cfg.decode_slots if cfg.decode_slots is not None
                        else cfg.slots - cfg.prefill_slots)
        if decode_slots < 1:
            raise SystemExit(
                f"--serve-disagg needs >= 1 decode slot: --slots "
                f"{cfg.slots} minus --prefill-slots {cfg.prefill_slots} "
                f"leaves {decode_slots} (pass --decode-slots or raise "
                f"--slots)"
            )
    if cfg.default_deadline is not None and cfg.default_deadline <= 0:
        raise SystemExit("--default-deadline must be > 0 seconds")
    # --speculate composes with sampling (ISSUE 20): temperature > 0
    # runs the stochastic (Leviathan) accept walk, which emits the
    # target distribution exactly — no greedy restriction.
    if cfg.top_k < 0:
        raise SystemExit("--top-k must be >= 0 (0 = off)")
    if cfg.temperature < 0:
        raise SystemExit("--temperature must be >= 0 (0 = greedy)")
    if cfg.speculate and not 1 <= cfg.draft_k <= 31:
        raise SystemExit("--draft-k must be in [1, 31]")
    if not 0.0 <= cfg.prefix_share <= 1.0:
        raise SystemExit("--prefix-share must be in [0, 1]")
    if cfg.prefix_cache and (cfg.prefix_block < 1
                             or cfg.prefix_block & (cfg.prefix_block - 1)):
        raise SystemExit("--prefix-block must be a power of two >= 1")
    if cfg.host_blocks < 0:
        raise SystemExit("--host-blocks must be >= 0")
    host_blocks = cfg.host_blocks if cfg.kv_tiering == "on" else 0
    if host_blocks:
        if not cfg.prefix_cache:
            raise SystemExit(
                "--host-blocks KV tiering requires --prefix-cache "
                "(demotion is what radix eviction becomes; with no "
                "radix tree nothing ever demotes)"
            )
    if cfg.kv_block is not None and (cfg.kv_block < 1
                                     or cfg.kv_block & (cfg.kv_block - 1)):
        raise SystemExit("--kv-block must be a power of two >= 1")
    if cfg.kv_blocks is not None and cfg.kv_blocks < 1:
        raise SystemExit("--kv-blocks must be >= 1")
    if cfg.prefix_cache \
            and cfg.kv_block is not None and cfg.kv_block != cfg.prefix_block:
        # The engine enforces this too (radix matching happens at page
        # granularity); surface it as the clean flag-error every other
        # serve-mode misuse gets, not a traceback.
        raise SystemExit(
            f"--prefix-block {cfg.prefix_block} must equal --kv-block "
            f"{cfg.kv_block} (or pass only one of them)"
        )
    # The cache is sized from the trace itself: longest possible prompt
    # plus the per-request budget, through the same rounding rule
    # generate() uses.
    from tree_attention_tpu.models.decode import round_cache_len

    cache_len = round_cache_len(
        cfg.prompt_len + cfg.prompt_jitter + cfg.max_new_tokens, mesh
    )
    if cfg.prefix_cache and cfg.prefix_block > cache_len:
        # Same clean rejection every sibling flag misuse gets — the
        # engine would raise the equivalent ValueError as a traceback.
        raise SystemExit(
            f"--prefix-block {cfg.prefix_block} exceeds the trace's slot "
            f"capacity {cache_len} (prompt-len + jitter + max-new-tokens, "
            f"rounded)"
        )
    if model is not None:
        from tree_attention_tpu.models.transformer import model_from_config

        try:
            tcfg = model_from_config(
                model, dtype=_dtype(cfg), max_seq_len=max(cache_len, 128),
                attn_impl=cfg.impl, attn_block_size=cfg.block_size,
                seq_layout=cfg.seq_layout,
            )
        except (KeyError, ValueError) as e:
            raise SystemExit(f"model configuration: {e!r}") from None
        if tcfg.cache_kind != "kv":
            for refused, what in _POOL_REFUSALS:
                if refused(cfg):
                    raise SystemExit(
                        f"a model served from the {tcfg.cache_kind} pool is "
                        f"not served with {what}: not built yet (ROADMAP "
                        f"2A)")
        if tcfg.cache_kind in ("state", "eva", "state_window") \
                and cfg.prefix_cache:
            raise SystemExit(
                f"a model served from the {tcfg.cache_kind} pool is not "
                f"served with --prefix-cache (a hit needs the recurrent "
                f"state, or the summary rows, at the matched boundary): "
                f"not built yet (ROADMAP 2A)")
    else:
        tcfg = _transformer_config(
            dataclasses.replace(cfg, seq_len=cache_len))
    # ``startup:engine`` so far: the flags held, the model read. Then
    # ``startup:params``: the weights drawn, where none were handed in
    # (the draw is queued: the span that waits for it is the next), and
    # re-laid, which ``serving_params`` records itself.
    t_params = time.monotonic()
    obs.STARTUP.add("engine", t_devices, t_params)
    if params is None:
        params = init_params(jax.random.PRNGKey(cfg.seed), tcfg)
        obs.STARTUP.add("params", t_params)
    # Re-laid here, once, for every engine ``make_engine`` builds (a
    # fleet's replicas share the tree), and so that nothing built here
    # keeps the outer format's leaves alive beside the served ones.
    params = serving_params(params)
    t_served = time.monotonic()
    if cfg.slo_ttft <= 0 or cfg.slo_tbt <= 0:
        raise SystemExit("--slo-ttft and --slo-tbt must be > 0")
    # The paged pool has ONE device budget (--kv-blocks) and one host
    # budget (--host-blocks); the PR-6-deprecated --prefix-pool-blocks
    # alias is gone (ISSUE 13) — the engine API keeps the retention-cap
    # kwarg for tests, but the CLI no longer exposes the old split.
    kv_blocks = cfg.kv_blocks
    drafter = cfg.drafter
    if cfg.speculate and cfg.drafter == "model":
        # A shrunk draft transformer (half the layers, same vocab) from
        # its own seed — the two-model speculative shape, CPU-proxy
        # sized. Acceptance depends on how well it tracks the big model;
        # the free 'ngram' drafter is the default for a reason.
        from tree_attention_tpu.serving.speculation import (
            DraftModelDrafter,
        )

        draft_cfg = dataclasses.replace(
            tcfg, n_layers=max(tcfg.n_layers // 2, 1)
        )
        drafter = DraftModelDrafter(
            init_params(jax.random.PRNGKey(cfg.seed + 3), draft_cfg),
            draft_cfg,
        )
    engine_kw = dict(
        slots=cfg.slots, cache_len=cache_len, mesh=mesh,
        quantize=cfg.kv_quant != "none",
        quant_kernel=cfg.resolved_quant_kernel() or "q8q",
        temperature=cfg.temperature, top_k=cfg.top_k, seed=cfg.seed + 2,
        prefill_chunk=cfg.prefill_chunk,
        prefill_budget=cfg.prefill_budget,
        slo_ttft=cfg.slo_ttft,
        slo_tbt=cfg.slo_tbt,
        prefix_cache=cfg.prefix_cache,
        prefix_block=cfg.prefix_block,
        kv_block=cfg.kv_block,
        kv_blocks=kv_blocks,
        kv_shard=cfg.kv_shard,
        host_blocks=host_blocks,
        speculate=cfg.speculate,
        draft_k=cfg.draft_k,
        drafter=drafter,
    )

    def make_engine():
        if cfg.serve_disagg:
            # The disaggregated pair (ISSUE 12): same seams as a fused
            # SlotServer, so the ingress below works unchanged on top.
            from tree_attention_tpu.serving.disagg import DisaggServer

            disagg_kw = {k: v for k, v in engine_kw.items()
                         if k != "slots"}
            return DisaggServer(
                params, tcfg, prefill_slots=cfg.prefill_slots,
                decode_slots=decode_slots, **disagg_kw,
            )
        return SlotServer(params, tcfg, **engine_kw)

    # The rest of ``startup:engine`` here; ``SlotServer.__init__`` adds
    # its own for every engine ``make_engine`` builds.
    obs.STARTUP.add("engine", t_served)
    return ServeSetup(
        tcfg=tcfg, params=params, cache_len=cache_len,
        host_blocks=host_blocks, decode_slots=decode_slots,
        make_engine=make_engine,
    )


def _run_serve(cfg: RunConfig, mesh) -> int:
    """Continuous batching over a synthetic request trace: the slot
    scheduler admits/retires requests while one compiled ragged decode step
    serves every live slot per tick (``tree_attention_tpu/serving``)."""
    from tree_attention_tpu.host_runtime import heartbeat
    from tree_attention_tpu.serving import synthetic_trace

    setup = build_serve_engine(cfg, mesh)
    tcfg, cache_len = setup.tcfg, setup.cache_len
    host_blocks, decode_slots = setup.host_blocks, setup.decode_slots
    make_engine = setup.make_engine

    if cfg.serve_fleet:
        # The fleet tier (ISSUE 11): --replicas in-process engines, each
        # behind its own loopback ingress, fronted by the cache-aware
        # router — one process, N engines (the CPU-proxy honest shape;
        # ProcessReplica + FleetSupervisor serve the multi-host story).
        from tree_attention_tpu.serving.fleet import (
            FleetSupervisor,
            LocalReplica,
            install_fleet_drain_signals,
        )
        from tree_attention_tpu.serving.router import FleetRouter

        if not cfg.prefix_cache:
            log.warning(
                "--serve-fleet without --prefix-cache: affinity routing "
                "groups shared prefixes per replica, but no replica can "
                "reuse them — expect no TTFT win"
            )
        reps = [
            LocalReplica(
                f"r{i}", make_engine,
                max_queue=cfg.max_queue,
                default_deadline_s=cfg.default_deadline,
                default_max_tokens=cfg.max_new_tokens,
            )
            for i in range(cfg.replicas)
        ]
        router = FleetRouter(
            port=cfg.router_port,
            block=cfg.prefix_block,
            affinity=cfg.affinity == "on",
        )
        fleet = FleetSupervisor(reps, router=router)
        drained = install_fleet_drain_signals(fleet)
        port = fleet.start()
        log.info(
            "serving fleet on http://127.0.0.1:%d/v1/completions "
            "(%d replica(s) x %d slot(s), cache_len %d, affinity %s) — "
            "SIGTERM rolls the fleet down gracefully",
            port, cfg.replicas, cfg.slots, cache_len, cfg.affinity,
        )
        heartbeat()
        drained.wait()  # blocks until SIGTERM/SIGINT
        fleet.stop()
        heartbeat()
        _emit({
            "mode": "serve",
            "fleet": {
                "router_port": port,
                "replicas": cfg.replicas,
                "affinity": cfg.affinity,
                "router": router.stats(),
                "leaks": fleet.leak_reports(),
            },
            "slots": cfg.slots,
            "cache_len": cache_len,
        })
        return 0

    server = make_engine()
    if _METRICS_HTTP["server"] is not None:
        # /slots introspection (ISSUE 16): the exporter started before
        # the engine existed; wire it now.
        _METRICS_HTTP["server"].attach_engine(server)

    if cfg.serve_http is not None:
        # The live ingress (ISSUE 10): serve real HTTP traffic until a
        # drain signal (SIGTERM/SIGINT) winds the engine down; no
        # synthetic trace — the slot capacity is still sized from
        # --prompt-len/--prompt-jitter/--max-new-tokens.
        from tree_attention_tpu.serving.ingress import (
            IngressServer, install_drain_signals,
        )

        ingress = IngressServer(
            server,
            port=cfg.serve_http,
            max_queue=cfg.max_queue,
            default_deadline_s=cfg.default_deadline,
            default_max_tokens=cfg.max_new_tokens,
        )
        install_drain_signals(ingress)
        port = ingress.start()
        log.info(
            "serving HTTP on http://127.0.0.1:%d/v1/completions "
            "(%d slot(s), cache_len %d, max queue %d%s) — SIGTERM "
            "drains gracefully",
            port, cfg.slots, cache_len, cfg.max_queue,
            f", default deadline {cfg.default_deadline}s"
            if cfg.default_deadline is not None else "",
        )
        heartbeat()
        report = ingress.join()  # blocks until drained
        ingress.stop()
        heartbeat()
        if report is None:
            # The engine thread died instead of draining — a crash must
            # not masquerade as a clean exit.
            log.error("engine loop crashed: %r", ingress.engine_error)
            return 1
        _emit({
            "mode": "serve",
            "ingress": {"port": port, "max_queue": cfg.max_queue,
                        "default_deadline_s": cfg.default_deadline},
            "slots": cfg.slots,
            "cache_len": cache_len,
            **({"disagg": {"prefill_slots": cfg.prefill_slots,
                           "decode_slots": decode_slots}}
               if cfg.serve_disagg else {}),
            **(_report_record(report) if report is not None else {}),
        })
        return 0

    trace = synthetic_trace(
        cfg.requests,
        prompt_len=cfg.prompt_len,
        prompt_jitter=cfg.prompt_jitter,
        max_new_tokens=cfg.max_new_tokens,
        arrival_every=cfg.arrival_every,
        vocab_size=tcfg.vocab_size,
        seed=cfg.seed + 1,
        prefix_share=cfg.prefix_share,
        prefix_len=cfg.prefix_len,
    )
    heartbeat()
    report = server.serve(trace)
    heartbeat()
    log.info(
        "served %d requests on %d slot(s): %.1f tokens/s aggregate, "
        "mean occupancy %.2f",
        len(report.results), cfg.slots, report.tokens_per_sec,
        report.mean_occupancy,
    )
    _emit({
        "mode": "serve",
        "slots": cfg.slots,
        "cache_len": cache_len,
        "prefill_chunk": cfg.prefill_chunk,
        **({"disagg": {"prefill_slots": cfg.prefill_slots,
                       "decode_slots": decode_slots}}
           if cfg.serve_disagg else {}),
        **({"speculate": {"draft_k": cfg.draft_k, "drafter": cfg.drafter}}
           if cfg.speculate else {}),
        **({"prefix_cache": {
            "block": cfg.prefix_block,
        }} if cfg.prefix_cache else {}),
        **({"kv_tiering": {"host_blocks": host_blocks}}
           if host_blocks else {}),
        # Outcome counts ride ServeReport.as_dict (the ISSUE 10 outcome
        # vocabulary threaded through the report).
        **_report_record(report),
        **({"kv_quant": cfg.kv_quant} if cfg.kv_quant != "none" else {}),
    })
    return 0


#: The live telemetry exporter, when --metrics-port started one — the
#: seam _run_serve uses to late-wire the engine behind /slots (the
#: exporter starts before the engine exists).
_METRICS_HTTP: dict = {"server": None}


def _start_metrics_http(cfg: RunConfig):
    """Start the live telemetry endpoint, or return None without the flag.

    /metrics needs the registry recording, /healthz + /flight need the
    ring armed, and /requests needs the request ledger armed even when no
    exit sinks were asked for (memory-only rings serve all three).
    """
    if cfg.metrics_port is None:
        return None
    obs.REGISTRY.enable()
    obs.STARTUP.publish()   # the gauge, of what closed before the registry
    if not obs.FLIGHT.enabled:
        obs.FLIGHT.arm()
    if not obs.REQLOG.enabled:
        obs.REQLOG.arm()
    from tree_attention_tpu.obs.http import MetricsHTTPServer

    server = MetricsHTTPServer(cfg.metrics_port)
    port = server.start()
    _METRICS_HTTP["server"] = server
    log.info(
        "telemetry endpoint: http://127.0.0.1:%d/metrics "
        "(/metrics.json /healthz /flight /requests /slots)", port,
    )
    return server


def main(argv: Optional[list] = None) -> int:
    cfg = parse_args(argv)
    # Under --launch, every child would otherwise open (and rotate) the same
    # file, corrupting each other's sink — rank-suffix the children's path.
    log_file = cfg.log_file
    if log_file and os.environ.get("TA_COORDINATOR"):
        log_file = f"{log_file}.p{os.environ.get('JAX_PROCESS_INDEX', '0')}"
    setup_logging(
        getattr(logging, cfg.log_level.upper()),
        log_file=log_file,
        all_processes=cfg.all_processes,
    )
    http_server = None
    try:
        if cfg.launch > 1:
            # The parent records launcher metrics; children re-run main()
            # with the same flags and rank-suffix their own sinks.
            obs.configure(
                metrics_out=cfg.metrics_out, trace_events=cfg.trace_events
            )
            # The parent serves the live endpoint (--metrics-port is
            # stripped from children): its launcher/heartbeat metrics are
            # the multi-process run's live view.
            http_server = _start_metrics_http(cfg)
            obs.install_crash_handlers()
            return _relaunch(cfg, argv)
        t_backend = time.monotonic()
        _configure_backend(cfg)

        import jax

        from tree_attention_tpu.parallel.mesh import initialize_distributed
        from tree_attention_tpu.utils.profiling import trace

        initialize_distributed()
        _require_devices(cfg)
        # The program's first device query, so the backend's client
        # starting (the TPU's takes seconds): ``startup:backend``.
        obs.STARTUP.add("backend", t_backend, platform=jax.default_backend(),
                        devices=jax.device_count())
        # Telemetry arms AFTER distributed init so the tracer's pid and the
        # metrics path's rank suffix see the real process index — on
        # auto-detected multi-host runs neither TA_COORDINATOR nor
        # JAX_PROCESS_INDEX exists in the environment.
        obs.configure(
            metrics_out=cfg.metrics_out, trace_events=cfg.trace_events,
            flight_out=cfg.flight_out,
        )
        http_server = _start_metrics_http(cfg)
        if (obs.REGISTRY.enabled or obs.TRACER.active
                or obs.FLIGHT.enabled):
            # An interrupted run still flushes its sinks (atexit +
            # SIGTERM; SIGUSR1 dumps the flight ring and keeps running).
            obs.install_crash_handlers()
        log.info(
            "backend=%s devices=%d mesh=%s mode=%s",
            jax.default_backend(), jax.device_count(), cfg.mesh or "none",
            cfg.mode,
        )
        mesh = _build_mesh(cfg)
        runner = {
            "decode": _run_decode,
            "train": _run_train,
            "generate": _run_generate,
            "serve": _run_serve,
            "bench": _run_bench,
        }[cfg.mode]
        with trace(cfg.profile_dir), obs.span(
            f"mode:{cfg.mode}",
            args=None if not obs.TRACER.active else {"mesh": cfg.mesh},
        ):
            return runner(cfg, mesh)
    finally:
        if http_server is not None:
            http_server.stop()
        sinks = obs.shutdown()
        if sinks["metrics_out"] or sinks["trace_events"] \
                or sinks["flight_out"]:
            # The exit snapshot contract of --metrics-out /
            # --trace-events / --flight-out.
            log.info(
                "telemetry: metrics=%s trace=%s flight=%s",
                sinks["metrics_out"] or "-", sinks["trace_events"] or "-",
                sinks["flight_out"] or "-",
            )


# The last line of this module's body: the process's start to here is the
# interpreter, this package's import (JAX's inside it) and this module's.
obs.STARTUP.add("import", obs.STARTUP.t_process)

if __name__ == "__main__":
    sys.exit(main())
