"""``chip_smoke.py`` off the chip: it refuses to run (and prints no result),
its phase functions work end to end at a tiny size in interpret mode, and the
compile-cache helper places the cache where the environment says.

The real run — Yi-6B widths, compiled kernels — only happens on a TPU through
the chip tool; this keeps the script's control flow from rotting between chip
runs. The phases that compile the most programs on the CPU (int8 serve, train,
the program listing, the four-chip path) are marked ``slow`` to keep the
default tier inside its clock.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve the module by name
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


@pytest.fixture(autouse=True, scope="module")
def _restore_process_state():
    """The phases call ``cli.main`` in this process, which configures the
    package logger (handlers, ``propagate = False``) and installs signal
    handlers: put both back, or later modules' ``caplog`` sees nothing."""
    import logging
    import signal

    logger = logging.getLogger("tree_attention_tpu")
    saved = (logger.level, logger.propagate, list(logger.handlers))
    signals = {sig: signal.getsignal(sig)
               for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGUSR1)}
    yield
    for h in list(logger.handlers):
        logger.removeHandler(h)
    logger.setLevel(saved[0])
    logger.propagate = saved[1]
    for h in saved[2]:
        logger.addHandler(h)
    for sig, handler in signals.items():
        signal.signal(sig, handler)

# Width 128, 2 layers, interpret mode: every phase in seconds on the CPU.
TINY = smoke.Sizes(
    model_dim=128, heads=4, kv_heads=2, vocab=512, dtype="float32",
    serve_layers=2, train_layers=2, sharded_layers=2,
    slots=2, prompt_len=24, prompt_jitter=8, max_new=4,
    prefix_len=16, prefix_block=8, prefill_chunk=16,
    requests=4, requests_int8=2,
    agree_prompt=32, agree_steps=2,
    train_seq=128, train_steps=3,
    interpret=True,
    tol_kernel=2e-2, tol_kernel_int8=6e-2, tol_grad=6e-2,
    tol_logits=1e-3, tol_logits_rms=1e-4,
    tree_heads=4, tree_ctx=1024,
)


def test_refuses_to_run_without_a_tpu():
    """As the driver runs it, but on this CPU: non-zero, and no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert proc.stdout.strip() == ""
    assert "no CPU fallback" in proc.stderr


def test_phase_device_names_what_jax_found(tmp_path):
    d = smoke.phase_device(str(tmp_path))
    assert d["platform"] == "cpu" and d["device_count"] == len(jax.devices())
    assert d["jax"] == jax.__version__
    assert d["compile_cache_dir"] == str(tmp_path)
    assert isinstance(d["native_library_loaded"], bool)


def test_phase_kernels_against_reference():
    d = smoke.phase_kernels(TINY)
    assert set(d["kernels"]) == {
        "paged_decode_tq1", "paged_chunk_tq64", "paged_tree_verify_tq8",
        "paged_int8_q8q_block_scales", "paged_int8_q8_block_scales",
        "paged_local_blocks_partial", "paged_row_write_tq1",
        "conv_tail_step_tq1", "prefill_fwd", "bwd_dq", "bwd_dkv",
    }
    assert all(k["ok"] for k in d["kernels"].values())
    # The row path leaves the block path's pool, bit for bit.
    assert d["kernels"]["paged_row_write_tq1"]["max_abs_err"] == 0.0
    # ... and the conv layers' one-launch step the XLA path's rows and pool.
    assert d["kernels"]["conv_tail_step_tq1"]["max_abs_err"] == 0.0


@pytest.mark.parametrize("int8", [
    pytest.param(False, id="exact"),
    pytest.param(True, id="int8", marks=pytest.mark.slow),
])
def test_phase_serve_through_the_cli(int8):
    d = smoke.phase_serve(TINY, int8=int8)
    n = TINY.requests_int8 if int8 else TINY.requests
    assert d["requests"] == n and d["tokens_generated"] == n * TINY.max_new
    assert d["prefix"]["hits"] >= 1
    assert d["dispatch_counters"]["forward_step_dispatch_total"]


def test_phase_ingress_streams_cancels_and_drains():
    d = smoke.phase_ingress(TINY)
    assert d["outcomes"] == {"budget": 3, "cancelled": 1}
    assert d["client"]["streamed"]["completion_tokens"] == TINY.max_new
    assert d["client"]["shared_b"]["prefix_hit_tokens"] >= TINY.prefix_len


def test_phase_agreement_with_the_plain_forward():
    d = smoke.phase_agreement(TINY)
    assert d["max_abs_err"] <= TINY.tol_logits


def test_phase_hybrid_serves_a_hit_and_a_fork_against_the_reference():
    """The hybrid phase at ``tests/test_hybrid_conv.py``'s small preset:
    blocks of 8, float32 (a served token lies at the reference's best to
    1e-4)."""
    from tests.test_hybrid_conv import SMALL

    d = smoke.phase_hybrid(TINY, dict(SMALL), device="cpu", block=8,
                           chunk=16, tol_gap=1e-4)
    assert d["prefix_hit_tokens"] == 32 and d["forks"] == 1
    assert d["tokens_compared"] == 4 * 12 and d["gap_max"] <= 1e-4
    assert d["layers"].count("conv") == 5
    assert d["kv_block_fixed_bytes"] == 5 * 2 * 64 * 4


def test_phase_window_serves_a_hit_a_fork_and_a_reused_slot():
    """The window phase at ``tests/test_window_moe.py``'s small preset:
    window 8, blocks of 4, float32 (a served token lies at the reference's
    best to 1e-4); the cold request outgrows three windows and three
    blocks, its slot never holds more than the bound."""
    from tests.test_window_moe import SMALL

    d = smoke.phase_window(TINY, dict(SMALL), device="cpu", block=4,
                           chunk=8, tol_gap=1e-4)
    assert d["prefix_hit_tokens"] == 12 and d["forks"] == 1
    assert d["tokens_compared"] == 20 + 4 * 20 and d["gap_max"] <= 1e-4
    assert d["layers"].count("window") == 3
    assert d["window_blocks_peak_slot"] <= d["window_blocks_bound"] == 5
    assert d["window_blocks_freed"] >= 4
    assert d["window_token_bytes"] == 3 * d["kv_token_bytes"]


def test_phase_state_serves_a_reused_slot_and_ragged_chunks():
    """The state phase at ``tests/test_state_space.py``'s small preset:
    blocks of 4, chunks of 16 over a scan blocked in 8, float32 (a served
    token lies at the reference's best to 1e-4)."""
    from tests.test_state_space import SMALL

    d = smoke.phase_state(TINY, dict(SMALL), device="cpu", block=4,
                          chunk=16, tol_gap=1e-4)
    assert d["layers"] == ["ssm", "ssm", "attention", "ssm"]
    assert d["ffn"] == ["expert", "none", "expert", "none"]
    assert d["ssm_state"] == [3, 2, 2, 16, 64]
    assert d["ssm_state_dtype"] == "float32"
    assert d["tokens_compared"] == 6 + 3 * 5 and d["gap_max"] <= 1e-4


def test_phase_eva_serves_three_boundaries_and_a_reused_slot():
    """The EVA phase at ``tests/test_eva.py``'s small preset: window 32 in
    chunks of 4, blocks of 8, float32 (a served token lies at the
    reference's best to 1e-4); the long request crosses three window
    boundaries, its slot never holds more exact rows' blocks than the
    bound."""
    from tests.test_eva import SMALL

    d = smoke.phase_eva(TINY, dict(SMALL), device="cpu", block=8, chunk=16,
                        tol_gap=1e-4)
    assert d["layers"] == ["eva"] and d["gap_max"] <= 1e-4
    assert d["tokens_compared"] == 20 + 2 * 6 + 4
    assert d["window_blocks_peak_slot"] <= d["window_blocks_bound"] == 7
    assert d["window_blocks_freed"] >= 3 * 4
    # 101 positions a slot: 13 blocks of exact rows a table row, 4 of
    # summaries; the exact rows' pool a constant of blocks a slot.
    assert d["tables"] == [[2, 4], [2, 13]]
    assert d["local_pool"][:2] == [2, 2 * 8] \
        and d["summary_pool"][:2] == [2, 2 * 4]


def test_phase_parallel_serves_two_mixers_a_layer_through_a_reused_slot():
    """The parallel phase at ``tests/test_parallel_mixer.py``'s small preset
    (every layer a state-space mixer AND rotary GQA 5 / 1 on one norm, no
    multiplier at 1): blocks of 4, chunks of 16 over a scan blocked in 8,
    float32 (a served token lies at the reference's best to 1e-4)."""
    from tests.test_parallel_mixer import SMALL

    d = smoke.phase_parallel(TINY, dict(SMALL), device="cpu", block=4,
                             chunk=16, tol_gap=1e-4)
    assert d["layers"] == ["parallel"] * 3 and d["ffn"] == ["dense"] * 3
    assert d["ssm_state"] == [3, 2, 2, 32, 32]
    assert d["ssm_state_dtype"] == "float32"
    # K/V rows in all three layers: 2 x 1 KV head x 16 x 4 B a layer.
    assert d["kv_token_bytes"] == 3 * 2 * 16 * 4
    assert d["tokens_compared"] == 6 + 3 * 5 and d["gap_max"] <= 1e-4


def test_phase_decoder_hybrid_serves_past_the_window_and_a_reused_slot():
    """The decoder-hybrid phase at ``tests/test_decoder_hybrid.py``'s small
    preset cut to 8 layers (every kind of layer, both periods single):
    blocks of 4, chunks of 16, a window of 8, float32 (a served token lies
    at the reference's best to 1e-3)."""
    from tests.test_decoder_hybrid import SMALL

    d = smoke.phase_decoder_hybrid(
        TINY, dict(SMALL, num_hidden_layers=8), device="cpu", block=4,
        chunk=16, tol_gap=1e-3)
    assert d["layers"] == ["ssm1", "window"] * 2 + [
        "ssm1", "attention", "gmu", "cross"] and d["row_cut"] == 6
    assert d["shared_rows"][0] == 1 and d["window_rows"][0] == 2
    assert d["ssm_state"] == [3, 1, 8, 256]
    assert d["ssm_state_dtype"] == "float32"
    assert d["window_blocks_freed"] > 0
    assert d["tokens_compared"] == 48 + 5 and d["gap_max"] <= 1e-3


@pytest.mark.slow
def test_phase_train_and_programs():
    assert smoke.phase_train(TINY)["losses"][-1] < 6.3
    d = smoke.phase_programs(TINY)
    # Interpret mode leaves no tpu_custom_call behind: the phase lists the
    # programs and (off the chip) gates nothing on them.
    assert "serve.mixed.tq1" in d["kernels_found"]
    assert "serve_int8.stage_chunk.tq16" in d["kernels_found"]
    assert "train.step" in d["kernels_found"]
    assert d["train_state_bytes_per_param"] > 0


@pytest.mark.slow
def test_four_chip_phases_on_virtual_devices(capsys, tmp_path):
    """Rehearsal 2: the ``--chips 4`` path on the virtual CPU devices (one
    ``seq`` mesh over all of them, as on the chips)."""
    run = smoke.Run(str(tmp_path))
    smoke.run_four_chips(run, TINY)
    lines = {l["phase"]: l for l in map(
        json.loads, capsys.readouterr().out.splitlines())}
    assert run.ok, lines
    n = len(jax.devices())
    assert len(lines["tree_decode"]["shard_device_ids"]) == n
    sharded = lines["serve_seq_sharded"]
    assert set(sharded["blocks_per_shard"].values()) == {
        sharded["pool_blocks"] // n}
    assert lines["fleet_placement"]["replicas"] == n


def test_run_prints_one_json_line_per_phase_and_fails_closed(capsys, tmp_path):
    run = smoke.Run(str(tmp_path))
    assert run.phase("good", lambda: {"x": 1})
    assert not run.phase("bad", lambda: smoke.check(False, "nope"))
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [(l["phase"], l["ok"]) for l in lines] == [
        ("good", True), ("bad", False)]
    assert "nope" in lines[1]["error"] and not run.ok
    assert smoke.phase_times(run)["wall_s_by_phase"].keys() == {"good", "bad"}


def test_compile_cache_helper_honours_the_environment(monkeypatch):
    from tree_attention_tpu import cli

    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert cli.configure_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in seen  # JAX reads the env itself
    seen.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cli.configure_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert seen["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".jax_cache")
    assert seen["jax_persistent_cache_min_compile_time_secs"] < 1.0
