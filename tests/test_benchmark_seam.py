"""The seam by which the benchmark finds a model family by name, guarded by
tier-1 (``benchmark/tests/test_seam.py`` holds the slower rehearsals, which
tier-1 does not run), and the cells that came through it: ``dsv2_codegen_sat``,
``lcflash_agentturn_sat``, ``lfm2_agentturn_sat``,
``kexaone_reasoning_long_sat``, ``nemotron3s_agentturn_sat`` and
``evabyte_bytedoc_sat``.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.spec import Spec, SpecError  # noqa: E402

BENCH = os.path.join(ROOT, "BENCHMARK.json")
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal",
                         "BENCHMARK.json")
CELL = "dsv2_codegen_sat"

# What only a family's own files, the configuration files, the kernels' cost
# functions and the benchmark's tests may say: the dense block's keys and
# leaves, and the latent / expert family's.
FAMILY_WORDS = ("hidden_size", "num_key_value_heads", "num_attention_heads",
                "intermediate_size", "head_dim", "rope_theta", "rms_norm_eps",
                "_transformer_config", "init_params", "kv_lora_rank",
                "q_lora_rank", "n_routed_experts", "first_k_dense_replace",
                "num_experts_per_tok", "rope_scaling", "ffn_hidden_size",
                "expert_ffn_hidden_size", "moe_topk", "zero_expert_num",
                "mla_scale_q_lora", "mla_scale_kv_lora", "routed_branch",
                "layer_types", "conv_L_cache", "num_dense_layers",
                "use_expert_bias", "tie_word_embeddings", "router_scoring",
                "sliding_window", "mlp_layer_types", "num_shared_experts",
                "rotary_layers", "scale_renormed", "norm_placement")
LEAVES = ("embed", "ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3", "w2",
          "ln_f", "wout", "wqa", "wqb", "wkva", "wkvb", "router", "we1",
          "router_bias", "sub", "w_in", "w_conv", "w_out", "q_ln", "k_ln",
          "wattn")
THE_FAMILYS_OWN = ("references", "adapters", "configs", "kernel_costs",
                   "tests")
# What PR 35 appended, in order: a tick's device time by part of the model.
PARTS_DENSE = ["dec_proj_ms_tick", "dec_attn_ms_tick", "dec_ffn_ms_tick",
               "dec_head_ms_tick", "mix_proj_ms_tick",
               "mix_attn_decode_ms_tick", "mix_attn_chunk_ms_tick",
               "mix_ffn_ms_tick", "mix_head_ms_tick", "tick_unscoped_pct"]
PARTS_MOE = PARTS_DENSE[:4] + ["dec_moe_ms_tick"] + PARTS_DENSE[4:9] + [
    "mix_moe_ms_tick", "tick_unscoped_pct"]
PARTS_ALL = PARTS_MOE[:5] + ["dec_conv_ms_tick"] + PARTS_MOE[5:11] + [
    "mix_conv_ms_tick", "tick_unscoped_pct"]
# What PR 37 appended after them, for every cell.
AFTER_PARTS = ["paged_steps_run_pct"]
# What PR 50 appended last of all, for every cell: set-up from inside.
SETUP_METRICS = ["setup_import_s", "setup_engine_s", "setup_programs_s",
                 "setup_programs_cached_pct", "setup_serve_s",
                 "setup_unseen_s", "programs_built_in_window"]


# What PR 52 appended after those, for its own cell.
DECODER_HYBRID_METRICS = ["ssm1_scan_ms_tick", "ssm1_scan_roofline",
                          "ssm1_states_advanced_pct", "rows_past_exit_pct"]


def before_setup(entries):
    """A per-layer list (a cell's or the file's; entries or their names)
    without PR 50's seven, which close every list but for PR 52's four
    after them (the file's, and its own cell's): the older PRs' tails are
    counted from what is left (``tests/test_startup_record.py`` holds the
    seven themselves)."""
    names = [m["name"] if isinstance(m, dict) else m for m in entries]
    if names[-len(DECODER_HYBRID_METRICS):] == DECODER_HYBRID_METRICS:
        entries = entries[:-len(DECODER_HYBRID_METRICS)]
        names = names[:-len(DECODER_HYBRID_METRICS)]
    assert names[-len(SETUP_METRICS):] == SETUP_METRICS
    return entries[:-len(SETUP_METRICS)]
# What PR 38 appended last, for its own cell.
WINDOW_METRICS = ["window_attn_ms_tick", "window_decode_paged_roofline",
                  "window_blocks_held_pct"]
KX_CELL = "kexaone_reasoning_long_sat"
# What PR 40 appended last, for its own cell.
STATE_METRICS = ["ssm_update_ms_tick", "ssm_decode_update_roofline",
                 "moe_ungated_ms_tick", "moe_ungated_matmul_roofline",
                 "ssm_states_advanced_pct"]
NS_CELL = "nemotron3s_agentturn_sat"
# What PR 44 appended last, for its own cell.
EVA_METRICS = ["eva_local_ms_tick", "eva_local_decode_roofline",
               "eva_summary_ms_tick", "eva_summary_decode_roofline",
               "eva_summaries_written_pct"]
EVA_CELL = "evabyte_bytedoc_sat"
# PR 47 appended no metric: its cell joins the lists of the metrics it reports.
FH_CELL = "falconh1_agentturn_sat"
PF_CELL = "phi4flash_reasoning_long_sat"


def _sources(but=()):
    top = os.path.join(ROOT, "benchmark")
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"
                   and not (base == top and d in but)]
        for f in files:
            if not f.endswith(".pyc"):
                path = os.path.join(base, f)
                with open(path, errors="replace") as fh:
                    yield os.path.relpath(path, top), fh.read()


def test_no_key_of_a_family_outside_its_own_files():
    leaf = re.compile(r"""["'](%s)["']""" % "|".join(LEAVES))
    seen = 0
    for rel, text in _sources(but=THE_FAMILYS_OWN):
        seen += 1
        for word in FAMILY_WORDS:
            assert word not in text, (rel, word)
        assert not leaf.search(text), (rel, leaf.search(text).group(0))
        if rel.endswith(".py"):
            assert "llama_dense" not in text, rel
            assert "deepseek_mla_moe" not in text, rel
            assert "longcat_scmoe" not in text, rel
    assert seen >= 48                   # the harness, the readers, the mixes
    for family in ("llama_dense", "deepseek_mla_moe", "longcat_scmoe"):
        with open(os.path.join(ROOT, "benchmark", "adapters",
                               family + ".py")) as f:
            assert "hidden_size" in f.read()     # the test's own control


def test_a_configuration_without_a_family_is_a_spec_error(tmp_path):
    with open(REHEARSAL) as f:
        bench = json.load(f)
    with open(os.path.join(os.path.dirname(REHEARSAL), "configs",
                           "tiny.json")) as f:
        config = json.load(f)
    del config["family"]
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(config))
    bench["paths"] = [os.path.dirname(REHEARSAL),
                      os.path.join(ROOT, "benchmark")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SpecError, match="names no family"):
        Spec(str(tmp_path / "BENCHMARK.json")).cell("tiny_sat")
    config["family"] = "no_such_family"
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(config))
    cell = Spec(str(tmp_path / "BENCHMARK.json")).cell("tiny_sat")
    with pytest.raises(SpecError, match="no_such_family"):
        cell.reference()


def test_a_familys_files_are_found_by_its_name_over_paths():
    spec = Spec(REHEARSAL)
    tied, dense = spec.cell("tiny_tied_sat"), spec.cell("tiny_sat")
    rehearsal = os.path.dirname(REHEARSAL)
    assert tied.reference().__file__ == os.path.join(
        rehearsal, "references", "toy_tied.py")
    assert tied.adapter().__file__ == os.path.join(
        rehearsal, "adapters", "toy_tied.py")
    assert dense.reference().__file__ == os.path.join(
        ROOT, "benchmark", "references", "llama_dense.py")
    bench = Spec(BENCH)
    for w in bench.data["workloads"]:
        cell = bench.cell(w["name"])
        for mod in (cell.reference(),):
            assert all(hasattr(mod, n) for n in (
                "Widths", "init_weights", "logits_at", "CONTROLS"))
        assert cell.adapter().build and cell.adapter().kernel_call


def test_the_new_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell = spec.cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "deepseek_mla_moe"
    assert cell.reference().__file__.endswith(
        os.path.join("references", "deepseek_mla_moe.py"))
    assert cell.adapter().__file__.endswith(
        os.path.join("adapters", "deepseek_mla_moe.py"))
    assert cell.traffic["kind"] == "backlog"
    spec.load_module("generators", cell.traffic["kind"] + ".py").Generator
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    for name in ("mla_decode_ms_tick", "mla_decode_paged_roofline",
                 "moe_ffn_ms_tick", "moe_grouped_matmul_roofline",
                 "experts_touched_pct", "expert_rows_max_over_mean",
                 "occupancy_pct", "kv_blocks_peak_pct", "hbm_peak_gb",
                 "tick_rows_useful_pct", "decode_tick_p50_ms",
                 "device_idle_pct"):
        assert name in names, name
    for kernel in ("mla_decode_paged", "moe_grouped_matmul"):
        assert cell.adapter().kernel_call(cell.config, kernel) is not None
        assert spec.load_module("kernel_costs", kernel + ".py").cost
    # The other cells do not read the new family's metrics, nor it theirs.
    for w in spec.data["workloads"][:3]:
        other = [m["name"] for m in spec.cell(w["name"]).per_layer]
        assert "mla_decode_ms_tick" not in other
    assert "flash_decode_paged_roofline" not in names
    # The traffic: the grids the issue gives, in 4 balanced groups of 4.
    from benchmark import grid
    prompts = [128, 128, 192, 192, 256, 256, 320, 384, 384, 448, 512, 512,
               576, 640, 704, 768]
    outputs = [128, 160, 192, 256, 256, 320, 320, 384, 384, 448, 512, 512,
               576, 640, 704, 768]
    assert cell.traffic["prompts"] == grid.balanced_groups(prompts, 4)
    assert cell.traffic["outputs"] == grid.balanced_groups(outputs, 4)
    assert sorted(grid.values(cell.traffic["prompts"])) == prompts
    assert cell.traffic["backlog_x_slots"] == 2
    assert cell.traffic["midlife"] == "slots"
    assert cell.config["serving"] == {
        "slots": 16, "cache_len": 2560, "kv_layout": "paged", "kv_block": 64,
        "admission": "chunked", "prefill_chunk": 256, "prefix_cache": True}


def test_reduced_and_published_agree_and_the_cut_is_5_16_billion():
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "deepseek-v2")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 60,
                              "n_routed_experts": 160, "vocab_size": 102400}
    assert entry["source"] == c["source"]
    assert c["deployment"]["experts_total"] == 160
    assert c["deployment"]["chips_per_layer"] * c["n_routed_experts"] == 160
    # Every published key of the catalog's entry, uncut but the three.
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if json.loads(l)["name"] == "DeepSeek-V2")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
    # The held parameters, reckoned from the file.
    D, H = c["hidden_size"], c["num_attention_heads"]
    attn = (D * c["q_lora_rank"]
            + c["q_lora_rank"] * H * (c["qk_nope_head_dim"]
                                      + c["qk_rope_head_dim"])
            + D * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + H * c["v_head_dim"] * D)
    assert attn == pytest.approx(149.2e6, rel=0.002)
    expert = 3 * D * c["moe_intermediate_size"]
    dense = attn + 3 * D * c["intermediate_size"]
    moe = (attn + c["n_shared_experts"] * expert
           + D * c["deployment"]["experts_total"]
           + c["n_routed_experts"] * expert)
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    total = (c["first_k_dense_replace"] * dense + n_moe * moe
             + 2 * c["vocab_size"] * D)
    assert total == pytest.approx(5.16e9, rel=0.01)
    assert n_moe >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]


def test_both_cost_functions_against_a_hand_count_at_one_tick():
    spec = Spec(BENCH)
    cell = spec.cell(CELL)
    of_model, calls = cell.adapter().kernel_call(cell.config,
                                                 "mla_decode_paged")
    assert of_model == {"heads": 128, "rank": 512, "row": 576,
                        "dtype_bytes": 2} and calls == 5
    pad = 64                    # 576 values lie on 640 lanes in the pool
    assert "64 zero lanes" in cell.config["assumed"]["row_padding"]
    mla = spec.load_module("kernel_costs", "mla_decode_paged.py").cost
    # Two slots of 1,000 and 500 tokens, one query row each: the rows once
    # (576 values and the pad to 640 lanes, 2 bytes each); 128 absorbed queries
    # in (a row wide) and 128 latent outputs out (512 wide) a slot;
    # q.row over 576 and p.row over 512, 2 operations a multiply-add.
    one = mla(contexts=[1000, 500], q_rows=1, **of_model)
    assert one["bytes"] == 1500 * (576 + pad) * 2 \
        + 2 * 128 * ((576 + pad) + 512) * 2
    assert one["flops"] == 2 * 128 * (576 + 512) * 1500
    # 242 operations a byte of cache at 128 heads, unpadded: near the ridge.
    assert 2 * 128 * (576 + 512) / (576 * 2) == pytest.approx(241.8, abs=0.1)

    of_model, calls = cell.adapter().kernel_call(cell.config,
                                                 "moe_grouped_matmul")
    assert calls == 4 and of_model["experts_held"] == 40
    moe = spec.load_module("kernel_costs", "moe_grouped_matmul.py").cost
    # 18 experts touched by 24 pairs: three matrices of 5120 x 1536 an
    # expert, once; a pair's row in and out of each product.
    one = moe(experts_touched=18, pairs=24, **of_model)
    assert one["bytes"] == 18 * 3 * 5120 * 1536 * 2 \
        + 24 * 2 * (5120 + 1536) * 2
    assert one["flops"] == 24 * 6 * 5120 * 1536
    assert moe(contexts=[10], q_rows=1, **of_model) == {
        "bytes": 0.0, "flops": 0.0}
    assert cell.adapter().kernel_call(cell.config, "flash_decode_paged") \
        is None


# -- the second latent family's cell (ISSUE 31) ------------------------------

LC_CELL = "lcflash_agentturn_sat"


def test_the_double_layer_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell = spec.cell(LC_CELL)
    assert cell.chips == 1 and cell.config["family"] == "longcat_scmoe"
    assert cell.config["name"] == "longcat-flash-omni"
    for d, mod in (("references", cell.reference()),
                   ("adapters", cell.adapter())):
        assert mod.__file__.endswith(os.path.join(d, "longcat_scmoe.py"))
    with open(cell.reference().__file__) as f:
        text = f.read()
    assert "tree_attention_tpu" not in text.split('"""', 2)[2]
    assert "import benchmark" not in text and "from benchmark" not in text
    assert cell.traffic["kind"] == "backlog"
    spec.load_module("generators", cell.traffic["kind"] + ".py").Generator
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    # Every per-layer metric the other latent cell reports, and its own two
    # (what later PRs appended for every cell comes after them: PR 32's,
    # and PR 35's parts as the expert cells list them).
    theirs = before_setup([m["name"] for m in spec.cell(CELL).per_layer])
    later = ["tick_ahead_pct"] + PARTS_MOE + AFTER_PARTS
    assert theirs[-len(later):] == later
    assert before_setup(names) == theirs[:-len(later)] + [
        "zero_expert_pairs_pct", "real_experts_row_max_over_mean"] + later
    for m in before_setup(cell.per_layer)[-2 - len(later):-len(later)]:
        assert m["workloads"] == [LC_CELL] and m["layer"] == "expert layer"
        assert (m["source"], m["moves"]) == ("program_counter", "tbt_p50_ms")
    for w in spec.data["workloads"][:4]:
        assert "zero_expert_pairs_pct" not in [
            m["name"] for m in spec.cell(w["name"]).per_layer]
    assert [w["name"] for w in spec.data["workloads"]].index(LC_CELL) == 4
    assert all(w["chips"] == 1 for w in spec.data["workloads"])
    # The traffic: the grids the issue gives, in 4 balanced groups of 4.
    from benchmark import grid
    prompts = [256, 256, 320, 384, 448, 512, 512, 576, 640, 704, 768, 768,
               832, 896, 960, 1024]
    outputs = [256, 320, 384, 384, 448, 512, 512, 576, 640, 640, 704, 768,
               832, 896, 960, 1024]
    assert cell.traffic["prompts"] == grid.balanced_groups(prompts, 4)
    assert cell.traffic["outputs"] == grid.balanced_groups(outputs, 4)
    assert sum(prompts) == sum(outputs) == 16 * 616
    assert (cell.traffic["backlog_x_slots"], cell.traffic["midlife"],
            cell.traffic["midlife_multiple"]) == (2, "slots", 64)
    assert cell.config["serving"] == {
        "slots": 32, "cache_len": 2560, "kv_layout": "paged", "kv_block": 64,
        "admission": "chunked", "prefill_chunk": 256, "prefix_cache": True}
    # Chunk remainders 64, 128, 192 and a whole chunk: with the decode tick,
    # five tick programs, each used once before the window.
    from benchmark import harness
    assert harness.warm_prompts(cell.traffic, 256, 64) == [64, 128, 192, 256]


def test_the_double_layers_cut_is_5_17_billion_and_says_so():
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "longcat-flash-omni")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_layers": 28, "n_routed_experts": 512,
                              "vocab_size": 131072}
    assert entry["source"] == c["source"]
    dep = c["deployment"]
    assert dep["experts_total"] == 512 and dep["expert_share"] == 0
    assert dep["chips_per_layer"] * c["n_routed_experts"] == 512
    assert c["block"]["sublayers"] == 2 and c["block"]["routed_branch"] == [0, 1]
    assert c["assumed"]["seeded_scales"]["router_bias_std"] > 0
    # Every published key of the catalog's entry, uncut but the three.
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if json.loads(l)["name"] == "LongCat-Flash-Omni")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
    # The held parameters, reckoned from the file.
    D, H = c["hidden_size"], c["num_attention_heads"]
    attn = (D * c["q_lora_rank"]
            + c["q_lora_rank"] * H * (c["qk_nope_head_dim"]
                                      + c["qk_rope_head_dim"])
            + D * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + H * c["v_head_dim"] * D)
    assert attn == pytest.approx(90.57e6, rel=0.001)
    ffn = 3 * D * c["ffn_hidden_size"]
    router = D * (dep["experts_total"] + c["zero_expert_num"])
    outside = c["block"]["sublayers"] * (attn + ffn) + router
    assert outside == pytest.approx(638.9e6, rel=0.001)
    expert = 3 * D * c["expert_ffn_hidden_size"]
    total = c["num_layers"] * (outside + c["n_routed_experts"] * expert) \
        + 2 * c["vocab_size"] * D
    assert total == pytest.approx(5.17e9, rel=0.01)
    assert c["num_layers"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]


def test_both_cost_functions_at_one_tick_of_the_double_layer():
    spec = Spec(BENCH)
    cell = spec.cell(LC_CELL)
    of_model, calls = cell.adapter().kernel_call(cell.config,
                                                 "mla_decode_paged")
    assert of_model == {"heads": 64, "rank": 512, "row": 576,
                        "dtype_bytes": 2} and calls == 8   # 2 a layer
    mla = spec.load_module("kernel_costs", "mla_decode_paged.py").cost
    one = mla(contexts=[1000, 500], q_rows=1, **of_model)
    assert one["bytes"] == 1500 * 640 * 2 + 2 * 64 * (640 + 512) * 2
    assert one["flops"] == 2 * 64 * (576 + 512) * 1500
    # 109 operations a byte of cache at 64 heads: under the chip's ridge of
    # 240, so the bytes bound the kernel and no reading can pass 100% of a
    # roofline that counts each row once.
    peaks = spec.load_json("peaks.json")["TPU v5 lite"]
    ridge = peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"]
    per_byte = 2 * 64 * (576 + 512) / (640 * 2)
    assert per_byte == pytest.approx(108.8)
    assert one["flops"] / one["bytes"] < per_byte < ridge

    of_model, calls = cell.adapter().kernel_call(cell.config,
                                                 "moe_grouped_matmul")
    assert calls == 4 and of_model["experts_held"] == 16
    moe = spec.load_module("kernel_costs", "moe_grouped_matmul.py").cost
    # A decode tick of 32 rows: ~26 of the 4 x 16 held experts touched by
    # ~32 pairs: three matrices of 6144 x 2048 an expert, once.
    one = moe(experts_touched=26, pairs=32, **of_model)
    assert one["bytes"] == 26 * 3 * 6144 * 2048 * 2 \
        + 32 * 2 * (6144 + 2048) * 2
    assert one["flops"] == 32 * 6 * 6144 * 2048
    assert one["flops"] / one["bytes"] < 2 < ridge     # bound by the bytes
    assert cell.adapter().kernel_call(cell.config, "flash_decode_paged") \
        is None


def test_the_double_layers_adapter_refuses_another_block_at_once():
    """Before a weight is drawn: a model read otherwise than the file says,
    and one that lacks the block's fields (what the parent's model is)."""
    import types

    from tree_attention_tpu.models.transformer import model_from_config

    spec = Spec(BENCH)
    cell, other = spec.cell(LC_CELL), spec.cell(CELL)
    adapter = cell.adapter()
    adapter._hold_to_file(model_from_config(cell.config), cell.config)
    with pytest.raises(SpecError, match="built otherwise"):
        adapter._hold_to_file(model_from_config(other.config), cell.config)
    old = types.SimpleNamespace(mla=types.SimpleNamespace(q_rank=1536),
                                moe=object(), d_model=6144)
    with pytest.raises(SpecError, match="cannot express"):
        adapter._hold_to_file(old, cell.config)
    with pytest.raises(SpecError, match="cannot read"):
        adapter.build({"family": "longcat_scmoe"}, [], 0, "cpu", None)


@pytest.mark.parametrize("flight, want", [
    # A parent's records have no such field: nothing to read, not zero.
    ([{"t_s": 1.0, "phases": [["ingest", 1.0], ["fetch", 1.1]]}], None),
    (None, None),
    # Three fetched ticks in the window, two dispatched ahead; a tick that
    # fetched nothing (chunks only) and one before the window do not count.
    ([{"t_s": 1.0, "ahead": False, "sync_reason": "first",
       "phases": [["ingest", 1.0], ["fetch", 1.1]]},
      {"t_s": 2.0, "ahead": True, "phases": [["ingest", 2.0], ["fetch", 2.1]]},
      {"t_s": 3.0, "ahead": True, "phases": [["ingest", 3.0], ["fetch", 3.1]]},
      {"t_s": 4.0, "ahead": True, "phases": [["ingest", 4.0], ["account", 4.1]]},
      {"t_s": 0.5, "ahead": True, "phases": [["ingest", 0.5], ["fetch", 0.6]]},
      {"tick": 9, "sweep_only": True}], 100.0 * 2 / 3),
], ids=["parent", "no-recorder", "window"])
def test_tick_ahead_pct_reads_the_look_ahead_counter(flight, want):
    """The look-ahead's per-layer metric (ISSUE 32): found by name like the
    others, listed for every cell (it moves ``tbt_p50_ms``, which every
    cell reports), after the entries that were there."""
    import types

    spec = Spec(BENCH)
    listed = json.load(open(BENCH))["per_layer"]
    at = [m["name"] for m in listed].index("tick_ahead_pct")
    assert listed[at] == {
        "name": "tick_ahead_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "admission and scheduling",
        "moves": "tbt_p50_ms"}
    # Only what later PRs appended comes after it (PR 33's for its own cell,
    # PR 35's parts).
    assert [m["name"] for m in before_setup(listed)[at + 1:]] == [
        "mixer_rest_ms_tick", "mixer_rest_stream_roofline"] + PARTS_ALL \
        + AFTER_PARTS + WINDOW_METRICS + STATE_METRICS + EVA_METRICS
    for w in json.load(open(BENCH))["workloads"]:
        assert "tick_ahead_pct" in [
            m["name"] for m in spec.cell(w["name"]).per_layer]
    read = spec.load_module("layer_metrics", "tick_ahead_pct.py").read
    run = types.SimpleNamespace(flight=flight, t_open=1.0, t_end=10.0)
    assert read(run) == (want if want is None else pytest.approx(want))


# -- the conv / attention hybrid's cell (ISSUE 33) ---------------------------

LFM_CELL = "lfm2_agentturn_sat"


def test_the_hybrid_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell, lc = spec.cell(LFM_CELL), spec.cell(LC_CELL)
    assert cell.chips == 1 and cell.config["family"] == "lfm2_moe"
    assert cell.config["name"] == "lfm2-8b-a1b"
    for d, mod in (("references", cell.reference()),
                   ("adapters", cell.adapter())):
        assert mod.__file__.endswith(os.path.join(d, "lfm2_moe.py"))
    with open(cell.reference().__file__) as f:
        text = f.read()
    assert "tree_attention_tpu" not in text.split('"""', 2)[2]
    assert "import benchmark" not in text and "from benchmark" not in text
    # The traffic file that was there, and the cell the sixth of six.
    assert cell.traffic == lc.traffic and cell.traffic["kind"] == "backlog"
    assert [w["name"] for w in spec.data["workloads"]][5:] == [
        LFM_CELL, KX_CELL, NS_CELL, EVA_CELL, FH_CELL, PF_CELL]
    assert all(w["chips"] == 1 for w in spec.data["workloads"])
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    # The dense cells' attention kernel, the latent cells' expert product
    # and counters, and its own two, which no other cell lists.
    for name in ("occupancy_pct", "kv_blocks_peak_pct", "hbm_peak_gb",
                 "tick_rows_useful_pct", "attn_kernel_ms_tick",
                 "flash_decode_paged_roofline", "moe_ffn_ms_tick",
                 "moe_grouped_matmul_roofline", "experts_touched_pct",
                 "expert_rows_max_over_mean", "tick_ahead_pct",
                 "device_idle_pct", "decode_tick_p50_ms"):
        assert name in names, name
    later = PARTS_ALL + AFTER_PARTS           # PR 35's, all of them; PR 37's
    assert before_setup(names)[-len(later):] == later
    own = slice(-2 - len(later), -len(later))
    assert before_setup(names)[own] == [
        "mixer_rest_ms_tick", "mixer_rest_stream_roofline"]
    for m in before_setup(cell.per_layer)[own]:
        assert m["workloads"] == [LFM_CELL] and m["layer"] == "kernels"
        assert (m["source"], m["moves"]) == ("device_trace", "tbt_p50_ms")
    for name in ("mla_decode_ms_tick", "zero_expert_pairs_pct"):
        assert name not in names
    for w in spec.data["workloads"][:5]:
        assert "mixer_rest_ms_tick" not in [
            m["name"] for m in spec.cell(w["name"]).per_layer]
    assert cell.config["serving"] == {
        "slots": 64, "cache_len": 2560, "kv_layout": "paged", "kv_block": 64,
        "admission": "chunked", "prefill_chunk": 256, "prefix_cache": True}
    assert list(cell.config["correct"]["limits"]) == ["gap_mean"]
    for kernel in ("flash_decode_paged", "moe_grouped_matmul", "mixer_rest"):
        assert cell.adapter().kernel_call(cell.config, kernel) is not None
        assert spec.load_module("kernel_costs", kernel + ".py").cost
    assert cell.adapter().kernel_call(cell.config, "mla_decode_paged") is None


def test_the_hybrids_cut_is_depth_alone_and_3_93_billion():
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2-8b-a1b")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types"]
    pub = c["published"]
    assert sorted(pub) == ["layer_types", "num_hidden_layers"]
    assert pub["num_hidden_layers"] == len(pub["layer_types"]) == 24
    # The cut is the first 12 layers as published: three periods c c A c.
    assert c["layer_types"] == pub["layer_types"][:12] \
        == ["conv", "conv", "full_attention", "conv"] * 3
    assert c["num_hidden_layers"] == 12
    assert entry["source"] == c["source"]
    dep = c["deployment"]
    assert (dep["chips"], dep["pipeline_stages"], dep["stage"]) == (2, 2, 0)
    assert dep["experts_total"] == c["num_experts"] == 32
    assert dep["expert_share"] == 0
    assert c["block"]["qk_norm"] and c["block"]["router_scoring"] == "sigmoid"
    assert c["tie_word_embeddings"] and c["assumed"]["tie_word_embeddings"]
    assert c["assumed"]["seeded_scales"]["router_bias_std"] > 0
    # Every published key of the catalog's entry, uncut but the two.
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if json.loads(l)["name"] == "LFM2-8B-A1B")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert (pub if k in c["reduced"] else c)[k] == v, k
    # The held parameters, reckoned from the file; the whole model's too.
    D = c["hidden_size"]
    kv = D // c["num_attention_heads"] * c["num_key_value_heads"]
    conv = D * 3 * D + D * D + c["conv_L_cache"] * D
    attn = 2 * D * D + 2 * D * kv
    dense = 3 * D * c["intermediate_size"]
    moe = c["num_experts"] * 3 * D * c["moe_intermediate_size"] \
        + D * c["num_experts"]
    embed = c["vocab_size"] * D

    def held(types):
        return sum((conv if t == "conv" else attn)
                   + (dense if l < c["num_dense_layers"] else moe)
                   for l, t in enumerate(types)) + embed

    assert held(c["layer_types"]) == pytest.approx(3.929e9, rel=0.001)
    assert held(pub["layer_types"]) == pytest.approx(8.34e9, rel=0.001)
    assert 2 * held(pub["layer_types"]) > 16e9      # the whole: over a chip
    assert c["num_hidden_layers"] - c["num_dense_layers"] >= 4
    # A token's bytes in the pool: 3 K/V layers, and 9 tails a block of 64.
    token = 3 * 2 * kv * 2 + 9 * 2 * D * 2 / c["serving"]["kv_block"]
    assert token == 7296


def test_the_hybrids_cost_functions_against_a_hand_count_at_one_tick():
    spec = Spec(BENCH)
    cell = spec.cell(LFM_CELL)
    kernel_call = cell.adapter().kernel_call
    of_model, calls = kernel_call(cell.config, "flash_decode_paged")
    assert of_model == {"heads": 32, "kv_heads": 8, "head": 64,
                        "dtype_bytes": 2} and calls == 3
    flash = spec.load_module("kernel_costs", "flash_decode_paged.py").cost
    # Two slots of 1,000 and 500 tokens: 8 heads x 64 of keys and of values
    # a token once (the pool packs them two a row of lanes: the same
    # bytes), 32 queries in and 32 outputs out a slot.
    one = flash(contexts=[1000, 500], q_rows=1, **of_model)
    assert one["bytes"] == 1500 * 2 * 8 * 64 * 2 + 2 * 2 * 32 * 64 * 2

    of_model, calls = kernel_call(cell.config, "moe_grouped_matmul")
    assert calls == 10 and of_model == {
        "hidden": 2048, "width": 1792, "experts_held": 32, "dtype_bytes": 2}
    moe = spec.load_module("kernel_costs", "moe_grouped_matmul.py").cost
    # A decode tick of 64 rows touches all 32 experts of a layer with 256
    # pairs: 7.05 GB over the ten layers, what the cell's why counts.
    one = moe(experts_touched=32, pairs=256, **of_model)
    assert one["bytes"] == 32 * 3 * 2048 * 1792 * 2 \
        + 256 * 2 * (2048 + 1792) * 2
    assert 10 * one["bytes"] == pytest.approx(7.05e9, rel=0.01)

    of_model, calls = kernel_call(cell.config, "mixer_rest")
    assert calls == 1
    rest = spec.load_module("kernel_costs", "mixer_rest.py").cost
    one = rest(contexts=[900] * 64, q_rows=1, **of_model)
    # Every weight outside the experts once: 9 conv mixers, 3 attention
    # mixers, 2 dense FFNs, 10 routers, the tied embedding as the head.
    weights = (9 * (2048 * 6144 + 2048 * 2048 + 3 * 2048)
               + 3 * (2 * 2048 * 2048 + 2 * 2048 * 512)
               + 2 * 3 * 2048 * 7168 + 10 * 2048 * 32 + 65536 * 2048)
    assert weights * 2 == pytest.approx(0.81e9, rel=0.01)
    # 64 rows: the residual in and out of both halves of 12 layers, two
    # tail rows read and one written a conv layer, the logits in float32.
    rows = 64 * 2048 * (2 * 2 * 12 + 3 * 9) * 2 + 64 * 65536 * 4
    assert one["bytes"] == weights * 2 + rows
    assert one["flops"] == 2 * 64 * weights
    # 64 operations a byte: under the chip's ridge, so the bytes bound it
    # and a reading over 100% of this roofline cannot be.
    peaks = spec.load_json("peaks.json")["TPU v5 lite"]
    ridge = peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"]
    assert one["flops"] / one["bytes"] < 64 < ridge
    assert rest(contexts=[], q_rows=1, **of_model)["flops"] == 0


def test_the_hybrids_adapter_refuses_another_model_at_once():
    """Before a weight is drawn: a model read otherwise than the file says,
    and one without the layers' fields (what the parent's reader makes of
    the file: a dense model, in silence)."""
    import types

    from tree_attention_tpu.models.transformer import model_from_config

    spec = Spec(BENCH)
    cell = spec.cell(LFM_CELL)
    adapter = cell.adapter()
    adapter._hold_to_file(model_from_config(cell.config), cell.config)
    dense = {k: v for k, v in cell.config.items()
             if k not in ("layer_types", "num_experts", "use_expert_bias")}
    with pytest.raises(SpecError, match="built otherwise"):
        adapter._hold_to_file(model_from_config(dense), cell.config)
    old = types.SimpleNamespace(mla=None, moe=None, d_model=2048, d_ff=7168,
                                n_layers=12)
    with pytest.raises(SpecError, match="cannot express"):
        adapter._hold_to_file(old, cell.config)
    with pytest.raises(SpecError, match="cannot read"):
        adapter.build({"family": "lfm2_moe"}, [], 0, "cpu", None)


@pytest.mark.parametrize("name", ["mixer_rest_ms_tick",
                                  "mixer_rest_stream_roofline"])
def test_the_rest_metrics_read_nothing_where_there_is_nothing(name):
    """An untraced run, a run without flight records and a trace without a
    decode tick in it give None, never a number and never an exception."""
    import types

    spec = Spec(BENCH)
    cell = spec.cell(LFM_CELL)
    read = spec.load_module("layer_metrics", name + ".py").read
    peaks = spec.load_json("peaks.json")["TPU v5 lite"]
    empty = {"offset_s": 0.0, "t0": 5.0, "t1": 8.0, "devices": 1,
             "events": {}}
    for trace, flight in ((None, None), (None, []), (empty, None),
                          (empty, [{"t_s": 1.0}])):
        run = types.SimpleNamespace(trace=trace, flight=flight, cell=cell,
                                    recs=[], peaks=peaks, t_open=1.0,
                                    t_end=10.0)
        assert read(run) is None


# -- a tick's device time by part (ISSUE 35) --------------------------------


@pytest.mark.parametrize("name", PARTS_ALL)
def test_a_parts_metric_is_listed_where_its_part_exists(name):
    """Fourteen entries appended by PR 35: device-trace metrics of the tick
    programs, the decode ones moving ``tbt_p50_ms`` and the mixed ones
    ``tbt_p99_ms``; the expert parts in the five expert cells, the conv
    parts in the hybrid's and the two state configurations', the rest in all
    ten."""
    spec = Spec(BENCH)
    listed = before_setup(json.load(open(BENCH))["per_layer"])
    tail = len(PARTS_ALL) + len(AFTER_PARTS) + len(WINDOW_METRICS) \
        + len(STATE_METRICS) + len(EVA_METRICS)
    assert [m["name"] for m in listed[-tail:]] == PARTS_ALL + AFTER_PARTS \
        + WINDOW_METRICS + STATE_METRICS + EVA_METRICS
    entry = next(m for m in listed if m["name"] == name)
    cells = [w["name"] for w in spec.data["workloads"]]
    want = cells
    if "_moe_" in name:
        want = [CELL, LC_CELL, LFM_CELL, KX_CELL, NS_CELL]
    elif "_conv_" in name:
        # The part ``conv``: a mixer with a fixed-size state (the short
        # convolution; a state-space mixer between its projections).
        want = [LFM_CELL, NS_CELL, FH_CELL, PF_CELL]
    if name.startswith("mix_"):
        # The window cell's traced 3 s hold a chunk tick in most runs and
        # none in some (1.5 a second, in clusters): a reader that finds
        # nothing to read there is not listed there (PERF.md section 7);
        # nor is the decoder-hybrid cell, on the same traffic.
        want = [c for c in want if c not in (KX_CELL, PF_CELL)]
    assert entry == {
        "name": name, "unit": "%" if name.endswith("_pct") else "ms",
        "better": "lower", "source": "device_trace",
        "layer": "tick programs",
        "moves": "tbt_p99_ms" if name.startswith("mix_") else "tbt_p50_ms",
        "workloads": want}
    for cell in cells:
        names = [m["name"] for m in spec.cell(cell).per_layer]
        assert (name in names) == (cell in want)
    assert spec.load_module("layer_metrics", name + ".py").read


def test_the_parts_readers_name_no_leaf_of_a_family():
    """``benchmark/parts.py`` takes the scopes' names from the program's own
    vocabulary (``obs/scopes.py``); beside a program without it, it has
    nothing to join and every reader gives None."""
    from benchmark import parts
    from tree_attention_tpu.obs import scopes

    assert set(parts.PART_OF) == set(scopes.SCOPES)
    with open(os.path.join(ROOT, "benchmark", "parts.py")) as f:
        text = f.read()
    assert "except ImportError" in text and '"embed"' not in text


# -- the paged kernels' work lists (ISSUE 37) --------------------------------


def test_paged_steps_run_pct_reads_the_lists_the_kernels_walk():
    """One entry appended by PR 37, for every cell: the flight records'
    ``kv_steps_run`` over ``kv_steps_grid`` in the window's decode ticks. A
    program without the counters (the parent), an untraced run and a window
    without a decode tick give None."""
    import types

    spec = Spec(BENCH)
    listed = before_setup(json.load(open(BENCH))["per_layer"])
    cells = [w["name"] for w in spec.data["workloads"]]
    assert listed[-1 - len(WINDOW_METRICS) - len(STATE_METRICS)
                  - len(EVA_METRICS)] == {
        "name": "paged_steps_run_pct", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tbt_p50_ms", "workloads": cells}
    for cell in cells:
        last = -1 - len(WINDOW_METRICS) * (cell in (KX_CELL, PF_CELL)) \
            - len(STATE_METRICS) * (cell == NS_CELL) \
            - (1 + len(EVA_METRICS)) * (cell == EVA_CELL) \
            - 3 * (cell == FH_CELL)     # the kernel's two and the pool's
        assert before_setup(spec.cell(cell).per_layer)[last]["name"] \
            == "paged_steps_run_pct"
    read = spec.load_module("layer_metrics", "paged_steps_run_pct.py").read
    tick = {"occupancy": 16, "chunk_tokens": 0, "kv_steps_grid": 160}
    flight = [
        dict(tick, t_s=2.0, kv_steps_run=50),
        dict(tick, t_s=3.0, kv_steps_run=70),
        dict(tick, t_s=4.0, kv_steps_run=170, kv_steps_grid=176,
             chunk_tokens=64),                       # a mixed tick
        dict(tick, t_s=5.0, kv_steps_run=16, occupancy=0),   # no live slot
        dict(tick, t_s=0.5, kv_steps_run=160),       # before the window
        dict(tick, t_s=12.0, kv_steps_run=160),      # after it
    ]
    run = types.SimpleNamespace(flight=flight, t_open=1.0, t_end=10.0)
    assert read(run) == pytest.approx(100.0 * 120 / 320)
    parent = [{k: v for k, v in r.items() if not k.startswith("kv_steps")}
              for r in flight]
    for none in (parent, [], None, flight[2:]):
        run = types.SimpleNamespace(flight=none, t_open=1.0, t_end=10.0)
        assert read(run) is None


# -- layers whose block counts differ: the window cell (ISSUE 38) ------------


def test_the_window_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell = spec.cell(KX_CELL)
    assert cell.chips == 1 and cell.config["family"] == "exaone_moe"
    assert cell.config["name"] == "k-exaone-236b-a23b"
    for d, mod in (("references", cell.reference()),
                   ("adapters", cell.adapter())):
        assert mod.__file__.endswith(os.path.join(d, "exaone_moe.py"))
    with open(cell.reference().__file__) as f:
        text = f.read()
    assert "tree_attention_tpu" not in text.split('"""', 2)[2]
    assert "import benchmark" not in text and "from benchmark" not in text
    # A traffic file of its own (data only), the cell the seventh of seven,
    # none on four chips.
    assert cell.traffic["kind"] == "backlog"
    assert spec.find("traffic", "reasoning_long_backlog.json")
    assert [w["name"] for w in spec.data["workloads"]][6:] == [
        KX_CELL, NS_CELL, EVA_CELL, FH_CELL, PF_CELL]
    assert all(w["chips"] == 1 for w in spec.data["workloads"])
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    for name in ("occupancy_pct", "kv_blocks_peak_pct", "hbm_peak_gb",
                 "tick_rows_useful_pct", "attn_kernel_ms_tick",
                 "flash_decode_paged_roofline", "moe_ffn_ms_tick",
                 "moe_grouped_matmul_roofline", "experts_touched_pct",
                 "expert_rows_max_over_mean", "tick_ahead_pct",
                 "device_idle_pct", "decode_tick_p50_ms",
                 "paged_steps_run_pct", "tick_unscoped_pct"):
        assert name in names, name
    assert before_setup(names)[-3:] == WINDOW_METRICS
    for name in ("mla_decode_ms_tick", "zero_expert_pairs_pct",
                 "dec_conv_ms_tick", "mix_conv_ms_tick",
                 "mixer_rest_ms_tick", "mix_attn_chunk_ms_tick",
                 "mix_moe_ms_tick"):
        assert name not in names
    for name in ("dec_proj_ms_tick", "dec_attn_ms_tick", "dec_ffn_ms_tick",
                 "dec_head_ms_tick", "dec_moe_ms_tick", "mixed_tick_p50_ms"):
        assert name in names, name
    listed = {m["name"]: m for m in spec.data["per_layer"]}
    # (The ledger's share is the one of the three a later cell reports:
    # its exact rows lie under the same ledger, by another rule.)
    for name in WINDOW_METRICS:
        assert listed[name]["workloads"] == [KX_CELL] + [EVA_CELL] * (
            name == "window_blocks_held_pct") + [PF_CELL]
    assert (listed["window_attn_ms_tick"]["layer"],
            listed["window_attn_ms_tick"]["moves"],
            listed["window_attn_ms_tick"]["source"]) == (
        "kernels", "tbt_p50_ms", "device_trace")
    assert listed["window_decode_paged_roofline"]["unit"] == "%"
    assert (listed["window_blocks_held_pct"]["layer"],
            listed["window_blocks_held_pct"]["better"],
            listed["window_blocks_held_pct"]["moves"]) == (
        "block pool", "lower", "out_tok_s")
    for w in spec.data["workloads"][:6]:
        assert not set(WINDOW_METRICS) & {
            m["name"] for m in spec.cell(w["name"]).per_layer}
    assert cell.config["serving"] == {
        "slots": 32, "cache_len": 9216, "kv_layout": "paged", "kv_block": 64,
        "admission": "chunked", "prefill_chunk": 256, "prefix_cache": True}
    assert list(cell.config["correct"]["limits"]) == ["gap_mean"]
    calls = {k: cell.adapter().kernel_call(cell.config, k)
             for k in ("flash_decode_paged", "window_decode_paged",
                       "moe_grouped_matmul", "mla_decode_paged")}
    assert calls["flash_decode_paged"][1] == 2
    assert calls["window_decode_paged"][1] == 6
    assert calls["window_decode_paged"][0]["window"] == 128
    assert calls["moe_grouped_matmul"] == (
        {"hidden": 6144, "width": 2048, "experts_held": 8,
         "dtype_bytes": 2}, 7)
    assert calls["mla_decode_paged"] is None
    for kernel in ("flash_decode_paged", "window_decode_paged",
                   "moe_grouped_matmul"):
        assert spec.load_module("kernel_costs", kernel + ".py").cost


def test_the_window_configurations_file_against_the_catalog():
    """Every number of the catalog's ``config`` under the same key but the
    keys ``reduced`` lists; the cut's arithmetic; every assumed rule marked
    unconfirmed; the drafter named as not built."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "k-exaone-236b-a23b")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size"]
    assert c["source"] == entry["source"]
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "K-EXAONE-236B-A23B")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
        pub = row["config"]
        assert c["layer_types"] == pub["layer_types"][:8]
        assert c["mlp_layer_types"] == pub["mlp_layer_types"][:8]
        assert c["sliding_windows"] == pub["sliding_windows"][:8]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        8, 8, 19200)
    assert c["published"]["num_experts"] == 128 \
        and c["published"]["vocab_size"] == 153600
    # Floors: two whole periods, 7 >= 4 layers after the dense one, 8
    # experts, an eighth of the vocabulary.
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 2
    assert c["vocab_size"] * 8 == 153600
    h, ffn, e = c["hidden_size"], c["intermediate_size"], \
        c["moe_intermediate_size"]
    attn = 2 * h * 64 * 128 + 2 * h * 8 * 128
    layer = attn + 3 * h * e + h * 128            # + shared expert + router
    held = (attn + 3 * h * ffn) + 7 * (layer + 8 * 3 * h * e) \
        + 2 * c["vocab_size"] * h
    # The file sums its rounded parts (3,865.5M); to the parameter it is
    # 3,865.3M before the norms' gains.
    assert abs(held / 1e6 - 3865.5) < 0.3 and "3,865.5M" in c["why_reduced"]
    dep = c["deployment"]
    assert (dep["chips"], dep["chips_a_layer"], dep["pipeline_stages"],
            dep["experts_total"], dep["expert_share"]) == (64, 16, 4, 128, 0)
    assert c["block"] == dict(
        c["block"], qk_norm=True, rotary_layers="sliding_attention",
        corrected_choice=True, scale_renormed=True, norm_placement="pre")
    for rule in ("qk_norm", "rotary_layers", "corrected_choice",
                 "norm_placement"):
        assert "unconfirmed" in c["assumed"]["unconfirmed"][rule]
    assert "num_nextn_predict_layers" in c["not_built"]
    assert set(c["assumed"]["seeded_scales"]) == {
        "embedding_std", "head_std", "attn_out_std", "dense_down_std",
        "expert_down_std", "shared_down_std", "qk_gain_mean", "qk_gain_std",
        "router_bias_std"}


def test_the_window_kernels_cost_against_a_hand_count_at_one_tick():
    spec = Spec(BENCH)
    cell = spec.cell(KX_CELL)
    of_model, calls = cell.adapter().kernel_call(
        cell.config, "window_decode_paged")
    cost = spec.load_module("kernel_costs", "window_decode_paged.py").cost
    c = cost(contexts=[50, 128, 9000], q_rows=1, **of_model)
    kv = 2 * (50 + 128 + 128) * 8 * 128 * 2
    qo = 3 * 2 * 1 * 64 * 128 * 2
    assert c["bytes"] == kv + qo and calls == 6
    assert c["flops"] == 4 * 64 * 128 * (50 + 128 + 128)
    # The full layers' cost grows with the context; this one does not.
    full = spec.load_module("kernel_costs", "flash_decode_paged.py").cost
    args = {k: v for k, v in of_model.items() if k != "window"}
    assert full(contexts=[9000], q_rows=1, **args)["bytes"] \
        > 30 * cost(contexts=[9000], q_rows=1, **of_model)["bytes"]


def test_the_window_adapter_refuses_another_model_at_once():
    spec = Spec(BENCH)
    adapter = spec.cell(KX_CELL).adapter()
    with pytest.raises(SpecError, match="cannot read"):
        adapter.build({"family": "exaone_moe"}, [], 0, "cpu", None)
    # A file that says another block than the engine would build.
    config = dict(spec.cell(KX_CELL).config)
    config["block"] = dict(config["block"], rotary_layers="all")
    from tree_attention_tpu.models.transformer import model_from_config
    model = model_from_config(spec.cell(KX_CELL).config)
    with pytest.raises(SpecError, match="built otherwise"):
        adapter._hold_to_file(model, config)


@pytest.mark.parametrize("name", WINDOW_METRICS)
def test_the_window_metrics_read_nothing_where_there_is_nothing(name):
    """An untraced run, a run without flight records, a trace without a
    decode tick and a program without the counters (the parent's records)
    give None, never a number and never an exception."""
    import types

    spec = Spec(BENCH)
    cell = spec.cell(KX_CELL)
    read = spec.load_module("layer_metrics", name + ".py").read
    peaks = spec.load_json("peaks.json")["TPU v5 lite"]
    empty = {"offset_s": 0.0, "t0": 5.0, "t1": 8.0, "devices": 1,
             "events": {}}
    parent = [{"t_s": 2.0, "occupancy": 4, "chunk_tokens": 0},
              {"t_s": 3.0, "occupancy": 4, "chunk_tokens": 0}]
    for trace, flight in ((None, None), (None, []), (empty, None),
                          (empty, [{"t_s": 1.0}]), (empty, parent)):
        run = types.SimpleNamespace(trace=trace, flight=flight, cell=cell,
                                    recs=[], peaks=peaks, t_open=1.0,
                                    t_end=10.0)
        assert read(run) is None


def test_window_blocks_held_pct_reads_the_ledgers_counters():
    import types

    spec = Spec(BENCH)
    read = spec.load_module("layer_metrics", "window_blocks_held_pct.py").read
    tick = {"occupancy": 32, "chunk_tokens": 0, "window_blocks_full": 2000}
    flight = [
        dict(tick, t_s=2.0, window_blocks_held=90),
        dict(tick, t_s=3.0, window_blocks_held=96),
        dict(tick, t_s=4.0, window_blocks_held=130, chunk_tokens=256),
        dict(tick, t_s=0.5, window_blocks_held=60),      # before the window
    ]
    run = types.SimpleNamespace(flight=flight, t_open=1.0, t_end=10.0)
    assert read(run) == pytest.approx(100.0 * 186 / 4000)


# -- a recurrent state a slot: the state cell (ISSUE 40) ---------------------


def test_the_state_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell, lc = spec.cell(NS_CELL), spec.cell(LC_CELL)
    assert cell.chips == 1 and cell.config["family"] == "nemotron_h"
    assert cell.config["name"] == "nemotron-3-super-120b-a12b"
    for d, mod in (("references", cell.reference()),
                   ("adapters", cell.adapter())):
        assert mod.__file__.endswith(os.path.join(d, "nemotron_h.py"))
    with open(cell.reference().__file__) as f:
        text = f.read()
    assert "tree_attention_tpu" not in text.split('"""', 2)[2]
    assert "import benchmark" not in text and "from benchmark" not in text
    assert "lax.scan(token" in text       # the recurrence, token by token
    # The traffic file that was there, the cell the eighth of eight, none
    # on four chips.
    assert cell.traffic == lc.traffic and cell.traffic["kind"] == "backlog"
    assert [w["name"] for w in spec.data["workloads"]][7:] == [
        NS_CELL, EVA_CELL, FH_CELL, PF_CELL]
    assert all(w["chips"] == 1 for w in spec.data["workloads"])
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    for name in ("occupancy_pct", "kv_blocks_peak_pct", "hbm_peak_gb",
                 "tick_rows_useful_pct", "attn_kernel_ms_tick",
                 "flash_decode_paged_roofline", "experts_touched_pct",
                 "expert_rows_max_over_mean", "tick_ahead_pct",
                 "device_idle_pct", "decode_tick_p50_ms",
                 "paged_steps_run_pct") + tuple(PARTS_ALL):
        assert name in names, name
    assert before_setup(names)[-5:] == STATE_METRICS
    # The gated product's time and roofline are not this cell's (its cost
    # file counts three matrices of hidden x width an expert).
    for name in ("moe_ffn_ms_tick", "moe_grouped_matmul_roofline",
                 "mixer_rest_ms_tick", "mla_decode_ms_tick",
                 "window_attn_ms_tick", "zero_expert_pairs_pct"):
        assert name not in names
    listed = {m["name"]: m for m in spec.data["per_layer"]}
    for name in STATE_METRICS:
        # (The second state-space configuration's cell reports the
        # kernel's and the pool's metrics, not the ungated product's.)
        assert listed[name]["workloads"] == [NS_CELL] + [FH_CELL] * (
            not name.startswith("moe_ungated"))
        assert listed[name]["moves"] == "tbt_p50_ms"
    assert [listed[n]["layer"] for n in STATE_METRICS] == [
        "kernels"] * 4 + ["state pool"]
    assert listed["ssm_decode_update_roofline"]["unit"] == "%" \
        == listed["moe_ungated_matmul_roofline"]["unit"]
    assert listed["ssm_states_advanced_pct"]["source"] == "program_counter"
    for w in spec.data["workloads"][:7]:
        assert not set(STATE_METRICS) & {
            m["name"] for m in spec.cell(w["name"]).per_layer}
    assert cell.config["serving"] == {
        "slots": 64, "cache_len": 2560, "kv_layout": "paged", "kv_block": 64,
        "admission": "chunked", "prefill_chunk": 256, "prefix_cache": False}
    assert list(cell.config["correct"]["limits"]) == ["gap_mean"]
    for kernel in ("flash_decode_paged", "ssm_decode_update",
                   "moe_ungated_matmul", "moe_grouped_matmul"):
        assert cell.adapter().kernel_call(cell.config, kernel) is not None
        assert spec.load_module("kernel_costs", kernel + ".py").cost
    assert cell.adapter().kernel_call(cell.config, "mla_decode_paged") is None


def test_the_state_configurations_file_against_the_catalog():
    """Every number of the catalog's ``config`` under the same key but the
    keys ``reduced`` lists; the cut's arithmetic; every assumed rule marked
    unconfirmed; the drafter named as not built."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "nemotron-3-super-120b-a12b")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert c["source"] == entry["source"]
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
        pub = row["config"]["hybrid_override_pattern"]
        assert c["hybrid_override_pattern"] == pub[:11]
        assert c["published"]["hybrid_override_pattern"] == pub
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (11, 128, 32768)
    assert c["published"]["n_routed_experts"] == 512 \
        and c["published"]["vocab_size"] == 131072 \
        and c["published"]["num_hidden_layers"] == 88
    # Floors: a whole period (5 M, 5 E, 1 *: the published 40 : 40 : 8), 128
    # experts, a quarter of the vocabulary.
    pat = c["hybrid_override_pattern"]
    assert (pat.count("M"), pat.count("E"), pat.count("*")) == (5, 5, 1)
    h, lat, e, sh = (c["hidden_size"], c["moe_latent_size"],
                     c["moe_intermediate_size"],
                     c["moe_shared_expert_intermediate_size"])
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    m = h * (inner + conv + c["mamba_num_heads"]) + inner * h \
        + (c["conv_kernel"] + 1) * conv + 3 * c["mamba_num_heads"] \
        + inner + h
    attn = 2 * h * 32 * 128 + 2 * h * 2 * 128 + h
    moe = h * 512 + 512 + 2 * h * lat + 2 * h * sh + h
    held = 5 * m + attn + 5 * (moe + 128 * 2 * lat * e) \
        + 2 * c["vocab_size"] * h + h
    assert abs(held / 1e6 - 4648.2) < 0.3 and "4,648.2M" in c["why_reduced"]
    assert abs(m / 1e6 - 109.64) < 0.01 and abs(attn / 1e6 - 35.66) < 0.01
    dep = c["deployment"]
    assert (dep["chips"], dep["chips_a_layer"], dep["pipeline_stages"],
            dep["experts_total"], dep["expert_share"]) == (32, 4, 8, 512, 0)
    assert c["block"] == dict(
        c["block"], rotary_layers="none", router_scoring="sigmoid",
        corrected_choice=True, scale_renormed=True, latent_proj_plain=True,
        gate_before_norm=True)
    for rule in ("rotary_layers", "corrected_choice", "latent_proj_plain",
                 "gate_before_norm"):
        assert "unconfirmed" in c["assumed"]["unconfirmed"][rule]
    assert "float32" in c["assumed"]["state_dtype"]
    assert "num_nextn_predict_layers" in c["not_built"]
    assert set(c["assumed"]["seeded_scales"]) == {
        "embedding_std", "head_std", "ssm_out_std", "attn_out_std",
        "expert_down_std", "latent_up_std", "shared_down_std", "gain_mean",
        "gain_std", "router_bias_std"}


def test_the_state_adapter_refuses_another_model_at_once():
    spec = Spec(BENCH)
    adapter = spec.cell(NS_CELL).adapter()
    with pytest.raises(SpecError, match="cannot read"):
        adapter.build({"family": "nemotron_h"}, [], 0, "cpu", None)
    # A file that says another block than the engine would build.
    config = dict(spec.cell(NS_CELL).config)
    config["block"] = dict(config["block"], corrected_choice=False)
    from tree_attention_tpu.models.transformer import model_from_config
    model = model_from_config(spec.cell(NS_CELL).config)
    with pytest.raises(SpecError, match="built otherwise"):
        adapter._hold_to_file(model, config)
    # A program whose model has no state-space widths.
    other = model_from_config(spec.cell(LFM_CELL).config)
    with pytest.raises(SpecError, match="cannot express|built otherwise"):
        adapter._hold_to_file(other, spec.cell(NS_CELL).config)


@pytest.mark.parametrize("name", STATE_METRICS)
def test_the_state_metrics_read_nothing_where_there_is_nothing(name):
    """An untraced run, a run without flight records, a trace without a
    decode tick and a program without the counters or the kernels (the
    parent's records) give None, never a number and never an exception."""
    import types

    spec = Spec(BENCH)
    cell = spec.cell(NS_CELL)
    read = spec.load_module("layer_metrics", name + ".py").read
    peaks = spec.load_json("peaks.json")["TPU v5 lite"]
    empty = {"offset_s": 0.0, "t0": 5.0, "t1": 8.0, "devices": 1,
             "events": {}}
    parent = [{"t_s": 2.0, "occupancy": 4, "chunk_tokens": 0},
              {"t_s": 3.0, "occupancy": 4, "chunk_tokens": 0}]
    for trace, flight in ((None, None), (None, []), (empty, None),
                          (empty, [{"t_s": 1.0}]), (empty, parent)):
        run = types.SimpleNamespace(trace=trace, flight=flight, cell=cell,
                                    recs=[], peaks=peaks, t_open=1.0,
                                    t_end=10.0)
        assert read(run) is None


def test_ssm_states_advanced_pct_reads_the_steps_counter():
    import types

    spec = Spec(BENCH)
    cell = spec.cell(NS_CELL)
    read = spec.load_module("layer_metrics",
                            "ssm_states_advanced_pct.py").read
    tick = {"occupancy": 60, "chunk_tokens": 0}
    flight = [
        dict(tick, t_s=2.0, ssm_states_advanced=300),
        dict(tick, t_s=3.0, ssm_states_advanced=320, occupancy=64),
        dict(tick, t_s=4.0, ssm_states_advanced=320, chunk_tokens=256),
        dict(tick, t_s=0.5, ssm_states_advanced=5),      # before the window
    ]
    run = types.SimpleNamespace(flight=flight, cell=cell, t_open=1.0,
                                t_end=10.0)
    assert read(run) == pytest.approx(100.0)
    flight[0]["ssm_states_advanced"] = 320       # an idle slot rewritten
    assert read(run) > 100.0


# -- two kinds of row for the same tokens: the EVA cell (ISSUE 44) -----------


def test_the_eva_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell = spec.cell(EVA_CELL)
    assert cell.chips == 1 and cell.config["family"] == "evabyte"
    assert cell.config["name"] == "evabyte"
    for d, mod in (("references", cell.reference()),
                   ("adapters", cell.adapter())):
        assert mod.__file__.endswith(os.path.join(d, "evabyte.py"))
    with open(cell.reference().__file__) as f:
        text = f.read()
    assert "tree_attention_tpu" not in text.split('"""', 2)[2]
    assert "import benchmark" not in text and "from benchmark" not in text
    # One dense softmax over both sets: the reference never merges partials.
    assert "jax.nn.softmax(jnp.where(see" in text \
        and "merge_partials" not in text and "logsumexp" not in text
    # A traffic file of its own (data only), the cell the ninth of nine,
    # none on four chips.
    assert cell.traffic["kind"] == "backlog"
    assert spec.find("traffic", "bytedoc_backlog.json")
    assert [w["name"] for w in spec.data["workloads"]][8:] == [
        EVA_CELL, FH_CELL, PF_CELL]
    assert all(w["chips"] == 1 for w in spec.data["workloads"])
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    for name in ("occupancy_pct", "kv_blocks_peak_pct", "hbm_peak_gb",
                 "tick_rows_useful_pct", "paged_steps_run_pct",
                 "window_blocks_held_pct", "tick_ahead_pct",
                 "device_idle_pct", "decode_tick_p50_ms",
                 "mixed_tick_p50_ms") + tuple(PARTS_DENSE):
        assert name in names, name
    assert before_setup(names)[-5:] == EVA_METRICS
    # Another kernel's name would read another kernel's cost file.
    for name in ("attn_kernel_ms_tick", "flash_decode_paged_roofline",
                 "window_attn_ms_tick", "window_decode_paged_roofline",
                 "mla_decode_ms_tick", "moe_ffn_ms_tick", "dec_moe_ms_tick",
                 "dec_conv_ms_tick", "mixer_rest_ms_tick",
                 "ssm_update_ms_tick", "ttft_p50_ms"):
        assert name not in names
    listed = {m["name"]: m for m in spec.data["per_layer"]}
    for name in EVA_METRICS:
        assert listed[name]["workloads"] == [EVA_CELL]
        assert listed[name]["moves"] == "tbt_p50_ms"
    assert [listed[n]["layer"] for n in EVA_METRICS] == [
        "kernels"] * 4 + ["block pool"]
    assert listed["eva_local_decode_roofline"]["unit"] == "%" \
        == listed["eva_summary_decode_roofline"]["unit"]
    assert listed["eva_summaries_written_pct"]["source"] == "program_counter"
    for w in spec.data["workloads"][:8]:
        assert not set(EVA_METRICS) & {
            m["name"] for m in spec.cell(w["name"]).per_layer}
    assert cell.config["serving"] == {
        "slots": 16, "cache_len": 16384, "kv_layout": "paged",
        "kv_block": 64, "admission": "chunked", "prefill_chunk": 256,
        "prefix_cache": False}
    assert list(cell.config["correct"]["limits"]) == ["gap_mean"]
    for kernel in ("eva_local_decode", "eva_summary_decode"):
        of_model, calls = cell.adapter().kernel_call(cell.config, kernel)
        assert calls == 8 and of_model == {
            "heads": 32, "kv_heads": 32, "head": 128, "dtype_bytes": 2,
            "window": 2048, "chunk": 16}
        assert spec.load_module("kernel_costs", kernel + ".py").cost
    for kernel in ("flash_decode_paged", "window_decode_paged",
                   "mla_decode_paged"):
        assert cell.adapter().kernel_call(cell.config, kernel) is None
    # The traffic: 16 values each in 4 balanced groups of 4, every value a
    # multiple of 64, the largest pair inside the cache.
    from benchmark import grid
    prompts, outputs = (grid.values(cell.traffic[k])
                        for k in ("prompts", "outputs"))
    assert (min(prompts), max(prompts), sum(prompts) / 16) == (
        4096, 12288, 8192)
    assert (min(outputs), max(outputs), sum(outputs) / 16) == (
        1024, 3072, 2048)
    assert all(v % 64 == 0 for v in prompts + outputs)
    assert max(prompts) + max(outputs) == 15360 \
        <= cell.config["serving"]["cache_len"]
    for k, vals in (("prompts", prompts), ("outputs", outputs)):
        assert cell.traffic[k] == grid.balanced_groups(vals, 4)
    assert cell.config["vocab_size"] == 320


def test_the_eva_configurations_file_against_the_catalog():
    """Every number of the catalog's ``config`` under the same key but the
    one ``reduced`` lists; the cut's arithmetic; every assumed rule marked
    unconfirmed; the drafter named as not built."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "evabyte")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    assert c["source"] == entry["source"]
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "EvaByte")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) \
        == (8, 32)
    assert (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["window_size"], c["chunk_size"], c["vocab_size"],
            c["num_pred_heads"]) == (4096, 11008, 32, 32, 2048, 16, 320, 8)
    h, ffn = c["hidden_size"], c["intermediate_size"]
    layer = 4 * h * h + 3 * h * ffn + 2 * 32 * 128 + 2 * h
    ends = 320 * h + 8 * 320 * h
    assert abs(layer / 1e6 - 202.4) < 0.05
    assert abs((32 * layer + ends) / 1e9 - 6.49) < 0.005
    assert abs((8 * layer + ends) / 1e6 - 1630.9) < 0.2 \
        and "1,630.9M" in c["why_reduced"]
    dep = c["deployment"]
    assert (dep["chips"], dep["pipeline_stages"], dep["stage"]) == (4, 4, 0)
    assert c["block"] == dict(
        c["block"], summary_key="weighted_plus_mu",
        summary_logits_scaled=False, summary_after_rotary=True,
        window_rule="aligned")
    for rule in ("summary_key", "summary_logits_scaled",
                 "summary_after_rotary", "window_rule", "head_0"):
        assert "unconfirmed" in c["assumed"]["unconfirmed"][rule]
    assert "multibyte_self_speculation" in c["not_built"]
    assert set(c["assumed"]["seeded_scales"]) == {
        "embedding_std", "head_std", "attn_out_std", "dense_down_std",
        "norm_gain_std", "phi_std", "mu_std"}
    # The two pools the file's arithmetic names.
    pools = Spec(BENCH).cell(EVA_CELL).adapter().pools(c)
    assert pools["wk"] == (8, 608, 32, 64, 128) \
        and pools["k"] == (8, 256, 32, 64, 128)
    assert (pools["table"], pools["wtable"]) == ((16, 16), (16, 256))
    block_mb = 2 * 8 * 32 * 64 * 128 * 2 / 1e6
    assert abs(608 * block_mb / 1e3 - 5.10) < 0.01 \
        and abs(256 * block_mb / 1e3 - 2.15) < 0.01


def test_the_eva_adapter_refuses_another_model_at_once():
    spec = Spec(BENCH)
    cell = spec.cell(EVA_CELL)
    adapter = cell.adapter()
    with pytest.raises(SpecError, match="cannot read"):
        adapter.build({"family": "evabyte"}, [], 0, "cpu", None)
    from tree_attention_tpu.models.transformer import model_from_config
    # A program that knows no ``attention_class`` reads the file as a dense
    # rotary model (the parent commit does): what it built is held to the
    # file and refused, before a weight is drawn.
    dense = {k: v for k, v in cell.config.items()
             if k not in ("attention_class", "window_size", "chunk_size")}
    with pytest.raises(SpecError, match="built otherwise"):
        adapter._hold_to_file(model_from_config(dense), cell.config)
    import types
    with pytest.raises(SpecError, match="cannot express"):
        adapter._hold_to_file(types.SimpleNamespace(d_model=4096),
                              cell.config)
    # A file that says another rule than the one built.
    config = dict(cell.config)
    config["block"] = dict(config["block"], summary_key="mean_plus_mu")
    with pytest.raises(SpecError, match="summary_key"):
        adapter._hold_to_file(model_from_config(cell.config), config)


@pytest.mark.parametrize("name", EVA_METRICS)
def test_the_eva_metrics_read_nothing_where_there_is_nothing(name):
    """An untraced run, a run without flight records, a trace without a
    decode tick and a program without the counters (the parent's records)
    give None, never a number and never an exception."""
    import types

    spec = Spec(BENCH)
    cell = spec.cell(EVA_CELL)
    read = spec.load_module("layer_metrics", name + ".py").read
    peaks = spec.load_json("peaks.json")["TPU v5 lite"]
    empty = {"offset_s": 0.0, "t0": 5.0, "t1": 8.0, "devices": 1,
             "events": {}}
    parent = [{"t_s": 2.0, "occupancy": 4, "chunk_tokens": 0},
              {"t_s": 3.0, "occupancy": 4, "chunk_tokens": 0}]
    for trace, flight in ((None, None), (None, []), (empty, None),
                          (empty, [{"t_s": 1.0}]), (empty, parent)):
        run = types.SimpleNamespace(trace=trace, flight=flight, cell=cell,
                                    recs=[], peaks=peaks, t_open=1.0,
                                    t_end=10.0)
        assert read(run) is None


def test_eva_summaries_written_pct_reads_the_steps_counter():
    import types

    spec = Spec(BENCH)
    read = spec.load_module("layer_metrics",
                            "eva_summaries_written_pct.py").read
    tick = {"occupancy": 16, "chunk_tokens": 0}
    flight = [
        dict(tick, t_s=2.0, eva_summaries_written=8, eva_summaries_due=8),
        dict(tick, t_s=3.0, eva_summaries_written=16, eva_summaries_due=16),
        dict(tick, t_s=3.5, eva_summaries_written=0, eva_summaries_due=0),
        dict(tick, t_s=4.0, eva_summaries_written=128, eva_summaries_due=128,
             chunk_tokens=256),
        dict(tick, t_s=0.5, eva_summaries_written=5,     # before the window
             eva_summaries_due=8),
    ]
    run = types.SimpleNamespace(flight=flight, t_open=1.0, t_end=10.0)
    assert read(run) == pytest.approx(100.0)
    flight[0]["eva_summaries_written"] = 16      # a row written twice
    assert read(run) > 100.0
    flight[0]["eva_summaries_written"] = 0       # a closed chunk skipped
    assert read(run) < 100.0
    # Nothing due: nothing to read, not 0 over 0.
    run.flight = [flight[2], dict(flight[2], t_s=3.7)]
    assert read(run) is None


# -- two mixers a layer on one norm: the two-branch cell (ISSUE 47) ----------


def test_the_two_branch_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell, ns = spec.cell(FH_CELL), spec.cell(NS_CELL)
    assert cell.chips == 1 and cell.config["family"] == "falcon_h1"
    assert cell.config["name"] == "falcon-h1-34b-instruct"
    for d, mod in (("references", cell.reference()),
                   ("adapters", cell.adapter())):
        assert mod.__file__.endswith(os.path.join(d, "falcon_h1.py"))
    with open(cell.reference().__file__) as f:
        text = f.read()
    body = text.split('"""', 2)[2]
    assert "tree_attention_tpu" not in body and "benchmark" not in body
    assert "lax.scan(token" in text       # the recurrence, token by token
    assert 'jax.nn.softmax(jnp.where(see' in text   # one dense softmax
    assert cell.reference().CONTROLS == (
        "int8", "serial", "no_ssm_branch", "unit_multipliers")
    # The traffic file that was there, the cell the tenth of ten, none on
    # four chips; no metric, cost file, reader or mix of its own.
    assert cell.traffic == ns.traffic and cell.traffic["kind"] == "backlog"
    assert [w["name"] for w in spec.data["workloads"]][9:] == [
        FH_CELL, PF_CELL]
    assert all(w["chips"] == 1 for w in spec.data["workloads"])
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    for name in ("occupancy_pct", "kv_blocks_peak_pct", "hbm_peak_gb",
                 "tick_rows_useful_pct", "paged_steps_run_pct",
                 "tick_unscoped_pct", "attn_kernel_ms_tick",
                 "flash_decode_paged_roofline", "ssm_update_ms_tick",
                 "ssm_decode_update_roofline", "ssm_states_advanced_pct",
                 "device_idle_pct", "decode_tick_p50_ms",
                 "mixed_tick_p50_ms", "tick_ahead_pct") + tuple(
            n for n in PARTS_ALL if "_moe_" not in n):
        assert name in names, name
    for name in names:
        assert not name.startswith(("moe_", "mla_", "window_", "eva_",
                                    "mixer_rest", "zero_expert", "expert",
                                    "real_experts", "dec_moe", "mix_moe",
                                    "ttft", "gen_late", "queue_wait"))
    assert cell.config["serving"] == {
        "slots": 48, "cache_len": 2560, "kv_layout": "paged", "kv_block": 64,
        "admission": "chunked", "prefill_chunk": 256, "prefix_cache": False}
    assert list(cell.config["correct"]["limits"]) == ["gap_mean"]
    for kernel in ("flash_decode_paged", "ssm_decode_update"):
        assert cell.adapter().kernel_call(cell.config, kernel)[1] == 9
        assert spec.load_module("kernel_costs", kernel + ".py").cost
    assert cell.adapter().kernel_call(cell.config, "mla_decode_paged") is None


def test_the_two_branch_configurations_file_against_the_catalog():
    """Every number of the catalog's ``config`` under the same key but the
    two keys ``reduced`` lists; the cut's arithmetic; every assumed rule
    marked unconfirmed; every published multiplier as it is published."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "falcon-h1-34b-instruct")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert c["source"] == entry["source"]
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "Falcon-H1-34B-Instruct")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
        assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    assert (c["num_hidden_layers"], c["vocab_size"]) == (9, 32640)
    assert c["published"] == {"num_hidden_layers": 72, "vocab_size": 261120}
    # Floors: whole periods of one layer, 9 >= 4, exactly an eighth of the
    # vocabulary.
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    h, f_, inner = c["hidden_size"], c["intermediate_size"], c["mamba_d_ssm"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    attn = 2 * h * q + 2 * h * kv
    ssm = h * (2 * inner + 2 * gn + c["mamba_n_heads"]) + inner * h \
        + (c["mamba_d_conv"] + 1) * (inner + 2 * gn) \
        + 3 * c["mamba_n_heads"] + inner
    layer = attn + ssm + 3 * h * f_ + 2 * h
    assert (attn, ssm, 3 * h * f_) == (31_457_280, 68_351_072, 330_301_440)
    assert abs(layer / 1e6 - 430.12) < 0.005
    held = 9 * layer + 2 * c["vocab_size"] * h + h
    assert abs(held / 1e6 - 4205.3) < 0.05 and "4,205.3M" in c["why_reduced"]
    whole = 72 * layer + 2 * 261120 * h + h
    assert abs(whole / 1e9 - 33.64) < 0.005 and "33.64B" in c["why_reduced"]
    dep = c["deployment"]
    assert (dep["chips"], dep["pipeline_stages"], dep["stage"]) == (8, 8, 0)
    assert c["block"] == dict(
        c["block"], mixer_arrangement="parallel_shared_norm",
        mup_segments=["z", "x", "B", "C", "dt"],
        rotary_convention="half_split")
    for rule in ("mixer_arrangement", "mup_segments", "rotary_convention",
                 "time_step_limit", "grouped_norm"):
        assert "unconfirmed" in c["assumed"]["unconfirmed"][rule]
    assert "float32" in c["assumed"]["state_dtype"]
    assert set(c["assumed"]["seeded_scales"]) == {
        "embedding_std", "head_std", "qk_std", "v_std", "attn_out_std",
        "ssm_in_std", "ssm_out_std", "mlp_gate_std", "mlp_up_std",
        "mlp_down_std", "gain_mean", "gain_std"}
    assert len(c["ssm_multipliers"]) == 5 and len(c["mlp_multipliers"]) == 2


def test_the_two_branch_adapter_refuses_another_model_at_once():
    spec = Spec(BENCH)
    cell = spec.cell(FH_CELL)
    adapter = cell.adapter()
    with pytest.raises(SpecError, match="cannot read"):
        adapter.build({"family": "falcon_h1"}, [], 0, "cpu", None)
    from tree_attention_tpu.models.transformer import model_from_config
    model = model_from_config(cell.config)
    adapter._hold_to_file(model, cell.config)
    # A file that states another multiplier than the engine would apply.
    for key, value in (("key_multiplier", 1.0),
                       ("ssm_multipliers", [1.0] * 5),
                       ("mamba_d_state", 128)):
        with pytest.raises(SpecError, match="built otherwise"):
            adapter._hold_to_file(model, dict(cell.config, **{key: value}))
    # A program whose model has no two-branch layer (the serial state
    # family's): refused before a weight is drawn.
    other = model_from_config(spec.cell(NS_CELL).config)
    with pytest.raises(SpecError, match="built otherwise"):
        adapter._hold_to_file(other, cell.config)
    # A program that knows no multipliers (what the parent commit builds).
    import types
    with pytest.raises(SpecError, match="cannot express"):
        adapter._hold_to_file(types.SimpleNamespace(ssm=None), cell.config)


# -- a decoder that feeds a second decoder: the decoder-hybrid cell (ISSUE 52)


def test_the_decoder_hybrid_cell_resolves_every_file_it_names():
    spec = Spec(BENCH)
    cell, kx = spec.cell(PF_CELL), spec.cell(KX_CELL)
    assert cell.chips == 1 and cell.config["family"] == "phi4flash"
    assert cell.config["name"] == "phi-4-mini-flash-reasoning"
    for d, mod in (("references", cell.reference()),
                   ("adapters", cell.adapter())):
        assert mod.__file__.endswith(os.path.join(d, "phi4flash.py"))
    with open(cell.reference().__file__) as f:
        text = f.read()
    body = text.split('"""', 2)[2]
    assert "tree_attention_tpu" not in body and "benchmark" not in body
    assert "lax.scan(token" in text       # the recurrence, token by token
    assert "jax.nn.softmax(jnp.where(see" in text   # one dense softmax a head
    assert "_pack" not in body            # the definition, no packed row
    assert cell.reference().CONTROLS == (
        "int8", "no_diff", "own_rows", "stale_memory", "scalar_decay")
    # The traffic file that was there, the cell the eleventh of eleven, none
    # on four chips; one cost file and four readers of its own.
    assert cell.traffic == kx.traffic and cell.traffic["kind"] == "backlog"
    assert [w["name"] for w in spec.data["workloads"]][10:] == [PF_CELL]
    assert all(w["chips"] == 1 for w in spec.data["workloads"])
    assert [m["name"] for m in cell.end_to_end] == [
        "out_tok_s", "tbt_p50_ms", "tbt_p99_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names[-4:] == DECODER_HYBRID_METRICS
    assert [m["name"] for m in spec.data["per_layer"]][-4:] \
        == DECODER_HYBRID_METRICS
    for m in spec.data["per_layer"][-4:]:
        assert m["workloads"] == [PF_CELL]
        assert m["moves"] == ("tbt_p99_ms" if m["name"]
                              == "rows_past_exit_pct" else "tbt_p50_ms")
    for name in names:
        assert spec.load_module("layer_metrics", name + ".py").read
    for name in ("occupancy_pct", "kv_blocks_peak_pct", "hbm_peak_gb",
                 "tick_rows_useful_pct", "paged_steps_run_pct",
                 "tick_unscoped_pct", "attn_kernel_ms_tick",
                 "flash_decode_paged_roofline", "window_attn_ms_tick",
                 "window_decode_paged_roofline", "window_blocks_held_pct",
                 "device_idle_pct", "decode_tick_p50_ms",
                 "mixed_tick_p50_ms", "tick_ahead_pct") + tuple(
            n for n in PARTS_ALL if n.startswith("dec_")
            and "_moe_" not in n) + tuple(SETUP_METRICS):
        assert name in names, name
    for name in names:
        assert not name.startswith(("moe_", "mla_", "eva_", "mixer_rest",
                                    "zero_expert", "expert", "real_experts",
                                    "dec_moe", "mix_", "ssm_", "ttft",
                                    "gen_late", "queue_wait"))
    assert cell.config["serving"] == {
        "slots": 48, "cache_len": 9216, "kv_layout": "paged", "kv_block": 64,
        "admission": "chunked", "prefill_chunk": 256, "prefix_cache": False,
        "sampling": "greedy"}
    assert list(cell.config["correct"]["limits"]) == ["gap_mean"]
    assert (cell.config["correct"]["min_tokens"],
            cell.config["correct"]["max_requests"]) == (2048, 3)
    calls = {k: cell.adapter().kernel_call(cell.config, k)[1]
             for k in ("flash_decode_paged", "window_decode_paged",
                       "ssm1_scan")}
    assert calls == {"flash_decode_paged": 8, "window_decode_paged": 8,
                     "ssm1_scan": 9}
    assert spec.load_module("kernel_costs", "ssm1_scan.py").cost
    assert cell.adapter().kernel_call(cell.config, "ssm_decode_update") is None


def test_the_decoder_hybrid_configurations_file_against_the_catalog():
    """Every number of the catalog's ``config`` under the same key, nothing
    reduced; the count's arithmetic; every assumed rule marked unconfirmed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    with open(BENCH) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "phi-4-mini-flash-reasoning")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert entry["reduced"] == c["reduced"] == []
    assert c["source"] == entry["source"]
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "Phi-4-mini-flash-reasoning")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert c[k] == v, k
    h, f_, n = c["hidden_size"], c["intermediate_size"], 32
    a = c["assumed"]
    inner, st, r = a["mamba_expand"] * h, a["mamba_d_state"], \
        a["mamba_dt_rank"]
    ln = 2 * h
    mlp = 3 * h * f_ + ln
    mamba = 2 * h * inner + (a["mamba_d_conv"] + 1) * inner \
        + inner * (r + 2 * st) + r * inner + 2 * inner + inner * st \
        + inner * h + ln
    tail = 4 * (h // c["num_attention_heads"]) \
        + 2 * (h // c["num_attention_heads"]) + ln
    own = h * 2 * h + 2 * h + h * h + h + tail
    cross = h * h + h + h * h + h + tail
    gmu = 2 * h * inner + ln
    whole = 32 * mlp + 9 * mamba + 9 * own + 7 * cross + 7 * gmu \
        + c["vocab_size"] * h + ln
    assert whole == 3_852_562_944 and "3,852,562,944" in c["why_reduced"]
    assert c["deployment"]["chips"] == 1
    assert c["block"] == dict(
        c["block"], decoder_split="sambay", attention="differential",
        diff_pairing="adjacent", cross_attention="differential",
        lambda_depth="layer_index_from_0",
        gmu_memory="scan_output_before_gate", mlp_order="gate_up",
        window_span=512)
    for rule in ("decoder_split", "diff_pairing", "lambda_depth",
                 "cross_attention", "window_span", "mlp_order",
                 "gmu_memory", "biases"):
        assert "unconfirmed" in a["unconfirmed"][rule]
    assert "float32" in a["state_dtype"]
    assert set(a["seeded_scales"]) == {
        "embedding_std", "ln_gain_std", "ln_bias_std", "mlp_in_std",
        "mlp_out_std", "ssm_in_std", "ssm_x_std", "ssm_dt_std",
        "ssm_out_std", "qkv_std", "attn_bias_std", "attn_out_bias_std",
        "attn_out_std", "sub_gain_mean", "sub_gain_std", "gmu_in_std",
        "gmu_out_std"}


def test_the_decoder_hybrid_adapter_refuses_another_model_at_once():
    spec = Spec(BENCH)
    cell = spec.cell(PF_CELL)
    adapter, ref = cell.adapter(), cell.reference()
    with pytest.raises(SpecError, match="cannot read"):
        adapter.build({"family": "phi4flash"}, [], 0, "cpu", ref)
    from tree_attention_tpu.models.transformer import model_from_config
    model = model_from_config(cell.config)
    adapter._hold_to_file(model, cell.config, ref)
    # A file that states another width than the engine would build.
    for key, value in (("sliding_window", 256), ("num_hidden_layers", 28)):
        with pytest.raises(SpecError, match="built otherwise"):
            adapter._hold_to_file(
                model, dict(cell.config, **{
                    key: value, "block": dict(cell.config["block"],
                                              window_span=value
                                              if key == "sliding_window"
                                              else 512)}), ref)
    # A program whose model is another family's (the window family's), and
    # one that knows no Mamba-1 widths (what the parent commit builds: a
    # dense rotary model): refused before a weight is drawn.
    other = model_from_config(spec.cell(KX_CELL).config)
    with pytest.raises(SpecError, match="cannot express"):
        adapter._hold_to_file(other, cell.config, ref)
    import types
    with pytest.raises(SpecError, match="cannot express"):
        adapter._hold_to_file(types.SimpleNamespace(ssm1=None), cell.config,
                              ref)


@pytest.mark.parametrize("name", DECODER_HYBRID_METRICS)
def test_the_decoder_hybrid_metrics_read_nothing_where_there_is_nothing(name):
    """Beside a program without the kernel, the counters or the trace (a
    parent commit, an untraced run) every one of the four gives None and
    does not raise."""
    import types

    spec = Spec(BENCH)
    read = spec.load_module("layer_metrics", name + ".py").read
    cell = spec.cell(PF_CELL)
    for flight in (None, [], [{"t_s": 1.0, "occupancy": 4,
                               "chunk_tokens": 0}]):
        run = types.SimpleNamespace(cell=cell, trace=None, flight=flight,
                                    recs=[], peaks=None, t_open=0.0,
                                    t_end=10.0, report={})
        assert read(run) is None


def test_rows_past_exit_pct_reads_the_mixed_ticks_counters():
    import types

    spec = Spec(BENCH)
    read = spec.load_module("layer_metrics", "rows_past_exit_pct.py").read
    flight = [
        {"t_s": 1.0, "chunk_tokens": 256, "rows_self": 304, "rows_cross": 48},
        {"t_s": 2.0, "chunk_tokens": 100, "rows_self": 176, "rows_cross": 48},
        {"t_s": 3.0, "chunk_tokens": 0, "rows_self": 48, "rows_cross": 48},
        {"t_s": 30.0, "chunk_tokens": 256, "rows_self": 304,
         "rows_cross": 304},                     # outside the window
    ]
    run = types.SimpleNamespace(cell=spec.cell(PF_CELL), flight=flight,
                                t_open=0.0, t_end=10.0)
    assert read(run) == pytest.approx(100.0 * 96 / 480)
    states = spec.load_module("layer_metrics",
                              "ssm1_states_advanced_pct.py").read
    run.flight = [{"t_s": 1.0, "chunk_tokens": 0, "occupancy": 40,
                   "ssm_states_advanced": 360},
                  {"t_s": 2.0, "chunk_tokens": 0, "occupancy": 48,
                   "ssm_states_advanced": 432},
                  {"t_s": 3.0, "chunk_tokens": 64, "occupancy": 48,
                   "ssm_states_advanced": 441}]
    assert states(run) == pytest.approx(100.0)
