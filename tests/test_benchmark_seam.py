"""The seam by which the benchmark finds a model family by name, guarded by
tier-1 (``benchmark/tests/test_seam.py`` holds the slower rehearsals, which
tier-1 does not run), and the cells that came through it: ``dsv2_codegen_sat``
and ``lcflash_agentturn_sat``.
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
                "mla_scale_q_lora", "mla_scale_kv_lora", "routed_branch")
LEAVES = ("embed", "ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3", "w2",
          "ln_f", "wout", "wqa", "wqb", "wkva", "wkvb", "router", "we1",
          "router_bias", "sub")
THE_FAMILYS_OWN = ("references", "adapters", "configs", "kernel_costs",
                   "tests")


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
    # (what later PRs appended for every cell comes after them: PR 32's).
    theirs = [m["name"] for m in spec.cell(CELL).per_layer]
    later = ["tick_ahead_pct"]
    assert theirs[-len(later):] == later
    assert names == theirs[:-len(later)] + [
        "zero_expert_pairs_pct", "real_experts_row_max_over_mean"] + later
    for m in cell.per_layer[-2 - len(later):-len(later)]:
        assert m["workloads"] == [LC_CELL] and m["layer"] == "expert layer"
        assert (m["source"], m["moves"]) == ("program_counter", "tbt_p50_ms")
    for w in spec.data["workloads"][:4]:
        assert "zero_expert_pairs_pct" not in [
            m["name"] for m in spec.cell(w["name"]).per_layer]
    assert len(spec.data["workloads"]) == 5
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
    assert listed[-1] == {
        "name": "tick_ahead_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "admission and scheduling",
        "moves": "tbt_p50_ms"}
    for w in json.load(open(BENCH))["workloads"]:
        assert "tick_ahead_pct" in [
            m["name"] for m in spec.cell(w["name"]).per_layer]
    read = spec.load_module("layer_metrics", "tick_ahead_pct.py").read
    run = types.SimpleNamespace(flight=flight, t_open=1.0, t_end=10.0)
    assert read(run) == (want if want is None else pytest.approx(want))
