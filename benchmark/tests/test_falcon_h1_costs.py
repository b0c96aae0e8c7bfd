"""The two-branch configuration's kernels at their shapes: the cost functions
the benchmark already has against a hand count at ``falcon-h1-34b-instruct``'s
widths, and its adapter's answers for the kernels its cell's metrics read."""

import json
import os

from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = Spec(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "falconh1_agentturn_sat"


def test_ssm_decode_update_at_32_heads_of_128_by_256():
    cost = SPEC.load_module("kernel_costs", "ssm_decode_update.py").cost
    # 48 live slots: a slot's state of a layer 32 x 128 x 256 float32 =
    # 4,194,304 B read and written; x and y rows of 4096, B and C rows of
    # 2 x 256, 32 step sizes.
    got = cost(contexts=[900] * 48, q_rows=1, heads=32, head=128, state=256,
               groups=2, state_bytes=4)
    assert got["bytes"] == 48 * (2 * 4194304
                                 + (2 * 4096 + 2 * 512 + 32) * 4)
    assert got["flops"] == 48 * 6 * 32 * 128 * 256
    # The same bytes a slot a layer as 128 heads of 64 by 128 in 8 groups.
    other = cost(contexts=[1], q_rows=1, heads=128, head=64, state=128,
                 groups=8, state_bytes=4)
    assert 32 * 128 * 256 == 128 * 64 * 128
    assert other["flops"] == got["flops"] / 48


def test_flash_decode_paged_at_20_over_4_heads_of_128():
    cost = SPEC.load_module("kernel_costs", "flash_decode_paged.py").cost
    # A slot at 900 tokens: keys and values 2 x 900 x 4 x 128 bf16 (2,048 B
    # a token a layer), 20 query rows in and out.
    got = cost(contexts=[900, 300], q_rows=1, heads=20, kv_heads=4,
               head=128, dtype_bytes=2)
    assert got["bytes"] == 1200 * 2048 + 2 * 2 * 20 * 128 * 2
    assert got["flops"] == 4 * 20 * 128 * 1200


def test_the_adapter_answers_for_the_cells_kernels():
    cell = SPEC.cell(CELL)
    call = cell.adapter().kernel_call
    assert call(cell.config, "ssm_decode_update") == (
        {"heads": 32, "head": 128, "state": 256, "groups": 2,
         "state_bytes": 4}, 9)
    assert call(cell.config, "flash_decode_paged") == (
        {"heads": 20, "kv_heads": 4, "head": 128, "dtype_bytes": 2}, 9)
    for other in ("mla_decode_paged", "moe_grouped_matmul",
                  "moe_ungated_matmul", "window_decode_paged",
                  "eva_local_decode", "eva_summary_decode"):
        assert call(cell.config, other) is None
    names = {m["name"] for m in cell.per_layer}
    assert {"ssm_update_ms_tick", "ssm_decode_update_roofline",
            "ssm_states_advanced_pct", "attn_kernel_ms_tick",
            "flash_decode_paged_roofline", "dec_conv_ms_tick",
            "mix_conv_ms_tick", "dec_attn_ms_tick"} <= names
    assert not {"moe_ungated_ms_tick", "moe_ffn_ms_tick", "dec_moe_ms_tick",
                "mixer_rest_ms_tick", "mla_decode_ms_tick",
                "window_attn_ms_tick", "eva_local_ms_tick"} & names
    for name in names:
        assert SPEC.load_module("layer_metrics", name + ".py").read
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"]
             if c["name"] == cell.config["name"]][0]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert cell.traffic == SPEC.cell("nemotron3s_agentturn_sat").traffic
