"""A configuration names its family, and the harness reaches everything that
depends on the architecture through the family's two files, found by that
name over ``paths``: a made-up second family that only ``tests/rehearsal``
adds is served, and judged through its own reference; nothing of the dense
block is left outside its two files; the move did not move the weights."""

import hashlib
import json
import os
import re
import time
import types

import numpy as np
import pytest

from benchmark import harness
from benchmark.spec import Spec, SpecError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "BENCHMARK.json")
HERE = os.path.join(ROOT, "benchmark", "tests")
REHEARSAL = os.path.join(HERE, "rehearsal", "BENCHMARK.json")

# What only the dense family's files, the configuration files, the kernels'
# cost functions and the tests may say.
DENSE_WORDS = ("hidden_size", "num_key_value_heads", "num_attention_heads",
               "intermediate_size", "head_dim", "rope_theta", "rms_norm_eps",
               "_transformer_config", "init_params")
DENSE_LEAVES = ("embed", "ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3",
                "w2", "ln_f", "wout")
THE_FAMILYS_OWN = ("references", "adapters", "configs", "kernel_costs",
                   "tests")


def sources(but=()):
    """Every file git would commit under ``benchmark/``, but those under the
    directories ``but`` names: (path from ``benchmark/``, text)."""
    top = os.path.join(ROOT, "benchmark")
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"
                   and not (base == top and d in but)]
        for f in files:
            if not f.endswith(".pyc"):
                path = os.path.join(base, f)
                with open(path, errors="replace") as fh:
                    yield os.path.relpath(path, top), fh.read()


def rehearse(cell, seed=5):
    lines = []
    line = harness.run_cell(cell, seed, 1.0, False, t_start=time.monotonic(),
                            require_tpu=False, say=lines.append)
    compared = next(ln for ln in lines if ln.get("info") == "compared")
    return line, {r["number"]: r for r in compared["rows"]}


def test_a_family_only_the_rehearsal_adds_is_served_and_judged_by_its_own():
    cell = Spec(REHEARSAL).cell("tiny_tied_sat")
    assert cell.config["family"] == "toy_tied"
    line, rows = rehearse(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and rows["gap_mean"]["ok"]


def test_judged_by_a_reference_that_leaves_the_head_untied_it_is_not_correct(
        monkeypatch):
    """The same run, with the family's ``logits_at`` swapped for the dense
    block's with a head of its own: the comparison goes through the
    family's reference, and through nothing else."""
    cell = Spec(REHEARSAL).cell("tiny_tied_sat")
    dense = Spec(REHEARSAL).load_module("references", "llama_dense.py")

    def untied(weights, w, tokens, rows, **kw):
        own_head = dense.init_weights(7, w)["wout"]
        return dense.logits_at({**weights, "wout": own_head}, w, tokens,
                               rows, **kw)

    monkeypatch.setattr(cell.reference(), "logits_at", untied)
    line, rows = rehearse(cell)
    assert line["correct"] is False and line["failed"] == 0
    assert not rows["gap_mean"]["ok"] and not rows["gap_max"]["ok"]
    assert rows["gap_mean"]["value"] > 10 * rows["gap_mean"]["limit"]


def test_the_familys_files_are_found_by_its_name_over_paths():
    spec = Spec(REHEARSAL)
    tied, dense = spec.cell("tiny_tied_sat"), spec.cell("tiny_sat")
    rehearsal = os.path.dirname(REHEARSAL)
    for find, kind in ((tied.reference, "references"),
                       (tied.adapter, "adapters")):
        assert find().__file__ == os.path.join(rehearsal, kind, "toy_tied.py")
    # What the rehearsal does not add comes from the benchmark's own files.
    assert dense.reference().__file__ == os.path.join(
        ROOT, "benchmark", "references", "llama_dense.py")
    assert dense.adapter().__file__ == os.path.join(
        ROOT, "benchmark", "adapters", "llama_dense.py")
    for mod in (tied.reference(), dense.reference()):
        assert all(hasattr(mod, n) for n in (
            "Widths", "init_weights", "logits_at", "CONTROLS"))
    for mod in (tied.adapter(), dense.adapter()):
        assert hasattr(mod, "build") and hasattr(mod, "kernel_call")
    assert tied.reference() is tied.reference()      # loaded once
    # Every cell of the benchmark itself names a family whose files exist.
    bench = Spec(BENCH)
    for w in bench.data["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.reference().CONTROLS and cell.adapter().build


def test_a_configuration_without_a_family_is_an_error_that_says_so(tmp_path):
    with open(REHEARSAL) as f:
        bench = json.load(f)
    with open(os.path.join(os.path.dirname(REHEARSAL), "configs",
                           "tiny.json")) as f:
        config = json.load(f)
    del config["family"]
    os.makedirs(tmp_path / "configs")
    with open(tmp_path / "configs" / "tiny.json", "w") as f:
        json.dump(config, f)
    bench["paths"] = [os.path.dirname(REHEARSAL),
                      os.path.join(ROOT, "benchmark")]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    with pytest.raises(SpecError, match="names no family"):
        Spec(str(tmp_path / "BENCHMARK.json")).cell("tiny_sat")
    # A family whose files are nowhere under ``paths`` is one too.
    config["family"] = "no_such_family"
    with open(tmp_path / "configs" / "tiny.json", "w") as f:
        json.dump(config, f)
    cell = Spec(str(tmp_path / "BENCHMARK.json")).cell("tiny_sat")
    with pytest.raises(SpecError, match="no_such_family"):
        cell.reference()


def test_the_made_up_family_is_named_nowhere_outside_the_tests():
    for rel, text in sources(but=("tests",)):
        assert "toy_tied" not in text and "tiny_tied" not in text, rel


def test_nothing_of_the_dense_block_outside_its_familys_files():
    leaf = re.compile(r"""["'](%s)["']""" % "|".join(DENSE_LEAVES))
    seen = 0
    for rel, text in sources(but=THE_FAMILYS_OWN):
        seen += 1
        for word in DENSE_WORDS:
            assert word not in text, (rel, word)
        assert not leaf.search(text), (rel, leaf.search(text).group(0))
        if rel.endswith(".py"):
            assert "llama_dense" not in text, rel
    assert seen >= 40                   # the harness, the readers, the mixes
    # The control of this test: the family's own files do say them.
    with open(os.path.join(ROOT, "benchmark", "adapters",
                           "llama_dense.py")) as f:
        text = f.read()
    assert all(word in text for word in DENSE_WORDS) and leaf.search(text)


def test_the_move_did_not_move_the_weights():
    """SHA-256 of every leaf of ``init_weights(7, Widths.of(small))`` as the
    parent's ``benchmark/reference.py`` made them on the CPU (PR 26)."""
    ref = Spec(REHEARSAL).cell("small_sat")
    with open(os.path.join(HERE, "fixtures",
                           "llama_dense_small_seed7.sha256.json")) as f:
        want = json.load(f)
    mod = ref.reference()
    weights = mod.init_weights(7, mod.Widths.of(ref.config))
    got = {}
    for name, leaf in weights.items():
        a = np.asarray(leaf)
        got[name] = {"dtype": str(a.dtype), "shape": list(a.shape),
                     "sha256": hashlib.sha256(a.tobytes()).hexdigest()}
    assert got == want


def test_a_kernels_cost_goes_through_the_familys_kernel_call(monkeypatch):
    """One decode tick of two live requests on hand-made records: the
    kernel's seconds are its events inside the tick, its bytes the cost
    function's at what ``kernel_call`` says of the model, once a layer."""
    from benchmark import kernels
    from benchmark import trace_reduce as tr

    cell = Spec(BENCH).cell("yi6b_chat_sat")
    of_model, calls = cell.adapter().kernel_call(cell.config,
                                                 "flash_decode_paged")
    assert of_model == {"heads": 32, "kv_heads": 4, "head": 128,
                        "dtype_bytes": 2} and calls == 16
    off = 1000.0                 # the trace's clock minus time.monotonic()
    flight = [{"t_s": 10.0, "chunk_tokens": 0, "occupancy": 2},
              {"t_s": 10.030, "chunk_tokens": 256, "occupancy": 2},
              {"t_s": 10.120, "chunk_tokens": 0, "occupancy": 2}]
    events = [("flash_decode_paged.9", off + 10.001 + 0.001 * i, 0.0005)
              for i in range(16)]
    events += [("fusion.1", off + 10.020, 0.005),
               ("flash_decode_paged.9", off + 10.050, 0.0005)]  # mixed tick
    data = {"host": {"python": [(tr.BEGIN_MARK, off + 9.9, 0.0),
                                (tr.END_MARK, off + 10.2, 0.0)]},
            "devices": {"/device:TPU:0": events}}
    recs = [types.SimpleNamespace(prompt=[0] * 1000, stamps=[9.0, 9.5],
                                  finished=None),
            types.SimpleNamespace(prompt=[0] * 3000, stamps=[9.9],
                                  finished=None),
            types.SimpleNamespace(prompt=[0] * 500, stamps=[10.01],
                                  finished=None)]          # not live yet
    run_ = types.SimpleNamespace(
        cell=cell, flight=flight, recs=recs,
        trace=dict(tr.reduce_events(data), offset_s=off))
    k = kernels.in_decode_ticks(run_, "flash_decode_paged")
    assert k["ticks"] == 1 and k["seconds"] == pytest.approx(16 * 0.0005)
    kv = 2 * (1002 + 3001) * 4 * 128 * 2
    assert k["bytes"] == 16 * (kv + 2 * (2 * 32 * 128 * 2))
    assert k["flops"] == 16 * 4 * 32 * 128 * (1002 + 3001)
    # A family that never launches the kernel reads as nothing.
    assert cell.adapter().kernel_call(cell.config, "no_such_kernel") is None
    monkeypatch.setattr(cell.adapter(), "kernel_call", lambda c, k: None)
    assert kernels.in_decode_ticks(run_, "flash_decode_paged") is None
