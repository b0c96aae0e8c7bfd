"""The control of the ``correct`` gate, at a size a test can hold.

On the chip, at each cell's own size, the reference computed in int8 reads a
mean gap 27 to 35 times the sound program's (PERF.md has the readings). Here,
on the CPU, the engine's own bf16 arithmetic is about as coarse as int8, so
the control that is held against the program's tokens is fp8; int8 is held
against the reference's own tokens, where the sound reading is exactly 0.
"""

import os
import time

import numpy as np
import pytest

from benchmark import check, harness
from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal",
                         "BENCHMARK.json")


@pytest.fixture(scope="module")
def small():
    return Spec(REHEARSAL).cell("small_sat")


@pytest.fixture(scope="module")
def reference(small):
    return small.reference()


def test_lower_precision_in_the_programs_place_is_not_correct(small):
    lines = []
    line = harness.run_cell(small, 3, 2.5, False, t_start=time.monotonic(),
                            require_tpu=False, say=lines.append,
                            control="fp8")
    assert line["correct"] is True and line["failed"] == 0
    sound = next(ln for ln in lines if ln.get("info") == "compared")
    control = next(ln for ln in lines if ln.get("info") == "control")
    assert sound["tokens_compared"] >= 200
    limits = small.config["correct"]["limits"]
    assert control["correct"] is False
    assert control["gap_mean"] > 3 * limits["gap_mean"]
    got = {r["number"]: r["value"] for r in sound["rows"]}
    assert got["gap_mean"] < limits["gap_mean"] / 3


def test_int8_moves_the_token_the_reference_puts_first(small, reference):
    """The reference's own greedy tokens read a gap of exactly 0; the same
    positions judged by the int8 forward do not."""
    w = reference.Widths.of(small.config)
    weights = reference.init_weights(7, w)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, w.vocab, size=96, dtype=np.int32)
    served = []
    for _ in range(24):
        tokens = np.concatenate([prompt, np.asarray(served, np.int32)])
        logits = reference.logits_at(weights, w, tokens,
                                     np.asarray([len(tokens) - 1]),
                                     pad_to=128)
        served.append(int(logits[0].argmax()))
    served = np.asarray(served, np.int32)
    own, _ = check.served_gaps(reference, weights, w, prompt, served)
    assert own.max() == 0.0
    other, judged = check.served_gaps(reference, weights, w, prompt, served,
                                      control="int8")
    fp8, _ = check.served_gaps(reference, weights, w, prompt, served,
                               control="fp8")
    assert (other >= 0).all() and fp8.mean() > other.mean() >= 0.0
    assert (judged != served).any() or other.max() == 0.0
    ruling = check.verdict({"gap_max": float(fp8.max()),
                            "gap_mean": float(fp8.mean())},
                           {"gap_max": 0.0, "gap_mean": 0.0})
    assert ruling["correct"] is False and len(ruling["compared"]) == 2


def test_the_same_seed_gives_the_same_weights_and_another_seed_others(
        small, reference):
    w = reference.Widths.of(small.config)
    a, b, c = (reference.init_weights(s, w) for s in (5, 5, 2 ** 31 + 6))
    assert a["wq"].dtype.name == "bfloat16" and a["ln1"].dtype.name == "float32"
    assert a["w1"].shape == (w.layers, w.hidden, w.ffn)
    assert (np.asarray(a["w2"], np.float32)
            == np.asarray(b["w2"], np.float32)).all()
    assert (np.asarray(a["w2"], np.float32)
            != np.asarray(c["w2"], np.float32)).any()
    assert abs(float(np.asarray(a["wq"], np.float32).std()) - 0.02) < 2e-3
