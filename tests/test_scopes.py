"""The vocabulary of a model's parts and the table from a compiled program's
operations to it (``tree_attention_tpu/obs/scopes.py``): the parser on a
recorded text, and that the layer bodies use the one vocabulary.

The fixture is the optimized HLO of a small paged decode step (2 scanned
layers at width 256, 8 slots, the ``flash_decode_paged`` kernel) compiled for
a described v5e chip, source locations and backend configurations stripped:
fusions, a ``while`` with its body and condition, a Pallas custom call whose
result is a tuple, asynchronous copies.
"""

import ast
import os
import sys

import pytest

from tree_attention_tpu.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "paged_step_v5e.hlo.txt")


@pytest.fixture(scope="module")
def text():
    with open(FIXTURE) as f:
        return f.read()


@pytest.fixture(scope="module")
def parsed(text):
    return {i.op: i for i in scopes.instructions(text)}


@pytest.fixture(scope="module")
def rows(text):
    return {r[0]: r for r in scopes.table(text)}


# -- a scope path ------------------------------------------------------------


@pytest.mark.parametrize("op_name, scope", [
    ("jit(fn)/while/body/closed_call/ffn/jit(silu)/mul", "ffn/jit(silu)/mul"),
    ("jit(fn)/head/dot_general", "head/dot_general"),
    ("jit(fn)/while/body/squeeze", ""),
    # Two paths in one name (a fusion of a transpose and a reshape).
    ("jit(f)/attn_in/transpose;jit(f)/attn_in/reshape",
     "attn_in/transpose;jit(f)/attn_in/reshape"),
    # Only a whole component counts: ``conv`` is not ``conv_general``.
    ("jit(f)/conv_general_dilated", ""),
    ("jit(f)/route/experts/x", "route/experts/x"),      # the outermost
    ("", ""),
])
def test_scope_of_a_path(op_name, scope):
    assert scopes.scope_of(op_name) == scope


# -- the parser, on the recorded text ---------------------------------------


def test_a_fusion_reads_as_the_trace_names_it(parsed):
    """Name, the result's type and dimensions without its layout, and the
    scope from the fusion's own ``op_name``."""
    f = parsed["fusion.174"]
    assert (f.result, f.opcode) == ("bf16[8,512]", "fusion")
    assert f.scope.split("/")[0] == scopes.FFN
    assert f.computation != "main.29"           # inside the loop's body
    assert f.nbytes == 8 * 512 * 2


def test_a_pallas_call_is_a_row_with_a_tuple_result(parsed):
    k = parsed["flash_decode_paged.8"]
    assert k.opcode == "custom-call" and k.result == ""
    assert k.scope == ("attn_decode/jit(attention_pallas_decode)/"
                       "flash_decode_paged/pallas_call")
    assert k.nbytes == 8 * 8 * 128 * (2 + 4)    # both arrays of the tuple
    assert k.operands[:2] == ("pad.49", "broadcast_add_fusion.2")


def test_the_loop_and_what_it_runs(parsed):
    """The ``while`` is a row of the entry computation; its body's and its
    condition's instructions are rows too; the inside of a fusion is not."""
    w = parsed["while.4"]
    assert w.opcode == "while" and w.result == "" and w.scope == ""
    comps = {i.computation for i in parsed.values()}
    assert len(comps) == 3 and w.computation in comps
    assert parsed["lt.128"].computation != parsed["add.332"].computation
    # ``%param_0.1`` and friends live in fused computations only.
    assert not any(op.startswith("param_") for op in parsed)


def test_what_has_no_scope_takes_its_users(text):
    """The loop's own slice of a layer's MLP norm gain carries no name of the
    vocabulary (``while/body/...squeeze``); its one user, behind a bitcast,
    is the feed-forward's fusion: a step, then a second."""
    before = {i.op: i for i in scopes.instructions(text)}
    after = {i.op: i for i in scopes.resolve(scopes.instructions(text))}
    assert before["constant_dynamic-slice_fusion.5"].scope == ""
    assert after["constant_dynamic-slice_fusion.5"].scope.startswith(
        "ffn/<-")
    assert after["bitcast.271"].scope == "ffn/<-fusion.174"
    # A prefetch's start takes its done's, which took its user's.
    assert after["copy-start.3"].scope == "ffn/<-copy-done.3"
    # Users that disagree, or carry none, leave it unscoped: the loop's
    # counter feeds every layer's addresses.
    assert after["add.332"].scope == ""
    # ...unless its operands agree (the second rule).
    assert after["copy-start.6"].scope == "attn_cache/fusion.171->"


def test_the_table_keeps_what_runs(rows, parsed):
    """No parameter, tuple, element, bitcast or constant; every other
    instruction once, as ``[op, result, scope]``."""
    kept = [i for i in parsed.values()
            if i.opcode not in scopes.MOVES_NOTHING]
    assert len(rows) == len(kept) == 76          # the ``while`` among them
    assert rows["flash_decode_paged.8"][1:] == [
        "", "attn_decode/jit(attention_pallas_decode)/flash_decode_paged/"
        "pallas_call"]
    assert rows["fusion.174"][:2] == ["fusion.174", "bf16[8,512]"]
    assert "bitcast.271" not in rows and "tokens.1" not in rows


def test_every_scope_the_step_has_is_found(rows):
    found = {r[2].split("/")[0] for r in rows.values()}
    assert found - {""} == {
        scopes.EMBED, scopes.ATTN_IN, scopes.ATTN_CACHE, scopes.ATTN_DECODE,
        scopes.ATTN_OUT, scopes.FFN, scopes.HEAD}


def test_nearly_all_result_bytes_resolve(text):
    leaf = [i for i in scopes.resolve(scopes.instructions(text))
            if i.opcode not in scopes.MOVES_NOTHING | scopes.ENCLOSES]
    assert len(leaf) == 75
    # What is left: the loop's counter, a few address vectors, and a copy of
    # one pool's blocks that the write and the kernel both read (at this toy
    # width it is a sixth of all result bytes; the tick programs at the
    # cells' widths are held to 95% by ``tests/test_chip_compile.py``).
    scoped = sum(i.nbytes for i in leaf if i.scope)
    assert scoped >= 0.8 * sum(i.nbytes for i in leaf)
    assert sorted(i.op for i in leaf if not i.scope) == [
        "add.332", "copy-done.6", "copy.31", "dynamic_slice.101",
        "fusion.119", "iota.13", "lt.128", "mul.472"]


def test_text_that_is_no_module_gives_no_rows():
    assert scopes.table("") == []
    assert scopes.table("not an HLO module\n") == []


# -- one vocabulary ----------------------------------------------------------


def _named_scope_args(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "named_scope":
            arg = node.args[0]
            yield from ((arg.body, arg.orelse)
                        if isinstance(arg, ast.IfExp) else (arg,))


MODEL_FILES = sorted(
    os.path.join("tree_attention_tpu", "models", f)
    for f in os.listdir(os.path.join(ROOT, "tree_attention_tpu", "models"))
    if f.endswith(".py")) + [
        os.path.join("tree_attention_tpu", "serving", "engine.py")]


@pytest.mark.parametrize("rel", MODEL_FILES)
def test_every_named_scope_is_of_the_vocabulary(rel):
    """A layer body names a scope as ``scopes.<NAME>``, never by a string of
    its own: the models, the table and the readers cannot drift. (The
    names inside ``conv``, ``scopes.INNER``, are of the vocabulary too.)"""
    for arg in _named_scope_args(os.path.join(ROOT, rel)):
        assert isinstance(arg, ast.Attribute) \
            and isinstance(arg.value, ast.Name) \
            and arg.value.id == "scopes", ast.dump(arg)
        assert getattr(scopes, arg.attr) in scopes.SCOPES + scopes.INNER


def test_the_layer_bodies_use_every_scope():
    used = set()
    for rel in MODEL_FILES:
        used |= {getattr(scopes, a.attr)
                 for a in _named_scope_args(os.path.join(ROOT, rel))}
    assert used == set(scopes.SCOPES + scopes.INNER)


def test_the_readers_know_every_scope():
    """``benchmark/parts.py`` maps each scope of the vocabulary, taken from
    here, to the part a metric reads; it covers the vocabulary exactly."""
    from benchmark import parts

    assert set(parts.PART_OF) == set(scopes.SCOPES)
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES) == 11
