"""``run.py`` end to end on the CPU at a toy size: the whole control flow,
the refusal to measure without a chip, and a broken timed path seen as not
correct."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal",
                         "BENCHMARK.json")


def drive(capsys, *extra, workload="tiny_sat", seconds="1", trace="0",
          seed="5"):
    rc = run.main(["--benchmark", REHEARSAL, "--workload", workload,
                   "--seed", seed, "--seconds", seconds, "--trace", trace,
                   *extra])
    said = capsys.readouterr()
    out = [ln for ln in said.out.splitlines() if ln.strip()]
    lines = [json.loads(ln) for ln in out if ln.startswith("{")]
    if rc == 0:                # and as the last lines of standard error
        rows = lines[-1]["compared"]
        assert said.err.splitlines()[-len(rows):] == [
            f"compared {k} {v['value']} limit {v['limit']}"
            for k, v in rows.items()]
    return rc, lines


def test_without_a_chip_the_command_fails_and_prints_no_result(capsys):
    rc, lines = drive(capsys)
    assert rc != 0 and lines == []


def test_rehearsal_names_the_cpu_and_reports_no_metric(capsys):
    rc, lines = drive(capsys, "--rehearse-on-cpu", seed=str(2 ** 31 + 9))
    assert rc == 0
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}             # no timing under a metric's name
    assert last["rehearsal"]["metrics_not_reported"] == [
        "out_tok_s", "setup_s", "tbt_p50_ms"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    window = next(ln for ln in lines if ln.get("info") == "window")
    # The backlog never ran dry: released and unfinished stayed above slots.
    assert window["outstanding_min"] >= 2 + 2 * 2
    compared = next(ln for ln in lines if ln.get("info") == "compared")
    assert {r["number"] for r in compared["rows"]} == {
        "gap_max", "gap_mean", "failed_requests", "blocks_leaked",
        "compiles_in_window"}
    assert all("limit" in r and "value" in r for r in compared["rows"])
    # The same numbers come last in the result line and on standard error.
    assert list(last)[-1] == "compared"
    assert last["compared"] == {r["number"]: {"value": r["value"],
                                              "limit": r["limit"]}
                                for r in compared["rows"]}


def test_traced_rehearsal_reads_the_layer_metrics_it_can(capsys):
    rc, lines = drive(capsys, "--rehearse-on-cpu", workload="tiny_trickle",
                      seconds="2", trace="1")
    assert rc == 0
    last = lines[-1]
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    # Readers that found something to read; the CPU has no device plane, so
    # device_idle_pct found nothing and was left out.
    assert last["rehearsal"]["metrics_not_reported"] == [
        "compiles_in_window", "decode_tick_p50_ms", "released_count"]
    assert last["device"]["window_s"] > 0 and "breakdown" in last


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    """The run as the harness drives it, the chip look skipped, and the
    timed path broken underneath: every delivered token is another id."""
    from tree_attention_tpu.serving.engine import SlotServer

    true_push = SlotServer._push_token

    def altered(self, req, tok, index=0):
        return true_push(self, req, (int(tok) + 1) % 128, index)

    monkeypatch.setattr(SlotServer, "_push_token", altered)
    rc, lines = drive(capsys, "--rehearse-on-cpu")
    assert rc == 0
    assert lines[-1]["correct"] is False
    compared = next(ln for ln in lines if ln.get("info") == "compared")
    bad = {r["number"] for r in compared["rows"] if not r["ok"]}
    assert "gap_mean" in bad and "failed_requests" not in bad


def test_a_truncated_request_counts_as_failed(capsys, monkeypatch):
    from benchmark import driver

    true_request = driver.WindowSource._request

    def short(self, shape, due, now, midlife):
        req = true_request(self, shape, due, now, midlife)
        if req.max_new_tokens > 4:
            req.max_new_tokens -= 1          # the system emits one too few
        return req

    monkeypatch.setattr(driver.WindowSource, "_request", short)
    rc, lines = drive(capsys, "--rehearse-on-cpu")
    assert rc == 0 and lines[-1]["failed"] > 0
    assert lines[-1]["correct"] is False
