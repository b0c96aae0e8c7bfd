"""The reduction from trace events to busy, idle, per-operation time and
idle gaps by host frame: on hand-made events, and on a recorded xplane."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "v5e_decode_ticks.xspace.txt")


def test_union_merges_overlaps_and_keeps_order():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (10, 11)]) == [
        (0, 2.5), (3, 4), (10, 11)]
    assert tr.union([]) == []
    assert tr.union([(0, 5), (1, 2)]) == [(0, 5)]


def test_clip_cuts_events_to_the_window():
    ev = [("a", 0.0, 2.0), ("b", 3.0, 1.0), ("c", 9.0, 5.0), ("d", 20.0, 1.0)]
    assert tr.clip(ev, 1.0, 10.0) == [
        ("a", 1.0, 1.0), ("b", 3.0, 1.0), ("c", 9.0, 1.0)]


def test_deepest_covering_frame_wins():
    host = {"main": [("outer", 0.0, 10.0), ("mid", 1.0, 5.0),
                     ("leaf", 2.0, 1.0), ("later", 7.0, 1.0)],
            "other": [("idle_thread", 0.0, 100.0)]}
    frames = tr.Frames(host)
    assert frames.at(2.5) == "leaf"
    assert frames.at(4.0) == "mid"
    assert frames.at(6.5) == "outer"
    assert frames.at(7.5) == "later"
    assert frames.at(50.0) == "idle_thread"
    assert tr.Frames({}).at(1.0) is None


def hand_made():
    host = {"python": [
        (tr.BEGIN_MARK, 10.0, 0.0), (tr.END_MARK, 20.0, 0.0),
        ("$engine.py:1 serve", 0.0, 30.0),
        ("$engine.py:2 _admit", 12.0, 1.0),
        ("$_array.py:3 __getitem__", 15.0, 2.0),
    ]}
    dev0 = [("fusion.1", 9.0, 3.0),          # clipped to 10..12
            ("while.5", 13.0, 2.0),           # encloses its body's operation
            ("flash_decode_paged.9", 14.0, 0.5),
            ("fusion.2", 17.0, 3.0)]
    dev1 = [("fusion.1", 10.0, 10.0)]         # never idle
    return {"host": host, "devices": {"/device:TPU:0": dev0,
                                      "/device:TPU:1": dev1}}


def test_busy_is_the_union_averaged_over_devices_between_the_marks():
    out = tr.reduce_events(hand_made())
    assert out["window_s"] == pytest.approx(10.0)
    # device 0: [10,12] + [13,15] + [17,20] = 7; device 1: 10.
    assert out["busy_s"] == pytest.approx(8.5)
    assert out["devices"] == 2
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx((2.0 + 10.0) / 2)
    assert ops["flash_decode_paged.9"] == pytest.approx(0.25)
    assert "while.5" not in ops          # its time is its body's
    assert out["device_ops"][0][0] == "fusion.1"


def test_short_names_keep_the_instruction_and_its_result():
    assert tr.short_name(
        "%fusion.209 = bf16[8,256,11008]{2,1,0:T(8,128)(2,1)} fusion(bf16[16"
    ) == "fusion.209 bf16[8,256,11008]"
    assert tr.short_name(
        "%flash_decode_paged.9 = (bf16[32,8,128]{2,1,0}, f32[32,8,128]) "
        "custom-call(") == "flash_decode_paged.9"
    assert tr.short_name("$engine.py:1 serve") == "$engine.py:1 serve"


def test_idle_gaps_are_named_by_the_host_frame_at_their_middle():
    gaps = dict(tr.reduce_events(hand_made())["idle_gaps"])
    # device 0 idles 12..13 (middle 12.5: _admit) and 15..17 (16: getitem).
    assert gaps == {"$engine.py:2 _admit": pytest.approx(0.5),
                    "$_array.py:3 __getitem__": pytest.approx(1.0)}


def test_without_marks_the_window_is_the_trace_and_no_device_is_no_busy():
    data = hand_made()
    data["host"] = {}
    out = tr.reduce_events(data)
    assert out["t0"] == 9.0 and out["t1"] == 20.0
    empty = tr.reduce_events({"host": {}, "devices": {}})
    assert empty["busy_s"] == 0.0 and empty["device_ops"] == []


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded xplane in this checkout")
def test_recorded_xplane_reduces_to_the_numbers_read_by_hand():
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        data = ProfileData.from_text_proto(f.read())
    events = tr.planes_to_events(data)
    assert list(events["devices"]) == ["/device:TPU:0"]
    out = tr.reduce_events(events)
    with open(FIXTURE.replace(".xspace.txt", ".expected.json")) as f:
        import json

        want = json.load(f)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["device_ops"][0][0] == want["top_op"]
    assert any("flash_decode_paged" in name for name, _ in out["device_ops"])
    assert out["idle_gaps"][0][0] == want["top_gap"]
