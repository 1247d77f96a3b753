"""PR 54's five readers: the jit registry's device seconds by program
(``stamps.py``), the ``first_token`` span's named inside, the done stamp's
lag in a capture.  ``data/sound_run/stamps.json`` brings the counters (which
``test_every_metric.py``'s hand-made run takes too) and the spans."""

import json
import os

import pytest

import stamps
from conftest import HERE
from test_every_metric import LANE_CELL, sound_run
from test_timeline_readers import reader

with open(os.path.join(HERE, "data", "sound_run", "stamps.json")) as _f:
    BROUGHT = json.load(_f)


def traced(spans):
    return [{"trace_id": f"{i:032x}", "root": {
        "name": "request", "start": s["start"] - 1, "end": s["end"] + 1,
        "children": [{"name": "prefill", "start": s["start"] - 0.5,
                      "end": s["end"], "children": [s]}]}}
        for i, s in enumerate(spans)]


def text(**seconds):
    return "".join(f'jit_device_seconds_total{{program="{k}"}} {v!r}\n'
                   for k, v in seconds.items())


# -- the counters -----------------------------------------------------------

def test_growth_is_last_less_first_and_a_new_program_counts_from_zero():
    run = {"samples": [(0.0, text(prefill_chunk=4.0)), (1.0, "not read"),
                       (10.0, text(prefill_chunk=6.5, first_sample=0.25))]}
    assert stamps.growth(run) == {"prefill_chunk": 2.5, "first_sample": 0.25}
    assert stamps.seconds_of(run, stamps.PREFILL) == 2.5
    assert stamps.seconds_of(run, stamps.LANE_CHUNK) == 0.0
    assert stamps.sampled_seconds(run) == 10.0


@pytest.mark.parametrize("samples", [
    [], [(0.0, text(prefill_chunk=1.0))],
    [(0.0, "scheduler_waves 3\n"), (9.0, "scheduler_waves 9\n")]],
    ids=["none", "one", "a_parent_without_the_counter"])
@pytest.mark.parametrize("name", ["prefill_program_ms_per_ktok",
                                  "decode_program_step_ms",
                                  "programs_busy_share"])
def test_without_the_counter_there_is_no_number(name, samples):
    run = {"samples": samples, "notes": {}}
    assert stamps.growth(run) is None
    assert reader(name)(run) is None


def test_the_three_counter_readers_on_the_sound_run(tmp_path):
    """1520 steps, 51 200 prompt tokens and 45 s between the samples."""
    run = sound_run(tmp_path)
    assert reader("decode_program_step_ms")(run) == pytest.approx(18.75)
    assert reader("prefill_program_ms_per_ktok")(run) == \
        pytest.approx(179.6875)
    assert reader("programs_busy_share")(run) == \
        pytest.approx(100.0 * 37.76 / 45.0)
    assert list(run["notes"]["device_s_by_program"]) == [
        "lane_decode_chunk", "prefill_chunk", "lane_write", "first_sample"]
    assert run["notes"]["device_s_by_program"]["prefill_chunk"] == \
        pytest.approx(9.2)


def test_nothing_prefilled_and_no_step_run_give_no_number(tmp_path):
    run = sound_run(tmp_path)
    first, last = run["samples"][0][1], run["samples"][-1][1]
    run["samples"] = [(100.0, first), (145.0, first)]
    assert reader("prefill_program_ms_per_ktok")(run) is None
    assert reader("decode_program_step_ms")(run) is None
    assert reader("programs_busy_share")(run) == 0.0
    run["samples"] = [(100.0, last), (100.0, last)]     # no time between
    assert reader("programs_busy_share")(run) is None


# -- the spans ----------------------------------------------------------------

def test_named_share_is_the_childrens_cover_of_the_span():
    alone, beside = BROUGHT["spans"]
    run = {"traces": traced([alone]), "notes": {}}
    # 40 ms of 2 s lie between the third and the fourth slice, unnamed
    assert reader("first_token_named_share")(run) == pytest.approx(98.0)
    assert run["notes"]["first_token_inside_ms"] == {
        "device.first_sample": pytest.approx(1.0),
        "device.prefill_chunk": pytest.approx(1910.0),
        "host_fetch": pytest.approx(49.0)}
    run = {"traces": traced([beside]), "notes": {}}
    assert reader("first_token_named_share")(run) == pytest.approx(100.0)
    assert "device.lane_decode_chunk" in run["notes"]["first_token_inside_ms"]
    run = {"traces": traced([alone, beside, beside]), "notes": {}}
    assert reader("first_token_named_share")(run) == pytest.approx(100.0)


def test_the_slices_device_time_is_noted_by_offset():
    alone, _ = BROUGHT["spans"]
    traces = traced([alone, alone])
    for tr, first in zip(traces, (150.0, 170.0)):
        tr["root"]["children"][0]["children"] += [
            {"name": "prefill_slice", "start": 9.0, "end": 9.01,
             "attrs": {"offset": 0, "tokens": 1024, "device_s": first / 1e3}},
            {"name": "prefill_slice", "start": 9.01, "end": 9.02,
             "attrs": {"offset": 1024, "tokens": 1024, "device_s": 0.2}},
            {"name": "prefill_slice", "start": 9.02, "end": 9.03,
             "attrs": {"offset": 2048, "tokens": 256}}]    # a parent's: bare
    run = {"traces": traces, "notes": {}}
    reader("first_token_named_share")(run)
    assert run["notes"]["slice_device_ms_by_offset"] == {
        "0": pytest.approx(160.0), "1024": pytest.approx(200.0)}


def test_named_share_counts_no_stretch_twice_and_no_other_child():
    span = {"name": "first_token", "start": 0.0, "end": 1.0,
            "duration_s": 1.0, "attrs": {}, "children": [
                {"name": "device.a", "start": 0.0, "end": 0.5},
                {"name": "device.b", "start": 0.25, "end": 0.75},   # overlaps
                {"name": "something_else", "start": 0.75, "end": 1.0},
                {"name": "host_fetch", "start": 0.9, "end": None}]}  # open
    run = {"traces": traced([span]), "notes": {}}
    assert reader("first_token_named_share")(run) == pytest.approx(75.0)


def test_named_share_without_children_and_without_spans(tmp_path):
    bare = {"name": "first_token", "start": 0.0, "end": 1.0,
            "duration_s": 1.0, "attrs": {"deferred": False}, "children": []}
    run = {"traces": traced([bare]), "notes": {}}
    assert reader("first_token_named_share")(run) == 0.0   # nothing named
    assert "first_token_inside_ms" not in run["notes"]
    assert reader("first_token_named_share")(
        {"traces": [], "notes": {}}) is None
    # the hand-made run's first_token spans are a parent's: bare
    assert reader("first_token_named_share")(sound_run(tmp_path)) == 0.0


# -- the stamps in a capture ------------------------------------------------------

def lag_reader():
    import importlib.util
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        "done_stamp_lag_p90_ms.py")
    spec = importlib.util.spec_from_file_location("done_stamp_lag", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_stamps_lag_is_from_the_last_module_end_before_it():
    mod = lag_reader()
    ends = [1.0, 2.0, 3.0]
    assert mod.lags_ms([1.0002, 2.001, 2.9, 3.0], ends) == pytest.approx(
        [0.2, 1.0, 900.0, 0.0])
    assert mod.lags_ms([0.5], ends) == []          # before every module
    assert mod.lags_ms([], ends) == [] and mod.lags_ms([1.5], []) == []


def test_the_lag_reader_takes_the_captures_device_done_events(
        tmp_path, monkeypatch):
    mod = lag_reader()
    run = sound_run(tmp_path)
    modules = run["profile"]["modules"]            # (name, start, seconds)
    ends = [s + d for _, s, d in modules]
    seen = [("lfkt-device-done", "lfkt.device_done", e - 0.01, 0.01 + lag)
            for e, lag in zip(ends, (0.0001, 0.0002, 0.0030))]
    seen += [("scheduler", "lfkt.wave", 0.0, 5.0)]          # another phase
    monkeypatch.setattr(mod.annotations, "events", lambda path: iter(seen))
    assert mod.read(run) == pytest.approx(
        0.2 + 0.8 * (3.0 - 0.2))                  # p90 of 0.1, 0.2, 3.0 ms
    assert run["notes"]["done_stamps"] == {"n": 3,
                                           "p50_ms": pytest.approx(0.2)}


def test_a_capture_without_a_stamp_gives_no_number(tmp_path):
    """The recorded capture is of a program older than the stamps."""
    assert reader("done_stamp_lag_p90_ms")(sound_run(tmp_path)) is None
    run = sound_run(tmp_path / "b")
    run["profile"] = None
    assert reader("done_stamp_lag_p90_ms")(run) is None


def test_the_entries_name_their_cells():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = ("first_token_named_share", "prefill_program_ms_per_ktok",
             "decode_program_step_ms", "programs_busy_share",
             "done_stamp_lag_p90_ms")
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in names}
    assert tuple(mine) == names
    # the sixteen cells the file held when the five were added (a cell a
    # later PR adds is that PR's to append)
    held = mine["programs_busy_share"]["workloads"]
    assert held == cells[:len(held)] and len(held) >= 16
    for name, m in mine.items():
        want = [c for c in held if not c.startswith("solar.")] \
            if name == "decode_program_step_ms" else held
        assert m["workloads"] == want, name
    assert LANE_CELL in mine["decode_program_step_ms"]["workloads"]
