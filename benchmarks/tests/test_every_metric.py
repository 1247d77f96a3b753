"""A sound traced run prints every per-layer metric its cell declares: on a
hand-made run of the 8-lane configuration that lacks nothing (spans,
counters, a capture on disk), every ``per_layer`` entry of
``BENCHMARK.json`` reads a float, also where the capture holds no idle
gap, no operation of any kernel group, or where ``/debug/profile`` never
answered while its trace file is on disk.  None is left for a run that is
unsound anyway: one without a capture."""

import json
import os
import shutil

import pytest

import client
import run as bench_run
import xplane
from conftest import BENCH, HERE, ROOT
from test_timeline_readers import (OPS, kernel_groups, lane_trace,
                                   metrics_text, reader, span)

RECORDED = os.path.join(HERE, "data", "solar.doc-1.lfkt.v5e.xplane.pb")
CASES = ("answered", "no_idle_gap", "no_operation_of_a_group", "unanswered")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
PER_LAYER = [m["name"] for m in BENCHMARK["per_layer"]]
BUSY_SHARES = ("q4k_busy_share", "q6k_busy_share", "decode_attn_busy_share",
               "qmatmul_busy_share", "attn_busy_share")
OF_THE_CAPTURE = [m["name"] for m in BENCHMARK["per_layer"]
                  if m["source"] == "device_trace"]


def request_trace(t0):
    """``lane_trace`` with three decode chunks of 8 tokens after it."""
    tr = lane_trace(t0=t0)
    end = tr["root"]["children"][2]["end"]
    tr["root"]["children"] += [
        span("decode_chunk", end + 0.2 * i, end + 0.2 * i + 0.18,
             {"tokens": 1 + 8 * (i + 1), "wave": 9 + i, "admit_slices": 0})
        for i in range(3)]
    return tr


def record(i):
    t = 100.0 + i
    return client.Record(index=i, due=t, sent=t, status=200,
                         chunks=[t + 0.9 + 0.2 * k for k in range(4)],
                         text=["abc"] * 4, prompt_tokens=368,
                         completion_tokens=25, finish="length", done=True,
                         max_tokens=25, request_id=f"{i:032x}")


def sound_run(tmp_path, case="answered"):
    """The ``run`` dict as ``run.py`` hands it to the readers."""
    where = tmp_path / "profile"
    shutil.copytree(os.path.dirname(RECORDED),
                    where / "plugins" / "profile" / "2026_09_27",
                    ignore=lambda d, names: [n for n in names
                                             if n != os.path.basename(RECORDED)])
    with open(os.path.join(BENCH, "configs",
                           "mistral-7b-v0.2-q4km-8lane.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "chat-closed-8.json")) as f:
        mix = json.load(f)
    groups = kernel_groups()
    ops = dict(OPS)
    gaps = [(0.5, 0.012), (1.5, 0.004)]
    if case == "no_operation_of_a_group":
        ops = {"%copy.3 = bf16[1,8]{1,0} copy(bf16[1,8]{1,0} %h)": 2.0}
    if case == "no_idle_gap":
        gaps = []
    busy = sum(ops.values())
    first = metrics_text(lanes_live=6, batch_size=8, waves=100,
                         wave_seconds=20.0, lane_live_seconds=120.0,
                         fetch_wait_seconds=17.0, admit_slices=60)
    last = metrics_text(lanes_live=7, batch_size=8, waves=300,
                        wave_seconds=60.0, lane_live_seconds=376.0,
                        fetch_wait_seconds=53.0, admit_slices=200)
    call = {"asked_s": 3.0, "t_send": 118.0, "t_recv": 124.0, "status": 200,
            "doc": {"ok": True, "dir": str(where), "seconds": 3.0}}
    if case == "unanswered":
        call = {"asked_s": 3.0, "t_send": 118.0, "t_recv": 238.0,
                "error": "TimeoutError: timed out"}
    return {
        "records": [record(i) for i in range(8)], "t0": 100.0, "t1": 145.0,
        "traces": [request_trace(100.0 + i) for i in range(8)],
        "samples": [(100.0, first), (145.0, last)],
        "compiles_in_window": 0, "profile_call": call,
        "memory": {"ground_truth": {"bytes": 10.6e9, "limit": 16.9e9}},
        "health": {"engine": {"load_phases": {
            "params_s": 8.5, "tokenizer_s": 0.4, "warmup_s": 9.3}}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "config": cfg, "mix": mix, "kernel_groups": groups,
        "e2e": {"ttft_p50_ms": 1300.0, "itl_p99_ms": 444.0}, "notes": {},
        "profile_dir": str(where),
        "profile": {
            "busy_s": busy, "window_s": busy + sum(s for _, s in gaps),
            "ops": ops, "groups": xplane.group_seconds(ops, groups),
            "gaps": gaps, "host": {},
            "modules": [("jit_prefill_chunk_jit(12)", 0.1, 0.038),
                        ("jit_batched_generate_chunk_perlane_jit(9)", 0.2, 0.183),
                        ("jit_batched_generate_chunk_perlane_jit(9)", 0.4, 0.184)]},
    }


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("name", PER_LAYER)
def test_a_sound_traced_run_reads_a_number(name, case, tmp_path):
    got = reader(name)(sound_run(tmp_path, case))
    assert isinstance(got, float), (name, case, got)
    assert got == got and abs(got) != float("inf")
    if name.endswith("_share") or name.endswith("_roofline"):
        assert 0.0 <= got <= 100.0


def test_the_stated_numbers_where_there_is_nothing_to_share(tmp_path):
    run = sound_run(tmp_path, "no_idle_gap")
    assert reader("idle_attributed_share")(run) == 100.0   # nothing unnamed
    assert reader("idle_in_tokenize_share")(run) == 0.0
    assert run["notes"]["idle_by_phase"] == {}
    run = sound_run(tmp_path / "b", "no_operation_of_a_group")
    for name in BUSY_SHARES:
        assert reader(name)(run) == 0.0, name


@pytest.mark.parametrize("name", BUSY_SHARES)
def test_a_share_that_matched_nothing_says_so(name, tmp_path):
    """0.0 because a kernel changed its name reads like a true zero: the
    diagnostics line's ``notes.no_match`` tells them apart."""
    run = sound_run(tmp_path, "no_operation_of_a_group")
    assert reader(name)(run) == 0.0
    assert run["notes"]["no_match"] == [name]
    run = sound_run(tmp_path / "b")
    assert reader(name)(run) > 0.0
    assert "no_match" not in run["notes"]


@pytest.mark.parametrize("name", OF_THE_CAPTURE)
def test_an_unanswered_capture_reads_what_an_answered_one_reads(name, tmp_path):
    """The answer of ``/debug/profile`` carries nothing a reader needs."""
    assert reader(name)(sound_run(tmp_path / "a", "unanswered")) == \
        reader(name)(sound_run(tmp_path / "b", "answered"))


@pytest.mark.parametrize("name", OF_THE_CAPTURE)
def test_without_a_capture_there_is_no_number(name, tmp_path):
    """No trace file, so no reduction either: the run is unsound and the
    metric is left out, whatever ``/debug/profile`` answered."""
    run = sound_run(tmp_path)
    shutil.rmtree(run["profile_dir"])
    run["profile"] = None
    assert reader(name)(run) is None


def test_reduction_and_phases_find_the_file_the_same_way(tmp_path):
    """``run.reduced_capture`` and ``annotations.of_run`` both go through
    ``xplane.capture_of``: the directory the run set, not the answer."""
    answered = sound_run(tmp_path / "a")
    lost = sound_run(tmp_path / "b", "unanswered")
    # an answer that names another directory is not followed
    answered["profile_call"]["doc"]["dir"] = str(tmp_path / "elsewhere")
    for run in (answered, lost):
        # the file is 100 ms cut from a 3 s capture: its own ``time.sleep``
        # frame would stretch the window over the 2.9 s that were cut away
        run["profile_call"]["asked_s"] = None
        got = bench_run.reduced_capture(run)
        assert got is not None and got["busy_s"] > 0
        assert run["capture_path"].startswith(run["profile_dir"])
        assert run["capture_path"].endswith(os.path.basename(RECORDED))
    a, b = (bench_run.reduced_capture(r) for r in (answered, lost))
    assert (a["busy_s"], a["window_s"], a["gaps"]) == \
        (b["busy_s"], b["window_s"], b["gaps"])
    for run in (answered, lost):
        run["profile"] = bench_run.reduced_capture(run)
        assert reader("idle_attributed_share")(run) > 90.0   # as PR 24 read it
        assert run["notes"]["phases_in_capture"]["tokenize"] >= 1


def test_why_a_metric_was_left_out(tmp_path):
    run = sound_run(tmp_path)
    tpu = run["device"]
    assert "found nothing" in bench_run.why_left_out(
        {**run, "capture_path": "x"}, tpu)
    assert "no operation ran" in bench_run.why_left_out(
        {**run, "capture_path": "x", "profile": None}, tpu)
    assert "no capture file" in bench_run.why_left_out(
        {**run, "capture_path": None}, tpu)
    assert "platform 'cpu'" in bench_run.why_left_out(run, {"platform": "cpu"})


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cells_declare_what_the_issue_counted(cell):
    """26 per-layer metrics in the 8-lane cells, 18 in ``solar.doc-1``, 17 in
    ``solar.chat-1``, whose 3 s capture may fall inside one answer."""
    mine = [m["name"] for m in bench_run.find_cell(cell)["per_layer"]]
    want = {"solar.chat-1": 17, "solar.doc-1": 18}.get(cell, 26)
    assert len(mine) == want
    assert ("prefill_device_ms_per_ktok" in mine) == (cell != "solar.chat-1")
