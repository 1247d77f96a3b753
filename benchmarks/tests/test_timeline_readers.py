"""The readers of PR 24, on hand-made span trees, ``/metrics`` texts and
event lists, and on a recorded slice of the chip that holds the program's
``lfkt.`` phases (``data/``).  A reader of spans or counters gives None,
and does not raise, for a program that has none of what it reads (the
parent commit of PR 24); a reader of the capture gives a number whenever
there is a capture (``test_every_metric.py``)."""

import importlib.util
import json
import os

import pytest

import annotations
import chain
import counters
import opshare
import xplane
from conftest import BENCH, HERE, ROOT

NEW = ["tokenize_p50_ms", "pending_wait_p50_ms", "admit_wait_p50_ms",
       "first_token_wait_p50_ms", "lane_occupancy_share",
       "admit_slices_per_wave", "fetch_wait_share",
       "prefill_device_ms_per_ktok", "idle_attributed_share",
       "idle_in_tokenize_share", "q4k_busy_share", "q6k_busy_share",
       "decode_attn_busy_share"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, start, end, attrs=None, children=(), events=()):
    return {"name": name, "start": start, "end": end,
            "duration_s": end - start, "attrs": attrs or {},
            "children": list(children), "events": list(events)}


def lane_trace(t0=100.0, pending=0.2, slices=2, wait=0.19, fetch=0.3):
    """One lane-engine request: queue 1 ms, ``pending``, a prefill of 10 ms
    tokenizer + ``slices`` 2 ms dispatches ``wait`` apart + a deferred first
    token, the first content chunk 3 ms after it."""
    t = t0 + 0.001
    kids = [span("tokenize", t + pending, t + pending + 0.010,
                 {"n_prompt": 368})]
    at = kids[0]["end"]
    for i in range(slices):
        at += wait if i else 0.0
        kids.append(span("prefill_slice", at, at + 0.002,
                         {"offset": 256 * i, "tokens": 256, "wave": 7 + i}))
        at += 0.002
    kids.append(span("first_token", at, at + fetch,
                     {"deferred": True, "waves": 2}))
    end = at + fetch
    return {"trace_id": "ab" * 16, "root": span("request", t0, end + 1.0, children=[
        span("queue", t0, t),
        span("pending", t, t + pending),
        span("prefill", t + pending, end, {"n_prompt": 368}, kids),
        span("stream", t0 + 0.0005, end + 1.0,
             events=[{"name": "first_content", "at": end + 0.003}]),
    ])}


def parent_trace(t0=100.0):
    """What the parent records: a ``prefill`` with slice *events*."""
    return {"trace_id": "cd" * 16, "root": span("request", t0, t0 + 2.0, children=[
        span("queue", t0, t0 + 0.001),
        span("pending", t0 + 0.001, t0 + 0.2),
        span("prefill", t0 + 0.2, t0 + 1.0, {"n_prompt": 368},
             events=[{"name": "prefill_slice", "at": t0 + 0.3, "offset": 0,
                      "tokens": 256, "host_s": 0.002}]),
    ])}


def run_of(**kw):
    base = {"traces": [], "samples": [], "profile": None, "notes": {},
            "records": [], "e2e": {}, "profile_call": {}, "kernel_groups": {}}
    base.update(kw)
    return base


# -- spans ------------------------------------------------------------------

def test_span_readers_on_a_lane_request():
    run = run_of(traces=[lane_trace(pending=0.2), lane_trace(pending=0.4)],
                 e2e={"ttft_p50_ms": 1000.0})
    assert reader("tokenize_p50_ms")(run) == pytest.approx(10.0)
    assert reader("pending_wait_p50_ms")(run) == pytest.approx(300.0)
    # self time of prefill: the one wait between its two slices
    assert reader("admit_wait_p50_ms")(run) == pytest.approx(190.0)
    assert reader("first_token_wait_p50_ms")(run) == pytest.approx(300.0)
    doc = run["notes"]["ttft_chain_ms"]
    assert doc["requests"] == 2 and doc["client_ttft_p50"] == 1000.0
    assert doc["prefill_slice"] == pytest.approx(4.0)
    assert doc["between_slices"] == pytest.approx(190.0)
    assert doc["to_first_content"] == pytest.approx(3.0)
    assert doc["chain"] == pytest.approx(
        1.0 + 300.0 + (10 + 4 + 190 + 300) + 3.0)
    assert doc["mean"]["chain"] == pytest.approx(sum(
        doc["mean"][k] for k in ("queue", "pending", "prefill",
                                 "to_first_content")))


def test_chain_links_add_up_to_the_servers_own_total():
    got = chain.links(lane_trace())
    assert got["chain"] + got["outside_chain"] == \
        pytest.approx(got["server_total"])
    assert got["outside_chain"] == pytest.approx(0.0, abs=1e-9)
    assert got["prefill"] == pytest.approx(
        got["tokenize"] + got["prefill_slice"] + got["between_slices"]
        + got["first_token"])
    assert chain.links(parent_trace()) is None      # no first_content mark


def test_admit_wait_needs_the_children():
    run = run_of(traces=[parent_trace()])
    assert reader("admit_wait_p50_ms")(run) is None
    assert reader("pending_wait_p50_ms")(run) == pytest.approx(199.0)


# -- counters ---------------------------------------------------------------

def metrics_text(**gauges):
    return "\n".join(["# TYPE scheduler_lanes_live gauge"]
                     + [f"scheduler_{k} {v!r}" for k, v in gauges.items()]) + "\n"


def test_counter_readers_take_last_less_first():
    a = metrics_text(batch_size=8, waves=100, wave_seconds=20.0,
                     lane_live_seconds=120.0, fetch_wait_seconds=17.0,
                     admit_slices=60)
    mid = metrics_text(batch_size=8, waves=1, wave_seconds=1.0)   # not read
    b = metrics_text(batch_size=8, waves=300, wave_seconds=60.0,
                     lane_live_seconds=376.0, fetch_wait_seconds=53.0,
                     admit_slices=200)
    run = run_of(samples=[(0.0, a), (1.0, mid), (2.0, b)])
    assert counters.delta(run, "scheduler_waves") == 200
    assert reader("lane_occupancy_share")(run) == pytest.approx(80.0)
    assert reader("admit_slices_per_wave")(run) == pytest.approx(0.7)
    assert reader("fetch_wait_share")(run) == pytest.approx(90.0)


def test_counter_readers_without_the_counters():
    old = "scheduler_lanes_live 6\nscheduler_batch_size 8\n"
    for samples in ([], [(0.0, old)], [(0.0, old), (1.0, old)]):
        run = run_of(samples=samples)
        for name in ("lane_occupancy_share", "admit_slices_per_wave",
                     "fetch_wait_share"):
            assert reader(name)(run) is None
    # nothing counted under the line: no wave in the window
    a = metrics_text(batch_size=8, waves=5, wave_seconds=1.0, admit_slices=3)
    assert reader("admit_slices_per_wave")(
        run_of(samples=[(0.0, a), (1.0, a)])) is None


# -- phases inside the capture ------------------------------------------------

PHASES = [("wave", 0.0, 1.0), ("dispatch_chunk", 0.0, 0.1),
          ("fetch", 0.5, 0.5), ("tokenize", 2.0, 1.0), ("wave", 3.5, 1.0)]


def test_a_gap_goes_to_the_innermost_phase_open_at_its_middle():
    assert annotations.phase_at(PHASES, 0.05) == "dispatch_chunk"
    assert annotations.phase_at(PHASES, 0.3) == "wave"
    assert annotations.phase_at(PHASES, 0.7) == "fetch"
    assert annotations.phase_at(PHASES, 1.5) is None
    gaps = [(2.2, 0.6), (0.6, 0.2), (1.2, 0.1), (3.6, 0.05)]
    by = annotations.idle_by_phase(gaps, PHASES)
    assert list(by) == ["tokenize", "fetch", annotations.UNNAMED, "wave"]
    assert by["tokenize"] == pytest.approx(0.6)


def test_idle_shares_from_a_runs_notes():
    run = run_of(notes={"idle_by_phase": {
        "tokenize": 0.12, annotations.UNNAMED: 0.01, "fetch": 0.07}})
    assert reader("idle_attributed_share")(run) == pytest.approx(95.0)
    assert reader("idle_in_tokenize_share")(run) == pytest.approx(60.0)


def test_no_capture_is_none(tmp_path):
    assert annotations.of_run(run_of()) is None
    # a directory was set and holds no trace file: the answer's word that
    # it does counts for nothing
    run = run_of(profile_dir=str(tmp_path), profile={"gaps": [(0.1, 0.2)]},
                 profile_call={"doc": {"ok": True, "dir": str(tmp_path)}})
    assert annotations.of_run(run) is None and run["notes"] == {}
    assert reader("idle_attributed_share")(run) is None


RECORDED = os.path.join(HERE, "data", "solar.doc-1.lfkt.v5e.xplane.pb")


def test_recorded_slice_with_phases():
    """100 ms of the mid-window capture of ``solar.doc-1`` on a TPU v5e (my
    chip run, PR 24), cut by ``data/cut_annotated.py`` around the capture's
    longest idle gap: one request ends, the next is tokenized, its first
    prefill slices go out.  The old reader sees what it saw (``lfkt.``
    events are not Python frames); the new one puts the gap to
    ``tokenize``."""
    phases = annotations.load(RECORDED)
    names = {n for n, _, _ in phases}
    assert {"tokenize", "prefill_slice", "decode_chunk"} <= names
    trace = xplane.load(RECORDED)
    assert all(n.startswith("$") for evs in trace["host"].values()
               for n, _, _ in evs)
    out = xplane.reduce(trace, {})
    by = annotations.idle_by_phase(out["gaps"], phases)
    assert list(by)[0] == "tokenize"
    assert by["tokenize"] / sum(by.values()) > 0.9
    tok = next(p for p in phases if p[0] == "tokenize")
    longest = out["gaps"][0]
    assert tok[1] <= longest[0] + longest[1] / 2 <= tok[1] + tok[2]


# -- device operations by the program's own names ------------------------------

OPS = {
    "%q4k_matmul_fewrow.3 = f32[1,14336]{1,0} custom-call(s32[1]{0} %a, "
    "bf16[1,4096]{1,0} %x, s8[48,14336,2048]{2,1,0} %w), "
    "custom_call_target=\"tpu_custom_call\"": 0.53,
    "%q6k_matmul_fewrow.5 = f32[1,4096]{1,0} custom-call(s32[1]{0} %a, "
    "bf16[1,14336]{1,0} %x, s8[48,4096,7168]{2,1,0} %w), "
    "custom_call_target=\"tpu_custom_call\"": 0.91,
    "%q6k_pre_matmul_manyrow.1 = f32[256,4096]{1,0} custom-call(s8[4096,64]"
    "{1,0} %w), custom_call_target=\"tpu_custom_call\"": 0.09,
    "%flash_attention.2 = bf16[8,1024,128]{2,1,0} custom-call(bf16[8,4096,"
    "128]{2,1,0} %k), custom_call_target=\"tpu_custom_call\"": 0.10,
    "%fusion.113 = f32[8,8,4,4096]{3,2,1,0} fusion(bf16[8,32,8,4096,128]"
    "{4,3,2,1,0} %ring, bf16[8,8,4,128]{3,2,1,0} %q), kind=kOutput": 0.32,
    "%fusion.9 = bf16[1,4096]{1,0} fusion(bf16[1,4096]{1,0} %h), "
    "kind=kLoop": 0.05,
}


def kernel_groups():
    out = {}
    kdir = os.path.join(BENCH, "kernels")
    for fn in os.listdir(kdir):
        with open(os.path.join(kdir, fn)) as f:
            doc = json.load(f)
        out[doc["name"]] = doc["patterns"]
    return out


def test_named_kernels_split_the_qmatmul_group():
    busy = sum(OPS.values())
    profile = {"busy_s": busy, "ops": OPS,
               "groups": xplane.group_seconds(OPS, kernel_groups())}
    run = run_of(profile=profile)
    q4, q6 = reader("q4k_busy_share")(run), reader("q6k_busy_share")(run)
    assert q4 == pytest.approx(100 * 0.53 / busy)
    assert q6 == pytest.approx(100 * 1.00 / busy)
    assert q4 + q6 == pytest.approx(reader("qmatmul_busy_share")(run))
    attn = reader("decode_attn_busy_share")(run)
    assert attn == pytest.approx(100 * 0.32 / busy)
    assert attn == pytest.approx(
        reader("attn_busy_share")(run) - 100 * 0.10 / busy)


def test_kernels_without_names_are_nothing_of_busy_time():
    unnamed = {"%closed_call.114 = f32[1,4096]{1,0} custom-call(s8[48,4096,"
               "7168]{2,1,0} %w), custom_call_target=\"tpu_custom_call\"": 1.0}
    profile = {"busy_s": 1.0, "ops": unnamed, "groups": {}}
    run = {"profile": profile}
    assert opshare.busy_share(run, "q4k_busy_share", r"^%q4k_matmul") == 0.0
    assert run["notes"] == {"no_match": ["q4k_busy_share"]}   # and says so
    assert opshare.busy_share(run, "all", r"custom-call") == 100.0
    assert run["notes"] == {"no_match": ["q4k_busy_share"]}
    assert opshare.busy_share({}, "q4k_busy_share", r"^%q4k_matmul") is None


def test_prefill_device_time_over_the_slices_tokens():
    mods = [("jit_prefill_chunk_jit(123)", 0.0, 0.044),
            ("jit_batched_generate_chunk_perlane_jit(9)", 0.1, 0.183),
            ("jit_prefill_chunk_jit(123)", 0.3, 0.046),
            ("jit_prefill_chunk_jit(123)", 0.5, 0.045)]
    run = run_of(profile={"modules": mods}, traces=[lane_trace()])
    assert reader("prefill_device_ms_per_ktok")(run) == \
        pytest.approx(45.0 / 0.256)
    # the capture holds no prefill, or the program records no slice spans
    assert reader("prefill_device_ms_per_ktok")(
        run_of(profile={"modules": mods[1:2]}, traces=[lane_trace()])) is None
    assert reader("prefill_device_ms_per_ktok")(
        run_of(profile={"modules": mods}, traces=[parent_trace()])) is None


# -- the parent: nothing to read, nothing raised --------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_none_on_the_parents_run(name):
    """A traced run of the parent commit under this PR's benchmark files:
    its spans, ``/metrics`` and capture lack what PR 24 added."""
    old = "scheduler_lanes_live 6\nscheduler_batch_size 8\n"
    unnamed = {"%closed_call.1 = f32[1,8]{1,0} custom-call(s8[8,8]{1,0} %w), "
               "custom_call_target=\"tpu_custom_call\"": 1.0,
               "%copy.3 = bf16[1,8]{1,0} copy(bf16[1,8]{1,0} %h)": 0.5}
    run = run_of(traces=[parent_trace()],
                 samples=[(0.0, old), (1.0, old)],
                 profile={"busy_s": 1.5, "window_s": 3.0, "ops": unnamed,
                          "groups": {}, "gaps": [(0.1, 0.2)], "modules": [],
                          "host": {}})
    # the span was always there; a share of a capture's busy time is 0.0
    # where nothing matches; the idle shares have no trace file to read
    want_number = {"pending_wait_p50_ms", "q4k_busy_share", "q6k_busy_share",
                   "decode_attn_busy_share"}
    got = reader(name)(run)
    assert (got is not None) == (name in want_number)


def test_every_new_metric_is_declared_where_the_issue_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    lanes = ["mistral.chat-8sat", "mistral.chat-8open"]
    only_lanes = {"pending_wait_p50_ms", "admit_wait_p50_ms",
                  "lane_occupancy_share", "admit_slices_per_wave",
                  "fetch_wait_share", "decode_attn_busy_share"}
    # PR 27: a 3 s capture of one stream of chat may hold no prefill
    with_prefill = {"prefill_device_ms_per_ktok": ["solar.doc-1"] + lanes}
    for name in NEW:
        assert layer[name].get("workloads") == with_prefill.get(
            name, lanes if name in only_lanes else None), name
    # appended: what was there keeps its place
    assert list(layer)[-len(NEW):] == NEW
