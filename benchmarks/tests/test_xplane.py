"""The trace reduction: on hand-made event lists, and on a small recorded
trace of the chip (``data/``)."""

import glob
import os

import pytest

import xplane
from conftest import HERE


def test_busy_is_the_union_of_intervals():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0)]
    assert xplane.busy_intervals(ev) == [(0.0, 1.5), (3.0, 4.0)]
    busy, window = xplane.busy_seconds(ev)
    assert (busy, window) == (2.5, 4.0)
    busy, window = xplane.busy_seconds(ev, window=(1.0, 3.5))
    assert (busy, window) == (1.0, 2.5)


def test_gaps_longest_first():
    ev = [("a", 0.0, 1.0), ("b", 1.2, 0.3), ("c", 3.0, 1.0)]
    gaps = xplane.idle_gaps(ev)
    assert gaps[0] == (1.5, 1.5)
    assert gaps[1][0] == 1.0 and gaps[1][1] == pytest.approx(0.2)


def test_self_time_takes_children_out():
    # a while loop that covers two kernels, then a lone kernel
    ev = [("while", 0.0, 10.0), ("k", 1.0, 3.0), ("k", 5.0, 4.0),
          ("k", 11.0, 1.0)]
    names = xplane.by_name(ev)
    assert names["while"] == pytest.approx(3.0)
    assert names["k"] == pytest.approx(8.0)
    # nesting two deep: the grandchild is taken from its parent only
    ev = [("outer", 0.0, 10.0), ("mid", 1.0, 8.0), ("leaf", 2.0, 5.0)]
    assert xplane.by_name(ev) == {"outer": pytest.approx(2.0),
                                  "mid": pytest.approx(3.0),
                                  "leaf": pytest.approx(5.0)}


def test_groups_by_pattern():
    names = {"q4k_matmul.3": 2.0, "flash_attention": 1.0, "fusion.7": 0.5,
             "q6k_matmul": 1.0}
    got = xplane.group_seconds(names, {"qmatmul": ["q4k", "q6k"],
                                       "attn": ["flash"]})
    assert got == {"qmatmul": 3.0, "attn": 1.0}


def test_window_clips_events_and_counts_idle_at_the_ends():
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": [
        ("a", 0.5, 1.0), ("b", 2.0, 0.5), ("c", 9.0, 1.0)]}}, "host": {}}
    out = xplane.reduce(trace, {}, window=(0.0, 3.0))
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["window_s"] == pytest.approx(3.0)
    assert sorted(g[1] for g in out["gaps"]) == [pytest.approx(0.5)] * 3
    assert "c" not in out["ops"]


def test_capture_window_is_the_programs_sleep():
    host = {"main": [("$threading.py:1 wait", 0.0, 9.0)],
            "worker": [("$tracing.py:60 capture_profile", 1.0, 4.0),
                       ("$time sleep", 1.2, 3.0)]}
    assert xplane.capture_window(host, 3.0) == (1.2, 4.2)
    assert xplane.capture_window(host, 10.0) is None


def test_gaps_are_labelled_by_the_programs_deepest_open_frame():
    host = {
        "loop": [("$selectors.py:451 select", 0.0, 10.0)],
        "engine": [("$threading.py:1001 run", 0.0, 10.0),
                   ("$engine.py:1412 _run", 1.0, 8.0),
                   ("$array.py:631 _value", 2.0, 1.0),
                   ("$engine.py:88 process", 5.0, 1.0)],
    }
    files = {"engine.py"}
    cols = {k: xplane.columns(v) for k, v in host.items()}
    assert xplane.host_frames_at(cols, 2.5, files) == \
        "engine.py:1412 _run > array.py:631 _value"
    assert xplane.host_frames_at(cols, 5.5, files) == "engine.py:88 process"
    assert xplane.host_frames_at(cols, 9.5, files) == \
        "no frame of the program open"
    rows = xplane.label_gaps([(2.4, 0.2), (5.4, 0.1), (2.0, 0.05)], host, files)
    assert rows[0] == ["sum over gaps: engine.py:1412 _run > array.py:631 _value",
                       pytest.approx(0.25)]
    assert rows[2][0].startswith("longest gap: engine.py:1412")


def test_reduce_without_a_device_plane_is_none():
    assert xplane.reduce({"devices": {}, "host": {}}, {}) is None


RECORDED = os.path.join(HERE, "data", "solar.chat-1.v5e.xplane.pb")


def kernel_groups():
    import json
    out = {}
    for path in glob.glob(os.path.join(HERE, "..", "kernels", "*.json")):
        with open(path) as f:
            doc = json.load(f)
        out[doc["name"]] = doc["patterns"]
    return out


def test_recorded_trace_reduces():
    """45 ms of the mid-window capture of ``solar.chat-1`` on a TPU v5e (my
    chip run, PR 23), cut by ``data/cut_trace.py``: names, starts and
    durations as recorded.  The slice fell into a prompt's second
    256-token prefill slice."""
    trace = xplane.load(RECORDED)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    out = xplane.reduce(trace, kernel_groups())
    assert 0.040 < out["window_s"] < 0.050
    assert 0.95 < out["busy_s"] / out["window_s"] <= 1.0
    # self times add up to the busy time: nothing counted twice under a while
    assert sum(out["ops"].values()) == pytest.approx(out["busy_s"], rel=0.01)
    # the fused matmuls are most of it, flash attention a little
    assert 0.70 < out["groups"]["qmatmul"] / out["busy_s"] < 0.95
    assert 0.01 < out["groups"]["attn"] / out["busy_s"] < 0.15
    assert any(n.startswith("%flash_attention") for n in out["ops"])
    assert out["gaps"] == sorted(out["gaps"], key=lambda g: -g[1])
    # the executed programs are on the modules line
    mods = [m for m in out["modules"] if "prefill_chunk" in m[0]]
    assert len(mods) == 1 and mods[0][2] == pytest.approx(0.0588, rel=0.01)
    # the host's frames label a gap with a frame of the program
    rows = xplane.label_gaps(out["gaps"][:5], out["host"],
                             {"engine.py", "generate.py", "devtime.py"})
    assert rows and any(".py:" in r[0] for r in rows)
