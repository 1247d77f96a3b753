"""AdmissionController (engine/continuous.py): the per-wave prefill budget
derived from measured lane-idle / decode-slack EMAs.

Unit scenarios from the round-6 issue: the budget must RISE while lanes sit
idle (admission-bound), SHRINK under sustained decode pressure, and never
drop below one slice per wave — a deadline-bearing admission always makes
progress even at the floor.
"""

from __future__ import annotations

import time

import pytest

from llama_fastapi_k8s_gpu_tpu.engine.continuous import AdmissionController

CHUNK, LANES, BASE = 256, 8, 512


def _ctl(**kw):
    return AdmissionController(CHUNK, LANES, BASE, **kw)


def test_budget_rises_with_idle_lanes():
    ctl = _ctl()
    start = ctl.budget
    seen = [start]
    for _ in range(40):
        # half the lanes free, decode finishing early (no fetch wait)
        seen.append(ctl.observe_wave(LANES // 2, 0.0, 0.010))
    assert seen[-1] > start
    assert seen[-1] == ctl.max_budget            # converges to the ceiling
    assert all(b2 >= b1 for b1, b2 in zip(seen, seen[1:]))  # monotone up


def test_budget_grows_on_decode_slack_even_when_full():
    """All lanes live but the device finishes chunks before the host needs
    them (fetch wait ~0): that slack is free admission headroom."""
    ctl = _ctl()
    for _ in range(40):
        ctl.observe_wave(LANES, 0.0005, 0.020)
    assert ctl.budget == ctl.max_budget


def test_budget_shrinks_under_decode_pressure():
    ctl = _ctl()
    for _ in range(60):
        # saturated lanes, host blocked on the device for ~the whole wave
        ctl.observe_wave(LANES, 0.019, 0.020)
    assert ctl.budget == ctl.min_budget
    assert ctl.ema_pressure > 0.9


def test_floor_is_one_slice_never_zero():
    ctl = _ctl()
    for _ in range(200):
        ctl.observe_wave(LANES, 1.0, 1.0)
        assert ctl.budget >= CHUNK               # ≥ one slice EVERY wave


def test_recovers_after_pressure_clears():
    ctl = _ctl()
    for _ in range(60):
        ctl.observe_wave(LANES, 0.019, 0.020)
    floor = ctl.budget
    for _ in range(40):
        ctl.observe_wave(2, 0.0, 0.010)          # lanes drain: idle again
    assert ctl.budget > floor


def test_ema_alpha_bounds_and_base_clamp():
    # tiny base clamps up to the one-slice floor; alpha clamps to (0, 1]
    ctl = AdmissionController(CHUNK, LANES, base=1, alpha=99.0)
    assert ctl.budget >= CHUNK
    assert ctl.alpha <= 1.0
    ctl2 = AdmissionController(CHUNK, LANES, base=BASE, alpha=0.0)
    assert ctl2.alpha > 0.0


def test_stats_surface():
    ctl = _ctl()
    ctl.observe_wave(LANES, 0.5, 1.0)
    s = ctl.stats()
    assert s["adm_budget_tokens"] == ctl.budget
    assert 0.0 <= s["adm_ema_idle"] <= 1.0
    assert 0.0 <= s["adm_ema_pressure"] <= 1.0


# ---------------------------------------------------------------------------
# integration: the floor never starves a deadline-bearing request
# ---------------------------------------------------------------------------

def test_deadline_request_progresses_at_budget_floor(tmp_path):
    """With the controller pre-loaded to maximum pressure (budget at the
    one-slice floor) and live decode traffic, a deadline-bearing request
    must still admit slice-by-slice and complete before its deadline."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=32,
                           prefill_buckets=(32, 64), prefill_chunk=16,
                           lane_prefix_cache=False)
    try:
        ctl = eng._adm_ctl
        assert ctl is not None                   # controller is the default
        # saturate the EMAs: the loop keeps observing, but from this state
        # the budget stays at/near the floor for the admission below
        ctl.ema_idle = 0.0
        ctl.ema_pressure = 1.0
        ctl.budget = ctl.min_budget
        eng._adm_budget = ctl.min_budget
        blocker = eng.submit([{"role": "user", "content": "keep decoding"}],
                             temperature=0.0, max_tokens=30)
        # multi-slice prompt (bucket 64 / slice 16) under a real deadline
        fut = eng.submit(
            [{"role": "user", "content": "x " * 40}],
            temperature=0.0, max_tokens=4, deadline=time.time() + 30)
        out = fut.result(timeout=60)
        assert out["usage"]["completion_tokens"] >= 1
        blocker.result(timeout=60)
    finally:
        eng.shutdown()


def test_static_budget_mode_unchanged(tmp_path):
    """adm_controller=False restores the static LFKT_ADM_BUDGET behavior:
    the budget attribute never moves."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64), prefill_chunk=16,
                           adm_budget=48, adm_controller=False,
                           lane_prefix_cache=False)
    try:
        assert eng._adm_ctl is None
        eng.create_chat_completion(
            [{"role": "user", "content": "hello"}], temperature=0.0,
            max_tokens=4)
        assert eng._adm_budget == 48
        stats = eng.scheduler_stats()
        assert stats["adm_budget_tokens"] == 48
        assert "adm_ema_idle" not in stats
    finally:
        eng.shutdown()


def test_static_mode_yields_after_one_slice_mid_prompt(tmp_path):
    """LFKT_ADM_CONTROLLER=0 preserves the pre-round-6 per-wave bound: a
    mid-prompt admission dispatches exactly ONE slice per _admit_round,
    regardless of budget — the static mode is a true A/B control arm.
    Controller mode consumes the wave budget in slices."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=8,
                           prefill_buckets=(32, 64), prefill_chunk=16,
                           adm_budget=64, adm_controller=False,
                           lane_prefix_cache=False)
    eng.shutdown()      # park the scheduler thread: pure-logic white-box
    calls = []

    def fake_admit_step(slots):
        calls.append(1)
        eng._adm = {"fake": "mid-prompt"}     # admission stays in flight
        return 16

    eng._admit_step = fake_admit_step
    try:
        assert eng._admit_round([None, None]) is True
        assert len(calls) == 1                # static: one slice per wave
        calls.clear()
        eng._adm = None
        eng._adm_ctl = AdmissionController(16, 2, 64)
        eng._adm_budget = 64
        assert eng._admit_round([None, None]) is True
        assert len(calls) == 4                # controller: budget of slices
    finally:
        eng._adm = None


def test_controller_seeds_from_first_observation():
    """A controller born into saturation must CUT from wave one — not ride
    an optimistic idle prior to max budget for ~1/alpha waves (the
    watchdog-recovery path re-creates controllers under live load)."""
    ctl = _ctl()
    start = ctl.budget
    for _ in range(3):
        ctl.observe_wave(LANES, 1.0, 1.0)     # max pressure immediately
    assert ctl.budget < start                 # cutting, not growing
    assert ctl.ema_pressure > 0.9


def test_pressure_cut_beats_idle_growth():
    """Free lanes under decode saturation must not grow the budget: the
    cut branch takes priority (idle lanes + saturated device = decode
    can't keep up; more prefill is the round-5 interference)."""
    ctl = _ctl()
    for _ in range(30):
        ctl.observe_wave(LANES // 2, 1.0, 1.0)   # half idle, max pressure
    assert ctl.budget == ctl.min_budget


@pytest.mark.parametrize("waves,lanes_live", [(5, 0), (5, LANES)])
def test_observe_wave_handles_zero_wave(waves, lanes_live):
    """Degenerate wave durations must not divide by zero or produce NaNs."""
    ctl = _ctl()
    for _ in range(waves):
        b = ctl.observe_wave(lanes_live, 0.0, 0.0)
        assert b == b and b >= ctl.min_budget    # not NaN, floored
