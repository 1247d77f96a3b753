"""The prefill slice plan as a pure function (engine/slices.py): one plan
for both engines.  It covers the prompt, cuts wide only while nobody waits
behind the slice and a whole wide slice of REAL tokens is left, keeps the
padding under one narrow slice, and emits only shapes the warm-up compiles.
"""

from __future__ import annotations

import pytest

from llama_fastapi_k8s_gpu_tpu.engine.slices import (
    WIDE_SLICE,
    next_slice,
    plan_slices,
    slice_shapes,
    wide_width,
)

BUCKETS = (128, 256, 512, 1024, 2048, 4096, 16384)


def _bucket(n: int, buckets=BUCKETS) -> int:
    return next(b for b in buckets if b >= n)


# (n_prompt, the widths in order) at narrow 256, wide 1024, nobody waiting
@pytest.mark.parametrize("n_prompt,widths", [
    (2448, [1024, 1024, 256, 256]),     # ISSUE 44's example: 4 passes for 10
    (1024, [1024]),
    (1023, [256, 256, 256, 256]),       # no whole wide slice of real tokens
    (1025, [1024, 256]),
    (300, [256, 256]),
    (100, [128]),                       # a small bucket: its own remainder
    (11300, [1024] * 11 + [256]),
    (3584, [1024, 1024, 1024, 256, 256]),
])
def test_wide_first_narrow_for_the_tail(n_prompt, widths):
    plan = plan_slices(0, n_prompt, _bucket(n_prompt), 256, 1024)
    assert [n for _, n in plan] == widths
    assert plan[0][0] == 0


@pytest.mark.parametrize("narrow,wide", [(256, 1024), (16, 64), (8, 32),
                                         (256, 256), (128, 1024)])
@pytest.mark.parametrize("alone", [True, False])
def test_the_plan_covers_the_prompt_and_pads_less_than_a_narrow_slice(
        narrow, wide, alone):
    buckets = tuple(b for b in (narrow // 2, narrow, 2 * narrow, 4 * narrow,
                                8 * narrow, 17 * narrow + 3) if b)
    shapes = set(slice_shapes(buckets, narrow, wide))
    for n_prompt in range(1, buckets[-1]):
        bucket = _bucket(n_prompt, buckets)
        for reuse in {0, narrow, 3 * narrow} - {r for r in (narrow, 3 * narrow)
                                                if r >= n_prompt}:
            plan = plan_slices(reuse, n_prompt, bucket, narrow, wide, alone)
            # contiguous from the reused prefix to past the last real token
            at = reuse
            for off, n in plan:
                assert off == at and n > 0
                at += n
            assert n_prompt <= at <= bucket
            # the padding stays under one narrow slice
            assert at - n_prompt < narrow
            for off, n in plan:
                if n > narrow:
                    # wide: only alone, on its own grid, of real tokens only
                    assert alone and n == wide
                    assert off % wide == 0 and off + n <= n_prompt
                assert n in shapes          # what the warm-up compiled
            if not alone:
                assert all(n <= narrow for _, n in plan)


@pytest.mark.parametrize("alone", [False, True])
def test_never_wide_beside_a_live_lane_or_a_chunk_in_flight(alone):
    """``alone`` is the lane engine's idle branch: no lane holds a request
    and no chunk is in flight.  Anything else is exactly the narrow slice
    there has always been."""
    assert next_slice(0, 4000, 4096, 256, 1024, alone) == \
        (1024 if alone else 256)


@pytest.mark.parametrize("narrow,widest,want", [
    (256, 0, WIDE_SLICE),        # any width: the one wide width
    (256, 256, 256),             # the block keeps the narrow width
    (16, 64, 64),                # a block's own bound (a window's share)
    (384, 0, 384),               # no whole number of narrow slices: narrow
    (1024, 0, 1024),
    (2048, 0, 2048),
])
def test_wide_width_of_an_engine(narrow, widest, want):
    assert wide_width(narrow, widest) == want


@pytest.mark.parametrize("buckets,narrow,wide,want", [
    ((128, 256, 512, 1024, 2048, 4096), 256, 1024, [128, 256, 1024]),
    ((128, 256, 512, 1024), 256, 1024, [128, 256]),     # no prompt holds one
    ((512, 1000), 256, 1024, [232, 256]),               # a ragged n_ctx
    ((32, 64, 128, 512), 16, 64, [16, 64]),
    ((64, 320), 16, 16, [16]),
])
def test_slice_shapes_are_what_the_plan_can_emit(buckets, narrow, wide, want):
    assert slice_shapes(buckets, narrow, wide) == want


def test_a_wide_slice_lies_inside_one_window():
    """A window cache's pass must lie inside one window (models/eva.py):
    the narrow width divides the window, and a wide slice starts on the
    wide grid, so a wide width that divides the window does too."""
    W, narrow, wide = 2048, 256, 1024
    for n_prompt in (2048, 3000, 5000, 6143):
        for reuse in (0, 256, 768, 1792):
            for off, n in plan_slices(reuse, n_prompt, 16384, narrow, wide):
                assert off // W == (off + n - 1) // W
