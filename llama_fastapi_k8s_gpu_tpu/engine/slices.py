"""How a prompt is cut into prefill slices: the one plan of both engines.

A slice of ``LFKT_PREFILL_CHUNK`` tokens (the NARROW width, 256) exists
because a slice stands in front of the live lanes' next decode chunk.  Where
nobody decodes (the serial engine always; the lane engine while no lane
holds a request and no chunk is in flight) nothing is bounded by it, and a
narrow slice only costs: every fused matmul call dequantizes every weight
tile of its matrix once, whatever its rows (ops/pallas/qmatmul.py), so a
prompt of 2.5k tokens in 256-token slices pays ten weight passes.  There a
prompt is cut WIDE first, narrow for the tail: wide slices while a whole
wide slice of REAL tokens is left, then narrow ones up to the slice that
holds the last real token, as ever (so the padding stays under one narrow
slice: 2448 tokens are 1024 + 1024 + 256 + 256, four weight passes for ten).

The plan depends on what it observes and nothing else: the prompt, the
offset, whether anybody waits behind the slice, and the widest slice the
block takes (``ModelConfig.widest_slice``).  The warm-up compiles exactly
:func:`slice_shapes`.
"""

from __future__ import annotations

#: the ONE wide width.  From the row sweep of the fused matmuls
#: (docs/PERF.md "Rows of a fused matmul call"); 1024 is also a bucket, so
#: the serial engine's reuse pass already compiled this ``prefill_chunk``
#: shape
WIDE_SLICE = 1024


def wide_width(narrow: int, widest: int = 0) -> int:
    """The wide width of an engine whose narrow width is ``narrow`` on a
    block whose widest slice is ``widest`` (0: any): :data:`WIDE_SLICE` or
    the block's bound, where that is a whole number of narrow slices (every
    offset then stays on the narrow grid); else ``narrow``: no wide slice."""
    w = min(WIDE_SLICE, widest or WIDE_SLICE)
    return w if w > narrow and w % narrow == 0 else narrow


def next_slice(off: int, n_prompt: int, bucket: int, narrow: int, wide: int,
               alone: bool) -> int:
    """Tokens of the slice dispatched at offset ``off`` of a prompt of
    ``n_prompt`` real tokens padded to ``bucket``.  Wide only while nobody
    waits behind it (``alone``), a whole wide slice of real tokens is left,
    and ``off`` lies on the wide grid (a wide slice then lies inside one
    window of a window cache whose window it divides, as a narrow one
    does); else narrow, cut at the bucket's end."""
    if alone and wide > narrow and off % wide == 0 \
            and off + wide <= n_prompt:
        return wide
    return min(narrow, bucket - off)


def plan_slices(off: int, n_prompt: int, bucket: int, narrow: int, wide: int,
                alone: bool = True) -> list:
    """[(offset, tokens)] of a prompt's slices from ``off`` (what a reused
    prefix covers) to the slice that holds the last real token, under one
    ``alone`` throughout."""
    out = []
    while off < n_prompt:
        n = next_slice(off, n_prompt, bucket, narrow, wide, alone)
        out.append((off, n))
        off += n
    return out


def slice_shapes(buckets, narrow: int, wide: int) -> list:
    """Every slice width :func:`next_slice` can return for these buckets,
    rising: the narrow width, a bucket's remainder on the narrow grid, and
    the wide width where a prompt can hold one."""
    shapes = {min(narrow, b - off) for b in buckets
              for off in range(0, b, narrow)}
    if wide > narrow and max(buckets) > wide:
        shapes.add(wide)
    return sorted(shapes)
