from .batched import (  # noqa: F401
    batched_generate_chunk_perlane_jit,
    init_batched_state,
    init_lane_left,
)
