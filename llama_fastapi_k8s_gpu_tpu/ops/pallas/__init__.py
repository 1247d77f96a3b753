"""Pallas TPU kernels — the in-tree analogue of the CUDA kernels the
reference consumes through llama.cpp's cuBLAS build (reference
docker/Dockerfile.base:30-32).

Two kernel families:

- :mod:`.attention` — blockwise flash attention (online softmax) for the
  prefill hot path, causal + optional sliding window, GQA-aware.
- :mod:`.dequant` — K-quant dequantization (Q4_K / Q5_K / Q6_K / Q8_0)
  executed *on device*: the host uploads the raw quantized block bytes
  (≈4.5 bit/weight) and the TPU expands them to bf16/f32 in HBM, so the
  host→device transfer is the quantized size, not the dequantized size.

Every kernel runs in interpret mode off-TPU so the whole suite is testable
on the CPU backend (SURVEY.md §4 "Device tests").
"""

from __future__ import annotations

import jax


def use_interpret() -> bool:
    """Pallas kernels compile natively only on TPU; interpret elsewhere.
    (Callers that need to force a mode pass ``interpret=`` explicitly —
    every kernel entry point takes it; the old module-global override
    hook was never used and was removed by the dead-code lint.)"""
    return jax.default_backend() != "tpu"


from .attention import (  # noqa: E402
    flash_attention, flash_attention_decode, latent_attention_decode,
    latent_attention_prefill)
from .dequant import (  # noqa: E402
    device_dequant,
    dequant_q4_k_device,
    dequant_q5_k_device,
    dequant_q6_k_device,
    dequant_q8_0_device,
)
from .q5matmul import prep_q5k, q5k_matmul, q5k_matmul_stacked  # noqa: E402
from .q6matmul import prep_q6k, q6k_matmul, q6k_matmul_stacked  # noqa: E402
from .q8matmul import prep_q8_0, q8_matmul, q8_matmul_stacked  # noqa: E402
from .qmatmul import prep_q4k, q4k_matmul, q4k_matmul_stacked  # noqa: E402

__all__ = [
    "flash_attention",
    "flash_attention_decode",
    "latent_attention_decode",
    "latent_attention_prefill",
    "device_dequant",
    "dequant_q4_k_device",
    "dequant_q5_k_device",
    "dequant_q6_k_device",
    "dequant_q8_0_device",
    "prep_q4k",
    "prep_q5k",
    "prep_q6k",
    "prep_q8_0",
    "q4k_matmul",
    "q4k_matmul_stacked",
    "q5k_matmul",
    "q5k_matmul_stacked",
    "q6k_matmul",
    "q6k_matmul_stacked",
    "q8_matmul",
    "q8_matmul_stacked",
    "use_interpret",
]
