"""llama_fastapi_k8s_gpu_tpu — a TPU-native LLM serving framework.

A ground-up JAX/XLA/Pallas re-implementation of the capabilities of the
reference service `dzatulin/llama-fastapi-k8s-gpu` (FastAPI + llama.cpp/cuBLAS
on GPU, see /root/reference/api.py).  Where the reference delegates the entire
model runtime to the external native dependency ``llama-cpp-python==0.2.77``
(reference docker/Dockerfile.base:30-32), this package implements that runtime
in-tree, TPU-first:

- ``gguf``       — GGUF v2/v3 container parsing (mmap, zero-copy) and K-quant
                   (Q4_K/Q5_K/Q6_K/Q8_0/...) reference codecs.
- ``tokenizer``  — Llama-3 byte-level BPE and SentencePiece-style tokenizers
                   reconstructed from GGUF metadata, plus chat templates.
- ``models``     — the transformer itself (Llama / Mistral families) as pure
                   JAX functions: jit'd prefill + on-device decode with a
                   persistent, donated KV cache.
- ``ops``        — TPU compute primitives: Pallas kernels (dequant, flash
                   attention, fused quantized matmul) and XLA-native
                   quantized-matmul paths.
- ``sampling``   — llama.cpp-parity sampling chain (repetition/frequency/
                   presence penalties, top-k, top-p, min-p, temperature).
- ``engine``     — the drop-in replacement for ``llama_cpp.Llama``:
                   ``Engine.create_chat_completion`` with OpenAI-shaped
                   responses and streaming.
- ``parallel``   — the lane engine's vmapped decode programs and the
                   block-paged KV pool, on the process's one device.
- ``server``     — the FastAPI layer preserving the reference's externally
                   observable behavior (routes, admission queue, timeouts),
                   plus the advertised-but-missing ``/health`` and ``/metrics``.
- ``utils``      — config, logging, metrics plumbing.
"""

import time as _time

#: when this package was first imported (``time.time()``): the start-up
#: timeline's boundary between what a launcher paid and what the program's
#: own imports cost (server/__main__.py, utils/startup.py)
T_IMPORTED = _time.time()

__version__ = "0.1.0"
