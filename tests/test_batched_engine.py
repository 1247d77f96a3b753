"""MeshEngine: batched completions over the virtual dp×tp mesh, plus the
server's request-coalescing consumer (the v5e-4 concurrent-load config)."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import Engine, MeshEngine
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

MSGS = [{"role": "user", "content": "Say something."}]


@pytest.fixture(scope="module")
def mesh_engine(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return MeshEngine(path, dp=2, tp=2, batch_size=4, n_ctx=128,
                      decode_chunk=4, max_gen_tokens=16,
                      prefill_buckets=(32, 64, 128))


def test_batch_shapes_and_order(mesh_engine):
    prompts = [
        [{"role": "user", "content": f"prompt number {i}"}] for i in range(3)
    ]
    outs = mesh_engine.create_chat_completions(prompts, max_tokens=6, seed=0)
    assert len(outs) == 3
    for o in outs:
        assert o["object"] == "chat.completion"
        assert o["usage"]["completion_tokens"] <= 6
        assert o["choices"][0]["finish_reason"] in ("stop", "length")


def test_batch_of_one_matches_engine_greedy(mesh_engine, tmp_path):
    """Greedy decoding must agree with the single-sequence Engine."""
    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    single = Engine(path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                    prefill_buckets=(32, 64, 128))
    a = single.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
    b = mesh_engine.create_chat_completions([MSGS], temperature=0.0,
                                            max_tokens=8)[0]
    assert a["choices"][0]["message"]["content"] == b["choices"][0]["message"]["content"]


def test_batch_greedy_is_padding_invariant(mesh_engine):
    """A sequence's greedy output must not depend on its batch neighbors."""
    solo = mesh_engine.create_chat_completions([MSGS], temperature=0.0,
                                               max_tokens=8)[0]
    crowd = mesh_engine.create_chat_completions(
        [MSGS, [{"role": "user", "content": "a much longer and very "
                 "different prompt that pads the bucket further out"}]],
        temperature=0.0, max_tokens=8)[0]
    assert solo["choices"][0]["message"]["content"] == \
        crowd["choices"][0]["message"]["content"]


def test_batch_overflow_raises(mesh_engine):
    with pytest.raises(ValueError):
        mesh_engine.create_chat_completions([MSGS] * 5)


def test_timings_recorded(mesh_engine):
    mesh_engine.create_chat_completions([MSGS] * 2, max_tokens=4, seed=1)
    t = mesh_engine.last_timings
    assert t["ttft_s"] > 0 and t["completion_tokens"] >= 2


# ---------------------------------------------------------------------------
# server coalescing
# ---------------------------------------------------------------------------

class BatchRecordingEngine:
    """Fake batch-capable engine recording the batch sizes it served."""

    def __init__(self):
        self.batches = []
        self.last_timings = None

    def create_chat_completions(self, batch_messages, **kw):
        self.batches.append(len(batch_messages))
        return [{
            "object": "chat.completion",
            "choices": [{"message": {"role": "assistant",
                                     "content": f"r{i}"}}],
            "usage": {"completion_tokens": 1},
        } for i in range(len(batch_messages))]

    def create_chat_completion(self, messages, **kw):
        return self.create_chat_completions([messages])[0]


@pytest.mark.anyio
async def test_server_coalesces_queued_requests():
    from tests.test_server import BODY, lifespan_client, make_client

    engine = BatchRecordingEngine()
    app, transport = make_client(engine, batch_size=4, max_queue_size=8)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            rs = await asyncio.gather(
                *[client.post("/response", json=BODY) for _ in range(5)])
            assert all(r.status_code == 200 for r in rs)
        await app.router.shutdown()
    # 5 requests over cycles of ≤4: at least one multi-request batch
    assert sum(engine.batches) == 5
    assert max(engine.batches) > 1


def test_oversized_prompt_isolated(mesh_engine):
    """An oversized prompt errors alone; batch neighbors still complete."""
    big = [{"role": "user", "content": "x" * 600}]  # byte-tokenizer: >128 toks
    outs = mesh_engine.create_chat_completions([big, MSGS], max_tokens=4)
    assert "error" in outs[0]
    assert "exceed context window" in outs[0]["error"]["message"]
    assert outs[1]["object"] == "chat.completion"
    assert outs[1]["usage"]["completion_tokens"] >= 1


def test_long_prompt_neighbor_does_not_truncate_short(mesh_engine):
    """Per-lane capacity: a long-prompt neighbor must not clamp a short
    request's budget to the batch-global context remainder."""
    short = [{"role": "user", "content": "hi"}]
    # ~100-token prompt in a 128-ctx model: leaves only ~27 slots for ITSELF
    long_p = [{"role": "user", "content": "y" * 80}]
    solo = mesh_engine.create_chat_completions([short], temperature=0.0,
                                               max_tokens=12)[0]
    crowd = mesh_engine.create_chat_completions([short, long_p],
                                                temperature=0.0,
                                                max_tokens=12)[0]
    assert crowd["usage"]["completion_tokens"] == solo["usage"]["completion_tokens"]
    assert crowd["choices"][0]["message"]["content"] == \
        solo["choices"][0]["message"]["content"]


def test_a_mesh_serves_xla_attention_and_refuses_flash_by_name(mesh_engine,
                                                              tmp_path):
    """The flash kernel has no partitioning rule, and JAX refuses to lower
    a bare Mosaic kernel into a program that spans devices: a mesh of more
    than one device resolves ``auto`` to ``xla`` and refuses ``pallas`` at
    construction, not at the warm-up compile (found on four chips, PR 22)."""
    assert mesh_engine.cfg.attn_impl == "xla"
    path = str(tmp_path / "m.gguf")
    write_tiny_llama_gguf(path)
    with pytest.raises(ValueError, match="no partitioning rule"):
        MeshEngine(path, dp=2, tp=2, batch_size=4, n_ctx=128,
                   attn_impl="pallas")
