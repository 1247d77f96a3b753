"""spec_decode="auto": the default is derived from the deployment's own
dispatch latency instead of a constant (VERDICT r4 weak #5 / next #7).  Pins the breakeven model (a > rtt/t_tok), both resolution directions,
the decision record, and the measurement-failure degradation."""

from __future__ import annotations

import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine
from llama_fastapi_k8s_gpu_tpu.engine import spec_auto
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

MSGS = [{"role": "user", "content": "Say something."}]


def test_breakeven_model_directions(monkeypatch):
    """rtt far below t_tok → lookup; rtt far above → off (8B at ~5.4
    GB/token, both production regimes from docs/PERF.md)."""
    import numpy as np

    params = {"layers": np.zeros(5_400_000_000 // 4, np.int32)}  # 5.4 GB

    monkeypatch.setattr(spec_auto, "measure_dispatch_rtt_s", lambda: 0.0015)
    mode, dec = spec_auto.resolve_auto(params, hbm_gbps=819.0, accept=1.0)
    assert mode == "lookup"
    assert dec["breakeven_acceptance"] < 0.5     # local-v5e regime

    monkeypatch.setattr(spec_auto, "measure_dispatch_rtt_s", lambda: 0.072)
    mode, dec = spec_auto.resolve_auto(params, hbm_gbps=819.0, accept=1.0)
    assert mode == "off"
    assert dec["breakeven_acceptance"] > 5       # slow-dispatch regime


def test_embedding_table_excluded_from_bytes():
    import numpy as np

    params = {"tok_emb": np.zeros((1000, 64), np.float32),
              "layers": {"w": np.zeros((64, 64), np.int8)}}
    assert spec_auto.decode_bytes_per_token(params) == 64 * 64


def test_measurement_failure_degrades_to_off(monkeypatch):
    def boom():
        raise RuntimeError("no device")

    monkeypatch.setattr(spec_auto, "measure_dispatch_rtt_s", boom)
    mode, dec = spec_auto.resolve_auto({}, hbm_gbps=819.0, accept=1.0)
    assert mode == "off"
    assert "no device" in dec["error"]


@pytest.fixture(scope="module")
def tiny_gguf(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return path


def test_engine_auto_resolves_on_and_serves(tiny_gguf, monkeypatch):
    monkeypatch.setattr(spec_auto, "measure_dispatch_rtt_s", lambda: 1e-9)
    eng = Engine(tiny_gguf, n_ctx=128, decode_chunk=4, max_gen_tokens=8,
                 prefill_buckets=(32, 64, 128), spec_decode="auto",
                 spec_draft=4)
    assert eng._spec_draft == 4
    assert eng.spec_auto_decision["resolved"] == "lookup"
    assert eng.spec_auto_decision["breakeven_acceptance"] < 1.0
    out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=6)
    assert out["usage"]["completion_tokens"] >= 1


def test_engine_auto_resolves_off_under_high_rtt(tiny_gguf, monkeypatch):
    monkeypatch.setattr(spec_auto, "measure_dispatch_rtt_s", lambda: 10.0)
    eng = Engine(tiny_gguf, n_ctx=128, decode_chunk=4, max_gen_tokens=8,
                 prefill_buckets=(32, 64, 128), spec_decode="auto",
                 spec_draft=4)
    assert eng._spec_draft == 0
    assert eng.spec_auto_decision["resolved"] == "off"
    # auto-off engines keep the serial prefix cache (spec is what excludes it)
    assert eng._prefix_cache


def test_continuous_engine_auto_gates_lane_prefix(tiny_gguf, monkeypatch):
    """When auto resolves ON in the lane scheduler, lane-prefix reuse must
    stay off (the spec-vs-reuse exclusion is decided post-resolution)."""
    monkeypatch.setattr(spec_auto, "measure_dispatch_rtt_s", lambda: 1e-9)
    eng = ContinuousEngine(tiny_gguf, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=8,
                           prefill_buckets=(32, 64, 128),
                           spec_decode="auto", spec_draft=4,
                           lane_prefix_cache=True)
    try:
        assert eng._spec_draft == 4
        assert not eng._lane_prefix
    finally:
        eng.shutdown()
