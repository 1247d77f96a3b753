"""LFKT_KV_PAGED=1 serving contracts (parallel/kvpool.py).

The load-bearing invariant mirrors the chunked-prefill rollout (PR 5):
paging changes WHERE prefix KV comes from, never WHAT a greedy request
produces.  With no cache hit the paged engines dispatch exactly the
dense-ring programs, so greedy decode is bit-identical on both
engines — pinned here against a dense serial reference.  On top
of that: radix reuse across turns and across conversations sharing a
system prompt, cross-lane reuse on the continuous scheduler, explicit
seeds bypassing reuse (the reproducibility contract), pool-exhaustion
backpressure at the engine level, and watchdog-recovery pool reset.
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

#: distinct prompts (only the few-token chat-template header is shared —
#: under a full page, so the radix index can never grant them reuse and
#: parity compares identical dispatch sequences)
PROMPTS = [
    [{"role": "user", "content": "Say something."}],
    [{"role": "user", "content": "alpha bravo charlie delta echo " * 4}],
    [{"role": "user", "content": "one two three four five six seven " * 8}],
]

#: the paged configuration under test: 16-token pages, a 64-page pool
#: (2 full 512-token contexts), a 16-page host spill tier
PAGED_KW = dict(kv_paged=True, kv_page_tokens=16, kv_pool_pages=64,
                kv_spill_pages=16, prefix_min=16)
BASE_KW = dict(n_ctx=512, decode_chunk=4, max_gen_tokens=16,
               prefill_buckets=BUCKETS, prefill_chunk=16, prefill_overlap=2)


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path, cfg=ModelConfig(
        **{**TINY_CFG.__dict__, "n_ctx": 512}))
    return path


def _texts(eng, prompts=PROMPTS, max_tokens=8):
    out = []
    for p in prompts:
        r = eng.create_chat_completion(p, temperature=0.0,
                                       max_tokens=max_tokens)
        assert r["lfkt_timings"]["prefix_reused_tokens"] == 0, \
            "distinct prompts must not hit the prefix cache"
        out.append(r["choices"][0]["message"]["content"])
    return out


@pytest.fixture(scope="module")
def dense_texts(model_path):
    """The reference outputs: serial engine, dense ring, no reuse."""
    eng = Engine(model_path, prefix_cache=False, **BASE_KW)
    return _texts(eng)


def _convo(turn2: str = "And another one."):
    msgs = [{"role": "system", "content": "You answer carefully. " * 8},
            {"role": "user", "content": "Tell me something interesting."}]
    return msgs, turn2


# ---------------------------------------------------------------------------
# paged-vs-dense greedy bit-parity, both engines
# ---------------------------------------------------------------------------

def test_serial_paged_matches_dense(model_path, dense_texts):
    eng = Engine(model_path, **BASE_KW, **PAGED_KW)
    assert eng._kv_paged and eng._prefix_cache is False
    assert _texts(eng) == dense_texts
    # misses were counted (the index WAS consulted), commits banked pages
    stats = eng._kvpool.stats()
    assert stats["misses"] >= len(PROMPTS) - 1
    assert stats["stored_pages"] > 0


def test_continuous_paged_matches_dense(model_path, dense_texts):
    eng = ContinuousEngine(model_path, batch_size=2,
                           **BASE_KW, **PAGED_KW)
    try:
        assert eng._lane_prefix is False       # folded behind the radix
        assert _texts(eng) == dense_texts
    finally:
        eng.shutdown()


@pytest.fixture(scope="module")
def paged_serial(model_path):
    return Engine(model_path, **BASE_KW, **PAGED_KW)


def test_serial_multi_turn_resumes_from_pages(paged_serial):
    eng = paged_serial
    msgs, turn2 = _convo()
    t1 = eng.create_chat_completion(msgs, temperature=0.0, max_tokens=8)
    assert t1["lfkt_timings"]["prefix_reused_tokens"] == 0
    msgs2 = msgs + [
        {"role": "assistant",
         "content": t1["choices"][0]["message"]["content"]},
        {"role": "user", "content": turn2}]
    t2 = eng.create_chat_completion(msgs2, temperature=0.0, max_tokens=8)
    reused = t2["lfkt_timings"]["prefix_reused_tokens"]
    assert reused > 0
    assert reused % eng._kvpool.page_tokens == 0   # page-aligned restore
    assert t2["choices"][0]["message"]["content"]
    assert eng._kvpool.stats()["hits"] >= 1
    assert eng._kvpool.occupancy()["pages_pinned"] == 0   # lease released


def test_shared_system_prompt_across_conversations(paged_serial):
    """The headline behavior the per-request claim could never give: a
    DIFFERENT conversation with the same system prompt reuses its pages
    — the system prompt prefills once per process."""
    eng = paged_serial
    sys_msg = {"role": "system", "content": "Be brief and precise. " * 10}
    a = [sys_msg, {"role": "user", "content": "First question here."}]
    b = [sys_msg, {"role": "user", "content": "Unrelated other ask."}]
    ra = eng.create_chat_completion(a, temperature=0.0, max_tokens=8)
    rb = eng.create_chat_completion(b, temperature=0.0, max_tokens=8)
    assert ra["lfkt_timings"]["prefix_reused_tokens"] == 0
    assert rb["lfkt_timings"]["prefix_reused_tokens"] > 0


def test_explicit_seed_bypasses_radix(paged_serial):
    """Same-seed calls must be bit-identical, so they always take the
    full prefill — the serial engine's reproducibility contract extends
    to the paged index."""
    eng = paged_serial
    msgs = [{"role": "user", "content": "Deterministic seeds please. " * 6}]
    r1 = eng.create_chat_completion(msgs, temperature=0.0, max_tokens=8,
                                    seed=7)
    r2 = eng.create_chat_completion(msgs, temperature=0.0, max_tokens=8,
                                    seed=7)
    assert r1["lfkt_timings"]["prefix_reused_tokens"] == 0
    assert r2["lfkt_timings"]["prefix_reused_tokens"] == 0
    assert (r1["choices"][0]["message"]["content"]
            == r2["choices"][0]["message"]["content"])


def test_recover_resets_pool(paged_serial):
    eng = paged_serial
    # pages of its own: under ``--dist load`` this case can be the first
    # of the file that its worker runs, on a pool nothing has stored in
    eng.create_chat_completion(_convo()[0], temperature=0.0, max_tokens=8)
    assert eng._kvpool.occupancy()["pages_used"] > 0
    assert eng.recover()
    occ = eng._kvpool.occupancy()
    assert occ["pages_used"] == 0 and occ["pages_pinned"] == 0


# ---------------------------------------------------------------------------
# radix reuse behavior (continuous scheduler)
# ---------------------------------------------------------------------------

def test_continuous_cross_lane_reuse_and_exhaustion(model_path):
    """One engine, two stories: (1) a follow-up turn reuses its pages no
    matter which lane admits it; (2) with the pool squeezed to 4 pages,
    a burst of distinct conversations completes normally — stores skip
    or evict, requests never fail (backpressure, not OOM)."""
    kw = dict(PAGED_KW, kv_pool_pages=4, kv_spill_pages=0)
    eng = ContinuousEngine(model_path, batch_size=2,
                           **BASE_KW, **kw)
    try:
        msgs, turn2 = _convo()
        r1 = eng.submit(msgs, temperature=0.0, max_tokens=8).result()
        msgs2 = msgs + [
            {"role": "assistant",
             "content": r1["choices"][0]["message"]["content"]},
            {"role": "user", "content": turn2}]
        r2 = eng.submit(msgs2, temperature=0.0, max_tokens=8).result()
        # 4 pages x 16 tokens: the commit degrades to the conversation
        # HEAD (where the system prompt lives), and the follow-up still
        # hits that partial prefix
        assert r2["lfkt_timings"]["prefix_reused_tokens"] > 0
        assert eng._kvpool.stats()["hits"] >= 1
        # realized reuse publishes under the PAGED stat name, and the
        # dense lane-prefix stat shows no phantom activity
        sstats = eng.scheduler_stats()
        assert sstats["radix_prefix_hits"] >= 1
        assert "lane_prefix_hits" not in sstats
        # exhaustion burst: distinct prompts, every one must complete
        futs = [eng.submit([{"role": "user",
                             "content": f"burst number {i} " * 6}],
                           temperature=0.0, max_tokens=8)
                for i in range(6)]
        for f in futs:
            out = f.result(timeout=120)
            assert out["choices"][0]["message"]["content"]
        stats = eng._kvpool.stats()
        assert stats["store_skips"] + stats["evictions"] > 0
        assert eng._kvpool.occupancy()["pages_pinned"] == 0
    finally:
        eng.shutdown()


def test_paged_prefill_span_attribution(model_path, paged_serial):
    """A traced paged-reuse prefill carries reused_pages/matched_tokens
    and a kv_restore event — the waterfall's spill/restore visibility."""
    from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer

    eng = paged_serial
    msgs = [{"role": "system", "content": "Trace me carefully now. " * 10},
            {"role": "user", "content": "warm the cache"}]
    eng.create_chat_completion(msgs, temperature=0.0, max_tokens=8)
    tracer = Tracer(sample=1.0, ring=4)
    tr = tracer.start()
    msgs2 = [msgs[0], {"role": "user", "content": "different follow-up"}]
    r = eng.create_chat_completion(msgs2, temperature=0.0, max_tokens=8,
                                   trace=tr)
    tracer.finish(tr)
    assert r["lfkt_timings"]["prefix_reused_tokens"] > 0
    doc = tr.to_dict()
    prefill = None
    stack = [doc["root"]]
    while stack:
        s = stack.pop()
        if s["name"] == "prefill":
            prefill = s
        stack.extend(s["children"])
    assert prefill is not None
    assert prefill["attrs"]["reused"] > 0
    assert prefill["attrs"]["reused_pages"] >= 1
    assert prefill["attrs"]["matched_tokens"] >= prefill["attrs"]["reused"]
    events = [e["name"] for e in prefill["events"]]
    assert "kv_restore" in events


def test_serial_restore_failure_does_not_poison_cache(model_path,
                                                      monkeypatch):
    """The ring is donated into the restore copy: a failed dispatch must
    not leave the dead donated buffer as the engine's cache (the next
    request would trip over it) — the engine rebuilds cold, releases the
    lease, and the request after the failure serves normally."""
    from llama_fastapi_k8s_gpu_tpu.parallel import kvpool

    eng = Engine(model_path, **BASE_KW, **PAGED_KW)
    msgs, turn2 = _convo()
    t1 = eng.create_chat_completion(msgs, temperature=0.0, max_tokens=8)
    msgs2 = msgs + [
        {"role": "assistant",
         "content": t1["choices"][0]["message"]["content"]},
        {"role": "user", "content": turn2}]

    def boom(*_a, **_k):
        raise RuntimeError("injected restore failure")

    monkeypatch.setattr(kvpool, "_restore_pages_jit", boom)
    with pytest.raises(RuntimeError, match="injected restore"):
        eng.create_chat_completion(msgs2, temperature=0.0, max_tokens=8)
    assert eng._kvpool.occupancy()["pages_pinned"] == 0   # lease released
    monkeypatch.undo()
    r = eng.create_chat_completion(msgs2, temperature=0.0, max_tokens=8)
    assert r["choices"][0]["message"]["content"]
    assert r["lfkt_timings"]["prefix_reused_tokens"] > 0


def test_continuous_reuse_survives_poisoned_span(model_path):
    """lfkt-lint RES001 regression (ISSUE 8): a raising span setter inside
    ``_paged_admission_reuse`` sat between ``pool.acquire`` and the lease
    handoff — the one statement whose failure would leak the pinned pages
    for the life of the process (``_begin_admission``'s cleanup releases
    its own ``lease`` local, still None while the helper is on the stack).
    The span set is now guarded: the hit proceeds, nothing stays pinned."""
    eng = ContinuousEngine(model_path, batch_size=2,
                           **BASE_KW, **PAGED_KW)
    try:
        msgs, _ = _convo()
        eng.submit(msgs, temperature=0.0, max_tokens=8).result()
        ids = eng.tokenize_messages(msgs)
        assert eng._kvpool.match_len(ids) >= eng._paged_align

        class PoisonedSpan:
            def set(self, **kw):
                raise RuntimeError("poisoned span setter")

        r, lease = eng._paged_admission_reuse(ids, PoisonedSpan())
        assert r > 0 and lease is not None, \
            "the radix hit must survive a failing span setter"
        eng._kvpool.release(lease)
        assert eng._kvpool.occupancy()["pages_pinned"] == 0
    finally:
        eng.shutdown()
