"""Server-level latency bench: p50 TTFT on the real ``/response`` path.

BASELINE.json's TTFT metric is defined at the **server boundary** — the
reference's hot path runs FastAPI → queue → semaphore → llama.cpp
(reference api.py:118-173).  The engine-level TTFT in ``bench.py`` omits the
tokenizer, chat template, HTTP framing, and queue hop; this bench closes that
gap (VERDICT r2 #3): it starts the in-tree httpd serving the real ASGI app
with a real Engine (synthetic 8B weights on the chip, full-scale synthetic
BPE vocab so tokenize cost is honest), fires loopback POSTs shaped like the
reference's ``BotMessageRequest``, and reports:

- ``ttft_ms_p50_server``  — time to the first *content* SSE chunk on
  ``/response/stream`` (true first-token latency through the whole stack);
- ``latency_ms_p50``      — full ``/response`` round trip (the non-streaming
  endpoint returns only the complete generation, so its latency is
  TTFT + decode of ``max_tokens``).

Prints ONE JSON line.  Run ALONE (one process for each chip); the full-size
preset needs a TPU and refuses to run without one:
    python bench_server.py                      # real chip, 8B q4k
    LFKT_BENCH_PRESET=tiny JAX_PLATFORMS=cpu python bench_server.py   # smoke
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request


def emit_result(d: dict) -> None:
    """One provenance-stamped bench JSON line — single implementation in
    bench.py (shared like probe_fused_or_degrade, so the benches can't
    drift in what they stamp or how failure lines are guaranteed)."""
    from bench import emit_result as _emit

    _emit(d)


A10G_TTFT_MS = 300.0  # BASELINE.md: p50 TTFT < 300 ms on /response


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    t_start = time.time()

    import dataclasses

    from bench import start_device, synth_params_device
    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.models.config import LLAMA3_8B, ModelConfig
    from llama_fastapi_k8s_gpu_tpu.server import httpd
    from llama_fastapi_k8s_gpu_tpu.server.app import create_app
    from llama_fastapi_k8s_gpu_tpu.testing import synth_bpe_vocab
    from llama_fastapi_k8s_gpu_tpu.tokenizer import BPETokenizer

    preset = os.environ.get("LFKT_BENCH_PRESET", "llama3-8b")
    wfmt = os.environ.get("LFKT_BENCH_FMT", "q4km")
    n_req = int(os.environ.get("LFKT_BENCH_N_REQ", "12"))
    max_tokens = int(os.environ.get("LFKT_BENCH_MAX_TOKENS", "48"))
    port = int(os.environ.get("LFKT_BENCH_PORT", "8017"))
    fullctx = os.environ.get("LFKT_BENCH_FULLCTX") == "1"
    multiturn = os.environ.get("LFKT_BENCH_MULTITURN") == "1"
    # mixed-model arm (docs/MULTIMODEL.md): serve TWO models from one
    # process through the continuous scheduler and alternate model=
    # across lanes via /v1/chat/completions — per-model agg tok/s says
    # what co-residency costs vs a single-model pod
    mixed_models = os.environ.get("LFKT_BENCH_MIXED_MODELS") == "1"
    # disagg arm (serving/disagg/): the two-role LOOPBACK split —
    # role=both on one serial paged engine, so every cold prompt's
    # prefill crosses the full page wire (serialize → TCP → deserialize
    # → import → restore) — reported against a role-off control run of
    # the same fresh-prompt workload.  On one host this measures the
    # transfer OVERHEAD the split pays; across hosts the same wire buys
    # the prefill/decode interference removal (docs/RUNBOOK.md
    # "Operating a split prefill/decode fleet").
    disagg_arm = os.environ.get("LFKT_BENCH_DISAGG") == "1"
    from llama_fastapi_k8s_gpu_tpu.utils.config import env_bool

    lane_prefix = env_bool("LFKT_LANE_PREFIX_CACHE")
    if multiturn:
        # turn 1 is the no-reuse baseline and follow-ups are the sample;
        # fewer than 2 turns leaves nothing to report
        n_req = max(2, n_req)

    if preset == "tiny":
        cfg = ModelConfig(vocab_size=0, dim=128, n_layers=2, n_heads=8,
                          n_kv_heads=4, ffn_dim=256, n_ctx=256)
        n_merges = 2_000
    else:
        cfg = dataclasses.replace(LLAMA3_8B, attn_impl=os.environ.get(
            "LFKT_BENCH_ATTN", "pallas"))
        n_merges = 280_000

    dev = start_device(preset)
    from bench import FUSED_KEYS, probe_fused_or_degrade

    wfmt, _ = probe_fused_or_degrade(wfmt, "bench_server")
    tokens, merges, types = synth_bpe_vocab(n_merges=n_merges)
    cfg = dataclasses.replace(cfg, vocab_size=len(tokens))
    tok = BPETokenizer(tokens, merges, types,
                       bos_id=tokens.index("<|begin_of_text|>"),
                       eos_id=tokens.index("<|eot_id|>"))
    params = synth_params_device(cfg, fmt=wfmt)
    fused_key = FUSED_KEYS.get(wfmt)
    if fused_key is not None and not any(
            isinstance(v, dict) and any(fk in v for fk in fused_key)
            for v in [*params["layers"].values(), params["output"]]):
        wfmt = "int8"  # label honesty: tiny shapes fall back
    # kv_dtype axis (docs/KV_CACHE.md): the engines read it off cfg, and a
    # non-default dtype rides the wfmt label so every result metric keys
    # its arm (same convention as bench.py's kv-int8 tag)
    kv_dtype = os.environ.get("LFKT_KV_DTYPE", "bf16")
    cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    if kv_dtype != "bf16":
        wfmt = f"{wfmt},kv-{kv_dtype}"
    batch = int(os.environ.get("LFKT_BENCH_BATCH", "1"))
    if disagg_arm and batch > 1:
        raise SystemExit(
            "LFKT_BENCH_DISAGG=1 measures the serial two-role loopback; "
            "set LFKT_BENCH_BATCH=1 (the continuous-scheduler split rides "
            "the same client — bench it via LFKT_DISAGG_ROLE on a real "
            "two-process fleet)")
    if mixed_models and batch <= 1:
        raise SystemExit(
            "LFKT_BENCH_MIXED_MODELS=1 needs LFKT_BENCH_BATCH>1: the arm "
            "measures models interleaving across scheduler lanes")
    # fleet arm (serving/fleet/): TWO in-process serial paged replicas
    # behind the prefix-affinity router, multi-turn replay affinity-on vs
    # the round-robin control — the hit-ratio/warm-TTFT answer to "does
    # the router actually keep conversations on their warm replica"
    fleet_arm = os.environ.get("LFKT_BENCH_FLEET") == "1"
    if fleet_arm and (batch > 1 or mixed_models or disagg_arm or multiturn):
        raise SystemExit(
            "LFKT_BENCH_FLEET=1 is its own arm (two serial paged replicas "
            "behind the router): drop LFKT_BENCH_BATCH/MULTITURN/"
            "MIXED_MODELS/DISAGG")
    # the app sizes its in-flight permit pool from settings.batch_size
    # (server/app.py: Semaphore(max(1, settings.batch_size))) — without
    # this the server serializes requests at inflight=1 and a B-lane
    # engine decodes one lane at a time (measured: batch=4 aggregate
    # throughput equal to a single lane's).  The mixed arm serves TWO
    # B-lane engines, so its permit pool must cover both fleets.
    os.environ["LFKT_BATCH_SIZE"] = str(2 * batch if mixed_models else batch)
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings, get_settings

    settings = get_settings()

    if fleet_arm:
        # LFKT_BENCH_FLEET=1: two replicas (serial paged engines, same
        # synthetic weights — bit-identical greedy twins) each behind a
        # real httpd, fronted by a real FleetRouter; C conversations x T
        # turns replayed round-robin ACROSS conversations, so
        # consecutive requests belong to different conversations (the
        # k8s traffic shape).  Phase A routes policy=affinity, phase B
        # (fresh replicas: counters and radix trees start cold) routes
        # the identical replay policy=roundrobin.  Reported per phase:
        # the aggregate token-weighted prefix hit ratio
        # (prefix_cache_reused_tokens_total / tokens_prompt_total across
        # both replicas — the fraction of prompt tokens served from
        # cached KV pages) and warm (turn>=2) streamed TTFT p50.  C is
        # ODD on purpose: with 2 replicas an even C makes round-robin
        # accidentally affine ((t*C+c) mod 2 == c mod 2), flattering the
        # control.
        from llama_fastapi_k8s_gpu_tpu.serving.fleet.peers import PeerTable
        from llama_fastapi_k8s_gpu_tpu.serving.fleet.router import (
            FleetRouter,
        )

        convs = int(os.environ.get("LFKT_BENCH_CONVS", "3"))
        if convs % 2 == 0:
            convs += 1
        turns = max(2, int(os.environ.get("LFKT_BENCH_TURNS", "3")))
        page_tokens = (16 if preset == "tiny"
                       else settings.kv_page_tokens)
        pq = lambda v, q: v[min(len(v) - 1, int(q * len(v)))]  # noqa: E731

        def wait_http(url: str, deadline_s: float = 120.0) -> None:
            deadline = time.time() + deadline_s
            while True:
                try:
                    urllib.request.urlopen(url, timeout=5)
                    return
                except Exception:  # noqa: BLE001 — booting
                    if time.time() > deadline:
                        raise
                    time.sleep(0.2)

        def start_replica(rport: int):
            reng = Engine.from_parts(
                params, cfg, tok, template_kind="llama3",
                max_gen_tokens=max_tokens, attn_impl=cfg.attn_impl,
                decode_chunk=settings.decode_chunk,
                prefill_chunk=settings.prefill_chunk,
                kv_paged=True, kv_page_tokens=page_tokens)
            reng.warmup()
            rapp = create_app(engine=reng)
            threading.Thread(
                target=lambda: asyncio.run(
                    httpd.serve(rapp, host="127.0.0.1", port=rport)),
                daemon=True).start()
            wait_http(f"http://127.0.0.1:{rport}/health")
            return reng

        def start_router(rport: int, peer_ports: list, policy: str):
            table = PeerTable(
                peers=[f"127.0.0.1:{p}" for p in peer_ports],
                probe_seconds=1.0).start()
            router = FleetRouter(table, policy=policy)
            threading.Thread(
                target=lambda: asyncio.run(
                    router.serve("127.0.0.1", rport)),
                daemon=True).start()
            wait_http(f"http://127.0.0.1:{rport}/health/ready")
            return router

        def fleet_stream_ttft(rport: int, body: bytes):
            req = urllib.request.Request(
                f"http://127.0.0.1:{rport}/response/stream", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            first, err, parts = None, None, []
            with urllib.request.urlopen(req, timeout=600) as r:
                for raw in r:
                    line = raw.decode("utf-8", "replace").strip()
                    if not line.startswith("data:"):
                        continue
                    body_ln = line[5:].strip()
                    if body_ln == "[DONE]":
                        break
                    evt = json.loads(body_ln)
                    if "error" in evt:
                        err = str(evt["error"])
                        break
                    c = evt["choices"][0]["delta"].get("content")
                    if c:
                        if first is None:
                            first = (time.perf_counter() - t0) * 1e3
                        parts.append(c)
            if first is None:
                first = (time.perf_counter() - t0) * 1e3
            return first, "".join(parts), err

        def replica_metric(rport: int, name: str) -> float:
            """Sum of one family's series (labeled or not) on a replica."""
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rport}/metrics", timeout=30) as r:
                text = r.read().decode()
            total = 0.0
            for ln in text.splitlines():
                head, _, val = ln.rpartition(" ")
                if head == name or head.startswith(name + "{"):
                    total += float(val)
            return total

        def fleet_payload(c: int, history: list) -> bytes:
            # distinct persona + opener per conversation: affinity keys
            # differ AND the radix shares nothing across conversations,
            # so reuse measured here is conversation affinity, not the
            # shared-system-prompt effect PR 6 already banked
            return json.dumps({
                "bot_profile": {
                    "name": f"Bot{c}",
                    "appearance": "tall, green eyes, red hair, calm voice",
                    "system_prompt": f"You are concise assistant #{c} "
                                     "who answers briefly.",
                },
                "user_profile": {"name": "Sam"},
                "context": history,
            }).encode()

        followups = [
            "Interesting, tell me more.", "Why is that?", "Go on.",
            "What happened next?", "Could you expand on that?",
        ]

        def fleet_phase(policy: str, base_port: int) -> dict:
            p1, p2 = base_port + 1, base_port + 2
            start_replica(p1)
            start_replica(p2)
            router = start_router(base_port, [p1, p2], policy)
            histories = {
                c: [{"turn": "user",
                     "message": f"Hello bot {c}! Please introduce "
                                "yourself briefly and tell me a story."}]
                for c in range(convs)
            }
            warm, turn1, errors = [], [], []
            t0p = time.perf_counter()
            for t in range(turns):
                for c in range(convs):
                    body = fleet_payload(c, histories[c])
                    try:
                        ms, text, err = fleet_stream_ttft(base_port, body)
                    except Exception as e:  # noqa: BLE001 — transport
                        errors.append(f"{type(e).__name__}: {e}")
                        continue
                    if err is not None:
                        errors.append(err)
                        continue
                    (turn1 if t == 0 else warm).append(ms)
                    histories[c].append(
                        {"turn": "bot", "message": (text or "...")[:400]})
                    histories[c].append(
                        {"turn": "user",
                         "message": followups[(c + t) % len(followups)]})
            wall = time.perf_counter() - t0p
            per_replica = []
            reused = prompt = hits = misses = 0.0
            for p in (p1, p2):
                row = {
                    "port": p,
                    "reused_tokens": replica_metric(
                        p, "prefix_cache_reused_tokens_total"),
                    "prompt_tokens": replica_metric(
                        p, "tokens_prompt_total"),
                    "hits": replica_metric(p, "prefix_cache_hits_total"),
                    "misses": replica_metric(
                        p, "prefix_cache_misses_total"),
                }
                per_replica.append(row)
                reused += row["reused_tokens"]
                prompt += row["prompt_tokens"]
                hits += row["hits"]
                misses += row["misses"]
            warm.sort()
            turn1.sort()
            return {
                "policy": policy,
                # THE headline: fraction of submitted prompt tokens
                # served from cached KV pages, fleet-wide
                "hit_ratio_tokens": (round(reused / prompt, 4)
                                     if prompt else 0.0),
                "hit_ratio_requests": (round(hits / (hits + misses), 4)
                                       if hits + misses else 0.0),
                "warm_ttft_ms_p50": (round(pq(warm, 0.5), 1)
                                     if warm else None),
                "turn1_ttft_ms_p50": (round(pq(turn1, 0.5), 1)
                                      if turn1 else None),
                "warm_samples": len(warm),
                "errors": errors[:8],
                "per_replica": per_replica,
                "router": dict(router.counters),
                "wall_s": round(wall, 1),
            }

        aff = fleet_phase("affinity", port)
        ctl = fleet_phase("roundrobin", port + 10)
        ratio = (aff["hit_ratio_tokens"] / ctl["hit_ratio_tokens"]
                 if ctl["hit_ratio_tokens"] else None)
        result = {
            "metric": (f"fleet_prefix_hit_ratio[/response,{preset},"
                       f"{wfmt},affinity]"),
            "value": aff["hit_ratio_tokens"],
            "unit": "ratio",
            "vs_roundrobin_control": (round(ratio, 2)
                                      if ratio is not None else None),
            "affinity": aff,
            "control": ctl,
            "conversations": convs,
            "turns": turns,
            "kv_page_tokens": page_tokens,
            "max_tokens": max_tokens,
            "decode_chunk": settings.decode_chunk,
            "device": str(dev),
        }
        emit_result(result)
        os._exit(0)  # daemon server threads: skip graceful teardown

    if batch > 1:
        # continuous batching on one chip: B slot-scheduled lanes amortize
        # every weight read over up to B decode tokens — the aggregate-
        # throughput mode the reference cannot express (Semaphore(1)
        # serializes its generations, reference api.py:114)
        from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

        eng = ContinuousEngine.from_parts(
            params, cfg, tok, template_kind="llama3",
            max_gen_tokens=max_tokens, attn_impl=cfg.attn_impl,
            batch_size=batch,
            # honor the same LFKT_* scheduler knobs the production factory
            # does (server/app.py passes each from Settings) — a
            # directly-constructed engine otherwise pins constructor
            # defaults and an env A/B silently measures the same arm
            # twice (the round-4 lane-prefix lesson).
            decode_chunk=settings.decode_chunk,
            adm_budget=settings.adm_budget,
            # the round-6 prefill-pipeline A/B axes: EMA admission
            # controller vs static budget (LFKT_ADM_CONTROLLER) and the
            # overlapped-prefill depth — both labeled on the metric so an
            # env A/B can never measure the same arm twice
            adm_controller=settings.adm_controller,
            adm_ema_alpha=settings.adm_ema_alpha,
            prefill_overlap=settings.prefill_overlap,
            # the lane-prefix A/B knobs (VERDICT r4 #8).  The admission
            # slice size matters to the A/B too: reuse is chunk-aligned,
            # so a 256-token slice needs 256 shared tokens before the
            # first claim pays.
            lane_prefix_cache=lane_prefix,
            prefill_chunk=settings.prefill_chunk)
        # report the engine's REALIZED setting, not the env request: the
        # paged pool replaces lane-prefix reuse (continuous.py), and a
        # ',laneprefix'-labeled artifact with reuse actually off would be a
        # mislabeled A/B arm in the evidence ledger
        lane_prefix = bool(getattr(eng, "_lane_prefix", False))
        if mixed_models:
            # second co-resident model: SAME synthetic weights (identity
            # matters to the scheduler, not the bytes — sharing the
            # params pytree keeps the HBM cost honest to a real
            # two-model pod only in the KV/lane dimension, which is what
            # this arm measures: interleaved multi-model scheduling)
            from llama_fastapi_k8s_gpu_tpu.serving import ModelRegistry

            eng_b = ContinuousEngine.from_parts(
                params, cfg, tok, template_kind="llama3",
                max_gen_tokens=max_tokens, attn_impl=cfg.attn_impl,
                batch_size=batch,
                decode_chunk=settings.decode_chunk,
                adm_budget=settings.adm_budget,
                adm_controller=settings.adm_controller,
                adm_ema_alpha=settings.adm_ema_alpha,
                prefill_overlap=settings.prefill_overlap,
                lane_prefix_cache=lane_prefix,
                prefill_chunk=settings.prefill_chunk)
            eng = ModelRegistry({"alpha": eng, "beta": eng_b}, "alpha")
    else:
        # prefix reuse stays OFF for the standard phases: they re-POST a
        # byte-identical payload n_req times, so the serial engine's
        # prompt-prefix KV reuse would silently shrink every measured
        # prefill to one suffix bucket and the TTFT metric (same name as
        # prior rounds') would stop measuring full-stack prefill latency.
        # The multiturn mode measures the reuse path, explicitly labeled.
        paged_kw = {}
        if disagg_arm:
            # the page wire needs the paged pool; small pages at tiny
            # scale so the fresh-prompt grid actually crosses page
            # boundaries (serial reuse is page-aligned)
            paged_kw = dict(kv_paged=True,
                            kv_page_tokens=32 if preset == "tiny"
                            else settings.kv_page_tokens)
        eng = Engine.from_parts(params, cfg, tok, template_kind="llama3",
                                max_gen_tokens=max_tokens,
                                attn_impl=cfg.attn_impl,
                                decode_chunk=settings.decode_chunk,
                                prefix_cache=multiturn,
                                prefill_chunk=settings.prefill_chunk,
                                prefill_overlap=settings.prefill_overlap,
                                **paged_kw)
    # compile every shape BEFORE the server phase, exactly like the
    # production factory (server/app.py calls eng.warmup() at startup);
    # without it the first request compiles for ~60 s and the 25 s
    # admission timeout 408s it, killing the warmup POST below
    eng.warmup()
    app = create_app(engine=eng)

    th = threading.Thread(
        target=lambda: asyncio.run(httpd.serve(app, host="127.0.0.1",
                                               port=port)),
        daemon=True)
    th.start()
    base = f"http://127.0.0.1:{port}"
    deadline = time.time() + 60
    while True:  # wait for the socket
        try:
            urllib.request.urlopen(base + "/health", timeout=5)
            break
        except Exception:
            if time.time() > deadline:
                raise
            time.sleep(0.5)

    # LFKT_BENCH_FULLCTX=1: a chat history that fills the reference's whole
    # context budget (api.py:17 MAX_CONTEXT_TOKENS=1024 at the chars/4
    # estimate, each message at the 400-char clip), so prefill runs the
    # full 1024-token bucket through the server stack — the TTFT shape the
    # short-prompt run doesn't exercise (VERDICT r3 #6).
    if fullctx:
        lines = ("The quick brown fox jumps over the lazy dog near the "
                 "riverbank while autumn leaves drift slowly down. ")
        # size the history with the REAL tokenizer (the reference's chars/4
        # estimate over-admits for low-merge synthetic vocabs): take a
        # token-budgeted slice of a long text, then split it into
        # clip-sized (400-char) turns
        budget = max(32, cfg.n_ctx - 200)   # headroom: template + system
        ids = tok.encode(lines * 40)
        text = tok.decode(ids[:budget])
        context = [
            {"turn": "user" if i % 2 == 0 else "bot",
             "message": text[j:j + 400]}
            for i, j in enumerate(range(0, len(text), 400))
        ] + [{"turn": "user", "message": "Tell me about the weather today."}]
    else:
        context = [
            {"turn": "user", "message": "Tell me about the weather today."},
        ]
    payload = json.dumps({  # the reference's wire shape (data/requests.py)
        "bot_profile": {
            "name": "Ada",
            "appearance": "tall, green eyes, red hair, calm voice",
            "system_prompt": "You are a concise assistant.",
        },
        "user_profile": {"name": "Sam"},
        "context": context,
    }).encode()

    def post(path):
        return urllib.request.Request(
            base + path, data=payload,
            headers={"Content-Type": "application/json"})

    # warmup: compile every shape through the server path.  The server's
    # reference-parity 25 s admission timeout (api.py:18) can 408 a slow
    # first generation (early-process executions run 20-40x slow on this
    # platform) — but that generation still runs to completion server-side
    # and warms the programs, so retry instead of crashing; the retry
    # queues behind it and completes fast once warm.
    warm_deadline = time.time() + 900   # outlasts a fully cold compile path
    while True:
        try:
            with urllib.request.urlopen(post("/response"), timeout=1800) as r:
                r.read()
            break
        except urllib.error.HTTPError as e:
            if e.code != 408 or time.time() > warm_deadline:
                raise
            print("bench_server: warmup got 408 (cold generation overran "
                  "the 25s admission timeout); retrying",
                  file=sys.stderr, flush=True)
            time.sleep(2)
    warm_s = time.time() - t_start

    def read_metrics_counters(names) -> dict | None:
        """Scrape named counters off the app's /metrics; None when the
        endpoint is unreadable (so callers report null, not fabricated
        zeros)."""
        try:
            with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
                text = r.read().decode()
        except Exception:  # noqa: BLE001 — measurement aid, not the result
            return None
        out = {n: 0.0 for n in names}
        for ln in text.splitlines():
            parts = ln.split()
            if len(parts) == 2 and parts[0] in out:
                out[parts[0]] = float(parts[1])
        return out

    def stream_ttft(body: bytes):
        """POST /response/stream; returns (ttft_ms, full_text, error).
        Drains the stream fully (an abandoned generation runs to completion
        and would queue under the next sample's TTFT).  ``error`` is the
        server's SSE error event text (context overflow, timeout) or None —
        callers must stop measuring a conversation once it errors, or every
        later "sample" is a fast error round trip mislabeled as TTFT."""
        req = urllib.request.Request(
            base + "/response/stream", data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        first = None
        err = None
        parts: list[str] = []
        with urllib.request.urlopen(req, timeout=600) as r:
            for raw in r:
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data:"):
                    continue
                body_ln = line[5:].strip()
                if body_ln == "[DONE]":
                    break
                evt = json.loads(body_ln)
                if "error" in evt:
                    err = str(evt["error"])
                    break
                delta = evt["choices"][0]["delta"]
                c = delta.get("content")
                if c:
                    if first is None:
                        first = (time.perf_counter() - t0) * 1e3
                    parts.append(c)
        if first is None:
            first = (time.perf_counter() - t0) * 1e3
        return first, "".join(parts), err

    if disagg_arm:
        # LFKT_BENCH_DISAGG=1: the same engine serves both halves over
        # loopback TCP — control phase first (role off: the engine's
        # _disagg gate is None), then the client is installed and the
        # identical fresh-prompt workload re-runs through the wire.
        from llama_fastapi_k8s_gpu_tpu.serving.disagg.decoder import (
            DisaggClient,
        )
        from llama_fastapi_k8s_gpu_tpu.serving.disagg.prefiller import (
            PrefillServer,
        )

        psrv = PrefillServer(eng, host="127.0.0.1", port=0,
                             metrics=app.state.metrics)
        pcli = DisaggClient(f"127.0.0.1:{psrv.port}", eng._kvpool,
                            timeout_s=60.0, metrics=app.state.metrics)

        # a prompt long enough that the serial paged-reuse constraints
        # grant page-aligned reuse (bucket > smallest bucket, suffix
        # fits a smaller one) — sized with the REAL tokenizer
        filler_ids = tok.encode(
            "The quick brown fox jumps over the lazy dog near the old "
            "riverbank while autumn leaves drift slowly down. " * 40)
        filler = tok.decode(filler_ids[:min(150, cfg.n_ctx // 2)])

        def disagg_payload(tag: str) -> bytes:
            # the tag leads, so every request's FIRST page differs —
            # each sample is a cold radix miss and the hop must fire
            return json.dumps({
                "bot_profile": {
                    "name": "Ada",
                    "appearance": "tall, green eyes, red hair, calm voice",
                    "system_prompt": "You are a concise assistant.",
                },
                "user_profile": {"name": "Sam"},
                "context": [{"turn": "user",
                             "message": (f"[{tag}] " + filler)[:400]}],
            }).encode()

        pq = lambda v, q: v[min(len(v) - 1, int(q * len(v)))]  # noqa: E731

        def read_metric_sum(name: str) -> float | None:
            # streamed responses meter into the LABELED per-model family
            # (tokens_generated_total{model=...}) — sum its series
            try:
                with urllib.request.urlopen(base + "/metrics",
                                            timeout=30) as r:
                    text = r.read().decode()
            except Exception:  # noqa: BLE001 — measurement aid
                return None
            total, found = 0.0, False
            for ln in text.splitlines():
                head, _, val = ln.rpartition(" ")
                if head == name or head.startswith(name + "{"):
                    total += float(val)
                    found = True
            return total if found else None

        def disagg_phase(label: str) -> dict:
            before = read_metric_sum("tokens_generated_total")
            samples = []
            t0p = time.perf_counter()
            for i in range(n_req):
                ms, _text, err = stream_ttft(disagg_payload(f"{label}{i}"))
                if err is None:
                    samples.append(ms)
                else:
                    print(f"bench_server: disagg {label} stream error: "
                          f"{err}", file=sys.stderr, flush=True)
            wall = time.perf_counter() - t0p
            after = read_metric_sum("tokens_generated_total")
            gen = (after - (before or 0.0)
                   if after is not None else None)
            samples.sort()
            return {
                "ttft_ms_p50": (round(pq(samples, 0.5), 1)
                                if samples else None),
                "ttft_ms_p95": (round(pq(samples, 0.95), 1)
                                if samples else None),
                "samples": len(samples),
                "agg_tok_s": (round(gen / wall, 1)
                              if gen and wall > 0 else None),
                "gen_tokens": int(gen) if gen is not None else None,
                "wall_s": round(wall, 1),
            }

        control = disagg_phase("ctl")      # role off: one attribute read
        eng.install_disagg(pcli)
        split = disagg_phase("dis")
        result = {
            "metric": (f"server_ttft_ms_p50[/response,{preset},{wfmt}"
                       ",disagg-loopback]"),
            "value": split["ttft_ms_p50"] or 0.0,
            "unit": "ms",
            "control": control,
            "disagg": split,
            "disagg_client": pcli.status(),
            "disagg_service": psrv.status(),
            "kv_page_tokens": eng._kvpool.page_tokens,
            "max_tokens": max_tokens,
            "n_requests": n_req,
            "warmup_s": round(warm_s, 1),
            "decode_chunk": settings.decode_chunk,
            "device": str(dev),
        }
        emit_result(result)
        os._exit(0)  # daemon server thread: skip graceful asyncio teardown

    if mixed_models:
        # LFKT_BENCH_MIXED_MODELS=1 + LFKT_BENCH_BATCH=B: `conc` worker
        # threads split across the two models, each POSTing
        # /v1/chat/completions with its model= — lanes of both models
        # decode concurrently and the schedulers interleave their waves
        # on the one device queue.  Per-model aggregate tok/s comes from
        # the responses' usage counts (the facade returns them; /response
        # strips usage off the wire).
        conc = int(os.environ.get("LFKT_BENCH_CONCURRENCY", str(2 * batch)))
        per = max(2, n_req // 2)
        model_names = ("alpha", "beta")
        agg = {name: {"tokens": 0, "completed": 0, "lat_ms": [],
                      "errors": 0} for name in model_names}
        lk = threading.Lock()

        def mixed_worker(i: int):
            name = model_names[i % 2]        # alternating model= per lane
            body = json.dumps({
                "model": name,
                "max_tokens": max_tokens,
                "temperature": 0.7,
                "messages": [{"role": "user",
                              "content": "Tell me about the weather "
                                         f"today, worker {i}."}],
            }).encode()
            req = urllib.request.Request(
                base + "/v1/chat/completions", data=body,
                headers={"Content-Type": "application/json"})
            for _ in range(per):
                t0 = time.perf_counter()
                try:
                    with urllib.request.urlopen(req, timeout=600) as r:
                        doc = json.loads(r.read())
                    ms = (time.perf_counter() - t0) * 1e3
                    with lk:
                        agg[name]["tokens"] += doc["usage"]["completion_tokens"]
                        agg[name]["completed"] += 1
                        agg[name]["lat_ms"].append(ms)
                except Exception:  # noqa: BLE001 — count, keep sampling
                    with lk:
                        agg[name]["errors"] += 1

        t_mx = time.perf_counter()
        ths = [threading.Thread(target=mixed_worker, args=(i,))
               for i in range(conc)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        mx_s = time.perf_counter() - t_mx
        pq = lambda v, q: v[min(len(v) - 1, int(q * len(v)))]  # noqa: E731
        per_model = {}
        for name, a in agg.items():
            a["lat_ms"].sort()
            per_model[name] = {
                "agg_tok_s": (round(a["tokens"] / mx_s, 1)
                              if mx_s > 0 else None),
                "gen_tokens": a["tokens"],
                "completed": a["completed"],
                "errors": a["errors"],
                "latency_ms_p50": (round(pq(a["lat_ms"], 0.5), 1)
                                   if a["lat_ms"] else None),
            }
        total_tokens = sum(a["tokens"] for a in agg.values())
        result = {
            "metric": (f"server_mixed_models_agg_tok_s[/v1,{preset},{wfmt}"
                       f",models2,batch{batch}]"),
            "value": round(total_tokens / mx_s, 1) if mx_s > 0 else 0.0,
            "unit": "tok/s",
            "per_model": per_model,
            "models": list(model_names),
            "threads": conc,
            "requests_per_thread": per,
            "max_tokens": max_tokens,
            "decode_chunk": settings.decode_chunk,
            "batch_size": batch,
            "warmup_s": round(warm_s, 1),
            "wall_s": round(mx_s, 1),
            "scheduler_stats": eng.scheduler_stats(),
            "device": str(dev),
        }
        emit_result(result)
        os._exit(0)  # daemon server thread: skip graceful asyncio teardown

    if multiturn and batch > 1:
        # LFKT_BENCH_MULTITURN=1 + LFKT_BENCH_BATCH=C: C concurrent growing
        # conversations through the lane scheduler — the workload the
        # lane-prefix cache exists for (VERDICT r4 #8's "multiturn client
        # mix").  Each follow-up re-sends persona + full history; with
        # LFKT_LANE_PREFIX_CACHE=1 admission finds the freed lane still
        # holding that conversation's KV and prefills only the suffix.
        # Distinct openers keep claims conversation-specific (the shared
        # persona tokens are legitimate cross-conversation reuse).
        followups = [
            "Interesting, tell me more.", "Why is that?", "Go on.",
            "What happened next?", "Could you expand on that?",
        ]
        turns = int(os.environ.get("LFKT_BENCH_TURNS", "4"))
        turn1, follow = [], []
        lk = threading.Lock()

        completed = []
        errors = []

        def convo_worker(cid: int):
            convo = [{"turn": "user",
                      "message": f"Hello bot {cid}! Introduce yourself "
                                 "briefly."}]
            done = 0
            for t in range(turns):
                body = json.dumps({
                    "bot_profile": {
                        "name": "Ada",
                        "appearance": "tall, green eyes, red hair, calm voice",
                        "system_prompt": "You are a concise assistant.",
                    },
                    "user_profile": {"name": "Sam"},
                    "context": convo,
                }).encode()
                try:
                    ms, text, err = stream_ttft(body)
                except Exception as e:  # noqa: BLE001 — transport failure
                    with lk:
                        errors.append(f"{type(e).__name__}: {e}")
                    break
                if err is not None:
                    # conversation outgrew the context (or timed out):
                    # stop HERE — the turns measured so far are valid
                    with lk:
                        errors.append(err)
                    break
                done += 1
                with lk:
                    (turn1 if t == 0 else follow).append(ms)
                convo.append({"turn": "bot", "message": (text or "...")[:400]})
                convo.append({"turn": "user",
                              "message": followups[(cid + t) % len(followups)]})
            with lk:
                completed.append(done)

        names = ("scheduler_lane_prefix_hits",
                 "scheduler_lane_prefix_reused_tokens")
        before = read_metrics_counters(names)
        t_mt = time.perf_counter()
        ths = [threading.Thread(target=convo_worker, args=(c,))
               for c in range(batch)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        mt_s = time.perf_counter() - t_mt
        after = read_metrics_counters(names)
        follow.sort()
        turn1.sort()
        pq = lambda v, q: v[min(len(v) - 1, int(q * len(v)))]  # noqa: E731
        result = {
            "metric": (f"server_ttft_ms_p50[/response,{preset},{wfmt}"
                       f",multiturn,batch{batch}"
                       + (",laneprefix]" if lane_prefix else "]")),
            "value": round(pq(follow, 0.5), 1) if follow else 0.0,
            "unit": "ms",
            "vs_baseline": (round(A10G_TTFT_MS / pq(follow, 0.5), 3)
                            if follow else 0.0),
            "ttft_ms_p95_server": (round(pq(follow, 0.95), 1)
                                   if follow else None),
            "turn1_ttft_ms_p50": round(pq(turn1, 0.5), 1) if turn1 else None,
            "follow_samples": len(follow),
            "decode_chunk": settings.decode_chunk,
            "conversations": batch,
            "turns": turns,
            "turns_completed": sorted(completed),
            "stream_errors": errors[:8],
            "max_tokens": max_tokens,
            "warmup_s": round(warm_s, 1),
            "lane_prefix_cache": lane_prefix,
            "lane_prefix": (
                {k: after[k] - before[k] for k in names}
                if before is not None and after is not None else None),
            "scheduler_stats": eng.scheduler_stats(),
            "wall_s": round(mt_s, 1),
            "device": str(dev),
        }
        emit_result(result)
        os._exit(0)  # daemon server thread: skip graceful asyncio teardown

    if multiturn:
        # LFKT_BENCH_MULTITURN=1: ONE growing conversation — each request
        # re-sends persona + full history + a new user turn, the reference's
        # actual workload shape (api.py:44-63).  Follow-up turns share their
        # whole history prefix with the previous request, so this measures
        # what the serial engine's prompt-prefix KV reuse is for: follow-up
        # TTFT scaling with the NEW turn, not the history.  Serial-engine
        # semantics (one conversation), so the concurrency phase is skipped.
        followups = [
            "Interesting, tell me more.", "Why is that?", "Go on.",
            "What happened next?", "Could you expand on that?",
            "How does that relate?", "Give me an example.",
        ]
        convo = [{"turn": "user",
                  "message": "Hello! Please introduce yourself briefly."}]

        def mt_payload() -> bytes:
            return json.dumps({
                "bot_profile": {
                    "name": "Ada",
                    "appearance": "tall, green eyes, red hair, calm voice",
                    "system_prompt": "You are a concise assistant.",
                },
                "user_profile": {"name": "Sam"},
                "context": convo,
            }).encode()

        first_ttft = None
        follow = []
        # Per-turn reused-token deltas: once the server's reference-parity
        # truncation starts popping the oldest history turn (api.py:54-65),
        # follow-ups stop sharing the resident prefix and silently measure
        # full prefill again.  Reporting reuse PER TURN makes those turns
        # distinguishable in the artifact instead of polluting an
        # aggregate labeled "multiturn reuse" (ADVICE r4 #3).
        per_turn = []

        def reused_total() -> float | None:
            got = read_metrics_counters(("prefix_cache_reused_tokens_total",))
            return None if got is None else got["prefix_cache_reused_tokens_total"]

        mt_errors = []
        for k in range(n_req):
            r_before = reused_total()
            ms, text, err = stream_ttft(mt_payload())
            if err is not None:
                # conversation outgrew the context: stop measuring (later
                # "samples" would be fast error round trips, not TTFT)
                mt_errors.append(err)
                break
            r_after = reused_total()
            per_turn.append({
                "turn": k + 1, "ttft_ms": round(ms, 1),
                "reused_tokens": (int(r_after - r_before)
                                  if r_after is not None and r_before is not None
                                  else None),
            })
            if k == 0:
                first_ttft = ms
            else:
                follow.append(ms)
            convo.append({"turn": "bot", "message": (text or "...")[:400]})
            convo.append({"turn": "user",
                          "message": followups[k % len(followups)]})
        counters = read_metrics_counters(
            ("prefix_cache_hits_total", "prefix_cache_reused_tokens_total"))
        follow.sort()
        pq = lambda v, q: v[min(len(v) - 1, int(q * len(v)))]  # noqa: E731
        result = {
            "metric": (f"server_ttft_ms_p50[/response,{preset},{wfmt}"
                       ",multiturn]"),
            "value": round(pq(follow, 0.5), 1) if follow else 0.0,
            "unit": "ms",
            "vs_baseline": (round(A10G_TTFT_MS / pq(follow, 0.5), 3)
                            if follow else 0.0),
            "ttft_ms_p95_server": (round(pq(follow, 0.95), 1)
                                   if follow else None),
            "turn1_ttft_ms": (round(first_ttft, 1)
                              if first_ttft is not None else None),
            "turns": n_req,
            "turns_measured": len(per_turn),
            "stream_errors": mt_errors,
            "decode_chunk": settings.decode_chunk,
            "max_tokens": max_tokens,
            "warmup_s": round(warm_s, 1),
            "prefix_cache": counters,
            "per_turn": per_turn,
            "device": str(dev),
        }
        emit_result(result)
        return

    lat = []
    for _ in range(n_req):
        t0 = time.perf_counter()
        with urllib.request.urlopen(post("/response"), timeout=600) as r:
            json.loads(r.read())
        lat.append((time.perf_counter() - t0) * 1e3)

    ttft = []
    for _ in range(n_req):
        ms, _text, err = stream_ttft(payload)
        if err is None:     # fixed warmed payload: errors are unexpected —
            ttft.append(ms)  # drop the sample rather than time the error path
        else:
            print(f"bench_server: stream error during TTFT phase: {err}",
                  file=sys.stderr, flush=True)

    # concurrent load (BASELINE config #5: "concurrent /response load ...
    # back-pressure"): fan out parallel POSTs; the server queues up to 5 and
    # 503s beyond (reference api.py:113,158-160 semantics preserved).
    # Service capacity = inflight(batch) + queue(5), so the default
    # concurrency must exceed batch + 5 for the 503 path to actually fire.
    conc = int(os.environ.get("LFKT_BENCH_CONCURRENCY",
                              str(max(8, batch + 8))))
    per = max(2, n_req // 2)
    oks, rejects, errors = [], [], []
    lock = threading.Lock()

    def read_generated_total() -> float | None:
        # server-side counter of usage.completion_tokens per completed
        # request (`/response` strips the usage dict off the wire, so the
        # client can't count; app.py:237-238 records it before stripping)
        got = read_metrics_counters(("generated_tokens_total",))
        return None if got is None else got["generated_tokens_total"]

    def worker(seed: int):
        # closed loop: each thread completes `per` requests, retrying 503s
        # with exponential backoff + jitter (what a real client does), so
        # the phase sustains the advertised concurrency and still counts
        # every 503.  A fixed short backoff instead synchronizes the
        # excess threads into a retry stampede that starves queued
        # requests into 408s at >1.3x overload (observed on-chip).
        import random

        rnd = random.Random(seed)
        done = 0
        attempts = 0
        backoff = 0.1
        while done < per and attempts < per * 200:
            attempts += 1
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(post("/response"), timeout=600) as r:
                    r.read()
                with lock:
                    oks.append((time.perf_counter() - t0) * 1e3)
                done += 1
                backoff = 0.1
            except urllib.error.HTTPError as e:
                with lock:
                    (rejects if e.code == 503 else errors).append(e.code)
                if e.code == 503:
                    time.sleep(backoff * (0.5 + rnd.random()))
                    backoff = min(backoff * 2, 1.6)
                else:
                    done += 1   # non-503 failure: don't retry forever
            except Exception as e:  # noqa: BLE001 — connection-level failure:
                with lock:          # count it, keep the sample sizes honest
                    errors.append(type(e).__name__)
                done += 1

    gen_before = read_generated_total()
    t_conc = time.perf_counter()
    ths = [threading.Thread(target=worker, args=(i,)) for i in range(conc)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    conc_s = time.perf_counter() - t_conc
    gen_after = read_generated_total()
    gen_total = (gen_after - gen_before
                 if gen_after is not None and gen_before is not None else None)

    lat.sort(); ttft.sort(); oks.sort()
    p = lambda v, q: v[min(len(v) - 1, int(q * len(v)))]  # noqa: E731
    result = {
        "metric": (f"server_ttft_ms_p50[/response,{preset},{wfmt}"
                   + (",fullctx" if fullctx else "")
                   + (",laneprefix" if lane_prefix and batch > 1 else "")
                   + (",admstatic" if batch > 1
                      and not settings.adm_controller else "")
                   + (f",chunk{settings.decode_chunk}"
                      if settings.decode_chunk != Settings.decode_chunk
                      else "")
                   + (f",batch{batch}]" if batch > 1 else "]")),
        "value": round(p(ttft, 0.5), 1),
        "unit": "ms",
        "vs_baseline": round(A10G_TTFT_MS / max(p(ttft, 0.5), 1e-9), 3),
        "ttft_ms_p95_server": round(p(ttft, 0.95), 1),
        "latency_ms_p50": round(p(lat, 0.5), 1),
        "latency_ms_p95": round(p(lat, 0.95), 1),
        "decode_chunk": settings.decode_chunk,
        "max_tokens": max_tokens,
        "n_requests": n_req,
        "warmup_s": round(warm_s, 1),
        "concurrent": {
            "threads": conc, "completed": len(oks), "rejected_503": len(rejects),
            "other_errors": len(errors),
            "latency_ms_p95": round(p(oks, 0.95), 1) if oks else None,
            "req_per_sec": round(len(oks) / conc_s, 2) if conc_s > 0 else None,
            # aggregate decode throughput under load, from the server's
            # generated_tokens_total counter delta (random logits CAN
            # sample a stop token early, so len(oks)*max_tokens would
            # overcount; the usage dict never crosses the /response wire)
            "agg_tok_s": (round(gen_total / conc_s, 1)
                          if conc_s > 0 and gen_total is not None else None),
            "gen_tokens_total": (int(gen_total)
                                 if gen_total is not None else None),
        },
        "batch_size": batch,
        "device": str(dev),
    }
    if batch > 1:
        # admission-controller telemetry for the prefill-heavy agg A/B:
        # live budget + EMAs say WHY an arm's agg_tok_s moved
        result["scheduler_stats"] = eng.scheduler_stats()
        result["adm_controller"] = settings.adm_controller
    emit_result(result)
    os._exit(0)  # daemon server thread: skip graceful asyncio teardown


if __name__ == "__main__":
    main()
