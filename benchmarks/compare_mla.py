#!/usr/bin/env python3
"""The program against the plain reference of the ``deepseek2`` block, at the
configuration's published widths, outside any timed window, on what the two
cells time.

    python3 benchmarks/compare_mla.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) two
requests of seeded words go through the ENGINES the cells time:

- ``agent``: a system line of 8192 words + a turn that brings the prompt to
  8576 tokens, 160 decoded (``gigachat.agent-16sat``'s medians), in
  ``ContinuousEngine`` beside 15 other live lanes: first every lane serves
  one request with the same system line, all at once, and is freed (its
  claim holds the line), then 15 fillers and the request are admitted, each
  through a LANE-CLAIM HIT (the lane's latent rows copied into the scratch
  cache, two slices against 8192 cached latents, ``lane_write``), and decode
  together: 16 lanes x 8.6k latents a step in the absorbed form, the held
  experts their rows picked, the shared expert;
- ``long``: a prompt of three quarters of ``n_ctx`` (12288) and 48 decoded
  (``gigachat.longdoc-1``'s band), alone on the engine: 48 slices each
  against a growing ring of latents, then steps with fifteen lanes dead.

Both once more through the serial ``Engine`` (explicit seeds: full
prefills).  The engines sample what they sample; the reference
(``reference_mla.py``: float32 at ``highest``, expanded keys and values, the
same share of experts) then runs on each request's prompt and the tokens
the engine fed, a layer at a time while it is dequantized, ON THE PROGRAM'S
PICKS (so that both sum the same experts).

The logits and the picks are read by a tap: the name ``forward`` in
``models/generate.py`` and ``parallel/batched.py`` is wrapped so that the
head is applied to every position, the routers hand out their picks, and
both reach the host through ``jax.pure_callback``; the engines get what
they asked for.  The programs are otherwise the served ones: the same jits,
operands and caches.

Three processes, each with the device to itself (the parent never imports
JAX): ``--phase lanes``, ``--phase serial``, ``--phase reference`` (which
also gives the verdict).

What is held (PERF.md section 6 has the readings each limit stands between):

``LIMIT`` on ``|got - want| / |want|`` (Frobenius over the vocabulary) over
each block of compared positions of each request on each engine (a
prompt's last 64 positions, the 64 positions after a claimed prefix, the
decode steps).  Below it: the engines (bf16 inputs to every product, a bf16
stream and cache, the absorbed form) and the reference with every matmul
and attention input rounded to bfloat16.  Above it: the reference with
those inputs rounded to float8_e4m3fn (the precision below the one the
configuration states), and the reference without the shared expert.

``ROUTER`` on the router's arithmetic at GIVEN inputs: the normed hidden
states the reference itself saw at every routed layer of the ``agent``
request's compared positions, rounded to bfloat16 as the program's stream
is, through the program's ``route_grouped`` on the file's own router and
bias, against the reference's float32 router on the same values: the share
of rows whose SET of picked experts differs.  Below it: the program
(float32 sums in another order).  Above it: the reference's router with
its inputs, weights and scores rounded to bfloat16 (a bf16 router), and
with ``exp_probs_b`` dropped.  (On the engines' own streams the picks also
carry what bf16 layers before them left: the share of rows that differ
from the reference's own is printed, not held.)

Exit 0 iff every reading that is held is on the right side; the last line
says so.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import run as bench                  # noqa: E402
from compare_eva import (engine_kwargs, find_config,   # noqa: E402
                         rel, say)

# PERF.md section 6 (my chip runs, PR 43) has every reading these stand
# between.
LIMIT = 0.12
ROUTER = 0.002
TAIL = 64
SYSTEM, AGENT, LONG_OUT = 8192, (8576, 160), 48
# (every filler's prompt is LONGER than the agent request's; the tap tells
# a lane's request by the position of its first step, and leaves out a lane
# that reaches that position while it decodes: Tap._lane_step)
FILLER_TURNS = tuple(range(416, 416 + 64 * 15, 64))
FIRST_OUT = 700      # the first round's requests: all lanes live at once


def plan_of(cfg_doc: dict, seed: int) -> dict:
    """The requests: (name, prompt tokens, decoded tokens), smaller where
    the file's ring is (the CPU rehearsal)."""
    n_ctx = int(cfg_doc["serve"]["n_ctx"])
    big = n_ctx >= 4096
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    return {"seed": seed, "n_ctx": n_ctx, "lanes": lanes,
            "system": SYSTEM if big else 256,
            "requests": [("agent",) + (AGENT if big else (330, 24)),
                         ("long", n_ctx * 3 // 4, LONG_OUT if big else 16)],
            "first_out": FIRST_OUT if big else 12 * lanes,
            "fillers": [(t if big else 80 + 8 * i, 400 if big else 40)
                        for i, t in enumerate(FILLER_TURNS[:lanes - 1])]}


def kept(name: str, n_prompt: int, n_out: int, claimed: int) -> dict:
    """The compared positions of a request."""
    out = {"prefill_tail": range(max(n_prompt - TAIL, claimed), n_prompt),
           "decode": range(n_prompt, n_prompt + n_out)}
    if claimed and claimed + TAIL < n_prompt - TAIL:
        out["after_claim"] = range(claimed, claimed + TAIL)
    return out


# ---------------------------------------------------------------------------
# the tap
# ---------------------------------------------------------------------------

class Tap:
    """Every call the engines' programs make of ``forward``, seen from the
    host: per watched request the tokens fed past its prompt, the logits at
    the compared positions and the routers' picks at every position the
    programs computed (a claimed prefix is computed by nobody)."""

    def __init__(self):
        self.prompts, self.want = [], []
        self.fed, self.got, self.picks = [], [], []
        self.current = None
        self.owner = {}
        self.last = {}
        self.alive_steps = {}

    def watch(self, ids, positions):
        self.prompts.append(np.asarray(ids, np.int32))
        self.want.append(set(positions))
        for store in (self.fed, self.got, self.picks):
            store.append({})
        return len(self.prompts) - 1

    def install(self):
        import jax
        import jax.numpy as jnp

        from llama_fastapi_k8s_gpu_tpu.models import generate, llama
        from llama_fastapi_k8s_gpu_tpu.parallel import batched

        real = llama.forward

        def tapped(params, cfg, tokens, pos, cache, last_idx=None,
                   live=None, with_stats=False, **kw):
            S = tokens.shape[0]
            logits, cache, stats, picks = real(
                params, cfg, tokens, pos, cache, last_idx=last_idx,
                live=live, return_all=True, with_stats=True,
                with_picks=True, **kw)
            alive = jnp.bool_(True) if live is None else live
            zero = jax.pure_callback(
                self._see, jax.ShapeDtypeStruct((), jnp.float32),
                tokens, pos, logits, picks, alive,
                vmap_method="broadcast_all")
            idx = S - 1 if last_idx is None else last_idx
            row = jax.lax.dynamic_index_in_dim(logits, idx, keepdims=False)
            return (row + zero, cache) + ((stats,) if with_stats else ())

        generate.forward = batched.forward = tapped

    def _see(self, tokens, pos, logits, picks, alive):
        tokens, pos = np.asarray(tokens), np.asarray(pos)
        alive = np.asarray(alive)
        if pos.ndim and tokens.shape[1] == 1:          # lanes of one step
            k = int(alive.sum())
            self.alive_steps[k] = self.alive_steps.get(k, 0) + 1
            for lane in range(pos.shape[0]):
                self._lane_step(lane, int(tokens[lane, 0]), int(pos[lane]),
                                logits[lane, 0], picks[lane][:, 0],
                                bool(alive[lane]))
        elif tokens.shape[0] > 1:
            self._slice(tokens, int(pos), logits, np.asarray(picks))
        elif self.current is not None:
            self._step(self.current, int(tokens[0]), int(pos), logits[0],
                       picks[:, 0])
        return np.zeros(pos.shape, np.float32)

    def _slice(self, tokens, off, logits, picks):
        first = [] if self.current is None else [self.current]
        for j in first + list(range(len(self.prompts))):
            ids = self.prompts[j]
            m = min(len(tokens), len(ids) - off)
            if m > 0 and np.array_equal(ids[off:off + m], tokens[:m]) \
                    and off not in self.picks[j]:
                for p in range(off, off + m):
                    self._step(j, int(ids[p]), p, logits[p - off],
                               picks[:, p - off])
                return

    def _step(self, j, token, pos, logits, picks):
        self.fed[j][pos] = token
        self.picks[j][pos] = np.asarray(picks)
        if pos in self.want[j]:
            self.got[j][pos] = np.asarray(logits, np.float32)

    def _lane_step(self, lane, token, pos, logits, picks, alive):
        if not alive:
            self.owner.pop(lane, None)
            self.last.pop(lane, None)
            return
        # (a lane that stepped at pos - 1 is DECODING through pos: another
        # request's answer passing a watched prompt's length is no start)
        decoding = self.last.get(lane) == pos - 1
        self.last[lane] = pos
        j, want = self.owner.get(lane, (None, None))
        if want != pos:         # a request's first step on this lane
            starts = [] if decoding else [
                i for i, ids in enumerate(self.prompts)
                if len(ids) == pos and pos not in self.fed[i]]
            if not starts:
                self.owner.pop(lane, None)
                return
            j = starts[0]
        self._step(j, token, pos, logits, picks)
        self.owner[lane] = (j, pos + 1)

    def save(self, path: str, names: list, extra: dict):
        out = dict(extra)
        for j, name in enumerate(names):
            seq, p = list(self.prompts[j]), len(self.prompts[j])
            while p in self.fed[j]:
                seq.append(self.fed[j][p])
                p += 1
            at = sorted(q for q in self.got[j] if q < len(seq))
            have = sorted(q for q in self.picks[j] if q < len(seq))
            out[f"seq_{name}"] = np.asarray(seq, np.int32)
            out[f"pos_{name}"] = np.asarray(at, np.int32)
            out[f"logits_{name}"] = np.stack([self.got[j][q] for q in at])
            out[f"picked_at_{name}"] = np.asarray(have, np.int32)
            out[f"picks_{name}"] = np.stack(
                [self.picks[j][q] for q in have], axis=1)
        np.savez(path, **out)


# ---------------------------------------------------------------------------
# the program's phases
# ---------------------------------------------------------------------------

def messages_of(system: str, text: str) -> list:
    return [{"role": "system", "content": system},
            {"role": "user", "content": text}]


def system_line(cfg_doc, n_words: int) -> str:
    from ggufgen import vocab_of

    word = vocab_of(cfg_doc).word
    return " ".join(word(i * 389) for i in range(n_words))


def words_for(eng, cfg_doc, system: str, n_tokens: int, seed: int):
    """(text, ids) of a turn that brings the chat prompt to exactly
    ``n_tokens`` tokens, system line and chat template and all."""
    import random

    from ggufgen import vocab_of

    word = vocab_of(cfg_doc).word
    rng = random.Random(seed)
    words = [word(rng.randrange(26 ** 3)) for _ in range(n_tokens)]
    n = max(n_tokens - len(system.split()) - 8, 1)
    for _ in range(8):
        ids = eng.tokenize_messages(messages_of(system, " ".join(words[:n])))
        if len(ids) == n_tokens:
            return " ".join(words[:n]), ids
        n -= len(ids) - n_tokens
    raise SystemExit(f"no prompt of {n_tokens} tokens: {len(ids)} at {n} words")


def note_loaded(eng, t0):
    import jax

    from llama_fastapi_k8s_gpu_tpu.models.params import flat_layers

    say(note="loaded", engine=type(eng).__name__,
        platform=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        attn_impl=eng.cfg.attn_impl, load_s=round(time.time() - t0, 1),
        weight_formats={k: sorted(v) for k, v in
                        flat_layers(eng.params["layers"])
                        if isinstance(v, dict)},
        output=sorted(eng.params["output"]), cache=eng.cache_kind)


def watch_all(tap, eng, cfg_doc, plan, claimed: int):
    """{name: (system line, text, tokens to decode)} of the two requests,
    each watched by the tap at its compared positions."""
    texts = {}
    for j, (name, n_prompt, n_out) in enumerate(plan["requests"]):
        system = system_line(cfg_doc, plan["system"] if name == "agent"
                             else 16)
        text, ids = words_for(eng, cfg_doc, system, n_prompt,
                              plan["seed"] + j)
        texts[name] = (system, text, n_out)
        tap.watch(ids, {p for r in kept(
            name, n_prompt, n_out, claimed if name == "agent" else 0
            ).values() for p in r})
    return texts


def phase_lanes(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    tap = Tap()
    tap.install()
    t0 = time.time()
    lanes = plan["lanes"]
    kw = engine_kwargs(cfg_doc)
    eng = ContinuousEngine(path, batch_size=lanes, **kw)
    note_loaded(eng, t0)
    chunk = kw["prefill_chunk"]
    claimed = plan["system"] // chunk * chunk
    texts = watch_all(tap, eng, cfg_doc, plan, claimed)
    system = texts["agent"][0]
    t0 = time.time()

    def turn(n_words, seed):
        return words_for(eng, cfg_doc, system,
                         plan["system"] + 20 + n_words, seed)[0]

    # every lane serves the system line once and is freed: its claim holds
    # it (long enough answers that all lanes are live at once: a request
    # that ended before the next was admitted would hand it its own lane)
    first = [eng.submit(messages_of(system, turn(24 + i, plan["seed"] + 200 + i)),
                        max_tokens=plan["first_out"]) for i in range(lanes)]
    for f in first:
        f.result()
    before = dict(eng.scheduler_stats())
    fill = [eng.submit(messages_of(system, turn(n, plan["seed"] + 100 + i)),
                       max_tokens=out)
            for i, (n, out) in enumerate(plan["fillers"])]
    _, text, n_out = texts["agent"]
    agent = eng.submit(messages_of(system, text), max_tokens=n_out + 1)
    for f in fill + [agent]:
        f.result()
    after = dict(eng.scheduler_stats())
    hits = after["lane_prefix_hits"] - before["lane_prefix_hits"]
    system2, text, n_out = texts["long"]
    eng.submit(messages_of(system2, text), max_tokens=n_out + 1,
               seed=plan["seed"] + 1).result()
    jax.effects_barrier()
    snap = eng.expert_counters.snapshot(block=True)
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())},
        claim_hits=hits, admitted=len(fill) + 1,
        reused_tokens=after["lane_prefix_reused_tokens"]
        - before["lane_prefix_reused_tokens"],
        counters=eng.cache_read_gauges(),
        picks_held=snap["picks_held"], picks_total=snap["picks_total"],
        experts_read_per_layer_step=snap["experts_read"]
        / max(snap["layer_steps"], 1))
    eng.shutdown()
    tap.save(os.path.join(work, "lanes.npz"), list(texts),
             {"claim_hits": hits, "admitted": len(fill) + 1})
    return 0


def phase_serial(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    tap = Tap()
    tap.install()
    t0 = time.time()
    eng = Engine(path, **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    texts = watch_all(tap, eng, cfg_doc, plan, 0)
    t0 = time.time()
    for j, name in reversed(list(enumerate(texts))):
        system, text, n_out = texts[name]
        tap.current = j
        eng.create_chat_completion(messages_of(system, text),
                                   max_tokens=n_out + 1,
                                   seed=plan["seed"] + j)
        jax.effects_barrier()
    say(note="serial engine done", seconds=round(time.time() - t0, 1))
    tap.save(os.path.join(work, "serial.npz"), list(texts), {})
    return 0


# ---------------------------------------------------------------------------
# the reference, and the verdict
# ---------------------------------------------------------------------------

def rows_that_differ(mine, theirs) -> float:
    """Share of rows whose SET of picked experts differs."""
    return float(np.mean(np.any(np.sort(mine, -1) != np.sort(theirs, -1), -1)))


def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str,
                    platform: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_mla as ref

    t0 = time.time()
    hp, tensors = ref.open_model(path)
    chunk = engine_kwargs(cfg_doc)["prefill_chunk"]
    claimed = plan["system"] // chunk * chunk
    runs = {}
    for engine in ("lanes", "serial"):
        p = os.path.join(work, engine + ".npz")
        if not os.path.exists(p):
            continue
        doc = np.load(p)
        for name, n_prompt, n_out in plan["requests"]:
            seq = doc[f"seq_{name}"]
            have = doc[f"picked_at_{name}"]
            runs[f"{engine}.{name}"] = {
                "seq": seq, "pos": doc[f"pos_{name}"],
                "logits": doc[f"logits_{name}"], "have": have,
                "picks": doc[f"picks_{name}"], "n_prompt": n_prompt,
                "n_out": n_out,
                # (the tap also sees the claimed rows: other requests'
                # slices computed them, on the same tokens)
                "claimed": claimed if (engine, name) == ("lanes", "agent")
                else 0}
        if engine == "lanes":
            say(note="lanes", claim_hits=int(doc["claim_hits"]),
                admitted=int(doc["admitted"]))
    calibrated = next(iter(runs))          # lanes.agent where lanes ran
    f8 = jnp.float8_e4m3fn
    variants = {"bfloat16": dict(emulate=jnp.bfloat16),
                "float8": dict(emulate=f8), "no_shared": dict(no_shared=True)}
    n_moe = hp["n_layers"] - hp["n_dense"]
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))
        xs = {k: emb[jnp.asarray(r["seq"])] for k, r in runs.items()}
        cal = {v: xs[calibrated] for v in variants}
        del emb
        own = {k: [] for k in runs}
        given = {"program": [], "bfloat16_router": [], "no_bias": []}
        for i in range(hp["n_layers"]):
            w = ref.layer_weights(tensors, i)
            j = i - hp["n_dense"]
            for k, r in runs.items():
                use = None
                if j >= 0:
                    # the program's picks where it computed them; the
                    # reference's own over a claimed prefix, which nobody
                    # computed in this request (rows of the lane's claim)
                    _, scores, mine = ref.layer(hp, w, xs[k], i)
                    use = np.asarray(mine).copy()
                    use[r["have"]] = r["picks"][j]
                    own[k].append(np.asarray(mine))
                if k == calibrated and j >= 0:
                    given_inputs(ref, hp, w, xs[k], r, given)
                xs[k] = ref.layer(hp, w, xs[k], i, use_picks=use)[0]
                if k == calibrated:
                    for v, kw in variants.items():
                        cal[v] = ref.layer(hp, w, cal[v], i, use_picks=use,
                                           **kw)[0]
            say(note="layer", layer=i, seconds=round(time.time() - t0, 1))
            del w
        want = {k: np.asarray(ref.head(hp, tensors, xs[k][r["pos"]]))
                for k, r in runs.items()}
        at = runs[calibrated]["pos"]
        cal = {v: np.asarray(ref.head(hp, tensors, x[at],
                                      variants[v].get("emulate")))
               for v, x in cal.items()}
    ok = True
    for k, r in runs.items():
        name = k.split(".")[1]
        for block, rng_ in kept(name, r["n_prompt"], r["n_out"],
                                r["claimed"]).items():
            sel = np.isin(r["pos"], np.asarray(list(rng_)))
            if not sel.any():
                continue
            d = rel(r["logits"][sel], want[k][sel])
            ok &= d < LIMIT
            # (the largest single position beside it: a reading carried by
            # a few steps and one carried by all are different faults)
            worst = max(rel(r["logits"][i:i + 1], want[k][i:i + 1])
                        for i in np.flatnonzero(sel))
            say(held="LIMIT", on=k, block=block, positions=int(sel.sum()),
                reading=d, limit=LIMIT, ok=bool(d < LIMIT),
                largest_position=worst)
        theirs = np.stack(own[k])[:, r["have"]]
        say(printed="rows whose picks differ from the reference's own "
                    "(the engine's stream carries bf16 layers before)",
            on=k, reading=rows_that_differ(r["picks"], theirs),
            claimed=r["claimed"])
    for v, must in (("bfloat16", "pass"), ("float8", "fail"),
                    ("no_shared", "fail")):
        d = rel(cal[v], want[calibrated])
        good = d < LIMIT if must == "pass" else d > LIMIT
        ok &= good
        say(held="LIMIT", control=v, on=calibrated, reading=d, limit=LIMIT,
            must=must, ok=bool(good))
    for v, must in (("program", "pass"), ("bfloat16_router", "fail"),
                    ("no_bias", "fail")):
        share = float(np.mean(given[v]))
        good = share < ROUTER if must == "pass" else share > ROUTER
        ok &= good
        say(held="ROUTER", control=v, on="given inputs", layers=n_moe,
            reading=share, limit=ROUTER, must=must, ok=bool(good))
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def given_inputs(ref, hp, w, x, r, given):
    """The router at GIVEN inputs: the reference's own normed hidden states
    at this layer's compared positions, rounded to bfloat16 as the
    program's stream is, through the program's ``route_grouped`` and through
    the reference's router (float32; with a bfloat16 router; without the
    bias).  Appends each one's share of rows that differ from the float32
    reference's."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.mla import route_grouped

    cfg = ModelConfig(
        vocab_size=8, dim=x.shape[1], n_layers=1, n_heads=1, n_kv_heads=1,
        ffn_dim=8, n_ctx=8, kv_lora_rank=hp["r_kv"],
        n_experts=hp["n_experts"], n_experts_used=hp["n_used"],
        norm_topk_prob=hp["norm_w"],
        expert_gating="sigmoid" if hp["gating"] == 2 else "softmax",
        n_expert_groups=hp["n_groups"], n_groups_used=hp["groups_used"],
        expert_weights_scale=hp["scale"])
    xa = ref.attention(hp, w, x)[r["pos"]]
    u = ref.norm(xa, w["ffn_norm"], hp["eps"]
                 ).astype(jnp.bfloat16).astype(jnp.float32)
    _, want = ref.router(hp, w, u)
    want = np.asarray(want)
    mine, _ = route_grouped(u.astype(jnp.bfloat16),
                            jnp.asarray(w["ffn_gate_inp"]),
                            jnp.asarray(w["exp_probs_b"]), cfg)
    given["program"].append(rows_that_differ(np.asarray(mine), want))
    given["bfloat16_router"].append(rows_that_differ(np.asarray(
        ref.router(hp, w, u, router_dtype=jnp.bfloat16)[1]), want))
    given["no_bias"].append(rows_that_differ(np.asarray(
        ref.router(hp, w, u, no_bias=True)[1]), want))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gigachat3.1-702b-a36b-q4km-ep8-16lane")
    ap.add_argument("--seed", type=int, default=43)
    ap.add_argument("--phase", choices=("lanes", "serial", "reference"))
    ap.add_argument("--work")
    ap.add_argument("--only", default="lanes,serial",
                    help="the engines to run, comma-separated")
    ap.add_argument("--reference-on", default=None,
                    help="platform of the reference (default: the first)")
    args = ap.parse_args()
    cfg_doc = find_config(args.config)
    plan = plan_of(cfg_doc, args.seed)
    if args.phase:
        path = bench.ensure_gguf(cfg_doc)
        if args.phase == "reference":
            import jax

            return reference_phase(
                cfg_doc, path, plan, args.work,
                args.reference_on or jax.default_backend())
        return {"lanes": phase_lanes, "serial": phase_serial}[args.phase](
            cfg_doc, path, plan, args.work)
    work = args.work or os.path.join(bench.CACHE, f"compare_mla_{args.seed}")
    os.makedirs(work, exist_ok=True)
    bench.ensure_gguf(cfg_doc)
    env = dict(os.environ)
    if cfg_doc.get("platform") == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    rc = 0
    for phase in [p for p in args.only.split(",") if p] + ["reference"]:
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--seed", str(args.seed), "--phase", phase,
               "--work", work]
        if args.reference_on:
            cmd += ["--reference-on", args.reference_on]
        rc = subprocess.run(cmd, env=env).returncode
        if rc and phase != "reference":
            say(ok=False, phase=phase, rc=rc)
            return rc
    return rc


if __name__ == "__main__":
    sys.exit(main())
