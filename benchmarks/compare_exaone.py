#!/usr/bin/env python3
"""The program against the plain reference of the ``exaone-moe`` block, at the
configuration's published widths, outside any timed window, on what the two
cells time.

    python3 benchmarks/compare_exaone.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) two
requests of seeded words go through the ENGINES the cells time:

- ``long``: a prompt of three quarters of ``n_ctx`` (12288) and 48 decoded
  (``kexaone.longdoc-1``'s band), alone on the engine: wide and narrow
  slices, each wrapping the window leaves (a slice of 1024 rows is eight
  times a leaf) and growing the global rings, then steps at context 12k
  with fifteen lanes dead;
- ``chat``: a prompt of 368 tokens and 104 decoded (``kexaone.chat-16sat``'s
  medians) in ``ContinuousEngine`` beside 15 other live lanes of chat
  lengths (prompts 136-696), all admitted at once and decoding beside each
  other: every lane past position 128, so every window leaf wrapped, 9 of
  12 decode-kernel calls a step on them.

Both once more through the serial ``Engine``.  The engines sample what they
sample; the reference (``reference_exaone.py``: float32 at ``highest``, the
whole sequence at once, no cache, the same share of experts) then runs on
each request's prompt and the tokens the engine fed, a layer at a time while
it is dequantized, ON THE PROGRAM'S PICKS (so that both sum the same
experts).  The logits and picks are read by ``compare_mla.py``'s tap on
``forward``; the programs are otherwise the served ones.

Three processes, each with the device to itself (the parent never imports
JAX): ``--phase lanes``, ``--phase serial``, ``--phase reference`` (which
also gives the verdict).

What is held (PERF.md section 6 has the readings each limit stands between):

``LIMIT`` on ``|got - want| / |want|`` (Frobenius over the vocabulary) over
each block of compared positions of each request on each engine (a
prompt's last 64 positions, the decode steps).  Below it: the engines (bf16
inputs to every product, a bf16 stream and cache) and the reference with
every matmul and attention input rounded to bfloat16.  Above it: the
reference with those inputs rounded to float8_e4m3fn (the precision below
the one the configuration states), with full attention in the sliding
layers, with rotation in the full layers, and without the shared expert
(each on the ``chat`` request of the first engine that ran: 472 positions,
which cross the window three times).

``ROUTER`` on the router's arithmetic at GIVEN inputs: the normed hidden
states the reference itself saw at every routed layer of the calibrated
request's compared positions, rounded to bfloat16 as the program's stream
is, through the program's ``route_grouped`` on the file's own router and
bias, against the reference's float32 router on the same values: the share
of rows whose SET of picked experts differs.  Below it: the program.  Above
it: the reference's router with its inputs, weights and scores rounded to
bfloat16 (a bf16 router).

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
from compare_eva import engine_kwargs, find_config, rel, say   # noqa: E402
from compare_mla import (            # noqa: E402
    Tap, messages_of, note_loaded, rows_that_differ, system_line, words_for)

# PERF.md section 6 (my chip runs, PR 45) has every reading these stand
# between.  LIMIT: the engines read 0.026-0.035 over two seeds (the
# bfloat16 reference 0.012-0.013); the controls 0.099 (rotation in the full
# layers: 3 of 12 layers, each averaging over hundreds of keys), 0.23
# (float8), 0.59 (no shared expert), 1.06 (no window) on the chat request.
LIMIT = 0.06
ROUTER = 0.002
TAIL = 64
CHAT, LONG_OUT = (368, 104), 48
# (no filler's prompt is as long as the chat request's: the tap tells a
# lane's request by the position of its first step)
FILLERS = tuple(range(136, 136 + 40 * 15, 40))


def plan_of(cfg_doc: dict, seed: int) -> dict:
    """The requests: (name, prompt tokens, decoded tokens), smaller where
    the file's ring is (the CPU rehearsal)."""
    n_ctx = int(cfg_doc["serve"]["n_ctx"])
    big = n_ctx >= 4096
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    return {"seed": seed, "n_ctx": n_ctx, "lanes": lanes,
            "requests": [("long", n_ctx * 3 // 4, LONG_OUT if big else 16),
                         ("chat",) + (CHAT if big else (150, 40))],
            # (answers long enough that the first filler still decodes when
            # the request, admitted last, ends: every lane live beside it)
            "fillers": [(t if big else 100 + 12 * i, 400 if big else 64)
                        for i, t in enumerate(FILLERS[:lanes - 1])]}


def kept(n_prompt: int, n_out: int) -> dict:
    """The compared positions of a request."""
    return {"prefill_tail": range(max(n_prompt - TAIL, 0), n_prompt),
            "decode": range(n_prompt, n_prompt + n_out)}


def watch_all(tap, eng, cfg_doc, plan):
    """{name: (system line, text, tokens to decode)} of the two requests,
    each watched by the tap at its compared positions."""
    texts = {}
    system = system_line(cfg_doc, 16)
    for j, (name, n_prompt, n_out) in enumerate(plan["requests"]):
        text, ids = words_for(eng, cfg_doc, system, n_prompt,
                              plan["seed"] + j)
        texts[name] = (system, text, n_out)
        tap.watch(ids, {p for r in kept(n_prompt, n_out).values()
                        for p in r})
    return texts


def phase_lanes(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    tap = Tap()
    tap.install()
    t0 = time.time()
    kw = engine_kwargs(cfg_doc)
    eng = ContinuousEngine(path, batch_size=plan["lanes"], **kw)
    note_loaded(eng, t0)
    texts = watch_all(tap, eng, cfg_doc, plan)
    t0 = time.time()
    system, text, n_out = texts["long"]
    eng.submit(messages_of(system, text), max_tokens=n_out + 1,
               seed=plan["seed"]).result()
    # the chat request beside 15 fillers, all at once: every lane live
    fill = [eng.submit(messages_of(system, words_for(
        eng, cfg_doc, system, n, plan["seed"] + 100 + i)[0]), max_tokens=out)
        for i, (n, out) in enumerate(plan["fillers"])]
    system, text, n_out = texts["chat"]
    chat = eng.submit(messages_of(system, text), max_tokens=n_out + 1)
    for f in fill + [chat]:
        f.result()
    jax.effects_barrier()
    snap = eng.expert_counters.snapshot(block=True)
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())},
        counters=eng.cache_read_gauges(),
        picks_held=snap["picks_held"], picks_total=snap["picks_total"],
        experts_read_per_layer_step=snap["experts_read"]
        / max(snap["layer_steps"], 1))
    eng.shutdown()
    tap.save(os.path.join(work, "lanes.npz"), list(texts), {})
    return 0


def phase_serial(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    tap = Tap()
    tap.install()
    t0 = time.time()
    eng = Engine(path, **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    texts = watch_all(tap, eng, cfg_doc, plan)
    t0 = time.time()
    for j, name in enumerate(texts):
        system, text, n_out = texts[name]
        tap.current = j
        eng.create_chat_completion(messages_of(system, text),
                                   max_tokens=n_out + 1,
                                   seed=plan["seed"] + j)
        jax.effects_barrier()
    say(note="serial engine done", seconds=round(time.time() - t0, 1),
        counters=eng.cache_read_gauges())
    tap.save(os.path.join(work, "serial.npz"), list(texts), {})
    return 0


# ---------------------------------------------------------------------------
# the reference, and the verdict
# ---------------------------------------------------------------------------

def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_exaone as ref

    t0 = time.time()
    hp, tensors = ref.open_model(path)
    runs = {}
    for engine in ("lanes", "serial"):
        p = os.path.join(work, engine + ".npz")
        if not os.path.exists(p):
            continue
        doc = np.load(p)
        for name, n_prompt, n_out in plan["requests"]:
            runs[f"{engine}.{name}"] = {
                "seq": doc[f"seq_{name}"], "pos": doc[f"pos_{name}"],
                "logits": doc[f"logits_{name}"],
                "have": doc[f"picked_at_{name}"],
                "picks": doc[f"picks_{name}"], "n_prompt": n_prompt,
                "n_out": n_out}
    # the controls run on a chat request (472 positions cross the window
    # three times; a control costs one more pass over its sequence)
    calibrated = next(k for k in runs if k.endswith(".chat"))
    variants = {"bfloat16": dict(emulate=jnp.bfloat16),
                "float8": dict(emulate=jnp.float8_e4m3fn),
                "no_window": dict(no_window=True),
                "rope_all": dict(rope_all=True),
                "no_shared": dict(no_shared=True)}
    n_moe = hp["n_layers"] - hp["n_dense"]
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))
        xs = {k: emb[jnp.asarray(r["seq"])] for k, r in runs.items()}
        cal = {v: xs[calibrated] for v in variants}
        del emb
        own = {k: [] for k in runs}
        given = {"program": [], "bfloat16_router": []}
        for i in range(hp["n_layers"]):
            w = ref.layer_weights(tensors, i)
            j = i - hp["n_dense"]
            for k, r in runs.items():
                use = None
                if j >= 0 and len(r["have"]) == len(r["seq"]):
                    use = r["picks"][j]       # the program's, everywhere
                elif j >= 0:
                    # the reference's own where the tap saw none (a prompt
                    # that went through the one-program prefill)
                    use = np.asarray(ref.layer(hp, w, xs[k], i)[2]).copy()
                    use[r["have"]] = r["picks"][j]
                if k == calibrated and j >= 0:
                    given_inputs(ref, hp, w, xs[k], i, r, given)
                xs[k], _, mine = ref.layer(hp, w, xs[k], i, use_picks=use)
                if j >= 0:
                    own[k].append(np.asarray(mine))
                if k == calibrated:
                    for v, kw in variants.items():
                        cal[v] = ref.layer(hp, w, cal[v], i, use_picks=use,
                                           **kw)[0]
            say(note="layer", layer=i, kind=ref.kind_of(hp, i),
                seconds=round(time.time() - t0, 1))
            del w
        want = {k: np.asarray(ref.head(hp, tensors, xs[k][r["pos"]]))
                for k, r in runs.items()}
        at = runs[calibrated]["pos"]
        cal = {v: np.asarray(ref.head(hp, tensors, x[at],
                                      variants[v].get("emulate")))
               for v, x in cal.items()}
    ok = True
    for k, r in runs.items():
        for block, rng_ in kept(r["n_prompt"], r["n_out"]).items():
            sel = np.isin(r["pos"], np.asarray(list(rng_)))
            if not sel.any():
                continue
            d = rel(r["logits"][sel], want[k][sel])
            ok &= d < LIMIT
            worst = max(rel(r["logits"][i:i + 1], want[k][i:i + 1])
                        for i in np.flatnonzero(sel))
            say(held="LIMIT", on=k, block=block, positions=int(sel.sum()),
                reading=d, limit=LIMIT, ok=bool(d < LIMIT),
                largest_position=worst)
        theirs = np.stack(own[k])[:, r["have"]]
        say(printed="rows whose picks differ from the reference's own "
                    "(the engine's stream carries bf16 layers before)",
            on=k, reading=rows_that_differ(r["picks"], theirs))
    for v in variants:
        must = "pass" if v == "bfloat16" else "fail"
        d = rel(cal[v], want[calibrated])
        good = d < LIMIT if must == "pass" else d > LIMIT
        ok &= good
        say(held="LIMIT", control=v, on=calibrated, reading=d, limit=LIMIT,
            must=must, ok=bool(good))
    for v, must in (("program", "pass"), ("bfloat16_router", "fail")):
        share = float(np.mean(given[v]))
        good = share < ROUTER if must == "pass" else share > ROUTER
        ok &= good
        say(held="ROUTER", control=v, on="given inputs", layers=n_moe,
            reading=share, limit=ROUTER, must=must, ok=bool(good))
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def given_inputs(ref, hp, w, x, i, r, given):
    """The router at GIVEN inputs: the reference's own normed hidden states
    at this layer's compared positions, rounded to bfloat16 as the
    program's stream is, through the program's ``route_grouped`` and through
    the reference's router (float32; with a bfloat16 router).  Appends each
    one's share of rows that differ from the float32 reference's."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.routed import route_grouped

    cfg = ModelConfig(
        vocab_size=8, dim=x.shape[1], n_layers=1, n_heads=1, n_kv_heads=1,
        ffn_dim=8, n_ctx=8, n_experts=hp["n_experts"],
        n_experts_used=hp["n_used"], norm_topk_prob=hp["norm_w"],
        expert_gating="sigmoid" if hp["gating"] == 2 else "softmax",
        n_expert_groups=hp["n_groups"], n_groups_used=hp["groups_used"],
        expert_weights_scale=hp["scale"])
    xa = ref.attention(hp, w, x, i)[r["pos"]]
    u = ref.norm(xa, w["ffn_norm"], hp["eps"]
                 ).astype(jnp.bfloat16).astype(jnp.float32)
    want = np.asarray(ref.router(hp, w, u)[1])
    mine, _ = route_grouped(u.astype(jnp.bfloat16),
                            jnp.asarray(w["ffn_gate_inp"]),
                            jnp.asarray(w["exp_probs_b"]), cfg)
    given["program"].append(rows_that_differ(np.asarray(mine), want))
    given["bfloat16_router"].append(rows_that_differ(np.asarray(
        ref.router(hp, w, u, router_dtype=jnp.bfloat16)[1]), want))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="k-exaone-236b-a23b-q4km-ep8-16lane")
    ap.add_argument("--seed", type=int, default=45)
    ap.add_argument("--phase", choices=("lanes", "serial", "reference"))
    ap.add_argument("--work")
    ap.add_argument("--only", default="lanes,serial",
                    help="the engines to run, comma-separated")
    args = ap.parse_args()
    cfg_doc = find_config(args.config)
    plan = plan_of(cfg_doc, args.seed)
    if args.phase:
        path = bench.ensure_gguf(cfg_doc)
        return {"lanes": phase_lanes, "serial": phase_serial,
                "reference": reference_phase}[args.phase](
            cfg_doc, path, plan, args.work)
    work = args.work or os.path.join(bench.CACHE,
                                     f"compare_exaone_{args.seed}")
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
        rc = subprocess.run(cmd, env=env).returncode
        if rc and phase != "reference":
            say(ok=False, phase=phase, rc=rc)
            return rc
    return rc


if __name__ == "__main__":
    sys.exit(main())
