#!/usr/bin/env python3
"""The program against the plain reference of the ``longcat-flash`` block, at
the configuration's published widths, outside any timed window, on what the
cell times.

    python3 benchmarks/compare_longcat.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) two
requests of seeded words, of ``chat-closed-16``'s lengths, go through the
ENGINES:

- ``chat``: a prompt of 368 tokens and 104 decoded (the mix's medians);
- ``top``: a prompt of 768 tokens (the mix's longest: three narrow slices)
  and 24 decoded (its shortest answer);

in ``ContinuousEngine`` beside 14 other live lanes of chat lengths (prompts
136-656), all sixteen admitted at once and decoding beside each other at
unlike positions: slices of 256 rows through the many-row expert kernels,
then steps of 16 x 12 = 192 picks a layer through the few-row ones, the
decode kernel on the 8 leaves of the latent ring.  Both once more through
the serial ``Engine`` (``--only lanes,serial``, the default).  The engines
sample what they sample; the reference (``reference_longcat.py``: float32 at
``highest``, the whole sequence at once, no cache, the same share of
experts) then runs on each request's prompt and the tokens the engine fed, a
layer at a time while it is dequantized, ON THE PROGRAM'S PICKS (so that
both sum the same experts and the same identity terms).  The logits and
picks are read by ``compare_mla.py``'s tap on ``forward``; the programs are
otherwise the served ones.

Three processes, each with the device to itself (the parent never imports
JAX): ``--phase lanes``, ``--phase serial``, ``--phase reference`` (which
also gives the verdict).

What is held (PERF.md section 6 has the readings each limit stands between):

``LIMIT`` on ``|got - want| / |want|`` (Frobenius over the vocabulary) over
each block of compared positions of each request on each engine (a prompt's
last 64 positions, the decode steps).  Below it: the engines (bf16 inputs to
every product, a bf16 stream and cache) and the reference with every matmul
and attention input rounded to bfloat16.  Above it, each on the ``chat``
request of the first engine that ran (472 positions): the reference with
those inputs rounded to float8_e4m3fn (the precision below the one the
configuration states), without ``mla_scale_q_lora``, without
``mla_scale_kv_lora``, with the picked weights normalised, without the
identity experts' term, and with the expert branch joined before sub-block
1.

``ROUTER`` on the router's arithmetic at GIVEN inputs: the normed hidden
states the reference itself saw at every layer's router, at the calibrated
request's compared positions, rounded to bfloat16 as the program's stream
is, through the program's ``route_grouped`` on the file's own router (768
outputs), against the reference's float32 router on the same values: the
share of rows whose SET of 12 picks differs.  Below it: the program.  Above
it: the reference's router with its inputs, weights and scores rounded to
bfloat16 (a bf16 router).

``FLIPS`` on the share of rows whose set of picks IN THE ENGINES' OWN RUN
differs from the reference's own on the same tokens: near-ties for the
twelfth place among 768 outputs that the bf16 layers before order the other
way.  Counted and bounded: a router that read other rows, or a bias that
were not zero, differs in most rows.

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
from compare_exaone import kept, phase_serial, watch_all   # noqa: E402
from compare_mla import (            # noqa: E402
    Tap, messages_of, note_loaded, rows_that_differ, words_for)

# PERF.md section 6 (my chip runs, PR 52) has every reading these stand
# between.  LIMIT: the engines read 0.045-0.049 on every block of both
# requests (the bfloat16 reference 0.0226); the controls 0.29 (no identity
# term), 0.33 (the join moved), 0.46 (float8), 0.88 (normalised weights),
# 0.99 (no mla_scale_q_lora), 1.25 (no mla_scale_kv_lora) on the chat
# request.  ROUTER: the program 0.0, a bfloat16 router 0.0997 of the rows.
# FLIPS: the engines 0.40-0.42 of the rows (the twelfth of 768 softmax
# outputs lies 0.03 in the logit from the thirteenth on average, and the
# bf16 layers before carry as much); a router that read other rows, or a
# choice that a bias decided, differs in every row (1.0).
LIMIT = 0.12
ROUTER = 0.002
FLIPS = 0.7
REQUESTS = (("chat", 368, 104), ("top", 768, 24))
# (no filler's prompt is as long as a watched request's: the tap tells a
# lane's request by the position of its first step)
FILLERS = tuple(range(136, 136 + 40 * 14, 40))


def plan_of(cfg_doc: dict, seed: int) -> dict:
    """The requests: (name, prompt tokens, decoded tokens), smaller where
    the file's ring is (the CPU rehearsal)."""
    n_ctx = int(cfg_doc["serve"]["n_ctx"])
    big = n_ctx >= 4096
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    return {"seed": seed, "n_ctx": n_ctx, "lanes": lanes,
            "requests": list(REQUESTS) if big
            else [("chat", 150, 40), ("top", 300, 16)],
            # (answers long enough that the first filler still decodes when
            # the requests, admitted last, end: every lane live beside them)
            "fillers": [(t if big else 100 + 12 * i, 400 if big else 64)
                        for i, t in enumerate(FILLERS[:max(lanes - 2, 0)])]}


def phase_lanes(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    tap = Tap()
    tap.install()
    t0 = time.time()
    eng = ContinuousEngine(path, batch_size=plan["lanes"],
                           **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    memory = jax.devices()[0].memory_stats() or {}
    say(note="after load", bytes_in_use=memory.get("bytes_in_use"),
        peak_bytes_in_use=memory.get("peak_bytes_in_use"),
        bytes_limit=memory.get("bytes_limit"))
    texts = watch_all(tap, eng, cfg_doc, plan)
    t0 = time.time()
    system = next(iter(texts.values()))[0]
    # the fillers, then the two requests, all at once: every lane live
    futs = [eng.submit(messages_of(system, words_for(
        eng, cfg_doc, system, n, plan["seed"] + 100 + i)[0]), max_tokens=out)
        for i, (n, out) in enumerate(plan["fillers"])]
    futs += [eng.submit(messages_of(system, text), max_tokens=n_out + 1)
             for system, text, n_out in texts.values()]
    for f in futs:
        f.result()
    jax.effects_barrier()
    snap = eng.expert_counters.snapshot(block=True)
    memory = jax.devices()[0].memory_stats() or {}
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())},
        counters=eng.cache_read_gauges(),
        picks_held=snap["picks_held"], picks_zero=snap["picks_zero"],
        picks_total=snap["picks_total"],
        experts_read_per_layer_step=snap["experts_read"]
        / max(snap["layer_steps"], 1),
        peak_bytes_in_use=memory.get("peak_bytes_in_use"))
    eng.shutdown()
    tap.save(os.path.join(work, "lanes.npz"), list(texts), {})
    return 0


# ---------------------------------------------------------------------------
# the reference, and the verdict
# ---------------------------------------------------------------------------

def given_inputs(ref, hp, w, x, r, given):
    """The router at GIVEN inputs: the reference's own normed hidden states
    at this layer's router at the compared positions, rounded to bfloat16
    as the program's stream is, through the program's ``route_grouped`` and
    through the reference's router (float32; with a bfloat16 router).
    Appends each one's share of rows that differ from the float32
    reference's."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.routed import route_grouped

    cfg = ModelConfig(
        vocab_size=8, dim=x.shape[1], n_layers=1, n_heads=1, n_kv_heads=1,
        ffn_dim=8, n_ctx=8, n_experts=hp["n_experts"],
        n_zero_experts=hp["n_zero"], n_experts_used=hp["n_used"],
        norm_topk_prob=hp["norm_w"], expert_gating="softmax",
        expert_weights_scale=hp["scale"])
    xa = ref.attention(hp, w[0], x)[r["pos"]]
    u = ref.norm(xa, w[0]["ffn_norm"], hp["eps"]
                 ).astype(jnp.bfloat16).astype(jnp.float32)
    want = np.asarray(ref.router(hp, w, u)[1])
    n_out = hp["n_experts"] + hp["n_zero"]
    mine, _ = route_grouped(
        u.astype(jnp.bfloat16), jnp.asarray(w["ffn_gate_inp"]),
        jnp.asarray(w.get("exp_probs_b", np.zeros(n_out, np.float32))), cfg)
    given["program"].append(rows_that_differ(np.asarray(mine), want))
    given["bfloat16_router"].append(rows_that_differ(np.asarray(
        ref.router(hp, w, u, router_dtype=jnp.bfloat16)[1]), want))


def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_longcat as ref

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
    # the controls run on a chat request (a control costs one more pass)
    calibrated = next(k for k in runs if k.endswith(".chat"))
    variants = {"bfloat16": (dict(emulate=jnp.bfloat16), "pass"),
                "float8": (dict(emulate=jnp.float8_e4m3fn), "fail"),
                "no_q_scale": (dict(no_q_scale=True), "fail"),
                "no_kv_scale": (dict(no_kv_scale=True), "fail"),
                "norm_weights": (dict(norm_weights=True), "fail"),
                "no_identity": (dict(no_identity=True), "fail"),
                "join_early": (dict(join_early=True), "fail")}
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))
        xs = {k: emb[jnp.asarray(r["seq"])] for k, r in runs.items()}
        cal = {v: xs[calibrated] for v in variants}
        del emb
        own = {k: [] for k in runs}
        given = {"program": [], "bfloat16_router": []}
        for l in range(hp["n_layers"]):
            w = ref.layer_weights(tensors, l)
            for k, r in runs.items():
                if len(r["have"]) == len(r["seq"]):
                    use = r["picks"][l]       # the program's, everywhere
                else:
                    # the reference's own where the tap saw none
                    use = np.asarray(ref.layer(hp, w, xs[k])[2]).copy()
                    use[r["have"]] = r["picks"][l]
                if k == calibrated:
                    given_inputs(ref, hp, w, xs[k], r, given)
                xs[k], _, mine = ref.layer(hp, w, xs[k], use_picks=use)
                own[k].append(np.asarray(mine))
                if k == calibrated:
                    for v, (kw, _) in variants.items():
                        cal[v] = ref.layer(hp, w, cal[v], use_picks=use,
                                           **kw)[0]
            say(note="layer", layer=l, seconds=round(time.time() - t0, 1))
            del w
        want = {k: np.asarray(ref.head(hp, tensors, xs[k][r["pos"]]))
                for k, r in runs.items()}
        at = runs[calibrated]["pos"]
        cal = {v: np.asarray(ref.head(hp, tensors, x[at],
                                      variants[v][0].get("emulate")))
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
        flips = rows_that_differ(r["picks"], theirs)
        ok &= flips < FLIPS
        say(held="FLIPS", on=k, rows=int(theirs.shape[0] * theirs.shape[1]),
            reading=flips, limit=FLIPS, ok=bool(flips < FLIPS),
            zero_share=float(np.mean(r["picks"] >= hp["n_experts"])),
            held_share=float(np.mean(
                (r["picks"] >= hp["held_first"])
                & (r["picks"] < hp["held_first"] + hp["held"]))))
    for v, (_, must) in variants.items():
        d = rel(cal[v], want[calibrated])
        good = d < LIMIT if must == "pass" else d > LIMIT
        ok &= good
        say(held="LIMIT", control=v, on=calibrated, reading=d, limit=LIMIT,
            must=must, ok=bool(good))
    for v, must in (("program", "pass"), ("bfloat16_router", "fail")):
        share = float(np.mean(given[v]))
        good = share < ROUTER if must == "pass" else share > ROUTER
        ok &= good
        say(held="ROUTER", control=v, on="given inputs",
            layers=hp["n_layers"], reading=share, limit=ROUTER, must=must,
            ok=bool(good))
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config",
                    default="longcat-flash-omni-560b-a27b-q4km-ep8-16lane")
    ap.add_argument("--seed", type=int, default=52)
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
                                     f"compare_longcat_{args.seed}")
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
