#!/usr/bin/env python3
"""The program against the plain reference of the ``lfm2moe`` block, at the
configuration's published widths, outside any timed window, on what the two
cells time.

    python3 benchmarks/compare_lfm2.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) two
requests of seeded words go through the ENGINE the cells time
(``compare_exaone.py``'s phases, which this file runs as they stand):

- ``long``: a prompt of three quarters of ``n_ctx`` (12288) and 48 decoded
  (``lfm2.longdoc-1``'s band), alone on the lane engine: wide slices of 1024
  rows, narrow ones of 256 and a last one narrower than 256, the conv rows
  carried across every slice's end and every slice through all 64 experts
  of 18 layers (the many-row K = 1536 form), then steps at context 12k with
  fifteen lanes dead (the few-row form at 4 picks);
- ``chat``: a prompt of 368 tokens and 104 decoded (``lfm2.chat-16sat``'s
  medians) beside 15 other live lanes of chat lengths (prompts 136-696),
  all admitted at once and decoding beside each other: sixteen lanes'
  carried rows and rings, the few-row form at 64 picks a layer.

``--only lanes,serial`` runs both once more through the serial ``Engine``.
The engines sample what they sample; the reference (``reference_lfm2.py``:
float32 at ``highest``, the whole sequence at once, no cache) then runs on
each request's prompt and the tokens the engine fed, a layer at a time while
it is dequantized, ON THE PROGRAM'S PICKS (so that both sum the same
experts).  The logits and picks are read by ``compare_mla.py``'s tap on
``forward``; the programs are otherwise the served ones.

What is held (PERF.md section 6 has the readings each limit stands between):

``LIMIT`` on ``|got - want| / |want|`` (Frobenius over the vocabulary) over
each block of compared positions of each request on each engine (a prompt's
last 64 positions, the decode steps).  Below it: the engines (bf16 inputs to
every product, a bf16 stream, carried rows and ring) and the reference with
every matmul and attention input rounded to bfloat16.  Above it: the
reference with those inputs rounded to float8_e4m3fn (the precision below
the one the configuration states), with the taps newest first, and without
the gate ``c *`` (each on the ``chat`` request: 472 positions).

``ROUTER`` on the router's arithmetic at GIVEN inputs (``compare_exaone.py
given_inputs``): the share of rows whose SET of picked experts differs from
the float32 reference's.  Below it: the program's ``route_grouped``.  Above
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
from compare_eva import find_config, rel, say   # noqa: E402
from compare_exaone import (         # noqa: E402
    given_inputs, kept, phase_lanes, phase_serial, plan_of)
from compare_mla import rows_that_differ   # noqa: E402

# PERF.md section 6 (my chip runs, PR 49) has every reading these stand
# between.  LIMIT: the lane engine read 0.034-0.035 (the bfloat16 reference
# 0.0126); the controls 0.215 (float8), 1.35 (the taps newest first), 1.41
# (no gate) on the chat request.  ROUTER: the program 0.0, a bfloat16 router
# 0.0169 of the rows.
LIMIT = 0.06
ROUTER = 0.002


class _Mixer:
    """``reference_lfm2`` as ``given_inputs`` asks it: ``attention`` is the
    layer's MIXER branch, whatever its kind."""

    def __init__(self, ref):
        self._ref = ref

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def attention(self, hp, w, x, i):
        return self._ref.mixer(hp, w, x, i)


def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_lfm2 as ref

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
                "flip_taps": (dict(flip_taps=True), "fail"),
                "no_gate": (dict(no_gate=True), "fail")}
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
                    # the reference's own where the tap saw none
                    use = np.asarray(ref.layer(hp, w, xs[k], i)[2]).copy()
                    use[r["have"]] = r["picks"][j]
                if k == calibrated and j >= 0:
                    given_inputs(_Mixer(ref), hp, w, xs[k], i, r, given)
                xs[k], _, mine = ref.layer(hp, w, xs[k], i, use_picks=use)
                if j >= 0:
                    own[k].append(np.asarray(mine))
                if k == calibrated:
                    for v, (kw, _) in variants.items():
                        cal[v] = ref.layer(hp, w, cal[v], i, use_picks=use,
                                           **kw)[0]
            say(note="layer", layer=i, kind=ref.kind_of(hp, i),
                seconds=round(time.time() - t0, 1))
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
        say(printed="rows whose picks differ from the reference's own "
                    "(the engine's stream carries bf16 layers before)",
            on=k, reading=rows_that_differ(r["picks"], theirs))
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
        say(held="ROUTER", control=v, on="given inputs", layers=n_moe,
            reading=share, limit=ROUTER, must=must, ok=bool(good))
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="lfm2-24b-a2b-q4km-l20-16lane")
    ap.add_argument("--seed", type=int, default=49)
    ap.add_argument("--phase", choices=("lanes", "serial", "reference"))
    ap.add_argument("--work")
    ap.add_argument("--only", default="lanes",
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
                                     f"compare_lfm2_{args.seed}")
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
