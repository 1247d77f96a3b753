#!/usr/bin/env python3
"""The program against the plain reference of the ``jamba`` block, at the
configuration's published widths, outside any timed window, on what the cell
times.

    python3 benchmarks/compare_jamba.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) two
requests of seeded words go through the ENGINE the cell times
(``compare_phi4flash.py``'s phases):

- ``long``: a prompt of 33920 tokens and 48 decoded (``jamba2.longctx-1``'s
  band), alone on the lane engine: 33 wide slices of 1024 rows and one
  narrow one, the scan kernel over every slice of 26 layers with states and
  conv rows carried across every slice's end, two layers attending from 20
  heads to one K/V head over a ring whose walk ends at the slice, then steps
  at context 34k with fifteen lanes dead;
- ``chat``: a prompt of 368 tokens and 104 decoded (a chat request's
  medians) beside 15 other live lanes of chat lengths, all admitted at once
  and decoding beside each other: sixteen lanes' states step in one program.

``--only lanes,serial`` runs both once more through the serial ``Engine``.
The engines sample what they sample; the reference (``reference_jamba.py``:
float32 at ``highest``, the whole sequence at once, a plain ``lax.scan``, no
cache) then runs on each request's prompt and the tokens the engine fed, a
layer at a time while it is dequantized.  Three processes, each with the
device to itself (the parent never imports JAX).

What is held (PERF.md section 6 has the readings the limit stands between),
on ``|got - want| / |want|`` (Frobenius over the vocabulary) over a block of
positions: ``LIMIT`` on the logits at a prompt's first 63 positions after the
first (where a query has few keys), at its last 64 and at every decode
step, of each request on each engine.  Below it: the engine
(bf16 inputs to every product, a bf16 stream, float32 states) and the
reference with every matmul and attention input rounded to bfloat16.  Over
it in ONE block at least, each on the ``chat`` request's positions: the reference with those
inputs rounded to float8_e4m3fn (the precision below the one the
configuration states), without the three inner norms, with a rotation on q
and k, and with the conv taps newest first; and, on the ``long`` request's
112 positions (what a bfloat16 state loses gathers over the positions a slow
channel remembers), with the state rounded to bfloat16 at every position
(``lax.reduce_precision``); the same on the ``chat`` request is printed.

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
    Tap, messages_of, note_loaded, system_line, words_for)

# PERF.md section 6 (my chip runs, PR 65, calls 2 and 4, seeds 65 and 6565)
# has every reading this stands between.  The lane engine read 0.068-0.076
# over the twelve blocks (0.113 at its worst single position), the bfloat16
# reference 0.031-0.032; the held controls, each in its most telling block:
# a rotation 0.171 (the chat prompt's head; 0.086-0.091 elsewhere), a
# bfloat16 state 0.189-0.192 (the long request's tail and decode; 0.009 at
# its head), float8 0.53-0.57, the inner norms left out 1.13-1.21, the taps
# newest first 1.24-1.28.  The limit sits at 1.46 x the engine's largest
# block (fresh seeds read higher) and at 0.64 of the lowest control's.
LIMIT = 0.11
TAIL = 64
#: chat lengths (prompt, answer) of the fifteen lanes beside ``chat``
FILLERS = ((136, 96), (168, 120), (200, 88), (232, 128), (264, 104),
           (296, 112), (328, 96), (392, 136), (424, 104), (456, 120),
           (488, 88), (520, 128), (584, 112), (632, 104), (696, 96))
LONG, CHAT = (33920, 48), (368, 104)


def plan_of(cfg_doc: dict, seed: int) -> dict:
    """The requests: (name, prompt tokens, decoded tokens), and the fillers'
    (prompt, answer); smaller where the file's ring is (the CPU
    rehearsal)."""
    n_ctx = int(cfg_doc["serve"]["n_ctx"])
    big = n_ctx >= 65536
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    fillers = list(FILLERS[:lanes - 1]) \
        if big else [(40 + 8 * i, 24) for i in range(lanes - 1)]
    return {"seed": seed, "n_ctx": n_ctx, "lanes": lanes,
            "requests": [("long",) + (LONG if big else (n_ctx * 5 // 8, 8)),
                         ("chat",) + (CHAT if big else (52, 24))],
            "fillers": fillers}


def kept(n_prompt: int, n_out: int) -> dict:
    """The positions whose LOGITS are compared.  ``prompt_head``: where a
    query has few keys, so what attention does with them is a large part of
    the stream (at position 400 an attention layer's output is a mean over
    400 values of random weights, a sliver: a rotation of q and k moves the
    logits there by less than the engine's own rounding)."""
    return {"prompt_head": range(1, min(TAIL, n_prompt)),
            "prompt_tail": range(max(n_prompt - TAIL, TAIL), n_prompt),
            "decode": range(n_prompt, n_prompt + n_out)}


class TailTap(Tap):
    """``compare_mla.Tap`` for a dense stack and long prompts: a slice's
    call of ``forward`` computes the logits of ALL its rows, and the host
    sees the ``TAIL`` rows that end at the slice's last real one and the
    slice's first ``TAIL`` rows (34 slices of 1024 x 65536 logits would be 9
    GB through the callback)."""

    def install(self):
        import jax
        import jax.numpy as jnp

        from llama_fastapi_k8s_gpu_tpu.models import generate, llama
        from llama_fastapi_k8s_gpu_tpu.parallel import batched

        real = llama.forward

        def tapped(params, cfg, tokens, pos, cache, last_idx=None,
                   live=None, **kw):
            S = tokens.shape[0]
            kw.pop("with_stats", None)
            kw.pop("with_picks", None)
            logits, cache = real(params, cfg, tokens, pos, cache,
                                 last_idx=last_idx, live=live,
                                 return_all=True, **kw)
            idx = jnp.int32(S - 1) if last_idx is None else last_idx
            n = min(TAIL, S)
            first = jnp.clip(idx - (n - 1), 0, S - n)
            alive = jnp.bool_(True) if live is None else live
            zero = jax.pure_callback(
                self._see, jax.ShapeDtypeStruct((), jnp.float32),
                tokens, pos, idx, first,
                jax.lax.dynamic_slice_in_dim(logits, first, n), logits[:n],
                alive, vmap_method="broadcast_all")
            row = jax.lax.dynamic_index_in_dim(logits, idx, keepdims=False)
            return row + zero, cache

        generate.forward = batched.forward = tapped

    def _see(self, tokens, pos, idx, first, logits, head, alive):
        tokens, pos = np.asarray(tokens), np.asarray(pos)
        alive = np.asarray(alive)
        none = np.zeros(0, np.int32)
        if pos.ndim and tokens.shape[1] == 1:          # lanes of one step
            k = int(alive.sum())
            self.alive_steps[k] = self.alive_steps.get(k, 0) + 1
            for lane in range(pos.shape[0]):
                self._lane_step(lane, int(tokens[lane, 0]), int(pos[lane]),
                                logits[lane, 0], none, bool(alive[lane]))
        elif tokens.shape[0] > 1:
            self._tail_slice(tokens, int(pos), int(idx), int(first),
                             np.asarray(logits), np.asarray(head))
        elif self.current is not None:
            self._step(self.current, int(tokens[0]), int(pos), logits[0],
                       none)
        return np.zeros(pos.shape, np.float32)

    def _tail_slice(self, tokens, off, idx, first, logits, head):
        own = [] if self.current is None else [self.current]
        for j in own + list(range(len(self.prompts))):
            ids = self.prompts[j]
            n = min(len(tokens), len(ids) - off)
            if n <= 0 or not np.array_equal(ids[off:off + n], tokens[:n]) \
                    or off in self.picks[j]:
                continue
            for p in range(off, off + n):
                self.fed[j][p] = int(ids[p])
                self.picks[j][p] = np.zeros(0, np.int32)
            # the slice's last real rows, and its first
            for rows, at in ((logits, off + first), (head, off)):
                for r in range(rows.shape[0]):
                    p = at + r
                    if p <= off + idx and p in self.want[j]:
                        self.got[j][p] = np.asarray(rows[r], np.float32)
            return

    def save(self, path: str, names: list, extra: dict):
        out = dict(extra)
        for j, name in enumerate(names):
            seq, p = list(self.prompts[j]), len(self.prompts[j])
            while p in self.fed[j]:
                seq.append(self.fed[j][p])
                p += 1
            at = sorted(q for q in self.got[j] if q < len(seq))
            out[f"seq_{name}"] = np.asarray(seq, np.int32)
            out[f"pos_{name}"] = np.asarray(at, np.int32)
            out[f"logits_{name}"] = np.stack([self.got[j][q] for q in at])
        np.savez(path, **out)


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

    tap = TailTap()
    tap.install()
    t0 = time.time()
    eng = ContinuousEngine(path, batch_size=plan["lanes"],
                           **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    texts = watch_all(tap, eng, cfg_doc, plan)
    t0 = time.time()
    system, text, n_out = texts["long"]
    eng.submit(messages_of(system, text), max_tokens=n_out + 1,
               seed=plan["seed"]).result()
    say(note="long done", seconds=round(time.time() - t0, 1),
        counters=eng.cache_read_gauges())
    # the chat request beside 15 fillers, all at once: every lane live
    fill = [eng.submit(messages_of(system, words_for(
        eng, cfg_doc, system, n, plan["seed"] + 100 + i)[0]), max_tokens=out)
        for i, (n, out) in enumerate(plan["fillers"])]
    system, text, n_out = texts["chat"]
    one = eng.submit(messages_of(system, text), max_tokens=n_out + 1)
    for f in fill + [one]:
        f.result()
    jax.effects_barrier()
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())},
        counters=eng.cache_read_gauges(), cache=eng.cache_kind,
        engine_health=eng.cache_engine_health)
    eng.shutdown()
    tap.save(os.path.join(work, "lanes.npz"), list(texts), {})
    return 0


def phase_serial(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    tap = TailTap()
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


def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_jamba as ref

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
                key: doc[f"{key}_{name}"] for key in
                ("seq", "pos", "logits")} | {
                "n_prompt": n_prompt, "n_out": n_out}
    # the controls run on a chat request (a control costs one more pass);
    # the state's precision on the LONG one: what a bfloat16 state loses
    # gathers over the thousand positions a slow channel remembers
    chat, long = (next(k for k in runs if k.endswith(name))
                  for name in (".chat", ".long"))
    variants = {
        "bfloat16": (dict(emulate=jnp.bfloat16), "pass", chat),
        "float8": (dict(emulate=jnp.float8_e4m3fn), "fail", chat),
        "no_inner_norms": (dict(skip_norms=("dt", "b", "c")), "fail", chat),
        "rotate": (dict(rotate=True), "fail", chat),
        "flip_taps": (dict(flip_taps=True), "fail", chat),
        "bfloat16_state": (dict(state_dtype=jnp.bfloat16), "fail", long),
        "bfloat16_state_chat": (dict(state_dtype=jnp.bfloat16), "print",
                                chat)}
    with jax.default_matmul_precision("highest"):
        xs = {k: ref.start(hp, tensors, r["seq"]) for k, r in runs.items()}
        cal = {v: ref.start(hp, tensors, runs[on]["seq"], kw.get("emulate"))
               for v, (kw, _, on) in variants.items()}
        for i in range(hp["n_layers"]):
            w = ref.layer_weights(tensors, i)
            for k in runs:
                xs[k] = ref.layer(hp, w, i, xs[k])
            for v, (kw, _, _) in variants.items():
                cal[v] = ref.layer(hp, w, i, cal[v], **kw)
            say(note="layer", layer=i, kind=hp["kinds"][i],
                seconds=round(time.time() - t0, 1))
            del w
        want = {k: np.asarray(ref.head(hp, tensors, xs[k][r["pos"]]))
                for k, r in runs.items()}
        cal = {v: np.asarray(ref.head(
                   hp, tensors, x[runs[variants[v][2]]["pos"]],
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
    # a control is told from the reference over ONE block of positions at
    # least, not over each (a rotation shows where a query has few keys)
    for v, (_, must, on) in variants.items():
        r = runs[on]
        read = {}
        for block, rng_ in kept(r["n_prompt"], r["n_out"]).items():
            sel = np.isin(r["pos"], np.asarray(list(rng_)))
            if sel.any():
                read[block] = rel(cal[v][sel], want[on][sel])
        over = [b for b, d in read.items() if d > LIMIT]
        good = not over if must == "pass" else bool(over) \
            if must == "fail" else True
        ok &= good
        say(held="LIMIT", control=v, on=on, readings=read, limit=LIMIT,
            over=over, must=must, ok=bool(good))
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="jamba2-3b-q4km-16lane")
    ap.add_argument("--seed", type=int, default=65)
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
                                     f"compare_jamba_{args.seed}")
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
