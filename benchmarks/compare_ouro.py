#!/usr/bin/env python3
"""The program against the plain reference of the ``ouro`` block (layers that
run several times), at the configuration's published widths and all of its
layer passes, outside any timed window, on what the cell times.

    python3 benchmarks/compare_ouro.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) FOUR
requests of seeded words, of ``chat-closed-4``'s lengths and unlike each
other, go through ``ContinuousEngine`` at once, one a lane: prompts of 368
(``chat``), 768 (``top``: the mix's longest, three slices), 200 and 520
tokens, prefilled in 256-token slices beside each other's decode steps, then
104, 24, 60 and 40 steps through the ring of ``n_layers x ut_steps``
leaves, the lanes at unlike positions: the lane engine's own programs
(``prefill_chunk``, ``lane_decode_chunk``), read by a tap on ``forward``
that keeps the logits of every compared position (a prompt's last 32 and
every decode step).  The engine samples what it samples; the reference
(``reference_ouro.py``: float32 at ``highest``, the whole sequence at once,
no cache) then runs on each request's prompt and the tokens the engine fed.

``--phase passes``: the SAME weights cut to 1, 2, 3 and all passes
(``dataclasses.replace(cfg, ut_steps=t)``: the first t passes of the loop
are the model of t passes) through the serial slice program on the ``chat``
request's sequence, against the reference's logits after pass t: where in
the depth the distance comes from.

Processes, each with the device to itself (the parent never imports JAX):
``--phase lanes``, ``--phase passes``, ``--phase reference`` (which also
gives the verdict).

What is held (PERF.md section 6 has the readings the limit stands between):

``LIMIT`` on ``|got - want| / |want|`` (Frobenius over the vocabulary) over
each block of compared positions of each request (a prompt's last 32
positions, the decode steps).  Below it: the engine (bf16 inputs to every
product, the fused K-quant kernels' bf16 ``d * sc``, a bf16 stream and
cache) and the reference with every matmul and attention input rounded to
bfloat16.  Above it, each on the ``chat`` request (472 positions): the
reference with those inputs rounded to float8_e4m3fn (the precision below
the one the configuration states), with one pass fewer, and with pass t
attending to the keys and values pass t - 1 projected (leaves shared by the
passes).

On seeded random weights with unit norm gains THE LOOP DOUBLES A DISTANCE
EVERY PASS (a pass starts from a stream of unit size, to which its first
layers add branches of unit size: a difference between two streams grows
with them; my chip run, PR 56: the engine's weights cut to 1 / 2 / 3 / 4
passes read 0.043 / 0.088 / 0.177 / 0.392 of the reference after as many
passes, the bfloat16 reference 0.153 after four).  So the limit is the
bfloat16 reference's own distance times 4.6, and a sequence cut to ``t``
passes is held to ``LIMIT / 2 ** (ut_steps - t)``: a distance that came
from one place in the depth would not halve pass by pass.

Exit 0 iff every reading that is held is on the right side; the last line
says so.
"""

from __future__ import annotations

import argparse
import dataclasses
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
    messages_of, note_loaded, system_line, words_for)

# PERF.md section 6 (my chip runs, PR 56) has every reading this stands
# between.  After all four passes: the engine 0.371-0.449 over the blocks of
# the four requests (largest single position 0.464), the bfloat16 reference
# 0.153 (x 4.6 = the limit); float8 1.176, one pass fewer 1.172, leaves
# shared 1.388 (1.41 is two unrelated vectors).
LIMIT = 0.70
LAST = 32       # compared positions at a prompt's end
REQUESTS = (("chat", 368, 104), ("top", 768, 24), ("short", 200, 60),
            ("mid", 520, 40))


def plan_of(cfg_doc: dict, seed: int) -> dict:
    """The requests: (name, prompt tokens, decoded tokens), smaller where
    the file's ring is (the CPU rehearsal)."""
    n_ctx = int(cfg_doc["serve"]["n_ctx"])
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    big = n_ctx >= 1280
    reqs = list(REQUESTS) if big else [
        ("chat", 150, 40), ("top", 300, 16), ("short", 90, 24),
        ("mid", 210, 20)]
    return {"seed": seed, "n_ctx": n_ctx, "lanes": lanes,
            "requests": reqs[:max(lanes, 1)]}


def kept(n_prompt: int, n_out: int) -> dict:
    return {"prompt_end": range(n_prompt - LAST, n_prompt),
            "decode": range(n_prompt, n_prompt + n_out)}


# ---------------------------------------------------------------------------
# the tap
# ---------------------------------------------------------------------------

class Tap:
    """Every call the engine's programs make of ``forward``, seen from the
    host: per watched request the tokens fed past its prompt and the logits
    at the compared positions.  A slice is told by its tokens (the prompt's
    at that offset), a lane's request by the position of its first step
    (the prompts' lengths differ)."""

    def __init__(self):
        self.prompts, self.want, self.fed, self.got = [], [], [], []
        self.owner, self.alive_steps = {}, {}

    def watch(self, ids, positions):
        self.prompts.append(np.asarray(ids, np.int32))
        self.want.append(set(positions))
        self.fed.append({})
        self.got.append({})

    def install(self):
        import jax
        import jax.numpy as jnp

        from llama_fastapi_k8s_gpu_tpu.models import generate, llama
        from llama_fastapi_k8s_gpu_tpu.parallel import batched

        real = llama.forward

        def tapped(params, cfg, tokens, pos, cache, last_idx=None,
                   live=None, with_stats=False, **kw):
            S = tokens.shape[0]
            logits, cache, *stats = real(
                params, cfg, tokens, pos, cache, last_idx=last_idx,
                live=live, return_all=True, with_stats=with_stats, **kw)
            alive = jnp.bool_(True) if live is None else live
            zero = jax.pure_callback(
                self._see, jax.ShapeDtypeStruct((), jnp.float32),
                tokens, pos, logits, alive, vmap_method="broadcast_all")
            idx = S - 1 if last_idx is None else last_idx
            row = jax.lax.dynamic_index_in_dim(logits, idx, keepdims=False)
            return (row + zero, cache, *stats)

        generate.forward = batched.forward = tapped

    def _see(self, tokens, pos, logits, alive):
        tokens, pos, alive = (np.asarray(a) for a in (tokens, pos, alive))
        if pos.ndim and tokens.shape[1] == 1:          # lanes of one step
            k = int(alive.sum())
            self.alive_steps[k] = self.alive_steps.get(k, 0) + 1
            for lane in range(pos.shape[0]):
                self._lane_step(lane, int(tokens[lane, 0]), int(pos[lane]),
                                logits[lane, 0], bool(alive[lane]))
        elif tokens.shape[0] > 1:
            self._slice(tokens, int(pos), logits)
        return np.zeros(pos.shape, np.float32)

    def _slice(self, tokens, off, logits):
        for j, ids in enumerate(self.prompts):
            m = min(len(tokens), len(ids) - off)
            if m > 0 and np.array_equal(ids[off:off + m], tokens[:m]):
                for p in range(off, off + m):
                    if p in self.want[j]:
                        self.got[j][p] = np.asarray(logits[p - off],
                                                    np.float32)
                return

    def _lane_step(self, lane, token, pos, logits, alive):
        if not alive:
            self.owner.pop(lane, None)
            return
        j = self.owner.get(lane)
        if j is None:
            j = next((i for i, ids in enumerate(self.prompts)
                      if len(ids) == pos and not self.fed[i]), None)
            if j is None:
                return
            self.owner[lane] = j
        self.fed[j][pos] = token
        if pos in self.want[j]:
            self.got[j][pos] = np.asarray(logits, np.float32)

    def save(self, path: str, names: list) -> None:
        out = {}
        for j, name in enumerate(names):
            fed = [self.fed[j][p] for p in sorted(self.fed[j])]
            pos = sorted(self.got[j])
            out[f"seq_{name}"] = np.concatenate(
                [self.prompts[j], np.asarray(fed, np.int32)])
            out[f"pos_{name}"] = np.asarray(pos, np.int32)
            out[f"logits_{name}"] = np.stack([self.got[j][p] for p in pos])
        np.savez(path, **out)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

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
        bytes_limit=memory.get("bytes_limit"),
        kv_cache_bytes=eng.kv_cache_bytes, loop=eng.cache_engine_health)
    system = system_line(cfg_doc, 16)
    texts = {}
    for i, (name, n_prompt, n_out) in enumerate(plan["requests"]):
        text, ids = words_for(eng, cfg_doc, system, n_prompt,
                              plan["seed"] + i)
        tap.watch(ids, [p for r in kept(n_prompt, n_out).values() for p in r])
        texts[name] = (text, n_out)
    t0 = time.time()
    # all at once: every lane live, slices beside steps, unlike positions
    futs = [eng.submit(messages_of(system, text), max_tokens=n_out + 1)
            for text, n_out in texts.values()]
    for f in futs:
        f.result()
    jax.effects_barrier()
    memory = jax.devices()[0].memory_stats() or {}
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())},
        counters=eng.cache_read_gauges(),
        peak_bytes_in_use=memory.get("peak_bytes_in_use"))
    eng.shutdown()
    tap.save(os.path.join(work, "lanes.npz"), list(texts))
    return 0


def phase_passes(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    """The ``chat`` sequence the lane engine ran, through the serial slice
    program of the SAME weights cut to t = 1 .. ut_steps passes."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    doc = np.load(os.path.join(work, "lanes.npz"))
    seq, pos = doc["seq_chat"], doc["pos_chat"]
    chunk = engine_kwargs(cfg_doc)["prefill_chunk"]
    n_ctx = -(-(len(seq) + 1) // chunk) * chunk
    t0 = time.time()
    eng = Engine(path, **{**engine_kwargs(cfg_doc), "n_ctx": n_ctx})
    note_loaded(eng, t0)
    out = {"pos": pos}
    for t in range(1, eng.cfg.ut_steps + 1):
        cfg = dataclasses.replace(eng.cfg, ut_steps=t)
        run = jax.jit(lambda toks, off, cache, cfg=cfg: forward(
            eng.params, cfg, toks, off, cache, return_all=True),
            donate_argnums=(2,))
        cache, rows = init_cache(cfg), []
        padded = np.zeros(n_ctx, np.int32)
        padded[:len(seq)] = seq
        for off in range(0, len(seq), chunk):
            logits, cache = run(jnp.asarray(padded[off:off + chunk]),
                                jnp.int32(off), cache)
            rows.append(np.asarray(logits, np.float32))
        out[f"logits_{t}"] = np.concatenate(rows)[pos]
        del cache
        say(note="passes", passes=t, seconds=round(time.time() - t0, 1))
    np.savez(os.path.join(work, "passes.npz"), **out)
    return 0


# ---------------------------------------------------------------------------
# the reference, and the verdict
# ---------------------------------------------------------------------------

def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_ouro as ref

    t0 = time.time()
    hp, tensors = ref.open_model(path)
    T, L = hp["ut_steps"], hp["n_layers"]
    doc = np.load(os.path.join(work, "lanes.npz"))
    runs = {name: {"seq": doc[f"seq_{name}"], "pos": doc[f"pos_{name}"],
                   "logits": doc[f"logits_{name}"], "n_prompt": n_prompt,
                   "n_out": n_out}
            for name, n_prompt, n_out in plan["requests"]}
    cal = "chat"
    # the variants of the calibrated request: what each must do to LIMIT
    per_pass_bf16 = {}
    variants = {"bfloat16": (dict(emulate=jnp.bfloat16), "pass"),
                "float8": (dict(emulate=jnp.float8_e4m3fn), "fail"),
                "shared_leaves": ({}, "fail")}
    keep: dict = {}       # the layers' float32 weights, dequantized once
    g_final = ref.tensor(tensors, "output_norm.weight")
    per_pass = {}
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))
        xs = {k: emb[jnp.asarray(r["seq"])] for k, r in runs.items()}
        vs = {v: xs[cal] for v in variants}
        del emb
        before = {}         # the shared-leaf control's one leaf a layer
        for t in range(T):
            for l in range(L):
                w = {k: jnp.asarray(a) for k, a in
                     ref.layer_weights(tensors, l, keep).items()}
                for k in runs:
                    xs[k], _ = ref.layer(hp, w, xs[k])
                for v, (kw, _) in variants.items():
                    kv = before.get(l) if v == "shared_leaves" else None
                    if v == "shared_leaves" and t + 1 < T:
                        before[l] = ref.project_kv(hp, w, vs[v])
                    vs[v], _ = ref.layer(hp, w, vs[v], kw.get("emulate"), kv)
                del w
            for store in (xs, vs):
                for k in store:
                    store[k] = ref.norm(store[k], g_final, hp["eps"])
            at = runs[cal]["pos"]
            per_pass[t + 1] = np.asarray(ref.head(tensors, xs[cal][at]))
            per_pass_bf16[t + 1] = np.asarray(ref.head(
                tensors, vs["bfloat16"][at], jnp.bfloat16))
            say(note="pass", passes=t + 1,
                seconds=round(time.time() - t0, 1),
                exit_gate_mean=float(jnp.mean(ref.gate(tensors, xs[cal]))))
        want = {k: np.asarray(ref.head(tensors, xs[k][r["pos"]]))
                for k, r in runs.items()}
        got_v = {v: np.asarray(ref.head(tensors, x[runs[cal]["pos"]],
                                        variants[v][0].get("emulate")))
                 for v, x in vs.items()}
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
    for v, (_, must) in variants.items():
        d = rel(got_v[v], want[cal])
        good = d < LIMIT if must == "pass" else d > LIMIT
        ok &= good
        say(held="LIMIT", control=v, on=cal, reading=d, limit=LIMIT,
            must=must, ok=bool(good),
            from_the_program=rel(runs[cal]["logits"], got_v[v]))
    # one pass fewer: the reference's own logits after pass T - 1
    d = rel(per_pass[T - 1], want[cal])
    ok &= d > LIMIT
    say(held="LIMIT", control="one_pass_fewer", on=cal, reading=d,
        limit=LIMIT, must="fail", ok=bool(d > LIMIT),
        from_the_program=rel(runs[cal]["logits"], per_pass[T - 1]))
    passes = os.path.join(work, "passes.npz")
    if os.path.exists(passes):
        pdoc = np.load(passes)
        for t in range(1, T + 1):
            d, limit = rel(pdoc[f"logits_{t}"], per_pass[t]), \
                LIMIT / 2 ** (T - t)
            ok &= d < limit
            say(held="LIMIT", on=f"{cal} cut to {t} passes (serial slices)",
                passes=t, reading=d, limit=limit, ok=bool(d < limit),
                bfloat16_reference=rel(per_pass_bf16[t], per_pass[t]),
                from_the_lane_engine=rel(pdoc[f"logits_{t}"],
                                         runs[cal]["logits"])
                if t == T else None)
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ouro-2.6b-q4km-4lane")
    ap.add_argument("--seed", type=int, default=56)
    ap.add_argument("--phase", choices=("lanes", "passes", "reference"))
    ap.add_argument("--work")
    ap.add_argument("--only", default="lanes,passes",
                    help="the program phases to run, comma-separated")
    args = ap.parse_args()
    cfg_doc = find_config(args.config)
    plan = plan_of(cfg_doc, args.seed)
    if args.phase:
        path = bench.ensure_gguf(cfg_doc)
        return {"lanes": phase_lanes, "passes": phase_passes,
                "reference": reference_phase}[args.phase](
            cfg_doc, path, plan, args.work)
    work = args.work or os.path.join(bench.CACHE,
                                     f"compare_ouro_{args.seed}")
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
