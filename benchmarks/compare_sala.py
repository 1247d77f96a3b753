#!/usr/bin/env python3
"""The program against the plain reference of the ``minicpm-sala`` stack, at
the configuration's published widths and full depth, outside any timed
window, on what the two cells time.

    python3 benchmarks/compare_sala.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) two
requests of seeded words go through the ENGINES the cells time:

- ``chat``: a prompt of 368 tokens and 104 decoded (``sala.chat-8sat``'s
  median), in ``ContinuousEngine`` beside seven other live lanes (fillers
  of other lengths, submitted first): admission slices into the scratch
  cache (the chunk form, from a zero state), ``lane_write`` over the five
  leaves, ``lane_decode_chunk`` with eight states stepping, the sparse
  layers on the ring's dense read;
- ``long``: a prompt of three quarters of ``n_ctx`` (12288) and 48 decoded
  (``sala.longdoc-1``'s band), alone on the engine: 48 slices, the last 16
  of them past ``dense_len`` (every query selects its blocks), then steps
  that select, gather and close compressed keys, seven lanes dead.

Both once more through the serial ``Engine``.  The engines sample what they
sample; the reference (``reference_sala.py``: float32 at ``highest``, the
recurrence token by token, explicit sets) then runs on each request's
prompt and the tokens the engine fed, a layer at a time while it is
dequantized.

The logits and the sets are read by a tap: the name ``forward`` in
``models/generate.py`` and ``parallel/batched.py`` is wrapped so that the
head is applied to every position, the sparse layers hand out the blocks
each query read, and both reach the host through ``jax.pure_callback``; the
engines get what they asked for.  The programs are otherwise the served
ones: the same jits, operands and caches.

Four processes, each with the device to itself (the parent never imports
JAX): ``--phase lanes``, ``--phase serial``, ``--phase state``, ``--phase
reference`` (which also gives the verdict).

What is held (PERF.md section 6 has the readings each limit stands between):

``LIMIT`` on ``|got - want| / |want|`` (Frobenius over the vocabulary) over
each block of compared positions of each request on each engine (a
prompt's last 64 positions, the 64 positions around ``dense_len``, the
decode steps), the reference computed ON THE PROGRAM'S SETS so that both
softmaxes run over the same keys.  Below it: the engines, and the reference
with every matmul and attention input rounded to bfloat16.  A STATE rounded to
bfloat16 after every step (the precision below the one the configuration
states) is one more rounding among 32 layers' on the logits (0.02 of their
norm over 12336 positions: printed, not held), and the engine's ``state``
leaf differs from the reference's own by what its k and v carry of the
layers before (0.11 of its norm, the rounded state's 0.12: printed, not
held; PERF.md section 6).  It is held where it shows:

``STATE`` on the state's own arithmetic at GIVEN inputs (``--phase
state``, as tier-1 holds it at the tiny size): seeded bfloat16 q, k, v at
the published heads, as long as the long request, through the program's
chunk form in slices (``models/sala.py lin_slice``) and then its decode
step (``ops/pallas/linstate.py``, one live lane among the configuration's
lanes), under the first and the last linear layer's decay, against the
reference's recurrence on the same values; the last state and the decode
steps' outputs.  Below it: the program (float32 sums in another order).
Above it: the recurrence with its state rounded to bfloat16 after every
step, whose error grows with the root of the length in the heads that
hardly decay.

``PICKS``: sets of blocks (per sparse layer, KV head and query past
``dense_len``) that differ from the reference's own under the program's
earlier sets, counted unless they are as LARGE as the reference's and the
reference's scores of the blocks exchanged lie within ``MARGIN`` of each
other; as a share of the sets.  Seeded random weights give near-uniform
scores over the compressed keys (the top-64 of 150 candidates is then
decided in the fourth digit, below what bfloat16 keys carry), so most sets
differ in WHICH blocks they picked and none beyond the margin: what the
count holds at this size is the selection's structure, its size and the
blocks that are there by right (``STRUCT``: every set as large as the
reference's, block 0 and every block of the window in it).  Below
``PICKS``: the engines.  Above it: the same count against the sets of the
reference run with top-(k - 1) and with no window blocks (every set is a
block short, or 33 blocks short).

Exit 0 iff every reading that is held is on the right side; the last line
says so.
"""

from __future__ import annotations

import argparse
import json
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
                         messages_of, rel, say)

# PERF.md section 6 (my chip runs, PR 38) has every reading these stand
# between.
LIMIT = 0.2
STATE = 0.01
PICKS = 0.01
MARGIN = 0.25
TAIL, AROUND = 64, 32
CHAT = (368, 104)
LONG_OUT = 48
FILLERS = ((212, 260), (276, 260), (340, 260), (404, 260), (468, 260),
           (532, 260), (596, 260))


def plan_of(cfg_doc: dict, seed: int) -> dict:
    """The requests: (name, prompt tokens, decoded tokens), smaller where
    the file's ring is (the CPU rehearsal)."""
    n_ctx = int(cfg_doc["serve"]["n_ctx"])
    big = n_ctx >= 4096
    chat = CHAT if big else (40, 24)
    fillers = FILLERS if big else ((24, 40),)
    return {"seed": seed, "n_ctx": n_ctx,
            "requests": [("chat",) + chat,
                         ("long", n_ctx * 3 // 4, LONG_OUT if big else 24)],
            "fillers": list(fillers)}


def kept(n_prompt: int, n_out: int, dense_len: int) -> dict[str, range]:
    """The compared positions of a request."""
    out = {"prefill_tail": range(max(n_prompt - TAIL, 0), n_prompt),
           "decode": range(n_prompt, n_prompt + n_out)}
    if n_prompt > dense_len + AROUND:
        out["around_dense_len"] = range(dense_len - 1 - AROUND,
                                        dense_len - 1 + AROUND)
    return out


# ---------------------------------------------------------------------------
# the tap
# ---------------------------------------------------------------------------

class Tap:
    """Every call the engines' programs make of ``forward``, seen from the
    host: per request the tokens fed, the logits at the compared positions
    and the sets of blocks at every position."""

    def __init__(self):
        self.prompts, self.want = [], []
        self.fed, self.got, self.sets = [], [], []
        self.current = None
        self.owner = {}
        self.lane_of = {}       # request -> the lane that held it
        self.alive_steps = {}

    def watch(self, ids, positions):
        self.prompts.append(np.asarray(ids, np.int32))
        self.want.append(set(positions))
        for store in (self.fed, self.got, self.sets):
            store.append({})
        return len(self.prompts) - 1

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
            logits, cache, sets = real(
                params, cfg, tokens, pos, cache, last_idx=last_idx,
                live=live, return_all=True, with_picks=True, **kw)
            alive = jnp.bool_(True) if live is None else live
            zero = jax.pure_callback(
                self._see, jax.ShapeDtypeStruct((), jnp.float32),
                tokens, pos, logits, sets, alive,
                vmap_method="broadcast_all")
            idx = S - 1 if last_idx is None else last_idx
            row = jax.lax.dynamic_index_in_dim(logits, idx, keepdims=False)
            return row + zero, cache

        generate.forward = batched.forward = tapped

    def _see(self, tokens, pos, logits, sets, alive):
        tokens, pos = np.asarray(tokens), np.asarray(pos)
        alive = np.asarray(alive)
        if pos.ndim and tokens.shape[1] == 1:          # lanes of one step
            k = int(alive.sum())
            self.alive_steps[k] = self.alive_steps.get(k, 0) + 1
            for lane in range(pos.shape[0]):
                self._lane_step(lane, int(tokens[lane, 0]), int(pos[lane]),
                                logits[lane, 0], sets[lane][:, :, 0],
                                bool(alive[lane]))
        elif tokens.shape[0] > 1:
            self._slice(tokens, int(pos), logits, sets)
        elif self.current is not None:
            self._step(self.current, int(tokens[0]), int(pos), logits[0],
                       sets[:, :, 0])
        return np.zeros(pos.shape, np.float32)

    def _slice(self, tokens, off, logits, sets):
        first = [] if self.current is None else [self.current]
        for j in first + list(range(len(self.prompts))):
            ids = self.prompts[j]
            m = min(len(tokens), len(ids) - off)
            if m > 0 and np.array_equal(ids[off:off + m], tokens[:m]):
                sets = np.asarray(sets)
                for p in range(off, off + m):
                    self._step(j, int(ids[p]), p, logits[p - off],
                               sets[:, :, p - off])
                return

    def _step(self, j, token, pos, logits, sets):
        self.fed[j][pos] = token
        self.sets[j][pos] = np.asarray(sets)
        if pos in self.want[j]:
            self.got[j][pos] = np.asarray(logits, np.float32)

    def _lane_step(self, lane, token, pos, logits, sets, alive):
        if not alive:
            self.owner.pop(lane, None)
            return
        j, want = self.owner.get(lane, (None, None))
        if want != pos:         # a request's first step on this lane
            starts = [i for i, ids in enumerate(self.prompts)
                      if len(ids) == pos and pos not in self.fed[i]]
            if not starts:
                self.owner.pop(lane, None)
                return
            j = starts[0]
        self._step(j, token, pos, logits, sets)
        self.owner[lane] = (j, pos + 1)
        self.lane_of[j] = lane

    def save(self, path: str, names: list, extra: dict):
        out = dict(extra)
        for j, name in enumerate(names):
            seq, p = [], 0
            while p in self.fed[j]:
                seq.append(self.fed[j][p])
                p += 1
            at = sorted(q for q in self.got[j] if q < len(seq))
            out[f"seq_{name}"] = np.asarray(seq, np.int32)
            out[f"pos_{name}"] = np.asarray(at, np.int32)
            out[f"logits_{name}"] = np.stack([self.got[j][q] for q in at])
            out[f"sets_{name}"] = np.packbits(np.stack(
                [self.sets[j][q] for q in range(len(seq))], axis=2), axis=-1)
        np.savez(path, **out)


# ---------------------------------------------------------------------------
# the program's phases
# ---------------------------------------------------------------------------

def words_for(eng, cfg_doc, n_tokens: int, seed: int):
    """(text, ids) of a chat prompt of exactly ``n_tokens`` tokens, chat
    template and all, in the vocabulary's words."""
    import random

    from ggufgen import vocab_of

    word = vocab_of(cfg_doc).word
    rng = random.Random(seed)
    words = [word(rng.randrange(26 ** 3)) for _ in range(n_tokens)]
    n = n_tokens
    for _ in range(8):
        ids = eng.tokenize_messages(messages_of(" ".join(words[:n])))
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


def watch_all(tap, eng, cfg_doc, plan):
    texts = {}
    for j, (name, n_prompt, n_out) in enumerate(plan["requests"]):
        text, ids = words_for(eng, cfg_doc, n_prompt, plan["seed"] + j)
        texts[name] = (text, n_out)
        tap.watch(ids, {p for r in kept(n_prompt, n_out,
                                        eng.cfg.sp_dense_len).values()
                        for p in r})
    return texts


def phase_lanes(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    tap = Tap()
    tap.install()
    t0 = time.time()
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    eng = ContinuousEngine(path, batch_size=lanes, **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    texts = watch_all(tap, eng, cfg_doc, plan)
    t0 = time.time()
    fill = [eng.submit(messages_of(words_for(
        eng, cfg_doc, n, plan["seed"] + 100 + i)[0]), max_tokens=out,
        seed=plan["seed"] + 100 + i)
        for i, (n, out) in enumerate(plan["fillers"][:lanes - 1])]
    text, n_out = texts["chat"]
    chat = eng.submit(messages_of(text), max_tokens=n_out + 1,
                      seed=plan["seed"])
    for f in fill + [chat]:
        f.result()
    text, n_out = texts["long"]
    eng.submit(messages_of(text), max_tokens=n_out + 1,
               seed=plan["seed"] + 1).result()
    jax.effects_barrier()
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())},
        counters=eng.cache_read_gauges())
    eng.shutdown()
    # the long request was the last admitted: its lane still holds its
    # state (a lane that holds no request is not stepped)
    lane = tap.lane_of[list(texts).index("long")]
    tap.save(os.path.join(work, "lanes.npz"), list(texts), {
        "state_long": np.asarray(eng._bstate["cache"]["state"][lane])})
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
    # the long one first: the chat request then starts over what it left
    for j, name in reversed(list(enumerate(texts))):
        text, n_out = texts[name]
        tap.current = j
        eng.create_chat_completion(messages_of(text), max_tokens=n_out + 1,
                                   seed=plan["seed"] + j)
        jax.effects_barrier()
    say(note="serial engine done", seconds=round(time.time() - t0, 1))
    tap.save(os.path.join(work, "serial.npz"), list(texts), {})
    return 0


def phase_state(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    """The state's arithmetic on given inputs: the readings go to
    ``state.json`` for the verdict."""
    import jax
    import jax.numpy as jnp

    import reference_sala as ref
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models import sala
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import use_interpret
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.linstate import lin_state_step

    kw = engine_kwargs(cfg_doc)
    cfg = ModelConfig.from_gguf(GGUFFile(path), n_ctx=kw["n_ctx"])
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    _, n_prompt, n_out = plan["requests"][1]
    C, H, hd = kw["prefill_chunk"], cfg.lin_heads, cfg.head_dim
    n = n_prompt + n_out
    rng = np.random.default_rng(plan["seed"])
    q, k, v = (jnp.asarray(rng.standard_normal((n, H, hd)), jnp.bfloat16)
               for _ in range(3))
    slopes = sala.decay_slopes(cfg)
    # lambda as the host has it in float64: a device's exp is off by 1e-6
    # of a value near 1, and the recurrence multiplies by it 12336 times
    lams = np.exp(-slopes.astype(np.float64))
    itp = use_interpret()
    chunk = jax.jit(sala.lin_slice)
    step = jax.jit(jax.vmap(
        lambda q, k, v, leaf, live, decay: lin_state_step(
            q, k, v, leaf, jnp.int32(0), live, decay, interpret=itp),
        in_axes=(None, None, None, 0, 0, None)))
    live = jnp.arange(lanes) == lanes // 2
    t0, out = time.time(), {}
    for layer in sorted({0, len(slopes) - 1}):
        slope = jnp.asarray(slopes[layer])
        lam = jnp.asarray(lams[layer], jnp.float32)
        state = jnp.zeros((H, hd, hd), jnp.float32)
        for a in range(0, n_prompt, C):
            m = min(C, n_prompt - a)
            qs, ks, vs = (jnp.concatenate(
                [x[a:a + m], jnp.ones((C - m, H, hd), x.dtype)])
                for x in (q, k, v))
            _, state = chunk(qs, ks, vs, state, slope, jnp.int32(m))
        leaf = jnp.zeros((lanes, 1, H, hd, hd), jnp.float32).at[
            lanes // 2, 0].set(state)
        outs = []
        for t in range(n_prompt, n):
            o, leaf = step(q[t], k[t], v[t], leaf, live, lam)
            outs.append(o[lanes // 2])
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            want_o, want_s = ref.recurrence(*f32, lam)
            bad_o, bad_s = ref.recurrence(*f32, lam, jnp.bfloat16)
        out[str(layer)] = {
            "device_exp_off": float(np.max(np.abs(np.asarray(
                jnp.exp(-slope), np.float64) / lams[layer] - 1.0))),
            "program_state": rel(leaf[lanes // 2, 0], want_s),
            "program_out": rel(jnp.stack(outs), want_o[n_prompt:]),
            "rounded_state": rel(bad_s, want_s),
            "rounded_out": rel(bad_o[n_prompt:], want_o[n_prompt:])}
        say(note="state on given inputs", linear_layer=layer,
            positions=n, slices_of=C, steps=n_out, lanes=lanes,
            kernel="interpret" if itp else "compiled",
            s=round(time.time() - t0, 1), **out[str(layer)])
    with open(os.path.join(work, "state.json"), "w") as f:
        json.dump(out, f)
    return 0


# ---------------------------------------------------------------------------
# the reference, and the verdict
# ---------------------------------------------------------------------------

CONTROLS = {"bfloat16": {"emulate": "bfloat16"},
            "bfloat16_state": {"state_dtype": "bfloat16"},
            "topk_less": {"topk_less": True},
            "no_window": {"no_window": True}}


def counted(got, theirs, scores, rows) -> int:
    """Sets of ``got`` (L, H, S, B) at the positions ``rows`` that differ
    from ``theirs`` beyond the margin (``tests/test_sala.py counted``)."""
    n = 0
    sub = (got[:, :, rows] != theirs[:, :, rows]).any(-1)
    for layer, head, r in zip(*np.nonzero(sub)):
        idx = (layer, head, rows[r])
        missing = np.sort(scores[idx][theirs[idx] & ~got[idx]])[::-1]
        extra = np.sort(scores[idx][got[idx] & ~theirs[idx]])[::-1]
        if len(missing) != len(extra) or np.any(
                missing - extra > MARGIN * np.maximum(missing, 1e-30)):
            n += 1
    return n


def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str,
                    where: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_sala as ref

    hp, tensors = ref.open_model(path)
    dense_len, B = hp["dense_len"], hp["block_size"]
    runs = {}
    for engine in ("lanes", "serial"):
        f = os.path.join(work, engine + ".npz")
        if os.path.exists(f):
            doc = np.load(f)
            for name, _, _ in plan["requests"]:
                seq = doc[f"seq_{name}"]
                nb = -(-len(seq) // B)
                sets = np.unpackbits(doc[f"sets_{name}"], axis=-1)[
                    ..., :nb].astype(bool)
                runs[f"{engine}.{name}"] = dict(
                    seq=seq, pos=doc[f"pos_{name}"],
                    logits=doc[f"logits_{name}"], sets=sets,
                    state=doc.get(f"state_{name}"))
    calibrated = "lanes.long" if "lanes.long" in runs else sorted(runs)[-1]
    device = jax.devices(where)[0]
    t0 = time.time()
    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        emb = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))
        xs = {k: hp["emb_scale"] * emb[jnp.asarray(r["seq"], jnp.int32)]
              for k, r in runs.items()}
        cal = {v: xs[calibrated] for v in CONTROLS}
        del emb
        own = {k: [] for k in list(runs) + list(CONTROLS)}
        scores = {k: [] for k in list(runs) + list(CONTROLS)}
        states = {k: [] for k in list(runs) + list(CONTROLS)}
        seen = {"lin": 0, "sp": 0}
        for i, kind in enumerate(hp["mixers"]):
            w = ref.layer_weights(tensors, i)
            n = seen[kind]
            for k, r in runs.items():
                if kind == "lin":
                    xs[k], last = ref.lin_layer(hp, w, xs[k], n,
                                                want_state=True)
                    states[k].append(np.asarray(last))
                else:
                    xs[k], mine, sc = ref.sp_layer(hp, w, xs[k],
                                                   picks=r["sets"][n])
                    own[k].append(np.asarray(mine))
                    scores[k].append(np.asarray(sc))
            for v, kw in CONTROLS.items():
                kw = {key: getattr(jnp, val) if isinstance(val, str) else val
                      for key, val in kw.items()}
                if kind == "lin":
                    cal[v], last = ref.lin_layer(hp, w, cal[v], n,
                                                 want_state=True, **kw)
                    states[v].append(np.asarray(last))
                else:
                    cal[v], mine, sc = ref.sp_layer(
                        hp, w, cal[v], picks=runs[calibrated]["sets"][n],
                        **kw)
                    own[v].append(np.asarray(mine))
                    scores[v].append(np.asarray(sc))
            seen[kind] += 1
            say(note="layer", i=i, kind=kind, s=round(time.time() - t0, 1))
        want = {k: np.asarray(ref.head(hp, tensors, xs[k][jnp.asarray(
            r["pos"])])) for k, r in runs.items()}
        at = jnp.asarray(runs[calibrated]["pos"])
        cal = {v: np.asarray(ref.head(
            hp, tensors, x[at],
            getattr(jnp, CONTROLS[v]["emulate"])
            if "emulate" in CONTROLS[v] else None))
            for v, x in cal.items()}
    ok = True
    by_name = {name: (n, out) for name, n, out in plan["requests"]}
    for k, r in runs.items():
        n_prompt, n_out = by_name[k.split(".")[1]]
        where_at = {p: i for i, p in enumerate(r["pos"])}
        for block, rng in kept(n_prompt, n_out, dense_len).items():
            rows = [where_at[p] for p in rng if p in where_at]
            if rows:
                d = rel(r["logits"][rows], want[k][rows])
                ok &= d < LIMIT
                say(held="LIMIT", run=k, block=block, positions=len(rows),
                    reading=d, limit=LIMIT, ok=bool(d < LIMIT))
        sparse_rows = np.arange(dense_len - 1, len(r["seq"]))
        if len(sparse_rows):
            theirs, sc = np.stack(own[k]), np.stack(scores[k])
            n_sets = theirs.shape[0] * theirs.shape[1] * len(sparse_rows)
            raw = int((r["sets"][:, :, sparse_rows]
                       != theirs[:, :, sparse_rows]).any(-1).sum())
            share = counted(r["sets"], theirs, sc, sparse_rows) / n_sets
            ok &= share < PICKS
            say(held="PICKS", run=k, sets=n_sets, differ_raw=raw,
                reading=share, limit=PICKS, margin=MARGIN,
                ok=bool(share < PICKS))
    r = runs[calibrated]
    d = rel(cal["bfloat16"], want[calibrated])
    ok &= d < LIMIT
    say(held="LIMIT", control="bfloat16", on=calibrated, reading=d,
        limit=LIMIT, must="pass", ok=bool(d < LIMIT))
    say(printed="a bfloat16 state on the logits", on=calibrated,
        reading=rel(cal["bfloat16_state"], want[calibrated]))
    if r.get("state") is not None:
        say(printed="the engine's state leaf against the reference's own, "
                    "and the reference's with a bfloat16 state",
            on=calibrated, layers=len(states[calibrated]),
            engine=max(rel(r["state"][n], s) for n, s in
                       enumerate(states[calibrated])),
            rounded=max(rel(b, s) for b, s in zip(
                states["bfloat16_state"], states[calibrated])))
    given = os.path.join(work, "state.json")
    if os.path.exists(given):
        with open(given) as f:
            doc = json.load(f)
        mine = max(max(x["program_state"], x["program_out"])
                   for x in doc.values())
        rounded = max(x["rounded_state"] for x in doc.values())
        ok &= mine < STATE < rounded
        say(held="STATE", on="given inputs", layers=sorted(doc),
            reading=mine, limit=STATE, ok=bool(mine < STATE))
        say(held="STATE", control="bfloat16_state", on="given inputs",
            reading=rounded, limit=STATE, must="fail",
            ok=bool(rounded > STATE))
    sparse_rows = np.arange(dense_len - 1, len(r["seq"]))
    if len(sparse_rows):
        theirs = np.stack(own[calibrated])[:, :, sparse_rows]
        mine = r["sets"][:, :, sparse_rows]
        t = sparse_rows[None, None, :, None]
        b = np.arange(mine.shape[-1])
        forced = (b <= t // B) & ((b < hp["init_blocks"]) | (
            b >= (t - hp["window_size"] + 1) // B))
        sound = float(((mine.sum(-1) == theirs.sum(-1))
                       & (mine | ~forced).all(-1)).mean())
        ok &= sound == 1.0
        say(held="STRUCT", on=calibrated, sets=int(mine[..., 0].size),
            reading=sound, limit=1.0, ok=bool(sound == 1.0))
        for v in ("topk_less", "no_window"):
            share = counted(r["sets"], np.stack(own[v]), np.stack(scores[v]),
                            sparse_rows) / mine[..., 0].size
            ok &= share > PICKS
            say(held="PICKS", control=v, on=calibrated, reading=share,
                limit=PICKS, must="fail", ok=bool(share > PICKS))
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="minicpm-sala-9b-q4km-8lane")
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--phase",
                    choices=("lanes", "serial", "state", "reference"))
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
        return {"lanes": phase_lanes, "serial": phase_serial,
                "state": phase_state}[args.phase](
            cfg_doc, path, plan, args.work)
    work = args.work or os.path.join(bench.CACHE, f"compare_sala_{args.seed}")
    os.makedirs(work, exist_ok=True)
    bench.ensure_gguf(cfg_doc)
    env = dict(os.environ)
    if cfg_doc.get("platform") == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    for phase in [p for p in args.only.split(",") if p] + ["state",
                                                            "reference"]:
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
