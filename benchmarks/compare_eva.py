#!/usr/bin/env python3
"""The program against the plain reference of the ``evabyte`` block, at the
configuration's published widths, outside any timed window.

    python3 benchmarks/compare_eva.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it), five
chat requests of seeded printable text go through the ENGINES the cell
times: two prompts of 4300-4700 bytes (two windows close in their prefill)
and three of 1990-2040 (their window closes while they decode), each
followed by 64 decode steps or more.  Once through ``Engine`` (one after
another: admission slices into the engine's cache, the serial decode
chunk, every request after the first over what the one before left
behind), once through ``ContinuousEngine`` with the configuration's lanes
(four submitted together, so each joins while others decode and leaves
while others join; the fifth submitted when the first is done, into a
freed lane that still holds its window and 256 summaries): admission slices
into the scratch cache, ``lane_write`` / ``lane_cache_copy`` on the four
leaves, ``lane_decode_chunk`` under its ``live`` mask with dead lanes
walking.  The engines sample what they sample; the reference then runs on
each request's prompt and the tokens the engine fed.

The logits are read by a tap: the name ``forward`` in ``models/generate.py``
and ``parallel/batched.py`` (every call the engines' programs make of the
model) is wrapped so that the head is applied to every position and every
prediction head and the result is handed to the host through
``jax.pure_callback``; the engines get what they asked for (head 0, one
position).  The programs are otherwise the served ones: the same jits, the
same operands, the same caches.

Three processes, so that each has the device to itself (the parent never
imports JAX): ``--phase serial``, ``--phase lanes``, then ``--phase
reference`` (``reference_eva.py``: float32 at ``highest`` matmul precision,
whole sequences, no cache, a layer at a time so that it fits; on the
accelerator where there is one, ``--reference-on cpu`` for the host: the
readings agree to four digits and the host takes 45 minutes, PERF.md
section 6).

What is held, each limit between a sound reading and a control that must
fail it, all on the same sequences (PERF.md section 6 has the readings):

``LIMIT``, on ``|got - want| / |want|`` (Frobenius, all ``vocab * heads``
rows) over each block of positions of each request on each engine (the
last 64 prefill positions, the first 64 decode steps, the 16 positions
after each window edge).  Below it: the engines (0.07-0.09: 32 layers of
fused K-quant matmuls with bfloat16 inputs), and the reference with every
matmul and attention input rounded to bfloat16 (0.036).  Above it: the
reference in float8_e4m3, the nearest precision below (1.10); the
reference without summaries and with a sliding window, over the positions
after an edge (1.3); the serial engine with its decode steps fed one slot
late (0.58).

``SHARE``, for two terms that weigh less in a seeded file than the
engines' own rounding does (0.08 and 0.04 of the logits' norm: no limit on
a distance has room between them and 0.08), each told by its DIRECTION:
the share of a deviation that lies in an error, 1 where the error is that
deviation, 0 where it is rounding.  (a) The current window's finished
chunks made visible: the deviation is the reference with them visible
less the reference, over the last 64 prompt positions of a short request
(1926-2040 into window 0: 120 chunks that must not be seen); the engines'
error there has a share of it near 0, a program that saw them 1.  (b)
``mu``: per layer, the mean over a request's closed chunks of (the
engine's pooled key in the summary leaf less the reference's pooled key
WITHOUT ``mu``) has a share of ``mu`` near 1; the control is the serial
engine run again on the same file with ``mu`` zeroed (near 0).
``SUMMARY`` holds layer 0's leaves to the reference's summaries directly
(under 1 %; without ``mu`` 6 %); deeper layers inherit the stream's
rounding and are printed.

``STREAM``, the float32 residual (``fp32_skip_add``): the model's
``forward`` on one slice of 256 bytes with the embedding table scaled by
4096 in the program and in the reference alike, so that the stream is
large beside what a layer adds, as a trained model's is, and a bfloat16
stream loses the additions.  Sound: the configuration's float32 stream
(0.3 %).  Control: the same program with a bfloat16 stream, what this
repo's dense files carry (1.3 %).  At the file's own scale a bfloat16
stream is one rounding among the bfloat16 matmul inputs the configuration
states and no output can tell it (PERF.md section 6).  (The rehearsal's
tiny file has 3 layers: there the control reads under the limit and the
last line says so.)

Exit 0 iff every reading that is held is on the right side; the last line
says so.
"""

from __future__ import annotations

import argparse
import dataclasses
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

# PERF.md section 6 (my chip runs, PR 35) has every reading these stand
# between.
LIMIT = 0.2
SHARE = 0.5
SUMMARY = 0.02
STREAM = 0.0065
TAIL, DECODE, AFTER_EDGE = 64, 64, 16
LONG, SHORT = (4300, 4700), (1990, 2040)
STREAM_BYTES, STREAM_SCALE = 256, 4096.0
# reply lengths, so that the lanes overlap (a prompt is admitted at one slice
# a wave of 8 steps: the second long one joins 144 steps after the first
# began to decode, the short ones 64 steps apart; the four are live together
# before the first leaves, and the fifth joins a freed lane among dead ones)
MAX_TOKENS = (6 * DECODE + 1, 4 * DECODE + 1, 2 * DECODE + 1, 2 * DECODE + 1,
              DECODE + 1)

VARIANTS = {"float32": {}, "bfloat16": {"emulate": "bfloat16"},
            "float8_e4m3fn": {"emulate": "float8_e4m3fn"},
            "no_summaries": {"no_summaries": True},
            "sliding": {"sliding": True},
            "own_window": {"own_window": True}}


def say(**kw):
    print(json.dumps(kw), flush=True)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def find_config(name: str) -> dict:
    for sub in ("configs", "rehearsal"):
        path = os.path.join(HERE, sub, name + ".json")
        if os.path.exists(path):
            return bench.load_json(path)
    raise SystemExit(f"no configuration {name!r}")


def blocks(n: int, window: int, decoded: int = DECODE) -> dict[str, range]:
    """The compared positions of a sequence with a prompt of ``n`` and
    ``decoded`` steps behind it: the prompt's tail, the first ``DECODE``
    steps, and what follows each window edge."""
    out = {"prefill_tail": range(n - TAIL, n),
           "decode": range(n, n + min(decoded, DECODE))}
    for edge in range(window, n + decoded - AFTER_EDGE + 1, window):
        out[f"after_edge_{edge}"] = range(edge, edge + AFTER_EDGE)
    return out


# ---------------------------------------------------------------------------
# the tap
# ---------------------------------------------------------------------------

class Tap:
    """Every call the engines' programs make of ``forward``, seen from the
    host: per request the tokens fed and the logits of every prediction
    head at every position."""

    def __init__(self, prompts: list):
        self.prompts = [np.asarray(p, np.int32) for p in prompts]
        self.fed = [{} for _ in prompts]      # position -> token
        self.got = [{} for _ in prompts]      # position -> logits (rows,)
        self.current = None     # the serial engine's request
        self.owner = {}         # lane -> (request, next position)
        self.last = {}          # lane -> the last request it held
        self.late = 0           # decode steps fed this many slots late
        self.alive_steps = {}   # lanes live in a step -> steps seen so
        self.on = True

    def install(self):
        import jax
        import jax.numpy as jnp

        from llama_fastapi_k8s_gpu_tpu.models import generate, llama
        from llama_fastapi_k8s_gpu_tpu.parallel import batched

        real = llama.forward

        def tapped(params, cfg, tokens, pos, cache, last_idx=None,
                   live=None, **kw):
            S = tokens.shape[0]
            if S == 1:
                pos = pos + jax.pure_callback(
                    lambda: np.int32(self.late),
                    jax.ShapeDtypeStruct((), jnp.int32))
            logits, cache, *tail = real(
                params, cfg, tokens, pos, cache, live=live,
                return_all=True, all_heads=True, **kw)
            alive = jnp.bool_(True) if live is None else live
            zero = jax.pure_callback(
                self._see, jax.ShapeDtypeStruct((), jnp.float32),
                tokens, pos, logits, alive, vmap_method="broadcast_all")
            idx = S - 1 if last_idx is None else last_idx
            row = jax.lax.dynamic_index_in_dim(logits, idx, keepdims=False)
            return (row[:cfg.vocab_size] + zero, cache, *tail)

        generate.forward = batched.forward = tapped

    def _see(self, tokens, pos, logits, alive):
        tokens, pos = np.asarray(tokens), np.asarray(pos)
        logits, alive = np.asarray(logits), np.asarray(alive)
        if self.on:
            if pos.ndim and tokens.shape[1] == 1:   # lanes of one step
                k = int(alive.sum())
                self.alive_steps[k] = self.alive_steps.get(k, 0) + 1
                for lane in range(pos.shape[0]):
                    self._lane_step(lane, int(tokens[lane, 0]),
                                    int(pos[lane]), logits[lane, 0],
                                    bool(alive[lane]))
            elif tokens.shape[0] > 1:
                self._slice(tokens, int(pos), logits)
            elif self.current is not None:
                j = self.current
                self._step(j, int(tokens[0]), int(pos) - self.late, logits[0])
        return np.zeros(pos.shape, np.float32)

    def _slice(self, tokens, off, logits):
        first = [] if self.current is None else [self.current]
        for j in first + list(range(len(self.prompts))):
            ids = self.prompts[j]
            m = min(len(tokens), len(ids) - off)
            if m > 0 and np.array_equal(ids[off:off + m], tokens[:m]):
                for p in range(off, off + m):
                    self.fed[j][p] = int(ids[p])
                    self.got[j][p] = logits[p - off]
                return

    def _step(self, j, token, pos, logits):
        self.fed[j][pos] = token
        self.got[j][pos] = logits

    def _lane_step(self, lane, token, pos, logits, alive):
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
        self._step(j, token, pos, logits)
        self.owner[lane] = (j, pos + 1)
        self.last[lane] = j

    def sequence(self, j) -> np.ndarray:
        """Request ``j``'s tokens as fed, as far as they are gapless."""
        out, p = [], 0
        while p in self.fed[j]:
            out.append(self.fed[j][p])
            p += 1
        return np.asarray(out, np.int32)


# ---------------------------------------------------------------------------
# the program's phases
# ---------------------------------------------------------------------------

def messages_of(text: str) -> list:
    return [{"role": "user", "content": text}]


def fit_prompts(tokenizer_of, plan: dict) -> tuple[list, list]:
    """Each request's text cut so that its prompt, chat template and all,
    is the planned number of tokens; (texts, token ids)."""
    texts, ids = [], []
    for text, n in zip(plan["texts"], plan["n_prompt"]):
        over = len(tokenizer_of(messages_of(text))) - n
        assert over >= 0, over
        text = text[:len(text) - over - 1] + "a"
        got = tokenizer_of(messages_of(text))
        assert len(got) == n, (len(got), n)
        texts.append(text)
        ids.append(got)
    return texts, ids


def summaries_of(cache: dict, n_tokens: int, cfg, lane=None) -> dict:
    """The closed windows' part of one sequence's summary leaves (of lane
    ``lane`` of a batched cache), on the host: {"sk", "sv": (L, H, chunks,
    hd)} (float16 holds what the bfloat16 leaves hold at these sizes)."""
    import jax.numpy as jnp

    m = n_tokens // cfg.eva_window * (cfg.eva_window // cfg.eva_chunk)
    at = (slice(None),) if lane is None else (lane, slice(None))
    return {k: np.asarray(cache[k][at + (slice(None), slice(m))]
                          .astype(jnp.float32)).astype(np.float16)
            for k in ("sk", "sv")}


def engine_kwargs(cfg_doc: dict) -> dict:
    serve = cfg_doc["serve"]
    return {"n_ctx": int(serve["n_ctx"]), "prefill_chunk": int(
        serve["env"].get("LFKT_PREFILL_CHUNK", 256))}


def note_loaded(eng, t0):
    import jax

    say(note="loaded", engine=type(eng).__name__,
        platform=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        attn_impl=eng.cfg.attn_impl, load_s=round(time.time() - t0, 1),
        weight_formats={k: sorted(v) for k, v in eng.params["layers"].items()
                        if isinstance(v, dict)},
        output=sorted(eng.params["output"]))


def save(work: str, name: str, tap: Tap, extra: dict):
    out = dict(extra)
    for j in range(len(tap.prompts)):
        seq = tap.sequence(j)
        out[f"seq{j}"] = seq
        out[f"pos{j}"] = np.asarray(sorted(
            p for p in tap.got[j] if p < len(seq)), np.int32)
        out[f"logits{j}"] = np.stack(
            [tap.got[j][p] for p in out[f"pos{j}"]]) if len(out[f"pos{j}"]) \
            else np.zeros((0, 0), np.float32)
    np.savez(os.path.join(work, name + ".npz"), **out)


def wanted(n: int, window: int, decoded: int) -> set:
    return {p for r in blocks(n, window, decoded).values() for p in r}


def trim(tap: Tap, window: int):
    """Keep the logits of the compared positions alone."""
    for j, ids in enumerate(tap.prompts):
        keep = wanted(len(ids), window, max(MAX_TOKENS))
        tap.got[j] = {p: v for p, v in tap.got[j].items() if p in keep}


def phase_serial(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    """``Engine``: the five requests one after another, the second short
    one once more with its decode steps one slot late, and the float32
    residual on a large stream."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    tap = Tap([[]])
    tap.install()
    t0 = time.time()
    eng = Engine(path, **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    cfg, W = eng.cfg, eng.cfg.eva_window
    texts, ids = fit_prompts(eng.tokenize_messages, plan)
    late_of = plan["late_of"]
    tap.prompts = [np.asarray(i, np.int32) for i in ids + [ids[late_of]]]
    tap.fed = [{} for _ in tap.prompts]
    tap.got = [{} for _ in tap.prompts]
    extra = {}
    t0 = time.time()
    for j, text in enumerate(texts + [texts[late_of]]):
        tap.current = j
        tap.late = int(j == len(texts))
        eng.create_chat_completion(
            messages_of(text), max_tokens=(MAX_TOKENS + (DECODE + 1,))[j],
            seed=plan["seed"] + j)
        jax.effects_barrier()
        trim(tap, W)
        if j < len(texts):
            for k, v in summaries_of(eng._cache, len(tap.sequence(j)),
                                     cfg).items():
                extra[f"{k}{j}"] = v
    tap.current, tap.late, tap.on = None, 0, False
    # the same engine on a file without mu: the first prompt's two windows
    with_mu = eng.params
    eng.params = dict(with_mu, layers=dict(
        with_mu["layers"], eva_mu=with_mu["layers"]["eva_mu"] * 0))
    eng.create_chat_completion(messages_of(texts[0]), max_tokens=1,
                               seed=plan["seed"])
    extra["sk_no_mu"] = summaries_of(eng._cache, len(ids[0]), cfg)["sk"]
    eng.params = with_mu
    say(note="serial engine done", seconds=round(time.time() - t0, 1),
        fed=[len(tap.sequence(j)) for j in range(len(tap.prompts))])

    # the residual stream on a large embedding: the model's forward, one
    # slice, float32 stream against bfloat16 stream
    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    big = dict(eng.params, tok_emb=eng.params["tok_emb"] * STREAM_SCALE)
    seq = jnp.asarray(plan["stream_seq"][:eng._prefill_chunk], jnp.int32)
    for name, c in (("stream_float32", cfg), ("stream_bfloat16",
                    dataclasses.replace(cfg, fp32_residual=False))):
        lg, _ = jax.jit(lambda p, t, cache, c=c: forward(
            p, c, t, jnp.int32(0), cache, return_all=True, all_heads=True))(
                big, seq, init_cache(c))
        extra[name] = np.asarray(lg, np.float32)
    save(work, "serial", tap, extra)
    return 0


def phase_lanes(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    """``ContinuousEngine`` with the configuration's lanes: four requests
    submitted together, the fifth when the first is done."""
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    tap = Tap([[]])
    tap.install()
    t0 = time.time()
    lanes = int(cfg_doc["serve"]["env"]["LFKT_BATCH_SIZE"])
    eng = ContinuousEngine(path, batch_size=lanes, **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    texts, ids = fit_prompts(eng.tokenize_messages, plan)
    tap.prompts = [np.asarray(i, np.int32) for i in ids]
    tap.fed = [{} for _ in ids]
    tap.got = [{} for _ in ids]
    t0 = time.time()

    def submit(j):
        return eng.submit(messages_of(texts[j]), max_tokens=MAX_TOKENS[j],
                          seed=plan["seed"] + j)

    futs = [submit(j) for j in range(len(texts) - 1)]
    futs[0].result()
    futs.append(submit(len(texts) - 1))
    for f in futs:
        f.result()
    jax.effects_barrier()
    trim(tap, eng.cfg.eva_window)
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        fed=[len(tap.sequence(j)) for j in range(len(ids))],
        last_request_of_lane={str(k): v for k, v in tap.last.items()},
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())})
    # each lane's last request: its summaries are still in the lane
    extra = {}
    cache = eng._bstate["cache"]
    for lane, j in tap.last.items():
        for k, v in summaries_of(cache, len(tap.sequence(j)), eng.cfg,
                                 lane).items():
            extra[f"{k}{j}"] = v
    tap.on = False
    eng.shutdown()
    save(work, "lanes", tap, extra)
    return 0


# ---------------------------------------------------------------------------
# the reference, and the verdict
# ---------------------------------------------------------------------------

def reference_all(path: str, seqs: dict, calibrated, stream_seq, where: str):
    """Float32 logits of every sequence in ``seqs`` ({name: tokens}) and
    their layers' summaries; the other variants' logits of sequence
    ``calibrated``; the large-stream logits of ``stream_seq``.  Every
    sequence and calibration goes through a layer while it is dequantized,
    on the first device of platform ``where``."""
    import jax
    import jax.numpy as jnp

    import reference_eva as ref

    hp, tensors = ref.open_model(path)
    device = jax.devices(where)[0]
    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        emb = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))
        xs = {name: emb[jnp.asarray(s, jnp.int32)] for name, s in seqs.items()}
        cal = {v: xs[calibrated] for v in VARIANTS if v != "float32"}
        stream = emb[jnp.asarray(stream_seq, jnp.int32)] * STREAM_SCALE
        del emb
        summ = {name: [] for name in seqs}
        mus = []
        for i in range(hp["n_layers"]):
            w = ref.layer_weights(tensors, i)
            mus.append(np.asarray(w["attn_eva_mu"]))
            for name in xs:
                xs[name], (kt, beta) = ref.layer(hp, w, xs[name],
                                                 want_summaries=True)
                m = xs[name].shape[0] // hp["window"] \
                    * (hp["window"] // hp["chunk"])
                summ[name].append((np.asarray(kt[:m]), np.asarray(beta[:m])))
            for v in cal:
                kw = dict(VARIANTS[v])
                if "emulate" in kw:
                    kw["emulate"] = getattr(jnp, kw["emulate"])
                cal[v] = ref.layer(hp, w, cal[v], **kw)
            stream = ref.layer(hp, w, stream)
        logits = {name: np.asarray(ref.head(hp, tensors, x))
                  for name, x in xs.items()}
        for v in cal:
            em = VARIANTS[v].get("emulate")
            cal[v] = np.asarray(ref.head(
                hp, tensors, cal[v], getattr(jnp, em) if em else None))
        stream = np.asarray(ref.head(hp, tensors, stream))
    return logits, summ, np.stack(mus), cal, stream


def share_of(error, deviation) -> float:
    """How much of ``deviation`` lies in ``error``: 1 where the error IS
    the deviation, 0 where it is only rounding (which has no direction in
    common with it)."""
    e, d = np.asarray(error, np.float64), np.asarray(deviation, np.float64)
    return float((e * d).sum() / (d * d).sum())


def summary_readings(got: dict, want: list, mus) -> dict:
    """Of one request's summary leaves ``got`` ({"sk", "sv"}: (L, H,
    chunks, hd)) against the reference's per-layer (ktilde, beta): layer
    0's distance with ``mu`` as held and with ``mu`` taken out of the leaf,
    the worst layer's distance, and the least share of ``mu`` over the
    layers in (the mean over the chunks of) the leaf's pooled keys less
    the reference's pooled keys WITHOUT ``mu``."""
    per_layer, shares = [], []
    for i, (kt, beta) in enumerate(want):
        sk = got["sk"][i].transpose(1, 0, 2).astype(np.float32)
        sv = got["sv"][i].transpose(1, 0, 2).astype(np.float32)
        m = min(len(sk), len(kt))
        sk, sv, kt, beta = sk[:m], sv[:m], kt[:m], beta[:m]
        per_layer.append(max(rel(sk, kt), rel(sv, beta)))
        if i == 0:
            out0 = rel(sk - mus[0][None], kt)
        shares.append(share_of((sk - (kt - mus[i][None])).mean(0), mus[i]))
    return {"layer0": per_layer[0], "layer0_mu_taken_out": out0,
            "worst_layer": max(per_layer), "mu_share": min(shares),
            "mu_share_most": max(shares)}


def phase_reference(cfg_doc: dict, path: str, plan: dict, work: str,
                    where: str | None) -> int:
    import jax

    where = where or jax.default_backend()
    W = cfg_doc["window_size"]
    runs = {name: np.load(os.path.join(work, name + ".npz"))
            for name in ("serial", "lanes")}
    n_req = len(plan["n_prompt"])
    seqs = {}
    for name, run in runs.items():
        for j in range(n_req + (name == "serial")):
            seqs[f"{name}{j}"] = run[f"seq{j}"]
    calibrated = f"serial{plan['late_of']}"
    t0 = time.time()
    logits, summ, mus, cal, stream = reference_all(
        path, seqs, calibrated,
        plan["stream_seq"][:len(runs["serial"]["stream_float32"])], where)
    f32 = logits[calibrated]
    n_cal = plan["n_prompt"][plan["late_of"]]
    after = list(range(W, len(f32)))          # every position past the edge
    tail = list(blocks(n_cal, W)["prefill_tail"])
    calib = {v: max(rel(cal[v][list(b)], f32[list(b)])
                    for b in blocks(n_cal, W).values())
             for v in ("bfloat16", "float8_e4m3fn")}
    calib.update({v: rel(cal[v][after], f32[after])
                  for v in ("no_summaries", "sliding")})
    calib["own_window"] = rel(cal["own_window"][tail], f32[tail])
    say(note="reference", on=where, seconds=round(time.time() - t0, 1),
        prompt_bytes=plan["n_prompt"], tail=TAIL, decode=DECODE,
        after_edge=AFTER_EDGE, calibrated_on=n_cal,
        **{"reference_" + v: round(x, 5) for v, x in calib.items()})

    worst = worst_l0 = own_share = 0.0
    mu_share, l0_out, wrong = 1e9, 1e9, []
    own = cal["own_window"][tail] - f32[tail]     # what seeing them does
    for name, run in runs.items():
        for j in range(n_req):
            n = plan["n_prompt"][j]
            pos, got = run[f"pos{j}"], run[f"logits{j}"]
            want = logits[f"{name}{j}"]
            at = {int(p): i for i, p in enumerate(pos)}
            decoded = len(run[f"seq{j}"]) - n
            errs = {}
            for b, r in blocks(n, W, min(decoded, MAX_TOKENS[j] - 1)).items():
                if not all(p in at for p in r):
                    wrong.append(f"{name} request {j}: block {b} not seen")
                    continue
                errs[b] = rel(got[[at[p] for p in r]], want[list(r)])
            if decoded < DECODE:
                wrong.append(f"{name} request {j}: {decoded} steps decoded")
            line = {"note": "program", "engine": name, "request": j,
                    "prompt_bytes": n, "decoded": decoded,
                    **{k: round(v, 5) for k, v in errs.items()}}
            worst = max(worst, *errs.values())
            if j == plan["late_of"] and all(p in at for p in tail):
                # the calibrated prompt: the engines prefilled the same
                line["own_window_share"] = round(share_of(
                    got[[at[p] for p in tail]] - f32[tail], own), 5)
                own_share = max(own_share, abs(line["own_window_share"]))
            if f"sk{j}" in run.files and run[f"sk{j}"].shape[2]:
                s = summary_readings(
                    {k: run[f"{k}{j}"] for k in ("sk", "sv")},
                    summ[f"{name}{j}"], mus)
                line.update({"summaries_" + k: round(v, 5)
                             for k, v in s.items()})
                mu_share = min(mu_share, s["mu_share"])
                worst_l0 = max(worst_l0, s["layer0"])
                l0_out = min(l0_out, s["layer0_mu_taken_out"])
            say(**line)

    # controls that are runs of the program: the serial engine one slot
    # late on the calibrated prompt, the serial engine on a file without
    # mu (the first prompt's windows), a bfloat16 stream
    run, j = runs["serial"], n_req
    at = {int(p): i for i, p in enumerate(run[f"pos{j}"])}
    r = [p for p in blocks(n_cal, W)["decode"] if p in at]
    late = rel(run[f"logits{j}"][[at[p] for p in r]],
               logits[f"serial{j}"][r]) if len(r) == DECODE else 0.0
    sk = run["sk_no_mu"]
    no_mu = summary_readings(
        {"sk": sk, "sv": run["sv0"][:, :, :sk.shape[2]]}, summ["serial0"],
        mus)
    s32 = rel(run["stream_float32"], stream)
    s16 = rel(run["stream_bfloat16"], stream)

    def hold(ok, text):
        if not ok:
            wrong.append(text)

    hold(worst < LIMIT, f"program {worst:.5f} is not under the limit")
    hold(calib["bfloat16"] < LIMIT, "the reference in bfloat16 is not under "
         "the limit")
    for v in ("float8_e4m3fn", "no_summaries", "sliding"):
        hold(calib[v] > LIMIT, f"the reference with {v} is not over the limit")
    hold(late > LIMIT, "the engine one slot late is not over the limit")
    hold(own_share < SHARE, f"the own window's share reads {own_share:.5f}")
    hold(mu_share < 1e9, "no summaries were read")
    hold(mu_share > SHARE, f"mu's share in the leaves reads {mu_share:.5f}")
    hold(no_mu["mu_share_most"] < SHARE, "an engine without mu holds it")
    hold(worst_l0 < SUMMARY, f"layer 0's summaries read {worst_l0:.5f}")
    hold(min(l0_out, no_mu["layer0"]) > SUMMARY,
         "layer 0's summaries without mu are not over their limit")
    hold(s32 < STREAM, f"the float32 stream reads {s32:.5f}")
    hold(s16 > STREAM, "a bfloat16 stream is not over its limit")
    say(ok=not wrong, wrong=wrong, worst=round(worst, 5), limit=LIMIT,
        **{"reference_" + v: round(x, 5) for v, x in calib.items()},
        engine_one_slot_late=round(late, 5),
        own_window_share=round(own_share, 5), own_window_share_control=1.0,
        mu_share=round(mu_share, 5),
        mu_share_engine_without_mu=round(no_mu["mu_share_most"], 5),
        share_limit=SHARE, summaries_layer0=round(worst_l0, 5),
        summaries_layer0_mu_taken_out=round(l0_out, 5),
        summaries_layer0_engine_without_mu=round(no_mu["layer0"], 5),
        summary_limit=SUMMARY, stream_float32=round(s32, 6),
        stream_bfloat16=round(s16, 6), stream_limit=STREAM,
        reference_on=where,
        device={"platform": jax.default_backend(),
                "kind": jax.devices()[0].device_kind})
    return 0 if not wrong else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-bytes", type=int, nargs=4, default=LONG + SHORT,
                    help="the long prompts' range, then the short ones'")
    ap.add_argument("--reference-on", default=None,
                    help="platform the reference runs on (default: the "
                         "accelerator where there is one, else cpu)")
    ap.add_argument("--phase", choices=("serial", "lanes", "reference"),
                    help="one of the three processes (the parent starts "
                         "them)")
    ap.add_argument("--work", help="the phases' directory")
    args = ap.parse_args(argv)
    cfg_doc = find_config(args.config)
    path = bench.ensure_gguf(cfg_doc)
    if args.phase:
        plan = bench.load_json(os.path.join(args.work, "plan.json"))
        if args.phase == "reference":
            return phase_reference(cfg_doc, path, plan, args.work,
                                   args.reference_on)
        phase = phase_serial if args.phase == "serial" else phase_lanes
        return phase(cfg_doc, path, plan, args.work)

    rng = np.random.default_rng(args.seed)
    lo, hi, slo, shi = args.prompt_bytes
    n_prompt = [int(n) for n in rng.integers(lo, hi + 1, size=2)]
    while len(set(n_prompt)) < 5:       # a lane's request is told by it
        n_prompt = n_prompt[:2] + [int(n) for n in
                                   rng.integers(slo, shi + 1, size=3)]
    # printable ASCII, the bytes the traffic is written in; a letter at each
    # end, which no chat template strips
    texts = ["a" + bytes(rng.integers(32, 127, size=n).astype(np.uint8)
                         ).decode("ascii") + "a" for n in n_prompt]
    n_control = cfg_doc["vocab_size"] - 256
    plan = {"seed": args.seed % 2 ** 31, "n_prompt": n_prompt,
            "texts": texts, "late_of": 3,
            "stream_seq": [int(t) for t in n_control + rng.integers(
                32, 127, size=STREAM_BYTES)]}
    work = args.work or os.path.join(os.path.dirname(path), "compare_eva")
    os.makedirs(work, exist_ok=True)
    with open(os.path.join(work, "plan.json"), "w") as f:
        json.dump(plan, f)
    for phase in ("serial", "lanes", "reference"):
        rc = subprocess.call(
            [sys.executable, os.path.abspath(__file__), "--phase", phase,
             "--work", work] + sys.argv[1:])
        if rc:
            say(ok=False, wrong=[f"phase {phase} exited {rc}"])
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
