#!/usr/bin/env python3
"""The program against the plain reference of the ``deepseek32`` block, at
the configuration's published widths, outside any timed window, on what the
cell ``dsv32.agent-16sat`` times.  After ``compare_mla.py``, whose tap,
requests and helpers this file imports.

    python3 benchmarks/compare_dsa.py --config <name> --seed <n>

On the configuration's GGUF file (written as ``run.py`` writes it) ONE
request of seeded words goes through the engines the cell times: a system
line of 8192 words + a turn that brings the prompt to 8576 tokens, 160
decoded (``agent-closed-16``'s medians), in ``ContinuousEngine`` beside 15
other live lanes, every admission a LANE-CLAIM HIT that copies BOTH leaves
(the latents and the index keys of the system line) into the scratch cache;
and once more through the serial ``Engine`` (an explicit seed: a full
prefill).  Every query of the request stands at 8.2k-8.7k positions: the
indexer scores four times what the selection keeps.

The tap (``compare_mla.Tap``, with the indexer's outputs beside the
routers'): the name ``forward`` in ``models/generate.py`` and
``parallel/batched.py`` is wrapped so that the head is applied to every
position and the logits, the routers' picks and, for a pass of at most 256
rows (a decode step, a narrow slice: a wide slice's scores would be 400 MB
a call), the indexer's scores and selection reach the host.  The reference
(``reference_dsa.py``: float32 at ``highest``, expanded keys and values, the
indexer and the selection written out, the same share of experts) then runs
on the request's prompt and the tokens the engine fed, a layer at a time
while it is dequantized, on the PROGRAM's picks and selection wherever the
tap saw them (a near-tie at rank 2048 or at the router's rank 8 may go the
other way under bf16 rounding; both then sum the same sets) and on its own
elsewhere (the claimed prefix, a wide slice's rows).

Three processes, each with the device to itself: ``--phase lanes``,
``--phase serial``, ``--phase reference`` (which also gives the verdict).

What is held (PERF.md section 6 has the readings each limit stands between):

``LIMIT`` on ``|got - want| / |want|`` over the vocabulary, over each block
of compared positions (the 64 positions after the claimed prefix, a
prompt's last 64, the decode steps), as ``compare_mla.py``'s.  Below it:
the engines, and the reference with every matmul and attention input
rounded to bfloat16.  Above it: those inputs rounded to float8_e4m3fn (the
precision below the one the configuration states), and the reference with
the selection OFF (``no_select``: the dense layer this model is not).

``SCORE`` on the indexer's scores of each layer at the rows the tap saw,
over each row's causal part, relative to their norm.  With seeded random
weights a score is a sum of 64 SIGNED terms that nearly cancel, so what a
bf16 stream leaves of the layers before (1-2 % of a hidden state) shows
eight times as large in it: under 1 % in the first layer, whose input is
the embedding itself, 8-9 % from the second on.  That this is inherited and
not the indexer's own is READ, not argued: the variant ``bfloat16_stream``
(every matmul input rounded to bfloat16 AND the hidden state rounded to
bfloat16 after each branch, as the program carries it) is printed beside
the program, and ``bfloat16_stream.float32_indexer`` is the indexer in
float32 on that stream's hidden states, the stream's share alone.  Below the
limit: the program, and the reference with every matmul input rounded to
bfloat16, with and without the bfloat16 stream.
Above it: those inputs rounded to float8_e4m3fn, the reference with every
``w_h`` dropped, and with the indexer's rotation on interleaved pairs.

``PICKS`` on the selection (``reference_dsa.picks_at_fault``): the share
of the program's picks, over the tapped rows of a layer, whose reference
score lies below the reference's 2048th largest of the row by more than
``SLACK`` x the row's spread (a row that is not exactly ``min(2048, t +
1)`` positions at or below ``t`` is at fault whole).  Below it: the
program (picks near rank 2048 go either way under the scores' rounding,
which is why the reference is then fed the program's selection).  Above
it: the two controls of ``SCORE``, under which three picks of four are
at fault.

``SUMS`` on the weighted sum over the 64 heads at GIVEN operands (the
reference's own qI, kI and w of the first layer at the decode positions,
rounded to bfloat16 as the program's are): the program's ``index_scores``
against the float32 sum.  Below it: the program (float32 products, relu,
weights and sums).  Above it: per-head scores, weights and partial sums
rounded to bfloat16, which against the whole model hides behind the bf16
operands that the program is allowed.

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
from compare_eva import engine_kwargs, find_config, rel, say  # noqa: E402
from compare_mla import (                                     # noqa: E402
    LIMIT, Tap, kept, messages_of, note_loaded, plan_of, rows_that_differ,
    words_for, system_line)

# PERF.md section 6 (my chip runs, PR 58) has every reading these stand
# between.
SCORE = 0.25
SLACK = 0.03
PICKS = 0.05         # share of the tapped rows' picks
SUMS = 1e-4
TAP_ROWS = 256       # a pass wider than this hands out no scores


def dsa_plan(cfg_doc: dict, seed: int) -> dict:
    """``compare_mla.plan_of`` without the long document: the agent request
    alone."""
    plan = plan_of(cfg_doc, seed)
    plan["requests"] = [r for r in plan["requests"] if r[0] == "agent"]
    return plan


# ---------------------------------------------------------------------------
# the tap
# ---------------------------------------------------------------------------

class IndexTap(Tap):
    """``compare_mla.Tap`` and, per watched request, the indexer's
    selection at every position a pass of at most ``TAP_ROWS`` rows
    computed, and its scores at the compared ones."""

    def __init__(self):
        super().__init__()
        self.sel, self.scores = [], []
        self._index = self._row = None

    def watch(self, ids, positions):
        j = super().watch(ids, positions)
        self.sel.append({})
        self.scores.append({})
        return j

    def install(self):
        import jax
        import jax.numpy as jnp

        from llama_fastapi_k8s_gpu_tpu.models import generate, mla
        from llama_fastapi_k8s_gpu_tpu.parallel import batched

        real = mla.forward      # (``llama.forward`` hands this kind to it)

        def tapped(params, cfg, tokens, pos, cache, last_idx=None,
                   live=None, with_stats=False, **kw):
            S = tokens.shape[0]
            wide = S > TAP_ROWS
            logits, cache, stats, picks, *index = real(
                params, cfg, tokens, pos, cache, last_idx=last_idx,
                live=live, return_all=True, with_stats=True,
                with_picks=True, with_index=not wide, **kw)
            if wide:
                index = [jnp.zeros((1, 1, 1), jnp.float32),
                         jnp.zeros((1, 1, 1), jnp.bool_)]
            alive = jnp.bool_(True) if live is None else live
            zero = jax.pure_callback(
                self._see_index, jax.ShapeDtypeStruct((), jnp.float32),
                tokens, pos, logits, picks, alive, *index,
                vmap_method="broadcast_all")
            idx = S - 1 if last_idx is None else last_idx
            row = jax.lax.dynamic_index_in_dim(logits, idx, keepdims=False)
            return (row + zero, cache) + ((stats,) if with_stats else ())

        generate.forward = batched.forward = tapped

    def _see_index(self, tokens, pos, logits, picks, alive, scores, sel):
        sel = np.asarray(sel)
        self._index = None if sel.shape[-1] == 1 else (np.asarray(scores),
                                                       sel)
        self._row = lambda p: (slice(None), 0)      # a serial decode step
        return self._see(tokens, pos, logits, picks, alive)

    def _slice(self, tokens, off, logits, picks):
        self._row = lambda p: (slice(None), p - off)
        super()._slice(tokens, off, logits, picks)

    def _lane_step(self, lane, token, pos, logits, picks, alive):
        self._row = lambda p: (lane, slice(None), 0)
        super()._lane_step(lane, token, pos, logits, picks, alive)

    def _step(self, j, token, pos, logits, picks):
        super()._step(j, token, pos, logits, picks)
        if self._index is None:
            return
        at = self._row(pos)
        self.sel[j][pos] = np.packbits(self._index[1][at], axis=-1)
        if pos in self.want[j]:
            self.scores[j][pos] = self._index[0][at][:, :pos + 1].copy()

    def save(self, path: str, names: list, extra: dict):
        out = dict(extra)
        for j, name in enumerate(names):
            n = len(self.prompts[j])
            while n in self.fed[j]:
                n += 1
            have = sorted(q for q in self.sel[j] if q < n)
            at = sorted(q for q in self.scores[j] if q < n)
            out[f"sel_at_{name}"] = np.asarray(have, np.int32)
            # (L, rows, n_ctx / 8) packed bits
            out[f"sel_{name}"] = np.stack([self.sel[j][q] for q in have], 1)
            out[f"scored_at_{name}"] = np.asarray(at, np.int32)
            scores = np.full((self.scores[j][at[0]].shape[0], len(at), n),
                             np.nan, np.float32)
            for r, q in enumerate(at):
                scores[:, r, :q + 1] = self.scores[j][q]
            out[f"scores_{name}"] = scores
        tmp = path + ".base.npz"
        super().save(tmp, names, out)
        os.replace(tmp, path)


# ---------------------------------------------------------------------------
# the program's phases
# ---------------------------------------------------------------------------

def watch_agent(tap, eng, cfg_doc, plan, claimed: int):
    (name, n_prompt, n_out), = plan["requests"]
    system = system_line(cfg_doc, plan["system"])
    text, ids = words_for(eng, cfg_doc, system, n_prompt, plan["seed"])
    tap.watch(ids, {p for r in kept(name, n_prompt, n_out, claimed).values()
                    for p in r})
    return system, text, n_out


def phase_lanes(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    tap = IndexTap()
    tap.install()
    t0 = time.time()
    lanes = plan["lanes"]
    kw = engine_kwargs(cfg_doc)
    eng = ContinuousEngine(path, batch_size=lanes, **kw)
    note_loaded(eng, t0)
    chunk = kw["prefill_chunk"]
    claimed = plan["system"] // chunk * chunk
    system, text, n_out = watch_agent(tap, eng, cfg_doc, plan, claimed)
    t0 = time.time()

    def turn(n_words, seed):
        return words_for(eng, cfg_doc, system,
                         plan["system"] + 20 + n_words, seed)[0]

    # every lane serves the system line once and is freed: its claim holds
    # the line's latents AND index keys (``compare_mla.phase_lanes``)
    first = [eng.submit(messages_of(system, turn(24 + i, plan["seed"] + 200 + i)),
                        max_tokens=plan["first_out"]) for i in range(lanes)]
    for f in first:
        f.result()
    before = dict(eng.scheduler_stats())
    fill = [eng.submit(messages_of(system, turn(n, plan["seed"] + 100 + i)),
                       max_tokens=out)
            for i, (n, out) in enumerate(plan["fillers"])]
    agent = eng.submit(messages_of(system, text), max_tokens=n_out + 1)
    for f in fill + [agent]:
        f.result()
    after = dict(eng.scheduler_stats())
    hits = after["lane_prefix_hits"] - before["lane_prefix_hits"]
    jax.effects_barrier()
    snap = eng.expert_counters.snapshot(block=True)
    say(note="lane engine done", seconds=round(time.time() - t0, 1),
        steps_by_live_lanes={str(k): v for k, v in
                             sorted(tap.alive_steps.items())},
        claim_hits=hits, admitted=len(fill) + 1,
        reused_tokens=after["lane_prefix_reused_tokens"]
        - before["lane_prefix_reused_tokens"],
        counters=eng.cache_read_gauges(),
        picks_held=snap["picks_held"], picks_total=snap["picks_total"])
    eng.shutdown()
    tap.save(os.path.join(work, "lanes.npz"), ["agent"],
             {"claim_hits": hits, "admitted": len(fill) + 1})
    return 0


def phase_serial(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax

    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    tap = IndexTap()
    tap.install()
    t0 = time.time()
    eng = Engine(path, **engine_kwargs(cfg_doc))
    note_loaded(eng, t0)
    system, text, n_out = watch_agent(tap, eng, cfg_doc, plan, 0)
    t0 = time.time()
    tap.current = 0
    eng.create_chat_completion(messages_of(system, text),
                               max_tokens=n_out + 1, seed=plan["seed"])
    jax.effects_barrier()
    say(note="serial engine done", seconds=round(time.time() - t0, 1),
        counters=eng.cache_read_gauges())
    tap.save(os.path.join(work, "serial.npz"), ["agent"], {})
    return 0


# ---------------------------------------------------------------------------
# the reference, and the verdict
# ---------------------------------------------------------------------------

def score_error(got, want_rows) -> float:
    """``got`` (rows, S) with NaN past each row's position; ``want_rows``
    the reference's rows: the distance over the causal parts, relative to
    their norm there."""
    seen = ~np.isnan(got)
    return rel(got[seen], np.asarray(want_rows)[seen])


def reference_phase(cfg_doc: dict, path: str, plan: dict, work: str) -> int:
    import jax
    import jax.numpy as jnp

    import reference_dsa as ref

    t0 = time.time()
    hp, tensors = ref.open_model(path)
    topk = hp["index_topk"]
    chunk = engine_kwargs(cfg_doc)["prefill_chunk"]
    claimed = plan["system"] // chunk * chunk
    (name, n_prompt, n_out), = plan["requests"]
    runs = {}
    for engine in ("lanes", "serial"):
        p = os.path.join(work, engine + ".npz")
        if not os.path.exists(p):
            continue
        doc = np.load(p)
        seq = doc[f"seq_{name}"]
        S = len(seq)
        runs[f"{engine}.{name}"] = {
            "seq": seq, "pos": doc[f"pos_{name}"],
            "logits": doc[f"logits_{name}"],
            "have": doc[f"picked_at_{name}"], "picks": doc[f"picks_{name}"],
            "sel_at": doc[f"sel_at_{name}"],
            "sel": np.unpackbits(doc[f"sel_{name}"], axis=-1)[..., :S]
            .astype(bool),
            "scored_at": doc[f"scored_at_{name}"],
            "scores": doc[f"scores_{name}"][..., :S],
            "claimed": claimed if engine == "lanes" else 0}
        if engine == "lanes":
            say(note="lanes", claim_hits=int(doc["claim_hits"]),
                admitted=int(doc["admitted"]))
    calibrated = next(iter(runs))          # lanes.agent where lanes ran
    variants = {"bfloat16": dict(emulate=jnp.bfloat16),
                "bfloat16_stream": dict(emulate=jnp.bfloat16,
                                        stream=jnp.bfloat16),
                "float8": dict(emulate=jnp.float8_e4m3fn),
                "no_select": dict(no_select=True)}
    index_controls = {"no_index_weights": dict(no_index_weights=True),
                      "index_rope_interleaved":
                          dict(index_rope_interleaved=True)}
    readings = {k: {"score": [], "faults": [],
                    **{c: [] for c in index_controls},
                    **{c + ".faults": [] for c in index_controls}}
                for k in runs}
    own_picks = {k: [] for k in runs}
    sums = {}
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(ref.mla.tensor(tensors, "token_embd.weight"))
        xs = {k: emb[jnp.asarray(r["seq"])] for k, r in runs.items()}
        cal = {v: ref._round(xs[calibrated], kw.get("stream"))
               for v, kw in variants.items()}
        del emb
        for i in range(hp["n_layers"]):
            w = ref.indexer_weights(tensors, ref.mla.layer_weights(tensors, i),
                                    i)
            j = i - hp["n_dense"]
            for k, r in runs.items():
                rows = (r["sel_at"], r["sel"][i])
                att, scores, _ = ref.attention(hp, w, xs[k],
                                               use_sel_rows=rows)
                scores = np.asarray(scores)
                at = r["scored_at"]
                readings[k]["score"].append(
                    score_error(r["scores"][i], scores[at]))
                readings[k]["faults"].append(ref.picks_at_fault(
                    r["sel"][i], scores[r["sel_at"]], r["sel_at"], topk,
                    SLACK))
                if k == calibrated:
                    n, c_q = ref.index_inputs(hp, w, xs[k])
                    for c, kw in index_controls.items():
                        other = np.asarray(ref.index_scores(hp, w, n, c_q,
                                                            **kw))
                        readings[k][c].append(
                            score_error(r["scores"][i], other[at]))
                        readings[k][c + ".faults"].append(ref.picks_at_fault(
                            r["sel"][i], other[r["sel_at"]], r["sel_at"],
                            topk, SLACK))
                    if i == 0:
                        sums = given_operands(ref, hp, w, n, c_q, at,
                                              cfg_doc)
                    del n, c_q
                use = None
                if j >= 0:
                    _, _, mine = ref.feed_forward(hp, w, att, i)
                    use = np.asarray(mine).copy()
                    use[r["have"]] = r["picks"][j]
                    own_picks[k].append(np.asarray(mine))
                xs[k] = ref.feed_forward(hp, w, att, i, use_picks=use)[0]
                if k == calibrated:
                    seen = ~np.isnan(r["scores"][i])
                    for v, kw in variants.items():
                        emulate, stream = kw.get("emulate"), kw.get("stream")
                        if stream is not None:
                            # the indexer in float32 on THIS stream: what
                            # the scores inherit from the layers before
                            n, c_q = ref.index_inputs(hp, w, cal[v])
                            readings[k].setdefault(
                                v + ".float32_indexer", []).append(
                                score_error(np.where(seen, np.asarray(
                                    ref.index_scores(hp, w, n, c_q))[at],
                                    np.nan), scores[at]))
                            del n, c_q
                        a, theirs, _ = ref.attention(
                            hp, w, cal[v], emulate, use_sel_rows=rows,
                            no_select=kw.get("no_select", False))
                        if emulate is not None:
                            # the same function at another precision: how
                            # far ITS scores stand from the float32 ones
                            readings[k].setdefault(v, []).append(
                                score_error(np.where(
                                    seen, np.asarray(theirs)[at], np.nan),
                                    scores[at]))
                        # (the program's hidden state is bfloat16 after
                        # each branch: ``stream`` rounds the reference's)
                        cal[v] = ref._round(ref.feed_forward(
                            hp, w, ref._round(a, stream), i, emulate,
                            use_picks=use)[0], stream)
                        del a, theirs
                del att, scores
            say(note="layer", layer=i, seconds=round(time.time() - t0, 1))
            del w
        want = {k: np.asarray(ref.mla.head(hp, tensors, xs[k][r["pos"]]))
                for k, r in runs.items()}
        at = runs[calibrated]["pos"]
        cal = {v: np.asarray(ref.mla.head(hp, tensors, x[at],
                                          variants[v].get("emulate")))
               for v, x in cal.items()}
    ok = True
    for k, r in runs.items():
        for block, rng_ in kept(name, n_prompt, n_out, r["claimed"]).items():
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
        d, f = max(readings[k]["score"]), max(readings[k]["faults"])
        ok &= d < SCORE and f < PICKS
        say(held="SCORE", on=k, rows=len(r["scored_at"]),
            by_layer=readings[k]["score"], reading=d, limit=SCORE,
            ok=bool(d < SCORE))
        say(held="PICKS", on=k, rows=len(r["sel_at"]),
            by_layer=readings[k]["faults"], reading=f, limit=PICKS,
            ok=bool(f < PICKS),
            selected_last_row=int(r["sel"][0][-1].sum()))
        theirs = np.stack(own_picks[k])[:, r["have"]]
        say(printed="rows whose picks differ from the reference's own",
            on=k, reading=rows_that_differ(r["picks"], theirs))
    for v, must in (("bfloat16", "pass"), ("bfloat16_stream", "pass"),
                    ("float8", "fail"), ("no_select", "fail")):
        d = rel(cal[v], want[calibrated])
        good = d < LIMIT if must == "pass" else d > LIMIT
        ok &= good
        say(held="LIMIT", control=v, on=calibrated, reading=d, limit=LIMIT,
            must=must, ok=bool(good))
    say(printed="the float32 indexer on the bfloat16 stream: what a layer's "
        "scores inherit from the layers before", on=calibrated,
        by_layer=readings[calibrated]["bfloat16_stream.float32_indexer"])
    for v, must in (("bfloat16", "pass"), ("bfloat16_stream", "pass"),
                    ("float8", "fail")):
        by_layer = readings[calibrated][v]
        d = max(by_layer)
        good = d < SCORE if must == "pass" else d > SCORE
        ok &= good
        say(held="SCORE", control=v, on=calibrated, by_layer=by_layer,
            reading=d, limit=SCORE, must=must, ok=bool(good))
    for c in index_controls:
        d = min(readings[calibrated][c])
        f = min(readings[calibrated][c + ".faults"])
        good = d > SCORE and f > PICKS
        ok &= good
        say(held="SCORE+PICKS", control=c, on=calibrated,
            by_layer=readings[calibrated][c], reading=d, limit=SCORE,
            faults_by_layer=readings[calibrated][c + ".faults"],
            faults=f, faults_limit=PICKS, must="fail", ok=bool(good))
    for v, must in (("program", "pass"), ("bfloat16_sums", "fail")):
        good = sums[v] < SUMS if must == "pass" else sums[v] > SUMS
        ok &= good
        say(held="SUMS", control=v, on="given operands", reading=sums[v],
            limit=SUMS, must=must, ok=bool(good))
    say(ok=bool(ok), reference_s=round(time.time() - t0, 1))
    return 0 if ok else 1


def given_operands(ref, hp, w, n, c_q, rows, cfg_doc) -> dict:
    """The sum over the heads at GIVEN operands: the reference's own qI, kI
    and w of this layer at the compared rows, rounded to bfloat16, through
    the program's ``index_scores`` (its blocks and head groups as served)
    and through the reference's sum in float32 and in bfloat16."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.mla import index_scores

    q_i, k_i, wts = ref.index_operands(hp, w, n, c_q)
    rows = np.asarray(rows)[-TAP_ROWS:]
    S = k_i.shape[0]
    n_ctx = -(-S // 1024) * 1024
    cfg = ModelConfig(
        vocab_size=8, dim=8, n_layers=1, n_heads=1, n_kv_heads=1, ffn_dim=8,
        n_ctx=n_ctx, kv_lora_rank=hp["r_kv"], index_heads=hp["index_heads"],
        index_dim=hp["index_dim"], index_topk=hp["index_topk"])
    q16 = q_i[rows].astype(jnp.bfloat16)
    k16 = k_i.astype(jnp.bfloat16)
    leaf = jnp.zeros((1, 1, n_ctx, k16.shape[-1]), jnp.bfloat16
                     ).at[0, 0, :S].set(k16)
    got = np.asarray(index_scores(q16, wts[rows], leaf, 0, S - 1, cfg))[:, :S]
    want = np.asarray(ref.weighted_relu_sum(q16, k16, wts[rows]))
    low = np.asarray(ref.weighted_relu_sum(q16, k16, wts[rows],
                                           index_dtype=jnp.bfloat16))
    return {"program": rel(got, want), "bfloat16_sums": rel(low, want)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config",
                    default="deepseek-v3.2-exp-671b-a37b-q4km-ep8-16lane")
    ap.add_argument("--seed", type=int, default=58)
    ap.add_argument("--phase", choices=("lanes", "serial", "reference"))
    ap.add_argument("--work")
    ap.add_argument("--only", default="lanes,serial",
                    help="the engines to run, comma-separated")
    args = ap.parse_args()
    cfg_doc = find_config(args.config)
    plan = dsa_plan(cfg_doc, args.seed)
    if args.phase:
        path = bench.ensure_gguf(cfg_doc)
        if args.phase == "reference":
            return reference_phase(cfg_doc, path, plan, args.work)
        return {"lanes": phase_lanes, "serial": phase_serial}[args.phase](
            cfg_doc, path, plan, args.work)
    work = args.work or os.path.join(bench.CACHE, f"compare_dsa_{args.seed}")
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
