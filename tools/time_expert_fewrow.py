"""Time the grouped expert call ALONE on the chip (ops/pallas/experts.py
``_grouped_call``), at the five routed configurations' shapes, over the
number of slots in use and, for a call of more than ``ROW_GROUP`` rows, the
rows that reach an expert: the parent commit's call (``--parent``: its
``experts.py``, loaded beside this tree's under another name, with ITS
families: the body and the N tile it hands the call) and this tree's side
by side, the same planes, rows and slots.  A call's results are compared
bit for bit with the first side's (``same_bits``).  A Q4_K call's are
expected EQUAL to a parent's that runs the stacked dense calls' float body
(PR 60 and before): the integer body of PR 61 builds that body's two
bfloat16 planes bit for bit (tier-1, tests/test_qmatmul.py) and makes the
same three dots in the same order, and the sums over a step's K tiles are
taken in the tiles' order, which is the order the grid took them in.  A
Q6_K call's against a parent before PR 59 are not equal: the head's integer
body builds the float body's plane and takes a K tile's float32 sums a
quarter at a time; ``max_rel`` is the largest difference over the largest
result.  ``--q6k-tn 256,1024`` / ``--q4k-tn 512,1024`` time this tree's
Q6_K / Q4_K body under those N tiles, a K tile a grid step, beside its own
rule (``new.tn512``: the body at the old tile), and ``--q4k-tn`` also the
PARENT's Q4_K body under them (``parent.tn1024``: the tile at the old
body).  The ``@8`` / ``@16`` / ``@32`` shapes are ``lfm2``'s gate call at
fewer rows than any served step has: what the rows cost.  Without the
parent's file it times this tree's alone.  ``--layer``
times the whole layer after the router instead (``routed_experts``: the
compaction, the choice between the two calls, the three products, the
gather back and the weighted sum), which is what a decode step pays.

A call takes 50-700 us and a dispatch round trip about 1 ms, so a timing is
the SLOPE of one jitted loop of calls over its trip count (the layer index
walks the planes' leading axis, as a decode step's does): ``(t(3n) - t(n)) /
2n``, each ``t`` the median of ``--reps`` runs.  One JSON line a timing
(PERF.md section 6, PR 51 and PR 53, have the tables).

    git archive --prefix=.parent_check/ <parent> | tar x
    chiprun -- python tools/time_expert_fewrow.py

A device number: it refuses to run without a TPU."""

from __future__ import annotations

import argparse
import copy
import importlib.util
import inspect
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, family, rows of the call, experts held = T, n_out, k_in): a decode
# step's gate / up call (Q4_K) and down call (Q6_K) at 16 lanes (8: olmoe)
FEW = (
    ("lfm2.gate", "q4k", 64, 64, 1536, 2048),
    ("lfm2.gate@8", "q4k", 8, 64, 1536, 2048),      # a call of 16 rows
    ("lfm2.gate@16", "q4k", 16, 64, 1536, 2048),
    ("lfm2.gate@32", "q4k", 32, 64, 1536, 2048),
    ("lfm2.down", "q6k", 64, 64, 2048, 1536),       # K held at 2048
    ("olmoe.gate", "q4k", 64, 64, 1024, 2048),
    ("olmoe.down", "q6k", 64, 64, 2048, 1024),      # folded: 128 rows of 2048
    ("gigachat.gate", "q4k", 128, 32, 2048, 7168),  # K held at 8192
    ("gigachat.down", "q6k", 128, 32, 7168, 2048),
    ("kexaone.gate", "q4k", 128, 16, 2048, 6144),
    ("kexaone.down", "q6k", 128, 16, 6144, 2048),
    ("longcat.gate", "q4k", 192, 64, 2048, 6144),
    ("longcat.down", "q6k", 192, 64, 6144, 2048),
)
USED = (1, 4, 7, 23, 29, 41)
# a call of more than ROW_GROUP rows: slots in use x rows that reach an
# expert (no fewer than the slots, no more than ROW_GROUP: with more the
# layer makes the parent's call)
USED_COMPACTED = (1, 7)
REAL = (1, 8, 12, 24, 64)
# the layer after the router: (name, lanes, picks a token, experts held,
# hidden size, expert width)
LAYER = (
    ("gigachat", 16, 8, 32, 7168, 2048),
    ("kexaone", 16, 8, 16, 6144, 2048),
    ("longcat", 16, 12, 64, 6144, 2048),
    ("olmoe", 8, 8, 64, 2048, 1024),
)
# a wide prefill slice (1024 tokens): (name, family, tokens, picks a token,
# experts the router ranks, experts held, n_out, k_in)
MANY = (
    ("lfm2.gate", "q4k", 1024, 4, 64, 64, 1536, 2048),
    ("lfm2.down", "q6k", 1024, 4, 64, 64, 2048, 1536),
    ("gigachat.gate", "q4k", 1024, 8, 256, 32, 2048, 7168),
    ("gigachat.down", "q6k", 1024, 8, 256, 32, 7168, 2048),
    ("olmoe.down", "q6k", 1024, 8, 64, 64, 2048, 1024),     # folded: 256 rows
    ("longcat.down", "q6k", 256, 12, 768, 64, 6144, 2048),  # a held share
)
LAYERS = 3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=".parent_check/llama_fastapi_k8s_gpu_tpu"
                    "/ops/pallas/experts.py",
                    help="the parent commit's experts.py")
    ap.add_argument("--q6k-tn", default="",
                    help="N tiles to time this tree's Q6_K body under, "
                    "beside its own rule")
    ap.add_argument("--q4k-tn", default="",
                    help="N tiles to time the Q4_K bodies under (this "
                    "tree's, a K tile a step, and the parent's), beside "
                    "their own rules")
    ap.add_argument("--no-many", action="store_true",
                    help="skip the wide-slice shapes")
    ap.add_argument("--no-few", action="store_true",
                    help="skip the decode steps' shapes")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--calls", type=int, default=24,
                    help="n: the loops run n and 3n calls")
    ap.add_argument("--only", default="",
                    help="substrings of the shapes' names, comma-separated")
    ap.add_argument("--used", default="",
                    help="slots in use to time (T is always timed); default "
                    f"{USED}, {USED_COMPACTED} for a call of more than "
                    "ROW_GROUP rows")
    ap.add_argument("--real", default=",".join(map(str, REAL)),
                    help="rows that reach an expert, for such a call")
    ap.add_argument("--group", type=int, default=0,
                    help="this tree's ROW_GROUP for the run (what it keeps "
                    "was chosen with this)")
    ap.add_argument("--all-rows", action="store_true",
                    help="a call of more than ROW_GROUP rows as the layer "
                    "makes it when more than ROW_GROUP reach an expert: all "
                    "the rows, every slot multiplies them")
    ap.add_argument("--layer", action="store_true",
                    help="time the whole layer after the router instead")
    ap.add_argument("--out", default="chiprun_out/time_expert_fewrow.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    if args.group:
        X.ROW_GROUP = args.group

    def wanted(name):
        return any(part in name for part in args.only.split(","))

    sides = {"new": X}
    if os.path.exists(args.parent):
        def load(name, path):
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod

        # the parent's experts.py with the Q6_K body of ITS q6matmul.py
        # (its other imports are this tree's)
        parent = load(X.__name__ + "_parent", args.parent)
        q6 = load(X._q6.__name__ + "_parent", os.path.join(
            os.path.dirname(args.parent), "q6matmul.py"))
        fam = parent.FAMILIES["q6k"]
        fam.kernel = getattr(q6, fam.kernel.__name__)
        sides = {"parent": parent, "new": X}
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"platform {dev.platform!r}: a device "
                          "number comes from a chip"}))
        return 2
    i32 = jnp.int32
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []

    def say(**row):
        row["device_kind"] = dev.device_kind
        lines.append(row)
        print(json.dumps(row), flush=True)

    def planes_of(fam, E, n_out, k_in):
        """Random planes (LAYERS, E, ...) in the family's layout: the
        kernels' time does not depend on the values."""
        f = X.fold_factor(k_in)
        N, K = n_out // f, X.padded_k(k_in) * f
        keys = jax.random.split(jax.random.PRNGKey(N + K), len(fam.planes))
        out = [jax.random.randint(key, (LAYERS, E, N, w * (K // X.TK)), -128,
                                  128, jnp.int8)
               for key, w in zip(keys, fam.widths)]
        out.append(jax.random.normal(keys[-1], (LAYERS, E, K // X.TK, N, 128),
                                     jnp.bfloat16) * 0.01)
        return out, f, N, K

    def loop_of(call, n):
        """n calls in one program, the layer walking the planes."""
        def run(meta, *a):
            def step(i, acc):       # a corner: the add costs nothing
                return acc + call(meta.at[0].set(i % LAYERS), *a)[:8, :128]
            return jax.lax.fori_loop(0, n, step, jnp.zeros((8, 128)))
        return jax.jit(run)

    def slope_us(call, *a):
        """us a call: (t(3n) - t(n)) / 2n, and the single call's result."""
        ts = []
        for n in (args.calls, 3 * args.calls):
            fn = loop_of(call, n)
            fn(*a).block_until_ready()                # compiled
            t = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                fn(*a).block_until_ready()
                t.append(time.perf_counter() - t0)
            ts.append(float(np.median(t)))
        return 1e6 * (ts[1] - ts[0]) / (2 * args.calls), \
            np.asarray(jax.jit(call)(*a))

    def k_steps(fam, tn, K, few):
        """Grid steps along K of a call of ``fam`` under the N tile ``tn``."""
        kt = K // X.TK
        return kt // X._few_k_tiles(kt, tn) if few and getattr(
            fam, "few_k_tiles", False) else kt

    def families(famname, N, K, rows, few):
        """(side, module, family, N tile) of each side to time: the parent
        under its own families, this tree, and this tree's body (a K tile a
        grid step) and, for Q4_K, the parent's under each ``--q6k-tn`` /
        ``--q4k-tn`` tile that divides N."""
        out = [(side, mod, mod.FAMILIES[famname],
                mod.FAMILIES[famname].tn(N, rows, False))
               for side, mod in sides.items()]
        tiles = {"q6k": args.q6k_tn, "q4k": args.q4k_tn}[famname]
        for tn in (int(t) for t in tiles.split(",") if t):
            for (side, mod), own in zip(sides.items(), list(out)):
                if N % tn or (side, famname) == ("parent", "q6k") or (
                        tn == own[3]
                        and k_steps(own[2], tn, K, few) == K // X.TK):
                    continue                # no such tile, or the side's own
                fam = copy.copy(mod.FAMILIES[famname])
                fam.tn = lambda N, rows, interpret, tn=tn: tn
                fam.few_k_tiles = False
                out.append((f"{side}.tn{tn}", mod, fam, tn))
        return out

    def run_sides(label, few, famname, N, K, rows, meta, xpa, extra_in,
                  planes, place=None):
        """``_grouped_call(fam, meta, xpa, planes, rows, few, extra_in,
        interpret)`` of each side on the same operands (and a ``variant``
        for a parent that takes one: PR 60 and before); ``place``: each
        row's place in a compacted call's result."""
        got = {}
        for side, mod, fam, tn in families(
                famname, N, K, xpa.shape[0] if few else rows, few):

            variant = ("resplit",) if "variant" in inspect.signature(
                mod._grouped_call).parameters else ()

            def call(meta, xpa, *rest, mod=mod, fam=fam, variant=variant):
                return mod._grouped_call(
                    fam, meta, xpa, rest[len(extra_in):], xpa.shape[0]
                    if few else rows, few, rest[:len(extra_in)], False,
                    *variant)
            row = dict(label, side=side, TN=tn,
                       steps_a_slot=(N // tn) * k_steps(fam, tn, K, few))
            try:
                us, out = slope_us(call, meta, xpa, *extra_in, *planes)
            except Exception as err:    # a tile the compiler refuses
                say(**row, error=str(err)[-300:])
                continue
            if place is not None:       # back from the places to the rows
                out = np.concatenate([out, np.zeros_like(out[:1])])[place]
            row["us"] = round(us, 2)
            if "_out" in got:
                # a many-row call leaves the tiles past the last in use
                # unwritten: compare the rows of the tiles in use
                live = label["used"] * rows if not few else out.shape[0]
                want = got["_out"][:live]
                row["same_bits"] = bool((out[:live] == want).all())
                row["max_rel"] = float(np.abs(out[:live] - want).max()
                                       / max(np.abs(want).max(), 1e-30))
            else:
                got["_out"] = out
            say(**row)

    def write() -> int:
        with open(args.out, "w") as fh:
            fh.writelines(json.dumps(row) + "\n" for row in lines)
        return 0

    if args.layer:
        time_layers(wanted, X, sides, planes_of, slope_us, say)
        return write()

    # ---- few rows: a decode step's call over the slots in use (and the
    # rows in use, where the layer compacts them: both sides do since PR 53;
    # --all-rows: the call of all the rows it falls back to)
    for name, famname, R, E, n_out, k_in in FEW:
        if not wanted(name) or args.no_few:
            continue
        fam = X.FAMILIES[famname]
        planes, f, N, K = planes_of(fam, E, n_out, k_in)
        T = min(E, R)
        compacted = R > X.ROW_GROUP and not args.all_rows
        rows = R * f + (-(R * f) % 16)
        x = jax.random.normal(jax.random.PRNGKey(1), (R, k_in), jnp.bfloat16)

        def operands(x, row_expert, R=R, f=f, fam=fam):
            """(activations, the rows' experts) as ``grouped_matmul_few``
            hands them to the call."""
            pad = -(R * f) % 16
            xf = jnp.pad(X._fold_rows(x, f, R), ((0, pad), (0, 0)))
            re = jnp.pad(jnp.tile(row_expert, f), (0, pad),
                         constant_values=jnp.iinfo(i32).max)[:, None]
            return X._activations(xf, fam), re

        useds = [int(u) for u in args.used.split(",") if u] or (
            USED_COMPACTED if compacted else USED)
        reals = [int(r) for r in args.real.split(",")
                 if int(r) <= X.ROW_GROUP] if compacted else [R]
        for used in sorted({u for u in useds if u < T} | {T}):
            for real in (r for r in reals if r >= used):
                # `used` experts spread over the held ones, dealt round to
                # `real` rows spread over the call's; no expert elsewhere
                experts = (np.arange(used) * E) // used
                at = (np.arange(real) * R) // real
                row_expert = np.full(R, E, np.int32)
                row_expert[at] = experts[np.arange(real) % used]
                row_expert = jnp.asarray(row_expert)
                _, slots, n_used = X.experts_in_use(row_expert, E, T)
                meta = jnp.concatenate([jnp.zeros(1, i32), n_used[None],
                                        slots])
                xpa, re = operands(x, row_expert)
                back = None
                if compacted:
                    places = X.ROW_GROUP
                    place, src, _ = X.compact_rows(row_expert, E, places)
                    zero = jnp.zeros((1, k_in), x.dtype)
                    xpa, re = operands(
                        jnp.concatenate([x, zero])[src],
                        jnp.concatenate([row_expert, jnp.full(1, E, i32)]
                                        )[src], R=places)
                    # copy j of the row at place p sits at j * places + p
                    place = np.asarray(place)
                    back = np.concatenate(
                        [np.where(place < places, j * places + place,
                                  xpa.shape[0]) for j in range(f)])
                run_sides(dict(regime="few", shape=name, rows=xpa.shape[0],
                               N=N, K=K, T=T, used=used, real=real),
                          True, famname, N, K, rows, meta, xpa, (re,),
                          planes, back)
        del planes
    # ---- many rows: a wide slice's call, the tiles the plan lays out
    for name, famname, M, k, E_all, E, n_out, k_in in MANY:
        if not wanted(name) or args.no_many:
            continue
        fam = X.FAMILIES[famname]
        planes, f, N, K = planes_of(fam, E, n_out, k_in)
        rng = np.random.default_rng(M + E)
        picks = np.stack([rng.permutation(E_all)[:k] for _ in range(M)])
        row_expert = jnp.asarray(np.minimum(picks, E).reshape(-1), i32)
        plan = X.plan_groups(row_expert, E, M, X.TM_MANY)
        T = int(plan["tile_expert"].shape[0])
        used = int(plan["n_used"])
        meta = jnp.concatenate([jnp.zeros(1, i32), plan["n_used"][None],
                                plan["tile_expert"]])
        xp = jax.random.normal(jax.random.PRNGKey(2), (T * X.TM_MANY, k_in),
                               jnp.bfloat16)
        xpa = X._activations(X._fold_rows(xp, f, X.TM_MANY), fam)
        rows = X.TM_MANY * f
        run_sides(dict(regime="many", shape=name, rows=rows, N=N, K=K, T=T,
                       used=used),
                  False, famname, N, K, rows, meta, xpa, (), planes)
        del planes

    return write()


def time_layers(wanted, X, sides, planes_of, slope_us, say) -> None:
    """``routed_experts`` of each side: the layer a decode step runs, at
    the occupancies of the saturated cells (live lanes x the share of their
    picks held here) and with every pick held."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    for name, lanes, k, E, D, F in LAYER:
        if not wanted(name):
            continue
        w = []
        for famname, n_out, k_in in (("q4k", F, D), ("q4k", F, D),
                                     ("q6k", D, F)):
            fam = X.FAMILIES[famname]
            w.append(dict(zip(fam.planes, planes_of(fam, E, n_out, k_in)[0])))
        x = jax.random.normal(jax.random.PRNGKey(3), (lanes, D), jnp.bfloat16)
        wts = jax.random.uniform(jax.random.PRNGKey(4), (lanes, k))
        R = lanes * k
        for real in sorted({r for r in (1, 10, 24, 64, R) if r <= R}):
            rng = np.random.default_rng(real)
            picks = np.full(R, E, np.int32)
            picks[rng.permutation(R)[:real]] = rng.integers(0, E, real)
            picks = jnp.asarray(picks.reshape(lanes, k))
            got = {}
            for side, mod in sides.items():
                def call(meta, x, picks, wts, g, u, d, mod=mod):
                    return mod.routed_experts(x, picks, wts, g, u, d,
                                              meta[0], interpret=False
                                              )[0].astype(jnp.float32)
                us, out = slope_us(call, jnp.zeros(1, jnp.int32), x, picks,
                                   wts, *w)
                row = dict(regime="layer", shape=name, rows=R, real=real,
                           side=side, us=round(us, 2))
                if "parent" in got:
                    row["same_bits"] = bool((out == got["_out"]).all())
                got[side], got["_out"] = us, out
                say(**row)
        del w


if __name__ == "__main__":
    sys.exit(main())
