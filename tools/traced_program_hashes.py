"""Hashes of the TRACED programs of every call that ops/pallas/qmatmul.py,
q6matmul.py and experts.py build, for telling which programs a change to
those files moved: the dense fused matmuls (plain and stacked, both
families, every row regime) and the routed layer after the router at the
five routed configurations' widths (a serial step, the lane engines'
vmapped step, prefill slices), each with the chip's kernels and in
interpret mode, and the grouped calls alone, a family each (``grouped.*``:
a 16-lane decode step's few-row call and a 1024-token slice's many-row one).
Since PR 63 also the dense calls of a K that ends in a tail tile (``tail.*``:
``phi4flash``'s widths; a tree without the tail layout leaves them out),
since PR 64 the families no cell loads (``other.*``: Q5_K, Q8_0, the Q6_K
``pre`` layout).

    python tools/traced_program_hashes.py <tree> <out.json>     # once a tree
    git archive --prefix=.parent_check/ <parent> | tar x
    python tools/traced_program_hashes.py .parent_check /tmp/parent.json
    python tools/traced_program_hashes.py . /tmp/change.json    # then diff

What is hashed is ``jax.make_jaxpr``'s text (the program with its kernels'
bodies in full, the addresses of closures taken out), not ``lower().as_text()``
for the chip: that holds each kernel as serialized MLIR WITH its source
locations (file and line), so it differs between two trees whatever the
code.  Needs no chip; a minute a tree."""

from __future__ import annotations

import hashlib
import inspect
import json
import os
import re
import sys


def hashes(tree: str | None = None, only=None) -> dict:
    """{program: hash} of the package importable now, or of ``tree`` put
    first on the path; ``only(key)``: the programs to trace (all)."""
    if tree is not None:
        sys.path.insert(0, os.path.abspath(tree))
    import jax
    import jax.numpy as jnp

    import llama_fastapi_k8s_gpu_tpu as pkg
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as P
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        fold_factor, padded_k, routed_experts)

    assert tree is None or os.path.realpath(pkg.__file__).startswith(
        os.path.realpath(tree)), pkg.__file__
    bf16, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct

    def traced(fn, *args):
        text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def planes(fmt, n, k, lead=(), t=""):
        sm = S((*lead, k // 2048, n, 128), bf16)
        if fmt == "q4k":
            return {"qs" + t: S((*lead, n, k // 2), i8), "sm" + t: sm}
        return {"q4" + t: S((*lead, n, k // 2), i8),
                "q2" + t: S((*lead, n, k // 4), i8), "sm6" + t: sm}

    def tail_planes(fmt, n, k, lead=()):
        """The planes of a K that ends in a tail tile (PR 63): the whole
        tiles', and the tail's under the ``_t`` keys."""
        tail = k % 2048
        w = planes(fmt, n, 2048, lead, "_t")
        w = {key: S((*a.shape[:-1], a.shape[-1] * tail // 2048), a.dtype)
             if a.dtype == i8 else a for key, a in w.items()}
        return {**planes(fmt, n, k - tail, lead), **w}

    res = {}

    def put(key, thunk):
        if only is None or only(key):
            res[key] = thunk()

    dense = {"q4k": (P.q4k_matmul, P.q4k_matmul_stacked),
             "q6k": (P.q6k_matmul, P.q6k_matmul_stacked)}
    for fmt, (plain, stacked) in dense.items():
        for k, n in ((4096, 4096), (4096, 14336), (14336, 4096),
                     (12288, 4096), (4096, 32000 if fmt == "q6k" else 1024)):
            for rows in (1, 8, 64, 128, 256, 512, 1024):
                for interp in (False, True):
                    if interp and (rows not in (1, 128) or k != 4096):
                        continue
                    tag = f"{fmt}.{k}x{n}.r{rows}." + ("interp" if interp
                                                       else "tpu")
                    put("dense." + tag, lambda: traced(
                        lambda x, w: plain(x, w, interpret=interp),
                        S((rows, k), bf16), planes(fmt, n, k)))
                    put("stacked." + tag, lambda: traced(
                        lambda x, w, i: stacked(x, w, i, interpret=interp),
                        S((rows, k), bf16), planes(fmt, n, k, (2,)),
                        S((), i32)))
    # the families no cell of the benchmark loads (Q5_K split and `pre`,
    # Q8_0, the Q6_K `pre` layout): they share ``qmatmul.py``'s builders
    # (``plain_pallas_call``, ``stacked_pallas_call``) with the two that do
    other = {
        "q5k": (P.q5k_matmul, P.q5k_matmul_stacked, lambda n, k, lead: {
            "q5s": S((*lead, n, k // 2), i8), "q5h": S((*lead, n, k // 8), i8),
            "sm5": S((*lead, k // 2048, n, 128), bf16)}),
        "q5k_pre": (P.q5k_matmul, P.q5k_matmul_stacked, lambda n, k, lead: {
            "q5p": S((*lead, n, k), i8),
            "sm5": S((*lead, k // 2048, n, 128), bf16)}),
        "q8_0": (P.q8_matmul, P.q8_matmul_stacked, lambda n, k, lead: {
            "q8": S((*lead, n, k), i8),
            "sm8": S((*lead, k // 2048, n, 128), bf16)}),
        "q6k_pre": (P.q6k_matmul, P.q6k_matmul_stacked, lambda n, k, lead: {
            "q6p": S((*lead, n, k), i8),
            "sm6": S((*lead, k // 2048, n, 128), bf16)}),
    }
    for fmt, (plain, stacked, shapes) in other.items():
        for rows in (1, 16, 256, 1024):
            for interp in (False, True):
                if interp and rows != 1:
                    continue
                tag = f"{fmt}.4096x4096.r{rows}." + ("interp" if interp
                                                     else "tpu")
                put("other.dense." + tag, lambda: traced(
                    lambda x, w: plain(x, w, interpret=interp),
                    S((rows, 4096), bf16), shapes(4096, 4096, ())))
                put("other.stacked." + tag, lambda: traced(
                    lambda x, w, i: stacked(x, w, i, interpret=interp),
                    S((rows, 4096), bf16), shapes(4096, 4096, (2,)),
                    S((), i32)))
    # a K that ends in a tail tile (``phi4flash``: gate / up, ``ssm_out``, the
    # head); a tree without ``qmatmul.tail_of`` has no such programs
    if hasattr(P.qmatmul, "tail_of"):
        for fmt, (plain, stacked) in dense.items():
            for k, n in ((2560, 10240), (5120, 2560),
                         (2560, 200064 if fmt == "q6k" else 2560)):
                for rows in (16, 1024):
                    tag = f"{fmt}.{k}x{n}.r{rows}.tpu"
                    put("tail.dense." + tag, lambda: traced(
                        lambda x, w: plain(x, w, interpret=False),
                        S((rows, k), bf16), tail_planes(fmt, n, k)))
                    put("tail.stacked." + tag, lambda: traced(
                        lambda x, w, i: stacked(x, w, i, interpret=False),
                        S((rows, k), bf16), tail_planes(fmt, n, k, (2,)),
                        S((), i32)))
    # the routed layer: (name, experts held, D, F, picks a token, tokens)
    for name, E, D, F, k, toks in (
            ("olmoe", 64, 2048, 1024, 8, (1, 8, 128, 512, 1024)),
            ("lfm2", 64, 2048, 1536, 4, (1, 16, 128, 256, 1024)),
            ("gigachat", 32, 7168, 2048, 8, (1, 16, 128, 256, 1024)),
            ("kexaone", 16, 6144, 2048, 8, (1, 16, 128, 256, 1024)),
            ("longcat", 64, 6144, 2048, 12, (1, 16, 128, 256, 1024))):
        def exps(fmt, n, kk):
            f = fold_factor(kk)
            return planes(fmt, n // f, padded_k(kk) * f, (2, E))

        w = (exps("q4k", F, D), exps("q4k", F, D), exps("q6k", D, F),
             S((), i32))
        for t in toks:
            for interp in (False, True):
                if interp and t > 16:
                    continue

                def layer(x, p, wt, g, u, d, i):
                    return routed_experts(x, p, wt, g, u, d, i,
                                          interpret=interp)

                key = f"routed.{name}.t{t}." + ("interp" if interp else "tpu")
                put(key, lambda: traced(
                    layer, S((t, D), bf16), S((t, k), i32), S((t, k), f32),
                    *w))
                if t in (8, 16):    # the lane engines: vmap over lanes
                    put(key + ".vmap", lambda: traced(
                        lambda x, p, wt, g, u, d, i: jax.vmap(
                            lambda a, b, c: layer(a, b, c, g, u, d, i))(
                                x, p, wt),
                        S((t, 1, D), bf16), S((t, 1, k), i32),
                        S((t, 1, k), f32), *w))
        # the grouped calls alone, a family each: a layer's program holds
        # both, so a change to one family's body or tile moves every
        # ``routed.*`` hash and only these tell the families apart
        for fmt, n, kk in (("q4k", F, D), ("q6k", D, F)):
            fam, f = X.FAMILIES[fmt], fold_factor(kk)
            w1 = list(planes(fmt, n // f, padded_k(kk) * f, (2, E)).values())
            # (a tree up to PR 60 hands the call a Q4_K variant too)
            variant = ("resplit",) if "variant" in inspect.signature(
                X.grouped_matmul_few).parameters else ()
            rows = min(16 * k, X.ROW_GROUP)         # a 16-lane decode step
            T = min(E, rows)
            put(f"grouped.{fmt}.{name}.few", lambda: traced(
                lambda m, x, re, *w: X.grouped_matmul_few(
                    fam, m, x, re, w, f, False, *variant),
                S((2 + T,), i32), S((rows, kk), bf16), S((rows,), i32), *w1))
            T = X.n_tiles(1024 * k, E, 1024, X.TM_MANY)
            put(f"grouped.{fmt}.{name}.many", lambda: traced(
                lambda m, x, *w: X.grouped_matmul_many(
                    fam, m, x, w, f, False, *variant),
                S((2 + T,), i32), S((T * X.TM_MANY, kk), bf16), *w1))
    return res


def main(tree: str, out: str) -> int:
    res = hashes(tree)
    with open(out, "w") as fh:
        json.dump(res, fh, indent=0, sort_keys=True)
    print(len(res), "programs hashed ->", out)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
