"""Parameter loading: GGUF tensors → stacked JAX pytrees.

Performs at load time what llama.cpp does lazily per-matmul on GPU: weights
are dequantized once (numpy reference codecs; Pallas dequant kernels take
over on TPU) and placed in HBM in the chosen compute format.  Stacking the
per-layer tensors (axis 0 = layer) is what lets the model scan over layers.

Formats (``ops.linear``):
- ``bf16`` — exact dequant, 2 B/weight.  16 GB for Llama-3-8B: does NOT fit
  one v5e chip; use for small models and CPU tests.
- ``int8`` — symmetric per-channel requant of the dequantized weights,
  1 B/weight (~8.5 GB for 8B incl. bf16 embeddings).
- ``q4k`` — fused serving: Q4_K / Q5_K / Q6_K / Q8_0 tensors stay in
  (nearly) their GGUF bit layouts in HBM (~5 / 6 / 7 / 9 bit/weight) and
  are dequantized in-VMEM by their fused Pallas matmuls (ops/pallas/
  q*matmul.py; a K that their 2048-wide tile does not divide ends in a
  narrow tail tile of the Q4_K / Q6_K layouts where filling would add a
  fifth or more, and is else filled up with zero blocks where that adds at
  most a quarter: ``ops.linear.padded_k``); anything else falls back to
  int8.  The v5e serving
  format: lowest decode HBM traffic at file fidelity.  Because per-layer
  tensors are stacked for ``lax.scan``, the choice is per tensor *name*:
  a name fuses only if every layer's tensor of that name shares one
  eligible type (Q4_K_M files mix in Q6_K for some layers).

GGUF tensor names follow llama.cpp's convention: ``token_embd.weight``,
``blk.{i}.attn_{q,k,v,output}.weight``, ``blk.{i}.ffn_{gate,up,down}.weight``,
``blk.{i}.{attn,ffn}_norm.weight``, ``output_norm.weight``, ``output.weight``
(absent when embeddings are tied).  A routed block (``cfg.n_experts``; the
``olmoe`` architecture) has, in place of the three ``ffn_*`` matrices, the
F32 router ``blk.{i}.ffn_gate_inp.weight`` (E, dim) and the 3-D
``blk.{i}.ffn_{gate,up,down}_exps.weight`` (E, out, in), which stay fused
K-quant planes with a leading (layer, expert) pair of axes
(ops/pallas/experts.py); with ``cfg.qk_norm`` also
``blk.{i}.attn_{q,k}_norm.weight``.  The window + summary cache kind
(``cfg.eva_window``; ``evabyte``) adds the two F32 pooling vectors
``blk.{i}.attn_eva_{phi,mu}.weight`` (n_heads, head_dim), and its float32
logits keep a float output matrix bf16 instead of requantizing it.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..gguf import GGUFFile
from ..obs.devtime import timed_jit
from ..ops import make_linear_bf16, make_linear_int8, make_linear_int8_device
from ..ops.linear import padded_k
from .config import ModelConfig

logger = logging.getLogger(__name__)

_LINEAR_MAKERS = {"bf16": make_linear_bf16, "int8": make_linear_int8}


def flat_layers(layers: dict):
    """(name, leaf) over the stacked layers' leaves, the leaves of a file
    of several layer kinds (``{"lin": {...}, "sp": {...}}``, models/sala.py)
    under ``<kind>.<name>``: what /health's ``weight_formats`` walks."""
    for name, leaf in layers.items():
        if isinstance(leaf, dict) and any(
                isinstance(v, dict) for v in leaf.values()):
            for sub, subleaf in leaf.items():
                yield f"{name}.{sub}", subleaf
        else:
            yield name, leaf


def _tensor_to_device(t, dtype=jnp.float32) -> jax.Array:
    """Raw GGUF bytes → dequantized device array via the Pallas kernels
    (ops/pallas/dequant.py): the host ships quantized bytes, the chip
    expands them."""
    from ..ops.pallas import device_dequant

    flat = device_dequant(t.raw(), t.ggml_type, t.n_elements, dtype)
    return flat.reshape(tuple(reversed(t.shape)))


#: a stacked leaf of this many bytes or more is stacked by ONE program.
#: ``jnp.stack`` outside a program first writes every input again with its
#: new leading axis, so beside the inputs and the result the device holds a
#: third copy: 2.4 GB more for the 64 x 18 expert planes of an ``lfm2moe``
#: file, whose load then peaks at 16.58 GB of a chip's 16.9 (PERF.md
#: section 6, PR 49).  Smaller leaves keep the call that needs no compile.
_ONE_PROGRAM_STACK_BYTES = 2 << 30

_stack_in_one_program = timed_jit(
    "load_stack", jax.jit(lambda *leaves: jnp.stack(leaves)),
    site="models.params")


def _stack(dicts: list[dict], free: bool = False) -> dict:
    """List of identically-keyed (possibly nested) dicts → dict of stacked
    arrays.  ``free=True`` drops each per-layer ref as soon as its stacked
    leaf exists (overlap mode: the inputs are device arrays, so holding all
    of them through the whole stack would double device-memory peak; with
    progressive freeing the peak is ~1× weights + the largest single
    name's stack)."""
    out = {}
    for key in dicts[0]:
        vals = [d[key] for d in dicts]
        if isinstance(vals[0], dict):
            out[key] = _stack(vals, free=free)
        else:
            big = free and sum(v.nbytes for v in vals) \
                >= _ONE_PROGRAM_STACK_BYTES
            out[key] = _stack_in_one_program(*vals) if big \
                else jnp.stack(vals)
            if free:
                del vals
                for d in dicts:
                    d[key] = None
    return out


def load_params(gf: GGUFFile, cfg: ModelConfig, fmt: str = "bf16",
                on_device: bool | None = None,
                fused_types: frozenset | None = None,
                phases_out: dict | None = None,
                fused_experts: bool = True,
                fill_out: dict | None = None) -> dict:
    """Dequantize all tensors from ``gf`` into a stacked param pytree.

    ``on_device=True`` (default on TPU) routes quantized tensors through the
    Pallas dequant kernels and requantizes int8 on device; ``False`` uses
    the numpy reference codecs.  Both produce identical pytrees.

    ``fused_types`` restricts which GGML types may use their fused kernel
    under ``fmt="q4k"`` (default: Q4_K, Q5_K, Q6_K and Q8_0).  The engine passes
    the set of types whose compile probes passed, so a Mosaic regression
    in ONE kernel degrades only that format's tensors to int8.
    ``fused_experts=False`` (the grouped expert kernels failed their probe)
    loads a routed block's experts dequantized.  ``phases_out`` receives
    {"prep" | "head" | "stack": (start, end)} on ``time.time()``,
    ``fill_out`` {"plane_bytes": the fused planes prepared, "fill_bytes":
    those of them that are zero fill} (``/health`` ``weight_fill_share``).
    """
    if on_device is None:
        on_device = jax.default_backend() == "tpu"
    base_fmt = "int8" if fmt == "q4k" else fmt
    make = _LINEAR_MAKERS[base_fmt]

    def _fused_names(names=None, layer_ids=None,
                     rows: dict | None = None) -> dict[str, object]:
        """Linear positions that can serve a fused kernel, mapped to the
        ONE GGML type the whole (L, ...) stack will use — stacked scan
        params need a single layout per name.

        Uniform names use their file type.  Names mixing the K-quants
        (Q4_K/Q5_K/Q6_K — llama.cpp's Q4_K_M ``use_more_bits`` recipe puts
        e.g. half the ffn_down layers on Q6_K and half on Q4_K) are
        PROMOTED to the highest K-quant present: the minority layers are
        requantized onto the finer grid (16-element sub-block scales —
        strictly finer than the int8 per-row fallback this replaces) and
        the whole name stays on the fused decode path at ≤0.88 B/weight.
        ``rows``: {name: output rows it is LOADED with} where the loader
        fills a matrix up with zero rows (:func:`padded_rows`)."""
        from ..gguf.constants import GGMLType
        from ..ops.pallas.qmatmul import q4k_compatible, tail_of

        def fits_padded(n_out, k_in):
            return q4k_compatible(n_out, padded_k(k_in))

        def tail_types(ts, types):
            """``types`` where a name's K ends in a tail tile
            (``ops/pallas/qmatmul.py tail_of``): the two families whose
            layout has one."""
            if any(tail_of(t.shape[0]) for t in ts):
                return [t for t in types
                        if t in (GGMLType.Q4_K, GGMLType.Q6_K)]
            return types

        fusable = tuple(fused_types) if fused_types is not None \
            else (GGMLType.Q4_K, GGMLType.Q5_K, GGMLType.Q6_K, GGMLType.Q8_0)
        k_rank = {GGMLType.Q4_K: 0, GGMLType.Q5_K: 1, GGMLType.Q6_K: 2}
        from ..ops.pallas.experts import experts_compatible
        from ..ops.pallas.experts import padded_k as experts_padded_k

        if names is None:
            names = ["attn_q", "attn_k", "attn_v", "attn_output"]
            if not cfg.n_experts:
                names += ["ffn_gate", "ffn_up", "ffn_down"]
            elif fused_experts:
                names += ["ffn_gate_exps", "ffn_up_exps", "ffn_down_exps"]
        if layer_ids is None:
            layer_ids = range(cfg.n_layers)
        # (an id may be ``"N.s"``: a sub-block of a ``longcat-flash`` layer)
        ok: dict[str, object] = {}
        for n in names:
            ts = [gf[f"blk.{i}.{n}.weight"] for i in layer_ids]
            # an expert stack (E, out, in): the grouped kernels' two types
            fits, allowed = (
                lambda n_out, k_in: experts_compatible(
                    n_out, experts_padded_k(k_in)),
                [t for t in fusable if t in (GGMLType.Q4_K, GGMLType.Q6_K)]) \
                if n.endswith("_exps") else (
                    fits_padded, tail_types(ts, fusable))
            if not all(fits((rows or {}).get(n, t.shape[1]), t.shape[0])
                       for t in ts):
                continue
            types = {t.ggml_type for t in ts}
            if len(types) == 1:
                t0 = ts[0].ggml_type
                if t0 in allowed:
                    ok[n] = t0
            elif types <= set(k_rank):
                target = max(types, key=k_rank.get)
                if target in allowed:
                    ok[n] = target
        t = gf.tensors.get("output.weight")
        if t is not None and t.ggml_type in tail_types([t], fusable) \
                and fits_padded(*reversed(t.shape)):
            ok["output"] = t.ggml_type
        return ok

    # (a ``deepseek2``, ``exaone-moe`` or ``lfm2moe`` file's layers fuse by
    # kind, in ``ffn_kind_layers`` / ``mixer_ffn_layers``: here its output
    # head alone)
    by_ffn_kind = bool(cfg.kv_lora_rank or cfg.attn_kinds)
    latent_attn = {"wq_a": "attn_q_a", "wq_b": "attn_q_b",
                   "wkv_a": "attn_kv_a_mqa", "wo": "attn_output"}
    latent_norms = (("attn_norm", "attn_norm"), ("q_a_norm", "attn_q_a_norm"),
                    ("kv_a_norm", "attn_kv_a_norm"))
    fused_names = _fused_names(
        [] if by_ffn_kind or cfg.conv_l_cache or cfg.ssm_d_state else None) \
        if fmt == "q4k" else {}

    import time as _time

    fill = {"plane_bytes": 0, "fill_bytes": 0}

    def planes(w: dict, real: int, stored: int) -> dict:
        """``w``, a fused layout's planes as prepared, counted: their bytes
        and those of them that are zero fill (``stored - real`` of ``stored``
        weights: a last K tile filled up, whole zero rows)."""
        size = sum(a.nbytes for a in w.values())
        fill["plane_bytes"] += size
        fill["fill_bytes"] += size * (stored - real) // stored
        return w

    # coarse load-phase attribution, logged at the end: prep (host packers /
    # codecs incl. the raw() mmap page-ins they trigger; with
    # LFKT_LOAD_OVERLAP the transfers they queue), head (embeddings and the
    # output head) and stack (jnp.stack, and the wait for every transfer
    # still in flight), back to back on time.time()

    def lin(name: str, fused_names: dict = fused_names,
            n_rows: int | None = None) -> dict:
        """``n_rows``: output rows a FUSED layout is filled up to with
        all-zero blocks (the caller slices the product)."""
        short = name.split(".")[-2] if name.startswith("blk.") else name.split(".")[0]
        if short in fused_names:
            from ..gguf.constants import GGMLType
            from ..ops.pallas.q5matmul import prep_q5k
            from ..ops.pallas.q6matmul import prep_q6k
            from ..ops.pallas.q8matmul import prep_q8_0
            from ..ops.pallas.qmatmul import prep_q4k

            t = gf[name]
            target = fused_names[short]
            n_out, k_in = tuple(reversed(t.shape))
            k_pad = padded_k(k_in)
            if t.ggml_type != target:
                # K-quant promotion (mixed-type name): dequantize and
                # requantize onto the name's chosen finer grid
                from ..ops.linear import make_linear_q5k, make_linear_q6k

                maker = {GGMLType.Q5_K: make_linear_q5k,
                         GGMLType.Q6_K: make_linear_q6k}[target]
                return planes(maker(np.pad(t.astype_f32(),
                                           ((0, 0), (0, k_pad - k_in)))),
                              k_in, k_pad)
            prep = {GGMLType.Q4_K: prep_q4k, GGMLType.Q5_K: prep_q5k,
                    GGMLType.Q6_K: prep_q6k,
                    GGMLType.Q8_0: prep_q8_0}[target]
            raw = np.asarray(t.raw())
            n_fill = max(n_rows or n_out, n_out)
            if k_pad != k_in or n_fill != n_out:
                # a row's last K tile filled up with all-zero blocks (scale
                # 0: every weight of them is 0), so that the file's own
                # blocks serve the fused kernel (``linear`` pads the
                # activations with zeros to match); whole zero rows alike.
                # (A K that ends in a tail tile is stored as it is:
                # ``padded_k`` returns it, and ``prep`` lays the tail out)
                raw = raw.reshape(n_out, -1)
                raw = np.pad(raw, ((0, n_fill - n_out), (
                    0, raw.shape[1] * (k_pad - k_in) // k_in))).reshape(-1)
            return planes(prep(raw, n_fill, k_pad), n_out * k_in,
                          n_fill * k_pad)
        if on_device:
            w = _tensor_to_device(gf[name])
            if base_fmt == "int8":
                return make_linear_int8_device(w)
            return {"w": w.astype(jnp.bfloat16)}
        return make(gf[name].astype_f32())

    def norm(name: str):
        return jnp.asarray(gf[name].astype_f32(), dtype=jnp.float32)

    def as_bf16(t) -> jax.Array:
        """A tensor dequantized once and kept bf16 (never requantized)."""
        return _tensor_to_device(t, jnp.bfloat16) if on_device \
            else jnp.asarray(t.astype_f32(), dtype=jnp.bfloat16)

    def experts(name: str, fused_names: dict = fused_names) -> dict:
        """A 3-D expert tensor (E, out, in): fused planes with a leading
        expert axis where its name fuses, else dequantized bf16."""
        t = gf[name]
        target = fused_names.get(name.split(".")[-2])
        if target is not None:
            from ..gguf.quants import quantize
            from ..ops.pallas.experts import padded_k as experts_padded_k
            from ..ops.pallas.experts import prep_experts

            k_in, n_out, n_exp = t.shape
            raw = np.asarray(t.raw()) if t.ggml_type == target \
                else quantize(t.astype_f32(), target)   # K-quant promotion
            k_pad = experts_padded_k(k_in)
            if k_pad != k_in:   # zero blocks fill a row's last K tile
                raw = raw.reshape(n_exp * n_out, -1)
                raw = np.pad(raw, ((0, 0), (
                    0, raw.shape[1] * (k_pad - k_in) // k_in))).reshape(-1)
            w = prep_experts(raw, n_exp, n_out, k_pad, target)
            if w is not None:
                return planes(w, k_in, k_pad)
        return {"w": as_bf16(t)}

    # LFKT_LOAD_OVERLAP=1: enqueue each layer's host→device transfer the
    # moment its planes are packed, so the (async) transfers stream while
    # the C++ packers prep the NEXT layers, instead of serializing all
    # packing before all transfer (the default _stack(host arrays) order).
    # The final stack then concatenates resident device arrays.  Default ON
    # since the 2026-08-01 coldstart A/B: 226.5 s -> 180.8 s load (the
    # first request then absorbs ~19 s of still-draining transfers, net
    # 245.8 -> 218.9 s to first token, -11% — coldstart_2026-08-01.json vs
    # coldstart_overlap_2026-08-01.json).
    from ..utils.config import env_bool

    overlap = env_bool("LFKT_LOAD_OVERLAP", default=True)

    def kinds_layers() -> dict:
        """A file of two layer kinds (models/sala.py): {kind: its layers'
        tensors, in the file's order within the kind}; a name fuses by the
        types of ITS kind's layers."""
        from .sala import LIN, SP

        mats = {"wq": "attn_q", "wk": "attn_k", "wv": "attn_v",
                "wo": "attn_output", "wg": "attn_gate", "w_gate": "ffn_gate",
                "w_up": "ffn_up", "w_down": "ffn_down"}
        norms = ["attn_norm", "attn_q_norm", "attn_k_norm", "ffn_norm"]
        out = {}
        for kind in (LIN, SP):
            ids = [i for i, m in enumerate(cfg.mixers) if m == kind]
            fused = _fused_names(list(mats.values()), ids) \
                if fmt == "q4k" else {}
            out[kind] = []
            for i in ids:
                p = f"blk.{i}."
                layer = {key: lin(p + name + ".weight", fused)
                         for key, name in mats.items()}
                for name in norms + (["attn_out_norm"] if kind == LIN else []):
                    layer[name] = norm(p + name + ".weight")
                if overlap:
                    layer = jax.tree.map(jax.device_put, layer)
                out[kind].append(layer)
        return out

    def ffn_kind_layers() -> dict:
        """A file whose feed-forward kind is the layer's (models/routed.py):
        {"dense": the leading layers, "moe": the routed ones}, with the
        attention of ``deepseek2`` (models/mla.py) or of ``exaone-moe``
        (models/hybrid.py).  Nothing is requantized: a matrix no fused
        kernel takes (``attn_q_b``, K = r_q; ``attn_kv_b``, which the
        absorbed form wants per head, W_uk and W_uv) is served bf16 under
        ``q4k``, never int8."""
        from .mla import lat_width
        from .routed import DENSE, MOE

        latent = bool(cfg.kv_lora_rank)
        attn = latent_attn if latent \
            else {"wq": "attn_q", "wk": "attn_k", "wv": "attn_v",
                  "wo": "attn_output"}
        norms = (*latent_norms, ("ffn_norm", "ffn_norm")) \
            if latent else (
                ("attn_norm", "attn_norm"), ("attn_q_norm", "attn_q_norm"),
                ("attn_k_norm", "attn_k_norm"), ("ffn_norm", "ffn_norm"))
        ffn = {DENSE: {"w_gate": "ffn_gate", "w_up": "ffn_up",
                       "w_down": "ffn_down"},
               MOE: {"w_gate_sh": "ffn_gate_shexp", "w_up_sh": "ffn_up_shexp",
                     "w_down_sh": "ffn_down_shexp"}}
        # the latent projection's r_kv + d_r rows, filled up to a kernel's N
        kv_rows = -(-lat_width(cfg) // 128) * 128 if latent else None
        if cfg.index_topk:
            # a ``deepseek32`` layer's indexer: its query projection from
            # the query latent (K = r_q, as ``attn_q_b``'s) and its ONE key
            # projection; its LayerNorm and the heads' F32 weights below
            attn = {**attn, "idx_wq_b": "indexer_q_b", "idx_wk": "indexer_k"}
            norms = (*norms, ("idx_k_norm", "indexer_k_norm"),
                     ("idx_proj", "indexer_proj"))
        out = {}
        for kind, ids in ((DENSE, range(cfg.n_dense_layers)),
                          (MOE, range(cfg.n_dense_layers, cfg.n_layers))):
            mats = {**attn, **ffn[kind]}
            exps = ["ffn_gate_exps", "ffn_up_exps", "ffn_down_exps"] \
                if kind == MOE and fused_experts else []
            fused = _fused_names(list(mats.values()) + exps, ids,
                                 {"attn_kv_a_mqa": kv_rows} if latent
                                 else None) \
                if fmt == "q4k" and len(ids) else {}
            out[kind] = []
            for i in ids:
                p = f"blk.{i}."
                layer = {}
                for key, name in mats.items():
                    if fmt == "q4k" and name not in fused:
                        layer[key] = {"w": as_bf16(gf[p + name + ".weight"])}
                    else:
                        layer[key] = lin(p + name + ".weight", fused,
                                         kv_rows if key == "wkv_a" else None)
                if latent:
                    layer.update(absorbed_halves(p))
                for key, name in norms:
                    layer[key] = norm(p + name + ".weight")
                if cfg.index_topk:
                    layer["idx_k_norm_b"] = norm(p + "indexer_k_norm.bias")
                if kind == MOE:
                    layer["w_router"] = norm(p + "ffn_gate_inp.weight")
                    layer["router_bias"] = norm(p + "exp_probs_b.bias")
                    for key in ("gate", "up", "down"):
                        layer[f"w_{key}_exps"] = experts(
                            p + f"ffn_{key}_exps.weight", fused)
                if overlap:
                    layer = jax.tree.map(jax.device_put, layer)
                out[kind].append(layer)
        return {k: v for k, v in out.items() if v}

    def absorbed_halves(p: str) -> dict:
        """``attn_kv_b`` per head, as the absorbed form wants it: W_uk and
        W_uv, bf16."""
        d_n = cfg.qk_nope_dim
        kv_b = as_bf16(gf[p + "attn_kv_b.weight"]).reshape(
            cfg.n_heads, d_n + cfg.v_head_dim, cfg.kv_lora_rank)
        return {"w_uk": {"w": kv_b[:, :d_n]}, "w_uv": {"w": kv_b[:, d_n:]}}

    def shortcut_layers() -> dict:
        """A ``longcat-flash`` file (models/mla.py ``shortcut_layer``):
        THREE stacks, the sub-blocks' attentions and their dense
        feed-forwards at depth ``2 L`` (sub-block ``s`` of layer ``l`` at
        ``2 l + s``; tensors ``blk.l.s.*``) and the layers' expert branches
        at depth ``L``.  As ``ffn_kind_layers``: nothing is requantized, a
        matrix no fused kernel takes is served bf16.  An absent choice bias
        (``exp_probs_b.bias``) loads as zeros."""
        from .mla import ATTN, FFN, lat_width
        from .routed import MOE

        dense = {"w_gate": "ffn_gate", "w_up": "ffn_up", "w_down": "ffn_down"}
        exps = ["ffn_gate_exps", "ffn_up_exps", "ffn_down_exps"]
        kv_rows = -(-lat_width(cfg) // 128) * 128
        subs = [f"{l}.{s}" for l in range(cfg.n_layers) for s in (0, 1)]
        q4k = fmt == "q4k"
        fused = _fused_names(
            [*latent_attn.values(), *dense.values()], subs,
            {"attn_kv_a_mqa": kv_rows}) if q4k else {}
        fused_exps = _fused_names(exps, range(cfg.n_layers)) \
            if q4k and fused_experts else {}

        def mats(p, names):
            return {key: {"w": as_bf16(gf[p + name + ".weight"])}
                    if q4k and name not in fused
                    else lin(p + name + ".weight", fused,
                             kv_rows if key == "wkv_a" else None)
                    for key, name in names.items()}

        def put(layer):
            return jax.tree.map(jax.device_put, layer) if overlap else layer

        out = {ATTN: [], FFN: [], MOE: []}
        for sub in subs:
            p = f"blk.{sub}."
            out[ATTN].append(put({
                **mats(p, latent_attn), **absorbed_halves(p),
                **{key: norm(p + name + ".weight")
                   for key, name in latent_norms}}))
            out[FFN].append(put({**mats(p, dense),
                                 "ffn_norm": norm(p + "ffn_norm.weight")}))
        n_out = cfg.n_experts + cfg.n_zero_experts
        for l in range(cfg.n_layers):
            p = f"blk.{l}."
            out[MOE].append(put({
                "w_router": norm(p + "ffn_gate_inp.weight"),
                "router_bias": norm(p + "exp_probs_b.bias")
                if p + "exp_probs_b.bias" in gf.tensors
                else jnp.zeros(n_out, jnp.float32),
                **{f"w_{key}_exps": experts(p + f"ffn_{key}_exps.weight",
                                            fused_exps)
                   for key in ("gate", "up", "down")}}))
        return out

    def mixer_ffn_layers() -> dict:
        """A ``lfm2moe`` file (models/lfm2.py): FOUR stacks, a layer's
        mixer tensors under its mixer kind (``conv`` | ``attn``, with the
        layer's ``attn_norm``) and its feed-forward tensors under its
        feed-forward kind (``dense`` | ``moe``, with its ``ffn_norm``); a
        name fuses by the types of ITS kind's layers.  Nothing is
        requantized: a matrix no fused kernel takes is served bf16."""
        from .config import ATTN, CONV
        from .routed import DENSE, MOE

        mats = {
            CONV: {"in_proj": "shortconv.in_proj",
                   "out_proj": "shortconv.out_proj"},
            ATTN: {"wq": "attn_q", "wk": "attn_k", "wv": "attn_v",
                   "wo": "attn_output"},
            DENSE: {"w_gate": "ffn_gate", "w_up": "ffn_up",
                    "w_down": "ffn_down"},
            MOE: {}}
        f32s = {
            CONV: {"attn_norm": "attn_norm.weight",
                   "conv": "shortconv.conv.weight"},
            ATTN: {"attn_norm": "attn_norm.weight",
                   "attn_q_norm": "attn_q_norm.weight",
                   "attn_k_norm": "attn_k_norm.weight"},
            DENSE: {"ffn_norm": "ffn_norm.weight"},
            MOE: {"ffn_norm": "ffn_norm.weight",
                  "w_router": "ffn_gate_inp.weight",
                  "router_bias": "exp_probs_b.bias"}}
        ids = {kind: [i for i, m in enumerate(cfg.mixers) if m == kind]
               for kind in (CONV, ATTN)}
        ids[DENSE] = list(range(cfg.n_dense_layers))
        ids[MOE] = list(range(cfg.n_dense_layers, cfg.n_layers))
        out = {}
        for kind, mine in ids.items():
            exps = ["ffn_gate_exps", "ffn_up_exps", "ffn_down_exps"] \
                if kind == MOE and fused_experts else []
            # ``_fused_names`` reads a name's last dotted part but one
            fused = _fused_names(list(mats[kind].values()) + exps, mine) \
                if fmt == "q4k" and mine else {}
            fused = {name.split(".")[-1]: t for name, t in fused.items()}
            out[kind] = []
            for i in mine:
                p = f"blk.{i}."
                layer = {}
                for key, name in mats[kind].items():
                    if fmt == "q4k" and name.split(".")[-1] not in fused:
                        layer[key] = {"w": as_bf16(gf[p + name + ".weight"])}
                    else:
                        layer[key] = lin(p + name + ".weight", fused)
                for key, name in f32s[kind].items():
                    layer[key] = norm(p + name)
                if kind == MOE:
                    for key in ("gate", "up", "down"):
                        layer[f"w_{key}_exps"] = experts(
                            p + f"ffn_{key}_exps.weight", fused)
                if overlap:
                    layer = jax.tree.map(jax.device_put, layer)
                out[kind].append(layer)
        return {k: v for k, v in out.items() if v}

    def ssm_values(p: str) -> dict:
        """An ssm layer's ``A`` (d_state, d_inner: the channels last, as the
        scan's kernel lays them) and ``b_dt``.  As STORED (``ssm_a`` holds A
        itself, negative, as llama.cpp's converter writes it), or, where the
        file says ``<arch>.ssm.values = init_offsets``, as offsets from
        Mamba's initialisation: ``A = -exp(log(n + 1) + ssm_a[c, n])`` and
        ``b_dt = softplus^-1(dt0[c]) + ssm_dt.bias[c]`` with ``dt0`` spread
        over 1e-3..1e-1 in the logarithm by the channel (a fixed
        low-discrepancy order).  That is how a file of small random values
        (the benchmark's writer gives a block no say over values) has the
        time scales of a trained one: its states neither vanish within a
        few positions nor grow."""
        a = np.asarray(gf[p + "ssm_a"].astype_f32(), np.float32)
        b_dt = np.asarray(gf[p + "ssm_dt.bias"].astype_f32(), np.float32)
        how = str(gf.hparam("ssm.values", "stored"))
        if how == "init_offsets":
            n_c, n_s = a.shape
            a = -np.exp(np.log(np.arange(1, n_s + 1, dtype=np.float32))[None]
                        + a)
            u = (np.arange(n_c, dtype=np.float64) * 0.6180339887498949) % 1.0
            dt0 = np.exp(np.log(1e-3) + u * (np.log(1e-1) - np.log(1e-3)))
            b_dt = (dt0 + np.log(-np.expm1(-dt0))).astype(np.float32) + b_dt
        elif how != "stored":
            raise ValueError(f"ssm.values {how!r} (stored, init_offsets)")
        return {"a": jnp.asarray(np.ascontiguousarray(a.T)),
                "dt_b": jnp.asarray(b_dt)}

    def ssm_mixer_layers() -> dict:
        """A file with Mamba layers: stacks by kind, a layer's mixer tensors
        under its mixer kind (with the layer's ``attn_norm``) and every
        layer's feed-forward under ``ffn``; a name fuses by the types of ITS
        kind's layers.  A ``phi4flash`` file (models/phi4flash.py) has FIVE
        (``ssm`` | ``attn``: the window layers and the full one | ``gmu`` |
        ``cross`` | ``ffn``), its norms LayerNorms with a bias; a ``jamba``
        file (models/jamba.py) THREE (``ssm`` with its three inner norms |
        ``attn`` | ``ffn``), RMSNorms.  Nothing is requantized: a matrix no
        fused kernel takes (``ssm_x``: its rows fill no tile) is served
        bf16, the F32 ``ssm_dt`` float32."""
        from .config import ATTN, CROSS, FULL, GMU, SSM, WINDOW
        from .mamba import FFN

        ssm_mats = {"in_proj": "ssm_in", "out_proj": "ssm_out"}
        ssm_f32s = {"conv": "ssm_conv1d.weight", "conv_b": "ssm_conv1d.bias",
                    "dt_proj": "ssm_dt.weight", "d": "ssm_d"}
        qkvo = {"wq": "attn_q", "wk": "attn_k", "wv": "attn_v",
                "wo": "attn_output"}
        ffn = {"w_gate": "ffn_gate", "w_up": "ffn_up", "w_down": "ffn_down"}
        if cfg.ssm_inner_norms:          # jamba
            mats = {SSM: ssm_mats, ATTN: qkvo, FFN: ffn}
            norm1 = {"attn_norm": "attn_norm.weight"}
            f32s = {
                SSM: {**norm1, **ssm_f32s, "dt_norm": "ssm_dt_norm.weight",
                      "b_norm": "ssm_b_norm.weight",
                      "c_norm": "ssm_c_norm.weight"},
                ATTN: norm1,
                FFN: {"ffn_norm": "ffn_norm.weight"}}
            kinds = ((SSM, (SSM,)), (ATTN, (ATTN,)))
        else:                            # phi4flash
            mats = {
                SSM: ssm_mats, ATTN: qkvo,
                GMU: {"in_proj": "gmu_in", "out_proj": "gmu_out"},
                CROSS: {"wq": "attn_q", "wo": "attn_output"}, FFN: ffn}
            lams = {f"lam_{k}": f"attn_lambda_{k}"
                    for k in ("q1", "k1", "q2", "k2")}
            norm1 = {"attn_norm": "attn_norm.weight",
                     "attn_norm_b": "attn_norm.bias"}
            f32s = {
                SSM: {**norm1, **ssm_f32s},
                ATTN: {**norm1, **lams, "sub_norm": "attn_sub_norm.weight",
                       "bq": "attn_q.bias", "bk": "attn_k.bias",
                       "bv": "attn_v.bias", "bo": "attn_output.bias"},
                GMU: norm1,
                CROSS: {**norm1, **lams, "sub_norm": "attn_sub_norm.weight",
                        "bq": "attn_q.bias", "bo": "attn_output.bias"},
                FFN: {"ffn_norm": "ffn_norm.weight",
                      "ffn_norm_b": "ffn_norm.bias"}}
            kinds = ((SSM, (SSM,)), (ATTN, (WINDOW, FULL)), (GMU, (GMU,)),
                     (CROSS, (CROSS,)))
        ids = {kind: [i for i, m in enumerate(cfg.mixers) if m in names]
               for kind, names in kinds}
        ids[FFN] = list(range(cfg.n_layers))
        out = {}
        for kind, mine in ids.items():
            fused = _fused_names(list(mats[kind].values()), mine) \
                if fmt == "q4k" and mine else {}
            out[kind] = []
            for i in mine:
                p = f"blk.{i}."
                layer = {}
                for key, name in mats[kind].items():
                    if fmt == "q4k" and name not in fused:
                        layer[key] = {"w": as_bf16(gf[p + name + ".weight"])}
                    else:
                        layer[key] = lin(p + name + ".weight", fused)
                for key, name in f32s[kind].items():
                    layer[key] = norm(p + name)
                if kind == SSM:
                    layer["x_proj"] = {"w": as_bf16(gf[p + "ssm_x.weight"])}
                    layer.update(ssm_values(p))
                if overlap:
                    layer = jax.tree.map(jax.device_put, layer)
                out[kind].append(layer)
        return out

    layers = []
    t_prep = _time.time()
    by_kind = ssm_mixer_layers() if cfg.ssm_d_state else \
        mixer_ffn_layers() if cfg.conv_l_cache else \
        kinds_layers() if cfg.mixers else \
        shortcut_layers() if cfg.attn_sublayers == 2 else \
        ffn_kind_layers() if by_ffn_kind else None
    for i in range(cfg.n_layers if by_kind is None else 0):
        p = f"blk.{i}."
        layer = {
            "attn_norm": norm(p + "attn_norm.weight"),
            "wq": lin(p + "attn_q.weight"),
            "wk": lin(p + "attn_k.weight"),
            "wv": lin(p + "attn_v.weight"),
            "wo": lin(p + "attn_output.weight"),
            "ffn_norm": norm(p + "ffn_norm.weight"),
        }
        if cfg.qk_norm:
            layer["attn_q_norm"] = norm(p + "attn_q_norm.weight")
            layer["attn_k_norm"] = norm(p + "attn_k_norm.weight")
        if cfg.eva_window:
            layer["eva_phi"] = norm(p + "attn_eva_phi.weight")
            layer["eva_mu"] = norm(p + "attn_eva_mu.weight")
        if cfg.sandwich_norm:   # a norm after each sub-block too
            layer["post_attn_norm"] = norm(p + "post_attention_norm.weight")
            layer["post_ffn_norm"] = norm(p + "post_ffw_norm.weight")
        if cfg.n_experts:
            layer["w_router"] = norm(p + "ffn_gate_inp.weight")
            for key in ("gate", "up", "down"):
                layer[f"w_{key}_exps"] = experts(
                    p + f"ffn_{key}_exps.weight")
        else:
            for key in ("gate", "up", "down"):
                layer[f"w_{key}"] = lin(p + f"ffn_{key}.weight")
        if overlap:
            layer = jax.tree.map(jax.device_put, layer)
        layers.append(layer)
        logger.debug("loaded layer %d/%d", i + 1, cfg.n_layers)
    t_head = _time.time()

    tied_fused = None
    if cfg.ssm_d_state and fmt == "q4k":
        # a ``phi4flash`` or ``jamba`` file's tied Q6_K table: ONE stored
        # tensor, the head's fused planes, of which the embedding lookup
        # dequantizes the rows it gathers (models/llama.py ``embed``)
        from ..gguf.constants import GGMLType
        from ..ops.pallas.q6matmul import q6k_compatible

        t = gf["token_embd.weight"]
        if t.ggml_type == GGMLType.Q6_K \
                and (fused_types is None or GGMLType.Q6_K in fused_types) \
                and q6k_compatible(t.shape[1], padded_k(t.shape[0])):
            tied_fused = lin("token_embd.weight",
                             {"token_embd": GGMLType.Q6_K})
    emb = tied_fused if tied_fused is not None \
        else as_bf16(gf["token_embd.weight"])
    if tied_fused is not None:
        output = tied_fused
    elif cfg.tie_embeddings or "output.weight" not in gf.tensors:
        output = {"w": emb}
    elif (cfg.fp32_residual or cfg.fp32_logits) \
            and gf["output.weight"].ggml_type.name in ("F32", "F16", "BF16"):
        # float32 logits from a float head: bf16 inputs, nothing requantized
        output = {"w": as_bf16(gf["output.weight"])}
    else:
        output = lin("output.weight")
    t_stack = _time.time()
    stacked = _stack(layers, free=overlap) if by_kind is None else {
        kind: _stack(ls, free=overlap) for kind, ls in by_kind.items()}
    jax.block_until_ready(stacked)
    t_end = _time.time()
    logger.info("load_params phases: per-layer prep+transfer %.1fs, "
                "stack %.1fs", t_head - t_prep, t_end - t_stack)
    if phases_out is not None:
        # caller-owned out-param (the children of the engine's ``params``
        # phase, utils/startup.py); no shared module state, so concurrent
        # loads can't cross-report
        phases_out.update(prep=(t_prep, t_head), head=(t_head, t_stack),
                          stack=(t_stack, t_end))
    if fill_out is not None:
        fill_out.update(fill)
    # a looped stack's exit gate: one F32 row and its bias
    gate = {"exit_gate": {
        "w": norm("ut_exit_gate.weight").reshape(cfg.dim),
        "b": norm("ut_exit_gate.bias").reshape(())}} \
        if cfg.ut_steps > 1 else {}
    return {
        "tok_emb": emb,
        "layers": stacked,
        # (``lfm2moe`` files name their FINAL norm ``token_embd_norm``)
        "out_norm": norm("output_norm.weight" if "output_norm.weight"
                         in gf.tensors else "token_embd_norm.weight"),
        "output": output,
        **gate,
        # (a ``phi4flash`` file's norms are LayerNorms: a bias beside each)
        **({"out_norm_b": norm("output_norm.bias")}
           if "output_norm.bias" in gf.tensors else {}),
    }


def synth_params(cfg: ModelConfig, fmt: str = "bf16", seed: int = 0,
                 scale: float | None = None) -> dict:
    """Random-weight params with the exact structure of :func:`load_params`.

    Used for tests and for benchmarking real-size models without network
    egress (BASELINE.md: bench models are synthesized, not downloaded).
    """
    rng = np.random.default_rng(seed)
    make = _LINEAR_MAKERS["int8" if fmt == "q4k" else fmt]
    if scale is None:
        scale = cfg.dim ** -0.5

    def lin(out_dim, in_dim):
        w = rng.standard_normal((out_dim, in_dim), dtype=np.float32) * scale
        if fmt == "q4k":
            from ..ops import make_linear_q4k
            from ..ops.pallas.qmatmul import q4k_compatible

            if q4k_compatible(out_dim, in_dim):
                return make_linear_q4k(w)
        return make(w)

    kv_dim = cfg.n_kv_heads * cfg.head_dim
    layers = []
    for _ in range(cfg.n_layers):
        layers.append({
            "attn_norm": jnp.ones(cfg.dim, jnp.float32),
            "wq": lin(cfg.dim, cfg.dim),
            "wk": lin(kv_dim, cfg.dim),
            "wv": lin(kv_dim, cfg.dim),
            "wo": lin(cfg.dim, cfg.dim),
            "ffn_norm": jnp.ones(cfg.dim, jnp.float32),
            "w_gate": lin(cfg.ffn_dim, cfg.dim),
            "w_up": lin(cfg.ffn_dim, cfg.dim),
            "w_down": lin(cfg.dim, cfg.ffn_dim),
        })
        if cfg.eva_window:
            for name in ("eva_phi", "eva_mu"):
                layers[-1][name] = jnp.asarray(rng.standard_normal(
                    (cfg.n_heads, cfg.head_dim), dtype=np.float32))
        if cfg.sandwich_norm:
            for name in ("post_attn_norm", "post_ffn_norm"):
                layers[-1][name] = jnp.ones(cfg.dim, jnp.float32)
    emb = jnp.asarray(
        rng.standard_normal((cfg.vocab_size, cfg.dim), dtype=np.float32) * scale,
        dtype=jnp.bfloat16,
    )
    output = {"w": emb} if cfg.tie_embeddings \
        else lin(cfg.vocab_size * cfg.n_pred_heads, cfg.dim)
    gate = {"exit_gate": {
        "w": jnp.asarray(rng.standard_normal(cfg.dim, dtype=np.float32)
                         * scale),
        "b": jnp.zeros((), jnp.float32)}} if cfg.ut_steps > 1 else {}
    return {
        "tok_emb": emb,
        "layers": _stack(layers),
        "out_norm": jnp.ones(cfg.dim, jnp.float32),
        "output": output,
        **gate,
    }
