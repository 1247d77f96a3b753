"""Routed experts over fused K-quant planes: the grouped matmul.

A routed feed-forward layer (models/llama.py ``_layer``, ``cfg.n_experts``)
sends each token to ``k`` of ``E`` SwiGLU experts.  Its weights are the
GGUF file's 3-D ``ffn_{gate,up,down}_exps`` tensors, kept as the SAME fused
Q4_K / Q6_K planes the dense matmuls read (ops/pallas/qmatmul.py,
q6matmul.py) with an (L, E) pair of leading axes, and computed by INTEGER
dequantizations of the packed bytes that build the stacked dense calls'
bfloat16 planes bit for bit at fewer vector operations: a Q6_K plane, since
PR 59, by the vocabulary head's (``q6matmul._q6k_tile_product``: the float32
sums of a K tile taken a quarter at a time, so a result differs from the
stacked call's in its last bits and equals the unstacked call's), a Q4_K
plane, since PR 61, by ``qmatmul._q4k_tile_product`` (the stacked body's
three dots in their order: its results bit for bit).  Both under an N tile
of their own (the head's rule: ``_Family.tn``), and a few-row Q4_K call
takes all its K tiles in one grid step (:func:`_few_k_tiles`).  The grid
around them:

- the grid has an expert-slot axis beside the N and K tiles; the layer, the
  number of slots in use and each slot's expert ride a prefetched scalar
  vector, and the weight BlockSpecs address ``planes[layer, expert]``
  through it, as ``qmatmul.stacked_pallas_call`` addresses
  ``planes[layer]``.  The slot axis ENDS at the slots in use (a traced grid
  bound: ``slot_extent`` of the prefetched count), so a step reads and
  multiplies the experts its rows picked and takes no grid step for any
  other: a step that skips its body still costs 0.1-0.2 us, and a
  saturated decode step of LFM2 leaves 35 of 64 slots idle (PERF.md
  section 6, PR 51);
- FEW rows (a decode step: lanes x k (token, pick) rows, at most
  ``FEW_ROWS``): a slot is a distinct expert; the rows are ONE resident
  block, and a slot adds its product into the one output block for the
  rows that picked its expert, zero for the rest
  (:func:`grouped_matmul_few`).  Grid (slot, N tile, K tile), the output
  block all of N and resident for the whole call: the traced extent is
  the outermost axis, so the pipeline never restarts inside the call.
  Every slot multiplies ALL the rows of the block, no sort, no padding:
  the MXU does a few times the work of the picked rows alone, which hides
  under the dequantization up to 64 rows.  It no longer does at 128 and
  192, of which a layer that holds a share of its experts
  (models/routed.py ``held_picks``) sends 9-12 to an expert here, and a
  call whose K is several grid steps fetches the whole row block again at
  every one.  So a layer of more than ``ROW_GROUP`` (64) rows COMPACTS
  them (:func:`_routed_raw`): the rows that reach an expert, in their
  order (:func:`compact_rows`: a cumulative sum, one scatter, one gather of
  the activations in place of their repeat), go through the SAME three
  calls as a block of 64 rows, and one gather puts the down call's rows
  back in their places; a step with more than 64 such rows makes the
  calls of all the rows instead (one ``lax.cond``; both forms are in the
  program).  A layer of 64 rows or fewer is built as it always was
  (PERF.md section 6, PR 53);
- MANY rows (a prefill slice): the rows are sorted by expert and laid out
  in tiles of ``TM_MANY`` rows, each expert's rows padded up to whole
  tiles, so a slot is a row tile of one expert (:func:`plan_groups`,
  :func:`grouped_matmul_many`).  Grid (N tile, slot, K tile): the output
  block moves with the slot, and consecutive tiles of one expert re-read
  no weights.  The split is the one ``qmatmul.kernel_name`` makes for the
  dense kernels.

The kernels take K in tiles of 2048.  An expert matrix whose K is a
divisor of 2048 (OLMoE's down projection: K = 1024) is *folded*: ``f =
2048 // K`` consecutive output rows are read as one row of ``f * K``
(:func:`prep_experts` hands the packers the same bytes under that shape),
and each activation row is offered ``f`` times, shifted into segment ``j``
of the wider row, so that copy ``j`` yields outputs ``f * n + j``.  The
weight bytes read stay the file's; the MXU does ``f`` times the few-row
work, which is not what bounds these kernels at ``f`` 2.  A K that is no
divisor of a tile is stored as the dense matrices' are, its last tile
filled up with zero blocks, here where that adds at most a THIRD
(:func:`padded_k`: LFM2's down projection, K = 1536 -> 2048; the dense
matrices keep their quarter, so no standing file loads otherwise).  Folding FOUR rows of 1536 into three tiles
was built and measured first (PERF.md section 6, PR 49): it keeps the
file's bytes, but a row offered four times in a row four times as wide is
sixteen times the activations, and a prefill slice spent more time laying
them out than in the kernels.

:func:`routed_experts` is the whole layer after the router: gather, gate
and up, SwiGLU, down, the weighted sum over a token's picks.  It carries a
``vmap`` rule that turns a batch of lanes into more rows of one call
(weights are shared), so the lane engines' vmapped decode step
(parallel/batched.py) groups the picks of all lanes together.  A pick equal
to ``E`` is no pick: the lane engine marks a lane that holds no request so,
and its rows reach no expert.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...gguf.constants import GGMLType
from ...obs.devtime import register_program
from . import q6matmul as _q6
from . import qmatmul as _q4
from .qmatmul import TK, _interpret

#: (token, pick) rows up to which every slot sees all rows: 16 lanes of 12
#: picks (``longcat-flash``); no program of another served file has rows
#: between 128, what it was, and this (a prefill slice is 128 tokens or more)
FEW_ROWS = 192
#: rows of a few-row layer up to which it is built as it always was (every
#: slot multiplies all the rows: at 64 the MXU's pass still hides under the
#: dequantization); a layer of more rows sends those that reach an expert
#: through calls of this many (module docstring)
ROW_GROUP = 64
TM_MANY = 128    # rows per tile of a prefill slice
FEW_VMEM = 64 * 2 ** 20  # a few-row call's limit: its output block is all N


def padded_k(k_in: int) -> int:
    """The K an expert matrix of ``k_in`` is stored at (module docstring):
    ``ops.linear.filled_k`` with a third where the dense matrices take a
    quarter, and no tail tile (``ops.linear.padded_k``)."""
    from ..linear import filled_k

    return filled_k(k_in, 3)


def fold_factor(k_in: int) -> int:
    """Output rows read as one kernel row (module docstring)."""
    return TK // k_in if k_in < TK and TK % k_in == 0 else 1


def experts_compatible(n_out: int, k_in: int,
                       for_tpu: bool | None = None) -> bool:
    """Whether an (E, n_out, k_in) expert tensor can use the fused path."""
    f = fold_factor(k_in)
    return n_out % f == 0 and _q4.q4k_compatible(n_out // f, k_in * f, for_tpu)


# ---------------------------------------------------------------------------
# the two families: which planes, which kernel, which activation layout
# ---------------------------------------------------------------------------

class _Family:
    def __init__(self, name, gtype, prep, planes, widths, kernel, body, tka,
                 permute, augment, few_k_tiles=False, many_vmem=None):
        self.name = name                # q4k | q6k
        self.gtype, self.prep = gtype, prep   # ggml type, the dense packer
        self.planes = planes            # plane keys, scale plane last
        self.widths = widths            # value planes' bytes per K tile
        self.kernel, self.body = kernel, body     # body: its /health name
        self.tka = tka
        self.permute, self.augment = permute, augment
        # whether a few-row call's grid step holds several K tiles
        # (:func:`_few_k_tiles`: the kernel then loops over them)
        self.few_k_tiles = few_k_tiles
        self.many_vmem = many_vmem      # a many-row call's limit; None: XLA's

    def tn(self, N: int, rows: int, interpret: bool) -> int:
        """The N tile of a grouped call, whatever its rows: the head's rule
        (``q6matmul.wide_tn``: 1024 at N 1024, 2048, 6144 and 7168, 768 at
        LFM2's 1536).  A grid step costs 0.3 us beside its bytes, and a
        many-row call fetches its slot's activation block (590 KB at 128
        rows) again at every (N tile, slot) step, which at a tile of 256 is
        more bytes than the planes (PERF.md section 6: PR 59, the Q6_K
        calls from 256, few rows -24 %, many rows -39 % under one body;
        PR 61, the Q4_K calls from 512 / 256)."""
        return _q6.wide_tn(N, interpret)


def _few_k_tiles(kt: int, tn: int) -> int:
    """K tiles a grid step of a few-row call whose family takes several
    (``few_k_tiles``): as many as divide the call's and fit the head's
    weight block (``q6matmul.HEAD_W_BLOCK``: all four of ``gigachat``'s gate
    at K 8192, all three of ``kexaone``'s and ``longcat``'s at 6144).  The
    grid then takes a step an (expert, N tile) and the row block, whose
    index no longer moves, is fetched once a call and not at every step."""
    return max(t for t in range(1, kt + 1)
               if kt % t == 0 and (t == 1 or tn * t * TK <= _q6.HEAD_W_BLOCK))


# Each family's kernel is the integer dequantization of its packed bytes
# under this module's grid; the dense Q4_K calls keep their float bodies
# (and the ``LFKT_Q4K_KERNEL`` variants, which reach no grouped call), the
# dense Q6_K calls run this family's body since PR 64.  The Q4_K calls' float32 temporaries of a half plane are
# (TN, 1024): 4 MB each at a tile of 1024, so the many-row call is given the
# few-row call's scoped VMEM (the Q6_K calls take a quarter at a time and
# fit XLA's own limit; their programs keep their text)
FAMILIES = {
    "q4k": _Family("q4k", GGMLType.Q4_K, _q4.prep_q4k, ("qs", "sm"),
                   (TK // 2,), _q4._q4k_expert_kernel, "q4k-int", _q4.TKA,
                   _q4.permute_x, _q4.augment_x, few_k_tiles=True,
                   many_vmem=FEW_VMEM),
    "q6k": _Family("q6k", GGMLType.Q6_K, _q6.prep_q6k, ("q4", "q2", "sm6"),
                   (TK // 2, TK // 4), _q6._q6k_expert_kernel, "q6k-int",
                   _q6.TKA6, _q6.permute_x6, _q6.augment_x6),
}


def family_of(w: dict) -> str | None:
    """The family of an expert weight dict, None for the dense fallback."""
    return next((f.name for f in FAMILIES.values() if f.planes[0] in w), None)


def prep_experts(raw: np.ndarray, n_experts: int, n_out: int, k_in: int,
                 ggml_type) -> dict | None:
    """Raw block bytes of an (E, n_out, k_in) expert tensor -> its fused
    planes with a leading expert axis: value planes (E, N', K'/x), the scale
    plane (E, K'/2048, N', 128), N' = n_out / f and K' = f * k_in.  The
    dense packers do the work (the C++ ones where built): the bytes of E x
    n_out rows of k_in are also those of E x N' rows of K'.  None where the
    type has no expert kernel or the packer chose a layout it lacks (the
    Q6_K ``pre`` layout): the caller loads the tensor dequantized."""
    fam = next((f for f in FAMILIES.values() if f.gtype == ggml_type), None)
    if fam is None or not experts_compatible(n_out, k_in):
        return None
    f = fold_factor(k_in)
    w = fam.prep(raw, n_experts * n_out // f, k_in * f)
    if set(w) != set(fam.planes):
        return None
    out = {}
    for key, plane in w.items():
        if key == fam.planes[-1]:                    # (kt, E*N', 128)
            kt = plane.shape[0]
            plane = plane.reshape(kt, n_experts, n_out // f, 128)
            out[key] = jnp.transpose(plane, (1, 0, 2, 3))
        else:                                        # (E*N', K'/x)
            out[key] = plane.reshape(n_experts, n_out // f, -1)
    return out


# ---------------------------------------------------------------------------
# rows -> slots
# ---------------------------------------------------------------------------

def experts_in_use(row_expert: jax.Array, n_experts: int, n_slots: int):
    """(rows per expert (E,), the distinct experts in rising order padded to
    ``n_slots`` by repeating the last (the grid walks the first ``n_used``),
    how many there are).  ``row_expert`` (R,) in [0, E]; E = no expert."""
    i32 = jnp.int32
    count = jnp.zeros(n_experts + 1, i32).at[row_expert].add(1)[:n_experts]
    used = count > 0
    n_used = jnp.sum(used, dtype=i32)
    slot = jnp.where(used, jnp.cumsum(used) - 1, n_slots)
    experts = jnp.zeros(n_slots, i32).at[slot].set(
        jnp.arange(n_experts, dtype=i32), mode="drop")
    t = jnp.arange(n_slots, dtype=i32)
    experts = jnp.where(t < n_used, experts,
                        experts[jnp.maximum(n_used - 1, 0)])
    return count, experts, n_used


def slot_extent(n_used):
    """How far the grid's slot axis runs: the slots in use, and one (which
    skips its body: the output is still zero-filled) when there is none."""
    return jnp.maximum(n_used, 1)


def decode_slots(n_experts: int, n_tokens: int, k: int) -> int:
    """The slots ``T`` of a few-row call of ``n_tokens`` tokens' picks: a
    slot a distinct expert, so the held experts or the (token, pick) rows,
    the fewer; 0 for more rows than :data:`FEW_ROWS` (the many-row plan's
    slots are row tiles).  Of them a call walks those in use
    (``expert_slots_skipped_total``: engine/expert_counters.py)."""
    rows = n_tokens * k
    return min(n_experts, rows) if rows <= FEW_ROWS else 0


def compacted_rows(n_tokens: int, k: int) -> int:
    """The (token, pick) rows of a few-row layer that is compacted to the
    rows that reach an expert (more than :data:`ROW_GROUP` of them); 0 for
    a layer built as it always was and for the many-row plan
    (``expert_rows_skipped_total``: engine/expert_counters.py)."""
    rows = n_tokens * k
    return rows if ROW_GROUP < rows <= FEW_ROWS else 0


def compact_rows(row_expert: jax.Array, n_experts: int, n_places: int):
    """The rows that reach an expert, in their order, laid out in
    ``n_places`` places: (``place`` (R,): each row's place, ``n_places`` or
    more for a row without an expert or past the last place; ``src``
    (n_places,): the row at each place, R past the ``n_real`` in use;
    ``n_real``, which may exceed the places).  ``row_expert`` (R,) in
    [0, E]; E = no expert."""
    i32 = jnp.int32
    R = row_expert.shape[0]
    real = row_expert < n_experts
    n_real = jnp.sum(real, dtype=i32)
    place = jnp.where(real, jnp.cumsum(real, dtype=i32) - 1, n_places)
    src = jnp.full(n_places, R, i32).at[place].set(
        jnp.arange(R, dtype=i32), mode="drop")
    return place, src, n_real


def n_tiles(n_rows: int, n_experts: int, n_tokens: int, tm: int) -> int:
    """Row tiles that always suffice: every expert in use wastes under one
    tile, and holds at most one row per token (a token's picks differ)."""
    return min(n_rows // tm + min(n_experts, n_rows),
               n_experts * -(-n_tokens // tm))


def plan_groups(row_expert: jax.Array, n_experts: int, n_tokens: int,
                tm: int) -> dict:
    """Lay R rows out by expert in T tiles of ``tm`` rows.  ``row_expert``
    (R,) int32 in [0, E]; E = the row reaches no expert.  Returns

    - ``src`` (T*tm,): the row that fills each padded slot, R for none;
    - ``pos`` (R,): each row's padded slot, T*tm for a row with no expert;
    - ``tile_expert`` (T,): each tile's expert (tiles past ``n_used``
      repeat the last one in use), ``n_used``: tiles that hold rows;
    - ``count`` (E,): rows per expert."""
    E, R = n_experts, row_expert.shape[0]
    T = n_tiles(R, E, n_tokens, tm)
    P = T * tm
    i32 = jnp.int32
    count = jnp.zeros(E + 1, i32).at[row_expert].add(1)[:E]
    tiles = (count + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_used = tile_end[-1]
    slot0 = (tile_end - tiles) * tm          # an expert's first padded slot
    rank0 = jnp.cumsum(count) - count        # its first row once sorted
    order = jnp.argsort(row_expert, stable=True).astype(i32)
    e_sorted = row_expert[order]
    e_safe = jnp.minimum(e_sorted, E - 1)
    dest = jnp.where(e_sorted < E,
                     slot0[e_safe] + jnp.arange(R, dtype=i32) - rank0[e_safe],
                     P)
    pos = jnp.zeros(R, i32).at[order].set(dest)
    src = jnp.full(P, R, i32).at[dest].set(order, mode="drop")
    t = jnp.arange(T, dtype=i32)
    te = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"), E - 1)
    te = jnp.where(t < n_used, te, te[jnp.maximum(n_used - 1, 0)])
    return {"src": src, "pos": pos, "tile_expert": te.astype(i32),
            "n_used": n_used.astype(i32), "count": count}


def _fold_rows(x: jax.Array, f: int, group: int) -> jax.Array:
    """(P, K) -> (P*f, f*K): within each group of ``group`` rows, the rows
    ``f`` times over, copy ``j`` shifted into segment ``j`` of the wider
    row (module docstring)."""
    if f == 1:
        return x
    P, K = x.shape
    xt = x.reshape(P // group, group, K)
    return jnp.concatenate(
        [jnp.pad(xt, ((0, 0), (0, 0), (j * K, (f - 1 - j) * K)))
         for j in range(f)], axis=1).reshape(P * f, f * K)


def _unfold_rows(out: jax.Array, f: int, group: int) -> jax.Array:
    """(P*f, N/f) -> (P, N): copy ``j``'s column ``n`` is output ``f*n+j``."""
    if f == 1:
        return out
    G = out.shape[0] // (f * group)
    return out.reshape(G, f, group, -1).transpose(0, 2, 3, 1).reshape(
        G * group, -1)


def _activations(x: jax.Array, fam: _Family) -> jax.Array:
    """Rows -> the kernel's permuted, augmented bf16 rows (zeros first
    where the planes' K is :func:`padded_k` of the rows')."""
    x = jnp.pad(x, ((0, 0), (0, padded_k(x.shape[1]) - x.shape[1])))
    return fam.augment(fam.permute(x).astype(jnp.bfloat16))


# ---------------------------------------------------------------------------
# the grouped calls
# ---------------------------------------------------------------------------

class _NoLead2:
    """Ref adapter hiding the length-1 (layer, expert) axes of an expert
    plane block (``qmatmul._NoLead`` hides one)."""

    __slots__ = ("_ref",)

    def __init__(self, ref):
        self._ref = ref

    @property
    def shape(self):
        return self._ref.shape[2:]

    def __getitem__(self, idx):
        if idx is Ellipsis:
            return self._ref[idx].reshape(self._ref.shape[2:])
        return self._ref[(0, 0) + (idx if isinstance(idx, tuple) else (idx,))]


def expert_kernel_name(family: str, few: bool) -> str:
    """``q4k_expert_matmul_fewrow`` ...: the name a profile shows
    (benchmarks/kernels/expert_matmul.json finds the kernels by it)."""
    return f"{family}_expert_matmul_{'fewrow' if few else 'manyrow'}"


def _grouped_call(fam: _Family, meta, xpa, planes, rows: int, few: bool,
                  extra_in: tuple, interpret: bool):
    """The pallas_call both regimes share.  ``meta`` = [layer, slots in
    use, expert of slot 0..T-1]; the slot axis of the grid runs to
    :func:`slot_extent` of ``meta[1]``, not to T.  ``few``: grid (slot, N
    tile, K step), the activation block all the rows, the output ONE
    resident block of all N that every slot adds into, and ``extra_in`` =
    the rows' experts (rows, 1).  Else grid (N tile, slot, K step), and the
    activation and output blocks are slot ``t``'s ``rows`` rows.  A K step
    is one K tile, or :func:`_few_k_tiles` of them."""
    from jax.experimental.pallas import tpu as pltpu

    kt = xpa.shape[1] // fam.tka
    N = planes[0].shape[2]
    TN = fam.tn(N, rows, interpret)
    tiles = _few_k_tiles(kt, TN) if few and fam.few_k_tiles else 1
    slots = slot_extent(meta[1])
    if few:
        grid = (slots, N // TN, kt // tiles)
        out_spec = pl.BlockSpec((rows, N), lambda t, n, k, m: (0, 0))
        vmem = FEW_VMEM
    else:
        grid = (N // TN, slots, kt)
        out_spec = pl.BlockSpec((rows, TN), lambda n, t, k, m: (t, n))
        vmem = fam.many_vmem
    kw = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=vmem)} if vmem else {}

    def ax(f):               # the index maps below are written in (n, t, k, m)
        return (lambda t, n, k, m: f(n, t, k, m)) if few else f

    specs = [pl.BlockSpec((rows, tiles * fam.tka),
                          ax(lambda n, t, k, m: (0 if few else t, k)))]
    specs += [pl.BlockSpec((rows, 1), ax(lambda n, t, k, m: (0, 0)))
              for _ in extra_in]
    specs += [pl.BlockSpec((1, 1, TN, tiles * w),
                           ax(lambda n, t, k, m: (m[0], m[2 + t], n, k)))
              for w in fam.widths]
    specs.append(pl.BlockSpec((1, 1, tiles, TN, 128),
                              ax(lambda n, t, k, m: (m[0], m[2 + t], k, n,
                                                     0))))

    def body(meta_ref, x_ref, *rest):
        t, n, k = (pl.program_id(i) for i in ((0, 1, 2) if few
                                              else (1, 0, 2)))
        o_ref = rest[-1]
        plane_refs = [_NoLead2(r) for r in rest[len(extra_in):-1]]
        if few:
            # every slot adds into the one block: its rows' share of the
            # N tile's columns
            mine = (rest[0][...] == meta_ref[2 + t]).astype(jnp.float32)
            cols = pl.ds(pl.multiple_of(n * TN, TN), TN)

            @pl.when((t == 0) & (n == 0) & (k == 0))
            def _():
                o_ref[...] = jnp.zeros_like(o_ref)

            def accum(o_ref, part):
                o_ref[:, cols] += part * mine
        else:
            def accum(o_ref, part):
                o_ref[...] = jnp.where(k == 0, part, o_ref[...] + part)

        @pl.when(t < meta_ref[1])       # false only where no slot is in use
        def _():
            fam.kernel(x_ref, *plane_refs, o_ref, interpret=interpret,
                       accum=accum)

    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=specs,
            out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct((xpa.shape[0], N), jnp.float32),
        interpret=interpret, name=expert_kernel_name(fam.name, few), **kw,
    )(meta, xpa, *extra_in, *planes)


def grouped_matmul_few(fam: _Family, meta, x, row_expert, planes, f: int,
                       interpret: bool) -> jax.Array:
    """x (R, K) rows against ``planes[meta[0], row_expert[r]]`` -> (R, N)
    f32, zero for a row without an expert; R <= FEW_ROWS."""
    R = x.shape[0]
    pad = -(R * f) % 16                    # bf16 packs 16 rows a tile
    xf = _fold_rows(x, f, R)               # copy j of row r at j*R + r
    xf = jnp.pad(xf, ((0, pad), (0, 0)))
    re = jnp.pad(jnp.tile(row_expert, f), (0, pad),
                 constant_values=jnp.iinfo(jnp.int32).max)
    out = _grouped_call(fam, meta, _activations(xf, fam), planes,
                        xf.shape[0], True, (re[:, None],), interpret)
    return _unfold_rows(out[:R * f], f, R)


def grouped_matmul_many(fam: _Family, meta, xp, planes, f: int,
                        interpret: bool) -> jax.Array:
    """xp (T*TM_MANY, K), rows in the padded layout of :func:`plan_groups`,
    against ``planes[meta[0], tile's expert]`` -> (T*TM_MANY, N) f32 (the
    tiles past the last in use are not written: no row's ``pos`` is there)."""
    out = _grouped_call(fam, meta, _activations(
        _fold_rows(xp, f, TM_MANY), fam), planes, TM_MANY * f, False, (),
        interpret)
    return _unfold_rows(out, f, TM_MANY)


# ---------------------------------------------------------------------------
# the layer after the router
# ---------------------------------------------------------------------------

def _routed_raw(fams: tuple, interpret: bool, idx, x, picks, weights,
                *planes):
    """x (M, D), picks (M, k) int32 in [0, E] (E: none), weights (M, k) f32
    -> (y (M, D) in x.dtype, rows per expert (E,) int32)."""
    gate, up, down = (FAMILIES[f] for f in fams)
    n_g, n_u = len(gate.planes), len(up.planes)
    pg, pu, pd = planes[:n_g], planes[n_g:n_g + n_u], planes[n_g + n_u:]
    M, D = x.shape
    k = picks.shape[1]
    R = M * k
    E = pg[0].shape[1]
    layer = jnp.asarray(idx, jnp.int32).reshape(1)
    row_expert = picks.reshape(R)
    few = R <= FEW_ROWS

    def products(call, xr):
        """Rows -> their picked experts' SwiGLU, ``call`` a grouped matmul."""
        g = call(gate, xr, pg)
        u = call(up, xr, pu)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        return call(down, h, pd)

    def back(out, pos):         # from the places of a layout to the rows
        P = out.shape[0]
        rows = out[jnp.minimum(pos, P - 1)]
        return jnp.where((pos < P)[:, None], rows, 0.0)

    if few:
        count, experts, n_used = experts_in_use(row_expert, E,
                                                decode_slots(E, M, k))

        def few_rows(xr, row_expert):
            return products(
                lambda fam, rows, w: grouped_matmul_few(
                    fam, meta, rows, row_expert, w,
                    fold_factor(rows.shape[1]), interpret), xr)

        def as_it_was():
            return few_rows(jnp.repeat(x, k, axis=0), row_expert)

        if compacted_rows(M, k):
            # the rows that reach an expert, in a call of ROW_GROUP rows,
            # put back in their places (zero where a row had none); the
            # call of all the rows where more of them do
            meta = jnp.concatenate([layer, n_used[None], experts])
            place, src, n_real = compact_rows(row_expert, E, ROW_GROUP)

            def compacted():
                token = jnp.where(src < R, src // k, M)   # M: the zero row
                xc = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[token]
                ec = jnp.concatenate([row_expert, jnp.full(1, E, jnp.int32)])
                return back(few_rows(xc, ec[src]), place)

            out = jax.lax.cond(n_real <= ROW_GROUP, compacted, as_it_was)
        else:
            xr = jnp.repeat(x, k, axis=0)              # row (m, j) = x[m]
            meta = jnp.concatenate([layer, n_used[None], experts])
            out = few_rows(xr, row_expert)
    else:
        plan = plan_groups(row_expert, E, M, TM_MANY)
        count, experts, n_used = (plan["count"], plan["tile_expert"],
                                  plan["n_used"])
        # each padded slot's token (row // k), or the zero row M
        token = jnp.where(plan["src"] < R, plan["src"] // k, M)
        xr = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[token]
        meta = jnp.concatenate([layer, n_used[None], experts])
        out = back(products(
            lambda fam, rows, w: grouped_matmul_many(
                fam, meta, rows, w, fold_factor(rows.shape[1]), interpret),
            xr), plan["pos"])
    y = jnp.sum(out.reshape(M, k, D) * weights[:, :, None], axis=1)
    return y.astype(x.dtype), count


@functools.lru_cache(maxsize=8)
def _routed_fn(fams: tuple | None, interpret: bool = False):
    """The jitted layer with its vmap rule: lanes become rows, of the
    grouped kernels (``fams``: the three matrices' families) or of the
    dequantized fallback (None)."""
    from jax.custom_batching import custom_vmap

    raw = functools.partial(_routed_raw, fams, interpret) \
        if fams else _routed_dense

    @custom_vmap
    def fn(idx, x, picks, weights, *planes):
        return raw(idx, x, picks, weights, *planes)

    @fn.def_vmap
    def _rule(axis_size, in_batched, idx, x, picks, weights, *planes):
        if in_batched[0] or any(in_batched[4:]):
            raise NotImplementedError(
                "routed experts vmap: only the activations, picks and their "
                "weights may carry the batch axis (weights are shared)")
        x, picks, weights = (
            a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, b in zip((x, picks, weights), in_batched[1:4]))
        nb, M = x.shape[:2]
        y, count = fn(idx, x.reshape(nb * M, -1), picks.reshape(nb * M, -1),
                      weights.reshape(nb * M, -1), *planes)
        return (y.reshape(nb, M, -1), count), (True, False)

    return jax.jit(fn)


def routed_experts(x: jax.Array, picks: jax.Array, weights: jax.Array,
                   w_gate: dict, w_up: dict, w_down: dict, idx,
                   interpret: bool | None = None):
    """The routed feed-forward of layer ``idx`` after its router: ``sum_j
    weights[m, j] * down_e(silu(gate_e(x[m])) * up_e(x[m]))`` with ``e =
    picks[m, j]`` (``E``: no expert, contributes nothing), over expert
    planes stacked (L, E, ...).  Returns (y (M, D), rows each expert took
    (E,) int32: an expert is read iff its count is not 0)."""
    fams = tuple(family_of(w) for w in (w_gate, w_up, w_down))
    if None in fams:
        fn, planes = _routed_fn(None), [w["w"] for w in (w_gate, w_up, w_down)]
    else:
        fn = _routed_fn(fams, _interpret(interpret))
        planes = [w[key] for w, f in zip((w_gate, w_up, w_down), fams)
                  for key in FAMILIES[f].planes]
    return fn(jnp.asarray(idx, jnp.int32), x, picks, weights, *planes)


def _routed_dense(idx, x, picks, weights, w_gate, w_up, w_down):
    """The same layer over dequantized experts (L, E, N, K): every expert's
    product, the picked ones kept.  For files whose expert tensors have no
    fused kernel (and the CPU tests' bf16 loads); E times the work, so
    nothing to serve a large model with."""
    E = w_gate.shape[1]
    wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, idx, 0, keepdims=False)
                  for w in (w_gate, w_up, w_down))
    f32 = jnp.float32
    g = jnp.einsum("md,efd->mef", x, wg, preferred_element_type=f32)
    u = jnp.einsum("md,efd->mef", x, wu, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    out = jnp.einsum("mef,edf->med", h, wd, preferred_element_type=f32)
    onehot = jax.nn.one_hot(picks, E, dtype=f32)          # pick E: all zero
    share = jnp.einsum("mk,mke->me", weights.astype(f32), onehot)
    y = jnp.einsum("me,med->md", share, out)
    count = jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)
    return y.astype(x.dtype), count


# devtime inventory (lfkt-lint PERF001): trace-inner, as the dense builders
register_program("_grouped_call", site="ops.pallas.experts")
register_program("_routed_fn", site="ops.pallas.experts")
