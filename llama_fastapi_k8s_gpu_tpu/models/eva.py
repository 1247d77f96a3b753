"""The second cache KIND: an exact window plus one summary per chunk of
every earlier window (``general.architecture = "evabyte"``; EVA attention,
Zheng et al., ICLR 2023, with the random feature replaced by a learned one
as EvaByte's released modelling code computes it).

Per layer and KV head the cache of a sequence holds (docs/KV_CACHE.md
"Cache kinds"):

- two WINDOW leaves ``k``/``v`` of ``W = cfg.eva_window`` slots: position t
  writes slot ``t mod W``.  The window is BLOCKED, not sliding: query t sees
  the exact keys of its own window ``w(t) = t // W`` only, ``w(t) W <= m <=
  t``, which are the slots ``<= t mod W``; staler slots are hidden by that
  mask and never cleared;
- two SUMMARY leaves ``sk``/``sv``: for every chunk of ``C = cfg.eva_chunk``
  positions of every CLOSED window one pooled key ``ktilde_c = sum_j a_j
  k_j + mu`` and one pooled value ``beta_c = sum_j a_j v_j``, ``a_j =
  softmax_j(s phi . k_j)`` over the chunk's C (rotated) keys, ``phi``/``mu``
  two learned vectors per head and layer.  The step or slice that writes
  position ``(w + 1) W - 1`` closes window w: its ``G = W / C`` summaries go
  to ``[G w, G (w + 1))``.  Query t sees the summaries of the windows
  before its own, ``c < w(t) G``, and none of its own window.

Attention is ONE softmax over both sets of scores.  A decode step reads
blocks of live window slots and then blocks of live summaries under TRACED
bounds with a running max and sum (``models/llama.py decode_attention``'s
recurrence), so one program serves every position; under ``vmap`` over
lanes the bounds are the largest LIVE lane's, unbatched, and a lane's
result does not depend on them (a block wholly beyond the lane's own fill
adds exactly nothing).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .cache import CacheKind
from .config import WINDOW_SUMMARIES, ModelConfig

#: window slots a decode step reads at a time (``models/llama.py
#: DECODE_KV_BLOCK``'s measurement: below 512 an iteration's fixed cost
#: eats what finer blocks save)
WINDOW_BLOCK = 512


def n_windows(cfg: ModelConfig) -> int:
    return -(-cfg.n_ctx // cfg.eva_window)


def per_window(cfg: ModelConfig) -> int:
    """Summaries one closed window leaves: G = W / C."""
    return cfg.eva_window // cfg.eva_chunk


def n_closable(cfg: ModelConfig) -> int:
    """Windows whose summaries a sequence of ``n_ctx`` positions can come
    to read: all but the last."""
    return n_windows(cfg) - 1


def n_summaries(cfg: ModelConfig) -> int:
    """Slots of a summary leaf: G for every closable window (one window's
    worth where ``n_ctx <= W``, so that no leaf is empty)."""
    return max(n_closable(cfg), 1) * per_window(cfg)


def window_block(cfg: ModelConfig) -> int:
    W = cfg.eva_window
    return WINDOW_BLOCK if W % WINDOW_BLOCK == 0 else W


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """Head-major like the ring: ``k``/``v`` (L, n_kv, W, hd), ``sk``/``sv``
    (L, n_kv, n_summaries, hd)."""
    if cfg.kv_dtype not in ("bf16", "bfloat16"):
        raise ValueError(
            f"kv_dtype={cfg.kv_dtype!r} cannot hold architecture 'evabyte': "
            "its window + summary cache is bf16 only")
    head = (cfg.n_layers, cfg.n_kv_heads)
    win = head + (cfg.eva_window, cfg.head_dim)
    summ = head + (n_summaries(cfg), cfg.head_dim)
    return {"k": jnp.zeros(win, dtype), "v": jnp.zeros(win, dtype),
            "sk": jnp.zeros(summ, dtype), "sv": jnp.zeros(summ, dtype)}


def cache_nbytes(cfg: ModelConfig) -> int:
    entries = cfg.eva_window + n_summaries(cfg)
    return 2 * cfg.n_layers * cfg.n_kv_heads * entries * cfg.head_dim * 2


# ---------------------------------------------------------------------------
# bounds and host arithmetic
# ---------------------------------------------------------------------------

def own_bounds(pos, cfg: ModelConfig):
    """(window fill, windows closed before it, whether this step closes a
    window) of ONE sequence whose decode step is at ``pos``: what a serial
    step reads up to."""
    W = cfg.eva_window
    return pos % W, pos // W, (pos + 1) % W == 0


def live_bounds(pos: jax.Array, live, cfg: ModelConfig):
    """The same three for a step over lanes, each ONE unbatched scalar
    (``parallel/batched.py live_bound``): the largest fill and the most
    closed windows among the lanes that hold a request (``live`` (B,) bool;
    None: all), and whether any of them closes a window in this step.  A
    freed lane keeps stepping and must not drag the read along."""
    W = cfg.eva_window
    p = pos if live is None else jnp.where(live, pos, 0)
    closing = (pos + 1) % W == 0
    if live is not None:
        closing &= live
    return jnp.max(p % W), jnp.max(p // W), jnp.any(closing)


def _read_blocks(fill, wins, cfg: ModelConfig):
    """(window blocks, summary blocks) a decode step covers under the
    bounds (fill, windows closed); host ints or traced scalars alike."""
    T = window_block(cfg)
    least = min if isinstance(fill, int) else jnp.minimum
    return (least((fill + T) // T, cfg.eva_window // T),
            least(wins, n_summaries(cfg) // per_window(cfg)))


def chunk_counts(positions: list[int], n_steps: int, cfg: ModelConfig,
                 live: list[int] | None = None) -> dict:
    """What ``n_steps`` decode steps read and needed, summed over the
    sequences that start the chunk at ``positions``: their steps, window
    slots and summaries covered / live, and windows closed.  Every one reads up to
    the bounds of the lanes at ``live`` (default ``positions``: a serial
    engine's one sequence is its own bound), which walk with the steps.
    Host arithmetic from tracked positions for the ``eva_*`` counters:
    nothing is fetched."""
    W, G, T = cfg.eva_window, per_window(cfg), window_block(cfg)
    out = {"lane_steps": n_steps * len(positions), "window_read": 0,
           "window_live": 0, "summaries_read": 0, "summaries_live": 0,
           "windows_closed": 0}
    for t in range(n_steps):
        at = [p + t for p in (live or positions)]
        wb, sb = _read_blocks(max(p % W for p in at), max(p // W for p in at),
                              cfg)
        for p in (p + t for p in positions):
            out["window_read"] += wb * T
            out["window_live"] += p % W + 1
            out["summaries_read"] += sb * G
            out["summaries_live"] += min(p // W, n_closable(cfg)) * G
            out["windows_closed"] += int((p + 1) % W == 0
                                         and p // W < n_closable(cfg))
    return out


def windows_closed_by_prefill(n_prompt: int, cfg: ModelConfig) -> int:
    """Windows a prompt of ``n_prompt`` positions closes for good (whole
    windows of real tokens)."""
    return min(n_prompt // cfg.eva_window, n_closable(cfg))


# ---------------------------------------------------------------------------
# window close
# ---------------------------------------------------------------------------

def summarize(kwin, vwin, phi, mu, cfg: ModelConfig):
    """The G summaries of one full window.  ``kwin``/``vwin`` (n_kv, W, hd)
    as the cache holds them (rotated keys), ``phi``/``mu`` (n_kv, hd) f32.
    Returns (ktilde, beta), each (n_kv, G, hd) f32."""
    n_kv, W, hd = kwin.shape
    G, C = per_window(cfg), cfg.eva_chunk
    hi = jax.lax.Precision.HIGHEST
    k = kwin.astype(jnp.float32).reshape(n_kv, G, C, hd)
    v = vwin.astype(jnp.float32).reshape(n_kv, G, C, hd)
    a = jax.nn.softmax(
        jnp.einsum("hd,hgcd->hgc", phi, k, precision=hi) * hd ** -0.5,
        axis=-1)
    ktilde = jnp.einsum("hgc,hgcd->hgd", a, k, precision=hi) + mu[:, None, :]
    beta = jnp.einsum("hgc,hgcd->hgd", a, v, precision=hi)
    return ktilde, beta


def close_window(cache: dict, i, w, mine, any_closing, phi, mu,
                 cfg: ModelConfig) -> dict:
    """Window close of layer ``i``: where ``any_closing`` (an UNBATCHED
    scalar under ``vmap``, so the work is a real branch and not a select),
    summarise the layer's window leaves and, where this sequence is the
    one closing (``mine``) and window ``w`` has a place in the summary
    leaves, write them at ``[G w, G (w + 1))``.  The last window of
    ``n_ctx`` has none (nothing can come to read it), nor has a freed
    lane's position that has walked past ``n_ctx``: an unguarded write
    there would be clamped onto the last valid window's summaries."""
    n_kv, hd, W, G = cfg.n_kv_heads, cfg.head_dim, cfg.eva_window, \
        per_window(cfg)
    ok = mine & (w < n_closable(cfg))
    at = jnp.clip(w, 0, max(n_closable(cfg) - 1, 0)) * G

    def close(sk, sv):
        with jax.named_scope("eva_window_close"):
            kwin = jax.lax.dynamic_slice(
                cache["k"], (i, 0, 0, 0), (1, n_kv, W, hd))[0]
            vwin = jax.lax.dynamic_slice(
                cache["v"], (i, 0, 0, 0), (1, n_kv, W, hd))[0]
            ktilde, beta = summarize(kwin, vwin, phi, mu, cfg)
            out = []
            for leaf, new in ((sk, ktilde), (sv, beta)):
                old = jax.lax.dynamic_slice(
                    leaf, (i, 0, at, 0), (1, n_kv, G, hd))
                new = jnp.where(ok, new.astype(leaf.dtype)[None], old)
                out.append(jax.lax.dynamic_update_slice(
                    leaf, new, (i, 0, at, 0)))
            return tuple(out)

    sk, sv = jax.lax.cond(any_closing, close, lambda sk, sv: (sk, sv),
                          cache["sk"], cache["sv"])
    return {**cache, "sk": sk, "sv": sv}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def prefill_attention(q, cache: dict, i, positions, cfg: ModelConfig,
                      out_dtype):
    """A prompt slice's attention (S > 1, all of it inside ONE window):
    exact causal scores over the window's slots ``<= t mod W`` and scores
    over the summaries of the windows before, one softmax over both.
    ``q`` (S, n_heads, hd); ``cache`` the stacked leaves with this slice's
    keys and values already written."""
    S = q.shape[0]
    n_kv, group, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
        cfg.head_dim
    W, G = cfg.eva_window, per_window(cfg)
    k, v, sk, sv = (jax.lax.dynamic_index_in_dim(cache[n], i, 0, False)
                    for n in ("k", "v", "sk", "sv"))
    qg = q.reshape(S, n_kv, group, hd).transpose(1, 2, 0, 3)
    with jax.named_scope("eva_scores"):
        s_w = jnp.einsum("ngsh,nch->ngsc", qg, k,
                         preferred_element_type=jnp.float32) * hd ** -0.5
        s_s = jnp.einsum("ngsh,nch->ngsc", qg, sk,
                         preferred_element_type=jnp.float32) * hd ** -0.5
    m_w = jnp.arange(W)[None, :] <= (positions % W)[:, None]
    m_s = (jnp.arange(sk.shape[1]) // G)[None, :] < (positions // W)[:, None]
    scores = jnp.concatenate(
        [jnp.where(m_w[None, None], s_w, -jnp.inf),
         jnp.where(m_s[None, None], s_s, -jnp.inf)], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    with jax.named_scope("eva_pv"):
        ctx = jnp.einsum("ngsc,nch->ngsh", probs[..., :W], v,
                         preferred_element_type=jnp.float32) \
            + jnp.einsum("ngsc,nch->ngsh", probs[..., W:], sv,
                         preferred_element_type=jnp.float32)
    return ctx.transpose(2, 0, 1, 3).reshape(
        S, cfg.n_heads * hd).astype(out_dtype)


def decode_attention(q, cache: dict, i, pos, bounds, cfg: ModelConfig,
                     out_dtype):
    """A decode step's attention (S = 1): the flash recurrence over blocks
    of window slots up to ``bounds[0]`` and then over the summaries of
    ``bounds[1]`` windows, one window's G at a time, masked by this
    sequence's own ``pos``.  ``bounds`` >= this sequence's own
    (:func:`own_bounds`) for every sequence whose output is used, and
    unbatched under ``vmap`` (:func:`live_bounds`).

    A LANE'S RESULT DOES NOT DEPEND ON ``bounds``: a block wholly beyond
    its fill, or a window's summaries it may not see, has every score at
    -inf, leaves the running max as it was and adds probabilities of
    exactly 0.0 under a rescale of exactly 1.0; the order of the blocks
    (window, then summaries) is the same under every bound."""
    n_kv, group, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
        cfg.head_dim
    W, G, T = cfg.eva_window, per_window(cfg), window_block(cfg)
    qg = q.reshape(n_kv, group, 1, hd)
    i = jnp.asarray(i, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    fill, wins = pos % W, pos // W
    n_wb, n_sb = _read_blocks(jnp.asarray(bounds[0], jnp.int32),
                              jnp.asarray(bounds[1], jnp.int32), cfg)

    def step(carry, kb, vb, mask):
        m, l, acc = carry
        with jax.named_scope("eva_scores"):
            s = jnp.einsum("ngsh,nch->ngsc", qg, kb,
                           preferred_element_type=jnp.float32) * hd ** -0.5
        s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        with jax.named_scope("eva_pv"):
            acc = acc * alpha[..., None] + jnp.einsum(
                "ngsc,nch->ngsh", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
        return m_new, l, acc

    def block_of(kname, vname, at, n):
        return (jax.lax.dynamic_slice(
                    cache[kname], (i, 0, at, 0), (1, n_kv, n, hd))[0],
                jax.lax.dynamic_slice(
                    cache[vname], (i, 0, at, 0), (1, n_kv, n, hd))[0])

    def window_step(j, carry):
        kb, vb = block_of("k", "v", j * T, T)
        return step(carry, kb, vb, j * T + jnp.arange(T) <= fill)

    def summary_step(j, carry):
        kb, vb = block_of("sk", "sv", j * G, G)
        return step(carry, kb, vb, jnp.broadcast_to(j < wins, (G,)))

    # a finite floor, not -inf: a block that holds nothing of this sequence
    # must leave exp(m - m_new) = 1 (models/llama.py decode_attention)
    carry = (jnp.full((n_kv, group, 1), -1e30, jnp.float32),
             jnp.zeros((n_kv, group, 1), jnp.float32),
             jnp.zeros((n_kv, group, 1, hd), jnp.float32))
    carry = jax.lax.fori_loop(0, n_wb, window_step, carry)
    _, l, acc = jax.lax.fori_loop(0, n_sb, summary_step, carry)
    ctx = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return ctx.transpose(2, 0, 1, 3).reshape(
        1, cfg.n_heads * hd).astype(out_dtype)


def attend(q, k, v, cache: dict, i, positions, kv_bound, phi, mu,
           cfg: ModelConfig, out_dtype):
    """One layer's cache write, attention and window close for the S new
    positions ``positions`` (all inside ONE window): ``q`` (S, n_heads,
    hd), ``k``/``v`` (S, n_kv, hd) rotated.  ``kv_bound``: a decode step's
    :func:`live_bounds` (None: this sequence's own).  Returns (ctx (S,
    n_heads * hd), cache)."""
    S, W = q.shape[0], cfg.eva_window
    if S > W:
        raise ValueError(
            f"architecture 'evabyte': {S} positions in one pass, its window "
            f"holds {W}: a prompt is prefilled in slices that lie inside "
            "one window (LFKT_PREFILL_CHUNK)")
    first, last = positions[0], positions[S - 1]
    new = {}
    for name, x in (("k", k), ("v", v)):
        # head-major write: transpose only the S new tokens
        slab = x.astype(cache[name].dtype).transpose(1, 0, 2)[None]
        with jax.named_scope("kv_write"):
            new[name] = jax.lax.dynamic_update_slice(
                cache[name], slab, (i, 0, first % W, 0))
    cache = {**cache, **new}
    # the step or slice that writes a window's last position closes it
    closing = any_closing = (last + 1) % W == 0
    if S == 1:
        bounds = own_bounds(first, cfg) if kv_bound is None else kv_bound
        any_closing = bounds[2]
        ctx = decode_attention(q, cache, i, first, bounds, cfg, out_dtype)
    else:
        ctx = prefill_attention(q, cache, i, positions, cfg, out_dtype)
    cache = close_window(cache, i, last // W, closing, any_closing, phi, mu,
                         cfg)
    return ctx, cache


def _slice_rule(cfg: ModelConfig, chunk: int) -> str | None:
    W, C = cfg.eva_window, cfg.eva_chunk
    if W % chunk or chunk % C:
        return (f"a prefill slice must divide its attention window ({W}) "
                f"and be a multiple of its chunk ({C}), so that no slice "
                "lies astride a window")


def _health(cfg: ModelConfig, engine) -> dict:
    return {"kind": WINDOW_SUMMARIES, "window": cfg.eva_window,
            "chunk": cfg.eva_chunk, "summaries": n_summaries(cfg),
            "prefix_reuse": "off: reuse is by token position and a "
                            "window restarts",
            "kv_paged": "refused at start"}


def _note_prefill(counts, cfg: ModelConfig, n_prompt: int, slices) -> dict:
    n = windows_closed_by_prefill(n_prompt, cfg)
    counts["windows_closed"] += n
    return {"windows_closed": n}


CACHE = CacheKind(
    name=WINDOW_SUMMARIES, arch="evabyte",
    init=init_cache, nbytes=cache_nbytes,
    step_bound=lambda cfg, pos, live: live_bounds(pos, live, cfg),
    supports={
        "int8": "its window + summary cache is bf16 only",
        "paged": "the pool pages runs of ring slots by token position, and "
                 "its cache is a window that restarts plus chunk summaries"},
    slice_rule=_slice_rule,
    # its attention is this file's own; no kernel, and no ring to write
    attn_impl=lambda cfg, asked: "xla",
    decode_kernel_block=lambda cfg: 0, kernel_writes=None,
    # the slice attention holds every head's scores whole (386 MB at 1024
    # rows of the published widths, beside a chip 83 % full)
    widest_slice=lambda cfg: min(256, cfg.eva_window),
    health=_health,
    own_gauges={"eva_lane_steps_total": "lane_steps",
                "eva_window_slots_read_total": "window_read",
                "eva_window_slots_live_total": "window_live",
                "eva_summaries_read_total": "summaries_read",
                "eva_summaries_live_total": "summaries_live",
                "eva_windows_closed_total": "windows_closed"},
    note_decode=lambda counts, cfg, wanted, n_steps, live=None: counts.update(chunk_counts(wanted, n_steps, cfg, live)),
    note_prefill=_note_prefill)
