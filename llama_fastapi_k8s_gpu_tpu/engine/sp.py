"""Sequence-parallel serving engine: long context over an sp×tp mesh.

The reference *suppresses* context (n_ctx=1024, 400-char clips, oldest-
message eviction — reference api.py:27,37-46); this engine scales it
instead: the KV cache's n_ctx dimension shards over the ``sp`` mesh axis and
attention runs as ring attention for prefill / sharded-LSE for decode
(parallel/ring.py), so no chip ever holds more than 1/sp of the KV.  Max
context grows linearly with the ring size while the serving surface — the
``create_chat_completion`` contract, streaming, admission control — stays
exactly :class:`Engine`'s (only the two jit call points are rerouted onto
the mesh).

Enable from the server with ``LFKT_MESH_SP > 1`` (utils/config.py); combine
with ``LFKT_MESH_TP`` for heads-sharded attention inside the ring.
"""

from __future__ import annotations

import dataclasses
import logging

import jax

from ..models.llama import init_cache
from ..parallel.mesh import make_mesh, shard_params
from ..parallel.ring import sp_generate_chunk, sp_prefill, sp_state_shardings
from .engine import Engine

logger = logging.getLogger(__name__)


class SPEngine(Engine):
    """An :class:`Engine` whose KV cache and attention are sequence-parallel.

    Serial like the base engine (one generation at a time, the reference's
    concurrency model); the mesh is spent on *context length*, not batch.
    """

    #: the overlapped slice-prefill path (Engine._prefill_padded) drives
    #: prefill_chunk_jit against an unsharded ring; this engine's ring is
    #: sp-sharded over n_ctx and its prefill is the rerouted ring program
    #: (sp_prefill), so it keeps monolithic bucket prefill.
    _SLICE_PREFILL = False

    #: the paged KV pool (LFKT_KV_PAGED, parallel/kvpool.py) slices and
    #: updates the ring's n_ctx dim, which this engine shards over the sp
    #: axis — paging stays off (Engine.__init__ warns and serves the
    #: dense sharded ring; greedy output is identical either way).
    _KV_PAGED = False

    #: asked of the cache kind (Engine._refuse_unsupported): slots to shard
    _asks = {"sp": True}

    def __init__(self, model_path: str | None, *, sp: int = 2, tp: int = 1,
                 n_ctx: int = 4096, **kw):
        if sp < 2:
            raise ValueError(f"SPEngine needs sp >= 2, got {sp} "
                             f"(use Engine for single-chip serving)")
        attn = kw.pop("attn_impl", "auto")
        if attn not in ("auto", "ring"):
            raise ValueError(
                f"SPEngine serves ring attention; attn_impl must be "
                f"auto|ring, got {attn!r}")
        super().__init__(model_path, n_ctx=n_ctx, attn_impl="xla", **kw)
        if self.cfg.n_ctx % sp:
            raise ValueError(f"n_ctx {self.cfg.n_ctx} must divide sp={sp}")
        with self.startup.phase("sp_alloc"):
            self.mesh = make_mesh(dp=1, tp=tp, sp=sp)
            self.sp = sp
            self.params = shard_params(self.params, self.mesh)
            self.cfg = dataclasses.replace(self.cfg, attn_impl="ring")
            # ring prefill shards the token dim: buckets round up to sp
            # multiples
            self.prefill_buckets = sorted(
                {min(self.cfg.n_ctx, -(-b // sp) * sp)
                 for b in self.prefill_buckets})
            self._cache = jax.device_put(
                init_cache(self.cfg), sp_state_shardings(self.cfg, self.mesh))
        logger.info("SPEngine: n_ctx=%d over sp=%d tp=%d (%d devices)",
                    self.cfg.n_ctx, sp, tp, sp * tp)

    def _trace_attrs(self) -> dict:
        """The ``engine`` span / /debug/requests identity, extended with
        the ring geometry so a slow long-context request's waterfall says
        which mesh shape served it."""
        return {**super()._trace_attrs(), "sp": self.sp,
                "devices": self.sp * self.mesh.shape["tp"],
                "tp": self.mesh.shape["tp"]}

    def _recover_locked(self) -> None:  # lfkt: holds[_lock]
        """Watchdog recovery: the fresh ring must carry the same sp-sharded
        layout __init__ installed — the base class's unsharded init_cache
        would replicate the full n_ctx ring per device, defeating the
        reason sp exists (HBM) on the first post-recovery request."""
        super()._recover_locked()
        self._cache = jax.device_put(
            init_cache(self.cfg), sp_state_shardings(self.cfg, self.mesh))

    # -- jit call points rerouted onto the mesh -----------------------------
    def _prefill_call(self, tokens, length, cache):
        return sp_prefill(self.params, self.cfg, tokens, length, cache,
                          self.mesh)

    def _decode_chunk_call(self, state, st, n_steps: int, top_k: int,
                           pos: int):
        # ring attention reads its whole shard: nothing for cache_counts
        state, out = sp_generate_chunk(self.params, self.cfg, state, st,
                                       self.mesh, n_steps, top_k)
        return state, self._take_expert_stats(out)
