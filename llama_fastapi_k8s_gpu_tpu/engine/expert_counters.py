"""The routed layers' counters, host side.

A decode chunk of a routed block returns, beside its tokens, one small
int32 vector (models/llama.py ``expert_stats_len``): the (layer, step) pairs
it ran, the distinct experts read summed over them, the rows each expert
HELD here took, and the picks the routers made over all experts (what
left the chip is those less the rows taken: models/mla.py) and, where the
router has zero experts (models/routed.py), how many of them fell on one.
The engines hand that DEVICE array here at dispatch
(:meth:`push`: a list append, nothing fetched, nothing waited for) and the
totals are folded when somebody reads them: a ``/metrics`` scrape folds the
chunks that have finished (:meth:`snapshot`), a test folds them all
(``block=True``).  With nobody reading, nothing on the decode path pays
more than the append.

``slots_skipped`` is host arithmetic on those totals: a decode step's
grouped expert call has ``n_slots`` slots whatever the router picked
(ops/pallas/experts.py ``decode_slots``) and its grid ends at the slots in
use, so ``layer_steps x n_slots - experts_read`` slots were never walked.
``rows_skipped`` is the same for the call's ROW axis: a step's layer of
``n_rows`` (token, pick) rows that is compacted to the rows that reach a
held expert (``compacted_rows``: more than 64 rows) multiplies those in a
block of 64, so ``layer_steps x n_rows - picks_held`` rows were offered and
never multiplied (a step with more than 64 such rows multiplies them all:
not told apart here, and not seen where a share of the experts is held).

A stack whose layers run several times (``cfg.ut_steps`` > 1;
models/llama.py ``exit_mass``) returns a float32 vector instead, the exit
gate's mass a pass summed over the chunk's decoded tokens, and
:class:`ExitMass` folds it the same way (``ut_exit_mass_total{pass=}``).
"""

from __future__ import annotations

import threading

import numpy as np

_MAX_PENDING = 64   # older chunks have long finished: folding them is free


class _Folded:
    """Device vectors pushed at dispatch, summed when somebody reads."""

    def __init__(self, n: int, dtype):
        self._lock = threading.Lock()
        self._pending: list = []
        self._total = np.zeros(n, dtype)

    def push(self, stats) -> None:
        """One dispatched chunk's vector (a device array)."""
        with self._lock:
            self._pending.append(stats)
            if len(self._pending) > _MAX_PENDING:
                self._total += np.asarray(self._pending.pop(0))

    def _fold(self, block: bool) -> np.ndarray:
        """The total with the finished chunks (all with ``block``) folded
        in; called under the lock."""
        keep = []
        for s in self._pending:
            if block or s.is_ready():
                self._total += np.asarray(s)
            else:
                keep.append(s)
        self._pending = keep
        return self._total


class ExitMass(_Folded):
    """The exit gate's mass a pass, summed over the decoded tokens."""

    def __init__(self, ut_steps: int):
        super().__init__(ut_steps, np.float64)

    def snapshot(self, block: bool = False) -> list:
        with self._lock:
            return self._fold(block).tolist()


class ExpertCounters(_Folded):
    def __init__(self, n_held: int, n_slots: int = 0, zero: bool = False,
                 n_rows: int = 0):
        self.n_slots = n_slots      # 0: no grouped few-row call serves
        self.n_rows = n_rows        # 0: or it is built as it always was
        self.n_held = n_held
        # (``zero``: the router has zero experts, and the vector their count)
        super().__init__(3 + n_held + zero, np.int64)

    def snapshot(self, block: bool = False) -> dict:
        """Cumulative counters of the chunks that have finished (all
        dispatched chunks with ``block``): ``layer_steps``, ``experts_read``,
        ``picks`` (a list, one count per held expert), ``picks_held`` (their
        sum), ``picks_total`` (over all the router's outputs),
        ``picks_zero`` (of those, the picks of a zero expert; 0 where the
        router has none), ``slots_skipped`` and ``rows_skipped`` (module
        docstring)."""
        with self._lock:
            t = self._fold(block)
            picks = t[2:2 + self.n_held]
            held = int(picks.sum())
            return {"layer_steps": int(t[0]), "experts_read": int(t[1]),
                    "picks": picks.tolist(), "picks_held": held,
                    "picks_total": int(t[2 + self.n_held]),
                    "picks_zero": int(t[3 + self.n_held:].sum()),
                    "slots_skipped": int(t[0] * self.n_slots - t[1])
                    if self.n_slots else 0,
                    "rows_skipped": int(t[0] * self.n_rows) - held
                    if self.n_rows else 0}
