"""``spec_decode="auto"``: decide speculation from MEASURED dispatch latency.

Whether prompt-lookup speculation pays depends on the deployment's dispatch
round trip: at tens of milliseconds its breakeven acceptance is far above
what lookup reaches, at a fraction of a millisecond lookup's typical 1-3
acceptance on re-sent-history chat pays handily (VERDICT r4 weak #5).
Rather than ship a constant, "auto" makes the decision from the
deployment's own numbers at engine construction.

Cost model (docs/PERF.md "Speculative decoding under the continuous
scheduler"): pipelined chunked decode hides dispatch behind device compute,
so its steady per-token cost is the weight read
``t_tok = bytes_per_token / hbm_bw``.  A verify round cannot pipeline —
drafts depend on the previous round's accepted tokens — so each round pays
the full dispatch round trip ``rtt`` and yields ``1 + a`` tokens
(``a`` = acceptance).  Per-token cost ``(t_tok + rtt) / (1 + a)`` beats
``t_tok`` iff ``a > rtt / t_tok``:

    breakeven_acceptance = rtt / t_tok

"auto" enables lookup iff breakeven < ``LFKT_SPEC_AUTO_ACCEPT`` (default
1.0 — the conservative end of prompt-lookup's 1-3 on workloads that re-send
persona + chat history verbatim, reference api.py:44-63).  The decision and
all its inputs are logged and exposed as ``engine.spec_auto_decision``.
"""

from __future__ import annotations

import time

from ..utils.config import knob

HBM_GBPS_DEFAULT = 819.0   # v5e spec; override via LFKT_HBM_GBPS
#                            (registry default mirrors this constant)


def measure_dispatch_rtt_s(n: int = 7) -> float:
    """Median wall time of a minimal jitted dispatch + host fetch.

    This is the per-verify-round overhead spec decoding pays: the host→
    device dispatch plus the device→host fetch of the sampled tokens.  Two
    warm executions (the compile, and the first run) are discarded first."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)  # lfkt: noqa[PERF001] -- raw-dispatch RTT probe: devtime wrapping would add the very overhead being measured
    x = jnp.zeros((), jnp.int32)
    for _ in range(2):
        int(f(x))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        int(f(x))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[n // 2]


def decode_bytes_per_token(params) -> int:
    """HBM bytes one decode token must read: every weight byte except the
    token-embedding table (a single-row gather)."""
    import jax

    emb = params.get("tok_emb") if isinstance(params, dict) else None
    emb_bytes = getattr(emb, "nbytes", 0)
    total = sum(getattr(leaf, "nbytes", 0)
                for leaf in jax.tree.leaves(params))
    return max(total - emb_bytes, 1)


def resolve_auto(params, *, hbm_gbps: float | None = None,
                 accept: float | None = None) -> tuple[str, dict]:
    """→ ("lookup" | "off", decision record).  Never raises: a measurement
    failure resolves to "off" with the error recorded (degradation
    contract, docs/PERF.md)."""
    if hbm_gbps is None:
        hbm_gbps = knob("LFKT_HBM_GBPS", default=HBM_GBPS_DEFAULT)
    if accept is None:
        accept = knob("LFKT_SPEC_AUTO_ACCEPT")
    try:
        # module-global lookup so tests can monkeypatch the measurement
        rtt_s = measure_dispatch_rtt_s()
        bpt = decode_bytes_per_token(params)
        t_tok_s = bpt / (hbm_gbps * 1e9)
        breakeven = rtt_s / t_tok_s
        mode = "lookup" if breakeven < accept else "off"
        return mode, {
            "rtt_ms": round(rtt_s * 1e3, 3),
            "bytes_per_token": int(bpt),
            "t_tok_ms": round(t_tok_s * 1e3, 3),
            "breakeven_acceptance": round(breakeven, 3),
            "assumed_acceptance": accept,
            "resolved": mode,
        }
    except Exception as e:  # noqa: BLE001 — serve without speculation
        return "off", {"resolved": "off", "error": str(e)[:200]}
