#!/usr/bin/env python
"""Single CI gate: lfkt-lint + the evidence-ledger check, one exit code.

The gate used to be two manual commands (the lint module and
tools/check_manifest.py); this entry point runs both, streams
their output, and aggregates exit codes — nonzero if ANY check fails, so
one command gates a commit:

  python tools/ci_gate.py            # human output, exit != 0 on failure
  python tools/ci_gate.py --json     # {"ok": bool, "checks": [...]}
  python tools/ci_gate.py --skip chaos-drill   # triage loop: skip a check
                                     # (still listed, marked skipped)

Each check runs in a subprocess (the same commands a human would run, so
this wrapper can never drift from what it claims to gate) with a bounded
timeout.  Add future repo-wide gates here rather than growing the
checklist.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (name, argv) — every gate a commit must pass, in order
CHECKS: list[tuple[str, list[str]]] = [
    ("lfkt-lint", [sys.executable, "-m", "llama_fastapi_k8s_gpu_tpu.lint"]),
    # the interprocedural concurrency families (ISSUE 15) ride a baseline
    # ratchet: any LOCK005/LOCK006/ASY001/ASY002 finding NOT grandfathered
    # in lint_baseline_concurrency.json fails here, and grandfathered ones
    # may only shrink (tools/lint_report.py reports the shrink so the
    # baseline gets trimmed).  Today the baseline is EMPTY — every
    # surviving in-tree audit is reason-annotated instead — so this gate
    # means "no new unaudited deadlock/stall hazard lands, ever"
    ("lint-concurrency", [sys.executable,
                          os.path.join(ROOT, "tools", "lint_report.py"),
                          "--baseline",
                          os.path.join(ROOT, "lint_baseline_concurrency.json"),
                          "--rules", "LOCK005", "LOCK006",
                          "ASY001", "ASY002"]),
    # the trust-boundary families (lfkt-lint v4): TAINT taint flows and
    # the WIRE wire-surface registry cross-checks, ratcheted against an
    # EMPTY baseline — every in-tree flow is either sanitized
    # (obs.logctx.sanitize_text), guard-declassified, or carries a
    # reason-annotated `sanitizes[...]` audit, so this gate means "no
    # new unaudited trust-boundary crossing lands, ever"
    ("lint-taint", [sys.executable,
                    os.path.join(ROOT, "tools", "lint_report.py"),
                    "--baseline",
                    os.path.join(ROOT, "lint_baseline_taint.json"),
                    "--rules", "TAINT001", "TAINT002", "TAINT003",
                    "WIRE001", "WIRE002", "WIRE003"]),
    ("check-manifest", [sys.executable,
                        os.path.join(ROOT, "tools", "check_manifest.py")]),
    # any incident bundle present (in $LFKT_INCIDENT_DIR) must validate
    # against the versioned flight-recorder schema; no dir = trivially OK
    ("incident-schema", [sys.executable,
                         os.path.join(ROOT, "tools", "incident_report.py"),
                         "--validate"]),
    # the disagg page-wire format (serving/disagg/wire.py) is pinned
    # against a committed golden header: a drive-by edit that would
    # strand a mixed-version prefill/decode fleet fails here until
    # WIRE_SCHEMA is bumped and the golden regenerated deliberately
    ("disagg-wire-schema", [sys.executable, "-m",
                            "llama_fastapi_k8s_gpu_tpu.serving.disagg.wire",
                            "--check-golden"]),
    # fleet-tier byte-exactness (ISSUE 14): greedy output proxied through
    # the prefix-affinity router must be BYTE-identical to direct-to-
    # replica serving — the router relays raw backend bytes, and this
    # gate keeps any future header/body rewriting honest.
    ("fleet-route-parity", ["env", "JAX_PLATFORMS=cpu", sys.executable,
                            "-m", "pytest", "-q", "-p", "no:cacheprovider",
                            os.path.join(ROOT, "tests", "test_fleet.py"),
                            "-k", "route_parity"]),
    # KV-survivability smoke (ISSUE 17): the no-engine subset of
    # tests/test_chaos.py — pull round-trip bitwise over the real wire,
    # every migrate fault point degrading with attribution, graceful
    # drain as a commanded pull, router stamp/strip security, and the
    # spill-budget 503.  The full SIGKILL/drain drills (real replica
    # processes) stay in tier-1; tools/chaos_drill.py is the operator
    # CLI twin.
    ("chaos-drill", ["env", "JAX_PLATFORMS=cpu", sys.executable,
                     "-m", "pytest", "-q", "-p", "no:cacheprovider",
                     os.path.join(ROOT, "tests", "test_chaos.py"),
                     "-k", "smoke"]),
    # cross-process trace continuity (ISSUE 19): one traced request
    # through the real router + replica yields ONE stitched span tree
    # spanning both processes with zero orphan fragments — the guard
    # keeping every future hop (proxy header, wire REQ field) honest
    # about propagating trace context instead of silently dropping it.
    ("fleet-trace-continuity", ["env", "JAX_PLATFORMS=cpu", sys.executable,
                                "-m", "pytest", "-q", "-p",
                                "no:cacheprovider",
                                os.path.join(ROOT, "tests",
                                             "test_fleet.py"),
                                "-k", "trace_continuity"]),
]


def run_checks(timeout: float = 300.0,
               skip: frozenset[str] = frozenset()) -> list[dict]:
    results = []
    for name, argv in CHECKS:
        if name in skip:
            # still listed (the aggregate shape is part of the contract)
            # but not executed — for triage loops and for callers that
            # already ran a check's substance another way (tier-1 runs
            # the pytest-subset checks first-class in the same session)
            results.append({"name": name, "exit": 0, "ok": True,
                            "skipped": True, "output": "skipped"})
            continue
        try:
            proc = subprocess.run(argv, cwd=ROOT, capture_output=True,
                                  text=True, timeout=timeout)
            results.append({
                "name": name,
                "exit": proc.returncode,
                "ok": proc.returncode == 0,
                "output": (proc.stdout + proc.stderr).strip(),
            })
        except subprocess.TimeoutExpired:
            results.append({"name": name, "exit": -1, "ok": False,
                            "output": f"timed out after {timeout:.0f}s"})
    return results


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="machine-readable aggregate result")
    ap.add_argument("--timeout", type=float, default=300.0,
                    help="per-check timeout in seconds")
    ap.add_argument("--skip", default="",
                    help="comma-separated check names to skip (they "
                         "still appear in the output, marked skipped)")
    args = ap.parse_args()

    skip = frozenset(n for n in args.skip.split(",") if n)
    known = {name for name, _ in CHECKS}
    if not skip <= known:
        ap.error(f"unknown check(s): {sorted(skip - known)} "
                 f"(known: {sorted(known)})")
    results = run_checks(timeout=args.timeout, skip=skip)
    ok = all(r["ok"] for r in results)
    if args.json:
        print(json.dumps({"ok": ok, "checks": results}, indent=1))
    else:
        for r in results:
            mark = "SKIP" if r.get("skipped") else \
                ("OK  " if r["ok"] else "FAIL")
            print(f"[{mark}] {r['name']} (exit {r['exit']})")
            if not r["ok"] and r["output"]:
                print("  " + r["output"].replace("\n", "\n  "))
        print(f"ci_gate: {'OK' if ok else 'FAIL'} "
              f"({sum(r['ok'] for r in results)}/{len(results)} checks)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
