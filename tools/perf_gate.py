"""Perf regression sentinel: refuse regressed bench artifacts.

``docs/bench/`` holds ~70 hand-banked evidence artifacts whose headline
numbers were, until now, compared by eyeball against whatever the last
session remembered.  This tool makes the comparison mechanical:

  python tools/perf_gate.py fresh.json [more.json ...]

Each fresh JSON line (single object or JSON-lines) is compared against
the baseline artifact named for its metric family in the "Perf gate
baselines" table of ``docs/bench/MANIFEST.md``, with per-metric noise
tolerances: higher-is-better rates may drop at most ``--rate-tol``
(default 5%), lower-is-better latencies may grow at most
``--latency-tol`` (default 10%).  Exit codes:

  0 — every comparable metric within tolerance (or nothing comparable:
      a fresh tag/config with no matching baseline is SKIPPED, loudly);
  1 — at least one regression;
  2 — the comparison itself is invalid (missing baseline file, device
      mismatch, knob-fingerprint drift under --strict-knobs, bad args).

Comparability guards: metrics compare only on an exact metric-string
match (same family AND same ``[tags]`` — a q5km run never gates against
the q4km baseline), a ``device`` mismatch refuses the comparison, and
when both sides carry a provenance stamp (utils/provenance.py) a
knob-fingerprint mismatch is reported (fatal with ``--strict-knobs``).

Run it on every fresh artifact BEFORE banking; smoke-tested in tier-1 against a planted regression
(tests/test_bench_entrypoints.py).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "docs", "bench")
MANIFEST = os.path.join(BENCH, "MANIFEST.md")

#: baseline-table rows: | `metric family` | `artifact.json` |
_BASELINE_ROW = re.compile(
    r"^\|\s*`([\w.\-]+)`\s*\|\s*`([\w.\-]+\.json)`\s*\|", re.M)

#: extra per-metric comparisons beyond the headline "value":
#: key -> "higher" (rate: more is better) | "lower" (latency-ish)
EXTRA_METRICS = {
    "ttft_ms_p50": "lower",
    "ttft_ms_p95_server": "lower",
    "latency_ms_p50": "lower",
    "latency_ms_p95": "lower",
    "cold_ttft_ms_p50": "lower",
    "first_request_s": "lower",
    "tokens_per_sec": "higher",
    "prefix_hit_ratio": "higher",
}
#: nested paths (dotted) with directions
EXTRA_NESTED = {
    "concurrent.agg_tok_s": "higher",
    "concurrent.req_per_sec": "higher",
    "concurrent.latency_ms_p95": "lower",
}


def load_baseline_table(manifest_path: str = MANIFEST) -> dict[str, str]:
    """metric family -> baseline artifact name, from the MANIFEST's
    'Perf gate baselines' section."""
    text = open(manifest_path, encoding="utf-8").read()
    if "Perf gate baselines" not in text:
        return {}
    section = text.split("Perf gate baselines", 1)[1]
    return {fam: art for fam, art in _BASELINE_ROW.findall(section)}


def load_records(path: str) -> list[dict]:
    """Bench JSON records from a file: one object, a list, or JSON-lines."""
    text = open(path, encoding="utf-8").read().strip()
    try:
        doc = json.loads(text)
        return doc if isinstance(doc, list) else [doc]
    except ValueError:
        out = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            out.append(json.loads(line))
        return out


def metric_family(metric: str) -> str:
    return metric.split("[", 1)[0]


def _nested(d: dict, path: str):
    cur = d
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _direction_for_unit(unit: str) -> str:
    u = (unit or "").lower()
    if "tokens/sec" in u or "req/s" in u:
        return "higher"
    return "lower"           # ms / seconds / anything latency-shaped


class Gate:
    def __init__(self, rate_tol: float, latency_tol: float,
                 strict_knobs: bool):
        self.rate_tol = rate_tol
        self.latency_tol = latency_tol
        self.strict_knobs = strict_knobs
        self.lines: list[str] = []
        self.regressions = 0
        self.errors = 0
        self.compared = 0
        self.skipped = 0

    def say(self, line: str) -> None:
        self.lines.append(line)
        print(line)

    def _check(self, label: str, direction: str, fresh: float,
               base: float) -> None:
        tol = self.rate_tol if direction == "higher" else self.latency_tol
        if direction == "higher":
            bound = base * (1.0 - tol)
            ok = fresh >= bound
            rel = (fresh - base) / base if base else 0.0
        else:
            bound = base * (1.0 + tol)
            ok = fresh <= bound
            rel = (fresh - base) / base if base else 0.0
        self.compared += 1
        tag = "ok" if ok else "REGRESSION"
        self.say(f"  {tag}: {label} fresh={fresh:g} baseline={base:g} "
                 f"({rel:+.1%}, {direction}-is-better, tol {tol:.0%})")
        if not ok:
            self.regressions += 1

    def compare(self, fresh: dict, base: dict, base_name: str) -> None:
        metric = fresh.get("metric", "?")
        self.say(f"{metric}  vs  {base_name}")
        if base.get("error"):
            self.say("  REGRESSION: baseline carries an error field "
                     "(failed run must not be banked)")
            self.regressions += 1
            return
        dev_f, dev_b = fresh.get("device"), base.get("device")
        if dev_f and dev_b and dev_f != dev_b:
            self.say(f"  ERROR: device mismatch ({dev_f!r} vs {dev_b!r}) — "
                     "not comparable")
            self.errors += 1
            return
        pf, pb = fresh.get("provenance"), base.get("provenance")
        if isinstance(pf, dict) and isinstance(pb, dict) \
                and pf.get("knob_hash") != pb.get("knob_hash"):
            msg = ("knob fingerprint drift "
                   f"({pf.get('knob_hash')} vs {pb.get('knob_hash')}) — "
                   "the runs measured different configurations")
            if self.strict_knobs:
                self.say(f"  ERROR: {msg}")
                self.errors += 1
                return
            self.say(f"  warn: {msg}")
        if isinstance(fresh.get("value"), (int, float)) \
                and isinstance(base.get("value"), (int, float)):
            self._check("value", _direction_for_unit(fresh.get("unit", "")),
                        float(fresh["value"]), float(base["value"]))
        for key, direction in EXTRA_METRICS.items():
            f, b = fresh.get(key), base.get(key)
            if isinstance(f, (int, float)) and isinstance(b, (int, float)):
                self._check(key, direction, float(f), float(b))
        for path, direction in EXTRA_NESTED.items():
            f, b = _nested(fresh, path), _nested(base, path)
            if isinstance(f, (int, float)) and isinstance(b, (int, float)):
                self._check(path, direction, float(f), float(b))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("fresh", nargs="+", help="fresh bench JSON artifact(s)")
    ap.add_argument("--baseline", default=None,
                    help="baseline artifact path (overrides the MANIFEST "
                         "table for every fresh record)")
    ap.add_argument("--manifest", default=MANIFEST)
    ap.add_argument("--bench-dir", default=BENCH)
    ap.add_argument("--rate-tol", type=float, default=0.05,
                    help="allowed drop for higher-is-better metrics")
    ap.add_argument("--latency-tol", type=float, default=0.10,
                    help="allowed growth for lower-is-better metrics")
    ap.add_argument("--strict-knobs", action="store_true",
                    help="fail on LFKT_* fingerprint drift instead of "
                         "warning")
    args = ap.parse_args(argv)

    gate = Gate(args.rate_tol, args.latency_tol, args.strict_knobs)
    table = load_baseline_table(args.manifest)
    if not table and args.baseline is None:
        print("ERROR: no 'Perf gate baselines' table in "
              f"{args.manifest} and no --baseline given", file=sys.stderr)
        return 2

    base_cache: dict[str, list[dict]] = {}

    def baseline_records(path: str) -> list[dict]:
        if path not in base_cache:
            base_cache[path] = load_records(path)
        return base_cache[path]

    for fresh_path in args.fresh:
        try:
            records = load_records(fresh_path)
        except (OSError, ValueError) as e:
            gate.say(f"ERROR: cannot read {fresh_path}: {e}")
            gate.errors += 1
            continue
        for rec in records:
            metric = rec.get("metric")
            if not isinstance(metric, str):
                continue                      # non-metric rows ride along
            if rec.get("error"):
                # checked BEFORE baseline resolution: a failed run must
                # not slip through the no-baseline-for-family skip path
                gate.say(f"{metric}: REGRESSION — artifact carries an "
                         "error field (failed run must not be banked)")
                gate.regressions += 1
                continue
            if args.baseline is not None:
                bpath, bname = args.baseline, os.path.basename(args.baseline)
            else:
                fam = metric_family(metric)
                if fam not in table:
                    gate.say(f"{metric}: no baseline for family {fam!r} "
                             "in the MANIFEST table — skipped")
                    gate.skipped += 1
                    continue
                bname = table[fam]
                bpath = os.path.join(args.bench_dir, bname)
            if not os.path.exists(bpath):
                gate.say(f"ERROR: baseline {bpath} does not exist")
                gate.errors += 1
                continue
            candidates = [b for b in baseline_records(bpath)
                          if b.get("metric") == metric]
            if not candidates:
                tags = sorted({b.get("metric") for b in
                               baseline_records(bpath)
                               if isinstance(b.get("metric"), str)})
                gate.say(f"{metric}: baseline {bname} has no record with "
                         f"this exact metric string (has {tags}) — skipped")
                gate.skipped += 1
                continue
            gate.compare(rec, candidates[0], bname)

    verdict = ("FAIL" if gate.regressions or gate.errors else "OK")
    print(f"{verdict}: {gate.compared} comparison(s), "
          f"{gate.regressions} regression(s), {gate.errors} error(s), "
          f"{gate.skipped} skipped")
    if gate.errors:
        return 2
    return 1 if gate.regressions else 0


if __name__ == "__main__":
    sys.exit(main())
