#!/usr/bin/env python
"""obs_doctor: automated bottleneck diagnosis over a metrics snapshot
(lightgbm_tpu/obs/diagnose.py, docs/OBSERVABILITY.md verdict catalogue):
a registry snapshot (tools/obs_dump.py writes one), or the live registry.

Joins measured signals (iteration seconds, straggler skew, brownout
counts) with planner-predicted ones (per-tier ICI/DCN payload bytes,
link models) and prints RANKED verdicts — "DCN-bound", "straggler slice
k", "contention" (co-resident train vs serve fighting over the same
devices; evidence carries the residency ledger's lease table + brownout
throttle/pause counts) — each with the evidence behind it; the rules
whose inputs no program records are listed in docs/OBSERVABILITY.md.
The LAST stdout line is one JSON summary.

Usage:
    python tools/obs_doctor.py \
        [--metrics obs_metrics.json]     # registry snapshot (obs_dump)
        [--json-only]                    # machine consumers
Exit codes: 0 = diagnosed (whatever the verdict), 2 = input unreadable.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_metrics_snapshot(path):
    """A dumped registry snapshot re-wrapped so ``collect_signals`` can
    read it like a live registry (duck-typed: only ``to_dict`` is
    consulted)."""
    if not path or not os.path.exists(path):
        return None
    with open(path) as fh:
        snap = json.load(fh)

    class _Snap:
        def to_dict(self):
            return snap

    return _Snap()


def run_doctor(registry=None):
    """collect -> diagnose -> summary (falls back to the live process
    registry)."""
    from lightgbm_tpu.obs.diagnose import run_doctor as _run
    return _run(registry=registry)


def format_human(report):
    lines = [f"obs_doctor: top verdict = {report['top_verdict']}", ""]
    for i, v in enumerate(report["verdicts"], 1):
        lines.append(f"{i}. [{v['name']}] score={v['score']:.2f}")
        lines.append(f"   {v['summary']}")
        if v["evidence"]:
            ev = ", ".join(f"{k}={v['evidence'][k]}"
                           for k in sorted(v["evidence"]))
            lines.append(f"   evidence: {ev}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--metrics", default="obs_metrics.json",
                    help="tools/obs_dump.py writes one into its --out-dir")
    ap.add_argument("--json-only", action="store_true")
    args = ap.parse_args()
    try:
        registry = load_metrics_snapshot(args.metrics)
    except (OSError, ValueError) as e:
        print(json.dumps({"error": f"unreadable input: {e}"}))
        return 2
    report = run_doctor(registry=registry)
    if not args.json_only:
        print(format_human(report))
    print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
