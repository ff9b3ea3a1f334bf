"""Automated bottleneck diagnosis: join measured and planner-predicted
signals into RANKED verdicts with the evidence behind each.

ROADMAP item 3 says "stop trusting analytic models alone"; PR 6-10 left
the raw material everywhere — measured MFU tables, per-tier payload
accounting (``ops/planner.py`` ``plan_collectives``), compile-cache
warmth, streaming ``overlap_efficiency``, straggler skew
(obs/aggregate.py).  This module is the judgment layer: one pure
function from a flat signal dict to an ordered list of verdicts, so the
same rules serve the ``obs_doctor`` CLI and the tests that inject each
bottleneck.

Verdict catalogue (docs/OBSERVABILITY.md):

- ``dcn-bound``        — the slow-tier wire time is a material fraction
                         of the iteration under the planner's link model;
- ``compile-bound``    — XLA compilation dominates wall-clock (cold
                         cache the usual suspect);
- ``input-bound``      — streaming is active but the block pump fails to
                         hide device_put behind compute;
- ``straggler``        — one slice's iterations run materially slower
                         than its peers' (names the slice);
- ``contention``       — co-resident train and serve are fighting over
                         the same devices: training has been throttled /
                         paused by brownout signals while serving p99
                         climbed (evidence: the residency-ledger lease
                         table plus the throttle/pause event counts —
                         coresident/scheduler.py);
- ``kernel-underutilized`` — none of the above, yet measured MFU says
                         the chip is mostly idle (the per-level work is
                         just too small: batch models or fuse more);
- ``healthy``          — nothing fired.

Each verdict carries ``score`` in [0, 1] (comparable across verdicts:
the ranking IS the diagnosis), a one-line human summary, and the raw
numbers as ``evidence``.  ``collect_signals`` assembles the dict from
the live registry or a snapshot of it; pure stdlib.  The rules that read
``compile_seconds`` / ``train_seconds``, ``overlap_efficiency``,
``bin_seconds`` and ``mfu_measured_best`` have no input there: a journal
of banked stages fed them, and no program writes one (ROADMAP.md C7).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

# tunable rule thresholds, named so tests and docs can cite them
DCN_FRACTION_MATERIAL = 0.25      # DCN seconds / iteration seconds
COMPILE_FRACTION_MATERIAL = 0.4   # compile / (compile + train) wall
OVERLAP_EFFICIENCY_FLOOR = 1.05   # pump gain below this = no overlap
STRAGGLER_SKEW_MATERIAL = 1.15    # slowest / fastest slice
MFU_HEALTHY_FLOOR = 0.01          # below this the chip is mostly idle
CONTENTION_EVENTS_MATERIAL = 1    # >= this many throttles+pauses fires
BIN_FRACTION_MATERIAL = 0.5       # bin_seconds / train_seconds


@dataclass
class Verdict:
    name: str
    score: float                  # 0..1, comparable across verdicts
    summary: str
    evidence: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "score": round(self.score, 4),
                "summary": self.summary, "evidence": self.evidence}


def _num(v, default=0.0):
    try:
        if isinstance(v, bool):
            return float(v)
        return float(v)
    except (TypeError, ValueError):
        return default


def collect_signals(registry=None) -> dict:
    """Assemble the diagnoser's flat signal dict from the live process
    registry (or a snapshot of one: only ``to_dict`` is read); absent
    signals simply don't fire their rules."""
    sig: dict = {}
    if registry is None:
        from .metrics import global_registry as registry
    d = registry.to_dict()
    g = d.get("gauges", {})
    for k in ("train_ici_payload_bytes", "train_dcn_payload_bytes",
              "train_num_slices", "train_hier_reduce",
              "train_trees_per_sec", "train_iter_seconds",
              "compile_cache_warm", "pod_straggler_skew",
              "pod_straggler_slice", "pod_ici_payload_bytes",
              "pod_dcn_payload_bytes", "pod_mfu", "mfu_measured_best",
              "host_rss_peak_bytes", "trace_events_dropped"):
        if k in g:
            sig[k] = g[k]
    c = d.get("counters", {})
    sig["slo_breach_total"] = sum(
        v for k, v in c.items() if k.startswith("slo_breach_total"))
    sig["stream_blocks_total"] = c.get("stream_blocks_total", 0)
    # co-residency contention signals: brownout event counters, the
    # residency ledger's lease accounting, and the worst watched p99
    sig["coresident_throttle_total"] = sum(
        v for k, v in c.items()
        if k.startswith("coresident_throttle_total"))
    sig["coresident_pause_total"] = sum(
        v for k, v in c.items() if k.startswith("coresident_pause_total"))
    for k, v in g.items():
        if k.startswith("ledger_leased_bytes"):
            sig["ledger_leased_bytes"] = sig.get("ledger_leased_bytes",
                                                 0.0) + _num(v)
    if "ledger_available_bytes" in g:
        sig["ledger_available_bytes"] = g["ledger_available_bytes"]
    p99s = [_num(v) for k, v in g.items()
            if k.startswith("watchdog_p99_")]
    if p99s:
        sig["watchdog_p99_ms_max"] = max(p99s)
    try:
        from ..ops.planner import active_ledger
        lg = active_ledger()
        if lg is not None:
            sig["ledger_lease_table"] = lg.table()
    except Exception:  # noqa: BLE001 — forensics only
        pass
    # planner link speeds (the model the DCN rule prices bytes with)
    try:
        from ..ops.planner import (DEFAULT_DCN_GBPS, DEFAULT_ICI_GBPS,
                                   _env_gbps)
        sig.setdefault("ici_gbps",
                       _env_gbps("LGBM_TPU_ICI_GBPS", DEFAULT_ICI_GBPS))
        sig.setdefault("dcn_gbps",
                       _env_gbps("LGBM_TPU_DCN_GBPS", DEFAULT_DCN_GBPS))
    except Exception:  # noqa: BLE001
        sig.setdefault("ici_gbps", 100.0)
        sig.setdefault("dcn_gbps", 6.25)
    # the ingest election's last outcome (ops/ingest.py): the input-bound
    # verdict names whether binning ran on the kernel or fell back + why
    try:
        from ..ops.ingest import ingest_last
        il = ingest_last()
        if il:
            sig["ingest_last"] = il
    except Exception:  # noqa: BLE001
        pass
    return sig


def diagnose(signals: dict) -> List[Verdict]:
    """Rank every verdict whose rule fires; ``healthy`` alone when none
    do.  Pure function of the signal dict — the whole test surface."""
    out: List[Verdict] = []
    s = signals

    # --- dcn-bound: price the DCN payload with the per-tier link model
    dcn_bytes = _num(s.get("train_dcn_payload_bytes"))
    num_slices = _num(s.get("train_num_slices"), 1.0)
    iter_s = _num(s.get("train_iter_seconds")) or \
        _num(s.get("sec_per_tree"))
    if dcn_bytes > 0 and num_slices > 1 and iter_s > 0:
        dcn_s = dcn_bytes / (_num(s.get("dcn_gbps"), 6.25) * 1e9)
        frac = dcn_s / iter_s
        if frac >= DCN_FRACTION_MATERIAL:
            out.append(Verdict(
                "dcn-bound", min(frac, 1.0),
                f"DCN wire time ~{frac:.0%} of each iteration "
                f"({dcn_bytes / 1e6:.1f} MB/sync at "
                f"{_num(s.get('dcn_gbps'), 6.25):g} GB/s across "
                f"{int(num_slices)} slices) — elect voting-parallel or "
                "shrink the cross-slice payload",
                {"dcn_payload_bytes": dcn_bytes,
                 "dcn_gbps": _num(s.get("dcn_gbps"), 6.25),
                 "dcn_seconds_per_sync": dcn_s,
                 "iter_seconds": iter_s, "fraction": round(frac, 4),
                 "num_slices": int(num_slices),
                 "hier_reduce": bool(_num(s.get("train_hier_reduce")))}))

    # --- compile-bound: one-time XLA compile vs the steady-state train
    comp = _num(s.get("compile_seconds"))
    train = _num(s.get("train_seconds"))
    if comp > 0 and (comp + train) > 0:
        frac = comp / (comp + train)
        warm = bool(_num(s.get("compile_cache_warm")))
        if frac >= COMPILE_FRACTION_MATERIAL:
            out.append(Verdict(
                "compile-bound", min(frac, 1.0),
                f"XLA compilation is {frac:.0%} of wall-clock "
                f"({comp:.1f}s compile vs {train:.1f}s train); compile "
                f"cache {'WARM — shapes are churning' if warm else 'COLD'}"
                " — keep the compile cache dir across runs / stop varying shapes",
                {"compile_seconds": comp, "train_seconds": train,
                 "fraction": round(frac, 4),
                 "compile_cache_warm": warm}))

    # --- input/stream-bound: the pump isn't hiding host->device puts
    streaming = _num(s.get("stream_blocks_total")) > 0 or \
        "overlap_efficiency" in s
    if streaming and "overlap_efficiency" in s:
        eff = _num(s.get("overlap_efficiency"), 1.0)
        if eff < OVERLAP_EFFICIENCY_FLOOR:
            score = min(max((OVERLAP_EFFICIENCY_FLOOR - eff) * 4 + 0.4,
                            0.0), 1.0)
            out.append(Verdict(
                "input-bound", score,
                f"block pump overlap efficiency {eff:.2f} (< "
                f"{OVERLAP_EFFICIENCY_FLOOR}): device compute is waiting "
                "on host reads/puts — deepen prefetch, grow blocks, or "
                "speed the spill store",
                {"overlap_efficiency": eff,
                 "stream_blocks_total":
                     int(_num(s.get("stream_blocks_total"))),
                 "floor": OVERLAP_EFFICIENCY_FLOOR}))

    # --- input-bound (ingest flavor): binning dominates training wall
    # clock — the verdict names its cure: whether the device ingest
    # kernel (ops/ingest.py) was elected or fell back, and why
    bin_s = _num(s.get("bin_seconds"))
    train_s = _num(s.get("train_seconds"))
    if bin_s > 0 and train_s > 0:
        frac = bin_s / train_s
        if frac >= BIN_FRACTION_MATERIAL:
            il = s.get("ingest_last")
            ev = {"bin_seconds": bin_s, "train_seconds": train_s,
                  "fraction": round(frac, 4),
                  "threshold": BIN_FRACTION_MATERIAL}
            if _num(s.get("bin_rows_per_sec")):
                ev["bin_rows_per_sec"] = _num(s.get("bin_rows_per_sec"))
            cure = ("route construction through the device ingest kernel "
                    "(ops/ingest.py)")
            if isinstance(il, dict) and il:
                ev["ingest_path"] = il.get("path")
                if il.get("path") == "kernel":
                    ev["ingest_elected_by"] = il.get("elected_by")
                    cure = ("the ingest kernel DID run (elected_by="
                            f"{il.get('elected_by')}) and binning still "
                            "dominates: grow the chunk "
                            "(LGBM_TPU_INGEST_CHUNK) or check H2D "
                            "bandwidth (ingest.put spans)")
                else:
                    ev["ingest_fallback_reason"] = il.get("reason")
                    cure = ("ingest fell back to host NumPy binning ("
                            f"{il.get('reason', 'no election ran')}) — "
                            "fix that, or pin LGBM_TPU_INGEST_KERNEL to "
                            "bisect the election")
            out.append(Verdict(
                "input-bound", min(0.3 + 0.4 * frac, 1.0),
                f"Dataset binning took {bin_s:.1f}s against {train_s:.1f}"
                f"s of training ({frac:.0%}): construction is the "
                f"bottleneck — {cure}",
                ev))

    # --- straggler: one slice materially slower than its peers
    skew = _num(s.get("pod_straggler_skew"), 1.0)
    if skew >= STRAGGLER_SKEW_MATERIAL:
        slice_k = int(_num(s.get("pod_straggler_slice")))
        out.append(Verdict(
            "straggler", min((skew - 1.0), 1.0),
            f"slice {slice_k} runs {skew:.2f}x slower than the fastest "
            "slice — check its hosts (thermal, neighbors, failing "
            "links); elastic shrink-rejoin can drop it",
            {"straggler_slice": slice_k, "straggler_skew": skew,
             "threshold": STRAGGLER_SKEW_MATERIAL}))

    # --- contention: co-resident planes fighting over the same devices
    thr = _num(s.get("coresident_throttle_total"))
    pauses = _num(s.get("coresident_pause_total"))
    if thr + pauses >= CONTENTION_EVENTS_MATERIAL:
        ev = {"coresident_throttle_total": int(thr),
              "coresident_pause_total": int(pauses)}
        for k in ("ledger_leased_bytes", "ledger_available_bytes",
                  "watchdog_p99_ms_max"):
            if k in s:
                ev[k] = s[k]
        table = s.get("ledger_lease_table")
        if isinstance(table, list):
            ev["ledger_lease_table"] = table
        # pauses weigh double: a pause means the brownout persisted past
        # throttling — deeper contention than a transient spike
        out.append(Verdict(
            "contention",
            min(0.4 + 0.05 * (thr + 2.0 * pauses), 0.9),
            f"co-resident training was throttled {int(thr)}x and paused "
            f"{int(pauses)}x by serving brownout signals — train and "
            "serve are contending for the same devices; shrink the "
            "training chunk cap / lease, move the refresh off-peak, or "
            "give serving its own devices",
            ev))

    # --- kernel-underutilized: nothing specific, chip still idle
    mfu = s.get("mfu_measured_best")
    if mfu is not None and _num(mfu) < MFU_HEALTHY_FLOOR and not out:
        mfu = _num(mfu)
        ev = {"mfu_measured_best": mfu, "floor": MFU_HEALTHY_FLOOR}
        out.append(Verdict(
            "kernel-underutilized",
            min(0.3 + (MFU_HEALTHY_FLOOR - mfu) / MFU_HEALTHY_FLOOR * 0.4,
                0.7),
            f"best measured kernel MFU {mfu:.5f} (< {MFU_HEALTHY_FLOOR})"
            " with no specific bottleneck: per-level work is too small "
            "for the MXU — batch boosters over a model axis or widen "
            "the fused frontier",
            ev))

    if not out:
        return [Verdict("healthy", 1.0,
                        "no rule fired: no dominant bottleneck in the "
                        "measured signals", {})]
    out.sort(key=lambda v: v.score, reverse=True)
    return out


def diagnosis_summary(verdicts: List[Verdict],
                      signals: Optional[dict] = None) -> dict:
    """JSON-ready report (the CLI last-line shape)."""
    out = {
        "top_verdict": verdicts[0].name if verdicts else "healthy",
        "verdicts": [v.to_dict() for v in verdicts],
    }
    if signals is not None:
        out["signals"] = {k: v for k, v in sorted(signals.items())
                          if isinstance(v, (int, float, str, bool))}
    return out


def run_doctor(registry=None) -> dict:
    """collect -> diagnose -> summarize in one call
    (tools/obs_doctor.py entry point)."""
    signals = collect_signals(registry=registry)
    return diagnosis_summary(diagnose(signals), signals)
