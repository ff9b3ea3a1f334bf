#!/usr/bin/env python3
"""Check a benchmark manifest against the driver's rules for names, units,
sources and references, before any chip time is spent.

    python3 benchmark/check_manifest.py [BENCHMARK.json]

Exits 0 and prints ``ok`` when every rule holds, else lists what breaks.
"""
import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|head_size|features")


def line(text, what, errors):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        errors.append(f"{what}: must be 1 to 200 characters on one line")


def check(manifest, allow_extra=()):
    errors = []
    extra = set(manifest) - KEYS - set(allow_extra)
    if extra or KEYS - set(manifest):
        errors.append(f"top-level keys: extra {sorted(extra)}, "
                      f"missing {sorted(KEYS - set(manifest))}")
        return errors
    if not 1 <= len(manifest["command"]) <= 32:
        errors.append("command: 1 to 32 strings")
    for word in manifest["command"]:
        line(word, f"command word {word!r}", errors)
        if word.startswith("/") or ".." in Path(word).parts:
            errors.append(f"command word {word!r} leaves the repo")
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in Path(p).parts:
            errors.append(f"path {p!r}")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")

    def under_paths(f):
        return any(Path(f).parts[:len(Path(p).parts)] == Path(p).parts
                   for p in paths)

    def names_unique(entries, what):
        seen = set()
        for e in entries:
            n = e.get("name", "")
            if not NAME.match(n):
                errors.append(f"{what} name {n!r} is not an identifier")
            if n in seen:
                errors.append(f"{what} name {n!r} appears twice")
            seen.add(n)
        return seen

    configs = names_unique(manifest["configs"], "config")
    if not 1 <= len(manifest["configs"]) <= 24:
        errors.append("configs: 1 to 24")
    files = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errors.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        line(c["source"], f"config {c['name']} source", errors)
        line(c["why"], f"config {c['name']} why", errors)
        f = c["file"]
        if not PATH.match(f) or not under_paths(f):
            errors.append(f"config {c['name']}: file {f!r} not under paths")
        elif not (REPO / f).is_file():
            errors.append(f"config {c['name']}: file {f!r} does not exist")
        if f in files:
            errors.append(f"config file {f!r} used twice")
        files.add(f)
        if len(c["reduced"]) > 16:
            errors.append(f"config {c['name']}: reduced has over 16 keys")
        for k in c["reduced"]:
            if not NAME.match(k):
                errors.append(f"config {c['name']}: reduced key {k!r}")
            if WIDTH.search(k):
                errors.append(f"config {c['name']}: reduced names a width "
                              f"({k!r})")
    cells = names_unique(manifest["workloads"], "workload")
    if not 1 <= len(manifest["workloads"]) <= 24:
        errors.append("workloads: 1 to 24")
    pairs = set()
    four = 0
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errors.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if w["config"] not in configs:
            errors.append(f"workload {w['name']}: no config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            errors.append(f"workload {w['name']}: traffic {w['traffic']!r}")
        elif not any((REPO / p / "traffic" / f"{w['traffic']}.json").is_file()
                     for p in paths):
            errors.append(f"workload {w['name']}: no traffic file "
                          f"traffic/{w['traffic']}.json under paths")
        if w["chips"] not in (1, 4):
            errors.append(f"workload {w['name']}: chips must be 1 or 4")
        four += w["chips"] == 4
        line(w["why"], f"workload {w['name']} why", errors)
        if (w["config"], w["traffic"]) in pairs:
            errors.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    if four > max(1, len(manifest["workloads"]) // 4):
        errors.append(f"{four} four-chip cells: over a quarter of the cells")
    for c in manifest["configs"]:
        if not any(w.get("config") == c["name"]
                   for w in manifest["workloads"]):
            errors.append(f"config {c['name']} is used by no cell")

    metrics = names_unique(manifest["end_to_end"] + manifest["per_layer"],
                           "metric")
    if "setup_s" not in metrics:
        errors.append("end_to_end lacks setup_s")
    reports = {}            # end-to-end metric -> cells that report it
    for m in manifest["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        if not {"name", "unit", "better", "bound", "source"} <= set(m) \
                or set(m) - allowed:
            errors.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            errors.append(f"end_to_end {m['name']}: source {m['source']!r}")
        if not (isinstance(m["bound"], (int, float))
                and 0.01 <= m["bound"] <= 0.1):
            errors.append(f"end_to_end {m['name']}: bound {m['bound']!r}")
        reports[m["name"]] = set(m.get("workloads", cells))
    for cell in cells:
        got = [n for n, ws in reports.items() if cell in ws]
        if "setup_s" not in got or len(got) < 2:
            errors.append(f"cell {cell}: reports {got}; needs setup_s and "
                          "one more end-to-end metric")
    layered = set()
    for m in manifest["per_layer"]:
        need = {"name", "unit", "better", "source", "layer", "moves"}
        if not need <= set(m) or set(m) - need - {"workloads"}:
            errors.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        if "workloads" not in m:
            errors.append(f"per_layer {m['name']}: no workloads list")
        if not NAME.match(m["layer"]):
            errors.append(f"per_layer {m['name']}: layer {m['layer']!r} "
                          "is not an identifier")
        if m["source"] not in SOURCES:
            errors.append(f"per_layer {m['name']}: source {m['source']!r}")
        if m["moves"] not in reports:
            errors.append(f"per_layer {m['name']}: moves {m['moves']!r}, "
                          "which is no end-to-end metric")
            continue
        for cell in m.get("workloads", []):
            if cell not in cells:
                errors.append(f"per_layer {m['name']}: no cell {cell!r}")
            elif cell not in reports[m["moves"]]:
                errors.append(f"per_layer {m['name']}: cell {cell} does "
                              f"not report {m['moves']}")
            layered.add(cell)
        if not any((REPO / p / "metrics" / f"{m['name']}.py").is_file()
                   for p in paths):
            errors.append(f"per_layer {m['name']}: no reader "
                          f"metrics/{m['name']}.py under paths")
        if (m["name"].endswith("_roofline") or "mfu" in m["name"]) \
                and m["unit"] != "%":
            errors.append(f"per_layer {m['name']}: unit must be %")
    for cell in cells - layered:
        errors.append(f"cell {cell}: reports no per-layer metric")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(str(m.get("unit", ""))):
            errors.append(f"metric {m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            errors.append(f"metric {m.get('name')}: better")
    if len(json.dumps(manifest)) > 64 * 1024:
        errors.append("the manifest is over 64 KiB")
    return errors + check_files(manifest)


def check_files(manifest, configs=None, traffics=None):
    """Every cell's traffic ``kind`` is a file ``kinds/<kind>.py`` under the
    manifest's paths, and the objective its configuration's parameters name
    a file ``objectives/<objective>.py`` there: what ``run.py`` looks up by
    name.  ``configs`` and ``traffics`` ({name: dict}) stand in for the
    files in tests."""
    errors = []
    paths = manifest["paths"]

    def under(rel):
        return any((REPO / p / rel).is_file() for p in paths)

    def load(rel):
        for p in paths:
            if (REPO / p / rel).is_file():
                return json.loads((REPO / p / rel).read_text())
        return None

    files = {c["name"]: c["file"] for c in manifest.get("configs", [])}
    for w in manifest["workloads"]:
        traffic = (traffics or {}).get(w["traffic"]) \
            or load(f"traffic/{w['traffic']}.json")
        if traffic is None:
            continue            # reported by check()
        kind = traffic.get("kind")
        if not (isinstance(kind, str) and NAME.match(kind)
                and under(f"kinds/{kind}.py")):
            errors.append(f"workload {w['name']}: no kind file "
                          f"kinds/{kind}.py under paths")
        config = (configs or {}).get(w["config"])
        if config is None and (REPO / files.get(w["config"], "")).is_file():
            config = json.loads((REPO / files[w["config"]]).read_text())
        if config is None:
            continue            # reported by check()
        objective = dict(config.get("params", {}),
                         **traffic.get("params", {})).get("objective")
        if not (isinstance(objective, str) and NAME.match(objective)
                and under(f"objectives/{objective}.py")):
            errors.append(f"workload {w['name']}: no reference objective "
                          f"objectives/{objective}.py under paths")
    return errors


def main(argv):
    rel = argv[1] if len(argv) > 1 else "BENCHMARK.json"
    manifest = json.loads((REPO / rel).read_text())
    errors = check(manifest, allow_extra=("rehearsal",))
    for e in errors:
        print(e)
    print("ok" if not errors else f"{len(errors)} faults")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
