"""Find the files that belong to a cell by the names in the manifest.

Everything that belongs to one configuration, one traffic mix, one cell,
one data generator or one per-layer metric is a file of its own under one
of the manifest's ``paths``; the harness never lists them in code.  A
manifest's paths are searched in order, so a rehearsal manifest under
``benchmark/tests/data`` can shadow sizes with tiny twins and fall through
to the real readers.
"""
import importlib.util
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_manifest(rel="BENCHMARK.json"):
    path = REPO / rel
    if not path.is_file():
        raise FileNotFoundError(f"no manifest at {path}")
    return load_json(path)


def find(manifest, rel):
    """First ``<path>/<rel>`` that exists over the manifest's paths."""
    for root in manifest["paths"]:
        cand = REPO / root / rel
        if cand.is_file():
            return cand
    raise FileNotFoundError(
        f"{rel} is under none of the manifest's paths {manifest['paths']}")


def find_optional(manifest, rel):
    try:
        return find(manifest, rel)
    except FileNotFoundError:
        return None


def load_module(path):
    """Import one file by path (metric readers, generators, rooflines)."""
    path = Path(path)
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_files(manifest, workload):
    """(cell entry, config entry, config dict, traffic dict, cell dict)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    centry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(REPO / centry["file"])
    traffic = load_json(find(manifest, f"traffic/{cell['traffic']}.json"))
    cpath = find_optional(manifest, f"cells/{workload}.json")
    return cell, centry, config, traffic, (load_json(cpath) if cpath else {})


def apply_env(config):
    """Set the program's documented environment switches that the
    configuration's file states under ``env`` (part of the deployment, like
    its parameters), before the program is imported."""
    for key, value in config.get("env", {}).items():
        os.environ[key] = str(value)


def metrics_for(manifest, workload, group):
    """The manifest's metrics of ``group`` that this cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def peaks_for(manifest, device_kind):
    table = load_json(find(manifest, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       f"have {sorted(table['devices'])}")
    return table["devices"][device_kind]
