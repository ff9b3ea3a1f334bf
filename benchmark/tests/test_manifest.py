"""BENCHMARK.json and the rehearsal twin keep to the driver's rules."""
import json

import pytest

from benchmark import check_manifest
from benchmark.lib.lookup import REPO


@pytest.fixture(scope="module")
def manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_manifest_is_valid(manifest):
    assert check_manifest.check(manifest) == []


def test_twin_manifest_is_valid():
    twin = json.loads(
        (REPO / "benchmark/tests/data/BENCHMARK.json").read_text())
    assert check_manifest.check(twin, allow_extra=("rehearsal",)) == []


def test_every_layer_is_an_identifier(manifest):
    for m in manifest["per_layer"]:
        assert check_manifest.NAME.match(m["layer"]), m


@pytest.mark.parametrize("breakage, needle", [
    (lambda m: m["per_layer"][0].__setitem__("layer", "the compile layer"),
     "is not an identifier"),
    (lambda m: m["per_layer"][0].pop("workloads"), "no workloads list"),
    (lambda m: m["per_layer"][0].__setitem__("moves", "nothing"),
     "no end-to-end metric"),
    (lambda m: m["per_layer"][0].__setitem__("unit", "seconds per tree"),
     "unit"),
    (lambda m: m["workloads"][0].__setitem__("traffic", "no_such_mix"),
     "no traffic file"),
    (lambda m: m["configs"][0].__setitem__("file", "benchmark/none.json"),
     "does not exist"),
    (lambda m: m["configs"][0].__setitem__("source", "x" * 201),
     "1 to 200 characters"),
    (lambda m: m["per_layer"][2].__setitem__(
        "workloads", ["no-such.cell"]), "no cell"),
    (lambda m: (m["end_to_end"][0].__setitem__(
        "workloads", [m["workloads"][0]["name"]]),
                m["end_to_end"].append(dict(m["end_to_end"][0], name="other",
                                            workloads=[m["workloads"][1]["name"]]))),
     "does not report"),
    (lambda m: m["end_to_end"][0].__setitem__("bound", 0.5), "bound"),
    (lambda m: m["per_layer"][0].__setitem__("why", "x"), "keys"),
])
def test_checker_catches(breakage, needle):
    """Broken copies of the twin manifest, which has several cells."""
    broken = json.loads(
        (REPO / "benchmark/tests/data/BENCHMARK.json").read_text())
    breakage(broken)
    errors = check_manifest.check(broken, allow_extra=("rehearsal",))
    assert any(needle in e for e in errors), errors


def test_config_env_is_applied(monkeypatch):
    """A configuration's ``env`` (the program's documented switches) is set
    before the program is imported; a configuration without one sets none."""
    import os
    from benchmark.lib import lookup
    monkeypatch.delenv("LGBM_TPU_STREAM", raising=False)
    lookup.apply_env({"params": {}})
    assert "LGBM_TPU_STREAM" not in os.environ
    cfg = json.loads((REPO / "benchmark/configs/criteo-quant.json").read_text())
    lookup.apply_env(cfg)
    assert os.environ["LGBM_TPU_STREAM"] == "0"
