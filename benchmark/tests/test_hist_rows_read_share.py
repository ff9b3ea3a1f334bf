"""``hist_rows_read_share``: the rows the window's accumulate passes read
over the rows of the padded matrix a pass, from the ``pass_rows`` of the
``grower.tree`` records; ``None`` for a program whose records carry no
``pass_rows``."""
from types import SimpleNamespace

import pytest

from benchmark import check_manifest
from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()
NAME = "hist_rows_read_share"
CELLS = ["criteo-goss.train"]
N_PAD = 1 << 20


def read(trees):
    reader = lookup.load_module(lookup.find(MANIFEST, f"metrics/{NAME}.py"))
    return reader.read({"run": SimpleNamespace(
        trees=trees, kind="train_loop_goss", info={"n_pad": N_PAD})})


@pytest.fixture
def ring(monkeypatch):
    from lightgbm_tpu.obs import flight
    ring = flight.FlightRecorder(max_events=64, enabled=True, max_dumps=0)
    monkeypatch.setattr(flight, "global_flight", ring)
    return ring


def _tree(it, rounds, pass_rows=None, kept=0):
    rec = dict(it=it, k=0, rounds=rounds, offered=2 * rounds, applied=254,
               slots=64 * rounds, clipped=0, lanes=64 * rounds,
               goss_kept=kept, goss_top=kept // 2)
    if pass_rows is not None:
        rec["pass_rows"] = pass_rows
    return rec


def test_reads_the_rows_of_the_window_passes(ring):
    # a warm-up tree over every row, then two sampled trees whose passes
    # stopped at the last tile of their kept rows
    ring.note("grower.tree", **_tree(0, 21, 22 * N_PAD))
    ring.note("grower.tree", **_tree(1, 25, 26 * 315392, kept=314573))
    ring.note("grower.tree", **_tree(2, 27, 28 * 319488, kept=318767))
    got = read(2)
    assert got == pytest.approx(
        100.0 * (26 * 315392 + 28 * 319488) / (54 * N_PAD))
    assert 30.0 < got < 31.0
    assert read(3) == pytest.approx(
        100.0 * (22 * N_PAD + 26 * 315392 + 28 * 319488) / (76 * N_PAD))
    assert read(4) is None          # fewer records than trees


def test_every_row_reads_100(ring):
    for it in range(3):
        ring.note("grower.tree", **_tree(it, 20 + it, (21 + it) * N_PAD))
    assert read(3) == 100.0


def test_none_on_records_without_pass_rows(ring):
    """The parent program: its passes read every row and counted none."""
    ring.note("grower.tree", **_tree(0, 22, kept=300000))
    ring.note("grower.tree", **_tree(1, 24, 25 * N_PAD))
    assert read(2) is None
    assert read(1) == 100.0
    ring.enabled = False
    assert read(1) is None


def test_none_without_records(ring):
    assert read(1) is None


def test_manifest_holds_the_metric():
    """Held to the entry's name, not to its place in ``per_layer``: the
    next PR appends."""
    assert check_manifest.check(MANIFEST) == []
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "lower",
                 "source": "program_counter", "layer": "hist_kernel",
                 "moves": "train_s_per_tree", "workloads": CELLS}
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["name"] in CELLS] == CELLS
