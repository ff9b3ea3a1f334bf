"""The training path measures itself (docs/OBSERVABILITY.md): seams on the
profiler's clock, named scopes and kernel names in the round program, the
grower's round counters beside the tree, and the compile counters."""
import ast
import glob
import math
from pathlib import Path

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.flight import global_flight
from lightgbm_tpu.obs.metrics import global_registry
from lightgbm_tpu.obs.trace import global_tracer

pytestmark = pytest.mark.obs

REPO = Path(__file__).resolve().parents[1]
SCOPES = ("lgbm.gradients", "lgbm.quantize", "lgbm.route", "lgbm.hist",
          "lgbm.scan", "lgbm.commit", "lgbm.leaf_values",
          "lgbm.score_update")
KERNELS = [("ops/fused.py", ["lgbm_hist_accum", "lgbm_sibling_scan"]),
           ("ops/histogram.py", ["lgbm_hist_staged"]),
           ("ops/ingest.py", ["lgbm_ingest_bin"]),
           ("ops/predict_kernels.py", ["lgbm_traverse"])]


def _data(seed, rows=3000, features=6):
    rng = np.random.RandomState(seed)
    X = rng.rand(rows, features).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.1 * rng.randn(rows)
         > 0.8).astype(np.float32)
    return X, y


def _counters(prefix):
    return {k: v for k, v in global_registry.to_dict()["counters"].items()
            if k.startswith(prefix)}


# ------------------------------------------------ (a) the profiler's clock


def test_seams_lie_on_the_profilers_clock(tmp_path, monkeypatch):
    """A tiny train under a profiler session: the host plane holds the
    program's seams by name, while the Chrome recorder (its flag unset)
    records nothing."""
    from jax.profiler import ProfileData
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    monkeypatch.setenv("LGBT_DEFER_HOST_TREES", "1")
    assert not global_tracer.enabled
    global_tracer.reset()
    X, y = _data(0, rows=5000)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=2)
        assert bst.num_trees() == 2     # drains the deferred trees
    finally:
        jax.profiler.stop_trace()
    assert global_tracer.events() == []
    xplane = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                       recursive=True)[-1]
    names = {ev.name for plane in ProfileData.from_file(xplane).planes
             if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("lgbm.")}
    for want in ("lgbm.macro.host_inputs", "lgbm.macro.dispatch",
                 "lgbm.macro.host_fetch", "lgbm.gbdt.drain_pending",
                 "lgbm.jit.build", "lgbm.ingest.edges",
                 "lgbm.ingest.device_bin", "lgbm.ingest.put",
                 "lgbm.ingest.wait_put", "lgbm.ingest.bin_chunk",
                 "lgbm.ingest.to_device"):
        assert want in names, (want, sorted(names))


def test_ring_holds_the_coarse_seams_with_parent_and_it():
    """With no session and no flag the flight ring still takes the coarse
    seams: one ``ingest.device_bin`` a construct carrying its chunks'
    sums, and the round's seams sharing ``it`` under ``engine.step``."""
    assert not global_tracer.enabled
    global_flight._ring.clear()
    X, y = _data(1)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              lgb.Dataset(X, label=y), num_boost_round=3)
    recs = [e for e in global_flight.ring_events() if e.get("ph") == "X"]
    by_name = {}
    for e in recs:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("macro.host_inputs", "macro.dispatch", "macro.host_fetch"):
        its = [e["args"]["it"] for e in by_name[name]]
        assert its == [0, 2], (name, its)     # a c=2 chunk, then c=1
        assert all(e["args"]["parent"] == "engine.step"
                   for e in by_name[name])
    assert [e["args"]["it"] for e in by_name["engine.step"]] == [0, 2]
    assert "ingest.edges" in by_name and "ingest.to_device" in by_name
    # per-chunk seams are annotations only
    assert not {"ingest.put", "ingest.wait_put",
                "ingest.bin_chunk"} & set(by_name)


def test_host_bin_is_one_ring_record_a_construct():
    """Where the host bins (here: no accelerator) the pass is on the ring,
    one record a construct, and the kernel's record is not."""
    global_flight._ring.clear()
    X, y = _data(2, rows=5000)
    lgb.Dataset(X, label=y, params={"verbosity": -1}).construct()
    names = [e["name"] for e in global_flight.ring_events()
             if e.get("ph") == "X"]
    assert names.count("ingest.host_bin") == 1
    assert "ingest.device_bin" not in names
    (rec,) = [e for e in global_flight.ring_events()
              if e["name"] == "ingest.host_bin"]
    assert rec["args"]["rows"] == 5000 and rec["dur"] > 0


def test_device_bin_record_carries_its_chunks_sums(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    monkeypatch.setenv("LGBM_TPU_INGEST_CHUNK", "1500")
    global_flight._ring.clear()
    X, y = _data(2, rows=5000)
    lgb.Dataset(X, label=y, params={"verbosity": -1}).construct()
    recs = [e for e in global_flight.ring_events()
            if e["name"] == "ingest.device_bin"]
    assert len(recs) == 1               # one a construct, not one a chunk
    args = recs[0]["args"]
    assert args["wait_put_s"] >= 0 and args["bin_s"] > 0
    assert args["wait_put_s"] + args["bin_s"] <= recs[0]["dur"] / 1e6 + 1e-3


# ------------------------------------- (b) scopes and kernel names


def test_round_program_carries_every_scope():
    from lightgbm_tpu.boosting.macro import (build_chunk_program,
                                             chunk_host_inputs)
    X, y = _data(3)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "tpu_tree_growth": "rounds", "use_quantized_grad": True,
              "num_grad_quant_bins": 4}
    b = lgb.Booster(params, lgb.Dataset(X, label=y, params=params)).boosting
    b.boost_from_average()
    xs, _ = chunk_host_inputs(b, 1)
    cu, cr = b._cegb_state
    gc, hc = b._macro_const_grads()
    text = build_chunk_program(b).lower(
        b.binned, b.train_score, cu, cr, np.int32(1), xs,
        b._macro_ctx["label"], b._macro_ctx["weight"], gc, hc,
        b._macro_ctx["obj_tables"]).compile().as_text()
    op_names = "\n".join(line for line in text.splitlines()
                         if "op_name=" in line)
    for scope in SCOPES:
        assert scope in op_names, scope


@pytest.mark.parametrize("rel, names", KERNELS)
def test_every_pallas_call_is_named(rel, names):
    tree = ast.parse((REPO / "lightgbm_tpu" / rel).read_text())
    found = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            kw = {k.arg: k.value for k in node.keywords}
            assert "name" in kw, f"{rel}:{node.lineno} has no name="
            found.append(kw["name"].value)
    assert found == names


def test_no_other_file_calls_pallas():
    known = {rel for rel, _ in KERNELS}
    for path in (REPO / "lightgbm_tpu").rglob("*.py"):
        rel = str(path.relative_to(REPO / "lightgbm_tpu"))
        if rel not in known:
            assert "pallas_call(" not in path.read_text(), rel


# --------------------------------------------- (c) the grower's counters


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_grower_counters(seed):
    num_leaves = 31
    X, y = _data(seed, rows=4000)
    params = {"objective": "binary", "num_leaves": num_leaves,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_tree_growth": "rounds"}
    global_flight._ring.clear()
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
    trees = [e["args"] for e in global_flight.ring_events()
             if e["name"] == "grower.tree"]
    assert [t["it"] for t in trees] == [0, 1, 2]
    for t, model in zip(trees, bst.boosting.models):
        assert model.num_leaves == num_leaves
        assert t["applied"] == num_leaves - 1
        assert t["offered"] >= t["applied"]
        assert math.ceil(math.log2(num_leaves)) <= t["rounds"] \
            <= num_leaves - 1
    # the ring record is the one carrier: no counter doubles it
    assert not [k for k in _counters("train_") if "grower" in k]


def test_serial_grower_counts_one_split_a_trip():
    X, y = _data(14)
    global_flight._ring.clear()
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "tpu_tree_growth": "serial"},
                    lgb.Dataset(X, label=y), num_boost_round=1)
    (t,) = [e["args"] for e in global_flight.ring_events()
            if e["name"] == "grower.tree"]
    splits = bst.boosting.models[0].num_leaves - 1
    assert (t["rounds"], t["offered"], t["applied"]) == (splits,) * 3
    assert t["slots"] == splits          # a pass one slot wide a split
    assert t["clipped"] == 0             # and no offer to clip
    assert t["lanes"] == splits          # rows routed against one split


@pytest.mark.parametrize("method", ["scatter", "fused"])
def test_grower_tree_carries_slots(method):
    """The fourth and fifth counters of the grower's carry land on the
    ``grower.tree`` record: the slot widths the histogram passes ran at,
    and the trips the offer clipped.
    The fused arm's passes (root included) run at the narrowest compiled
    width that holds the round's candidates; a staged pass at the cap."""
    num_leaves = 63
    X, y = _data(15, rows=4000)
    global_flight._ring.clear()
    bst = lgb.train({"objective": "binary", "num_leaves": num_leaves,
                     "min_data_in_leaf": 5, "verbosity": -1, "max_bin": 31,
                     "tpu_tree_growth": "rounds",
                     "tpu_hist_method": method},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    assert bst.boosting.grower_cfg.hist_method == method
    trees = [e["args"] for e in global_flight.ring_events()
             if e["name"] == "grower.tree"]
    assert len(trees) == 2
    cap = num_leaves - 1
    for t in trees:
        assert t["offered"] <= t["slots"]
        # the fifth counter: trips in which the offer bound and all of it
        # committed.  The offer moves on the same rungs in both families
        assert 0 <= t["clipped"] <= t["rounds"]
        # the sixth: the lanes the route compared rows with (the CPU's
        # candidate scan passes over the live ones alone)
        assert t["lanes"] == t["offered"]
        if method == "fused":
            # root at 16, then each round at the rung its offer named:
            # 16 or the cap (62 < 64)
            assert 16 * (t["rounds"] + 1) <= t["slots"] \
                < 16 + cap * t["rounds"]
            assert (t["slots"] - 16 * (t["rounds"] + 1)) % (cap - 16) == 0
            # an offer of 16 holds at most 16: the rounds at the cap are
            # those the slots count, and only they may offer more
            wide = (t["slots"] - 16 * (t["rounds"] + 1)) // (cap - 16)
            assert t["offered"] <= 16 * (t["rounds"] - wide) + cap * wide
        else:
            assert t["slots"] == cap * t["rounds"]


def test_counters_leave_the_tree_as_it_was():
    """with_stats adds an output, nothing else: same tree, same rows."""
    import jax.numpy as jnp
    from lightgbm_tpu.dataset import FeatureMeta
    from lightgbm_tpu.grower import GrowerConfig
    from lightgbm_tpu.grower_rounds import grow_tree_rounds
    from lightgbm_tpu.ops.split import SplitHyperparams
    rng = np.random.RandomState(5)
    n, F, B = 4096, 8, 32
    binned = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    grad = (rng.randn(n) + 0.7 * (binned[:, 1] > 16)).astype(np.float32)
    meta = FeatureMeta(
        num_bin=np.full(F, B, np.int32), missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32),
        most_freq_bin=np.zeros(F, np.int32),
        is_categorical=np.zeros(F, bool), max_num_bin=B)
    cfg = GrowerConfig(num_leaves=31, num_bins=B, hp=SplitHyperparams(),
                       hist_method="scatter")
    args = (jnp.asarray(binned.T), jnp.asarray(grad),
            jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32), meta, cfg)
    t0, lid0 = grow_tree_rounds(*args)
    t1, lid1, stats = grow_tree_rounds(*args, with_stats=True)
    for a, b in zip(jax.tree_util.tree_leaves(t0),
                    jax.tree_util.tree_leaves(t1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(lid0), np.asarray(lid1))
    rounds, offered, applied, slots, clipped, lanes = (int(v) for v in stats)
    assert lanes == offered             # the scan: the live lanes alone
    assert applied == int(t1.num_leaves) - 1
    assert 1 <= rounds <= applied <= offered
    assert 0 <= clipped <= rounds
    # the staged family builds every pass at the round cap (30 of 31
    # leaves); its root is no segment pass
    assert slots == 30 * rounds


# ----------------------------------------------- (d) the compile counters


def test_compile_counters_rise_once():
    """The first round of a new shape traces, lowers and compiles; an
    identical later round does none of it."""
    from lightgbm_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    X, y = _data(15, rows=2777, features=7)     # a shape of this test's
    params = {"objective": "binary", "num_leaves": 9, "verbosity": -1}
    c0 = _counters("compile_")
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    bst.update()
    c1 = _counters("compile_")
    assert c1["compile_programs_total"] > c0["compile_programs_total"]
    assert c1["compile_trace_seconds"] > c0["compile_trace_seconds"]
    assert c1["compile_lower_seconds"] > c0["compile_lower_seconds"]
    spent = (c1["compile_backend_seconds"] + c1["compile_cache_read_seconds"]
             - c0["compile_backend_seconds"]
             - c0["compile_cache_read_seconds"])
    assert spent > 0
    bst.update()        # the second round may still meet a first (it > 0)
    c2 = _counters("compile_")
    bst.update()
    assert _counters("compile_") == c2


# ------------------------------------ (e) the loop's whole turn, one clock


def _ring_spans():
    return [e for e in global_flight.ring_events() if e.get("ph") == "X"]


def _on_ring_clock(t_ns):
    """A ``perf_counter_ns`` reading in the ring's microseconds."""
    return (t_ns - global_tracer.epoch_ns) / 1e3


def test_engine_step_is_the_whole_turn(tmp_path):
    """One ``engine.step`` record a turn holds the before-round callbacks,
    the update's seams, the evaluation, the after-round callbacks and the
    snapshot save; the watchdog's beat and ``train_iter_seconds`` still
    read the update."""
    import time
    X, y = _data(16)
    ds = lgb.Dataset(X, label=y)
    dv = lgb.Dataset(X[:600], label=y[:600], reference=ds)
    seen = {"before": [], "after": []}

    def before(env):
        seen["before"].append((env.iteration, time.perf_counter_ns()))
    before.before_iteration = True

    def after(env):
        time.sleep(0.01)
        seen["after"].append((env.iteration, time.perf_counter_ns()))

    global_flight._ring.clear()
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metric": "auc"}, ds, num_boost_round=4, valid_sets=[dv],
              callbacks=[before, after], snapshot_freq=2,
              snapshot_out=str(tmp_path / "m.txt"))
    recs = _ring_spans()
    steps = [e for e in recs if e["name"] == "engine.step"]
    assert [e["args"]["it"] for e in steps] == [0, 1, 2, 3]
    assert all(e["args"]["parent"] == "engine.train" for e in steps)
    for step in steps:
        lo, hi = step["ts"], step["ts"] + step["dur"]
        it = step["args"]["it"]
        for when in ("before", "after"):
            (t_ns,) = [t for i, t in seen[when] if i == it]
            assert lo < _on_ring_clock(t_ns) < hi, (when, it)
        kids = {e["name"] for e in recs if e is not step
                and lo <= e["ts"] and e["ts"] + e["dur"] <= hi}
        assert {"engine.eval", "macro.dispatch"} <= kids, kids
        assert ("checkpoint.save" in kids) == (it % 2 == 1), (it, kids)
    for e in recs:
        if e["name"] in ("engine.eval", "checkpoint.save", "macro.dispatch"):
            assert e["args"]["parent"] == "engine.step", e
    # the update's own accounting: not the turn's 10 ms callback sleep
    g = global_registry.to_dict()["gauges"]
    assert g["train_iter_seconds"] < steps[-1]["dur"] / 1e6


@pytest.mark.parametrize("metric_freq", [1, 2])
def test_engine_eval_is_on_the_ring(metric_freq):
    """One ``engine.eval`` record an evaluation boundary, with ``it`` and
    ``parent`` ``engine.step``; ``gbdt.eval`` stays an annotation."""
    X, y = _data(17)
    ds = lgb.Dataset(X, label=y)
    dv = lgb.Dataset(X[:600], label=y[:600], reference=ds)
    global_flight._ring.clear()
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metric": ["auc", "binary_logloss"],
               "metric_freq": metric_freq},
              ds, num_boost_round=4, valid_sets=[dv])
    recs = _ring_spans()
    evals = [e for e in recs if e["name"] == "engine.eval"]
    assert [e["args"]["iteration"] for e in evals] \
        == [j for j in range(4) if (j + 1) % metric_freq == 0]
    steps = {e["args"]["it"]: e for e in recs if e["name"] == "engine.step"}
    for e in evals:
        assert e["args"]["parent"] == "engine.step"
        step = steps[e["args"]["it"]]
        assert step["ts"] <= e["ts"] \
            and e["ts"] + e["dur"] <= step["ts"] + step["dur"]
    names = {e["name"] for e in recs}
    assert not {"gbdt.eval", "eval.pull", "metric.auc",
                "engine.callbacks"} & names


def test_ring_offset_lays_the_ring_on_a_profiler_trace(tmp_path):
    """A real profiler session on XLA:CPU around a short ``lgb.train``:
    every ring record of the session lands on its ``lgbm.`` annotation by
    one offset; the evaluation's pull and metrics are annotations."""
    from benchmark.lib import trace_reduce as tr
    from lightgbm_tpu.obs.trace import ring_offset_ns
    X, y = _data(18)
    ds = lgb.Dataset(X, label=y)
    dv = lgb.Dataset(X[:600], label=y[:600], reference=ds)
    global_flight._ring.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                   "metric": ["auc", "binary_logloss"]}, ds,
                  num_boost_round=3, valid_sets=[dv],
                  snapshot_freq=2, snapshot_out=str(tmp_path / "m.txt"))
    finally:
        jax.profiler.stop_trace()
    planes = tr.read_planes(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1])
    host = [(n, s, d) for p, lines in planes.items() if p.startswith("/host")
            for events in lines.values() for n, s, d in events
            if n.startswith("lgbm.")]
    names = {n for n, _, _ in host}
    assert {"lgbm.engine.step", "lgbm.engine.eval", "lgbm.engine.callbacks",
            "lgbm.eval.pull", "lgbm.metric.auc",
            "lgbm.metric.binary_logloss"} <= names
    spans = _ring_spans()
    got = ring_offset_ns(spans, host)
    assert got is not None
    assert got["unmatched"] == 0
    assert got["no_annotation"] == ["grower.tree"]
    assert got["matched"] == sum(e["name"] != "grower.tree" for e in spans)
    assert got["worst_ns"] < 50e3, got


def test_ring_offset_on_planted_records():
    """Records before the session match nothing and count for nothing;
    a record inside it with no annotation of its name is unmatched."""
    from lightgbm_tpu.obs.trace import ring_offset_ns
    epoch, off = 10**12, 777_000
    ring = [{"name": "a", "ph": "X", "ts": float(t), "dur": 5.0}
            for t in (0, 1e6, 2e6, 3e6, 4e6)]
    # the session saw the last three, each 1-3 us late
    host = [("lgbm.a", epoch + t * 1e3 + off + d, 5e3)
            for t, d in ((2e6, 1000), (3e6, 3000), (4e6, 2000))]
    got = ring_offset_ns(ring, host, epoch_ns=epoch)
    assert got["matched"] == 3 and got["unmatched"] == 0
    assert got["offset_ns"] == off + 2000 and got["worst_ns"] == 1000
    ring.append({"name": "a", "ph": "X", "ts": 3.5e6, "dur": 1.0})
    ring.append({"name": "b", "ph": "X", "ts": 3.6e6, "dur": 1.0})
    got = ring_offset_ns(ring, host, epoch_ns=epoch)
    assert got["unmatched"] == 1 and got["no_annotation"] == ["b"]
    assert ring_offset_ns(ring, [("lgbm.c", 0, 1)], epoch_ns=epoch) is None


def test_idle_split_of_a_kept_trace():
    """``tools/scope_table.py --idle``: the idle pieces add up to the
    window less the busy time, and match the benchmark reducer's
    ``idle_gaps`` on a trace whose only annotations are the harness's."""
    from benchmark.lib import trace_reduce as tr
    from tools import scope_table
    path = REPO / "benchmark/tests/data/trace_small.xplane.pb.gz"
    got = scope_table.idle(path)
    assert got["idle_s"] == pytest.approx(got["window_s"] - got["busy_s"])
    assert sum(got["by_innermost_s"].values()) \
        == pytest.approx(got["idle_s"], rel=1e-9)
    reduced = tr.reduce_trace(path)
    assert got["window_s"] == pytest.approx(reduced["window_s"])
    assert got["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-6)
    expect = {("outside_spans" if n == "outside_harness_spans" else n): s
              for n, s in reduced["idle_gaps"]}
    assert got["by_innermost_s"] == pytest.approx(expect)
    # with one span nested in another, the outer one holds both under_s
    planes = tr.read_planes(path)
    planes["/host:CPU"]["probe"] = [("lgbm.probe", 0.0, 1e30)]
    inner = {"/host:CPU": planes["/host:CPU"],
             "/device:TPU:0": planes["/device:TPU:0"]}
    import unittest.mock as mock
    with mock.patch.object(scope_table.tr, "read_planes",
                           return_value=inner):
        nested = scope_table.idle(path)
    # the harness's spans stay innermost; what they left now reads probe
    expect["lgbm.probe"] = expect.pop("outside_spans")
    assert nested["by_innermost_s"] == pytest.approx(expect)
    assert nested["under_s"]["lgbm.probe"] == pytest.approx(got["idle_s"])
