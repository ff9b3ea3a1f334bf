"""Out-of-core streaming data plane (lightgbm_tpu/data/).

The hard contracts:

- streamed training == resident training BYTE-identical (model text)
  for quantized payloads, and bit-identical in pinned block order for
  f32 (the resident comparator pins the rounds grower — the streamed
  grower mirrors it op for op);
- the two-level budget planner (ops/planner.plan_stream) elects
  streaming exactly when residency blows either the device or the host
  budget, and sizes blocks to fit both;
- the spill store is checksummed: corruption raises loudly, never
  wrong trees; writes are atomic; spill-mode loads keep host RSS
  O(chunk);
- push_rows validates overlap/gaps instead of silently overwriting;
- checkpoints resume mid-stream bit-identically, across modes.
"""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.data.blockstore import (BlockStore, BlockStoreCorruptError)
from lightgbm_tpu.data.stream import BlockPump, host_rss_bytes
from lightgbm_tpu.dataset import Dataset
from lightgbm_tpu.ops.planner import (plan_stream, predict_host_peak_bytes)

RNG = np.random.RandomState(7)
N, F = 1200, 10
X = RNG.randn(N, F)
Y_BIN = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.2 * RNG.randn(N) > 0).astype(float)
XV = RNG.randn(400, F)
YV_BIN = (XV[:, 0] + 0.5 * XV[:, 1] * XV[:, 2]
          + 0.2 * RNG.randn(400) > 0).astype(float)

BASE = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
        "verbosity": -1, "tpu_tree_growth": "rounds"}

PARITY_CASES = {
    "f32": {},
    "quant": {"use_quantized_grad": True},
    "quant_renew": {"use_quantized_grad": True,
                    "quant_train_renew_leaf": True},
    "bagging": {"bagging_fraction": 0.7, "bagging_freq": 2,
                "bagging_seed": 11},
    "goss": {"boosting": "goss", "learning_rate": 0.2},
    "l1_renew": {"objective": "regression_l1"},
    "multiclass": {"objective": "multiclass", "num_class": 3,
                   "num_leaves": 7},
}


def _stream_env(monkeypatch, block_rows=256):
    monkeypatch.setenv("LGBM_TPU_STREAM", "1")
    monkeypatch.setenv("LGBM_TPU_STREAM_BLOCK_ROWS", str(block_rows))


def _train(params, y=Y_BIN, rounds=12, x=None):
    ds = lgb.Dataset(X if x is None else x, label=y, free_raw_data=False)
    b = lgb.Booster(params=dict(BASE, **params), train_set=ds)
    for _ in range(rounds):
        b.update()
    return b


# ---------------------------------------------------------------- parity

@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_streamed_equals_resident(case, monkeypatch):
    params = PARITY_CASES[case]
    y = Y_BIN
    if case == "multiclass":
        y = np.digitize(X[:, 0] + X[:, 1], [-0.5, 0.5]).astype(float)
    monkeypatch.setenv("LGBM_TPU_STREAM", "0")
    resident = _train(params, y).model_to_string()
    _stream_env(monkeypatch)
    b = _train(params, y)
    assert b.boosting._stream is not None, "stream election did not engage"
    assert b.model_to_string() == resident, \
        f"{case}: streamed != resident model text"


def test_streamed_block_size_invariance(monkeypatch):
    """Quantized folds are associative: ANY block partition gives the
    byte-identical model (f32 pins ONE block order; quant pins none)."""
    params = {"use_quantized_grad": True}
    _stream_env(monkeypatch, block_rows=256)
    m256 = _train(params).model_to_string()
    _stream_env(monkeypatch, block_rows=500)
    m500 = _train(params).model_to_string()
    assert m256 == m500


def test_streamed_engine_train_with_valid(monkeypatch):
    """Full engine path: eval history, valid scores, metric_freq — the
    streamed booster must reproduce the resident run exactly."""
    def run():
        ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
        vs = lgb.Dataset(XV, label=YV_BIN, reference=ds,
                         free_raw_data=False)
        evals = {}
        bst = lgb.train(dict(BASE, metric="binary_logloss"), ds,
                        num_boost_round=10, valid_sets=[vs],
                        evals_result=evals, verbose_eval=False)
        return bst.model_to_string(), evals

    monkeypatch.setenv("LGBM_TPU_STREAM", "0")
    m_r, ev_r = run()
    _stream_env(monkeypatch)
    m_s, ev_s = run()
    assert m_s == m_r
    assert ev_s == ev_r


def test_resume_mid_stream(tmp_path, monkeypatch):
    """A checkpoint written mid-stream resumes to the byte-identical
    final model — within streamed mode AND restored into a resident
    run (streamed == resident is bit-invariant, so bundles cross)."""
    snap = str(tmp_path / "m.txt")
    params = dict(BASE, bagging_fraction=0.7, bagging_freq=1)

    def run(stream, resume=None):
        if stream:
            _stream_env(monkeypatch)
        else:
            monkeypatch.setenv("LGBM_TPU_STREAM", "0")
        ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
        return lgb.train(params, ds, num_boost_round=14,
                         verbose_eval=False, snapshot_freq=5,
                         snapshot_out=snap,
                         resume_from=resume).model_to_string()

    full = run(True)
    assert run(True, resume=snap + ".ckpt") == full
    assert run(False, resume=snap + ".ckpt") == full


def test_checkpoint_records_stream_provenance(tmp_path, monkeypatch):
    import glob
    import zipfile
    _stream_env(monkeypatch)
    snap = str(tmp_path / "m.txt")
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    lgb.train(BASE, ds, num_boost_round=4, verbose_eval=False,
              snapshot_freq=2, snapshot_out=snap)
    bundle = sorted(glob.glob(snap + ".ckpt/*.lgbckpt"))[-1]
    with zipfile.ZipFile(bundle) as zf:
        man = json.loads(zf.read("manifest.json"))
    sp = man["stream_plan"]
    assert sp is not None and sp["stream"]
    assert sp["store_num_blocks"] >= 2
    assert sp["store_block_rows"] == 256


def test_stream_unsupported_config_falls_back_resident(monkeypatch):
    """A forced stream election with a config the streamed executor does
    not cover warns and trains resident instead of failing."""
    _stream_env(monkeypatch)
    b = _train({"objective": "regression",
                "monotone_constraints": [1] + [0] * (F - 1)}, y=X[:, 0])
    assert b.boosting._stream is None
    assert b.num_trees() == 12


def test_chunk_scheduler_declines_streamed(monkeypatch):
    _stream_env(monkeypatch)
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    b = lgb.Booster(params=dict(BASE), train_set=ds)
    assert b.boosting._stream is not None
    assert not b.boosting.chunk_supported()
    with pytest.raises(RuntimeError, match="per-iteration"):
        b.update_chunk(4)


# ------------------------------------------------- spill-mode construction

def test_from_sample_spill_trains_and_matches(monkeypatch, tmp_path):
    n, f = 4000, 6
    rng = np.random.RandomState(3)
    Xs = rng.rand(n, f)
    ys = (Xs[:, 0] + Xs[:, 1] > 1.0).astype(np.float32)
    p = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "tpu_tree_growth": "rounds"}

    ds = Dataset.from_sample(Xs[:1000], n, spill=str(tmp_path / "st"),
                             spill_block_rows=512)
    for lo in range(0, n, 700):        # ragged final chunk (5*700 + 500)
        ds.push_rows(Xs[lo:lo + 700])
    assert ds.constructed and ds.binned is None
    assert ds._block_store.num_blocks == 8
    ds.set_label(ys)
    b = lgb.Booster(params=p, train_set=ds)
    assert b.boosting._stream is not None
    for _ in range(5):
        b.update()
    spilled = b.model_to_string()

    monkeypatch.setenv("LGBM_TPU_STREAM", "0")
    ds2 = Dataset.from_sample(Xs[:1000], n)
    for lo in range(0, n, 700):
        ds2.push_rows(Xs[lo:lo + 700])
    ds2.set_label(ys)
    b2 = lgb.Booster(params=p, train_set=ds2)
    for _ in range(5):
        b2.update()
    assert spilled == b2.model_to_string()


def test_push_rows_overlap_raises():
    ds = Dataset.from_sample(X[:300], N)
    ds.push_rows(X[:400])
    with pytest.raises(ValueError, match="overlap"):
        ds.push_rows(X[300:600], start_row=300)
    # disjoint explicit ranges still fine (out-of-order fill)
    ds.push_rows(X[800:], start_row=800)
    ds.push_rows(X[400:800], start_row=400)
    assert ds.constructed


def test_push_rows_spill_gap_raises(tmp_path):
    ds = Dataset.from_sample(X[:300], N, spill=str(tmp_path / "st"),
                             spill_block_rows=256)
    ds.push_rows(X[:400])
    with pytest.raises(ValueError, match="append in order"):
        ds.push_rows(X[600:], start_row=600)


def test_incomplete_stream_construct_names_gap():
    ds = Dataset.from_sample(X[:300], N)
    ds.push_rows(X[:400])
    with pytest.raises(RuntimeError, match="first unpushed row: 400"):
        ds.construct()


def test_binned_metadata_accessors(monkeypatch):
    # released matrix: shape/dtype stay valid, data access raises
    monkeypatch.setenv("LGBM_TPU_FREE_BINNED", "1")
    monkeypatch.setenv("LGBM_TPU_STREAM", "0")
    ds = lgb.Dataset(X, label=Y_BIN)
    lgb.Booster(params=dict(BASE), train_set=ds)
    assert ds.binned is None
    assert ds.binned_shape() == (N, ds.num_groups)
    assert ds.binned_dtype() == np.uint8
    with pytest.raises(RuntimeError, match="released"):
        ds.host_binned()
    # block-backed matrix (free_raw_data=True releases the host copy
    # after the spill): same metadata, block-store-specific error
    monkeypatch.delenv("LGBM_TPU_FREE_BINNED")
    _stream_env(monkeypatch)
    ds2 = lgb.Dataset(X, label=Y_BIN)
    lgb.Booster(params=dict(BASE), train_set=ds2)
    assert ds2.binned is None and ds2._block_store is not None
    assert ds2.binned_shape() == (N, ds2.num_groups)
    with pytest.raises(RuntimeError, match="block store"):
        ds2.host_binned()


def test_spill_keeps_host_matrix_when_raw_kept(monkeypatch):
    """free_raw_data=False keeps the host matrix next to the spill store
    (the user asked for reuse); free_raw_data=True releases it."""
    _stream_env(monkeypatch)
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    lgb.Booster(params=dict(BASE), train_set=ds)
    assert ds.binned is not None
    ds2 = lgb.Dataset(X, label=Y_BIN)
    lgb.Booster(params=dict(BASE), train_set=ds2)
    assert ds2.binned is None


# ----------------------------------------------------------- block store

def test_blockstore_roundtrip(tmp_path):
    rng = np.random.RandomState(0)
    arr = rng.randint(0, 200, (1000, 7), dtype=np.uint8)
    st = BlockStore.from_array(str(tmp_path / "st"), arr, 256)
    assert st.num_blocks == 4                      # 256*3 + 232 ragged
    st2 = BlockStore.open(str(tmp_path / "st"))
    got = np.concatenate([np.asarray(st2.read_block(i)).T
                          for i in range(st2.num_blocks)])
    np.testing.assert_array_equal(got, arr)
    # readinto path returns the same bytes
    buf = np.empty((7, st2.block_rows), np.uint8)
    view = st2.read_block(0, out=buf, verify=True)
    np.testing.assert_array_equal(view, np.asarray(st2.read_block(0)))


def test_blockstore_ragged_chunk_composition(tmp_path):
    rng = np.random.RandomState(1)
    arr = rng.randint(0, 255, (900, 4), dtype=np.uint8)
    st = BlockStore.create(str(tmp_path / "st"), 900, 4, np.uint8, 128)
    for lo, hi in ((0, 50), (50, 500), (500, 900)):   # uneven appends
        st.append_rows(arr[lo:hi])
    st.finalize()
    st2 = BlockStore.open(str(tmp_path / "st"))
    got = np.concatenate([np.asarray(st2.read_block(i)).T
                          for i in range(st2.num_blocks)])
    np.testing.assert_array_equal(got, arr)


def test_blockstore_corruption_raises(tmp_path):
    rng = np.random.RandomState(2)
    arr = rng.randint(0, 255, (600, 5), dtype=np.uint8)
    path = str(tmp_path / "st")
    BlockStore.from_array(path, arr, 256)
    victim = os.path.join(path, "block_00001.bin")
    raw = bytearray(open(victim, "rb").read())
    raw[17] ^= 0xFF
    with open(victim, "wb") as fh:
        fh.write(raw)
    st = BlockStore.open(path)
    st.read_block(0)                               # intact block fine
    with pytest.raises(BlockStoreCorruptError, match="checksum"):
        st.read_block(1)
    buf = np.empty((5, st.block_rows), np.uint8)
    with pytest.raises(BlockStoreCorruptError, match="checksum"):
        st.read_block(1, out=buf, verify=True)


def test_blockstore_corrupt_training_fails_loudly(tmp_path, monkeypatch):
    """End to end: a corrupted spill block must ABORT streamed training,
    not produce silently wrong trees."""
    _stream_env(monkeypatch)
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    b = lgb.Booster(params=dict(BASE), train_set=ds)
    b.update()
    store = ds._block_store
    victim = os.path.join(store.path, "block_00002.bin")
    raw = bytearray(open(victim, "rb").read())
    raw[3] ^= 0x40
    with open(victim, "wb") as fh:
        fh.write(raw)
    store._verified.discard(2)                     # fresh-process read
    with pytest.raises(BlockStoreCorruptError, match="checksum"):
        b.update()


def test_blockstore_unfinalized_refused(tmp_path):
    st = BlockStore.create(str(tmp_path / "st"), 100, 3, np.uint8, 64)
    st.append_rows(np.zeros((100, 3), np.uint8))
    with pytest.raises(BlockStoreCorruptError, match="manifest"):
        BlockStore.open(str(tmp_path / "st"))
    with pytest.raises(RuntimeError, match="not finalized"):
        st.read_block(0)
    st.finalize()
    assert BlockStore.open(str(tmp_path / "st")).num_blocks == 2


def test_block_pump_prefetch_matches_serial(tmp_path):
    rng = np.random.RandomState(4)
    arr = rng.randint(0, 255, (1000, 6), dtype=np.uint8)
    st = BlockStore.from_array(str(tmp_path / "st"), arr, 128)
    a = [(i, s, r, np.asarray(blk))
         for (i, s, r, blk) in BlockPump(st, prefetch=True)]
    b = [(i, s, r, np.asarray(blk))
         for (i, s, r, blk) in BlockPump(st, prefetch=False)]
    assert [x[:3] for x in a] == [x[:3] for x in b]
    for (_, _, _, xa), (_, _, _, xb) in zip(a, b):
        np.testing.assert_array_equal(xa, xb)


# ----------------------------------------------------------- planner

def test_plan_stream_resident_when_both_fit():
    p = plan_stream(rows=10_000, features=8, num_bins=64,
                    device_budget_bytes=1 << 33, host_budget_bytes=1 << 33)
    assert not p.stream and p.feasible
    assert p.resident_device_ok and p.resident_host_ok
    assert p.reason == "resident fits both budgets"


def test_plan_stream_elects_on_device_budget():
    p = plan_stream(rows=50_000_000, features=28, num_bins=64,
                    device_budget_bytes=3 << 30,
                    host_budget_bytes=1 << 40)
    assert p.stream and not p.resident_device_ok and p.resident_host_ok
    assert "device" in p.reason
    assert p.block_rows > 0 and p.num_blocks >= 2
    assert p.predicted_device_peak_bytes <= p.device_budget_bytes


def test_plan_stream_elects_on_host_budget():
    p = plan_stream(rows=50_000_000, features=28, num_bins=64,
                    device_budget_bytes=1 << 40,
                    host_budget_bytes=2 << 30)
    assert p.stream and p.resident_device_ok and not p.resident_host_ok
    assert "host" in p.reason
    assert p.predicted_host_peak_bytes <= p.host_budget_bytes


def test_plan_stream_infeasible_verdict():
    p = plan_stream(rows=1_000_000_000, features=28, num_bins=64,
                    device_budget_bytes=1 << 26, host_budget_bytes=1 << 26)
    assert p.stream and not p.feasible


def test_plan_stream_env_overrides(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_STREAM", "0")
    p = plan_stream(rows=50_000_000, features=28, num_bins=64,
                    device_budget_bytes=1 << 28, host_budget_bytes=1 << 28)
    assert not p.stream and "disabled" in p.reason
    monkeypatch.setenv("LGBM_TPU_STREAM", "1")
    monkeypatch.setenv("LGBM_TPU_STREAM_BLOCK_ROWS", "4096")
    p = plan_stream(rows=100_000, features=8, num_bins=64,
                    device_budget_bytes=1 << 33, host_budget_bytes=1 << 33)
    assert p.stream and p.block_rows == 4096 and p.num_blocks == 25


def test_predict_host_peak_streaming_beats_resident():
    res = predict_host_peak_bytes(100_000_000, 28, 1)[0]
    stream = predict_host_peak_bytes(100_000_000, 28, 1, 1 << 20)[0]
    # the O(n) per-row metadata (labels/weights) stays in both modes;
    # the matrix term itself drops to O(block)
    assert stream < res / 4
    # and scales with the block, not the rows
    small = predict_host_peak_bytes(100_000_000, 28, 1, 1 << 16)[0]
    assert small < stream


def test_stream_plan_in_manifest_summary_roundtrips():
    p = plan_stream(rows=1_000_000, features=8, num_bins=64,
                    device_budget_bytes=1 << 24, host_budget_bytes=1 << 40)
    s = p.summary()
    assert json.loads(json.dumps(s)) == s
