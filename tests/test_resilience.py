"""Fault-tolerant training: checkpoint bundles, bit-identical resume,
corruption fallback, atomic model writes (docs/RESILIENCE.md).

The core contract under test is the acceptance bar of PR 2: a run killed
after a checkpoint at iteration k and resumed via ``resume_from``
produces a model file BYTE-identical to the uninterrupted run — across
bagging, GOSS and DART configs — and a corrupted newest bundle is
detected (sha256 manifest) and skipped for the previous good one.
"""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import Dataset
from lightgbm_tpu.resilience import (CheckpointCorruptError,
                                     CheckpointManager,
                                     CheckpointNotFoundError,
                                     load_checkpoint, save_checkpoint)


def _data(seed=0, n=400, f=6):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    Xv = rng.rand(n // 2, f)
    yv = (Xv[:, 0] + Xv[:, 1] * Xv[:, 2] > 0.8).astype(np.float32)
    return X, y, Xv, yv


BASE = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
        "min_data_in_leaf": 5}


def _resume_parity(tmp_path, params, rounds=12, die_after=7, freq=3,
                   early_stopping_rounds=None):
    """Train full; train-and-die at ``die_after`` with bundles every
    ``freq``; resume from the bundle dir; compare final model bytes."""
    X, y, Xv, yv = _data()
    kw = dict(verbose_eval=False,
              early_stopping_rounds=early_stopping_rounds)

    er_full = {}
    full = lgb.train(params, Dataset(X, label=y), rounds,
                     valid_sets=[Dataset(Xv, label=yv)],
                     evals_result=er_full, **kw)
    full.save_model(str(tmp_path / "full.txt"))

    er_part = {}
    lgb.train(params, Dataset(X, label=y), die_after,
              valid_sets=[Dataset(Xv, label=yv)], evals_result=er_part,
              snapshot_freq=freq, snapshot_out=str(tmp_path / "part.txt"),
              **kw)

    er_res = {}
    res = lgb.train(params, Dataset(X, label=y), rounds,
                    valid_sets=[Dataset(Xv, label=yv)], evals_result=er_res,
                    resume_from=str(tmp_path / "part.txt.ckpt"), **kw)
    res.save_model(str(tmp_path / "res.txt"))

    a = (tmp_path / "full.txt").read_bytes()
    b = (tmp_path / "res.txt").read_bytes()
    assert a == b, "resumed model file is not byte-identical"
    assert full.best_iteration == res.best_iteration
    assert er_full == er_res, "resumed eval history diverged"
    return full, res


def test_resume_bit_identical_bagging(tmp_path):
    _resume_parity(tmp_path, {**BASE, "bagging_fraction": 0.7,
                              "bagging_freq": 2, "feature_fraction": 0.8})


def test_resume_bit_identical_dart(tmp_path):
    _resume_parity(tmp_path, {**BASE, "boosting": "dart", "drop_rate": 0.5})


def test_resume_bit_identical_dart_nonuniform(tmp_path):
    _resume_parity(tmp_path, {**BASE, "boosting": "dart", "drop_rate": 0.5,
                              "uniform_drop": False})


def test_resume_bit_identical_goss(tmp_path):
    _resume_parity(tmp_path, {**BASE, "boosting": "goss",
                              "learning_rate": 0.3})


def test_resume_bit_identical_rf(tmp_path):
    _resume_parity(tmp_path, {**BASE, "boosting": "rf",
                              "bagging_fraction": 0.7, "bagging_freq": 1})


def test_resume_bit_identical_cegb(tmp_path):
    """CEGB carries cross-iteration device state (used features + lazy
    row coverage); already-charged penalties must not re-charge after
    resume."""
    _resume_parity(tmp_path, {
        **BASE, "cegb_tradeoff": 0.5, "cegb_penalty_split": 0.1,
        "cegb_penalty_feature_coupled": [0.4] * 6,
        "cegb_penalty_feature_lazy": [0.3] * 6})


def test_resume_early_stopping_state(tmp_path):
    """The patience window carries across the kill: the resumed run must
    stop at the same iteration with the same best score."""
    rng = np.random.RandomState(3)
    X = rng.rand(400, 6)
    y = (X[:, 0] > 0.5).astype(np.float32)
    Xv = rng.rand(150, 6)
    yv = (rng.rand(150) > 0.5).astype(np.float32)   # noise: stops early
    kw = dict(verbose_eval=False, early_stopping_rounds=4)

    full = lgb.train(BASE, Dataset(X, label=y), 40,
                     valid_sets=[Dataset(Xv, label=yv)], **kw)
    full.save_model(str(tmp_path / "full.txt"))
    assert full.best_iteration < 40, "test needs early stopping to fire"

    lgb.train(BASE, Dataset(X, label=y), 4,
              valid_sets=[Dataset(Xv, label=yv)],
              snapshot_freq=2, snapshot_out=str(tmp_path / "p.txt"), **kw)
    res = lgb.train(BASE, Dataset(X, label=y), 40,
                    valid_sets=[Dataset(Xv, label=yv)],
                    resume_from=str(tmp_path / "p.txt.ckpt"), **kw)
    res.save_model(str(tmp_path / "res.txt"))
    assert (tmp_path / "full.txt").read_bytes() == \
        (tmp_path / "res.txt").read_bytes()
    assert res.best_iteration == full.best_iteration
    assert res.best_score == full.best_score


def test_corrupted_newest_bundle_falls_back(tmp_path):
    """Bit-flip the newest bundle: it must be detected and skipped, and
    resume must continue from the previous verified one."""
    X, y, _, _ = _data()
    lgb.train(BASE, Dataset(X, label=y), 9, verbose_eval=False,
              snapshot_freq=3, snapshot_out=str(tmp_path / "m.txt"))
    d = tmp_path / "m.txt.ckpt"
    bundles = sorted(p for p in os.listdir(d) if p.endswith(".lgbckpt"))
    assert bundles == ["ckpt_iter_00000003.lgbckpt",
                       "ckpt_iter_00000006.lgbckpt",
                       "ckpt_iter_00000009.lgbckpt"]
    newest = d / bundles[-1]
    blob = bytearray(newest.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    newest.write_bytes(bytes(blob))

    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(str(newest))
    ck = CheckpointManager(str(d)).latest_verified()
    assert ck.iteration == 6

    res = lgb.train(BASE, Dataset(X, label=y), 9, verbose_eval=False,
                    resume_from=str(d))
    assert len(res.boosting.models) == 9


def test_truncated_bundle_detected(tmp_path):
    X, y, _, _ = _data()
    bst = lgb.train(BASE, Dataset(X, label=y), 3, verbose_eval=False)
    p = str(tmp_path / "one.lgbckpt")
    save_checkpoint(bst, p, iteration=3)
    blob = (tmp_path / "one.lgbckpt").read_bytes()
    (tmp_path / "one.lgbckpt").write_bytes(blob[:len(blob) // 2])
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(p)


def test_all_bundles_corrupt_raises_not_found(tmp_path):
    X, y, _, _ = _data()
    lgb.train(BASE, Dataset(X, label=y), 4, verbose_eval=False,
              snapshot_freq=2, snapshot_out=str(tmp_path / "m.txt"))
    d = tmp_path / "m.txt.ckpt"
    for name in os.listdir(d):
        if name.endswith(".lgbckpt"):
            (d / name).write_bytes(b"garbage")
    with pytest.raises(CheckpointNotFoundError):
        CheckpointManager(str(d)).latest_verified()


def test_retention_keeps_last_k(tmp_path):
    X, y, _, _ = _data()
    lgb.train(BASE, Dataset(X, label=y), 10, verbose_eval=False,
              snapshot_freq=2, snapshot_out=str(tmp_path / "m.txt"),
              snapshot_keep=2)
    d = tmp_path / "m.txt.ckpt"
    bundles = sorted(p for p in os.listdir(d) if p.endswith(".lgbckpt"))
    assert bundles == ["ckpt_iter_00000008.lgbckpt",
                       "ckpt_iter_00000010.lgbckpt"]


def test_resume_from_specific_bundle_file(tmp_path):
    X, y, _, _ = _data()
    lgb.train(BASE, Dataset(X, label=y), 6, verbose_eval=False,
              snapshot_freq=2, snapshot_out=str(tmp_path / "m.txt"))
    bundle = tmp_path / "m.txt.ckpt" / "ckpt_iter_00000004.lgbckpt"
    res = lgb.train(BASE, Dataset(X, label=y), 6, verbose_eval=False,
                    resume_from=str(bundle))
    assert len(res.boosting.models) == 6


def test_resume_missing_location_raises(tmp_path):
    X, y, _, _ = _data()
    with pytest.raises(CheckpointNotFoundError):
        lgb.train(BASE, Dataset(X, label=y), 3, verbose_eval=False,
                  resume_from=str(tmp_path / "nope"))


def test_bundle_model_txt_member_loads_standalone(tmp_path):
    """The model.txt member is a complete reference-format model."""
    X, y, _, _ = _data()
    bst = lgb.train(BASE, Dataset(X, label=y), 5, verbose_eval=False)
    p = str(tmp_path / "b.lgbckpt")
    save_checkpoint(bst, p, iteration=5)
    ck = load_checkpoint(p)
    loaded = lgb.Booster(model_str=ck.model_str)
    np.testing.assert_allclose(loaded.predict(X[:16]), bst.predict(X[:16]),
                               rtol=1e-6)


def test_save_model_atomic_creates_parent_dirs(tmp_path):
    """Satellite: snapshot_out / save_model into a nonexistent directory
    must work, and no temp sibling may linger."""
    X, y, _, _ = _data()
    bst = lgb.train(BASE, Dataset(X, label=y), 2, verbose_eval=False)
    target = tmp_path / "does" / "not" / "exist" / "model.txt"
    bst.save_model(str(target))
    assert target.is_file()
    siblings = os.listdir(target.parent)
    assert siblings == ["model.txt"], siblings
    reload = lgb.Booster(model_file=str(target))
    np.testing.assert_allclose(reload.predict(X[:8]), bst.predict(X[:8]),
                               rtol=1e-6)


def test_snapshot_out_into_new_dir(tmp_path):
    X, y, _, _ = _data()
    out = tmp_path / "fresh" / "dir" / "m.txt"
    lgb.train(BASE, Dataset(X, label=y), 4, verbose_eval=False,
              snapshot_freq=2, snapshot_out=str(out))
    assert (out.parent / "m.txt.ckpt" / "index.json").is_file()


# ---- quantized training: what every cell of the benchmark trains with ------

QUANT = {"use_quantized_grad": True, "num_grad_quant_bins": 4}
QUANT_CASES = {
    "binary-stochastic": {},
    "binary-nearest": {"stochastic_rounding": False},
    "binary-stochastic-renew": {"quant_train_renew_leaf": True},
    "binary-nearest-renew": {"stochastic_rounding": False,
                             "quant_train_renew_leaf": True},
    "lambdarank-stochastic": {"objective": "lambdarank"},
    "lambdarank-stochastic-renew": {"objective": "lambdarank",
                                    "quant_train_renew_leaf": True},
    "lambdarank-nearest-renew": {"objective": "lambdarank",
                                 "stochastic_rounding": False,
                                 "quant_train_renew_leaf": True},
    # the shape of the chip's path: the rounds grower, trees left on the
    # device until somebody reads ``models`` (a save does)
    "binary-stochastic-rounds-deferred": {"tpu_tree_growth": "rounds",
                                          "_defer": True},
}


def _quant_data(objective, n=1200, f=8):
    rng = np.random.RandomState(5)
    X = rng.rand(n, f).astype(np.float32)
    signal = X[:, 0] + X[:, 1] * X[:, 2] + 0.2 * rng.randn(n)
    if objective == "lambdarank":
        y = np.clip((signal * 2.5).astype(np.int32), 0, 4).astype(np.float32)
        return X, y, {"group": [40] * (n // 40)}
    return X, (signal > 0.8).astype(np.float32), {}


@pytest.mark.parametrize("case", sorted(QUANT_CASES))
def test_resume_bit_identical_quantized(tmp_path, monkeypatch, case):
    """4-level gradients: the per-round key the stochastic rounding draws
    from, the scales and the deferred trees all ride the bundle, so the
    resumed job's model text is byte-equal and its train score
    ``array_equal`` to the uninterrupted job's."""
    extra = dict(QUANT_CASES[case])
    if extra.pop("_defer", False):
        monkeypatch.setenv("LGBT_DEFER_HOST_TREES", "1")
    params = {**BASE, "num_leaves": 15, **QUANT, **extra}
    X, y, kw = _quant_data(params["objective"])
    rounds, die_after, freq = 11, 7, 5

    def train(n, **more):
        return lgb.train(params, Dataset(X, label=y, **kw), n,
                         verbose_eval=False, **more)
    full = train(rounds)
    train(die_after, snapshot_freq=freq,
          snapshot_out=str(tmp_path / "part.txt"))
    ck = CheckpointManager(str(tmp_path / "part.txt.ckpt")).latest_verified()
    assert ck.iteration == 5 and len(ck.boosting_state["models"]) == 5
    res = train(rounds, resume_from=str(tmp_path / "part.txt.ckpt"))
    assert res.model_to_string() == full.model_to_string()
    assert np.array_equal(np.asarray(res.boosting.train_score),
                          np.asarray(full.boosting.train_score))
    # it was quantized training that was resumed, not a fallback: the last
    # round's scales ride the bundle and are the uninterrupted job's
    scales = np.asarray(res.boosting._quant_scales)
    assert scales.any() and np.array_equal(
        scales, np.asarray(full.boosting._quant_scales))


# ---- the container: state.pkl stored, old bundles still load ----------------

def _bundle_members(path):
    import zipfile
    with zipfile.ZipFile(path) as zf:
        return {i.filename: i for i in zf.infolist()}


def test_state_is_stored_and_text_deflated(tmp_path):
    import zipfile
    X, y, _, _ = _data()
    bst = lgb.train(BASE, Dataset(X, label=y), 4, verbose_eval=False)
    p = str(tmp_path / "b.lgbckpt")
    save_checkpoint(bst, p, iteration=4)
    members = _bundle_members(p)
    assert members["state.pkl"].compress_type == zipfile.ZIP_STORED
    assert members["state.pkl"].compress_size == members["state.pkl"].file_size
    for name in ("manifest.json", "model.txt"):
        assert members[name].compress_type == zipfile.ZIP_DEFLATED
    ck = load_checkpoint(p)
    assert ck.iteration == 4 and ck.nbytes == os.path.getsize(p)
    assert ck.model_str == bst.model_to_string(num_iteration=-1)
    assert np.array_equal(ck.boosting_state["train_score"],
                          np.asarray(bst.boosting.train_score))


def test_bundle_with_deflated_state_still_loads(tmp_path):
    """A bundle written the old way (every member deflated) verifies and
    resumes: the format tag did not move."""
    import zipfile
    X, y, _, _ = _data()
    lgb.train(BASE, Dataset(X, label=y), 4, verbose_eval=False,
              snapshot_freq=4, snapshot_out=str(tmp_path / "m.txt"))
    new = tmp_path / "m.txt.ckpt" / "ckpt_iter_00000004.lgbckpt"
    with zipfile.ZipFile(new) as zf:
        parts = {n: zf.read(n) for n in zf.namelist()}
    with zipfile.ZipFile(new, "w", zipfile.ZIP_DEFLATED) as zf:
        for name, data in parts.items():
            zf.writestr(name, data)
    assert _bundle_members(new)["state.pkl"].compress_type \
        == zipfile.ZIP_DEFLATED
    ck = load_checkpoint(str(new))
    assert ck.iteration == 4 and ck.manifest["format"] == "lgbt-ckpt/1"
    full = lgb.train(BASE, Dataset(X, label=y), 7, verbose_eval=False)
    res = lgb.train(BASE, Dataset(X, label=y), 7, verbose_eval=False,
                    resume_from=str(tmp_path / "m.txt.ckpt"))
    assert res.model_to_string() == full.model_to_string()


def test_flipped_byte_in_stored_state_is_caught(tmp_path):
    """Stored bytes sit in the file as they are: one flipped inside
    ``state.pkl`` is caught by the member's sha256 (or by the zip's own
    CRC before it), never unpickled."""
    X, y, _, _ = _data()
    bst = lgb.train(BASE, Dataset(X, label=y), 3, verbose_eval=False)
    p = tmp_path / "one.lgbckpt"
    save_checkpoint(bst, str(p), iteration=3)
    info = _bundle_members(p)["state.pkl"]
    blob = bytearray(p.read_bytes())
    # the member's data follows its local header: name + 30 bytes
    at = info.header_offset + 30 + len("state.pkl") + info.file_size // 2
    blob[at] ^= 0x01
    p.write_bytes(bytes(blob))
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(str(p))


# ---- a resume in the process that wrote the bundle ----------------------------

def test_resume_in_the_same_process_keeps_no_dead_booster_alive(tmp_path):
    """The first call dies by an exception out of a callback; once its
    ``Booster`` is dropped, a second ``lgb.train(resume_from=)`` on the
    kept ``Dataset`` holds what the first held and no more: no array of
    the dead ``Booster`` stays alive, by bytes of ``jax.live_arrays()``."""
    import gc

    import jax

    class Killed(Exception):
        pass

    X, y, _ = _quant_data("binary")

    def live():
        # per-row arrays, which are what a Booster holds by the row count
        # (JAX keeps an 8-byte PRNG key of its own alive now and then)
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays() if a.size >= len(X))

    params = {**BASE, **QUANT}
    ds = Dataset(X, label=y, params=params)
    ds.construct()
    seen = {}

    def die_after_7(env):
        if env.iteration + 1 == 7:
            seen["first"] = live()
            seen["binned"] = env.model.boosting.binned.nbytes
            raise Killed()

    def note_at_7(env):
        if env.iteration + 1 == 7:
            seen["second"] = live()

    base = live()
    with pytest.raises(Killed):
        lgb.train(params, ds, 12, verbose_eval=False, snapshot_freq=5,
                  snapshot_out=str(tmp_path / "m.txt"),
                  callbacks=[die_after_7])
    # what is left is the kept Dataset's: the binned matrix on the device,
    # which the next Booster on that Dataset reads and does not copy
    assert live() - base == seen["binned"] < seen["first"] - base, \
        "the dead Booster's arrays are still alive"
    res = lgb.train(params, ds, 12, verbose_eval=False,
                    resume_from=str(tmp_path / "m.txt.ckpt"),
                    callbacks=[note_at_7])
    assert seen["second"] == seen["first"]
    assert len(res.boosting.models) == 12
    del res
    assert live() - base == seen["binned"]


# ---- what the benchmark's readers read of a save and a resume ----------------

def test_ring_records_and_counters_of_a_job(tmp_path):
    from lightgbm_tpu.obs.flight import global_flight
    from lightgbm_tpu.obs.metrics import global_registry

    def counters():
        c = global_registry.to_dict()["counters"]
        return [c.get(k, 0) for k in ("checkpoint_saves_total",
                                      "checkpoint_bytes_total",
                                      "checkpoint_resumes_total")]

    def ring(name, since):
        return [e for e in global_flight.ring_events()
                if e.get("ph") == "X" and e["name"] == name
                and e["ts"] >= since]

    X, y, _, _ = _data()
    before = counters()
    marks = [e["ts"] for e in global_flight.ring_events() if "ts" in e]
    since = max(marks) if marks else 0.0
    lgb.train(BASE, Dataset(X, label=y), 6, verbose_eval=False,
              snapshot_freq=3, snapshot_out=str(tmp_path / "m.txt"))
    lgb.train(BASE, Dataset(X, label=y), 8, verbose_eval=False,
              resume_from=str(tmp_path / "m.txt.ckpt"))
    sizes = [os.path.getsize(tmp_path / "m.txt.ckpt" / n)
             for n in ("ckpt_iter_00000003.lgbckpt",
                       "ckpt_iter_00000006.lgbckpt")]
    after = counters()
    assert after[0] - before[0] == 2
    assert after[1] - before[1] == sum(sizes)
    assert after[2] - before[2] == 1
    saves = ring("checkpoint.save", since)
    assert [e["args"]["it"] for e in saves] == [3, 6]
    assert [e["args"]["bytes"] for e in saves] == sizes
    for part in ("checkpoint.capture", "checkpoint.encode",
                 "checkpoint.write"):
        kids = ring(part, since)
        assert len(kids) == 2
        for kid, save in zip(kids, saves):
            assert kid["args"]["parent"] == "checkpoint.save"
            assert kid["args"]["it"] == save["args"]["it"]
            assert save["ts"] <= kid["ts"] \
                and kid["ts"] + kid["dur"] <= save["ts"] + save["dur"] + 1
    # the parts are the save: nothing of it lies outside them
    for save, parts in zip(saves, zip(*(ring(p, since) for p in (
            "checkpoint.capture", "checkpoint.encode",
            "checkpoint.write")))):
        assert sum(p["dur"] for p in parts) <= save["dur"]
        assert sum(p["dur"] for p in parts) >= 0.9 * save["dur"]
    resume, = ring("engine.resume", since)
    assert resume["args"]["it"] == 6 and resume["args"]["bytes"] == sizes[1]
    load, = ring("checkpoint.load", since)
    assert load["args"]["parent"] == "engine.resume"
    assert load["args"]["bytes"] == sizes[1]
    assert resume["dur"] >= load["dur"]


@pytest.mark.parametrize("kept", [False, True])
def test_resume_on_the_same_dataset_wants_it_kept(tmp_path, monkeypatch,
                                                  kept):
    """On an accelerator a default ``Dataset`` gives up its host matrix
    (and the right to build a second ``Booster``) once the first holds the
    device copy; ``LGBM_TPU_FREE_BINNED=1`` steers the CPU the same way.
    A job that is to be resumed on the same ``Dataset`` in the same process
    says ``free_raw_data=False``; the default one is refused by name."""
    monkeypatch.setenv("LGBM_TPU_FREE_BINNED", "1")
    X, y, _, _ = _data()
    ds = Dataset(X, label=y, free_raw_data=not kept)
    lgb.train(BASE, ds, 4, verbose_eval=False, snapshot_freq=2,
              snapshot_out=str(tmp_path / "m.txt"))
    if not kept:
        with pytest.raises(RuntimeError, match="free_raw_data=False"):
            lgb.train(BASE, ds, 6, verbose_eval=False,
                      resume_from=str(tmp_path / "m.txt.ckpt"))
        return
    res = lgb.train(BASE, ds, 6, verbose_eval=False,
                    resume_from=str(tmp_path / "m.txt.ckpt"))
    full = lgb.train(BASE, Dataset(X, label=y), 6, verbose_eval=False)
    assert res.model_to_string() == full.model_to_string()
