"""Device-ingest parity matrix + planner election (ops/ingest.py).

The bucketize+pack kernel's one invariant is BYTE identity with the
host ``BinMapper.value_to_bin`` + ``_bin_block`` path — across missing
types, categorical lookup, EFB bundles, uint8/uint16 group dtypes and
ragged last blocks.  Off-accelerator the kernel interprets as the same
jnp math, so these tests pin ``LGBM_TPU_INGEST_KERNEL=kernel`` (the
bisect gate) to force the device arm on tiny CPU-sized data; the
planner tests exercise the analytic election, the ledger budget arm,
and the env pins directly.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.data.stream import IngestPump
from lightgbm_tpu.ops import ingest as ING
from lightgbm_tpu.ops import planner as P

GB = 1 << 30


def _raw(rows=3000, features=8, seed=0, categorical=True):
    """Every binning recipe at once: a categorical column, NaN routing,
    two mostly-zero columns (EFB actually bundles)."""
    rng = np.random.RandomState(seed)
    X = (rng.rand(rows, features) * 10.0).astype(np.float32)
    if categorical:
        X[:, 0] = rng.randint(0, 12, size=rows)
    X[rng.rand(rows) < 0.1, 2] = np.nan
    X[rng.rand(rows) < 0.7, 3] = 0.0
    X[rng.rand(rows) < 0.8, 5] = 0.0
    y = (rng.rand(rows) > 0.5).astype(np.float64)
    return X, y


def _dataset(X, y, max_bin=63, categorical=True):
    ds = lgb.Dataset(X, label=y,
                     params={"verbosity": -1, "max_bin": max_bin},
                     categorical_feature=[0] if categorical else None)
    ds.construct()
    return ds


def _host_ref(ds, X):
    ref = np.zeros((X.shape[0], ds.num_groups), ds.binned.dtype)
    with np.errstate(invalid="ignore"):
        ds._bin_block(np.asarray(X, np.float64), None, ref)
    return ref


# ---------------------------------------------------------------------
# byte identity
# ---------------------------------------------------------------------

@pytest.mark.parametrize("max_bin,categorical", [
    (63, True),        # uint8 groups + categorical + NaN + zero-as-bin
    (63, False),       # uint8, numerical only
    (1000, True),      # >256 bins -> uint16 groups
])
def test_construct_byte_identity(monkeypatch, max_bin, categorical):
    """The whole construct path: env-pinned kernel binning produces the
    exact bytes host binning does, salted rows included."""
    X, y = _raw()
    host = _dataset(X.copy(), y, max_bin, categorical)
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    dev = _dataset(X.copy(), y, max_bin, categorical)
    assert dev.binned.dtype == host.binned.dtype
    assert np.array_equal(dev.binned, host.binned)
    story = ING.ingest_last()
    assert story.get("path") == "kernel"
    assert story.get("elected_by") == "env"
    assert story.get("parity_probe") is True


def test_binner_salted_block_parity(monkeypatch):
    """DeviceBinner directly vs the host oracle on the salt rows (all
    edge cases: zeros, all-NaN, +-1e30, non-integers, negative codes)."""
    X, y = _raw()
    ds = _dataset(X, y)
    tables = ING.build_ingest_tables(ds)
    binner = ING.DeviceBinner(tables, tile_rows=256)
    probe = np.concatenate([X[:300], ING.salt_rows(X.shape[1], X)])
    assert np.array_equal(np.asarray(binner(probe)), _host_ref(ds, probe))


def test_ragged_last_tile_and_block(monkeypatch):
    """Rows that are a multiple of neither the VMEM tile nor the pump
    chunk: padding rows must never leak into the committed bytes."""
    X, y = _raw(rows=2000 + 137)
    host = _dataset(X.copy(), y)
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    monkeypatch.setenv("LGBM_TPU_INGEST_CHUNK", "700")   # 4 blocks, ragged
    dev = _dataset(X.copy(), y)
    assert np.array_equal(dev.binned, host.binned)


def test_float64_raw_stays_on_host(monkeypatch):
    """The directed-rounded boundary table is exact only against f32
    input; f64 raw must take the host oracle even when env-pinned."""
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    X, y = _raw()
    ds = _dataset(X.astype(np.float64), y)
    out = np.zeros((100, ds.num_groups), ds.binned.dtype)
    assert not ds._maybe_device_bin(X[:100].astype(np.float64), None, out)


def test_parity_failure_demotes_for_good(monkeypatch, capsys):
    """A diverging probe must demote the dataset permanently (never
    wrong bytes), leave the host result intact, and say why — at warning
    level, with the gauges naming what ran and who decided."""
    from lightgbm_tpu.obs.metrics import global_registry
    from lightgbm_tpu.utils import log
    X, y = _raw()
    host = _dataset(X.copy(), y)
    monkeypatch.setattr(log, "_current_level", 0)     # warnings on
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    monkeypatch.setattr(ING, "parity_probe", lambda *a, **k: False)
    dev = _dataset(X.copy(), y)
    assert "device ingest demoted to host binning" in capsys.readouterr().err
    assert np.array_equal(dev.binned, host.binned)
    assert dev._ingest == {}                  # cached demotion
    story = ING.ingest_last()
    assert story.get("path") == "host"
    assert "parity" in story.get("reason", "")
    gauges = global_registry.to_dict()["gauges"]
    assert gauges["ingest_variant"] == "host"
    assert gauges["ingest_elected_by"] == "parity_probe"


def test_kernel_exception_propagates(monkeypatch):
    """A kernel error is not a verdict: it never elects the host path —
    a compile or runtime failure of the elected kernel reaches the
    caller."""
    X, y = _raw()
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")

    def boom(self, X):
        raise RuntimeError("Mosaic failed to compile TPU kernel")
    monkeypatch.setattr(ING.DeviceBinner, "__call__", boom)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        _dataset(X.copy(), y)


def test_int32_overflow_categorical_unsupported():
    """Category codes outside int32 cannot ride the device tables."""
    rng = np.random.RandomState(0)
    X = rng.rand(500, 3).astype(np.float64) * 10
    X[:, 0] = rng.choice([0.0, 1.0, 3.0e9], size=500)
    ds = _dataset(X, (rng.rand(500) > 0.5).astype(np.float64))
    with pytest.raises(ING.IngestUnsupported):
        ING.build_ingest_tables(ds)


# ---------------------------------------------------------------------
# directed rounding
# ---------------------------------------------------------------------

def test_round_bounds_f32_is_largest_f32_below():
    rng = np.random.RandomState(1)
    ub = np.concatenate([
        rng.randn(500) * 1e3, rng.randn(500) * 1e-3,
        [0.0, -0.0, 1e300, -1e300, np.inf, -np.inf]])
    r = ING.round_bounds_f32(ub)
    assert r.dtype == np.float32
    assert np.all(r.astype(np.float64) <= ub)          # never above
    with np.errstate(over="ignore"):
        up = np.nextafter(r, np.float32(np.inf)).astype(np.float64)
    finite = np.isfinite(ub)
    assert np.all(up[finite] > ub[finite])             # largest such f32
    assert np.isposinf(r[np.isposinf(ub)]).all()
    assert np.isneginf(r[np.isneginf(ub)]).all()


# ---------------------------------------------------------------------
# the pump
# ---------------------------------------------------------------------

def test_ingest_pump_pinned_ascending_order():
    """Resume safety: chunks arrive in index order with exact slices,
    ragged tail included, prefetched or not."""
    X = np.arange(1037 * 3, dtype=np.float32).reshape(1037, 3)
    for prefetch in (True, False):
        seen = []
        for i, start, rows, chunk in IngestPump(X, 100,
                                                prefetch=prefetch):
            seen.append(i)
            assert start == i * 100
            assert np.array_equal(np.asarray(chunk),
                                  X[start:start + rows])
        assert seen == list(range(11))


def test_ingest_pump_reader_error_surfaces():
    class Bad:
        shape = (500, 2)

        def __getitem__(self, sl):
            raise ValueError("torn source")
    with pytest.raises(ValueError, match="torn source"):
        for _ in IngestPump(Bad(), 100):
            pass


# ---------------------------------------------------------------------
# planner election
# ---------------------------------------------------------------------

def test_chunk_election_under_tight_ledger(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_INGEST_CHUNK", raising=False)
    monkeypatch.delenv("LGBM_TPU_INGEST_KERNEL", raising=False)
    tight = P.ResidencyLedger(limit_bytes=64 << 20)
    roomy = P.ResidencyLedger(limit_bytes=16 * GB)
    kw = dict(rows=50_000_000, features=28, num_groups=28, item_bytes=1)
    small = P.plan_ingest(ledger=tight, **kw)
    big = P.plan_ingest(ledger=roomy, **kw)
    assert small.limit_source == "ledger"
    assert small.chunk_bytes <= small.budget_bytes
    assert small.chunk_rows < big.chunk_rows
    assert small.chunk_rows >= P.MIN_BUCKET_ROWS
    assert big.chunk_rows <= P.MAX_INGEST_CHUNK_ROWS
    # chunks are ladder rungs: nearby shapes share one compiled program
    assert small.chunk_rows == P.bucket_rows(small.chunk_rows)


def test_chunk_env_pin_wins(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_INGEST_CHUNK", "8192")
    plan = P.plan_ingest(rows=1_000_000, features=28, num_groups=28)
    assert plan.chunk_rows == 8192


def test_small_datasets_never_elect_chunks_past_rows(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_INGEST_CHUNK", raising=False)
    plan = P.plan_ingest(rows=10_000, features=28, num_groups=28)
    assert plan.chunk_rows <= P.bucket_rows(10_000)


def test_variant_env_gate(monkeypatch):
    kw = dict(rows=1_000_000, features=28, num_groups=28)
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "host")
    p1 = P.plan_ingest(**kw)
    assert (p1.variant, p1.elected_by) == ("host", "env")
    assert p1.tile_rows == 0
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    p2 = P.plan_ingest(**kw)
    assert (p2.variant, p2.elected_by) == ("kernel", "env")
    assert p2.tile_rows in P.INGEST_TILES


def test_analytic_election_host_off_accelerator(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_INGEST_KERNEL", raising=False)
    off = P.plan_ingest(rows=1_000_000, features=28, num_groups=28,
                        accel=False)
    assert (off.variant, off.elected_by) == ("host", "analytic")
    on = P.plan_ingest(rows=1_000_000, features=28, num_groups=28,
                       accel=True)
    assert (on.variant, on.elected_by) == ("kernel", "analytic")
    wide = P.plan_ingest(rows=1_000_000,
                         features=P.MAX_INGEST_KERNEL_FEATURES + 1,
                         num_groups=28, accel=True)
    assert wide.variant == "host"     # unrolled kernel stops paying


def test_ingest_vmem_model_monotone():
    a = P.ingest_vmem_bytes(28, 256, 64, 1, 28)
    b = P.ingest_vmem_bytes(28, 2048, 64, 1, 28)
    assert 0 < a < b
    assert P.plan_ingest(rows=1_000_000, features=28, num_groups=28,
                         accel=True, vmem_bytes=1 << 10).variant == "host"
