"""Device-side ingest: the fused bucketize+pack binning kernel.

Training (histogram families, the fused megakernel) and inference
(predict_kernels.py) both run on kernels; this module moves the last
unkernelized hot path — the second binning pass of ``Dataset.construct``
— onto the accelerator.  One Pallas kernel owns one row tile: the
per-feature bin-boundary tables stay VMEM-resident across grid steps
(their block index never moves, the predict-kernel trick), each f32 row
tile is bucketized with a vectorized searchsorted-equivalent, and the
EFB group fold packs the per-feature bins straight into the [tile, G]
output block — raw floats cross HBM once and the binned matrix comes
back, nothing in between.

Bit-parity contract (tests/test_ingest.py): the
device matrix is BYTE-identical to the host ``BinMapper.value_to_bin``
+ ``Dataset._bin_block`` path.  Three constructions make that exact
rather than approximate:

- **directed-rounded boundaries**: the host compares the widened-f64
  value against f64 upper bounds (``searchsorted(ub, v, "left")`` ==
  count of ``ub < v``).  For f32 inputs, ``ub < v`` is equivalent to
  ``round_toward_neg_inf_f32(ub) < v`` — there is no f32 strictly
  between a bound and its round-down — so the kernel compares in pure
  f32 against a pre-rounded table and loses nothing.  Consequence: the
  device path applies ONLY to dense float32 raw input; float64 and
  sparse inputs take the host oracle.
- **the host fold, verbatim**: bundle members fold in ascending
  used-feature order with ``col = where(bin != 0, start + bin - 1,
  col)``; the host's singleton special case (``feat_start == 1``,
  group size 1) is the same fold evaluated from zero, so one rule
  covers every group byte-for-byte, including the reference's
  observable last-writer-wins conflict semantics.
- **categorical truncation**: ``int(v)`` truncates toward zero
  (``jnp.fix``), NaN and >= 2^31 magnitudes map to "no category"
  (the host's int64 cast of such values can never match an int32
  category code either), and a match requires ``iv >= 0`` exactly as
  the host lookup does.

The host NumPy path is the never-deleted fallback AND the parity
oracle: before the first committed device block of a dataset, a salted
probe (first rows + zeros / NaN / sign extremes / non-category codes)
is binned both ways and compared byte-for-byte; a mismatch demotes that
dataset to the host path at warning level (``fused_predict_verified``
precedent: never wrong bytes).  The probe is numeric only — a compile,
lowering or runtime error of the kernel propagates, it never elects the
host.  ``LGBM_TPU_INGEST_KERNEL`` pins the arm for bisection; off
accelerators the kernel interprets as the same jnp math, so CPU parity
tests are meaningful, and tier-1 also compiles it for the chip at the
tile the planner elects (tests/test_chip_compile.py).
"""

from __future__ import annotations

import threading
import time
from typing import NamedTuple, Optional, Tuple

import numpy as np

INGEST_VARIANTS = ("kernel", "host")

# |v| >= 2^31 cannot equal any int32 categorical code; the host's int64
# cast of such a value cannot match one either, so "no category" is
# parity-exact (f32 has no integers between 2^31 and this boundary)
_CAT_HUGE = np.float32(2147483648.0)


class IngestUnsupported(ValueError):
    """This dataset's binning recipe cannot run on device (the caller
    falls back to the host oracle — never an error for users)."""


def _interp(interpret):
    """Pallas interpret-mode default (the ops/fused.py convention)."""
    if interpret is None:
        from .histogram import on_accelerator
        return not on_accelerator()
    return bool(interpret)


class FeatureSpec(NamedTuple):
    """Static per-used-feature binning recipe (Python ints — closed over
    by the kernel factory, so they are trace-time constants)."""

    column: int          # raw matrix column
    group: int           # EFB output column
    start: int           # feat_start offset inside the merged column
    is_cat: bool
    num_bin: int
    row: int             # row in the bounds (numerical) / cats table
    nan_as_last: bool    # numerical MissingType.NAN: NaN -> num_bin - 1


class IngestTables(NamedTuple):
    """Everything the kernel needs, host-side: the directed-rounded f32
    boundary table, the int32 category-code table, and the per-feature
    static specs."""

    specs: Tuple[FeatureSpec, ...]
    bounds: np.ndarray       # f32 [max(Fnum,1), Bmax], +inf padded
    cats: np.ndarray         # i32 [max(Fcat,1), Cmax], -2 padded
    num_features: int        # raw matrix width the kernel consumes
    num_groups: int
    out_dtype: np.dtype      # uint8 | uint16 (the group dtype)


def round_bounds_f32(ub: np.ndarray) -> np.ndarray:
    """f64 upper bounds -> the largest f32 <= each bound (round toward
    -inf), the table the kernel's pure-f32 compare is exact against."""
    ub = np.asarray(ub, np.float64)
    with np.errstate(over="ignore"):     # f32-overflow -> inf IS the
        ub32 = ub.astype(np.float32)     # round-up case handled below
        over = ub32.astype(np.float64) > ub      # round-to-nearest went UP
        ub32[over] = np.nextafter(ub32[over], np.float32(-np.inf))
    return ub32


def build_ingest_tables(ds) -> IngestTables:
    """Compile a constructed-or-fitting Dataset's bin mappers + EFB
    layout into device tables.  Raises ``IngestUnsupported`` when the
    recipe cannot be represented (categorical codes outside int32)."""
    from ..binning import BinType, MissingType

    specs = []
    brows = []
    crows = []
    for j, f in enumerate(ds.used_features):
        m = ds.bin_mappers[f]
        g = int(ds.feat_group[j])
        start = int(ds.feat_start[j])
        if m.bin_type == BinType.CATEGORICAL:
            cats = np.asarray(m.bin_2_categorical, dtype=np.int64)
            if cats.size and (cats.max() >= 2 ** 31
                              or cats.min() < -2 ** 31):
                raise IngestUnsupported(
                    f"feature {f}: categorical codes exceed int32")
            specs.append(FeatureSpec(int(f), g, start, True,
                                     int(m.num_bin), len(crows), False))
            crows.append(cats.astype(np.int32))
        else:
            r = m.num_bin - 1
            if m.missing_type == MissingType.NAN:
                r -= 1
            specs.append(FeatureSpec(
                int(f), g, start, False, int(m.num_bin), len(brows),
                m.missing_type == MissingType.NAN))
            brows.append(round_bounds_f32(
                np.asarray(m.bin_upper_bound)[:max(r, 0)]))
    bmax = max([len(b) for b in brows] + [1])
    cmax = max([len(c) for c in crows] + [1])
    bounds = np.full((max(len(brows), 1), bmax), np.inf, np.float32)
    for i, b in enumerate(brows):
        bounds[i, :len(b)] = b
    cats_t = np.full((max(len(crows), 1), cmax), -2, np.int32)
    for i, c in enumerate(crows):
        cats_t[i, :len(c)] = c
    dtype = np.dtype(np.uint8 if ds.max_group_bin <= 256 else np.uint16)
    return IngestTables(tuple(specs), bounds, cats_t,
                        int(ds.num_total_features), int(ds.num_groups),
                        dtype)


# ----------------------------------------------------------------------
# the fused bucketize+pack kernel
# ----------------------------------------------------------------------

def _ingest_kernel(specs, num_groups, cats_width):
    """Kernel body factory.  One grid step owns one row tile: bucketize
    every used feature of the [tile, F] f32 block against the resident
    boundary/category tables, fold each EFB group's members in the
    host's exact order, and write the [tile, G] packed block.  The
    feature loop is unrolled at trace time (``specs`` are Python
    constants), so each feature compiles to a broadcast compare +
    row-sum — the vectorized searchsorted."""
    import jax.numpy as jnp

    def kernel(x_ref, bounds_ref, cats_ref, out_ref):
        X = x_ref[...]                              # [tile, F] f32
        tile = X.shape[0]
        carange = jnp.arange(cats_width, dtype=jnp.int32)
        cols = [jnp.zeros((tile,), jnp.int32) for _ in range(num_groups)]
        for s in specs:
            v = X[:, s.column]
            nan = v != v
            if s.is_cat:
                nan_bin = s.num_bin - 1
                miss = nan | (jnp.abs(v) >= _CAT_HUGE)
                iv = jnp.fix(jnp.where(miss, jnp.float32(-1.0), v)
                             ).astype(jnp.int32)
                hit = ((iv[:, None] == cats_ref[s.row, :][None, :])
                       & (iv[:, None] >= 0))
                # at most one code matches: the sum IS the select
                bins = jnp.sum(
                    jnp.where(hit, carange[None, :] - nan_bin, 0),
                    axis=1) + nan_bin
            else:
                fz = jnp.where(nan, jnp.float32(0.0), v)
                bins = jnp.sum(
                    (bounds_ref[s.row, :][None, :] < fz[:, None]
                     ).astype(jnp.int32), axis=1)
                if s.nan_as_last:
                    bins = jnp.where(nan, s.num_bin - 1, bins)
            # the host fold, verbatim (singletons are the start==1 case)
            cols[s.group] = jnp.where(bins != 0, s.start + bins - 1,
                                      cols[s.group])
        out_ref[...] = jnp.stack(cols, axis=1)

    return kernel


class DeviceBinner:
    """A compiled bucketize+pack program for one dataset's tables.

    ``__call__`` takes a [rows, F] f32 block (host or device) and
    returns the [rows, G] binned block in the group dtype, on device.
    Rows pad up to whole tiles and slice back off; jit caches one
    program per padded shape (full chunks share one, the ragged tail
    adds one)."""

    def __init__(self, tables: IngestTables, tile_rows: int = 1024,
                 interpret=None):
        import jax
        import jax.numpy as jnp

        self.tables = tables
        self.tile_rows = max(int(tile_rows), 8)
        self.interpret = _interp(interpret)
        self._bounds = jnp.asarray(tables.bounds)
        self._cats = jnp.asarray(tables.cats)
        self._kernel = _ingest_kernel(tables.specs, tables.num_groups,
                                      tables.cats.shape[1])
        self._call = jax.jit(self._run)

    def _run(self, X):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        n, F = X.shape
        G = int(self.tables.num_groups)
        tile = min(self.tile_rows, max(int(n), 8))
        ntiles = max(-(-n // tile), 1)
        npad = ntiles * tile
        if npad != n:
            X = jnp.pad(X, ((0, npad - n), (0, 0)))

        def _full(a):
            return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

        out = pl.pallas_call(
            self._kernel, grid=(ntiles,),
            in_specs=[pl.BlockSpec((tile, F), lambda i: (i, 0)),
                      _full(self._bounds), _full(self._cats)],
            out_specs=pl.BlockSpec((tile, G), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((npad, G), jnp.int32),
            interpret=self.interpret,
            name="lgbm_ingest_bin")(X, self._bounds, self._cats)
        return out[:n].astype(self.tables.out_dtype)

    def __call__(self, X):
        import jax.numpy as jnp
        if X.shape[1] != self.tables.num_features:
            raise ValueError(
                f"ingest kernel built for {self.tables.num_features} "
                f"features, got a block of {X.shape[1]}")
        return self._call(jnp.asarray(X, jnp.float32))


# ----------------------------------------------------------------------
# parity probe + the ingest story
# ----------------------------------------------------------------------

def salt_rows(width: int, like: Optional[np.ndarray] = None) -> np.ndarray:
    """Edge-case rows every parity check must cover: zeros, all-NaN,
    sign extremes, non-integer positives, negative and huge codes."""
    salt = np.zeros((6, width), np.float32)
    salt[1, :] = np.nan
    salt[2, :] = -np.float32(1e30)
    salt[3, :] = np.float32(1e30)
    salt[4, :] = np.float32(2.5)
    salt[5, :] = np.float32(-1.0)
    if like is not None and len(like):
        # a real row with alternating NaN: missing routing inside data
        extra = np.array(like[:1], np.float32)
        extra[0, ::2] = np.nan
        salt = np.concatenate([salt, extra])
    return salt


def parity_probe(binner: DeviceBinner, ds, raw_head: np.ndarray) -> bool:
    """Byte-compare device vs host binning on a salted head sample.
    True == the kernel may commit blocks for this dataset."""
    probe = np.concatenate([
        np.asarray(raw_head[:512], np.float32),
        salt_rows(raw_head.shape[1], raw_head)])
    ref = np.zeros((probe.shape[0], ds.num_groups),
                   binner.tables.out_dtype)
    with np.errstate(invalid="ignore"):   # host int64 cast of the salted
        ds._bin_block(probe.astype(np.float64), None, ref)  # 1e30 rows
    got = np.asarray(binner(probe))
    return bool(np.array_equal(ref, got))


# last construct's election + outcome, for obs/diagnose.py's
# input-bound verdict
_INGEST_LAST: dict = {}
_INGEST_LAST_LOCK = threading.Lock()


def record_ingest_story(**kw) -> None:
    with _INGEST_LAST_LOCK:
        _INGEST_LAST.clear()
        _INGEST_LAST.update(kw, ts=time.time())
    # which arm binned the last dataset, and who chose it (the ingest
    # twin of train_hist_method / train_hist_elected_by)
    from ..obs.metrics import global_registry
    global_registry.gauge("ingest_variant").set(kw.get("path", ""))
    global_registry.gauge("ingest_elected_by").set(kw.get("elected_by", ""))


def ingest_last() -> dict:
    with _INGEST_LAST_LOCK:
        return dict(_INGEST_LAST)


def demote(reason: str, elected_by: str, warn: bool = True) -> None:
    """Record a host fallback and say why (the bisect gate's evidence)."""
    record_ingest_story(path="host", reason=reason, elected_by=elected_by)
    if warn:
        from ..utils.log import log_warning
        log_warning(f"device ingest demoted to host binning: {reason}")
