"""Dataset: binned feature matrix + metadata, host construction, device views.

TPU-native redesign of LightGBM's Dataset / DatasetLoader / Metadata
(reference: include/LightGBM/dataset.h:41,333, src/io/dataset_loader.cpp:167,
src/io/metadata.cpp).  The key inversion vs the reference: instead of
per-feature-group Bin objects with sparse/dense variants and 4-bit packing,
the binned matrix is ONE dense row-major uint8 (or uint16) array
``[num_data, num_features]`` that is transferred once to HBM; histograms are
then built on-device over the whole matrix (see ops/histogram.py).  Sparse
inputs are densified at bin time — after binning, "sparse" just means the
most-frequent bin repeats, which costs nothing on the MXU path.
"""

from __future__ import annotations

import io
import json
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from .binning import BinMapper, BinType, MissingType
from .obs.trace import span as _span

_BINARY_MAGIC = b"lgbm_tpu.dataset.v1\n"


def _as_2d(data) -> np.ndarray:
    """2-D view of the input WITHOUT materializing a float64 copy.

    Streaming construction (reference: the two-pass DatasetLoader never
    holds a dense double matrix either — SampleTextDataFromFile +
    ExtractFeaturesFromFile push row by row, dataset_loader.cpp:775,1101):
    binning walks one column at a time, so an 11M x 28 float32 input costs
    one float64 COLUMN of scratch (88 MB) instead of a 2.5 GB full copy.
    Non-float dtypes (ints, object) still need one up-front cast.
    """
    if hasattr(data, "values"):  # pandas
        data = data.values
    if isinstance(data, (list, tuple)) and data and all(
            isinstance(a, np.ndarray) for a in data):
        # list of row-chunk arrays (reference: list-of-numpy input,
        # basic.py __init_from_list_np2d)
        data = np.vstack([np.atleast_2d(a) for a in data])
    arr = np.asarray(data)
    if arr.ndim != 2:
        raise ValueError(f"data must be 2-D, got shape {arr.shape}")
    if arr.dtype not in (np.float32, np.float64):
        arr = arr.astype(np.float64)
    return arr


def _data_from_pandas(data, feature_name, categorical_feature,
                      pandas_categorical):
    """DataFrame -> float matrix with category columns as codes.

    reference: _data_from_pandas (python-package/lightgbm/basic.py:331):
    category-dtype columns map to their codes (-1/unseen -> NaN); the
    category VALUE lists (pandas_categorical) are recorded at train time
    and re-applied to valid/predict frames so codes align; 'auto'
    categorical_feature resolves to the NOT-ordered category columns
    (ordered categoricals stay ordinal/numeric).
    Returns (values, feature_name, categorical_feature, pandas_categorical).
    """
    if not (hasattr(data, "dtypes") and hasattr(data, "columns")):
        return data, feature_name, categorical_feature, pandas_categorical
    import pandas as pd
    if feature_name in ("auto", None):
        data = data.rename(columns=str)
    cat_cols = [str(c) for c in
                data.select_dtypes(include=["category"]).columns]
    cat_cols_not_ordered = [c for c in cat_cols
                            if not data[c].cat.ordered]
    if pandas_categorical is None:     # train dataset
        pandas_categorical = [list(data[c].cat.categories)
                              for c in cat_cols]
    else:
        if len(cat_cols) != len(pandas_categorical):
            raise ValueError(
                "train and valid dataset categorical_feature do not match.")
        for col, category in zip(cat_cols, pandas_categorical):
            if list(data[col].cat.categories) != list(category):
                data[col] = data[col].cat.set_categories(category)
    if cat_cols:
        data = data.copy()
        data[cat_cols] = (data[cat_cols]
                          .apply(lambda x: x.cat.codes)
                          .replace({-1: np.nan}))
    if categorical_feature is not None:
        if categorical_feature == "auto":
            categorical_feature = cat_cols_not_ordered
        else:
            categorical_feature = list(categorical_feature)
    if feature_name == "auto":
        feature_name = [str(c) for c in data.columns]
    values = data.values
    if values.dtype not in (np.float32, np.float64):
        values = values.astype(np.float32)
    return values, feature_name, categorical_feature, pandas_categorical


def _sample_indices(num_data: int, sample_cnt: int, seed: int) -> np.ndarray:
    if num_data <= sample_cnt:
        return np.arange(num_data)
    rng = np.random.RandomState(seed)
    return np.sort(rng.choice(num_data, size=sample_cnt, replace=False))


def _avoid_inf(value):
    """reference: Common::AvoidInf (utils/common.h:697-715), applied by
    Metadata::SetLabel/SetWeights/SetInitScore — NaN becomes 0 and
    infinities clamp to the type's sane maximum, so downstream math never
    sees NaN/Inf metadata."""
    a = np.asarray(value)
    if a.dtype.kind != "f":
        return a
    lim = 1e300 if a.dtype == np.float64 else np.finfo(a.dtype).max
    if np.isnan(a).any() or np.isinf(a).any():
        a = np.nan_to_num(a, nan=0.0, posinf=lim, neginf=-lim)
    return a


@dataclass
class Metadata:
    """Labels / weights / query boundaries / init scores.

    reference: include/LightGBM/dataset.h:41-249, src/io/metadata.cpp.
    """

    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    query_boundaries: Optional[np.ndarray] = None  # int32 [num_queries + 1]
    init_score: Optional[np.ndarray] = None

    def __setattr__(self, name, value):
        # every ingestion path (ctor, set_field, properties, binary load)
        # funnels through attribute assignment — sanitize centrally
        if name in ("label", "weight", "init_score") and value is not None:
            value = _avoid_inf(value)
        object.__setattr__(self, name, value)

    def set_group(self, group: Optional[Sequence[int]]) -> None:
        if group is None:
            self.query_boundaries = None
            return
        g = np.asarray(group, dtype=np.int64)
        self.query_boundaries = np.concatenate([[0], np.cumsum(g)]).astype(np.int32)

    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1

    def check(self, num_data: int) -> None:
        if self.label is not None and len(self.label) != num_data:
            raise ValueError(f"label length {len(self.label)} != num_data {num_data}")
        if self.weight is not None and len(self.weight) != num_data:
            raise ValueError("weight length mismatch")
        if self.query_boundaries is not None and self.query_boundaries[-1] != num_data:
            raise ValueError("sum of query group sizes != num_data")


class Dataset:
    """User-facing dataset; lazily constructed (binned) on first use.

    Mirrors the Python-side semantics of the reference's ``lightgbm.Dataset``
    (python-package/lightgbm/basic.py:730) with construction logic from
    DatasetLoader (src/io/dataset_loader.cpp:527 ConstructFromSampleData).
    """

    def __init__(
        self,
        data,
        label=None,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        init_score=None,
        silent: bool = False,
        feature_name="auto",
        categorical_feature="auto",
        params: Optional[dict] = None,
        free_raw_data: bool = True,
    ):
        # positional order mirrors the reference Dataset.__init__
        # (python-package/lightgbm/basic.py:730) — callers pass reference/
        # weight/group positionally; ``silent`` accepted for compatibility
        self.params = dict(params or {})
        self.raw_data = data
        self.reference = reference
        self.free_raw_data = free_raw_data
        self.metadata = Metadata()
        if label is not None:
            self.metadata.label = np.asarray(label, dtype=np.float32).reshape(-1)
        if weight is not None:
            self.metadata.weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        if group is not None:
            self.metadata.set_group(group)
        if init_score is not None:
            self.metadata.init_score = np.asarray(init_score, dtype=np.float64)
        self._feature_name_param = feature_name
        self._categorical_feature_param = categorical_feature
        self.pandas_categorical = None      # category values per cat column
        # filled by construct():
        self.constructed = False
        self.bin_mappers: List[BinMapper] = []         # per ORIGINAL feature
        self.used_features: List[int] = []             # original idx of non-trivial features
        self.binned: Optional[np.ndarray] = None       # [n, F_used] uint8/uint16
        self.feature_names: List[str] = []
        self.num_data = 0
        self.num_total_features = 0

    # -- construction --------------------------------------------------------

    def construct(self) -> "Dataset":
        if self.constructed:
            return self
        if getattr(self, "_streaming", False):
            # name the first gap so an out-of-order loader sees WHERE its
            # coverage broke, not just a count
            missing = np.flatnonzero(~self._pushed)
            first = int(missing[0]) if len(missing) else 0
            raise RuntimeError(
                f"streaming dataset load incomplete: "
                f"{int(self._pushed.sum())}/{self.num_data} rows pushed "
                f"(first unpushed row: {first})")
        from .utils.timer import global_timer
        with global_timer.section("Dataset::Construct"):
            return self._construct_inner()

    def _construct_inner(self) -> "Dataset":
        if self.raw_data is None:
            raise RuntimeError("cannot construct Dataset: raw data was freed")
        data = self.raw_data
        if hasattr(data, "dtypes") and hasattr(data, "columns"):
            # pandas: category columns -> codes with the category values
            # recorded (train) or re-applied (valid/aligned sets)
            pc_in = None
            if self.reference is not None:
                pc_in = getattr(self.reference.construct(),
                                "pandas_categorical", None)
            data, fn, cf, pc = _data_from_pandas(
                data, self._feature_name_param,
                self._categorical_feature_param, pc_in)
            self.pandas_categorical = pc
            if self._feature_name_param in ("auto", None) and fn:
                self.feature_names = list(fn)
            if self._categorical_feature_param in ("auto", None):
                self._categorical_auto_resolved = cf or []
        if isinstance(data, (str, os.PathLike)):
            # a saved binary cache routes to the binary loader, whatever
            # the filename (reference: DatasetLoader::LoadFromFile checks
            # the binary token first, dataset_loader.cpp:273); the sniff
            # uses the scheme-routed opener so gs://-style caches route too
            from .utils.file_io import open_file
            try:
                with open_file(str(data), "rb") as _fh:
                    is_bin = _fh.read(len(_BINARY_MAGIC)) == _BINARY_MAGIC
            except OSError:
                is_bin = False
            if is_bin:
                pre = self.metadata
                # file params win: the cache carries its construction
                # params and the Booster's param-change check must see
                # the TRUE old values
                loaded = Dataset.load_binary(str(data), params=None)
                keep = {"reference", "free_raw_data",
                        "_feature_name_param", "_categorical_feature_param"}
                if not self.free_raw_data:
                    # get_data() on a kept binary-file dataset returns the
                    # PATH (reference basic.py get_data semantics)
                    keep.add("raw_data")
                for k, v in loaded.__dict__.items():
                    if k not in keep:
                        self.__dict__[k] = v
                # self.params now holds the file's TRUE construction
                # params; the flag makes the Booster's param-change check
                # compare explicit caller params against them (reference
                # DatasetUpdateParamChecking on binary load — binned data
                # cannot be rebuilt from a cache)
                self._from_binary_cache = True
                if self.reference is not None:
                    # a cache used as a VALIDATION set must have been
                    # binned identically to the training set (reference:
                    # "Cannot add validation data, since it has different
                    # bin mappers with training data")
                    ref = self.reference
                    ref.construct()
                    aligned = (
                        len(ref.bin_mappers) == len(self.bin_mappers)
                        and ref.used_features == self.used_features
                        and np.array_equal(ref.feat_group, self.feat_group)
                        and np.array_equal(ref.feat_start, self.feat_start)
                        and all(a.to_dict() == b.to_dict()
                                for a, b in zip(ref.bin_mappers,
                                                self.bin_mappers)))
                    if not aligned:
                        from .config import LightGBMError
                        raise LightGBMError(
                            "Cannot add validation data, since it has "
                            "different bin mappers with training data")
                # fields handed to the ctor override the file's sidecars
                for f in ("label", "weight", "init_score",
                          "query_boundaries"):
                    v = getattr(pre, f, None)
                    if v is not None:
                        setattr(self.metadata, f, v)
                self.metadata.check(self.num_data)
                self.constructed = True
                return self
            from .io_utils import _param_bool
            if _param_bool(self.params, "two_round"):
                # two-pass streamed load: never holds the full float matrix
                # (reference: two_round config, dataset_loader.cpp:775,1101)
                from .io_utils import load_text_dataset_two_round
                load_text_dataset_two_round(str(data), self)
                return self
            from .io_utils import load_text_dataset
            data = load_text_dataset(str(data), self)
        if _is_sparse(data):
            raw = None
            sp = data.tocsc()
            self.num_data, self.num_total_features = sp.shape
        else:
            raw = _as_2d(data)
            sp = None
            self.num_data, self.num_total_features = raw.shape

        p = self.params
        sample_cnt = int(p.get("bin_construct_sample_cnt", 200000))
        seed = int(p.get("data_random_seed", 1))

        if self._feature_name_param == "auto" or self._feature_name_param is None:
            if hasattr(self.raw_data, "columns"):
                self.feature_names = [str(c) for c in self.raw_data.columns]
            else:
                self.feature_names = [f"Column_{i}" for i in range(self.num_total_features)]
        else:
            self.feature_names = list(self._feature_name_param)

        categorical = self._resolve_categorical()

        if self.reference is not None:
            # validation set: reuse the reference's bin mappers
            # (reference: DatasetLoader::LoadFromFileAlignWithOtherDataset,
            # src/io/dataset_loader.cpp:229)
            ref = self.reference.construct()
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.feature_names = ref.feature_names
            # identical EFB layout so valid sets bin into the same columns
            self.feat_group = ref.feat_group
            self.feat_start = ref.feat_start
            self.num_groups = ref.num_groups
            self._group_size = ref._group_size
            self.group_num_bin = ref.group_num_bin
            self.max_group_bin = ref.max_group_bin
        else:
            # bin boundaries and EFB groups from the row sample (host)
            with _span("ingest.edges", ring=True, rows=self.num_data,
                       sample=sample_cnt) as edges:
                sample_idx = _sample_indices(self.num_data, sample_cnt,
                                             seed)
                cat_s = self._fit_bin_mappers(raw, sp, sample_idx,
                                              categorical)
                if categorical:
                    # the categorical columns' part (count-sorted codes)
                    edges.set(categorical=len(categorical), cat_s=cat_s)

        # second pass: bin every row into the per-GROUP merged columns —
        # on device when plan_ingest elects the bucketize+pack kernel
        # (ops/ingest.py), with the host path as fallback/parity oracle
        G = self.num_groups
        dtype = np.uint8 if self.max_group_bin <= 256 else np.uint16
        self.binned = np.zeros((self.num_data, G), dtype=dtype)
        if not self._maybe_device_bin(raw, sp, self.binned):
            with _span("ingest.host_bin", ring=True, rows=self.num_data):
                self._bin_block(raw, sp, self.binned)

        self.metadata.check(self.num_data)
        if self.metadata.label is None:
            self.metadata.label = np.zeros(self.num_data, dtype=np.float32)
        self.constructed = True
        if self.free_raw_data:
            self.raw_data = None
        return self

    def _fit_bin_mappers(self, raw, sp, sample_idx, categorical) -> float:
        """FindBin per feature over a row sample + EFB grouping; returns
        the seconds the categorical features' FindBin took.

        reference: DatasetLoader::ConstructBinMappersFromTextData
        (dataset_loader.cpp:823) + Dataset::Construct EFB
        (dataset.cpp:97-313)."""
        p = self.params
        max_bin = int(p.get("max_bin", 255))
        # per-feature bin budgets (reference: Config::max_bin_by_feature,
        # applied per feature in DatasetLoader::ConstructBinMappers)
        mbbf = p.get("max_bin_by_feature") or []
        if isinstance(mbbf, str):
            mbbf = [int(v) for v in mbbf.split(",") if v.strip()]
        if mbbf and len(mbbf) != self.num_total_features:
            from .basic import LightGBMError
            raise LightGBMError(
                "Length of max_bin_by_feature is not same with feature "
                "number")
        min_data_in_bin = int(p.get("min_data_in_bin", 3))
        min_data_in_leaf = int(p.get("min_data_in_leaf", 20))
        use_missing = bool(p.get("use_missing", True))
        zero_as_missing = bool(p.get("zero_as_missing", False))
        pre_filter = bool(p.get("feature_pre_filter", True))
        forced_bounds = _load_forced_bins(p, self.num_total_features)
        total_sample_cnt = len(sample_idx)
        sample_nonzero = {}               # used-feature pos -> bool [S]
        # one row-gather of the whole sample block: per-feature strided
        # column gathers from the [n, F] matrix cost ~7 s at 968 features
        # (profiled); a [S, F] contiguous block makes them slices
        sraw = (np.ascontiguousarray(raw[sample_idx])
                if raw is not None else None)
        self.bin_mappers = []
        cat_s = 0.0
        for f in range(self.num_total_features):
            col = _get_col(sraw, sp, f,
                           None if sraw is not None else sample_idx)
            # keep NaN and non-zero samples; zeros are implicit
            keep = np.isnan(col) | (np.abs(col) > 1e-35)
            vals = col[keep]
            m = BinMapper()
            btype = (BinType.CATEGORICAL if f in categorical
                     else BinType.NUMERICAL)
            t_bin = time.perf_counter()
            m.find_bin(
                vals, total_sample_cnt,
                int(mbbf[f]) if mbbf else max_bin,
                min_data_in_bin=min_data_in_bin,
                min_split_data=min_data_in_leaf,
                pre_filter=pre_filter,
                bin_type=btype,
                use_missing=use_missing,
                zero_as_missing=zero_as_missing,
                forced_upper_bounds=forced_bounds.get(f, ()),
            )
            if btype == BinType.CATEGORICAL:
                cat_s += time.perf_counter() - t_bin
            self.bin_mappers.append(m)
        self.used_features = [f for f, m in enumerate(self.bin_mappers)
                              if not m.is_trivial]
        if not self.used_features and self.bin_mappers:
            # every feature is constant: keep one never-splittable dummy
            # column so the jitted grower has a non-empty feature axis and
            # trains stump trees (the reference trains with zero usable
            # features the same way — all split gains invalid;
            # boost_from_average supplies the constant prediction)
            self.bin_mappers[0] = BinMapper(
                num_bin=2, is_trivial=False,
                bin_upper_bound=np.array([0.0, np.inf]))
            self.used_features = [0]
        # EFB grouping from the sample (reference: FindGroups /
        # FastFeatureBundling, dataset.cpp:97-313)
        for j, f in enumerate(self.used_features):
            col = _get_col(sraw, sp, f,
                           None if sraw is not None else sample_idx)
            # NaN counts as non-default: a NaN row occupies the
            # feature's NaN bin in the merged column, so it can
            # conflict with other bundle members (reference counts
            # sampled NaN values as non-zero entries)
            sample_nonzero[j] = np.isnan(col) | (np.abs(col) > 1e-35)
        self._build_groups(sample_nonzero, total_sample_cnt)
        return cat_s

    def _bin_block(self, raw, sp, out: np.ndarray) -> None:
        """Bin a block of raw rows into ``out`` (a [rows, G] uint view).

        Parallelized over GROUPS (numpy's searchsorted releases the GIL;
        the reference's second pass is likewise OpenMP row-parallel,
        dataset_loader.cpp ExtractFeaturesFromFile).  Bundle members share
        an output column and EFB tolerates bounded conflicts where write
        ORDER is observable, so each group's features stay serial within
        one task — output columns are disjoint across tasks.  Peak host
        scratch is ``workers`` float64 columns (8 x 88 MB at 11M rows)
        instead of the serial path's one.
        """
        dtype = out.dtype
        by_group: Dict[int, list] = {}
        for j, f in enumerate(self.used_features):
            by_group.setdefault(int(self.feat_group[j]), []).append((j, f))

        def run_group(g, members):
            for j, f in members:
                col = _get_col(raw, sp, f, None)
                bins = self.bin_mappers[f].value_to_bin(col)
                start = int(self.feat_start[j])
                if start == 1 and self._group_size[g] == 1:
                    out[:, g] = bins.astype(dtype)
                else:
                    nz = bins != 0   # bundled features are zero-default
                    out[nz, g] = (start + bins[nz] - 1).astype(dtype)

        if len(by_group) > 1 and out.shape[0] * len(self.used_features) > (1 << 22):
            from concurrent.futures import ThreadPoolExecutor
            workers = min(8, len(by_group), os.cpu_count() or 1)
            with ThreadPoolExecutor(max_workers=workers) as ex:
                list(ex.map(lambda kv: run_group(*kv), by_group.items()))
        else:
            for g, members in by_group.items():
                run_group(g, members)

    # -- device-side ingest (ops/ingest.py): the fused bucketize+pack
    #    kernel path; ``_bin_block`` above is the never-deleted host
    #    fallback AND the parity oracle its bytes are checked against --

    def _ingest_state(self) -> Optional[dict]:
        """Build (once per dataset) the device-ingest state: tables,
        plan, compiled binner.  None == this dataset bins on host
        (unsupported recipe, or the election said so); the verdict is
        cached so repeated pushes pay nothing."""
        st = getattr(self, "_ingest", None)
        if st is not None:
            return st or None                 # {} == demoted for good
        from .ops import ingest as ING
        from .ops.planner import active_ledger, plan_ingest
        try:
            tables = ING.build_ingest_tables(self)
        except ING.IngestUnsupported as e:
            ING.demote(str(e), elected_by="unsupported", warn=False)
            self._ingest = {}
            return None
        plan = plan_ingest(
            rows=self.num_data, features=tables.num_features,
            num_groups=tables.num_groups,
            item_bytes=tables.out_dtype.itemsize,
            bounds_width=tables.bounds.shape[1],
            cats_width=tables.cats.shape[1],
            ledger=active_ledger())
        if plan.variant != "kernel":
            ING.record_ingest_story(
                path="host", elected_by=plan.elected_by,
                reason=f"planner elected host ({plan.elected_by})",
                plan=plan.summary())
            self._ingest = {}
            return None
        st = {"plan": plan, "binner": ING.DeviceBinner(tables,
                                                       plan.tile_rows),
              "probed": False}
        self._ingest = st
        return st

    def _maybe_device_bin(self, raw, sp, out: np.ndarray) -> bool:
        """Bin ``raw`` into ``out`` on device when the election says
        so.  True only when every byte was committed device-side and
        the salted parity probe passed first (byte-identical to
        ``_bin_block`` by contract); a probe mismatch returns False so
        the host oracle runs.  A kernel error is not a verdict: it
        propagates."""
        from .ops import ingest as ING
        if sp is not None:
            return False
        if not isinstance(raw, np.ndarray) or raw.dtype != np.float32:
            # the kernel's directed-rounded boundary table is exact
            # ONLY against f32 inputs (ops/ingest.py); f64 stays host
            return False
        st = self._ingest_state()
        if st is None:
            return False
        plan, binner = st["plan"], st["binner"]
        n = out.shape[0]
        if n == 0 or (n < 4096 and plan.elected_by != "env"):
            return False          # dispatch overhead beats tiny blocks
        if not st["probed"]:
            with _span("ingest.parity_probe"):
                if not ING.parity_probe(binner, self, raw):
                    ING.demote(
                        "parity probe: device bytes diverge from "
                        "host value_to_bin", elected_by="parity_probe")
                    self._ingest = {}
                    return False
            st["probed"] = True
        import jax

        from .data.stream import IngestPump
        local = jax.local_devices()
        devices = local if len(local) > 1 else None
        pump = IngestPump(raw, plan.chunk_rows, devices=devices)
        bin_s = 0.0
        # one ring record a construct: the per-chunk seams below and in
        # the pump are annotations, their sums ride on this record
        with _span("ingest.device_bin", ring=True, rows=n,
                   chunk_rows=plan.chunk_rows,
                   tile_rows=plan.tile_rows) as seam:
            for _i, start, rows, chunk in pump:
                # kernel and pull back; np.asarray blocks, so the host
                # clock holds the device time here
                with _span("ingest.bin_chunk") as chunk_seam:
                    out[start:start + rows] = np.asarray(binner(chunk))
                bin_s += chunk_seam.seconds
            seam.set(wait_put_s=pump.wait_s, bin_s=bin_s)
        dt = seam.seconds
        rps = round(n / max(dt, 1e-9), 1)
        ING.record_ingest_story(
            path="kernel", elected_by=plan.elected_by, rows=n,
            chunk_rows=plan.chunk_rows, tile_rows=plan.tile_rows,
            bin_seconds=round(dt, 4), bin_rows_per_sec=rps,
            parity_probe=True)
        from .obs.metrics import global_registry
        global_registry.counter("ingest_rows_total").inc(n)
        global_registry.gauge("bin_rows_per_sec").set(rps)
        return True

    # -- streaming construction (reference: LGBM_DatasetCreateFromSampledColumn
    #    + LGBM_DatasetPushRows / PushRowsByCSR, c_api.h:98-144) -------------

    @classmethod
    def from_sample(cls, sample, num_total_rows: int, params=None,
                    feature_name="auto", categorical_feature="auto",
                    spill=None, spill_block_rows: Optional[int] = None):
        """Create a streaming Dataset: bin boundaries + EFB layout from a
        row sample, the binned matrix preallocated for ``num_total_rows``;
        fill it with ``push_rows`` (rows never all resident as floats).

        reference: LGBM_DatasetCreateFromSampledColumn (c_api.cpp) decides
        bins from sampled columns, then LGBM_DatasetPushRows streams row
        blocks in; the load auto-finishes when every row has been pushed.

        ``spill`` routes the binned rows to an out-of-core block store
        (lightgbm_tpu/data/) instead of a host-resident matrix — host RSS
        stays O(chunk) no matter how many rows stream in, and training
        executes out-of-core (docs/PERF.md "out-of-core streaming").
        ``spill=True`` picks a temp directory (``LGBM_TPU_STREAM_DIR``
        honored); a string is the store directory.  Spill-mode pushes
        must be sequential (append-only); chunk sizes may vary freely,
        including a ragged final chunk.
        """
        ds = cls(sample, params=params, feature_name=feature_name,
                 categorical_feature=categorical_feature)
        sample = _as_2d(sample)
        ds.num_data = int(num_total_rows)
        ds.num_total_features = sample.shape[1]
        if ds._feature_name_param == "auto" or ds._feature_name_param is None:
            ds.feature_names = [f"Column_{i}"
                                for i in range(ds.num_total_features)]
        else:
            ds.feature_names = list(ds._feature_name_param)
        categorical = ds._resolve_categorical()
        ds._fit_bin_mappers(sample, None, np.arange(sample.shape[0]),
                            categorical)
        G = ds.num_groups
        dtype = np.uint8 if ds.max_group_bin <= 256 else np.uint16
        if spill:
            ds._setup_spill(spill, dtype, spill_block_rows)
        else:
            ds.binned = np.zeros((ds.num_data, G), dtype=dtype)
        ds.raw_data = None
        ds._pushed = np.zeros(ds.num_data, bool)   # per-row coverage
        ds._streaming = True
        ds._append_cursor = 0
        return ds

    def _setup_spill(self, spill, dtype, block_rows: Optional[int]) -> None:
        """Route streamed pushes to a block store (spill mode)."""
        import weakref

        from .data.blockstore import BlockStore
        from .data.stream import default_spill_dir
        path = spill if isinstance(spill, (str, os.PathLike)) \
            else default_spill_dir()
        if block_rows is None:
            from .ops.planner import plan_stream
            plan = plan_stream(rows=self.num_data, features=self.num_groups,
                               num_bins=self.max_group_bin)
            block_rows = plan.block_rows or self.num_data
        self.binned = None
        self._block_store = BlockStore.create(
            str(path), self.num_data, self.num_groups, dtype,
            int(block_rows))
        self._block_store_owned = not isinstance(spill, (str, os.PathLike))
        if self._block_store_owned:
            weakref.finalize(self, BlockStore.cleanup, self._block_store)
        # spill scratch: one chunk of binned rows, reused per push
        self._spill_scratch = None

    @classmethod
    def from_reference_streaming(cls, reference: "Dataset",
                                 num_total_rows: int,
                                 params=None) -> "Dataset":
        """Empty streaming Dataset aligned with ``reference``'s binning
        (reference: LGBM_DatasetCreateByReference, c_api.h) — fill with
        ``push_rows``."""
        ref = reference.construct()
        ds = cls(None, reference=reference, params=params)
        ds.num_data = int(num_total_rows)
        ds.num_total_features = ref.num_total_features
        ds.feature_names = list(ref.feature_names)
        ds.bin_mappers = ref.bin_mappers
        ds.used_features = ref.used_features
        ds.feat_group = ref.feat_group
        ds.feat_start = ref.feat_start
        ds.num_groups = ref.num_groups
        ds._group_size = ref._group_size
        ds.group_num_bin = ref.group_num_bin
        ds.max_group_bin = ref.max_group_bin
        dtype = np.uint8 if ds.max_group_bin <= 256 else np.uint16
        ds.binned = np.zeros((ds.num_data, ds.num_groups), dtype=dtype)
        ds.raw_data = None
        ds._pushed = np.zeros(ds.num_data, bool)
        ds._streaming = True
        ds._append_cursor = 0
        return ds

    def push_rows(self, chunk, start_row: Optional[int] = None) -> "Dataset":
        """Bin a block of raw rows into [start_row, start_row+len) of the
        preallocated matrix (reference: LGBM_DatasetPushRows, c_api.h:98).
        ``start_row=None`` appends after the previous push.  Chunk sizes
        may vary push to push — a ragged final chunk smaller than the
        sample/chunk-size hint is fine.  The dataset marks itself
        constructed when every row has been pushed.

        Overlap with already-pushed rows raises (a silent overwrite would
        corrupt the load invisibly); a retry of a FAILED push is not an
        overlap — coverage is only recorded after a chunk bins cleanly.
        Spill-mode datasets (``from_sample(spill=...)``) additionally
        require appends in order: the block store is append-only, so a
        ``start_row`` past the cursor (a gap) raises too."""
        if not getattr(self, "_streaming", False):
            raise RuntimeError(
                "push_rows requires a Dataset created by from_sample")
        if self.constructed:
            raise RuntimeError("dataset load already finished")
        if _is_sparse(chunk):
            sp, raw = chunk.tocsc(), None
            rows = sp.shape[0]
        else:
            raw = _as_2d(chunk)
            sp = None
            rows = raw.shape[0]
        if start_row is None:
            start_row = self._append_cursor
        if start_row + rows > self.num_data:
            raise ValueError(
                f"push past the end: {start_row}+{rows} > {self.num_data}")
        # per-ROW coverage (not a count): a silent overwrite of loaded
        # rows would make the finished matrix depend on push order
        already = np.flatnonzero(self._pushed[start_row:start_row + rows])
        if len(already):
            raise ValueError(
                f"push_rows overlap: row {start_row + int(already[0])} was "
                f"already pushed (chunk covers [{start_row}, "
                f"{start_row + rows})); pushes must cover disjoint row "
                "ranges — only a failed push may be retried")
        store = getattr(self, "_block_store", None)
        if store is not None:
            if start_row != self._append_cursor:
                raise ValueError(
                    f"spill-mode push_rows must append in order: expected "
                    f"start_row={self._append_cursor}, got {start_row} "
                    "(the block store is append-only)")
            if self._spill_scratch is None \
                    or self._spill_scratch.shape[0] < rows:
                self._spill_scratch = np.zeros(
                    (rows, self.num_groups), store.dtype)
            out = self._spill_scratch[:rows]
            out[:] = 0
            if not self._maybe_device_bin(raw, sp, out):
                self._bin_block(raw, sp, out)
            store.append_rows(out)
        else:
            out = self.binned[start_row:start_row + rows]
            if not self._maybe_device_bin(raw, sp, out):
                self._bin_block(raw, sp, out)
        self._pushed[start_row:start_row + rows] = True
        self._append_cursor = max(self._append_cursor, start_row + rows)
        if self._pushed.all():                   # auto-finish like the C API
            if store is not None:
                store.finalize()
            self.metadata.check(self.num_data)
            if self.metadata.label is None:
                self.metadata.label = np.zeros(self.num_data, np.float32)
            self.constructed = True
        return self

    def _build_groups(self, sample_nonzero: dict, total_sample_cnt: int) -> None:
        """Greedy conflict-bounded exclusive feature bundling.

        reference: Dataset::FindGroups (dataset.cpp:97-234) — features whose
        non-default rows rarely overlap share one stored column; conflict
        budget is total_sample_cnt/10000 (dataset.cpp:105), bins per merged
        column capped at 256 (dataset.cpp:104,127 — the GPU cap, which TPU
        uint8 storage likes too).  Only zero-default numerical features are
        bundled; everything else gets a singleton column.
        """
        F = len(self.used_features)
        enable = str(self.params.get("enable_bundle", True)).lower() not in (
            "false", "0", "no")
        eligible = []
        for j, f in enumerate(self.used_features):
            m = self.bin_mappers[f]
            if (enable and m.bin_type == BinType.NUMERICAL
                    and m.most_freq_bin == 0 and m.default_bin == 0
                    and m.num_bin <= 256 and j in sample_nonzero):
                eligible.append(j)
        budget = max(total_sample_cnt // 10000, 0)

        groups: List[List[int]] = []       # positions (into used_features)
        group_nz: List[np.ndarray] = []    # bool [S] union of nonzeros
        group_cnt: List[int] = []          # popcount of the union
        group_conflict: List[int] = []
        group_bins: List[int] = []         # 1 + sum(nb_f - 1)
        nz_cnt = {j: int(sample_nonzero[j].sum()) for j in eligible}
        eligible.sort(key=lambda j: nz_cnt[j], reverse=True)
        # bounded search, like the reference: at most max_search_group
        # groups are probed per feature (dataset.cpp FindGroups samples
        # kMaxSearchGroup candidates), and a group is only probed when the
        # PIGEONHOLE lower bound on overlap — cnt_j + cnt_g - S — leaves
        # the budget reachable.  Without these, 2000 dense features cost
        # O(F^2 * S) boolean ANDs (measured: minutes at Epsilon shape).
        max_search_group = 100
        for j in eligible:
            nz = sample_nonzero[j]
            cnt_j = nz_cnt[j]
            nb = self.bin_mappers[self.used_features[j]].num_bin
            placed = False
            searched = 0
            for gi in range(len(groups)):
                if searched >= max_search_group:
                    break
                if group_bins[gi] + nb - 1 > 256:
                    continue
                lower = max(0, cnt_j + group_cnt[gi] - total_sample_cnt)
                if group_conflict[gi] + lower > budget:
                    continue
                searched += 1
                conflict = int(np.count_nonzero(group_nz[gi] & nz))
                if group_conflict[gi] + conflict <= budget:
                    groups[gi].append(j)
                    group_nz[gi] = group_nz[gi] | nz
                    group_cnt[gi] = group_cnt[gi] + cnt_j - conflict
                    group_conflict[gi] += conflict
                    group_bins[gi] += nb - 1
                    placed = True
                    break
            if not placed:
                groups.append([j])
                group_nz.append(nz.copy())
                group_cnt.append(cnt_j)
                group_conflict.append(0)
                group_bins.append(1 + (nb - 1))

        feat_group = np.zeros(F, np.int32)
        feat_start = np.ones(F, np.int32)
        group_size: List[int] = []
        group_num_bin: List[int] = []
        gid = 0
        bundled_pos = set()
        for gi, members in enumerate(groups):
            if len(members) == 1:
                continue   # singletons handled below for stable ordering
            off = 1
            for j in members:
                feat_group[j] = gid
                feat_start[j] = off
                off += self.bin_mappers[self.used_features[j]].num_bin - 1
                bundled_pos.add(j)
            group_size.append(len(members))
            group_num_bin.append(off)
            gid += 1
        for j in range(F):
            if j in bundled_pos:
                continue
            feat_group[j] = gid
            feat_start[j] = 1
            group_size.append(1)
            group_num_bin.append(
                self.bin_mappers[self.used_features[j]].num_bin)
            gid += 1

        self.feat_group = feat_group
        self.feat_start = feat_start
        self.num_groups = gid
        self._group_size = group_size
        self.group_num_bin = group_num_bin
        self.max_group_bin = max(group_num_bin, default=2)

    def _resolve_categorical(self) -> set:
        cf = self._categorical_feature_param
        if cf == "auto" or cf is None:
            cats = set()
            # pandas auto-resolution: the NOT-ordered category columns
            # (recorded by _data_from_pandas during construct)
            auto = getattr(self, "_categorical_auto_resolved", None)
            if auto:
                cats |= self._names_to_indices(auto)
            # also honor categorical_feature in params (CLI-style)
            pcf = self.params.get("categorical_feature") or self.params.get("categorical_column")
            if pcf:
                cats |= self._names_to_indices(pcf)
            return cats
        return self._names_to_indices(cf)

    @property
    def categorical_feature(self):
        """The categorical_feature spec as given (reference keeps the
        user's names/indices on the Dataset)."""
        return self._categorical_feature_param

    def _names_to_indices(self, spec) -> set:
        if isinstance(spec, str):
            spec = [s for s in spec.split(",") if s]
        out = set()
        for s in spec:
            if isinstance(s, str) and not s.lstrip("-").isdigit():
                if s in self.feature_names:
                    out.add(self.feature_names.index(s))
                else:
                    raise ValueError(f"unknown categorical feature {s!r}")
            else:
                out.add(int(s))
        return out

    # -- accessors mirroring reference python API ----------------------------

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        """Create a validation Dataset aligned with this one (bins with
        THIS dataset's BinMappers).

        reference: Dataset.create_valid (python-package/lightgbm/basic.py:1142).
        """
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       feature_name=self._feature_name_param,
                       categorical_feature=self._categorical_feature_param,
                       params=dict(params or self.params),
                       free_raw_data=self.free_raw_data)

    # -- field accessors (reference: Dataset.get_field/set_field,
    # python-package/lightgbm/basic.py:1255-1339 -> LGBM_DatasetGetField /
    # SetField, src/c_api.cpp; 'group' follows the reference's asymmetry:
    # set takes per-query SIZES, get returns CUMULATIVE boundaries) -------

    _FIELDS = ("label", "weight", "init_score", "group")

    def set_field(self, field_name: str, data) -> "Dataset":
        if field_name not in self._FIELDS:
            raise ValueError(f"unknown field {field_name!r}")
        if field_name == "label":
            self.metadata.label = (None if data is None else
                                   np.asarray(data, np.float32).reshape(-1))
        elif field_name == "weight":
            self.metadata.weight = (None if data is None else
                                    np.asarray(data, np.float32).reshape(-1))
        elif field_name == "init_score":
            self.metadata.init_score = (None if data is None else
                                        np.asarray(data, np.float64))
        else:
            self.metadata.set_group(data)
        return self

    def get_field(self, field_name: str):
        if field_name not in self._FIELDS:
            raise ValueError(f"unknown field {field_name!r}")
        if field_name == "group":
            return self.metadata.query_boundaries
        if field_name == "init_score":
            return self.metadata.init_score
        return getattr(self.metadata, field_name)

    def get_data(self):
        """The raw data this Dataset was built from (reference:
        Dataset.get_data, basic.py — raises after raw data was freed)."""
        if self.raw_data is None and self.constructed:
            raise RuntimeError(
                "Cannot get data: raw data was freed after construction "
                "(pass free_raw_data=False to keep it)")
        return self.raw_data

    def release_host_binned(self) -> "Dataset":
        """Free the host [n, F] binned matrix once a device-resident copy
        exists (GBDT.__init__ calls this when ``free_raw_data`` is set on
        accelerator backends, halving peak RSS for large matrices).  The
        Dataset can no longer build another booster, subset, save_binary
        or add_features_from afterwards; ``host_binned`` raises then."""
        if self.binned is not None:
            self.binned = None
            self._host_binned_released = True
            # the device-binned reuse cache (boosting/gbdt.py) rides on
            # the live Dataset; a released Dataset keeps the documented
            # cannot-build-another-booster contract
            self._dev_binned_cache = None
        return self

    def host_binned(self) -> np.ndarray:
        """The host binned matrix DATA, with an informative error when it
        is not resident.  Consumers that only need shape/dtype metadata
        must use ``binned_shape``/``binned_dtype`` instead — those stay
        valid on released and block-backed (out-of-core) datasets."""
        if self.binned is None:
            if getattr(self, "_block_store", None) is not None:
                raise RuntimeError(
                    "this Dataset's binned matrix lives in an out-of-core "
                    "block store (lightgbm_tpu/data/), not host memory; "
                    "metadata consumers should use binned_shape()/"
                    "binned_dtype(), bulk consumers must stream blocks "
                    "via Dataset._block_store.read_block")
            if getattr(self, "_host_binned_released", False):
                raise RuntimeError(
                    "the Dataset's host binned matrix was released after "
                    "device upload (free_raw_data=True on an accelerator "
                    "backend); pass free_raw_data=False or set "
                    "LGBM_TPU_FREE_BINNED=0 to keep it for reuse")
        return self.binned

    def binned_shape(self) -> tuple:
        """(num_data, num_groups) of the binned matrix — metadata only,
        valid whether the data is host-resident, released after device
        upload, or spilled to an out-of-core block store."""
        self.construct()
        return (self.num_data, self.num_groups)

    def binned_dtype(self) -> np.dtype:
        """Storage dtype of the binned matrix (metadata twin of
        ``binned_shape``)."""
        self.construct()
        return np.dtype(np.uint8 if self.max_group_bin <= 256
                        else np.uint16)

    def get_params(self) -> dict:
        return dict(self.params)

    def get_ref_chain(self, ref_limit: int = 100) -> set:
        """Chain of Datasets reachable through .reference (reference:
        Dataset.get_ref_chain, basic.py:1633)."""
        head, chain = self, set()
        while len(chain) < ref_limit:
            if isinstance(head, Dataset):
                chain.add(head)
                if head.reference is not None and head.reference not in chain:
                    head = head.reference
                else:
                    break
            else:
                break
        return chain

    def num_feature(self) -> int:
        """Number of (original) features, after construction (reference:
        LGBM_DatasetGetNumFeature -> max_feature_idx + 1)."""
        self.construct()
        return self.num_total_features

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if self._categorical_feature_param == categorical_feature:
            return self
        if self.constructed:
            raise RuntimeError(
                "Cannot set categorical feature after dataset construction; "
                "create a new Dataset")
        self._categorical_feature_param = categorical_feature
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        if feature_name != "auto":
            self._feature_name_param = feature_name
            if self.constructed:
                if len(feature_name) != self.num_total_features:
                    raise ValueError(
                        f"Length of feature names ({len(feature_name)}) does "
                        f"not equal number of features "
                        f"({self.num_total_features})")
                self.feature_names = list(feature_name)
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if self.reference is reference:
            return self
        if self.constructed:
            raise RuntimeError(
                "Cannot set reference after dataset construction; "
                "create a new Dataset")
        self.reference = reference
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append ``other``'s feature columns to this Dataset in place.

        Both must be constructed with the same number of rows (reference:
        LGBM_DatasetAddFeaturesFrom -> Dataset::AddFeaturesFrom,
        src/io/dataset.cpp).  Bin groups are concatenated: the merged matrix
        keeps each source's EFB bundling with the other's group ids offset.
        """
        if not (self.constructed and other.constructed):
            raise ValueError(
                "Both source and target Datasets must be constructed "
                "before adding features")
        if self.num_data != other.num_data:
            from .basic import LightGBMError
            raise LightGBMError(
                f"Cannot add features from {other.num_data}-row Dataset to "
                f"{self.num_data}-row Dataset")
        base = self.num_total_features
        self.bin_mappers = list(self.bin_mappers) + list(other.bin_mappers)
        self.used_features = list(self.used_features) + [
            base + f for f in other.used_features]
        dtype = (np.uint16 if max(self.max_group_bin, other.max_group_bin) > 256
                 else np.uint8)
        self.binned = np.hstack([self.host_binned().astype(dtype, copy=False),
                                 other.host_binned().astype(dtype, copy=False)])
        self.feat_group = np.concatenate(
            [self.feat_group, other.feat_group + self.num_groups]).astype(np.int32)
        self.feat_start = np.concatenate(
            [self.feat_start, other.feat_start]).astype(np.int32)
        self._group_size = list(self._group_size) + list(other._group_size)
        self.group_num_bin = list(self.group_num_bin) + list(other.group_num_bin)
        self.num_groups += other.num_groups
        self.max_group_bin = max(self.max_group_bin, other.max_group_bin)
        self.num_total_features += other.num_total_features
        self.feature_names = list(self.feature_names) + list(other.feature_names)
        return self

    def _dump_text(self, filename: str) -> "Dataset":
        """Debug dump of the binned matrix (reference: Dataset::DumpTextFile,
        src/io/dataset.cpp:994 via LGBM_DatasetDumpText): header stats,
        feature names, then one line of per-feature BIN values per row.
        Not loadable back; for debugging parity only."""
        self.construct()
        from .utils.file_io import open_atomic
        F = len(self.used_features)
        # streamed row-by-row (num_data lines): open_atomic keeps the
        # per-row write with O(1) extra memory and still lands atomically
        with open_atomic(filename, "w") as fh:
            fh.write(f"num_features: {F}\n")
            fh.write(f"num_total_features: {self.num_total_features}\n")
            fh.write(f"num_groups: {self.num_groups}\n")
            fh.write(f"num_data: {self.num_data}\n")
            fh.write("feature_names: "
                     + ", ".join(self.feature_names) + "\n")
            meta = self.feature_meta().resolved()
            for i in range(self.num_data):
                row = self.host_binned()[i]
                bins = []
                for j in range(F):
                    g = meta.feat_group[j]
                    st = meta.feat_start[j]
                    dec = int(row[g]) - st + 1
                    bins.append(dec if 1 <= dec < meta.num_bin[j] else 0)
                fh.write(", ".join(str(b) for b in bins) + "\n")
        return self

    def get_label(self):
        return self.metadata.label

    def set_label(self, label):
        self.metadata.label = np.asarray(label, dtype=np.float32).reshape(-1)

    # attribute-style field access (the reference Dataset keeps .label /
    # .weight / .init_score / .group instance attributes)
    @property
    def label(self):
        return self.metadata.label

    @label.setter
    def label(self, value):
        self.metadata.label = (None if value is None else
                               np.asarray(value, np.float32).reshape(-1))

    @property
    def weight(self):
        return self.metadata.weight

    @weight.setter
    def weight(self, value):
        self.metadata.weight = (None if value is None else
                                np.asarray(value, np.float32).reshape(-1))

    @property
    def init_score(self):
        return self.metadata.init_score

    @init_score.setter
    def init_score(self, value):
        self.metadata.init_score = (None if value is None else
                                    np.asarray(value, np.float64))

    @property
    def group(self):
        return self.get_group()

    @group.setter
    def group(self, value):
        self.metadata.set_group(value)

    def get_weight(self):
        return self.metadata.weight

    def set_weight(self, weight):
        self.metadata.weight = None if weight is None else np.asarray(weight, np.float32).reshape(-1)

    def set_group(self, group):
        self.metadata.set_group(group)

    def set_init_score(self, init_score):
        self.metadata.init_score = None if init_score is None else np.asarray(init_score, np.float64)

    def get_init_score(self):
        return self.metadata.init_score

    def get_group(self):
        """Per-query group SIZES (reference: Dataset.get_group converts the
        stored cumulative boundaries back with np.diff)."""
        qb = self.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def num_features(self) -> int:
        self.construct()
        return len(self.used_features)

    def get_feature_names(self) -> List[str]:
        return self.feature_names

    def subset(self, used_indices, params=None) -> "Dataset":
        self.construct()
        idx = np.asarray(used_indices, dtype=np.int64)
        sub = Dataset.__new__(Dataset)
        sub.params = dict(params or self.params)
        # a kept-raw parent hands its subset the raw rows too (reference:
        # subsets re-materialize from the parent's data — needed for
        # fpreproc / continued training on subsets)
        import os as _os
        if self.raw_data is not None and not isinstance(
                self.raw_data, (str, _os.PathLike)):
            sub.raw_data = (self.raw_data.iloc[idx]
                            if hasattr(self.raw_data, "iloc")
                            else self.raw_data[idx])
            sub.free_raw_data = self.free_raw_data
        elif isinstance(self.raw_data, (str, _os.PathLike)):
            # file-backed parent: subsets report the same path from
            # get_data() (reference test_init_with_subset asserts this)
            sub.raw_data = self.raw_data
            sub.free_raw_data = self.free_raw_data
        else:
            sub.raw_data = None
            sub.free_raw_data = True
        sub.reference = self
        qb = None
        if self.metadata.query_boundaries is not None:
            # rows of one query must stay contiguous in the subset (true for
            # group-aware fold splits); rebuild boundaries from run-lengths
            gid = np.searchsorted(self.metadata.query_boundaries, idx,
                                  side="right") - 1
            if np.any(np.diff(gid) < 0):
                raise ValueError(
                    "subset() of grouped (ranking) data requires used_indices "
                    "to keep each query's rows contiguous and in order")
            change = np.flatnonzero(np.diff(gid)) + 1
            qb = np.concatenate([[0], change, [len(idx)]]).astype(np.int32)
        sub.metadata = Metadata(
            label=None if self.metadata.label is None else self.metadata.label[idx],
            weight=None if self.metadata.weight is None else self.metadata.weight[idx],
            init_score=None if self.metadata.init_score is None else
            np.asarray(self.metadata.init_score).reshape(self.num_data, -1)[idx].reshape(-1),
            query_boundaries=qb,
        )
        sub._feature_name_param = self.feature_names
        sub._categorical_feature_param = self._categorical_feature_param
        sub.pandas_categorical = getattr(self, "pandas_categorical", None)
        sub.constructed = True
        sub.bin_mappers = self.bin_mappers
        sub.used_features = self.used_features
        sub.binned = self.host_binned()[idx]
        sub.feat_group = self.feat_group
        sub.feat_start = self.feat_start
        sub.num_groups = self.num_groups
        sub._group_size = self._group_size
        sub.group_num_bin = self.group_num_bin
        sub.max_group_bin = self.max_group_bin
        sub.feature_names = self.feature_names
        sub.num_data = len(idx)
        sub.num_total_features = self.num_total_features
        return sub

    # -- binary serialization (reference: Dataset::SaveBinaryFile dataset.cpp:890)

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        meta = {
            "version": 1,
            "params": {k: v for k, v in self.params.items()
                       if isinstance(v, (int, float, str, bool, list))
                       or v is None},
            "num_data": int(self.num_data),
            "num_total_features": int(self.num_total_features),
            "used_features": list(map(int, self.used_features)),
            "feature_names": self.feature_names,
            "bin_mappers": [m.to_dict() for m in self.bin_mappers],
            "dtype": str(self.host_binned().dtype),
            "feat_group": list(map(int, self.feat_group)),
            "feat_start": list(map(int, self.feat_start)),
            "num_groups": int(self.num_groups),
            "group_size": list(map(int, self._group_size)),
            "group_num_bin": list(map(int, self.group_num_bin)),
            "has_label": self.metadata.label is not None,
            "has_weight": self.metadata.weight is not None,
            "has_group": self.metadata.query_boundaries is not None,
            "has_init_score": self.metadata.init_score is not None,
        }
        # a binary cache is reloaded by later runs: a crash mid-write must
        # not leave a truncated file that load_binary trusts — stream
        # through the atomic seam (the binned matrix can be GBs; no
        # second resident copy)
        from .utils.file_io import open_atomic
        with open_atomic(filename, "wb") as fh:
            fh.write(_BINARY_MAGIC)
            hdr = json.dumps(meta).encode()
            fh.write(len(hdr).to_bytes(8, "little"))
            fh.write(hdr)
            fh.write(np.ascontiguousarray(self.binned).tobytes())
            for arr in (self.metadata.label, self.metadata.weight,
                        self.metadata.query_boundaries,
                        self.metadata.init_score):
                if arr is not None:
                    fh.write(np.ascontiguousarray(arr).tobytes())
        return self

    @staticmethod
    def load_binary(filename: str, params: Optional[dict] = None) -> "Dataset":
        from .utils.file_io import open_file
        with open_file(filename, "rb") as fh:
            magic = fh.read(len(_BINARY_MAGIC))
            if magic != _BINARY_MAGIC:
                raise ValueError(f"{filename} is not a lightgbm_tpu binary dataset")
            n = int.from_bytes(fh.read(8), "little")
            meta = json.loads(fh.read(n).decode())
            ds = Dataset.__new__(Dataset)
            # the binary cache carries the construction params (reference:
            # SaveBinaryFile serializes the Config the dataset was built
            # with) so param-change checking sees the true old values
            ds.params = dict(params or meta.get("params") or {})
            ds.raw_data = None
            ds.reference = None
            ds.free_raw_data = True
            ds._feature_name_param = meta["feature_names"]
            ds._categorical_feature_param = None
            ds.constructed = True
            ds.num_data = meta["num_data"]
            ds.num_total_features = meta["num_total_features"]
            ds.used_features = meta["used_features"]
            ds.feature_names = meta["feature_names"]
            ds.bin_mappers = [BinMapper.from_dict(d) for d in meta["bin_mappers"]]
            F = len(ds.used_features)
            if "feat_group" in meta:
                ds.feat_group = np.asarray(meta["feat_group"], np.int32)
                ds.feat_start = np.asarray(meta["feat_start"], np.int32)
                ds.num_groups = int(meta["num_groups"])
                ds._group_size = list(meta["group_size"])
                ds.group_num_bin = list(meta["group_num_bin"])
                ds.max_group_bin = max(ds.group_num_bin, default=2)
            else:   # pre-EFB file: identity groups
                ds.feat_group = np.arange(F, dtype=np.int32)
                ds.feat_start = np.ones(F, np.int32)
                ds.num_groups = F
                ds._group_size = [1] * F
                ds.group_num_bin = [ds.bin_mappers[f].num_bin
                                    for f in ds.used_features]
                ds.max_group_bin = max(ds.group_num_bin, default=2)
            ncols = ds.num_groups
            dtype = np.dtype(meta["dtype"])
            ds.binned = np.frombuffer(
                fh.read(ds.num_data * ncols * dtype.itemsize), dtype=dtype
            ).reshape(ds.num_data, ncols).copy()
            ds.metadata = Metadata()
            if meta["has_label"]:
                ds.metadata.label = np.frombuffer(fh.read(ds.num_data * 4), np.float32).copy()
            if meta["has_weight"]:
                ds.metadata.weight = np.frombuffer(fh.read(ds.num_data * 4), np.float32).copy()
            if meta["has_group"]:
                rest = fh.read()
                # query boundaries precede init score; length unknown → parse both
                if meta["has_init_score"]:
                    qb_len = len(rest) - ds.num_data * 8
                    ds.metadata.query_boundaries = np.frombuffer(rest[:qb_len], np.int32).copy()
                    ds.metadata.init_score = np.frombuffer(rest[qb_len:], np.float64).copy()
                else:
                    ds.metadata.query_boundaries = np.frombuffer(rest, np.int32).copy()
            elif meta["has_init_score"]:
                ds.metadata.init_score = np.frombuffer(fh.read(ds.num_data * 8), np.float64).copy()
            return ds

    # -- device view ---------------------------------------------------------

    def feature_meta(self) -> "FeatureMeta":
        self.construct()
        return FeatureMeta.from_mappers(
            [self.bin_mappers[f] for f in self.used_features],
            feat_group=self.feat_group, feat_start=self.feat_start,
            num_groups=self.num_groups, max_group_bin=self.max_group_bin)


@dataclass(frozen=True)
class FeatureMeta:
    """Static (trace-time) per-used-feature metadata arrays for device kernels.

    EFB mapping (reference: FeatureGroup bin stacking, feature_group.h:32-50):
    scan/tree/partition all operate on ORIGINAL used features; the stored
    matrix has one column per GROUP.  Feature f's non-default bins b>=1 live
    at merged bin ``feat_start[f] + b - 1`` of column ``feat_group[f]``; its
    bin 0 (the shared default) is reconstructed from leaf totals at scan time
    (the reference's FixHistogram trick, dataset.cpp:1410).  Singleton groups
    use feat_start=1 so the same formulas hold (merged bin == feature bin).
    """

    num_bin: np.ndarray        # int32 [F]
    missing_type: np.ndarray   # int32 [F]
    default_bin: np.ndarray    # int32 [F]
    most_freq_bin: np.ndarray  # int32 [F]
    is_categorical: np.ndarray  # bool [F]
    max_num_bin: int           # padded per-feature bin axis size B
    feat_group: Optional[np.ndarray] = None   # int32 [F] column of feature
    feat_start: Optional[np.ndarray] = None   # int32 [F] merged-bin start
    num_groups: int = 0                       # G (0 -> identity: G == F)
    max_group_bin: int = 0                    # padded group bin axis Bg

    def with_identity_groups(self) -> "FeatureMeta":
        F = len(self.num_bin)
        import dataclasses
        return dataclasses.replace(
            self,
            feat_group=np.arange(F, dtype=np.int32),
            feat_start=np.ones(F, np.int32),
            num_groups=F,
            max_group_bin=self.max_num_bin,
        )

    @property
    def has_bundles(self) -> bool:
        return (self.num_groups != 0 and
                self.num_groups != len(self.num_bin))

    def resolved(self) -> "FeatureMeta":
        return self if self.num_groups else self.with_identity_groups()

    def as_runtime_arrays(self) -> tuple:
        """The per-feature metadata as DEVICE arrays in the canonical
        (num_bin, missing_type, default_bin, is_categorical, feat_group,
        feat_start) order that grow_tree / grow_tree_rounds /
        predict_leaf_index_binned unpack — the single construction site
        for the runtime-metadata tuple that lets one compiled program
        serve every same-shaped dataset."""
        import jax.numpy as jnp
        m = self.resolved()
        return tuple(jnp.asarray(a) for a in (
            m.num_bin, m.missing_type, m.default_bin,
            m.is_categorical, m.feat_group, m.feat_start))

    @staticmethod
    def from_mappers(mappers: Sequence[BinMapper],
                     feat_group=None, feat_start=None,
                     num_groups: int = 0, max_group_bin: int = 0) -> "FeatureMeta":
        nb = np.array([m.num_bin for m in mappers], dtype=np.int32)
        meta = FeatureMeta(
            num_bin=nb,
            missing_type=np.array([m.missing_type for m in mappers], dtype=np.int32),
            default_bin=np.array([m.default_bin for m in mappers], dtype=np.int32),
            most_freq_bin=np.array([m.most_freq_bin for m in mappers], dtype=np.int32),
            is_categorical=np.array([m.bin_type == BinType.CATEGORICAL for m in mappers], dtype=bool),
            max_num_bin=int(nb.max()) if len(nb) else 2,
            feat_group=feat_group, feat_start=feat_start,
            num_groups=num_groups, max_group_bin=max_group_bin,
        )
        return meta.resolved()


def _is_sparse(data) -> bool:
    return hasattr(data, "tocsc") and hasattr(data, "nnz")


def _get_col(raw, sp, f: int, rows: Optional[np.ndarray]) -> np.ndarray:
    if raw is not None:
        if rows is not None:
            # gather first, THEN widen: the float64 scratch is O(sample)
            return np.asarray(raw[rows, f], dtype=np.float64)
        return np.asarray(raw[:, f], dtype=np.float64)
    col = np.asarray(sp[:, f].todense()).reshape(-1).astype(np.float64)
    return col if rows is None else col[rows]


def _load_forced_bins(params: dict, num_features: int) -> Dict[int, List[float]]:
    """reference: forcedbins_filename (dataset_loader.cpp DatasetLoader ctor)."""
    fn = params.get("forcedbins_filename", "")
    if not fn:
        return {}
    with open(fn) as fh:
        spec = json.load(fh)
    out: Dict[int, List[float]] = {}
    for entry in spec:
        out[int(entry["feature"])] = [float(x) for x in entry["bin_upper_bound"]]
    return out
