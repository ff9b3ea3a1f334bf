"""The chip's compiler, held in tier-1: every Pallas kernel the main path
can elect on a TPU is compiled here for a DESCRIBED v5e chip (the TPU
compiler ships with jaxlib; no chip is attached), at HIGGS width —
28 features, max_bin 63, 255 leaves, 1M and 10.5M-bucket rows — the
accumulate pass also at the benchmark's own shapes, and every variant taken out of the on-chip election is shown to stay out
and to raise the compiler's own error when forced.

Interpret mode (what the rest of tier-1 runs the kernels in) cannot see
what is refused here: a shape cast Mosaic has no layout for, a block the
TPU lowering does not tile, more scoped VMEM than a kernel may take, a
primitive without a lowering rule.  A compile that passes is not a chip
run — ``chip_smoke.py`` is.

All cases live in this one file, and the topology is described inside a
module-scoped fixture: only one process at a time may load the TPU's
library, so it must happen in the one xdist worker that runs this file,
after a test of it has started — never at import.
"""

import numpy as np
import pytest

F, B, LEAVES = 28, 63, 255          # HIGGS width, max_bin=63, 255 leaves
K = 128                             # min(LEAVES - 1, tpu_round_width=128)
ROWS_1M = 1 << 20
ROWS_10M = 12_582_912               # bucket_rows(10_500_000)


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on the first chip of a described v5e:2x2,
    with the persistent compile cache off while this file runs (a
    described-device compile can be written to it but never read back)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_accelerator(monkeypatch):
    """Steer ``on_accelerator()`` to True for code that asks the live
    backend (which is the CPU here) which branch to take."""
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setattr(H, "ACCEL_BACKENDS", ("cpu",))


def _compile(fn, *shapes):
    import jax
    return jax.jit(fn).lower(*shapes).compile()


def _shape(one_chip, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_planner_elects_the_fused_arm_at_higgs_width():
    """The shape the cases below compile is the shape ``auto`` elects."""
    from lightgbm_tpu.ops.planner import plan_histograms
    plan = plan_histograms(ROWS_1M, F, B, num_leaves=LEAVES, method="auto",
                           round_width=K, fused_ok=True, accel=True,
                           budget_bytes=16 << 30)
    assert plan.fused
    assert (plan.fused_feat_tile, plan.fused_block_rows) == (8, 1024)
    # the narrower slot widths of the rounds grower's accumulate pass
    # share the widest's feature tile, each with its own row tile: the
    # narrower the arena, the longer the tile the VMEM model admits
    from lightgbm_tpu.ops.fused import NARROW_SLOT_WIDTHS
    from lightgbm_tpu.ops.planner import plan_fused
    assert NARROW_SLOT_WIDTHS == (16, 64)

    def tiles(bins, quant, ft):
        return [plan_fused(w, bins, quant, feat_tile=ft)["block_rows"]
                for w in NARROW_SLOT_WIDTHS + (K,)]

    assert tiles(B, False, 8) == [4096, 2048, 1024]
    assert tiles(B, True, 8) == [8192, 8192, 8192]
    assert tiles(255, False, 2) == [4096, 2048, 1024]
    assert tiles(255, True, 4) == [8192, 4096, 1024]


@pytest.mark.parametrize("rows", [ROWS_1M, ROWS_10M])
def test_histogram_pallas_compiles(one_chip, rows):
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram import histogram_pallas
    c = _compile(lambda b, v: histogram_pallas(b, v, B, interpret=False),
                 _shape(one_chip, (F, rows), jnp.uint8),
                 _shape(one_chip, (3, rows), jnp.float32))
    assert _kernels(c) == 1


@pytest.mark.parametrize("rows", [ROWS_1M, ROWS_10M])
@pytest.mark.parametrize("family", ["f32", "int8"])
def test_fused_accumulate_compiles(one_chip, family, rows):
    """The accumulate half at the planner's own {feat_tile, block_rows}
    (None = plan_fused): the kernel ``auto`` runs every round."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused import fused_frontier_accumulate
    ch, dt = (3, jnp.float32) if family == "f32" else (2, jnp.int8)
    c = _compile(
        lambda b, v, s: fused_frontier_accumulate(b, v, s, K, B,
                                                  interpret=False),
        _shape(one_chip, (F, rows), jnp.uint8),
        _shape(one_chip, (ch, rows), dt),
        _shape(one_chip, (rows,), jnp.int32))
    assert _kernels(c) == 1


@pytest.mark.parametrize("bins", [B, 255])
@pytest.mark.parametrize("width", [16, 64, 128])
@pytest.mark.parametrize("family", ["f32", "int8"])
def test_fused_accumulate_compiles_at_every_slot_width(one_chip, family,
                                                       width, bins):
    """The rounds grower runs a pass at the narrowest of these widths that
    holds its candidates, over ONE feature-blocked operand at the widest
    width's feature tile, each width at ``plan_fused``'s own row tile: at
    the Criteo cell's 67 columns (a ragged last feature block) and 10.5M
    -bucket rows."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused import (NARROW_SLOT_WIDTHS,
                                        fused_frontier_accumulate)
    from lightgbm_tpu.ops.planner import plan_fused
    assert width in NARROW_SLOT_WIDTHS + (K,)
    quant = family == "int8"
    ch, dt = (2, jnp.int8) if quant else (3, jnp.float32)
    ft = plan_fused(K, bins, quant)["feat_tile"]
    tile = plan_fused(width, bins, quant, feat_tile=ft)
    cols, nf = 67, -(-67 // ft)
    c = _compile(
        lambda b, v, s: fused_frontier_accumulate(
            b, v, s, width, bins, block_rows=tile["block_rows"],
            num_features=cols, interpret=False),
        _shape(one_chip, (nf, ft, ROWS_10M), jnp.uint8),
        _shape(one_chip, (ch, ROWS_10M), dt),
        _shape(one_chip, (ROWS_10M,), jnp.int32))
    assert _kernels(c) == 1


@pytest.mark.parametrize("width", [16, 64, 128])
@pytest.mark.parametrize("cols,rows", [
    (67, 25_165_824),        # criteo-quant
    (220, 8_388_608),        # istella-rank
    (39, 33_554_432),        # criteo-cat
])
def test_packed_accumulate_compiles_at_the_benchmarks_shapes(one_chip, cols,
                                                             rows, width):
    """The pass every cell of the benchmark runs (64 bins, int8, the row
    tile ``plan_fused`` elects) builds its operands four cells to a word:
    Mosaic has to lower the int8 <-> int32 bitcasts, at 16 slots over
    half-filled sublane tiles, and the packed bytes' int32 arithmetic."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused import (_arena_dims,
                                        fused_frontier_accumulate,
                                        packed_operands)
    from lightgbm_tpu.ops.planner import plan_fused
    bins = 64
    ft = plan_fused(K, bins, True, num_features=cols)["feat_tile"]
    tile = plan_fused(width, bins, True, feat_tile=ft)["block_rows"]
    assert (ft, tile) == (8, 8192)
    assert packed_operands(True, jnp.uint8,
                           _arena_dims(width, bins, ft, True)[1])
    c = _compile(
        lambda b, v, s: fused_frontier_accumulate(
            b, v, s, width, bins, block_rows=tile, num_features=cols,
            interpret=False),
        _shape(one_chip, (-(-cols // ft), ft, rows), jnp.uint8),
        _shape(one_chip, (2, rows), jnp.int8),
        _shape(one_chip, (rows,), jnp.int32))
    assert _kernels(c) == 1


@pytest.mark.parametrize("width", [16, 64, 128])
def test_bounded_accumulate_compiles_at_the_goss_shape(one_chip, width):
    """A sampled tree's pass over its kept rows gathered to the front
    (``live_rows``): the live tile count rides in as a scalar-prefetch
    operand that the row blocks' index maps clamp to, and the accumulate
    sits under a ``pl.when``: criteo-goss's 67 columns, 25.2M rows."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused import fused_frontier_accumulate
    cols, rows, ft, tile = 67, 25_165_824, 8, 8192
    c = _compile(
        lambda b, v, s, live: fused_frontier_accumulate(
            b, v, s, width, 64, block_rows=tile, num_features=cols,
            interpret=False, live_rows=live),
        _shape(one_chip, (-(-cols // ft), ft, rows), jnp.uint8),
        _shape(one_chip, (2, rows), jnp.int8),
        _shape(one_chip, (rows,), jnp.int32),
        _shape(one_chip, (), jnp.int32))
    assert _kernels(c) == 1


@pytest.mark.parametrize("k,bins,bin_dtype", [
    (30, 63, "uint8"),       # 31 leaves: slots off the sublane tiling
    (128, 255, "uint8"),     # default max_bin: the planner drops to Ft=2
    (128, 300, "uint16"),    # wide bins: 2-byte matrix
])
def test_fused_accumulate_compiles_off_the_aligned_shape(one_chip, k, bins,
                                                         bin_dtype):
    """``auto`` elects the fused arm at whatever width the data has, and
    a refusal is no longer demoted — so the shapes just off HIGGS's
    aligned one must compile too."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused import fused_frontier_accumulate
    c = _compile(
        lambda b, v, s: fused_frontier_accumulate(b, v, s, k, bins,
                                                  interpret=False),
        _shape(one_chip, (F, ROWS_1M), jnp.dtype(bin_dtype)),
        _shape(one_chip, (3, ROWS_1M), jnp.float32),
        _shape(one_chip, (ROWS_1M,), jnp.int32))
    assert _kernels(c) == 1


def _scan_operands(one_chip):
    import jax.numpy as jnp
    return (_shape(one_chip, (K, 3, F, B), jnp.float32),     # small hists
            _shape(one_chip, (3, 2 * K), jnp.float32),       # child sums
            _shape(one_chip, (K,), jnp.bool_),               # small_left
            _shape(one_chip, (K, 3, F, B), jnp.float32))     # parent hists


def _meta_vectors():
    return (np.full((F,), B, np.int32), np.zeros((F,), np.int32),
            np.zeros((F,), np.int32))


def test_elected_scan_form_compiles(one_chip, as_accelerator):
    """What "fused" means on the chip: the accumulate kernel, then the
    sibling-derive + gain scan as plain XLA — one Mosaic kernel, and the
    whole per-round program compiles."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused import fused_frontier_splits
    from lightgbm_tpu.ops.split import SplitHyperparams
    nb, mt, db = _meta_vectors()
    hp = SplitHyperparams(min_data_in_leaf=20)
    _, sums, small_left, parent = _scan_operands(one_chip)
    c = _compile(
        lambda b, v, s, su, sl, ph: fused_frontier_splits(
            b, v, s, K, B, su, sl, ph, nb, mt, db, hp),
        _shape(one_chip, (F, ROWS_1M), jnp.uint8),
        _shape(one_chip, (3, ROWS_1M), jnp.float32),
        _shape(one_chip, (ROWS_1M,), jnp.int32), sums, small_left, parent)
    assert _kernels(c) == 1


def test_scan_kernels_are_out_of_the_election(one_chip, as_accelerator):
    """The in-kernel scan epilogue — the standalone half and the combined
    kernel alike — has no TPU lowering.  Left to the election
    (``interpret=None``) an accelerator traces no Pallas scan; forced
    (``interpret=False``) the compiler's own error comes out."""
    import jax

    from lightgbm_tpu.ops.fused import (fused_segment_splits,
                                        fused_sibling_scan)
    from lightgbm_tpu.ops.split import SplitHyperparams
    import jax.numpy as jnp
    nb, mt, db = _meta_vectors()
    hp = SplitHyperparams(min_data_in_leaf=20)
    small, sums, small_left, parent = _scan_operands(one_chip)

    def scan(interpret):
        return lambda h, su, sl, ph: fused_sibling_scan(
            h, su, nb, mt, db, hp, small_left=sl, parent_hist=ph,
            interpret=interpret)

    elected = jax.make_jaxpr(scan(None))(small, sums, small_left, parent)
    assert "pallas_call" not in str(elected)
    _compile(scan(None), small, sums, small_left, parent)
    with pytest.raises(NotImplementedError, match="cumsum"):
        _compile(scan(False), small, sums, small_left, parent)
    with pytest.raises(NotImplementedError, match="cumsum"):
        _compile(
            lambda b, v, s, su: fused_segment_splits(
                b, v, s, K, B, su, nb, mt, db, hp, interpret=False),
            _shape(one_chip, (F, ROWS_1M), jnp.uint8),
            _shape(one_chip, (3, ROWS_1M), jnp.float32),
            _shape(one_chip, (ROWS_1M,), jnp.int32),
            _shape(one_chip, (3, K), jnp.float32))


def _higgs_ingest_tables():
    from lightgbm_tpu.ops.ingest import FeatureSpec, IngestTables
    specs = tuple(FeatureSpec(j, j, 1, False, B, j, False) for j in range(F))
    bounds = np.sort(np.random.RandomState(0).rand(F, B - 1)
                     .astype(np.float32), axis=1)
    return IngestTables(specs, bounds, np.full((1, 1), -2, np.int32), F, F,
                        np.dtype(np.uint8))


def test_ingest_kernel_compiles_at_the_planned_tile(one_chip):
    """``plan_ingest_tile`` must return a rung the compiler accepts: the
    1024-row tile the old byte model elected asked for 25.74 MB of scoped
    VMEM against a 16 MB limit."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.ingest import DeviceBinner
    from lightgbm_tpu.ops.planner import plan_ingest_tile
    tables = _higgs_ingest_tables()
    tile = plan_ingest_tile(F, tables.bounds.shape[1], 1, F)
    assert tile is not None and tile["tile_rows"] < 1024
    binner = DeviceBinner(tables, tile["tile_rows"], interpret=False)
    c = _compile(binner._run, _shape(one_chip, (ROWS_1M, F), jnp.float32))
    assert _kernels(c) == 1
    refused = DeviceBinner(tables, 1024, interpret=False)
    with pytest.raises(Exception, match="(?i)vmem|scoped"):
        _compile(refused._run, _shape(one_chip, (ROWS_1M, F), jnp.float32))


def test_ingest_kernel_compiles_with_category_tables(one_chip):
    """The click log's schema: 13 numeric columns and 26 categorical ones
    of 63 kept codes each, at the tile the planner elects for it."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.ingest import (DeviceBinner, FeatureSpec,
                                         IngestTables)
    from lightgbm_tpu.ops.planner import plan_ingest_tile
    f, codes = 39, 63
    specs = tuple(FeatureSpec(j, j, 1, j >= 13, codes,
                              j - 13 if j >= 13 else j, False)
                  for j in range(f))
    rs = np.random.RandomState(0)
    bounds = np.sort(rs.rand(13, codes - 1).astype(np.float32), axis=1)
    cats = np.stack([rs.permutation(10_000_000)[:codes]
                     for _ in range(26)]).astype(np.int32)
    tables = IngestTables(specs, bounds, cats, f, f, np.dtype(np.uint8))
    tile = plan_ingest_tile(f, bounds.shape[1], codes, f)
    assert tile is not None
    binner = DeviceBinner(tables, tile["tile_rows"], interpret=False)
    c = _compile(binner._run, _shape(one_chip, (ROWS_1M, f), jnp.float32))
    assert _kernels(c) == 1


@pytest.fixture(scope="module")
def forest():
    """A 500-tree x 255-leaf forest (a few trained trees, tiled)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.predict import StackedForest
    rng = np.random.RandomState(0)
    X = rng.rand(20000, F).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    bst = lgb.train(dict(objective="binary", num_leaves=LEAVES, max_bin=63,
                         min_data_in_leaf=5, verbosity=-1),
                    lgb.Dataset(X, label=y), num_boost_round=2,
                    verbose_eval=False)
    return StackedForest([bst.models[i % 2] for i in range(500)])


def test_fused_traversal_is_out_of_the_election(one_chip, forest):
    """Mosaic's gather rule refuses the traversal kernel's table gathers,
    so the analytic verdict never elects it; ``fori`` — what is elected —
    compiles, and the forced kernel raises the compiler's error."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops import planner as P
    from lightgbm_tpu.ops import predict_kernels as PK
    from lightgbm_tpu.predict import DeviceForest
    shape = dict(num_trees=500, nodes_dim=LEAVES - 1, leaves_dim=LEAVES,
                 features=F, rows=100_000)
    on_chip = P.plan_predict(accel=True, **shape)
    assert (on_chip.variant, on_chip.elected_by) == ("fori", "analytic")

    dev = DeviceForest(forest, variant="fori", chunk_rows=1 << 16,
                       tile_rows=512)
    X = _shape(one_chip, (1 << 16, F), jnp.float32)
    _compile(lambda x: PK.leaves_fori(dev, x), X)
    with pytest.raises(AssertionError):
        _compile(lambda x: PK.fused_traverse(dev, x, 512, interpret=False), X)


def _no_row_parameter_array(compiled, n):
    """The router decides rows a block at a time: no f32 array of the
    program holds two or more values a row (a per-row parameter table, or
    its transpose; the gradients are one a row)."""
    import re
    sizes = [int(np.prod([int(d) for d in m.split(",")]))
             for m in re.findall(r"\bf32\[([\d,]+)\]", compiled.as_text())]
    return max(sizes) < 2 * n


@pytest.mark.parametrize("f,n", [(F, ROWS_1M), (67, 25_165_824),
                                 (220, 8_388_608)],
                         ids=["higgs", "criteo-quant", "istella-rank"])
def test_rounds_grower_compiles_with_the_offer(one_chip, as_accelerator, f,
                                               n):
    """One whole tree of the rounds grower, int8 gradients, as the chip
    runs it, at HIGGS width and at the benchmark cells' shapes: the root
    pass, then a loop whose round elects the width of its accumulate pass
    from the ``k`` its offer allowed, the route in the same branch — one
    Mosaic kernel a width and the root's, the whole program compiles, and
    no per-row parameter array of all the rows exists."""
    import jax.numpy as jnp

    from lightgbm_tpu.dataset import FeatureMeta
    from lightgbm_tpu.grower import GrowerConfig
    from lightgbm_tpu.grower_rounds import grow_tree_rounds, router_engages
    from lightgbm_tpu.ops.fused import slot_widths
    from lightgbm_tpu.ops.split import SplitHyperparams
    assert router_engages()
    meta = FeatureMeta(num_bin=np.full((f,), B, np.int32),
                       missing_type=np.zeros((f,), np.int32),
                       default_bin=np.zeros((f,), np.int32),
                       most_freq_bin=np.zeros((f,), np.int32),
                       is_categorical=np.zeros((f,), bool), max_num_bin=B)
    cfg = GrowerConfig(num_leaves=LEAVES, num_bins=B + 1, quant=True,
                       quant_bins=4, hist_method="fused",
                       hp=SplitHyperparams(min_data_in_leaf=20))
    rows = _shape(one_chip, (n,), jnp.float32)
    q = _shape(one_chip, (n,), jnp.int8)
    scale = _shape(one_chip, (), jnp.float32)
    c = _compile(
        lambda b, g, h, m, gq, hq, gs, hs: grow_tree_rounds(
            b, g, h, m, meta, cfg, quant_vals=(gq, hq, gs, hs),
            with_stats=True),
        _shape(one_chip, (f, n), jnp.uint8), rows, rows, rows,
        q, q, scale, scale)
    assert _kernels(c) == 1 + len(slot_widths(K))
    assert _no_row_parameter_array(c, n)


@pytest.mark.parametrize("n", [ROWS_1M, 33_554_432],
                         ids=["1m", "criteo-cat"])
def test_rounds_grower_compiles_with_categorical_columns(one_chip,
                                                         as_accelerator, n):
    """The click log's schema (13 numeric + 26 categorical columns, 64
    bins) through one whole tree as the chip runs it, at 1M rows and at
    the cell's: the router form with the candidates' sets on its lanes,
    and the categorical scan (a sort a column) merged into the fused pick
    under int32 histograms."""
    import jax.numpy as jnp

    from lightgbm_tpu.binning import MissingType
    from lightgbm_tpu.dataset import FeatureMeta
    from lightgbm_tpu.grower import GrowerConfig
    from lightgbm_tpu.grower_rounds import grow_tree_rounds, router_engages
    from lightgbm_tpu.ops.fused import slot_widths
    from lightgbm_tpu.ops.split import SplitHyperparams
    assert router_engages()
    f, bins = 39, 64
    is_cat = np.arange(f) >= 13
    meta = FeatureMeta(
        num_bin=np.where(is_cat, bins, bins - 1).astype(np.int32),
        missing_type=np.where(is_cat, int(MissingType.NAN), 0
                              ).astype(np.int32),
        default_bin=np.zeros((f,), np.int32),
        most_freq_bin=np.zeros((f,), np.int32),
        is_categorical=is_cat, max_num_bin=bins)
    cfg = GrowerConfig(num_leaves=LEAVES, num_bins=bins, quant=True,
                       quant_bins=4, hist_method="fused",
                       hp=SplitHyperparams(min_data_in_leaf=1,
                                           min_sum_hessian_in_leaf=100.0))
    rows = _shape(one_chip, (n,), jnp.float32)
    q = _shape(one_chip, (n,), jnp.int8)
    scale = _shape(one_chip, (), jnp.float32)
    c = _compile(
        lambda b, g, h, m, gq, hq, gs, hs: grow_tree_rounds(
            b, g, h, m, meta, cfg, quant_vals=(gq, hq, gs, hs),
            with_stats=True),
        _shape(one_chip, (f, n), jnp.uint8), rows, rows, rows,
        q, q, scale, scale)
    assert _kernels(c) == 1 + len(slot_widths(K))
    assert "lgbm.cat_scan" in c.as_text()
    assert _no_row_parameter_array(c, n)


def test_validation_update_compiles_in_its_path_form(one_chip,
                                                     as_accelerator):
    """One tree applied to ``criteo-quant.monitored``'s validation set
    (2,796,202 x 67 uint8, 255 leaves) as the chip runs it: no Mosaic
    kernel (the benchmark books every one as histogram time), and the
    [L, block] int32 of leaf against row that the path matmul produces is
    consumed where it is made: the program's temporaries stay under one
    block of it."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.grower import (ROUTE_BLOCK_ROWS, TreeArrays,
                                     predict_tree_binned)
    G, n = 67, 2_796_202
    tree = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, a.shape, a.dtype), TreeArrays.empty(LEAVES))
    meta = tuple(_shape(one_chip, (G,), d)
                 for d in (jnp.int32, jnp.int32, jnp.int32, jnp.bool_,
                           jnp.int32, jnp.int32))
    c = _compile(
        lambda vs, t, b, m: vs.at[0].add(predict_tree_binned(
            t, b, None, meta_arrays=m, routed=True)),
        _shape(one_chip, (1, n), jnp.float32), tree,
        _shape(one_chip, (G, n), jnp.uint8), meta)
    assert _kernels(c) == 0
    assert c.memory_analysis().temp_size_in_bytes < LEAVES * ROUTE_BLOCK_ROWS * 4


def test_validation_update_compiles_with_the_metrics(one_chip):
    """``criteo-quant.monitored``'s ``upd`` as the chip runs it: one tree
    applied to 2,796,202 x 67 one-byte bins, then AUC (a stable sort of
    the scores with the classes, cumulative max and min, int32 block sums)
    and logloss (int32 and f32 block sums) reduced from the updated score,
    the labels a parameter of the program."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.boosting.macro import build_chunk_valid
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import FeatureMeta
    from lightgbm_tpu.grower import TreeArrays
    from lightgbm_tpu.metrics import AUCMetric, create_metric
    from lightgbm_tpu.objectives import BinaryLogloss
    G, n = 67, 2_796_202
    meta = FeatureMeta(num_bin=np.full((G,), B + 1, np.int32),
                       missing_type=np.zeros((G,), np.int32),
                       default_bin=np.zeros((G,), np.int32),
                       most_freq_bin=np.zeros((G,), np.int32),
                       is_categorical=np.zeros((G,), bool), max_num_bin=B + 1)
    objective = BinaryLogloss.__new__(BinaryLogloss)
    objective.config = Config()
    booster = SimpleNamespace(num_tree_per_iteration=1, _leaf_routed=True,
                              meta=meta, boosting_type="gbdt",
                              init_scores=[0.0], objective=objective)
    forms = tuple(create_metric(name, Config())
                  for name in ("auc", "binary_logloss"))
    seq = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, (1, 1) + a.shape, a.dtype),
        TreeArrays.empty(LEAVES))
    c = build_chunk_valid(booster, forms).lower(
        _shape(one_chip, (1, n), jnp.float32), seq,
        _shape(one_chip, (G, n), jnp.uint8),
        _shape(one_chip, (1,), jnp.int32), _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (n,), jnp.float32)).compile()
    assert _kernels(c) == 0
    text = c.as_text()
    blocks = -(-n // forms[1].block_rows)
    assert f"s32[{-(-n // AUCMetric.block_rows(n))}]" in text
    assert f"s32[{blocks}]" in text and f"f32[{blocks}]" in text


def test_goss_selection_compiles_without_a_sort(one_chip):
    """``criteo-goss.train``'s selection as the chip runs it in every round
    of the chunk program: 25,165,824 rows, top 20% by |g*h| and exactly
    10% of all rows from the rest, by bisections (``boosting/goss.py``).
    No sort and no kernel: a ``lax.top_k`` over the rows lowers to a sort
    that compiles for half a minute and takes 68 ms a round on the v5e."""
    import jax.numpy as jnp

    from lightgbm_tpu.boosting.goss import make_goss_weights
    n = 25_165_824
    c = _compile(make_goss_weights(n, 0.2, 0.1),
                 _shape(one_chip, (1, n), jnp.float32),
                 _shape(one_chip, (1, n), jnp.float32),
                 _shape(one_chip, (2,), jnp.uint32),
                 _shape(one_chip, (n,), jnp.float32))
    text = c.as_text()
    assert _kernels(c) == 0 and " sort(" not in text
    assert c.memory_analysis().temp_size_in_bytes < 4 * n
