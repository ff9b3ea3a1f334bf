"""Distributed tree learners over a jax.sharding.Mesh.

TPU-native replacement for the reference's distributed learners
(src/treelearner/{feature,data,voting}_parallel_tree_learner.cpp) and the
whole src/network/ transport/topology layer: the three reduction points —
histogram reduce-scatter, best-split sync, scalar sums — become
`lax.psum`/`lax.all_gather` inside the jitted grow step over ICI, selected
by how the Mesh axes shard the data:

- data parallel: rows sharded over axis "data"; histograms psum'd; every
  device then finds the identical best split (the reference's
  ReduceScatter + per-machine ownership + best-split allreduce,
  data_parallel_tree_learner.cpp:149-241, collapses into one psum).
- feature parallel: features sharded over axis "feature"; local best splits
  merged by all_gather+argmax (SyncUpGlobalBestSplit,
  parallel_tree_learner.h:190), partition mask broadcast by psum.
- 2-D: both at once (not expressible in the reference at all).

With ``use_quantized_grad`` the data- and voting-parallel reductions move
INTEGER histograms (``ops.histogram.psum_quant_hist`` inside the growers):
[2, F, B] i32 — 8 bytes/cell vs the f32 path's 12 — narrowed to int16
(4 bytes/cell) when the static rows x quant-level bound proves overflow
impossible, so the ICI payload shrinks with the quantization width
(``ops.histogram.hist_payload_bytes`` is the accounting twin).

The factory mirrors CreateTreeLearner (src/treelearner/tree_learner.cpp:13).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..dataset import FeatureMeta
from ..grower import GrowerConfig, TreeArrays, grow_tree
from .collectives import (DCN_AXIS, HYBRID_AXES, ICI_AXIS,  # noqa: F401
                          axis_size)

DATA_AXIS = "data"
FEATURE_AXIS = "feature"

# a data axis may be ONE mesh axis ("data", the historical single-tier
# layout) or the hybrid outermost-first tuple ("dcn", "ici") of
# make_hybrid_mesh — every helper below accepts both
DataAxis = Union[str, Tuple[str, ...]]


def pad_rows_to(n: int, devices: int) -> int:
    return (n + devices - 1) // devices * devices


def fused_best_payload_bytes(num_features: int) -> int:
    """Bytes of ONE per-feature-best tuple set (the fused megakernel's
    writeback: gain, bin, direction, left grad/hess/count — 6 cells × F,
    ops/fused.py) — what a collective would move if it exchanged
    candidates instead of histograms: F·~6 cells vs the histogram
    payload's F·B·ch (``ops.histogram.hist_payload_bytes``).  Pure
    accounting; the EXACT data-parallel reduction still psums histograms
    (gains are not summable across shards — the same reason
    voting-parallel exchanges elected candidates, PV-Tree).  This is the
    DCN/ICI headroom figure the voting/fused combination targets."""
    return 6 * num_features * 4


def make_sharded_grower(
    mesh: Mesh,
    meta: FeatureMeta,
    cfg: GrowerConfig,
    data_axis: Optional[DataAxis] = DATA_AXIS,
    feature_axis: Optional[str] = None,
    auto_plan: bool = True,
):
    """Build a jitted sharded grow-tree callable.

    Inputs must be sharded/padded by the caller:
      binned_t [F_pad, n_pad] (feature-major), grad/hess/row_mask [n_pad]
    (pad rows with row_mask = 0; pad features with trivial bins).
    Returns fn(binned_t, grad, hess, row_mask) -> (TreeArrays, leaf_id).

    ``auto_plan``: when ``cfg.tile_rows`` is unset (0), run the HBM
    budget planner (ops/planner.py) at trace time over the PER-SHARD
    shapes, so the standalone learners obey the same memory verdict as
    engine-driven training (row tiling, record-arena hoisting).
    """
    if feature_axis and meta.resolved().has_bundles \
            and cfg.num_feature_shards <= 1:
        raise NotImplementedError(
            "feature-axis sharding over EFB bundles requires the shard-major "
            "group layout (GBDT._build_group_sharding); train through the "
            "engine (lgb.train with tree_learner=feature) or disable "
            "bundling for this standalone grower")
    if cfg.hist_method == "fused" and feature_axis:
        # recorded design exclusion: under FEATURE sharding each shard
        # owns different columns and the winner is elected by a pmax
        # gather over per-shard SplitResults — the fused kernel's
        # in-kernel scan + writeback layout doesn't ride that exchange,
        # so feature-parallel growth stays on the staged family.  DATA
        # sharding keeps fused: the rounds grower splits the kernel at
        # the collective seam (accumulate → psum of the smaller-child
        # hists → sibling-derive + scan on the reduced arena,
        # grower_rounds.py) — gains never cross the wire, exactly like
        # the staged arm.
        from ..utils.log import log_info
        log_info("hist_method=fused is not a feature-parallel arm (the "
                 "winner exchange moves SplitResults, not histograms); "
                 "feature-sharded growth uses the staged kernel family")
        cfg = cfg._replace(hist_method="auto")
    row_spec = P(data_axis) if data_axis else P()
    binned_spec = (P(feature_axis, data_axis) if feature_axis
                   else P(None, data_axis))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(binned_spec, row_spec, row_spec, row_spec),
        out_specs=(P(), row_spec),
        check_vma=False,
    )
    def sharded(binned_t, grad, hess, row_mask):
        run_cfg = cfg
        if auto_plan and cfg.tile_rows == 0:
            # trace-time planning over the local (per-shard) shapes —
            # binned_t here is already the device slice
            from ..ops.planner import apply_plan
            run_cfg, plan = apply_plan(cfg, int(binned_t.shape[1]),
                                       int(binned_t.shape[0]),
                                       fused_ok=(feature_axis is None))
            if not plan.feasible:
                from ..utils.log import log_warning
                log_warning(
                    "HBM planner: predicted peak "
                    f"{plan.predicted_peak_bytes / 1e9:.2f} GB exceeds "
                    f"the {plan.budget_bytes / 1e9:.2f} GB budget even "
                    f"at tile_rows={plan.tile_rows}; training may OOM "
                    "(LGBM_TPU_HBM_BYTES / LGBM_TPU_TILE_ROWS override)")
        out = grow_tree(
            binned_t, grad, hess, row_mask, meta, run_cfg,
            axis_name=data_axis, feature_axis_name=feature_axis)
        # CEGB-enabled configs return (tree, leaf_id, cegb_state); this
        # standalone grower drops the cross-tree state (single-tree API)
        return out[0], out[1]

    return jax.jit(sharded)


def shard_dataset(mesh: Mesh, binned: np.ndarray, *row_arrays,
                  data_axis: DataAxis = DATA_AXIS):
    """Pad rows to the data-axis size and place arrays on the mesh.

    ``binned`` is the HOST row-major [n, F] matrix; the device copy is
    feature-major [F, n_pad] (ops/histogram.py LAYOUT DOCTRINE).
    ``data_axis`` may be the hybrid ``("dcn", "ici")`` tuple: rows then
    shard over BOTH tiers in the mesh's row-major device order — an
    elastic re-tile after a slice loss is just this call over the
    re-planned smaller mesh (docs/RESILIENCE.md)."""
    ndev = axis_size(mesh, data_axis)
    n = binned.shape[0]
    n_pad = pad_rows_to(n, ndev)
    out = []
    b = np.ascontiguousarray(np.pad(binned, ((0, n_pad - n), (0, 0))).T)
    out.append(jax.device_put(b, NamedSharding(mesh, P(None, data_axis))))
    for arr in row_arrays:
        a = np.pad(np.asarray(arr), (0, n_pad - n))
        out.append(jax.device_put(a, NamedSharding(mesh, P(data_axis))))
    return out, n_pad


def put_stacked_rows(mesh: Mesh, data_axis: DataAxis,
                     stacked: jax.Array) -> jax.Array:
    """Place a ``[c, n_pad]`` stack of per-iteration row arrays (bagging /
    GOSS masks for a fused macro-step chunk, boosting/macro.py) with the
    ROW axis sharded like every other per-row array, so the chunk scan's
    per-step slices feed shard_map without a cross-device gather."""
    return jax.device_put(stacked, NamedSharding(mesh, P(None, data_axis)))


def make_mesh(n_devices: Optional[int] = None,
              axes: Tuple[str, ...] = (DATA_AXIS,),
              shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axes) - 1)
    arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, axes)


def simulated_slices() -> int:
    """``LGBM_TPU_NUM_SLICES``: simulated DCN slice count for
    single-process runs (the whole hybrid plane then exercises under
    ``--xla_force_host_platform_device_count=N`` on CPU); 0/unset = no
    simulation."""
    v = os.environ.get("LGBM_TPU_NUM_SLICES", "").strip()
    try:
        return max(int(v), 0) if v else 0
    except ValueError:
        return 0


def make_hybrid_mesh(n_devices: Optional[int] = None,
                     num_slices: Optional[int] = None) -> Mesh:
    """Two-axis ``("dcn", "ici")`` mesh: slices over the slow cross-host
    tier, each slice's devices over the fast ICI tier.

    Real multi-host (``jax.distributed`` initialized): one slice per
    process, its local devices on the ICI axis — the physical topology.
    Single-process: ``num_slices`` (or LGBM_TPU_NUM_SLICES) PARTITIONS
    the local devices into simulated slices; the collectives then
    exercise the exact tiered reduction schedule the pod would run.
    Device order is row-major over (slice, device-in-slice) — the same
    linear order as the flat single-axis mesh, so flat and hybrid
    shardings place identical row blocks on identical devices (the
    bit-parity tests lean on this).
    """
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    nd = len(devs)
    if num_slices is None:
        num_slices = (jax.process_count() if jax.process_count() > 1
                      else simulated_slices()) or 1
    s = max(int(num_slices), 1)
    if nd % s != 0:
        raise ValueError(
            f"cannot partition {nd} devices into {s} slices; "
            f"num_slices must divide the device count")
    arr = np.asarray(devs).reshape(s, nd // s)
    return Mesh(arr, HYBRID_AXES)


def data_axis_of(mesh: Mesh) -> DataAxis:
    """The row-sharding axis spec for ``mesh``: the hybrid tuple when the
    mesh carries the ("dcn", "ici") axes, else the flat "data" axis."""
    if DCN_AXIS in mesh.axis_names and ICI_AXIS in mesh.axis_names:
        return HYBRID_AXES
    return DATA_AXIS


def _hybrid_cfg(cfg: GrowerConfig, mesh: Mesh,
                data_axis: DataAxis) -> GrowerConfig:
    """Thread the hybrid mesh's shape + the planner's reduction election
    into the grower config (no-op on a flat mesh)."""
    if data_axis != HYBRID_AXES:
        return cfg
    total = axis_size(mesh, data_axis)
    slices = int(mesh.shape[DCN_AXIS])
    if cfg.num_machines <= 1 or cfg.num_machines != total:
        cfg = cfg._replace(num_machines=total)
    from ..ops.planner import plan_collectives
    plan = plan_collectives(
        features=0, num_bins=cfg.num_bins, rows_global=0,
        quant=cfg.quant, quant_bins=cfg.quant_bins,
        num_slices=slices, devices_per_slice=total // slices,
        voting_k=cfg.voting_top_k)
    return cfg._replace(num_slices=slices,
                        hier_reduce=plan.hierarchical,
                        pinned_reduce=plan.pinned)


def create_parallel_grower(tree_learner: str, mesh: Mesh, meta: FeatureMeta,
                           cfg: GrowerConfig):
    """Factory mirroring CreateTreeLearner (tree_learner.cpp:13-36).

    tree_learner: serial | data | feature | voting | data_feature (2-D).
    A hybrid ``make_hybrid_mesh`` mesh routes rows over BOTH tiers and
    threads the tiered-reduction election (ops/planner.plan_collectives)
    into the grower config; when the config carries a ``num_machines``
    that disagrees with the mesh's actual shard count, the mesh wins —
    LOUDLY (the reference would deadlock on such a mismatch; here it
    would silently mis-scale voting's local constraints).
    """
    data_axis = data_axis_of(mesh)
    if tree_learner in ("data", "voting", "data_parallel",
                        "voting_parallel", "data_feature", "2d"):
        shards = axis_size(mesh, data_axis)
        if cfg.num_machines > 1 and cfg.num_machines != shards:
            from ..utils.log import log_warning
            log_warning(
                f"num_machines={cfg.num_machines} disagrees with the "
                f"mesh's actual data-shard count ({shards}); using the "
                "mesh — fix num_machines (or the machine list) so the "
                "configured world matches the devices actually present")
            cfg = cfg._replace(num_machines=shards)
    if tree_learner in ("data", "data_parallel"):
        cfg = _hybrid_cfg(cfg, mesh, data_axis)
        return make_sharded_grower(mesh, meta, cfg, data_axis=data_axis,
                                   feature_axis=None)
    if tree_learner in ("feature", "feature_parallel"):
        return make_sharded_grower(mesh, meta, cfg, data_axis=None,
                                   feature_axis=FEATURE_AXIS)
    if tree_learner in ("voting", "voting_parallel"):
        # real PV-Tree voting (reference voting_parallel_tree_learner.cpp),
        # consistent with the GBDT engine path: the grower runs its top-k
        # vote + elected-features-only psum when voting_top_k > 0.  Default
        # top_k mirrors the reference config default (config.h top_k = 20).
        if cfg.voting_top_k <= 0:
            cfg = cfg._replace(voting_top_k=20)
        if cfg.num_machines <= 1:
            cfg = cfg._replace(num_machines=axis_size(mesh, data_axis))
        cfg = _hybrid_cfg(cfg, mesh, data_axis)
        return make_sharded_grower(mesh, meta, cfg, data_axis=data_axis,
                                   feature_axis=None)
    if tree_learner in ("data_feature", "2d"):
        return make_sharded_grower(mesh, meta, cfg, data_axis=DATA_AXIS,
                                   feature_axis=FEATURE_AXIS)
    raise ValueError(f"unknown tree_learner {tree_learner!r}")
