"""Pod-scale parallel plane (parallel/collectives.py, hybrid ICI x DCN mesh).

The hard contracts, on the virtual 8-device CPU mesh (conftest.py):

- hierarchical (2-tier) reduction == flat psum — BYTE-identical model
  text for quantized payloads across {2x4, 4x2} simulated slice shapes
  (integer associativity), and f32 model-text-identical under the pinned
  tier-ordered reduction (LGBM_TPU_PINNED_REDUCE);
- voting-parallel's DCN bytes sit strictly below data-parallel's at
  equal trees on the same workload (ops/planner.plan_collectives);
- a preempted slice (seeded chaos over the allgather seam) resumes from
  the latest verified checkpoint bundle on a re-planned SMALLER mesh
  with eval history intact (resilience/elastic.py).
"""

import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.planner import plan_collectives
from lightgbm_tpu.parallel import network as net
from lightgbm_tpu.parallel.collectives import (DCN_AXIS, HYBRID_AXES,
                                               ICI_AXIS, axis_index_flat,
                                               axis_size, psum_int_tiered,
                                               psum_tiered)
from lightgbm_tpu.parallel.learners import (DATA_AXIS, data_axis_of,
                                            make_hybrid_mesh, make_mesh)
from lightgbm_tpu.resilience import (ChaosRegistry, ResilienceConfig,
                                     SliceLostError, apply_world,
                                     membership_probe, plan_shrunk_world,
                                     shrink_and_resume)
from lightgbm_tpu.parallel.dist_data import make_fake_allgather

pytestmark = pytest.mark.multihost

RNG = np.random.RandomState(7)
# n NOT divisible by 8 on purpose: every mesh width pads differently, so
# the elastic resume's row re-tiling (gbdt.restore_state) is exercised
N, F = 1201, 10
X = RNG.randn(N, F).astype(np.float32)
Y = (X[:, 0] + 0.5 * X[:, 3] ** 2 + 0.1 * RNG.randn(N) > 0.5).astype(
    np.float32)
XV = RNG.randn(301, F).astype(np.float32)
YV = (XV[:, 0] + 0.5 * XV[:, 3] ** 2 > 0.5).astype(np.float32)

BASE = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
        "max_bin": 63, "min_data_in_leaf": 5, "verbosity": -1,
        "tree_learner": "data"}
QUANT = {"use_quantized_grad": True, "num_grad_quant_bins": 16}


def _train(monkeypatch, *, slices=0, hier=None, pinned=False, rounds=8,
           extra=None):
    """One engine run under the given simulated-slice topology; returns
    (model_text, booster)."""
    for k in ("LGBM_TPU_NUM_SLICES", "LGBM_TPU_HIER_REDUCE",
              "LGBM_TPU_PINNED_REDUCE"):
        monkeypatch.delenv(k, raising=False)
    if slices:
        monkeypatch.setenv("LGBM_TPU_NUM_SLICES", str(slices))
    if hier is not None:
        monkeypatch.setenv("LGBM_TPU_HIER_REDUCE", "1" if hier else "0")
    if pinned:
        monkeypatch.setenv("LGBM_TPU_PINNED_REDUCE", "1")
    params = dict(BASE, **(extra or {}))
    ds = lgb.Dataset(X, label=Y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=rounds, verbose_eval=False)
    return bst.model_to_string(), bst


# ---------------------------------------------------------------- mesh


def test_make_hybrid_mesh_shapes():
    for s in (2, 4):
        mesh = make_hybrid_mesh(8, num_slices=s)
        assert mesh.axis_names == HYBRID_AXES
        assert int(mesh.shape[DCN_AXIS]) == s
        assert int(mesh.shape[ICI_AXIS]) == 8 // s
        assert data_axis_of(mesh) == HYBRID_AXES
        assert axis_size(mesh, HYBRID_AXES) == 8
        # row-major over (slice, device-in-slice): same linear device
        # order as the flat mesh, so shard CONTENTS never move when the
        # hybrid mesh is elected (the parity tests lean on this)
        flat = make_mesh(8, (DATA_AXIS,))
        assert [d.id for d in mesh.devices.ravel()] \
            == [d.id for d in flat.devices.ravel()]
    assert data_axis_of(make_mesh(8, (DATA_AXIS,))) == DATA_AXIS


def test_make_hybrid_mesh_rejects_non_dividing():
    with pytest.raises(ValueError, match="partition"):
        make_hybrid_mesh(8, num_slices=3)


def test_mesh_plan_priority(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_NUM_SLICES", raising=False)
    monkeypatch.delenv("LGBM_TPU_SLICE_DEVICES", raising=False)
    flat = net.mesh_plan(8)
    assert (flat.num_slices, flat.total_shards, flat.hybrid) == (1, 8, False)
    assert flat.source == "flat"
    # simulated slices env
    monkeypatch.setenv("LGBM_TPU_NUM_SLICES", "2")
    mp = net.mesh_plan(8)
    assert (mp.num_slices, mp.devices_per_slice, mp.source) == (2, 4, "env")
    # env additionally bounded by per-slice device count: the elastic
    # shrink's way to express a smaller surviving world
    monkeypatch.setenv("LGBM_TPU_SLICE_DEVICES", "2")
    mp = net.mesh_plan(8)
    assert (mp.num_slices, mp.devices_per_slice, mp.total_shards) \
        == (2, 2, 4)
    monkeypatch.delenv("LGBM_TPU_NUM_SLICES")
    monkeypatch.delenv("LGBM_TPU_SLICE_DEVICES")
    # num_machines steers the DCN tier when it divides the device count
    mp = net.mesh_plan(8, num_machines=4)
    assert (mp.num_slices, mp.devices_per_slice, mp.source) \
        == (4, 2, "num_machines")
    # ... and degrades to a flat capped mesh (loudly) when it doesn't
    mp = net.mesh_plan(8, num_machines=3)
    assert (mp.num_slices, mp.total_shards) == (1, 3)


def test_mesh_plan_mismatch_warns(monkeypatch, capsys):
    # a verbosity=-1 run earlier in the session silences warnings
    # globally; the loud-mismatch contract is about the DEFAULT level
    monkeypatch.setattr("lightgbm_tpu.utils.log._current_level", 1)
    monkeypatch.setenv("LGBM_TPU_NUM_SLICES", "2")
    mp = net.mesh_plan(8, num_machines=5, local_listen_port=12399)
    assert mp.num_slices == 2
    err = capsys.readouterr().err
    assert "num_machines=5 disagrees" in err
    assert "12399" in err


def test_init_network_roundtrips_into_mesh_plan(monkeypatch):
    # a single-machine non-dry-run call records itself without touching
    # jax.distributed; mesh_plan then consults the recorded call
    assert net.last_network_init() is None or True  # state may linger
    net.init_network(machines="127.0.0.1:12400", num_machines=1,
                     local_listen_port=12400)
    rec = net.last_network_init()
    assert rec is not None and rec["num_machines"] == 1
    assert rec["local_listen_port"] == 12400
    net.free_network()
    assert net.last_network_init() is None
    # mesh_plan falls back to the recorded init when no explicit
    # num_machines is passed
    monkeypatch.delenv("LGBM_TPU_NUM_SLICES", raising=False)
    monkeypatch.setattr(net, "_LAST_INIT",
                        {"num_machines": 4, "local_listen_port": 12401})
    mp = net.mesh_plan(8)
    assert (mp.num_slices, mp.source) == (4, "num_machines")


def test_create_parallel_grower_mismatch_warns(monkeypatch, capsys):
    monkeypatch.setattr("lightgbm_tpu.utils.log._current_level", 1)
    from lightgbm_tpu.dataset import FeatureMeta
    from lightgbm_tpu.grower import GrowerConfig
    from lightgbm_tpu.ops.split import SplitHyperparams
    from lightgbm_tpu.parallel.learners import create_parallel_grower
    meta = FeatureMeta(num_bin=np.full(F, 16, np.int32),
                       missing_type=np.zeros(F, np.int32),
                       default_bin=np.zeros(F, np.int32),
                       most_freq_bin=np.zeros(F, np.int32),
                       is_categorical=np.zeros(F, bool), max_num_bin=16)
    cfg = GrowerConfig(num_leaves=7, hp=SplitHyperparams(), num_bins=16,
                       num_machines=5)
    create_parallel_grower("data", make_mesh(8, (DATA_AXIS,)), meta, cfg)
    assert "num_machines=5 disagrees" in capsys.readouterr().err


# ---------------------------------------------------- collective prims


@pytest.mark.parametrize("slices", [2, 4])
def test_tiered_psum_matches_flat(slices):
    mesh = make_hybrid_mesh(8, num_slices=slices)
    xf = np.arange(8 * 24, dtype=np.float32).reshape(8, 24) * 0.37
    xi = np.arange(8 * 24, dtype=np.int32).reshape(8, 24) - 91

    def run(body, arr):
        f = jax.shard_map(body, mesh=mesh, in_specs=(P(HYBRID_AXES),),
                             out_specs=P(HYBRID_AXES), check_vma=False)
        return np.asarray(jax.jit(f)(jnp.asarray(arr)))

    flat_f = run(lambda v: psum_tiered(v, HYBRID_AXES), xf)
    hier_f = run(lambda v: psum_tiered(v, HYBRID_AXES, hierarchical=True),
                 xf)
    np.testing.assert_allclose(hier_f, flat_f, rtol=1e-6)
    np.testing.assert_allclose(flat_f[0], xf.sum(axis=0), rtol=1e-6)
    # pinned: flat and hierarchical arms share ONE tier-ordered
    # association, so they agree bitwise
    pin_flat = run(lambda v: psum_tiered(v, HYBRID_AXES, pinned=True), xf)
    pin_hier = run(lambda v: psum_tiered(v, HYBRID_AXES, hierarchical=True,
                                         pinned=True), xf)
    np.testing.assert_array_equal(pin_flat, pin_hier)
    # integers: exact under every schedule, narrowed or not
    flat_i = run(lambda v: psum_int_tiered(v, HYBRID_AXES), xi)
    hier_i = run(lambda v: psum_int_tiered(v, HYBRID_AXES,
                                           hierarchical=True), xi)
    nar_i = run(lambda v: psum_int_tiered(v, HYBRID_AXES, hierarchical=True,
                                          narrow=jnp.int16), xi)
    np.testing.assert_array_equal(flat_i, hier_i)
    np.testing.assert_array_equal(flat_i, nar_i)
    np.testing.assert_array_equal(flat_i[0], xi.sum(axis=0))
    assert nar_i.dtype == np.int32          # widened back after the wire


def test_axis_index_flat_is_linear_rank():
    mesh = make_hybrid_mesh(8, num_slices=2)

    def body(v):
        return v + axis_index_flat(HYBRID_AXES)

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(HYBRID_AXES),),
                         out_specs=P(HYBRID_AXES), check_vma=False)
    got = np.asarray(jax.jit(f)(jnp.zeros(8, jnp.int32)))
    np.testing.assert_array_equal(got, np.arange(8))


# -------------------------------------------------------- planner model


def test_plan_collectives_elects_hierarchical_on_slow_dcn():
    plan = plan_collectives(features=28, num_bins=64, rows_global=10**6,
                            num_slices=2, devices_per_slice=4,
                            ici_gbps=100.0, dcn_gbps=5.0)
    assert plan.hierarchical and plan.elected == "hierarchical"
    assert plan.dcn_bytes == plan.payload_bytes       # pre-aggregated once
    assert plan.flat_dcn_bytes == plan.payload_bytes * 4
    s = plan.summary()
    assert s["mesh_shape"] == [2, 4] and s["hierarchy_elected"]


def test_plan_collectives_flat_cases(monkeypatch):
    # single tier: nothing to elect
    p1 = plan_collectives(features=28, num_bins=64, rows_global=1000,
                          num_slices=1, devices_per_slice=8)
    assert not p1.hierarchical and p1.dcn_bytes == 0
    # forced flat on a hybrid mesh
    monkeypatch.setenv("LGBM_TPU_HIER_REDUCE", "0")
    p2 = plan_collectives(features=28, num_bins=64, rows_global=1000,
                          num_slices=2, devices_per_slice=4)
    assert not p2.hierarchical and p2.elected == "flat"
    assert p2.dcn_bytes == p2.flat_dcn_bytes


def test_plan_collectives_voting_shrinks_dcn():
    kw = dict(features=28, num_bins=64, rows_global=10**6, num_slices=2,
              devices_per_slice=4, ici_gbps=100.0, dcn_gbps=5.0)
    data = plan_collectives(**kw)
    vote = plan_collectives(voting_k=8, **kw)
    assert vote.elected == "hierarchical+voting"
    assert vote.dcn_bytes < data.dcn_bytes       # the acceptance signal
    assert vote.ici_bytes == data.ici_bytes      # full hist still on ICI
    # quantized payloads narrow the wire on BOTH tiers
    quant = plan_collectives(quant=True, quant_bins=16, **kw)
    assert quant.payload_bytes < data.payload_bytes


# ------------------------------------------- end-to-end model parity


@pytest.mark.parametrize("slices", [2, 4])
def test_quant_hierarchical_equals_flat_byte_identical(monkeypatch, slices):
    """Integer histograms are associative, so the tiered schedule must
    change NOTHING: flat single-tier == hierarchical {2x4, 4x2}, byte
    for byte, without pinning."""
    flat, _ = _train(monkeypatch, slices=0, extra=QUANT)
    hier, bst = _train(monkeypatch, slices=slices, hier=True, extra=QUANT)
    assert bst.boosting.collective_plan is not None
    assert bst.boosting.collective_plan.hierarchical
    assert hier == flat
    # and forcing the flat schedule on the SAME hybrid mesh agrees too
    hier_off, _ = _train(monkeypatch, slices=slices, hier=False,
                         extra=QUANT)
    assert hier_off == flat


@pytest.mark.parametrize("slices", [2, 4])
def test_f32_pinned_hier_equals_flat_model_text(monkeypatch, slices):
    """f32 sums are not associative; the pinned tier-ordered reduction
    (all_gather + fixed-order sum per tier) IS the pinned order under
    which hierarchical == flat extends to f32 model text."""
    a, bst = _train(monkeypatch, slices=slices, hier=True, pinned=True)
    b, _ = _train(monkeypatch, slices=slices, hier=False, pinned=True)
    assert bst.boosting.collective_plan.pinned
    assert a == b


def test_voting_hybrid_trains_and_shrinks_dcn(monkeypatch):
    text, bst = _train(monkeypatch, slices=2,
                       extra={"tree_learner": "voting", "top_k": 6})
    plan = bst.boosting.collective_plan
    assert plan is not None and plan.voting_k == 6
    assert plan.elected == "hierarchical+voting"
    assert plan.dcn_bytes < plan.payload_bytes
    p = bst.predict(XV)
    assert np.isfinite(p).all()
    # obs satellites: the two-hop ladder's per-tier payload gauges
    from lightgbm_tpu.obs.metrics import global_registry
    gauges = global_registry.to_dict()["gauges"]
    assert int(gauges["train_ici_payload_bytes"]) == plan.ici_bytes
    assert int(gauges["train_dcn_payload_bytes"]) == plan.dcn_bytes


def test_collective_reduce_spans_show_two_hop_ladder(monkeypatch):
    """A traced hierarchical run's trace shows one collective.reduce
    span per tier (docs/OBSERVABILITY.md) — the two-hop ladder."""
    from lightgbm_tpu.obs.trace import global_tracer
    global_tracer.reset()
    global_tracer.enable()
    try:
        _train(monkeypatch, slices=2, hier=True, rounds=2)
        events = global_tracer.events()
    finally:
        global_tracer.disable()
        global_tracer.reset()
    tiers = {e.get("args", {}).get("tier") for e in events
             if e.get("name") == "collective.reduce"}
    assert DCN_AXIS in tiers and ICI_AXIS in tiers


# ------------------------------------------------------ elastic resume


def test_membership_probe_commits_and_detects_loss():
    world = 4
    fake = make_fake_allgather(world, timeout=2.0)

    def run(chaos):
        out, errs = [None] * world, [None] * world

        def runner(k):
            try:
                ag = fake(k)
                if chaos is not None:
                    ag = chaos.wrap_allgather(ag, k)
                out[k] = membership_probe(
                    ag, world=world, rank=k,
                    config=ResilienceConfig(deadline_s=3.0, max_retries=3,
                                            base_backoff_s=0.01))
            except Exception as e:      # noqa: BLE001 — asserted below
                errs[k] = e
        ts = [threading.Thread(target=runner, args=(k,))
              for k in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts), "a rank is HUNG"
        return out, errs

    out, errs = run(None)
    assert errs == [None] * world
    assert all(o == [0, 1, 2, 3] for o in out)

    # seeded chaos kills rank 2's transport for good: every SURVIVOR
    # sees a rank-consistent SliceLostError instead of a hang
    dead = ",".join(f"allgather.stall@{i}:rank=2:sec=60" for i in range(40))
    fake = make_fake_allgather(world, timeout=0.4)
    out, errs = run(ChaosRegistry(dead, seed=3))
    assert all(isinstance(e, SliceLostError) for k, e in enumerate(errs)
               if k != 2), errs


def test_plan_shrunk_world():
    plan = plan_shrunk_world(4, 2, lost_slices=2)
    assert (plan.num_slices, plan.devices_per_slice, plan.total_shards) \
        == (2, 2, 4)
    assert plan.source == "elastic"
    with pytest.raises(SliceLostError):
        plan_shrunk_world(2, 4, lost_slices=2)


def test_elastic_shrink_resume_end_to_end(monkeypatch, tmp_path):
    """The full rejoin: 4x2 world trains with snapshots; a slice loss is
    detected (chaos-killed membership probe); the survivors re-plan a
    2x2 world and resume from the latest VERIFIED bundle — the model
    stays valid, eval history survives, and the new bundle's manifest
    records the re-planned (re-tiled) per-shard plan.

    stochastic_rounding is OFF: each shard folds its axis index into the
    rounding key (i.i.d. noise across shards), so stochastic quant is
    deliberately world-size-DEPENDENT; deterministic quant is the mode
    whose trees are mesh-invariant, which the byte-parity coda needs."""
    monkeypatch.setenv("LGBM_TPU_NUM_SLICES", "4")
    monkeypatch.setenv("LGBM_TPU_SLICE_DEVICES", "2")
    params = dict(BASE, stochastic_rounding=False, **QUANT)
    out = str(tmp_path / "model.txt")
    ev1 = {}
    ds = lgb.Dataset(X, label=Y, free_raw_data=False)
    dv = lgb.Dataset(XV, label=YV, reference=ds, free_raw_data=False)
    bst1 = lgb.train(params, ds, num_boost_round=6, valid_sets=[dv],
                     valid_names=["v"], snapshot_freq=2, snapshot_out=out,
                     verbose_eval=False,
                     callbacks=[lgb.record_evaluation(ev1)])
    assert bst1.boosting.collective_plan.summary()["mesh_shape"] == [4, 2]
    ckdir = out + ".ckpt"

    # ---- "mid-training" slice loss: rank 1's transport dies; the
    # membership probe's rank-consistent verdict IS the shrink decision
    world = 4
    dead = ",".join(f"allgather.stall@{i}:rank=1:sec=60" for i in range(40))
    chaos = ChaosRegistry(dead, seed=11)
    fake = make_fake_allgather(world, timeout=0.4)
    errs = [None] * world

    def runner(k):
        try:
            membership_probe(
                chaos.wrap_allgather(fake(k), k), world=world, rank=k,
                config=ResilienceConfig(deadline_s=3.0, max_retries=3,
                                        base_backoff_s=0.01))
        except Exception as e:          # noqa: BLE001 — asserted below
            errs[k] = e
    ts = [threading.Thread(target=runner, args=(k,)) for k in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert isinstance(errs[0], SliceLostError)

    # ---- shrink + resume on the 2-slice survivor world
    ev2 = {}
    bst2 = shrink_and_resume(
        params, lgb.Dataset(X, label=Y, free_raw_data=False), ckdir,
        num_slices=4, devices_per_slice=2, lost_slices=2,
        num_boost_round=10,
        valid_sets=[lgb.Dataset(XV, label=YV, free_raw_data=False)],
        valid_names=["v"], snapshot_freq=2, snapshot_out=out,
        verbose_eval=False, callbacks=[lgb.record_evaluation(ev2)])
    assert bst2.current_iteration() == 10
    assert bst2.boosting.collective_plan.summary()["mesh_shape"] == [2, 2]
    p = bst2.predict(XV)
    assert np.isfinite(p).all()
    # eval history survives the shrink: the restored prefix is the old
    # world's, byte-equal in quantized mode (hier==flat==any mesh)
    h1 = ev1["v"]["binary_logloss"]
    h2 = ev2["v"]["binary_logloss"]
    assert len(h2) == 10 and h2[:6] == h1
    # the new bundle's manifest records the re-planned per-shard world
    from lightgbm_tpu.resilience.checkpoint import CheckpointManager
    ck = CheckpointManager(ckdir).latest_verified()
    assert ck.iteration == 10
    assert ck.manifest["collective_plan"]["mesh_shape"] == [2, 2]
    assert ck.manifest["hist_plan"] is not None
    # quant mode: the shrunk-world continuation is byte-identical to
    # training 10 rounds on the small world from scratch (re-tiling is
    # exact and integer reductions are mesh-invariant)
    monkeypatch.setenv("LGBM_TPU_NUM_SLICES", "2")
    ds3 = lgb.Dataset(X, label=Y, free_raw_data=False)
    bst3 = lgb.train(params, ds3, num_boost_round=10, verbose_eval=False)
    assert bst2.model_to_string() == bst3.model_to_string()


# ------------------------------------------------------------- stress


@pytest.mark.slow
def test_two_slice_stress_voting_quant(monkeypatch):
    """2-slice stress on a larger workload: the quantized DATA learner
    stays byte-identical across the flat and hierarchical schedules, and
    the hierarchical VOTING learner — per-SLICE election is a genuinely
    different (DCN-cheaper) schedule, so no byte parity is claimed for
    it — still trains a usable model with the DCN payload shrunk."""
    rng = np.random.RandomState(3)
    n = 20_000
    Xl = rng.randn(n, 24).astype(np.float32)
    yl = (Xl[:, 0] * Xl[:, 1] + Xl[:, 2] + 0.1 * rng.randn(n) > 0).astype(
        np.float32)

    def run(learner, hier, extra=None):
        monkeypatch.setenv("LGBM_TPU_NUM_SLICES", "2")
        monkeypatch.setenv("LGBM_TPU_HIER_REDUCE", "1" if hier else "0")
        params = dict(BASE, tree_learner=learner, num_leaves=31,
                      **QUANT, **(extra or {}))
        ds = lgb.Dataset(Xl, label=yl, free_raw_data=False)
        return lgb.train(params, ds, num_boost_round=20,
                         verbose_eval=False)

    a = run("data", True)
    b = run("data", False)
    assert a.model_to_string() == b.model_to_string()
    v = run("voting", True, {"top_k": 8})
    plan = v.boosting.collective_plan
    assert plan.elected == "hierarchical+voting"
    assert plan.dcn_bytes < plan.payload_bytes
    pred = v.predict(Xl[:2000])
    acc = np.mean((pred > 0.5) == (yl[:2000] > 0.5))
    assert acc > 0.7
