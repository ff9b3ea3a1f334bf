"""Serving subsystem: micro-batched, shape-bucketed inference
(lightgbm_tpu/serving/) — concurrency bit-equality, bucket reuse,
hot-swap under load, deadline/backpressure rejection, graceful drain.

All CPU-runnable under the tier-1 command (conftest forces the CPU
backend); data is generated float32-precise so the "device" backend's
routing-exactness domain applies and serving output must be BIT-equal to
``StackedForest.predict_raw``.
"""

import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serving import (BucketLadder, DeadlineExceeded, QueueFull,
                                  ServerClosed, ServingError)

F = 10


def _f32_data(rng, n, f=F):
    """float64 data whose values are exactly float32-representable."""
    return rng.randn(n, f).astype(np.float32).astype(np.float64)


def _train(n=1500, rounds=12, leaves=15, seed=0, num_class=None):
    rng = np.random.RandomState(seed)
    X = _f32_data(rng, n)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": leaves}
    if num_class:
        params.update({"objective": "multiclass", "num_class": num_class})
        y = rng.randint(0, num_class, n).astype(float)
    else:
        y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=rounds,
                    verbose_eval=False)
    return bst


@pytest.fixture(scope="module")
def binary_booster():
    return _train()


# ------------------------------------------------------------ bucket ladder


def test_bucket_ladder():
    lad = BucketLadder(8, 1024)
    assert lad.buckets == [8, 16, 32, 64, 128, 256, 512, 1024]
    assert lad.bucket_for(1) == 8
    assert lad.bucket_for(8) == 8
    assert lad.bucket_for(9) == 16
    assert lad.bucket_for(1024) == 1024
    with pytest.raises(ValueError):
        lad.bucket_for(1025)
    # non-power-of-two bounds round up
    assert BucketLadder(6, 100).buckets == [8, 16, 32, 64, 128]


# --------------------------------------------- concurrency + bit-equality


@pytest.mark.parametrize("backend", ["device", "host"])
def test_concurrent_mixed_sizes_bit_equal(binary_booster, backend):
    """N threads x mixed request sizes through the server == direct
    StackedForest.predict_raw, bitwise; batches mix submitters."""
    bst = binary_booster
    sf = bst._forest(0, 12)
    srv = bst.serve(max_batch_rows=256, batch_window_ms=2.0,
                    backend=backend)
    mismatches = []

    def worker(seed):
        r = np.random.RandomState(seed)
        for _ in range(8):
            m = int(r.randint(1, 400))        # spans buckets AND splits
            Xr = _f32_data(r, m)
            out = srv.predict(Xr, timeout=30)
            ref = sf.predict_raw(Xr)[0]
            if not np.array_equal(out, ref):
                mismatches.append((seed, m))

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    md = srv.metrics_dict()
    srv.close()
    assert mismatches == []
    c = md["counters"]
    assert c["requests_completed"] == 48
    # the acceptance bar: at least one batch coalesced >= 2 submitters
    assert c.get("multi_submitter_batches", 0) >= 1
    assert md["histograms"]["batch_fill_ratio"]["count"] == c["batches_total"]


def test_multiclass_and_transform(binary_booster):
    bst = _train(num_class=3, rounds=6, seed=2)
    sf = bst._forest(0, 6)
    rng = np.random.RandomState(5)
    Xq = _f32_data(rng, 70)
    with bst.serve(max_batch_rows=128) as srv:
        out = srv.predict(Xq)
        assert out.shape == (70, 3)
        assert np.array_equal(out, sf.predict_raw(Xq, num_class=3).T)
    # raw_score=False matches Booster.predict's transformed output
    with binary_booster.serve(max_batch_rows=128, raw_score=False) as srv:
        got = srv.predict(Xq)
        np.testing.assert_array_equal(got, binary_booster.predict(Xq))


def test_rf_average_output_raw_scaling(binary_booster):
    """raw_score=True must match Booster.predict(raw_score=True), which
    for average_output (rf) models divides by the iteration count."""
    rng = np.random.RandomState(17)
    X = _f32_data(rng, 1200)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    rf = lgb.train(
        {"objective": "binary", "verbosity": -1, "num_leaves": 15,
         "boosting": "rf", "bagging_fraction": 0.8, "bagging_freq": 1},
        lgb.Dataset(X, label=y), num_boost_round=6, verbose_eval=False)
    assert rf.average_output
    Xq = _f32_data(rng, 40)
    with rf.serve(max_batch_rows=64) as srv:
        np.testing.assert_array_equal(srv.predict(Xq),
                                      rf.predict(Xq, raw_score=True))
    with rf.serve(max_batch_rows=64, raw_score=False) as srv:
        np.testing.assert_array_equal(srv.predict(Xq), rf.predict(Xq))


def test_single_row_and_empty(binary_booster):
    sf = binary_booster._forest(0, 12)
    rng = np.random.RandomState(9)
    with binary_booster.serve(max_batch_rows=64) as srv:
        x1 = _f32_data(rng, 1)[0]            # 1-D input, single row
        assert np.array_equal(srv.predict(x1), sf.predict_raw(x1[None])[0])
        out = srv.predict(np.zeros((0, F)))
        assert out.shape == (0,)
        with pytest.raises(ServingError):
            srv.predict(np.zeros((3, F + 2)))   # feature-count mismatch


# ------------------------------------------------------------ bucket reuse


def test_bucket_reuse_no_recompile(binary_booster):
    """Repeat shapes must hit the program registry: the compile counter
    freezes after warmup while the hit counter keeps climbing."""
    rng = np.random.RandomState(3)
    srv = binary_booster.serve(max_batch_rows=256, batch_window_ms=0.5)
    sizes = [5, 20, 70, 200]
    for m in sizes:                           # warmup: one compile per bucket
        srv.predict(_f32_data(rng, m))
    compiles_after_warmup = srv.metrics_dict()["counters"]["compile_events"]
    assert compiles_after_warmup <= len(sizes)
    for _ in range(3):
        for m in sizes:
            srv.predict(_f32_data(rng, m))
    md = srv.metrics_dict()
    srv.close()
    assert md["counters"]["compile_events"] == compiles_after_warmup
    assert md["counters"]["bucket_hits"] >= 3 * len(sizes)


# ---------------------------------------------------------------- hot swap


def test_hot_swap_under_load(binary_booster):
    """Swap the serving model while traffic flows: no dropped or failed
    requests, every result bit-matches either the old or the new model,
    and post-swap results match the new model."""
    b1 = binary_booster
    b2 = _train(rounds=9, leaves=7, seed=4)
    sf1, sf2 = b1._forest(0, 12), b2._forest(0, 9)
    srv = b1.serve(max_batch_rows=128, batch_window_ms=1.0)
    stop = threading.Event()
    bad = []

    def load(seed):
        r = np.random.RandomState(seed)
        while not stop.is_set():
            Xr = _f32_data(r, int(r.randint(1, 100)))
            out = srv.predict(Xr, timeout=30)
            if not (np.array_equal(out, sf1.predict_raw(Xr)[0])
                    or np.array_equal(out, sf2.predict_raw(Xr)[0])):
                bad.append(len(Xr))

    threads = [threading.Thread(target=load, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    srv.swap_model(b2, warm=True, block=True)
    time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join()
    rng = np.random.RandomState(11)
    Xq = _f32_data(rng, 40)
    post = srv.predict(Xq)
    md = srv.metrics_dict()
    srv.close()
    assert bad == []
    assert np.array_equal(post, sf2.predict_raw(Xq)[0])
    assert md["counters"]["hot_swaps"] == 1
    assert md["gauges"]["model_generation"] == 1
    # warm=True pre-compiled the new model's buckets: the digest changed
    assert md["gauges"]["active_model_digest"] != ""


def test_swap_pins_in_flight_requests(binary_booster):
    """A request admitted before the flip completes on the model it was
    validated against — even when the new model expects a DIFFERENT
    feature count, and even while the request still sits in the queue."""
    rng = np.random.RandomState(7)
    b_wide = _train_features(F + 3, seed=13)
    sf_old = binary_booster._forest(0, 12)
    sf_wide = b_wide._forest(0, 8)
    # a long coalescing window keeps the submitted request queued while
    # the swap lands, so execution deterministically happens post-flip
    srv = binary_booster.serve(max_batch_rows=64, batch_window_ms=300.0)
    Xq = _f32_data(rng, 16)
    fut = srv.submit(Xq)
    srv.swap_model(b_wide, warm=False, block=True)
    out = fut.result(30)
    assert np.array_equal(out, sf_old.predict_raw(Xq)[0])
    # post-swap traffic validates and serves against the new model
    Xw = _f32_data(rng, 10, f=F + 3)
    assert np.array_equal(srv.predict(Xw, timeout=30),
                          sf_wide.predict_raw(Xw)[0])
    with pytest.raises(ServingError):
        srv.submit(Xq)                    # old feature count now rejected
    srv.close()


def _train_features(f, rounds=8, seed=0):
    rng = np.random.RandomState(seed)
    X = _f32_data(rng, 1200, f)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    return lgb.train({"objective": "binary", "verbosity": -1,
                      "num_leaves": 15}, lgb.Dataset(X, label=y),
                     num_boost_round=rounds, verbose_eval=False)


def test_submit_copies_input(binary_booster):
    """submit() must own its rows: a caller refilling a preallocated
    buffer while the request is still queued cannot corrupt results."""
    sf = binary_booster._forest(0, 12)
    rng = np.random.RandomState(31)
    srv = binary_booster.serve(max_batch_rows=64, batch_window_ms=100.0)
    buf = _f32_data(rng, 12)
    want = sf.predict_raw(buf)[0]
    fut = srv.submit(buf)
    buf[:] = 0.0                       # caller reuses the buffer
    out = fut.result(30)
    srv.close()
    assert np.array_equal(out, want)


def test_swap_across_num_class(binary_booster):
    """warm=True must pre-compile the seen buckets for the new model
    even when the swap changes num_class (binary -> multiclass)."""
    b3 = _train(num_class=3, rounds=4, seed=9)
    sf3 = b3._forest(0, 4)
    srv = binary_booster.serve(max_batch_rows=64)
    rng = np.random.RandomState(5)
    srv.predict(_f32_data(rng, 10))    # seed the warm set (bucket 16)
    srv.swap_model(b3, warm=True, block=True)
    compiles_after_warm = srv.metrics_dict()["counters"]["compile_events"]
    Xq = _f32_data(rng, 10)
    out = srv.predict(Xq)
    md = srv.metrics_dict()
    srv.close()
    assert np.array_equal(out, sf3.predict_raw(Xq, num_class=3).T)
    assert md["counters"]["compile_events"] == compiles_after_warm


def test_swap_nonblocking(binary_booster):
    b2 = _train(rounds=5, leaves=7, seed=6)
    sf2 = b2._forest(0, 5)
    srv = binary_booster.serve(max_batch_rows=64)
    rng = np.random.RandomState(2)
    srv.predict(_f32_data(rng, 10))           # seed the warm set
    t = srv.swap_model(b2, warm=True, block=False)
    assert t is not None
    t.join(30)
    Xq = _f32_data(rng, 10)
    out = srv.predict(Xq)
    srv.close()
    assert np.array_equal(out, sf2.predict_raw(Xq)[0])


# ------------------------------------------- deadline / backpressure / drain


def test_deadline_rejection(binary_booster):
    srv = binary_booster.serve(max_batch_rows=64, batch_window_ms=0.5)
    rng = np.random.RandomState(1)
    fut = srv.submit(_f32_data(rng, 8), deadline_ms=1e-4)
    with pytest.raises(DeadlineExceeded):
        fut.result(10)
    # a sane deadline still serves
    out = srv.submit(_f32_data(rng, 8), deadline_ms=30_000).result(30)
    assert out.shape == (8,)
    md = srv.metrics_dict()
    srv.close()
    assert md["counters"]["requests_rejected_deadline"] >= 1


def test_queue_backpressure(binary_booster):
    srv = binary_booster.serve(max_batch_rows=64, max_queue_rows=128,
                               batch_window_ms=200.0)
    rng = np.random.RandomState(1)
    X = _f32_data(rng, 64)
    accepted = []
    with pytest.raises(QueueFull):
        for _ in range(64):                    # far beyond 128 queued rows
            accepted.append(srv.submit(X))
    assert srv.metrics_dict()["counters"]["requests_rejected_queue_full"] >= 1
    # accepted work still completes (reject-new, not drop-old)
    for fut in accepted:
        assert fut.result(30).shape == (64,)
    # a request that can NEVER fit is rejected with a non-retryable
    # ServingError, not a QueueFull that backoff cannot satisfy
    with pytest.raises(ServingError) as ei:
        srv.submit(_f32_data(rng, 129))
    assert not isinstance(ei.value, QueueFull)
    srv.close()


def test_close_semantics(binary_booster):
    rng = np.random.RandomState(8)
    srv = binary_booster.serve(max_batch_rows=64, batch_window_ms=100.0)
    futs = [srv.submit(_f32_data(rng, 16)) for _ in range(4)]
    srv.close(drain=True, timeout=30)          # graceful: all served
    for f in futs:
        assert f.result(0).shape == (16,)
    with pytest.raises(ServerClosed):
        srv.submit(_f32_data(rng, 4))
    # drain=False fails whatever is still queued
    srv2 = binary_booster.serve(max_batch_rows=64, batch_window_ms=500.0)
    futs2 = [srv2.submit(_f32_data(rng, 16)) for _ in range(8)]
    srv2.close(drain=False, timeout=30)
    outcomes = {"served": 0, "closed": 0}
    for f in futs2:
        try:
            f.result(5)
            outcomes["served"] += 1
        except ServerClosed:
            outcomes["closed"] += 1
    assert outcomes["closed"] >= 1             # tail of the queue was failed


def test_cancelled_future_does_not_wedge_scheduler(binary_booster):
    """Caller-side cancellation (asyncio.wait_for on apredict cancels the
    wrapped Future) must neither kill the singleton scheduler thread nor
    fail co-batched requests — the server keeps serving."""
    rng = np.random.RandomState(21)
    sf = binary_booster._forest(0, 12)
    srv = binary_booster.serve(max_batch_rows=64, batch_window_ms=100.0)
    for _ in range(3):
        fut = srv.submit(_f32_data(rng, 8))
        fut.cancel()
    Xq = _f32_data(rng, 12)
    out = srv.predict(Xq, timeout=30)     # scheduler thread still alive
    srv.close()
    assert np.array_equal(out, sf.predict_raw(Xq)[0])


def test_async_predict(binary_booster):
    import asyncio
    sf = binary_booster._forest(0, 12)
    rng = np.random.RandomState(12)
    Xq = _f32_data(rng, 25)

    async def go(srv):
        outs = await asyncio.gather(*[srv.apredict(Xq) for _ in range(4)])
        return outs

    with binary_booster.serve(max_batch_rows=128) as srv:
        outs = asyncio.run(go(srv))
    ref = sf.predict_raw(Xq)[0]
    for out in outs:
        assert np.array_equal(out, ref)


# ------------------------------------------------------------ stress (slow)


@pytest.mark.slow
def test_serving_stress(binary_booster):
    """1k mixed-shape requests from 8 threads; registered slow so tier-1
    stays fast (tools/serve_smoke.py is the CLI twin)."""
    sf = binary_booster._forest(0, 12)
    srv = binary_booster.serve(max_batch_rows=512, batch_window_ms=2.0)
    bad = []

    def worker(seed):
        r = np.random.RandomState(seed)
        for _ in range(125):
            Xr = _f32_data(r, int(r.randint(1, 700)))
            out = srv.predict(Xr, timeout=60)
            if not np.array_equal(out, sf.predict_raw(Xr)[0]):
                bad.append(seed)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    md = srv.metrics_dict()
    srv.close()
    assert bad == []
    assert md["counters"]["requests_completed"] == 1000
    assert md["counters"]["multi_submitter_batches"] >= 1


# --------------------------------------- take_from_table on-device probe


def test_table_matmul_probe_fallback(monkeypatch, capsys):
    """A backend failing the one-time exactness probe must demote
    take_from_table to the plain gather (ADVICE.md round 5), at warning
    level; a probe that RAISES is a defect and propagates."""
    import jax.numpy as jnp
    import lightgbm_tpu.ops.histogram as H
    from lightgbm_tpu.utils import log

    monkeypatch.setattr(log, "_current_level", 0)     # warnings on
    monkeypatch.setattr(H, "on_accelerator", lambda: True)
    table = jnp.asarray(np.linspace(-2, 2, 9).astype(np.float32))
    idx = jnp.asarray(np.arange(9, dtype=np.int32))

    # healthy backend: probe passes once, matmul path serves
    monkeypatch.setattr(H, "_TABLE_MATMUL_PROBE", {})
    out = np.asarray(H.take_from_table(table, idx))
    np.testing.assert_array_equal(out, np.asarray(table))
    assert H._TABLE_MATMUL_PROBE == {"cpu": True}

    # broken backend: matmul path perturbs values -> probe must demote
    monkeypatch.setattr(H, "_TABLE_MATMUL_PROBE", {})
    real = H._take_matmul

    def skewed(t, i, leading=False, block=65536):
        return real(t, i, leading, block) * 1.0000001

    monkeypatch.setattr(H, "_take_matmul", skewed)
    out = np.asarray(H.take_from_table(table, idx))
    assert "NOT bit-exact" in capsys.readouterr().err
    np.testing.assert_array_equal(out, np.asarray(table))  # gather served
    assert H._TABLE_MATMUL_PROBE == {"cpu": False}
    # verdict is cached: no re-probe, still the gather
    out = np.asarray(H.take_from_table(table, idx))
    np.testing.assert_array_equal(out, np.asarray(table))

    # a probe that cannot run is not a verdict
    monkeypatch.setattr(H, "_TABLE_MATMUL_PROBE", {})

    def refused(t, i, leading=False, block=65536):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(H, "_take_matmul", refused)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        H.take_from_table(table, idx)
    assert H._TABLE_MATMUL_PROBE == {}


# ------------------------------------------------- swap probe / quarantine


def test_swap_probe_quarantines_poisoned_model(binary_booster):
    """A hot-swap candidate producing non-finite output must be rejected
    BEFORE promotion: SwapQuarantined raised, generation unchanged,
    swap_quarantines counted, old model still serving identical bytes."""
    from lightgbm_tpu.serving import SwapQuarantined
    rng = np.random.RandomState(5)
    X = _f32_data(rng, 32)
    srv = binary_booster.serve(backend="host")
    try:
        before = srv.predict(X)
        gen = srv.metrics.gauge("model_generation").value
        poisoned = _train(rounds=4, seed=9)
        poisoned.boosting.models[0].leaf_value[:] = np.nan
        with pytest.raises(SwapQuarantined):
            srv.swap_model(poisoned)
        assert srv.metrics.gauge("model_generation").value == gen
        assert srv.metrics.counter("swap_quarantines").value == 1
        assert srv.metrics.counter("swap_failures").value >= 1
        np.testing.assert_array_equal(srv.predict(X), before)
    finally:
        srv.close()


def test_swap_probe_quarantines_raising_model(binary_booster):
    """A candidate whose predict path RAISES is quarantined the same way
    (probe catches the exception, not the first live batch)."""
    from lightgbm_tpu.serving import SwapQuarantined
    srv = binary_booster.serve(backend="host")
    try:
        bad = _train(rounds=4, seed=11)

        class _Exploding:
            num_trees = 0

            def predict_raw(self, Xpad, num_class=1):
                raise RuntimeError("boom")

        gen = srv.metrics.gauge("model_generation").value
        # sabotage the CompiledModel the registry will build: swap via the
        # registry directly with a broken forest
        from lightgbm_tpu.serving.registry import CompiledModel
        new = CompiledModel(bad, backend="host")
        new.forest = _Exploding()
        new.make_program(8)  # sanity: building the callable is fine
        with pytest.raises(SwapQuarantined):
            srv.models._probe(new)
        assert srv.metrics.counter("swap_quarantines").value == 1
        assert srv.metrics.gauge("model_generation").value == gen
    finally:
        srv.close()


def test_swap_healthy_model_passes_probe(binary_booster):
    srv = binary_booster.serve(backend="host")
    try:
        gen = srv.metrics.gauge("model_generation").value
        srv.swap_model(_train(rounds=6, seed=13))
        assert srv.metrics.gauge("model_generation").value == gen + 1
        assert srv.metrics.counter("swap_quarantines").value == 0
    finally:
        srv.close()
