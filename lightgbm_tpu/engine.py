"""Training entry points: train() and cv().

reference: python-package/lightgbm/engine.py — train (:18) with the callback
protocol, cv (:375) with CVBooster and fold aggregation.
"""

from __future__ import annotations

import collections
import copy
import time
from typing import Callable, Dict, List, Optional, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster
from .config import Config
from .dataset import Dataset
from .obs.flight import global_flight as _flight
from .obs.metrics import global_registry as _obs_registry
from .obs.trace import span as _span
from .obs.watchdog import global_watchdog as _watchdog


class TrainingPaused(Exception):
    """Raised out of ``train()`` when its ``pause_control`` orders a
    pause: the full training state was evicted to a checkpoint bundle
    FIRST, so the caller resumes byte-identically later by re-calling
    ``train`` with the same arguments plus ``resume_from=e.bundle_path``
    (the PR 2 capture/restore machinery — docs/RESILIENCE.md).  Not an
    error: the engine's forensic on-exception dump does not fire."""

    def __init__(self, iteration: int, bundle_path: str):
        super().__init__(
            f"training paused at iteration {iteration}; state evicted "
            f"to {bundle_path}")
        self.iteration = int(iteration)
        self.bundle_path = bundle_path


def train(params: dict, train_set: Dataset, num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj: Optional[Callable] = None, feval: Optional[Callable] = None,
          init_model=None, feature_name="auto", categorical_feature="auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[dict] = None,
          verbose_eval: Union[bool, int] = True,
          learning_rates=None, keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          snapshot_freq: int = -1, snapshot_out: str = "model.txt",
          snapshot_keep: int = 3,
          resume_from: Optional[str] = None,
          pause_control=None) -> Booster:
    """reference: engine.py:18.

    ``snapshot_freq`` mirrors the CLI's periodic snapshots
    (gbdt.cpp:259-263) but writes CHECKPOINT BUNDLES — atomic,
    sha256-manifested, full training state — into ``<snapshot_out>.ckpt/``
    (keep-last-``snapshot_keep``) instead of bare model files a crash can
    truncate.  ``resume_from`` (a bundle file or that directory) restores
    the captured state so the continued run produces a model
    BIT-IDENTICAL to the uninterrupted one; corrupt newest bundles are
    skipped in favor of the previous verified one (docs/RESILIENCE.md).

    The persistent XLA compilation cache is on from engine init
    (``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    — utils/platform.py): repeated trainings of same-shaped programs
    skip XLA entirely on the warm path.

    ``pause_control`` is the co-resident brownout seam
    (coresident/control.py, duck-typed): consulted at every chunk
    boundary.  ``consult(i)`` may sleep (throttle) and returns "run" or
    "pause"; ``chunk_cap()`` caps the macro-chunk so training yields the
    device between serving deadlines.  A "pause" verdict checkpoints the
    full state and raises ``TrainingPaused`` — docs/PERF.md co-residency.
    """
    from .utils.platform import enable_compile_cache
    enable_compile_cache(family="train")
    # active observability (docs/OBSERVABILITY.md): the env-gated SLO
    # sentry + metrics HTTP endpoint, and run context for any forensic
    # bundle this training might have to dump
    from .obs.http import maybe_start_from_env as _http_from_env
    from .obs.watchdog import maybe_start_from_env as _wd_from_env
    _wd_from_env()
    _http_from_env()
    params = dict(params)
    cfg = Config.from_params(params)
    if "num_iterations" in {Config.canonical_key(k) for k in params}:
        num_boost_round = cfg.num_iterations
    # the resolved round count is logged into the model file's parameters
    # section (reference train() writes params['num_iterations'])
    params["num_iterations"] = num_boost_round
    # reference: train() accepts a bare Dataset / name (engine.py:18)
    if isinstance(valid_sets, Dataset):
        valid_sets = [valid_sets]
    if isinstance(valid_names, str):
        valid_names = [valid_names]
    if fobj is not None:
        params["objective"] = "none"
    if feature_name != "auto":
        train_set._feature_name_param = feature_name
    if categorical_feature != "auto":
        train_set._categorical_feature_param = categorical_feature

    predictor = None
    init_score_offset = None
    if init_model is not None:
        predictor = init_model if isinstance(init_model, Booster) else \
            Booster(model_file=init_model, params=params)
    # raw features must be captured BEFORE construction possibly frees them
    # (reference predicts the init scores during lazy construction,
    # basic.py:840 _set_init_score_by_predictor — free_raw_data=True still
    # works for a fresh Dataset there)
    train_raw = train_set.raw_data if predictor is not None else None

    booster = Booster(params=params, train_set=train_set)

    # continued training: old model predictions become init scores
    # (reference: basic.py:840 _set_init_score_by_predictor)
    if predictor is not None:
        _apply_init_model(booster, predictor, train_set, raw=train_raw)

    train_in_valid = False
    if valid_sets:
        names_given = valid_names is not None
        valid_names = valid_names or [f"valid_{i}" for i in range(len(valid_sets))]
        added = []
        for vs, name in zip(valid_sets, valid_names):
            if vs is train_set:
                # reference: a valid set identical to the train set reports
                # the TRAINING metrics, under the passed name when one was
                # given (engine.py:175-187 is_valid_contain_train)
                train_in_valid = True
                if names_given:
                    booster.set_train_data_name(name)
                continue
            added.append((vs, vs.raw_data))
            booster.add_valid(vs, name)
        if predictor is not None:
            # valid scores must also start from the old model's predictions
            import jax.numpy as jnp
            K = booster.boosting.num_tree_per_iteration
            for i, (vs, raw) in enumerate(added):
                if raw is None:
                    raise ValueError(
                        "continued training requires free_raw_data=False "
                        "on validation Datasets")
                pred = predictor.predict(raw, raw_score=True)
                arr = (np.asarray(pred, np.float32).reshape(-1, K).T
                       if K > 1 else
                       np.asarray(pred, np.float32).reshape(1, -1))
                booster.boosting.valid_scores[i] = (
                    booster.boosting.valid_scores[i] + jnp.asarray(arr))

    cbs = set(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback_mod.early_stopping(
            early_stopping_rounds, cfg.first_metric_only,
            verbose=bool(verbose_eval)))
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        cbs.add(callback_mod.early_stopping(
            cfg.early_stopping_round, cfg.first_metric_only,
            verbose=bool(verbose_eval)))
    if verbose_eval is True:
        cbs.add(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        cbs.add(callback_mod.print_evaluation(verbose_eval))
    if evals_result is not None:
        cbs.add(callback_mod.record_evaluation(evals_result))
    if learning_rates is not None:
        cbs.add(callback_mod.reset_parameter(learning_rate=learning_rates))

    cbs_before = {cb for cb in cbs if getattr(cb, "before_iteration", False)}
    cbs_after = cbs - cbs_before
    cbs_before = sorted(cbs_before, key=lambda cb: getattr(cb, "order", 0))
    cbs_after = sorted(cbs_after, key=lambda cb: getattr(cb, "order", 0))

    start_iter = 0
    if resume_from is not None:
        from .resilience.checkpoint import (resolve_resume_point,
                                            restore_booster)
        with _span("engine.resume", ring=True) as resume:
            ck = resolve_resume_point(resume_from)
            restore_booster(booster, ck)
            _restore_callback_states(cbs_before + cbs_after,
                                     ck.engine_state.get("callbacks", {}))
            start_iter = ck.iteration
            resume.set(it=start_iter, bytes=ck.nbytes)
        _obs_registry.counter("checkpoint_resumes_total").inc()
        from .utils.log import log_info
        log_info(f"resume: restored iteration {start_iter} from "
                 f"{ck.path or resume_from}")
        # elastic resume (docs/RESILIENCE.md): the bundle records the
        # mesh it trained under; a DIFFERENT mesh here means a shrunk
        # (or regrown) world — restore_state already re-tiled the rows,
        # and the fresh planner events carry the re-planned per-shard
        # verdicts, so just make the transition visible
        old_cp = (ck.manifest or {}).get("collective_plan")
        new_cp = getattr(booster.boosting, "collective_plan", None)
        old_shape = (old_cp or {}).get("mesh_shape")
        new_shape = (list(new_cp.summary()["mesh_shape"])
                     if new_cp is not None else None)
        # only a bundle that RECORDED its mesh can evidence a transition
        # (a legacy manifest without collective_plan is not one)
        if old_cp is not None and old_shape != new_shape:
            log_info(
                f"elastic resume: bundle trained on mesh {old_shape}, "
                f"this world is {new_shape} — rows re-tiled, planner "
                "re-planned for the new per-shard shapes")

    ckpt_mgr = None
    if snapshot_freq > 0:
        from .resilience.checkpoint import CheckpointManager
        ckpt_mgr = CheckpointManager(f"{snapshot_out}.ckpt",
                                     keep_last=snapshot_keep)

    # eval cadence: the reference's OutputMetric loop evaluates every
    # ``metric_freq`` (alias output_freq) iterations; default 1 keeps the
    # historical evaluate-every-round behavior
    mf = max(int(cfg.metric_freq), 1)
    eval_possible = bool(
        (valid_sets and booster.boosting.valid_metrics)
        or feval is not None or cfg.is_provide_training_metric
        or train_in_valid)
    # early_stopping's init error moved up front: non-eval iterations no
    # longer reach the callback's init, so "no eval at all" must be
    # diagnosed here (dart disables early stopping inside the callback)
    is_dart = any(params.get(a, "") == "dart"
                  for a in ("boosting", "boosting_type", "boost"))
    has_early_stop = any(
        str(getattr(cb, "_resume_token", "")).startswith("early_stopping")
        for cb in cbs_after)
    if has_early_stop and not is_dart and not eval_possible \
            and num_boost_round > start_iter:
        raise ValueError(
            "For early stopping, at least one dataset and eval metric is "
            "required for evaluation")

    # fused macro-steps (boosting/macro.py): chunk the boosting loop into
    # lax.scan programs of c iterations each, chunks ending at the next
    # boundary that genuinely needs the host — eval (metric_freq),
    # snapshots, end of training.  Per-iteration host logic (DART, CEGB,
    # forced splits, custom fobj, non-schedule callbacks) forces c=1.
    from .boosting.macro import chunk_cap, pow2_chunk
    cap = chunk_cap()
    lr_cbs = [cb for cb in cbs_before
              if getattr(cb, "_lr_schedule", None) is not None]
    lr_lists_ok = all(
        not isinstance(cb._lr_schedule, list)
        or len(cb._lr_schedule) == num_boost_round for cb in lr_cbs)
    can_chunk = (cap > 1 and fobj is None
                 and booster.boosting.chunk_supported()
                 and len(lr_cbs) == len(cbs_before) and lr_lists_ok
                 and all(getattr(cb, "_chunk_safe", False)
                         for cb in cbs_after))

    def _lr_at(j):
        v = None
        for cb in lr_cbs:
            s = cb._lr_schedule
            v = s[j] if isinstance(s, list) else s(j)
        return float(v)

    evaluation_result_list = []
    i = start_iter
    t_loop0 = time.perf_counter()
    K_per_iter = booster.boosting.num_tree_per_iteration
    _flight.set_context(
        phase="train", num_boost_round=num_boost_round,
        start_iter=start_iter, objective=cfg.objective,
        num_leaves=cfg.num_leaves, rows=train_set.num_data)
    # the engine-loop heartbeat is stale-watched only WHILE the loop
    # runs (watchdog.py: a finished loop must never breach)
    _watchdog.watch_heartbeat(
        "engine.step", floor=_watchdog.config.trees_per_sec_floor)
    train_root = _span("engine.train", start_iter=start_iter,
                       num_boost_round=num_boost_round)
    train_root.__enter__()
    try:
        while i < num_boost_round:
            if pause_control is not None \
                    and pause_control.consult(i) == "pause":
                # evict the full training state to a bundle BEFORE
                # yielding the device: the resumed run is byte-identical
                mgr = ckpt_mgr
                if mgr is None:
                    from .resilience.checkpoint import CheckpointManager
                    mgr = CheckpointManager(f"{snapshot_out}.ckpt",
                                            keep_last=max(snapshot_keep, 1))
                path = mgr.save(
                    booster, iteration=i,
                    engine_state={"callbacks": _collect_callback_states(
                        cbs_before + cbs_after)})
                _flight.note("engine.pause", i=i, bundle=str(path))
                raise TrainingPaused(i, path)
            c = 1
            if can_chunk:
                d = num_boost_round - i
                if eval_possible:
                    d = min(d, mf - (i % mf))
                if ckpt_mgr is not None:
                    d = min(d, snapshot_freq - (i % snapshot_freq))
                c = pow2_chunk(d, cap)
                if pause_control is not None:
                    # brownout throttle: the negotiated cap shrinks the
                    # macro-chunk so the host regains control (and the
                    # batcher its deadline) sooner
                    c = pow2_chunk(c, max(int(pause_control.chunk_cap()),
                                          1))
            t_step0 = time.perf_counter()
            # one turn of the loop, whole: callbacks, update, evaluation,
            # snapshot (the round's seams run under it)
            with _span("engine.step", ring=True, it=i, c=c):
                if c > 1:
                    lrs = ([_lr_at(j) for j in range(i, i + c)]
                           if lr_cbs else None)
                    finished = booster.update_chunk(c, lrs)
                    if lrs is not None:
                        # replicate the last reset_parameter side effects
                        # so the post-chunk state matches per-iteration
                        # training
                        booster.reset_parameter({"learning_rate": lrs[-1]})
                        params["learning_rate"] = lrs[-1]
                    i += c
                else:
                    if cbs_before:
                        with _span("engine.callbacks"):
                            for cb in cbs_before:
                                cb(callback_mod.CallbackEnv(
                                    booster, params, i, 0, num_boost_round,
                                    None))
                    finished = booster.update(fobj=fobj)
                    i += 1
                # live-rate gauges + heartbeat of the update (cheap
                # host-side accounting — no device work, no numerics)
                step_s = time.perf_counter() - t_step0
                _flight.sample_metrics()
                _obs_registry.gauge("train_iter_seconds").set(
                    round(step_s / max(c, 1), 6))
                live = (i - start_iter) * K_per_iter / max(
                    time.perf_counter() - t_loop0, 1e-9)
                _obs_registry.gauge("train_trees_per_sec_live").set(
                    round(live, 3))
                _watchdog.beat("engine.step", count=i * K_per_iter)
                j = i - 1        # last iteration trained this turn
                evaluation_result_list = []
                if eval_possible and (j + 1) % mf == 0:
                    with _span("engine.eval", ring=True, iteration=j):
                        if cfg.is_provide_training_metric or train_in_valid:
                            evaluation_result_list.extend(
                                booster.eval_train(feval))
                        evaluation_result_list.extend(
                            booster.eval_valid(feval))
                    # pod telemetry at the eval boundary
                    # (obs/aggregate.py): a no-op unless a pod transport
                    # is registered
                    from .obs.aggregate import maybe_gather_at_eval
                    maybe_gather_at_eval()
                early_stopped = False
                try:
                    with _span("engine.callbacks"):
                        for cb in cbs_after:
                            cb(callback_mod.CallbackEnv(
                                booster, params, j, 0, num_boost_round,
                                evaluation_result_list))
                except callback_mod.EarlyStopException as e:
                    booster.best_iteration = e.best_iteration + 1
                    for item in e.best_score:
                        booster.best_score.setdefault(
                            item[0], collections.OrderedDict())
                        booster.best_score[item[0]][item[1]] = item[2]
                    early_stopped = True
                # snapshot even on the iteration that triggered early stop
                # (reference: GBDT::Train reaches the snapshot write,
                # gbdt.cpp:259-263)
                if ckpt_mgr is not None and (j + 1) % snapshot_freq == 0:
                    ckpt_mgr.save(
                        booster, iteration=j + 1,
                        engine_state={"callbacks": _collect_callback_states(
                            cbs_before + cbs_after)})
            if early_stopped or finished:
                break
    except TrainingPaused:
        # a brownout pause is an ORDERED yield, not a failure: no
        # forensic dump (the scheduler journals the pause/resume spans)
        train_root.set(paused=True)
        raise
    except BaseException as e:
        train_root.set(error=type(e).__name__)
        # unhandled engine-loop failure: dump the forensic bundle (ring
        # + metrics + fingerprint) BEFORE the raise unwinds the process
        _flight.on_exception("engine.train", e)
        raise
    finally:
        train_root.__exit__(None, None, None)
        _watchdog.unwatch("engine.step")
    # training-loop instruments on the unified process registry
    # (docs/OBSERVABILITY.md): cheap host-side gauges, no device work
    wall = time.perf_counter() - t_loop0
    trained = i - start_iter
    if trained > 0:
        _obs_registry.counter("train_iterations_total").inc(trained)
        if wall > 0:
            _obs_registry.gauge("train_trees_per_sec").set(round(
                trained * booster.boosting.num_tree_per_iteration / wall, 3))
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration()
        for item in evaluation_result_list:
            booster.best_score.setdefault(item[0], collections.OrderedDict())
            booster.best_score[item[0]][item[1]] = item[2]
    return booster


def _collect_callback_states(cbs) -> dict:
    """Resumable-callback state, keyed by each callback's ``_resume_token``
    (early_stopping / record_evaluation attach one; see callback.py)."""
    out = {}
    for cb in cbs:
        tok = getattr(cb, "_resume_token", None)
        if tok is not None and hasattr(cb, "get_state"):
            out[tok] = cb.get_state()
    return out


def _restore_callback_states(cbs, states: dict) -> None:
    for cb in cbs:
        tok = getattr(cb, "_resume_token", None)
        if tok is not None and tok in states and hasattr(cb, "set_state"):
            cb.set_state(states[tok])


class InitModelCompatibilityError(ValueError):
    """The ``init_model`` cannot continue training on this train set —
    raised by name at ``train()`` entry (feature count, class count, or
    bin-mapper layout mismatch) instead of a shape failure mid-boost."""


def _validate_init_model(booster: Booster, predictor: Booster,
                         train_set: Dataset) -> None:
    """Continued training runs the old model's trees against the NEW
    training matrix; every mismatch that would otherwise surface as an
    opaque jit shape error (or silently wrong scores) is checked here.
    Covers the cross-load path too: a predictor loaded from stock
    LightGBM model text carries its feature count and class count in
    the header."""
    f_model = predictor.num_features()
    f_train = train_set.num_total_features
    if f_model != f_train:
        raise InitModelCompatibilityError(
            f"init_model was trained on {f_model} features but the "
            f"training data has {f_train}; continued training requires "
            "the same feature layout")
    k_model = max(predictor.num_tree_per_iteration, 1)
    k_train = max(booster.boosting.num_tree_per_iteration, 1)
    if k_model != k_train:
        raise InitModelCompatibilityError(
            f"init_model has {k_model} tree(s) per iteration but this "
            f"training is configured for {k_train} (num_class / "
            "objective mismatch); continued training cannot mix them")
    # an in-process predictor that retains its training Dataset also
    # pins a bin grid.  Continued training itself is grid-agnostic (the
    # old trees carry REAL thresholds, so init scores are exact on any
    # binning — the stock cross-load path relies on that), but a
    # production refresh is supposed to bin fresh rows on the DEPLOYED
    # grid (Dataset(reference=...) / lifecycle.fresh_dataset): warn by
    # name when the grids differ so a silent re-binning of the world is
    # at least a visible decision.  Shared-identity mappers (the
    # reference= path) short-circuit without comparing content.
    pts = getattr(predictor, "train_set", None)
    if pts is not None and getattr(pts, "constructed", False) \
            and train_set.bin_mappers and pts.bin_mappers \
            and pts.bin_mappers is not train_set.bin_mappers:
        same = all(a.to_dict() == b.to_dict()
                   for a, b in zip(pts.bin_mappers, train_set.bin_mappers))
        if not same:
            from .utils.log import log_warning
            log_warning(
                "continued training: the new train set's bin mappers "
                "differ from the init model's training grid — init "
                "scores stay exact (trees hold real thresholds), but "
                "fresh histograms live on a DIFFERENT grid; bin "
                "against the deployed Dataset (Dataset(reference=...) "
                "/ lifecycle.fresh_dataset) to keep one grid")


def _apply_init_model(booster: Booster, predictor: Booster, train_set: Dataset,
                      raw=None):
    _validate_init_model(booster, predictor, train_set)
    # streamed refresh (lifecycle/refresh.py): the deployed model's raw
    # scores were computed chunk-by-chunk at push time — the dataset
    # never kept raw features to re-predict from
    pre = getattr(train_set, "_init_model_raw_scores", None)
    if pre is not None:
        raw = np.asarray(pre, np.float64)
    else:
        raw = predictor.predict(raw if raw is not None
                                else _recover_raw(train_set),
                                raw_score=True)
    K = booster.boosting.num_tree_per_iteration
    import jax.numpy as jnp
    n = train_set.num_data
    isc = np.asarray(raw, np.float32).reshape(-1, K).T if K > 1 else \
        np.asarray(raw, np.float32).reshape(1, n)
    n_pad = booster.boosting._n_pad
    if n_pad > n:
        isc = np.pad(isc, ((0, 0), (0, n_pad - n)))
    booster.boosting.train_score = booster.boosting.train_score + jnp.asarray(isc)
    booster.boosting._init_score_added = True
    booster.boosting.models = list(predictor.models)
    booster.boosting.iter = len(predictor.models) // K
    # continued-training bookkeeping (reference: num_init_iteration_,
    # gbdt.cpp LoadModelFromString): DART must only drop this-run trees
    booster.boosting.num_init_iteration = len(predictor.models) // K


def _recover_raw(train_set: Dataset):
    if train_set.raw_data is not None:
        return train_set.raw_data
    raise ValueError("continued training requires free_raw_data=False on the "
                     "training Dataset")


class CVBooster:
    """reference: engine.py CVBooster."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster):
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: dict,
                  seed: int, stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data
    if folds is not None:
        if not hasattr(folds, "__iter__") and hasattr(folds, "split"):
            group = None
            if full_data.metadata.query_boundaries is not None:
                group = np.diff(full_data.metadata.query_boundaries)
            if group is not None:
                # sklearn splitters take PER-ROW group ids (reference:
                # engine.py:306 np.repeat over the group sizes)
                group = np.repeat(np.arange(len(group)), group)
            folds = folds.split(X=np.empty(num_data),
                                y=full_data.get_label(), groups=group)
        return list(folds)
    rng = np.random.RandomState(seed)
    qb = full_data.metadata.query_boundaries
    if qb is not None:
        # ranking: split whole queries across folds (reference: engine.py:301
        # GroupKFold over the flattened group array); rows of each query stay
        # contiguous and in order, as Dataset.subset() requires
        nq = len(qb) - 1
        if nfold > nq:
            raise ValueError(
                f"nfold={nfold} exceeds the number of query groups ({nq})")
        try:
            # reference: the default ranking split IS sklearn's GroupKFold
            # over per-row group ids (engine.py:301-306) — deterministic,
            # so cv(folds=GroupKFold(n)) gives identical folds
            from sklearn.model_selection import GroupKFold
            flat = np.repeat(np.arange(nq), np.diff(qb))
            return list(GroupKFold(n_splits=nfold).split(
                X=np.empty(num_data), groups=flat))
        except ImportError:
            pass
        q_idx = np.arange(nq)
        if shuffle:
            rng.shuffle(q_idx)
        q_chunks = np.array_split(q_idx, nfold)

        def rows(qs):
            qs = np.sort(qs)
            return np.concatenate([np.arange(qb[q], qb[q + 1]) for q in qs])

        return [(rows(np.concatenate([c for j, c in enumerate(q_chunks) if j != i])),
                 rows(q_chunks[i])) for i in range(nfold)]
    if stratified:
        from sklearn.model_selection import StratifiedKFold
        skf = StratifiedKFold(n_splits=nfold, shuffle=shuffle,
                              random_state=seed if shuffle else None)
        return list(skf.split(np.empty(num_data), full_data.get_label()))
    idx = np.arange(num_data)
    if shuffle:
        rng.shuffle(idx)
    chunks = np.array_split(idx, nfold)
    return [(np.concatenate([c for j, c in enumerate(chunks) if j != i]), chunks[i])
            for i in range(nfold)]


def cv(params: dict, train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       early_stopping_rounds=None, fpreproc=None, verbose_eval=None,
       show_stdv: bool = True, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False,
       fused: bool = False) -> Dict[str, List[float]]:
    """reference: engine.py:375.

    ``fused=True`` batches the folds' per-round training steps along a
    model axis (lightgbm_tpu/multi/): every fold advances one iteration
    in ONE vmapped device dispatch instead of nfold sequential programs.
    The results dict is IDENTICAL — same keys, same mean/stdv layout,
    bit-for-bit the same values as the serial loop (tests/test_multi.py
    pins it) — because both paths run the same c=1 chunk program per
    fold; configs with per-iteration host logic (or a custom ``fobj``)
    fall back to serial stepping with a logged warning.
    """
    from .utils.platform import enable_compile_cache
    enable_compile_cache(family="train")
    params = dict(params)
    if fobj is not None:
        # custom objective: no built-in objective, hence no default metric
        # (reference cv sets objective to none, engine.py:485)
        params["objective"] = "none"
    if metrics is not None:
        # the metrics ARG overwrites every metric alias in params
        # (reference cv pops all _ConfigAliases 'metric' keys first)
        for k in [k for k in params if Config.canonical_key(k) == "metric"]:
            params.pop(k)
        params["metric"] = metrics
    cfg = Config.from_params(params)
    if cfg.objective in ("binary",) or cfg.objective.startswith("multiclass"):
        pass
    else:
        stratified = False

    folds_idx = _make_n_folds(train_set, folds, nfold, params, seed,
                              stratified, shuffle)
    cvbooster = CVBooster()
    results = collections.defaultdict(list)

    boosters = []
    for (tr_idx, te_idx) in folds_idx:
        tr = train_set.subset(tr_idx, params)
        te = train_set.subset(te_idx, params)
        if fpreproc is not None:
            tr, te, params = fpreproc(tr, te, dict(params))
        bst = Booster(params=params, train_set=tr)
        bst.add_valid(te, "valid")
        boosters.append(bst)
        cvbooster._append(bst)

    cbs = set(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback_mod.early_stopping(early_stopping_rounds,
                                            cfg.first_metric_only, verbose=False))
    if verbose_eval is True:
        cbs.add(callback_mod.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and verbose_eval:
        cbs.add(callback_mod.print_evaluation(verbose_eval, show_stdv))
    cbs = sorted(cbs, key=lambda cb: getattr(cb, "order", 0))

    from .multi.driver import CVStepper
    stepper = CVStepper(boosters, fused, fobj)
    for i in range(num_boost_round):
        agg: Dict[str, List[float]] = collections.defaultdict(list)
        # advance EVERY fold first (batched across folds when fused),
        # then evaluate — folds are independent, so the reordering vs
        # the reference's update-then-eval-per-fold changes nothing
        stepper.step()
        for bst in boosters:
            # reference cv names the train split 'train' (engine.py:353)
            res = ([("train", mn, v, h)
                    for (_, mn, v, h) in bst.eval_train(feval)]
                   if eval_train_metric else []) + bst.eval_valid(feval)
            for (dname, mname, val, hib) in res:
                agg[(dname if eval_train_metric else "valid", mname, hib)].append(val)
        evaluation_result_list = [
            ("cv_agg", f"{d} {m}" if eval_train_metric else m,
             float(np.mean(v)), h, float(np.std(v)))
            for (d, m, h), v in agg.items()]
        for (_, m, mean, _, std) in evaluation_result_list:
            results[m + "-mean"].append(mean)
            results[m + "-stdv"].append(std)
        try:
            for cb in cbs:
                cb(callback_mod.CallbackEnv(cvbooster, params, i, 0,
                                            num_boost_round, evaluation_result_list))
        except callback_mod.EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for k in results:
                results[k] = results[k][:cvbooster.best_iteration]
            break
    out = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvbooster
    return out


def serve(model, config=None, **overrides):
    """Construct a serving.Server from a Booster or a model-file path.

    The module-level twin of ``Booster.serve`` (docs/SERVING.md) so a
    deployment can go file -> server in one call::

        server = lgb.serve("model.txt", max_batch_rows=512)
    """
    from .serving import Server
    return Server(Server._as_booster(model), config=config, **overrides)
