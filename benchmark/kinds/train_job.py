"""``train_job``: closed loop, one trainer, run as a job that is saved,
killed and resumed, the way users run one on a machine that can be taken
from them: ``lgb.train(params, train_set, num_boost_round, snapshot_freq=,
snapshot_out=, snapshot_keep=, callbacks=[<the harness's two>])`` with the
configuration's ``job`` (a checkpoint bundle every ``snapshot_freq``
rounds, the newest ``snapshot_keep`` kept), no validation set.

One process, in this order (rounds are counted from 1; ``kill_after_round``
6, ``open_round`` 11 and a bundle every 5 rounds in the cell):

A.  The call.  Rounds 1-2 are ``train_call``'s warm rounds (host snapshots
    for the comparison with the plain reference).  The engine writes the
    bundle of round 5.  Round 6 is grown *uninterrupted*: the harness keeps
    its host tree and the train score after it, which is what durability is
    held to: the job that was not killed.  Then its after-round callback
    raises ``JobKilled`` out of ``lgb.train``.
K.  The kill.  Every reference to the ``Booster`` and its device state is
    dropped; the ``Dataset`` is kept (a new process would ingest again, at
    the cost ``ingest_s`` reports) and is therefore built with
    ``free_raw_data=False``, as a user who means to train on it again
    builds it.  The harness reads the bundle directory
    with its own reader (``zipfile``, ``hashlib``, ``pickle``: nothing of
    ``lightgbm_tpu.resilience``): the newest bundle that verifies has to be
    round 5's, hold 5 trees in its model text and the train score the
    harness kept on the device at that boundary.
B.  The same call with ``resume_from=<that directory>``.  Its first round
    is round 6 again: every field of the host tree and the train score have
    to equal A's, element for element (``resume_tree_mismatch``,
    ``resume_score_mismatch``; limit 0, no tolerance).  Rounds 6-10 are its
    warm rounds (the new ``Booster``'s trace and lower land there).
W.  The window opens when the before-round callback of round ``open_round``
    is entered and closes when that of round ``open_round + k *
    snapshot_freq`` is entered, ``k`` the smallest for which ``seconds``
    have passed: ``k`` whole periods, ``k * snapshot_freq`` trees and ``k``
    saves, each save wholly inside.  The round the close falls in is grown
    (a before-round callback cannot stop the engine), stopped by
    ``EarlyStopException`` in its after-round callback, and does not count.
C.  After the close: the plain reference follows A's first trees and the
    window's last counted tree (``correct.train_numbers``, unchanged), and
    the harness's reader takes the newest bundle: the iteration of the
    window's last round, that many trees, the train score kept on the
    device at that boundary (``bundle_state_mismatch``); one bundle seen
    written a boundary and no more than ``snapshot_keep`` left
    (``bundles_missing``).

``setup_s`` is process start to the window's opening, so the kill, the
read-back and the resume are set-up; the span ``resume`` is entry of call B
to the entry of its first round.
"""
import gc
import hashlib
import json
import os
import pickle
import shutil
import tempfile
import time
import zipfile

import numpy as np

from benchmark.lib import correct, faults
from benchmark.lib.traffic import (TREE_FIELDS, Run, _generator, generate,
                                   host_score, host_tree, objective_module,
                                   peak_bytes)

PRIMARY = "train_s_per_tree"
JOB_NUMBERS = ("resume_tree_mismatch", "resume_score_mismatch",
               "bundle_state_mismatch", "bundles_missing")
LIMITS = {"window_tree_missing": 0, **{name: 0 for name in JOB_NUMBERS}}
BUNDLE_SUFFIX = ".lgbckpt"


class JobKilled(Exception):
    """The harness's kill: raised out of ``lgb.train`` after round
    ``kill_after_round``."""


# ---- the harness's own reader of a bundle directory ------------------------

def read_bundle(path):
    """One bundle, verified member by member against its manifest's sizes
    and sha256; ``None`` where anything is missing or differs."""
    try:
        with zipfile.ZipFile(path) as zf:
            manifest = json.loads(zf.read("manifest.json"))
            members = {}
            for name, meta in manifest["members"].items():
                data = zf.read(name)
                if len(data) != meta["size"] \
                        or hashlib.sha256(data).hexdigest() != meta["sha256"]:
                    return None
                members[name] = data
        state = pickle.loads(members["state.pkl"])
        return {"iteration": int(manifest["iteration"]),
                "trees": members["model.txt"].decode().count("\nTree="),
                "train_score": np.asarray(state["boosting"]["train_score"]),
                "bytes": os.path.getsize(path)}
    except Exception:  # noqa: BLE001  (a bundle that cannot be read is not trusted)
        return None


def bundles_left(directory):
    try:
        return sorted(f for f in os.listdir(directory)
                      if f.endswith(BUNDLE_SUFFIX))
    except OSError:
        return []


def newest_verified(directory):
    """-> (the newest bundle of ``directory`` that verifies or ``None``,
    how many newer ones did not)."""
    names = bundles_left(directory)
    for skipped, name in enumerate(reversed(names)):
        got = read_bundle(os.path.join(directory, name))
        if got is not None:
            return got, skipped
    return None, len(names)


def read_back(directory, iteration, kept_score):
    """What the newest verified bundle holds against what the harness knows
    of that boundary: what was read, with ``wrong`` (how much of it differs)
    and ``read_s``."""
    t0 = time.perf_counter()
    got, skipped = newest_verified(directory)
    if got is None:
        seen = {"found": None, "wrong": 1.0}
    else:
        wrong = float(got["iteration"] != iteration) \
            + float(got["trees"] != iteration)
        kept = np.asarray(kept_score) if kept_score is not None else None
        if kept is None or kept.shape != got["train_score"].shape:
            wrong += 1.0
        else:
            wrong += float(np.count_nonzero(kept != got["train_score"]))
        seen = {"found": got["iteration"], "trees": got["trees"],
                "bytes": got["bytes"], "wrong": wrong}
    return dict(seen, skipped=skipped, read_s=time.perf_counter() - t0)


# ---- the kind's own faults ---------------------------------------------------

class JobFault(faults.Fault):
    """Hooks of this kind beside ``lib/faults.Fault``'s:
    ``resume_from(directory)`` -> what call B is given,
    ``after_restore(booster)`` at the entry of call B's first round,
    ``before_read_back(directory)`` before the last read-back."""

    def resume_from(self, directory):
        return directory

    def after_restore(self, bst):
        pass

    def before_read_back(self, directory):
        pass


class RestoreSkipped(JobFault):
    """Call B is given no bundle: it starts from round 0."""
    name = "restore_skipped"

    def resume_from(self, directory):
        return None


class ScorePerturbed(JobFault):
    """One restored score is off by one unit in the last place."""
    name = "score_perturbed"

    def after_restore(self, bst):
        import jax.numpy as jnp
        score = bst.boosting.train_score
        bst.boosting.train_score = score.at[0, 0].set(
            jnp.nextafter(score[0, 0], jnp.float32(np.inf)))


class SaveDropped(JobFault):
    """The last boundary's bundle is gone before it is read back."""
    name = "save_dropped"

    def before_read_back(self, directory):
        names = bundles_left(directory)
        if names:
            os.remove(os.path.join(directory, names[-1]))


FAULTS = {**faults.TRAIN,
          **{f.name: f for f in (RestoreSkipped, ScorePerturbed,
                                 SaveDropped)}}


def _hook(fault, name, *args, default=None):
    fn = getattr(fault, name, None) if fault else None
    return fn(*args) if fn else default


# ---- the run -------------------------------------------------------------------

def run(manifest, config, traffic, cell_file, seed, seconds,
        spans, compiles, devices, on_window=None, fault=None):
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.callback import EarlyStopException
    gen, gen_args = _generator(manifest, config)
    rows, features = int(config["rows"]), int(config["features"])
    params = dict(config["params"])
    freq = int(config["job"]["snapshot_freq"])
    keep = int(config["job"]["snapshot_keep"])
    warm = int(traffic["warm_rounds"])
    kill_after = int(traffic["kill_after_round"])
    open_round = int(traffic["open_round"])
    follow = int(cell_file.get("reference_trees", warm))
    if not warm <= freq < kill_after < open_round \
            or (open_round - 1) % freq or kill_after > 2 * freq:
        raise ValueError(
            "train_job wants warm_rounds <= snapshot_freq < kill_after_round "
            "<= 2 x snapshot_freq < open_round = 1 + a multiple of "
            f"snapshot_freq; got {warm}, {freq}, {kill_after}, {open_round}")
    resume_at = (kill_after - 1) // freq * freq     # the newest boundary
    with spans.span("data"):
        X, y, fields = generate(gen, seed, rows, features, **gen_args)
    with spans.span("ingest"):
        # kept for call B: on an accelerator a default Dataset gives up its
        # host matrix, and with it the right to build a second Booster,
        # once the first holds the device copy (Dataset.release_host_binned)
        ds = lgb.Dataset(X, label=y, params=params, free_raw_data=False,
                         **fields)
        ds.construct()

    workdir = tempfile.mkdtemp(prefix="train_job_")
    snapshot_out = os.path.join(workdir, "model.txt")
    ckpt_dir = snapshot_out + ".ckpt"
    # the engine's forensic dump of the kill goes beside the bundles and
    # not into the checkout (the program's documented directory switch)
    flight_dir_was = os.environ.get("LIGHTGBM_TPU_FLIGHT_DIR")
    os.environ["LIGHTGBM_TPU_FLIGHT_DIR"] = workdir

    answers, snaps, step_seconds = [], [], []
    job = {name: 0.0 for name in JOB_NUMBERS}
    info = {}
    s = {"call": "A", "bst": None, "t_call": None, "t_round": None,
         "before": None, "token": None, "boundary": None, "written": 0,
         "expect": None, "reference": None, "first_b": None, "held": 0,
         "trees": 0, "t0": None, "t1": None, "last": None}

    def before_round(env):
        bst = env.model
        now = time.perf_counter()
        if s["bst"] is None:
            # the Booster this call built
            s["bst"] = bst
            jax.block_until_ready(bst.boosting.binned)
            if s["call"] == "A":
                spans.add("ingest", time.perf_counter() - s["t_call"])
            else:
                spans.add("resume", time.perf_counter() - s["t_call"])
                s["first_b"] = env.iteration + 1
                _hook(fault, "after_restore", bst)
            if fault:
                fault.after_build(bst)
            s["t_round"] = time.perf_counter()
        if s["expect"] is not None:
            # the boundary round before this one: its bundle is on disk
            s["written"] += os.path.isfile(os.path.join(
                ckpt_dir, f"ckpt_iter_{s['expect']:08d}{BUNDLE_SUFFIX}"))
            s["expect"] = None
        rnd = env.iteration + 1
        if s["call"] == "B" and s["t0"] is None and rnd == open_round:
            if on_window:
                on_window("start")
            compiles.active = True
            s["t0"] = s["t_round"] = time.perf_counter()
        elif s["t0"] is not None and s["t1"] is None \
                and (rnd - open_round) % freq == 0 \
                and now - s["t0"] >= seconds:
            s["t1"] = now           # the close
            compiles.active = False
            if on_window:
                on_window("stop")
        if s["t1"] is not None:
            return          # the round past the close: nothing of it is kept
        s["token"] = fault.before_step(bst) if fault else None
        with spans.span("keep_score"):
            s["before"] = jnp.copy(bst.boosting.train_score)
    before_round.before_iteration = True

    def after_round(env):
        bst = env.model
        if s["t1"] is not None:
            raise EarlyStopException(env.iteration,
                                     env.evaluation_result_list)
        if fault:
            fault.after_step(bst, s["token"])
        with spans.span("pull_trees"):
            s["held"] = len(bst.models)
        if fault:
            fault.after_pull(bst)
        done = env.iteration + 1
        now = time.perf_counter()
        if done % freq == 0:
            # a boundary: the engine writes the bundle after this callback
            # returns; what it has to hold of the score stays on the device
            with spans.span("keep_score"):
                s["boundary"] = (done, jnp.copy(bst.boosting.train_score))
            s["expect"] = done
        if s["t0"] is None:
            spans.add("warm_round", now - s["t_round"])
            if s["call"] == "A":
                if done <= follow:
                    answers.append(host_tree(bst.models[done - 1]))
                    # 100 MB to the host; set-up, not window
                    snaps.append(host_score(bst.boosting.train_score, rows))
                if done == kill_after:
                    s["reference"] = {
                        "tree": host_tree(bst.models[done - 1]),
                        "score": np.asarray(bst.boosting.train_score)}
                    raise JobKilled(f"killed after round {done}")
            elif s["reference"] is not None and done == s["first_b"]:
                # B's first round against the uninterrupted round of A
                ref, s["reference"] = s["reference"], None
                tree = host_tree(bst.models[done - 1])
                job["resume_tree_mismatch"] = float(
                    done != kill_after) + sum(
                    not np.array_equal(tree[k], ref["tree"][k])
                    for k in TREE_FIELDS)
                score = np.asarray(bst.boosting.train_score)
                job["resume_score_mismatch"] = float(
                    np.count_nonzero(score != ref["score"])
                    if score.shape == ref["score"].shape else score.size)
            s["t_round"] = time.perf_counter()
            return
        s["trees"] += 1
        step_seconds.append(now - s["t_round"])
        s["t_round"] = now
        s["last"] = done - 1
    after_round.order = 40

    def call(resume_from=None):
        s["bst"], s["t_call"] = None, time.perf_counter()
        return lgb.train(
            params, ds, num_boost_round=int(traffic["num_boost_round"]),
            verbose_eval=False, snapshot_freq=freq,
            snapshot_out=snapshot_out, snapshot_keep=keep,
            resume_from=resume_from, callbacks=[before_round, after_round])

    # ---- A: the job, killed after round kill_after ---------------------
    try:
        call()
        raise RuntimeError("call A returned: the kill never came")
    except JobKilled:
        pass
    # ---- K: the kill ----------------------------------------------------
    t_kill = time.perf_counter()
    kept_at, kept_score = s["boundary"] or (None, None)
    s["bst"] = s["before"] = s["token"] = s["boundary"] = None
    gc.collect()
    live_after_kill = sum(a.nbytes for a in jax.live_arrays()) \
        - (kept_score.nbytes if kept_score is not None else 0)
    seen = read_back(
        ckpt_dir, resume_at, kept_score if kept_at == resume_at else None)
    job["bundle_state_mismatch"] += seen["wrong"]
    info["after_kill"] = dict(seen, live_bytes=live_after_kill,
                              flight_dumps=len([f for f in os.listdir(workdir)
                                                if f.startswith("flight_")]))
    kept_score = None
    spans.add("kill", time.perf_counter() - t_kill)
    # ---- B: the same call, resumed ---------------------------------------
    s["call"] = "B"
    bst = call(_hook(fault, "resume_from", ckpt_dir, default=ckpt_dir))
    if s["t1"] is None:
        raise RuntimeError("call B returned before the window closed")
    peak = peak_bytes(devices)
    trees, held = s["trees"], s["held"]
    window_s = s["t1"] - s["t0"]
    periods = trees // freq
    last_round = open_round - 1 + trees
    # ---- C: the newest bundle, and what the comparison follows ------------
    _hook(fault, "before_read_back", ckpt_dir)
    kept_at, kept_score = s["boundary"] or (None, None)
    seen = read_back(
        ckpt_dir, last_round, kept_score if kept_at == last_round else None)
    job["bundle_state_mismatch"] += seen["wrong"]
    left = bundles_left(ckpt_dir)
    want_written = periods + open_round // freq
    job["bundles_missing"] = float(
        max(want_written - s["written"], 0)
        + abs(min(keep, want_written) - len(left)))
    info["after_close"] = dict(seen, written=s["written"], left=left,
                               periods=periods)
    last = None
    if trees and trees % freq == 0 and held == last_round \
            and s["last"] == last_round - 1 and kept_at == last_round:
        last = {"index": last_round - 1,
                "tree": host_tree(bst.models[last_round - 1]),
                # the close left the score kept before that round alone
                "before": host_score(s["before"], rows),
                "after": host_score(kept_score, rows)}
    kept_score = None
    s["bst"] = s["before"] = s["token"] = s["boundary"] = None
    info.update(binned_shape=list(bst.boosting.binned.shape),
                binned_dtype=str(bst.boosting.binned.dtype),
                n_pad=int(bst.boosting._n_pad), trees_held=held,
                first_round_of_b=s["first_b"], job=dict(job))

    def free():
        nonlocal bst, ds
        bst = ds = None
        gc.collect()
        shutil.rmtree(workdir, ignore_errors=True)
        if flight_dir_was is None:
            os.environ.pop("LIGHTGBM_TPU_FLIGHT_DIR", None)
        else:
            os.environ["LIGHTGBM_TPU_FLIGHT_DIR"] = flight_dir_was

    return Run(kind="train_job", attempted=trees,
               failed=max(last_round - held, 0), window_s=window_s,
               trees=trees, saves=periods, step_seconds=step_seconds,
               rows=rows, features=features, peak_bytes=peak, X=X, y=y,
               answers=answers, snaps=snaps, last=last, params=params,
               objective=objective_module(manifest, params), aux=fields,
               job=job, info=info, free=free)


def primary(run):
    return PRIMARY, run.window_s / max(run.trees, 1)


def numbers(run, detail=None):
    out = correct.train_numbers(run, detail=detail)
    out.update(run.job)
    return out
