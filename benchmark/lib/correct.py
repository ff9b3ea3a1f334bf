"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, one named number each, each with a limit of
its own from the cell's file (``cells/<cell>.json``; PERF.md gives the
readings every limit was set from).

A number the cell's file gives no limit for is printed and not held.
"""
import numpy as np

from . import forest as forest_lib
from . import reference_gbdt as ref


def _worst(gap):
    return float(np.max(gap)) if len(gap) else 0.0


CHECK_NODES = (0, 1, 2)     # the first tree's first three splits
NOTHING_TO_COMPARE = 1e30   # a gap with one side missing (finite: it is printed as JSON)


def train_numbers(run, detail=None):
    """Follow the first trees the timed path grew and the last one the
    window finished (see reference_gbdt); each number is the worst over
    the followed trees.  ``detail``, a list, is given one dict of per-leaf
    arrays per tree (``calibrate.py`` keeps them, so a limit can be set
    from any statistic of them without another chip run)."""
    out = {"count_mismatch": 0.0, "leaf_value_gap": 0.0, "hess_gap": 0.0,
           "gain_gap": 0.0, "loss_gap": 0.0, "score_gap": 0.0,
           "count_gap": 0.0, "hess_noise": 0.0, "grad_noise": 0.0,
           "split_choice_gap": 0.0, "split_runner_up_gap": 0.0,
           "window_tree_missing": 0.0}
    hz, gz = [], []
    y64 = run.y.astype(np.float64)
    if len(run.answers) < len(run.snaps) or not run.answers:
        out["trees_missing"] = 1.0
        return out
    out["trees_missing"] = 0.0
    objective, aux = run.objective, getattr(run, "aux", None)
    learning_rate = float(run.params["learning_rate"])
    lambda_l2 = float(run.params.get("lambda_l2", 0.0))
    bias0 = objective.init_score(run.y, aux)
    min_hess = float(run.params.get("min_sum_hessian_in_leaf", 1e-3))
    min_rows = int(run.params.get("min_data_in_leaf", 20))

    def compare(tree, r, snap, before):
        live = r["count"] > 0
        out["count_mismatch"] += float(
            np.sum(tree["leaf_count"].astype(np.int64) != r["count"]))
        out["count_gap"] = max(out["count_gap"], _worst(
            np.abs(tree["leaf_count"] - r["count"])[live]
            / np.maximum(r["count"][live], np.median(r["count"]))))
        v_ref = r["value"]
        v_prog = tree["leaf_value"] - r["bias"]
        scale = np.maximum(np.abs(v_ref), np.median(np.abs(v_ref)))
        out["leaf_value_gap"] = max(out["leaf_value_gap"],
                                    _worst(np.abs(v_prog - v_ref) / scale))
        out["hess_gap"] = max(out["hess_gap"], _worst(
            np.abs(tree["leaf_weight"] - r["H"])[live]
            / np.maximum(r["H"][live], np.median(r["H"]))))
        g_ref = r["gain"]
        gscale = np.maximum(np.abs(g_ref), np.median(np.abs(g_ref)))
        out["gain_gap"] = max(out["gain_gap"], _worst(
            np.abs(tree["split_gain"] - g_ref) / gscale))
        # the sums' error in units of sqrt(rows of the leaf) * largest
        # |value|: what a stochastic discretisation of that step predicts
        root = np.sqrt(np.maximum(r["count"], 1))[live]
        hz.append((tree["leaf_weight"] - r["H"])[live] / root)
        G_prog = -(v_prog / learning_rate) * (tree["leaf_weight"] + lambda_l2)
        gz.append((G_prog - r["G"])[live] / root)
        for k, (got, best, runner) in r["splits"].items():
            out["split_choice_gap"] = max(out["split_choice_gap"],
                                          (best - got) / best)
            if k == 0:
                # what a scan that took the second-best feature at the
                # root would read: printed beside it, never held
                out["split_runner_up_gap"] = (best - runner) / best
        if detail is not None:
            detail.append({
                "count_ref": r["count"], "H_ref": r["H"], "G_ref": r["G"],
                "value_ref": v_ref, "gain_ref": g_ref, "G_prog": G_prog,
                "count_prog": tree["leaf_count"], "H_prog": tree["leaf_weight"],
                "value_prog": v_prog, "gain_prog": tree["split_gain"],
                "left_child": tree["left_child"],
                "right_child": tree["right_child"], "loss_ref": r["loss"],
                "splits": np.array([[k, *v] for k, v in r["splits"].items()]
                                   ).reshape(-1, 4)})
        if snap is not None:
            out["loss_gap"] = max(out["loss_gap"], abs(
                objective.loss(snap, y64, aux) - r["loss"]) / r["loss"])
            # in units of the rms move of the score: over all trees so
            # far for the first ones, of this tree for a later one
            moved = r["score"] - (bias0 if before is None else before)
            out["score_gap"] = max(out["score_gap"], float(
                np.max(np.abs(snap - r["score"]))
                / np.sqrt(np.mean(moved * moved))))

    trees = list(run.answers)
    snaps = [run.snaps[t] if t < len(run.snaps) else None
             for t in range(len(trees))]
    befores = [None] * len(trees)
    last = getattr(run, "last", None)
    if last is None:
        # the window finished no tree that the host holds
        out["window_tree_missing"] = 1.0
    elif last["index"] >= len(trees):
        trees.append(last["tree"])
        snaps.append(last["after"])
        befores.append(last["before"])
    steps = ref.follow(run.X, run.y, trees, learning_rate, lambda_l2,
                       starts={len(run.answers): last["before"]}
                       if len(trees) > len(run.answers) else None,
                       check_nodes=CHECK_NODES, min_hess=min_hess,
                       min_rows=min_rows, objective=objective, aux=aux)
    for tree, r, snap, before in zip(trees, steps, snaps, befores):
        compare(tree, r, snap, before)
    out["hess_noise"] = float(np.sqrt(np.mean(np.concatenate(hz) ** 2)))
    out["grad_noise"] = float(np.sqrt(np.mean(np.concatenate(gz) ** 2)))
    return out


def eval_numbers(ev, objective):
    """What the program recorded for the validation set at the last counted
    round against the reference's own: every validation row routed through
    every tree the host held at the close by real-valued thresholds, the
    loss and the AUC of those scores in float64.  ``ev`` is the kind's
    record: ``recorded`` {metric: [a value a round]}, ``rounds`` counted
    (warm ones included), ``X``, ``y``, ``aux``, ``trees``, and under
    ``loss`` and ``auc`` the recorded metrics' names."""
    lists = [ev["recorded"].get(ev[k], []) for k in ("loss", "auc")]
    out = {"eval_rounds_missing":
           float(max(ev["rounds"] - min(map(len, lists)), 0)),
           "eval_logloss_gap": NOTHING_TO_COMPARE,
           "eval_auc_gap": NOTHING_TO_COMPARE}
    if not ev["trees"] or not all(lists):
        return out
    y64 = np.asarray(ev["y"], np.float64)
    score = ref.raw_scores(ev["X"], ev["trees"])
    loss = objective.loss(score, y64, ev["aux"])
    auc = ref.auc(score, y64)
    out["eval_logloss_gap"] = abs(lists[0][-1] - loss) / loss
    out["eval_auc_gap"] = abs(lists[1][-1] - auc) / auc
    out["eval_loss_ref"], out["eval_auc_ref"] = loss, auc
    return out


def score_reference(run, dtype=np.float64):
    """The plain traversal over each block's sampled rows; ``dtype`` is the
    precision of the comparison at each node (the control narrows it)."""
    return [forest_lib.score_rows(run.forest, blk[run.sample], dtype=dtype)
            for blk in run.blocks]


def score_gap(answers, exact):
    """Widest |answer - reference| over (block, scores) pairs, in units of
    the reference scores' root mean square."""
    scale = float(np.sqrt(np.mean(np.concatenate(exact) ** 2)))
    return max((float(np.max(np.abs(got - exact[b]))) / scale
                for b, got in answers), default=0.0)


def score_numbers(run):
    """Every finished request's sampled rows against the plain traversal."""
    exact = score_reference(run)
    return {"score_gap": score_gap(run.sampled, exact),
            "short_answers": float(run.failed),
            "requests_missing": float(run.attempted == 0)}


def judge(numbers, limits):
    """-> (correct, {name: [value, limit]}) over the numbers with a limit."""
    compared = {}
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"the cell's file limits {name!r}, which the "
                           f"comparison does not produce: {sorted(numbers)}")
        compared[name] = [numbers[name], limit]
    ok = all(np.isfinite(v) and v <= lim for v, lim in compared.values())
    return bool(ok), compared
