"""``train_loop_goss``: ``train_loop`` under gradient-based one-side
sampling (``boosting=goss``).

The run is ``kinds/train_loop.py``'s own, round for round the same timed
window; this kind listens at the hooks that run offers a planted fault
(``lib/faults.py``).  GOSS leaves the first 1/learning_rate rounds
unsampled, so the traffic's warm rounds run through that warm-up and the
last of them is the first sampled round: the window holds sampled rounds
only.  What a sampled tree was grown on are its row weights, which the
program hands out as ``Booster.boosting.last_row_weights`` (the newest
tree's); a program without them cannot be followed, and the run ends right
after the ``Booster`` is built, with exit code 5.  During set-up the kind
takes the first sampled round's weights and the train score it started
from to the host; once the window has closed, the window's last tree's.

The comparison is ``correct.train_numbers`` with ``lib/reference_goss.py``
in the numeric reference's place, twice: over the first warm trees and the
window's last tree (from the program's score before it), and over the same
warm trees and the first sampled round (from its own snapshot); each number
is the worse of the two.  The split check runs on the first tree and on the
first sampled one.  Beside them come the sample's own numbers
(``reference_goss.sample_numbers``) over both sampled trees.
"""
import sys
from types import SimpleNamespace
from unittest import mock

import numpy as np

from benchmark.lib import correct, faults, lookup
from benchmark.lib import reference_goss as ref_goss
from benchmark.lib.traffic import host_tree

train_loop = lookup.load_module(lookup.REPO / "benchmark/kinds/train_loop.py")

PRIMARY = train_loop.PRIMARY
LIMITS = dict(train_loop.LIMITS, goss_top_missing=0, goss_top_extra=0,
              goss_rest_count_gap=0, goss_rounds_missing=0)
primary = train_loop.primary


class _GossFault(faults.Fault):
    """A fault of the selection: the chunk program the booster builds at its
    first round traces ``alter(weights, counts, ...)`` around the program's
    own selection."""

    def after_build(self, bst):
        b = bst.boosting
        inner = b._macro_goss_mask

        def selection(grad, hess, key, row_valid):
            return self.alter(inner, grad, hess, key, row_valid)
        b._macro_goss_mask = selection


class Unamplified(_GossFault):
    """The sampled rest at weight 1."""
    name = "goss_unamplified"

    def alter(self, inner, grad, hess, key, row_valid):
        import jax.numpy as jnp
        w, counts = inner(grad, hess, key, row_valid)
        return jnp.minimum(w, 1.0), counts


class TopRandom(_GossFault):
    """A random top set of the right size: each row takes the weight the
    selection gives the row a fixed random permutation puts in its place."""
    name = "goss_top_random"

    def alter(self, inner, grad, hess, key, row_valid):
        import jax
        perm = jax.random.permutation(jax.random.PRNGKey(7), grad.shape[1])
        return inner(grad[:, perm], hess[:, perm], key, row_valid)


class Never(faults.Fault):
    """The warm-up never ends: no round is sampled."""
    name = "goss_never"

    def after_build(self, bst):
        import jax.numpy as jnp
        b = bst.boosting
        inner = b._macro_goss_inputs

        def inputs(c, it0, lrs):
            keys, flags = inner(c, it0, lrs)
            return keys, jnp.zeros_like(flags)
        b._macro_goss_inputs = inputs


FAULTS = dict(faults.TRAIN, **{f.name: f for f in (Unamplified, TopRandom,
                                                   Never)})


class _Listener(faults.Fault):
    """Hands every hook on to the planted fault, if any; keeps the
    ``Booster``, and the first sampled round's weights and the train score
    it started from (host, float64, padding rows dropped)."""

    def __init__(self, inner, first, rows):
        self.inner = inner or faults.Fault()
        self.first, self.rows = first, rows
        self.bst = None
        self.sampled = None

    def after_build(self, bst):
        if not hasattr(bst.boosting, "last_row_weights"):
            print("benchmark/kinds/train_loop_goss.py: this program hands "
                  "out no row weights (Booster.boosting.last_row_weights): "
                  "a GOSS tree cannot be followed without them",
                  file=sys.stderr, flush=True)
            sys.exit(5)
        self.bst = bst
        self.inner.after_build(bst)

    def host(self, a):
        return np.asarray(a)[..., :self.rows].reshape(-1).astype(np.float64)

    def before_step(self, bst):
        mine = (self.host(bst.boosting.train_score)
                if bst.boosting.iter == self.first else None)
        return self.inner.before_step(bst), mine

    def after_step(self, bst, token):
        inner, before = token
        self.inner.after_step(bst, inner)
        if before is not None:
            self.sampled = {"index": self.first, "before": before,
                            "after": self.host(bst.boosting.train_score),
                            "weights": self.host(
                                bst.boosting.last_row_weights)}

    def after_pull(self, bst):
        self.inner.after_pull(bst)


def run(manifest, config, traffic, cell_file, seed, seconds,
        spans, compiles, devices, on_window=None, fault=None):
    first = int(traffic["warm_rounds"]) - 1
    rows = int(config["rows"])
    ear = _Listener(fault, first, rows)
    out = train_loop.run(manifest, config, traffic, cell_file, seed, seconds,
                         spans, compiles, devices, on_window, ear)
    models = ear.bst.models
    out.sampled = ear.sampled
    if out.sampled is not None and len(models) > first:
        out.sampled["tree"] = host_tree(models[first])
    if out.last is not None:
        out.last["weights"] = ear.host(ear.bst.boosting.last_row_weights)
    out.kind = "train_loop_goss"
    free = out.free

    def free_all():
        ear.bst = None
        free()
    out.free = free_all
    return out


def _worse(a, b):
    return {k: max(a.get(k, 0.0), b.get(k, 0.0)) for k in {*a, *b}}


def _follow(view, weights, check_at, num_bins, detail):
    """``correct.train_numbers`` over ``view`` with the GOSS reference in
    the numeric one's place, and the leaves' sums in units of the 4-level
    rounding (``reference_goss.rounding_z``)."""
    reference = ref_goss.GossReference(weights, check_at)
    steps = []
    with mock.patch.object(correct, "ref", reference):
        out = correct.train_numbers(view, detail=steps)
    z = [ref_goss.rounding_z(st, sc, num_bins)
         for st, sc in zip(steps, reference.scales)]
    out["grad_rounding_z"] = max((g for g, _ in z), default=0.0)
    out["hess_rounding_z"] = max((h for _, h in z), default=0.0)
    if detail is not None:
        detail.extend(steps)
    return out


def numbers(run, detail=None):
    follow = len(run.answers)
    p = run.params
    bins = int(p.get("num_grad_quant_bins", 4))
    last = run.last
    if last is not None and last["index"] < follow:
        last = None
    # the warm trees and the window's last tree
    window = SimpleNamespace(**vars(run))
    window.last = last
    out = _follow(window, {follow: last["weights"]} if last else {}, (0,),
                  bins, detail)
    sampled = getattr(run, "sampled", None)
    checked = [t for t in (sampled, last) if t is not None and "weights" in t]
    if sampled is not None and "tree" in sampled:
        first = SimpleNamespace(**vars(run))
        first.last = dict(sampled, index=follow)
        more = _follow(first, {follow: sampled["weights"]}, (follow,), bins,
                       detail)
        more.pop("window_tree_missing")
        out = _worse(out, more)
    out["goss_rounds_missing"] = float(2 - len(checked))
    top_rate = float(p.get("top_rate", 0.2))
    other_rate = float(p.get("other_rate", 0.1))
    for t in checked:
        out = _worse(out, ref_goss.sample_numbers(
            t["before"], t["weights"], np.asarray(run.y, np.float64),
            run.objective, top_rate, other_rate, run.aux))
    for name in ("goss_top_missing", "goss_top_extra", "goss_rest_count_gap",
                 "goss_weight_gap", "goss_rest_bias_z"):
        out.setdefault(name, correct.NOTHING_TO_COMPARE)
    return out
