"""Faults planted under the timed path, to show that ``correct`` comes out
false (``tests/test_correct.py`` on the CPU twins, ``calibrate.py --fault``
on the chip at the cell's own size).  No benchmark run plants one.

A fault is an object with hooks the traffic kinds call when they are given
one: ``after_build(booster)``, ``before_step(booster)`` ->  token,
``after_step(booster, token)``, ``after_pull(booster)``.
"""
import numpy as np


class Fault:
    name = "none"

    def after_build(self, bst):
        pass

    def before_step(self, bst):
        return None

    def after_step(self, bst, token):
        pass

    def after_pull(self, bst):
        pass


class StateUnchanged(Fault):
    """A step that returns its state unchanged: the train score is put back
    to what it was before every round after the first ``sound`` ones."""
    name = "state_unchanged"
    sound = 1

    def before_step(self, bst):
        import jax.numpy as jnp
        return jnp.copy(bst.boosting.train_score)

    def after_step(self, bst, token):
        if bst.boosting.iter > self.sound:
            bst.boosting.train_score = token


class LateStateUnchanged(StateUnchanged):
    """The same fault starting only inside the window (the two warm rounds
    are sound): only the tree followed from the window can show it."""
    name = "late_state_unchanged"
    sound = 2


class HalfBatch(Fault):
    """Half of the rows left out of every histogram and leaf sum, the leaf
    values taken over the rest."""
    name = "half_batch"

    def after_build(self, bst):
        import jax.numpy as jnp
        b = bst.boosting
        keep = (jnp.arange(b._row_valid.shape[0]) % 2 == 0)
        b._row_valid = b._row_valid * keep.astype(b._row_valid.dtype)


class AlteredAnswer(Fault):
    """One leaf's value flipped in the host model, where the trees the
    window hands out are produced: in tree ``first`` and every later one
    as it is pulled."""
    name = "altered_answer"
    first = 1

    def __init__(self):
        self.altered = self.first

    def after_pull(self, bst):
        models = bst.boosting._models
        while self.altered < len(models):
            value = models[self.altered].leaf_value
            value[int(np.argmax(np.abs(value)))] *= -1.0
            self.altered += 1


class LateAlteredAnswer(AlteredAnswer):
    """The same fault in the window's trees only."""
    name = "late_altered_answer"
    first = 2


class StaleEval(Fault):
    """An evaluation one round stale: every evaluation of the validation
    sets after the first answers with the round before's values."""
    name = "stale_eval"

    def after_build(self, bst):
        inner = bst.boosting.eval_valid
        kept = []

        def eval_valid():
            kept.append(inner())
            return kept[-2] if len(kept) > 1 else kept[-1]
        bst.boosting.eval_valid = eval_valid


class ValidTreeSkipped(Fault):
    """A validation score that skipped a tree: the update of the validation
    scores for round ``skip`` (the first of the window) hands them back as
    they were."""
    name = "valid_tree_skipped"
    skip = 2

    def after_build(self, bst):
        b = bst.boosting
        inner = b._chunk_valid_update

        def update(vscore, stacked_seq, binned, its):
            if int(its[0]) == self.skip:
                return vscore
            return inner(vscore, stacked_seq, binned, its)
        b._chunk_valid_update = update


class ScoreFault(Fault):
    """Scoring faults wrap ``Booster.predict`` where the answer is made."""

    def alter(self, out, call):
        return out

    def after_build(self, bst):
        inner = bst.predict
        calls = []

        def predict(data, **kw):
            calls.append(1)
            return self.alter(np.array(inner(data, **kw)), len(calls))
        bst.predict = predict


class AlteredScore(ScoreFault):
    """Every thousandth row's score moved by one."""
    name = "altered_answer"

    def alter(self, out, call):
        out[::1000] += 1.0
        return out


class HalfRequest(ScoreFault):
    """The second half of every request left out, the mean of the first
    half given in its place."""
    name = "half_batch"

    def alter(self, out, call):
        half = len(out) // 2
        out[half:] = out[:half].mean()
        return out


class StaleAnswer(ScoreFault):
    """Every request answered with the first request's scores."""
    name = "state_unchanged"
    first = None

    def alter(self, out, call):
        if self.first is None:
            self.first = out.copy()
        return self.first.copy()


TRAIN = {f.name: f for f in (StateUnchanged, HalfBatch, AlteredAnswer,
                             LateStateUnchanged, LateAlteredAnswer)}
EVAL = {f.name: f for f in (StaleEval, ValidTreeSkipped)}
SCORE = {f.name: f for f in (StaleAnswer, HalfRequest, AlteredScore)}
