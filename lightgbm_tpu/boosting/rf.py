"""Random forest mode.

reference: src/boosting/rf.hpp — bagging is mandatory, no shrinkage,
gradients are computed ONCE from the constant boost-from-average scores
(Boosting override, rf.hpp:77-98), every tree carries its class's init
score as a bias (AddBias, rf.hpp:137), and train/valid scores are the
RUNNING MEAN of the trees' outputs (MultiplyScore dance, rf.hpp:140-142);
prediction averages over iterations (average_output).

Percentile-renewing objectives (L1/quantile/MAPE) renew leaf outputs
against the CONSTANT init score (reference residual_getter, rf.hpp:133);
the jitted step is rebuilt in that mode once the init scores are final.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .gbdt import GBDT, K_EPSILON
from ..tree import tree_to_host
from ..utils.log import log_warning


class RF(GBDT):
    boosting_type = "rf"
    _stream_ok = False       # const-gradient renewal + running-mean score
    #                          renorm ride the resident iteration program
    _defer_host_ok = False   # custom eager finish (averaged extension)

    def __init__(self, config, train_set, objective):
        if not (config.bagging_freq > 0 and 0.0 < config.bagging_fraction < 1.0):
            raise ValueError("random forest requires bagging "
                             "(bagging_freq > 0 and bagging_fraction < 1)")
        if objective is None:
            raise ValueError("RF mode does not support custom objective "
                             "functions, please use built-in objectives")
        super().__init__(config, train_set, objective)
        self.shrinkage_rate = 1.0
        K = self.num_tree_per_iteration
        # constant per-class init scores; NOT added to the score vectors —
        # they ride inside each tree as a bias (reference rf.hpp:84,137)
        if config.boost_from_average:
            self.init_scores = [objective.boost_from_score(k) for k in range(K)]
        self._init_score_added = True   # disable GBDT.boost_from_average
        # gradients once, from the constant init scores (rf.hpp:77-98)
        init_col = jnp.asarray(self.init_scores, jnp.float32)[:, None]
        score0 = jnp.broadcast_to(init_col, self.train_score.shape)
        g, h = self._gradients_fn(score0)
        self._grad, self._hess = g, h
        # percentile-renewing objectives (L1/quantile/MAPE) must renew
        # against the constant init score (reference residual_getter,
        # rf.hpp:130-135); rebuild the jitted step with that mode now that
        # init_scores are final
        self._rf_renew_const_init = True
        self._build_jit_fns()

    def _macro_const_grads(self):
        """The macro-step scan body (boosting/macro.py) uses RF's
        once-computed gradients as loop-invariant runtime inputs."""
        return self._grad, self._hess

    def _finish_chunk_inner(self, stacked_seq, c, shrinks, it0,
                            gstats_seq=None) -> bool:
        """RF chunk finish: eager averaged extension per iteration from ONE
        bulk device fetch; valid scores renormalized by the fused
        running-mean scan (macro.build_chunk_valid's rf mode)."""
        import jax
        K = self.num_tree_per_iteration
        bh, gh = jax.device_get((stacked_seq, gstats_seq))
        stopped = False
        kept = 0
        for j in range(c):
            new_models, any_split = [], False
            for k in range(K):
                tree_k = jax.tree_util.tree_map(
                    lambda x: np.asarray(x[j][k]), bh)
                ht = tree_to_host(tree_k, self.train_set, 1.0)
                if ht.num_leaves > 1:
                    any_split = True
                if abs(self.init_scores[k]) > K_EPSILON:
                    ht.add_bias(self.init_scores[k])
                new_models.append(ht)
            if not any_split:
                log_warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                stopped = True
                break
            self.models.extend(new_models)
            self._note_trees(it0 + j, None if gh is None else gh[j])
            kept = j + 1
        self.models_version += 1
        if kept:
            seq_kept = (stacked_seq if kept == c else
                        jax.tree_util.tree_map(lambda x: x[:kept],
                                               stacked_seq))
            its = jnp.arange(it0, it0 + kept, dtype=jnp.int32)
            for i in range(len(self.valid_scores)):
                self.valid_scores[i] = self._chunk_valid_update(
                    self.valid_scores[i], seq_kept, self.valid_binned[i],
                    its)
        self.iter = it0 + kept
        return stopped

    def train_one_iter(self, grad=None, hess=None) -> bool:
        if grad is not None:
            raise ValueError("RF mode does not support custom objectives")
        single = self._chunk_single()
        if single is not None:
            return single
        it = self.iter
        mask = self._bagging_mask(it)
        # run the shared step on it*mean (so "+ tree" keeps the sum), then
        # renormalize to the running mean including the per-tree bias
        s1 = self.train_score * it
        s2, stacked, _, cu, cr, self._quant_scales, gstats = self._iter_fn(
            self.binned, s1, mask, self._grad, self._hess,
            self._feature_masks(), jnp.float32(1.0),
            self._node_key(), *self._cegb_state)
        self._cegb_state = (cu, cr)
        init_col = jnp.asarray(self.init_scores, jnp.float32)[:, None]
        self.train_score = (s2 + init_col) / (it + 1)
        return self._finish_iter(stacked, gstats)

    def _finish_iter(self, stacked, gstats=None) -> bool:
        K = self.num_tree_per_iteration
        it = self.iter
        import jax
        new_models = []
        should_continue = False
        for k in range(K):
            tree_k = jax.tree_util.tree_map(lambda x: np.asarray(x[k]), stacked)
            ht = tree_to_host(tree_k, self.train_set, 1.0)
            if ht.num_leaves > 1:
                should_continue = True
            if abs(self.init_scores[k]) > K_EPSILON:
                ht.add_bias(self.init_scores[k])
            new_models.append(ht)
        if not should_continue:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True
        self.models.extend(new_models)
        self._note_trees(it, None if gstats is None else np.asarray(gstats))
        init_col = jnp.asarray(self.init_scores, jnp.float32)[:, None]
        for i in range(len(self.valid_scores)):
            vs = self._valid_update(self.valid_scores[i] * it, stacked,
                                    self.valid_binned[i])
            self.valid_scores[i] = (vs + init_col) / (it + 1)
        self.iter += 1
        return False
