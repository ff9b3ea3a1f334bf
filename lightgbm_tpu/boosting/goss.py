"""GOSS: gradient-based one-side sampling.

reference: src/boosting/goss.hpp:24-132 — keep the top ``top_rate`` fraction
of rows by |grad*hess|, sample ``other_rate`` of the rest and amplify their
weight by (1-top_rate)/other_rate; no sampling during the first
1/learning_rate warm-up iterations (goss.hpp:126-131).

TPU form: pure weight mask (1 / amplified / 0) computed on device from the
current gradients — no index compaction, shapes stay static.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .gbdt import GBDT


class GOSS(GBDT):
    boosting_type = "goss"

    def __init__(self, config, train_set, objective):
        super().__init__(config, train_set, objective)
        if config.bagging_freq > 0 and config.bagging_fraction < 1.0:
            raise ValueError("cannot use bagging in GOSS")
        if config.top_rate + config.other_rate > 1.0:
            raise ValueError("top_rate + other_rate cannot be larger than 1.0")

        top_rate = config.top_rate
        other_rate = config.other_rate
        n = self.num_data
        n_pad = self._n_pad
        row_valid = self._row_valid

        def goss_mask_raw(grad, hess, key, row_valid):
            # grad/hess: [K, n_pad]; sharding-pad rows (row_valid == 0) are
            # pushed below any real score so they can never enter the top set
            score = jnp.sum(jnp.abs(grad * hess), axis=0)
            score = score * row_valid - (1.0 - row_valid)
            top_k = max(1, int(top_rate * n))
            thresh = jax.lax.top_k(score, top_k)[0][-1]
            is_top = score >= thresh
            rest_p = other_rate / max(1e-12, 1.0 - top_rate)
            keep_rest = jax.random.uniform(key, (n_pad,)) < rest_p
            amp = (1.0 - top_rate) / max(other_rate, 1e-12)
            return jnp.where(is_top, 1.0,
                             jnp.where(keep_rest, amp, 0.0)) * row_valid

        # the macro-step scan body (boosting/macro.py) traces the SAME
        # function with the row mask riding as the scan input
        self._macro_goss_mask = goss_mask_raw
        self._goss_mask_fn = jax.jit(
            lambda grad, hess, key: goss_mask_raw(grad, hess, key,
                                                  row_valid))

    def _bagging_mask(self, it):
        return self._row_valid

    def train_one_iter(self, grad=None, hess=None):
        if grad is None:
            # macro path: warm-up gating and sampling ride inside the
            # chunk program (_macro_goss_inputs); keeps per-iteration and
            # chunked GOSS on the same compiled loop body
            single = self._chunk_single()
            if single is not None:
                return single
        # warm-up: no sampling for the first 1/learning_rate iterations
        warmup = 1.0 / max(self.config.learning_rate, 1e-12)
        if grad is None and self.iter >= warmup:
            self.boost_from_average()
            g, h = self._boost(self.train_score)
            self._goss_rng_key, sub = jax.random.split(self._goss_rng_key)
            mask = self._goss_mask_fn(g, h, sub)
            return self._train_with(g, h, mask)
        return super().train_one_iter(grad, hess)

    def _macro_goss_inputs(self, c, it0, lrs):
        """Per-chunk GOSS subkeys: sampling iterations consume a split of
        the stream in the exact per-iteration order; warm-up iterations
        (no sampling) leave the stream untouched and get a dummy key.
        ``lrs`` carries the per-iteration learning rate (a reset_parameter
        schedule moves the 1/lr warm-up threshold per iteration)."""
        keys, flags = [], []
        for j in range(c):
            warmup = 1.0 / max(lrs[j], 1e-12)
            if it0 + j >= warmup:
                self._goss_rng_key, sub = jax.random.split(self._goss_rng_key)
                keys.append(sub)
                flags.append(True)
            else:
                keys.append(jnp.zeros_like(self._goss_rng_key))
                flags.append(False)
        return jnp.stack(keys), jnp.asarray(np.asarray(flags))

    def _train_with(self, grad, hess, mask):
        if self._stream is not None:
            # out-of-core streamed executor (data/stream.py): same mask,
            # same RNG order, streamed tree growth
            return self._stream_step(grad, hess, mask)
        (self.train_score, stacked, leaf_ids, cu, cr,
         self._quant_scales, gstats) = self._iter_fn(
            self.binned, self.train_score, mask, grad, hess,
            self._feature_masks(), jnp.float32(self.shrinkage_rate),
            self._node_key(), *self._cegb_state)
        self._cegb_state = (cu, cr)
        return self._finish_iter(stacked, gstats)
