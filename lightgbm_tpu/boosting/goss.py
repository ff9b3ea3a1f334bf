"""GOSS: gradient-based one-side sampling.

reference: Ke et al. 2017 (NIPS), Algorithm 2; src/boosting/goss.hpp:24-132.
Each sampled round keeps the ``top_k = max(1, floor(top_rate * n))`` rows
of largest |grad*hess| (summed over classes), draws exactly ``other_k =
floor(other_rate * n)`` rows from the rest and multiplies their gradients
and hessians by ``(n - top_k) / other_k`` (f32); every other row weighs 0.
No sampling during the first 1/learning_rate rounds (goss.hpp:126-131).

TPU form: a weight mask (1 / amplified / 0) computed on the device from the
round's gradients, inside the round program; shapes stay static.  Nothing
sorts the rows:

- the top set is every real row whose f32 score is ``>=`` the k-th largest,
  found by a 31-step bisection on the int32 bit pattern (non-negative f32
  values order like their bits): a count of the rows at or below each
  step's bits decides it, so the threshold equals ``lax.top_k(score,
  top_k)[0][-1]`` to the bit.  Ties at the threshold are all kept, as
  goss.hpp keeps them; padding rows never enter;
- the rest is the ``other_k`` rest rows with the smallest 32-bit random keys
  (``jax.random.bits`` from the round's GOSS subkey), the cut found by a
  32-step bisection on the keys, ties at the cut taken in row order by a
  prefix count (a bisection on the row index, run only in a round whose cut
  holds more rows than it needs).

Every count is a sum over all rows of the global array, so under a data
mesh it is summed over the data axis.

Where the rounds grower's fused arm runs on one chip over quantized
gradients, the tree is grown on the kept rows alone (``kept_rows_first``,
``GBDT._build_jit_fns_inner``): once a round, after the values are
quantized on the full rows, the kept rows are gathered, in row order, to
the front of a row buffer of the matrix's own size, and the histogram
passes and routes stop at the last of them.  Integer sums do not depend on
the order of their terms, so the tree is the mask form's bit for bit;
every row's leaf then comes from the finished tree.  Elsewhere (a mesh,
the streamed executor, the staged arm, f32 gradients, renewal from true
sums) the tree is grown on all rows under the mask.

Where this departs from goss.hpp (the count and the multiplier are equal):
goss.hpp selects per thread block, this program over all rows at once, as
the paper's Algorithm 2 does; goss.hpp draws its rest row by row with
adaptive probabilities, this program takes the smallest keys; the paper
ranks by |g|, goss.hpp and this program by |g*h|.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .gbdt import GBDT


def _count(mask):
    return jnp.sum(mask.astype(jnp.int32))


def _smallest_at_least(count_upto, hi, k, steps):
    """Smallest ``t`` in [0, hi] with ``count_upto(t) >= k`` (``hi`` where
    none is), by bisection; ``count_upto`` does not decrease in ``t``."""
    def step(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = count_upto(mid) >= k
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)
    lo, _ = lax.fori_loop(0, steps, step, (jnp.zeros_like(hi), hi))
    return lo


def kth_largest_bits(bits, k):
    """Bit pattern of the k-th largest of the non-negative f32 values whose
    int32 bits are ``bits`` (rows to leave out hold -1): the (m - k + 1)-th
    smallest of the m held, by bisection over [0, 2**31 - 1]."""
    held = bits >= 0
    return _smallest_at_least(lambda t: _count(held & (bits <= t)),
                              jnp.int32(0x7FFFFFFF), _count(held) - k + 1, 31)


def smallest_keys(keys, pool, k):
    """Rows of ``pool`` holding the ``k`` smallest uint32 ``keys``, ties at
    the cut taken in row order (a prefix count, bisected over the row
    index where the cut holds more rows than are wanted); all of ``pool``
    where it has fewer."""
    cut = _smallest_at_least(lambda t: _count(pool & (keys <= t)),
                             jnp.uint32(0xFFFFFFFF), k, 32)
    below = pool & (keys < cut)
    at = pool & (keys == cut)
    need = k - _count(below)
    row = lax.iota(jnp.int32, keys.shape[0])

    def first_in_row_order(at):
        last = _smallest_at_least(lambda r: _count(at & (row <= r)),
                                  jnp.int32(keys.shape[0] - 1), need,
                                  max(1, (keys.shape[0] - 1).bit_length()))
        return at & (row <= last)
    return below | lax.cond(_count(at) > need, first_in_row_order,
                            lambda at: at, at)


def kept_rows_first(weights):
    """Where a round's kept rows go in the row buffer: ``(rows, live)``,
    ``rows`` [n] i32 whose first ``live`` entries are the rows of weight
    above 0 in row order (the rest: some row, never read), and ``live``
    (i32) how many were kept.

    A kept row moves toward the front by the count of weight-0 rows before
    it, one bit of that distance a step, low bits first: two kept rows
    never land on one place, so ceil(log2 n) selects over shifted copies
    place them all, with no scatter and no sort (at 25.2M rows on one
    v5e, 41.2 ms with a ``jnp.cumsum`` count, where a scatter takes
    186.7: ``PERF.md`` section 5).  The prefix count that gives the
    distance is ceil(log2 n) shifted adds: the TPU compiler takes 7-8 s
    over ``jnp.cumsum`` of 262,144 rows, and under half a second over
    these."""
    n = weights.shape[0]
    keep = weights > 0
    steps = max(n - 1, 1).bit_length()
    kept_upto = keep.astype(jnp.int32)
    for b in range(steps):
        s = 1 << b
        kept_upto = kept_upto + jnp.concatenate(
            [jnp.zeros(s, jnp.int32), kept_upto[:-s]])
    row = lax.iota(jnp.int32, n)
    dist = jnp.where(keep, row + 1 - kept_upto, -1)   # -1: no kept row
    for b in range(steps):
        s = 1 << b
        d_in = jnp.concatenate([dist[s:], jnp.full(s, -1, jnp.int32)])
        r_in = jnp.concatenate([row[s:], jnp.zeros(s, jnp.int32)])
        come = (d_in >= 0) & ((d_in >> b) & 1 == 1)
        stay = (dist >= 0) & ((dist >> b) & 1 == 0)
        row = jnp.where(come, r_in, row)
        dist = jnp.where(come, d_in, jnp.where(stay, dist, -1))
    return row, kept_upto[-1]


# The gather of the kept rows runs this many buffer columns a step: its
# cost is per column gathered (one v5e, 25.2M x 67 uint8, 7.55M kept:
# 94.1 ms in blocks of 2**17, 120.4 in blocks of 2**20, 418.3 over every
# column at once; PERF.md section 5)
KEPT_BLOCK = 1 << 17


def take_kept(planes, rows, live):
    """``planes`` [P, n] with their columns ``rows[:live]`` at the front of
    a [P, n] buffer; past ``live`` it holds columns no one reads.  Where
    the kept rows are the first ``live`` (a round that kept every row)
    that is ``planes`` itself.  Else the gather runs a block of columns at
    a time and stops at the block that holds the last kept row; a last
    block that does not fit starts at ``n - block`` and rewrites columns
    the one before took the same way."""
    P, n = planes.shape
    C = min(KEPT_BLOCK, n)

    def step(i, buf):
        s = jnp.minimum(i * C, n - C)
        cols = lax.dynamic_slice_in_dim(rows, s, C)
        return lax.dynamic_update_slice_in_dim(
            buf, planes.at[:, cols].get(mode="promise_in_bounds"), s, 1)

    def gather(planes):
        return lax.fori_loop(0, (live + C - 1) // C, step,
                             jnp.zeros_like(planes))
    in_place = rows[jnp.maximum(live - 1, 0)] == live - 1
    return lax.cond(in_place, lambda p: p, gather, planes)


def make_goss_weights(n, top_rate, other_rate):
    """The round's selection for ``n`` real rows: ``fn(grad, hess, key,
    row_valid) -> (weights [n_pad] f32, [kept, top] int32)``.  ``grad`` and
    ``hess`` are [K, n_pad]; ``row_valid`` is 0 on padding rows."""
    top_k = max(1, int(top_rate * n))
    other_k = int(other_rate * n)
    amp = np.float32(n - top_k) / np.float32(max(other_k, 1))

    def goss_weights(grad, hess, key, row_valid):
        with jax.named_scope("lgbm.goss"):
            real = row_valid > 0
            score = jnp.sum(jnp.abs(grad * hess), axis=0)
            bits = jnp.where(real, lax.bitcast_convert_type(score, jnp.int32),
                             -1)
            is_top = bits >= kth_largest_bits(bits, top_k)
            if other_k > 0:
                keys = jax.random.bits(key, score.shape, jnp.uint32)
                rest = smallest_keys(keys, real & ~is_top, other_k)
            else:
                rest = jnp.zeros_like(is_top)
            weights = jnp.where(is_top, jnp.float32(1.0),
                                jnp.where(rest, amp, jnp.float32(0.0)))
            top = _count(is_top)
            return weights, jnp.stack([top + _count(rest), top])
    return goss_weights


class GOSS(GBDT):
    boosting_type = "goss"
    _rows_sampled = True

    def __init__(self, config, train_set, objective):
        super().__init__(config, train_set, objective)
        if config.bagging_freq > 0 and config.bagging_fraction < 1.0:
            raise ValueError("cannot use bagging in GOSS")
        if config.top_rate + config.other_rate > 1.0:
            raise ValueError("top_rate + other_rate cannot be larger than 1.0")
        # the weights the newest tree was grown on (device [n_pad] f32): a
        # round's sample, or the row mask for an unsampled round
        self.last_row_weights = None
        # the chunk program (boosting/macro.py) traces the SAME function
        self._macro_goss_mask = make_goss_weights(
            self.num_data, config.top_rate, config.other_rate)
        self._goss_mask_fn = jax.jit(self._macro_goss_mask)

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
            mask, counts = self._goss_mask_fn(g, h, sub, self._row_valid)
            self.last_row_weights = mask
            return self._train_with(g, h, mask, counts)
        self.last_row_weights = self._row_valid
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

    def _train_with(self, grad, hess, mask, counts):
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
        return self._finish_iter(stacked, gstats.at[:, 6:8].set(counts))
