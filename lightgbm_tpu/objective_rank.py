"""Ranking objectives: LambdaRank-NDCG and RankXENDCG.

reference: src/objective/rank_objective.hpp — RankingObjective base (:48,
per-query parallel loop), LambdarankNDCG (:98, pairwise lambdas x deltaNDCG
with sigmoid table and optional normalization), RankXENDCG (:288).

TPU re-design of the per-query loop (SURVEY hard part (d)): queries are
**bucketed by padded size** (next power of two) at init; a bucket is a
dense [queries, Q] block of *slots*, a query's documents first and padding
after them.  A query's rows are contiguous, so a bucket reads its scores
with one slice a query (``lax.gather`` of Q-long windows at the query's
first row, no per-slot index table) and every row reads its gradient back
through the **inverse slot map** (one gather over the concatenated
buckets).  The tables (first row, length, label, gain and inverse max DCG
a bucket, the inverse slot map) are built once at init, vectorized over
queries, and enter the jitted round program as runtime arguments
(``device_tables``): closed over, they would be constants of its HLO.

LambdaRank's pairs are the reference's: ranks i < j (by score, best first,
ties in row order) with i below ``lambdarank_truncation_level`` and unequal
labels.  Under ``lax.map`` over chunks of a bucket's queries, every slot's
rank is counted ([queries, Q, Q] comparisons, fused into their sum), the
documents of the ranks below the level are picked by their rank, and the
pairs are evaluated as [queries, min(level, Q), Q] with j in slot order, so
the sums come out in slot order and nothing is sorted, gathered or
scattered inside a bucket.  No sigmoid lookup table — the VPU computes
exact sigmoids faster than a gather would be.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .config import Config
from .objectives import ObjectiveFunction
from .obs.trace import span as _span

K_EPSILON = 1e-15
_MIN_BUCKET = 8
_PAIR_BUDGET = 1 << 24  # max elements per [chunk, Q, Q] intermediate


def _bucket_sizes(sizes: np.ndarray) -> np.ndarray:
    """Padded size (next power of two, at least ``_MIN_BUCKET``) a query."""
    s = np.maximum(np.asarray(sizes, np.int64), 1)
    return np.maximum(_MIN_BUCKET, 1 << np.ceil(np.log2(s)).astype(np.int64))


class RankingObjective(ObjectiveFunction):
    need_group = True

    def _pair_rows(self, Q: int) -> int:
        """Ranks a bucket of padded size Q evaluates against all of its
        slots (LambdaRank: the truncation level); 0 where no pair is
        formed."""
        return 0

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            raise RuntimeError("Ranking tasks require query information")
        with _span("rank.init", ring=True, rows=int(num_data)) as seam:
            seam.set(**self._build_tables(metadata, num_data))

    def _build_tables(self, metadata, n):
        """Bucket tables and the inverse slot map, vectorized over queries
        (one NumPy pass a bucket; there are at most a dozen buckets)."""
        qb = np.asarray(metadata.query_boundaries, np.int64)
        nq = len(qb) - 1
        sizes = np.diff(qb)
        lbl = np.asarray(metadata.label, np.float32)
        padded = _bucket_sizes(sizes)
        self.max_bucket = int(padded.max()) if nq else _MIN_BUCKET
        base = np.zeros(nq, np.int64)       # a query's first slot, flat
        buckets, self.bucket_chunks = {}, {}
        offset = pair_slots = 0
        for Q in np.unique(padded):
            Q = int(Q)
            qids = np.flatnonzero(padded == Q)
            # whole chunks of the pair evaluation: the rest are queries of
            # no documents, which start past the last row
            chunks = self._chunks(Q, len(qids))
            rows_b = -(-len(qids) // chunks) * chunks
            start = np.full(rows_b, n, np.int32)
            cnt = np.zeros(rows_b, np.int32)
            start[:len(qids)] = qb[qids]
            cnt[:len(qids)] = sizes[qids]
            slot_row = start[:, None].astype(np.int64) + np.arange(Q)
            valid = np.arange(Q) < cnt[:, None]
            label = np.where(valid, lbl[np.minimum(slot_row, n - 1)], -1.0)
            base[qids] = offset + np.arange(len(qids)) * Q
            buckets[Q] = {"start": start, "cnt": cnt,
                          "label": label.astype(np.float32)}
            self.bucket_chunks[Q] = chunks
            offset += rows_b * Q
            pair_slots += rows_b * self._pair_rows(Q) * Q
        self._bucket_extras(buckets)
        # row -> slot of the concatenated buckets
        slot_of_row = (np.repeat(base - qb[:-1], sizes)
                       + np.arange(n)).astype(np.int32)
        self.num_slots = int(offset)
        self.device_tables = jax.tree_util.tree_map(
            jnp.asarray, {"buckets": buckets, "slot_of_row": slot_of_row})
        return {"queries": int(nq), "slots": int(offset),
                "pair_slots": int(pair_slots),
                "label_pairs": int(self._label_pairs(sizes, lbl))}

    def _chunks(self, Q: int, queries: int) -> int:
        """Pieces a bucket's evaluation runs in (``lax.map``), so that one
        piece's intermediates stay under ``_PAIR_BUDGET`` elements."""
        return 1

    def _bucket_extras(self, buckets) -> None:
        """Per-bucket tables of the subclass, added in place."""

    def _label_pairs(self, sizes, lbl) -> int:
        return 0

    def get_gradients(self, score, tables):
        """``tables``: ``device_tables``, a runtime argument of the jitted
        program that traces this."""
        n = self.num_data
        # room for the longest window past the last row (a clamped start
        # would shift the window)
        score_pad = jnp.concatenate(
            [score, jnp.zeros(self.max_bucket, score.dtype)])
        lam, hes = [], []
        for Q, tb in tables["buckets"].items():
            with jax.named_scope("lgbm.rank.gather"):
                s = _windows(score_pad, tb["start"], Q)       # [nq, Q]
            g, h = self._query_gradients(Q, s, tb)
            lam.append(g.reshape(-1))
            hes.append(h.reshape(-1))
        with jax.named_scope("lgbm.rank.gather"):
            # one gather of [2, slots] columns: a fifth of the time of two
            # gathers, or of the two scatter-adds it replaces, on a v5e
            both = jnp.take(
                jnp.stack([jnp.concatenate(lam), jnp.concatenate(hes)]),
                tables["slot_of_row"], axis=1)                # [2, n]
            grad, hess = both[0], both[1]
        if self.weight is not None:
            grad = grad * self.weight
            hess = hess * self.weight
        return grad, hess

    def _query_gradients(self, Q, s, tb):
        """(lambda, hessian) [queries, Q] of a bucket's slots from their
        scores ``s``; slots from ``tb["cnt"]`` on are padding."""
        raise NotImplementedError


def _windows(x, starts, Q):
    """``x[starts[i] : starts[i] + Q]`` for every i: [len(starts), Q]."""
    dn = lax.GatherDimensionNumbers(offset_dims=(1,), collapsed_slice_dims=(),
                                    start_index_map=(0,))
    return lax.gather(x, starts[:, None], dn, slice_sizes=(Q,),
                      indices_are_sorted=True,
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


class LambdarankNDCG(RankingObjective):
    """reference: LambdarankNDCG (rank_objective.hpp:98)."""

    name = "lambdarank"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        self.norm = config.lambdarank_norm
        self.truncation_level = config.lambdarank_truncation_level
        lg = list(config.label_gain)
        if not lg:
            lg = [float((1 << i) - 1) for i in range(31)]
        self.label_gain_np = np.asarray(lg, np.float64)

    def _pair_rows(self, Q):
        return max(1, min(int(self.truncation_level), Q))

    def _chunks(self, Q, queries):
        # the widest intermediate is the rank count's [queries, Q, Q]
        return max(1, -(-queries * Q * Q // _PAIR_BUDGET))

    def init(self, metadata, num_data):
        lbl = np.asarray(metadata.label, np.int64)
        if lbl.min() < 0 or lbl.max() >= len(self.label_gain_np):
            raise ValueError("ranking label out of range of label_gain")
        super().init(metadata, num_data)

    def _bucket_extras(self, buckets):
        # gain a slot and inverse max DCG at the truncation level a query
        # (reference: rank_objective.hpp:124-132)
        T = int(self.truncation_level)
        for tb in buckets.values():
            gain = np.where(tb["label"] >= 0, self.label_gain_np[
                np.maximum(tb["label"], 0).astype(np.int64)], 0.0)
            top = -np.sort(-gain, axis=1)[:, :T]
            dcg = top @ (1.0 / np.log2(np.arange(top.shape[1]) + 2.0))
            inv = np.where(dcg > 0, 1.0 / np.maximum(dcg, K_EPSILON), 0.0)
            tb["gain"] = gain.astype(np.float32)
            tb["inv_max_dcg"] = inv.astype(np.float32)

    def _label_pairs(self, sizes, lbl):
        """Pairs of unequal labels a query, at most what the truncation
        level admits (ranks i < j, i below the level): the most pairs a
        round can find, whatever the scores."""
        qid = np.repeat(np.arange(len(sizes)), sizes)
        same = np.zeros(len(sizes), np.int64)
        for v in np.unique(lbl):
            c = np.bincount(qid[lbl == v], minlength=len(sizes))
            same += c * c
        unequal = (sizes * sizes - same) // 2
        t = np.minimum(int(self.truncation_level), np.maximum(sizes - 1, 0))
        admitted = t * (sizes - 1) - t * (t - 1) // 2
        return int(np.minimum(unequal, admitted).sum())

    def _query_gradients(self, Q, s, tb):
        sig = self.sigmoid
        norm = self.norm
        T = self._pair_rows(Q)
        slot = jnp.arange(Q, dtype=jnp.int32)
        top = jnp.arange(T, dtype=jnp.int32)[None, :, None]
        disc_top = (1.0 / jnp.log2(top.astype(jnp.float32) + 2.0))

        def one_chunk(args):
            s_c, lbl_c, gain_c, cnt_c, inv_c = args          # [c, Q], [c]
            valid_c = slot < cnt_c[:, None]
            with jax.named_scope("lgbm.rank.sort"):
                # rank of a slot = slots that come before it: best score
                # first, ties in slot (= row) order, padding last — the
                # reference's stable descending sort, by counting (a sort
                # a bucket costs the compiler a minute at these widths)
                key = jnp.where(valid_c, -s_c, jnp.inf)
                k_j, k_k = key[:, :, None], key[:, None, :]
                before = (k_k < k_j) | ((k_k == k_j)
                                        & (slot[None, :] < slot[:, None]))
                rank = before.sum(axis=2, dtype=jnp.int32)   # [c, Q]
                # the documents of ranks 0..T-1, picked by their rank
                at = rank[:, None, :] == top                 # [c, T, Q]

                def pick(x):
                    return jnp.where(at, x[:, None, :], 0.0).sum(axis=2)
                s_i, lbl_i, gain_i = pick(s_c), pick(lbl_c), pick(gain_c)
                last = (cnt_c - 1)[:, None]
                worst = jnp.where(rank == last, s_c, 0.0).sum(axis=1)
            with jax.named_scope("lgbm.rank.pairs"):
                s_i, lbl_i, gain_i = (x[:, :, None]
                                      for x in (s_i, lbl_i, gain_i))
                s_j, lbl_j = s_c[:, None, :], lbl_c[:, None, :]
                rank_j = rank[:, None, :]
                # ranks i < j, j a document of the query, labels unequal
                pair = (rank_j > top) & valid_c[:, None, :] & \
                    (lbl_i != lbl_j)
                # +1 where rank i holds the larger label (it is ``high``)
                side = jnp.where(lbl_i > lbl_j, 1.0, -1.0)
                ds = (s_i - s_j) * side                      # high - low
                disc_j = 1.0 / jnp.log2(rank_j.astype(jnp.float32) + 2.0)
                delta_ndcg = ((gain_i - gain_c[:, None, :]) * side
                              * jnp.abs(disc_top - disc_j)
                              * inv_c[:, None, None])
                if norm:
                    has_range = (s_i[:, 0, 0] != worst)[:, None, None]
                    delta_ndcg = jnp.where(
                        has_range, delta_ndcg / (0.01 + jnp.abs(ds)),
                        delta_ndcg)
                p = 1.0 / (1.0 + jnp.exp(sig * ds))
                p_lambda = jnp.where(pair, sig * delta_ndcg * p, 0.0)
                p_hess = jnp.where(
                    pair, sig * sig * delta_ndcg * p * (1.0 - p), 0.0)
                toward = p_lambda * side    # rank j gains it, rank i loses
                lam_i = -toward.sum(axis=2)                  # [c, T]
                hes_i = p_hess.sum(axis=2)
                # slot order: what a slot gets as rank j, and as rank i
                lam = toward.sum(axis=1) + jnp.where(
                    at, lam_i[:, :, None], 0.0).sum(axis=1)
                hes = p_hess.sum(axis=1) + jnp.where(
                    at, hes_i[:, :, None], 0.0).sum(axis=1)
                if norm:
                    sum_lambdas = 2.0 * p_lambda.sum(axis=(1, 2))
                    factor = jnp.where(
                        sum_lambdas > 0,
                        jnp.log2(1.0 + sum_lambdas)
                        / jnp.maximum(sum_lambdas, K_EPSILON), 1.0)
                    lam = lam * factor[:, None]
                    hes = hes * factor[:, None]
            return lam, hes

        nq = s.shape[0]                     # a whole number of chunks
        chunks = self.bucket_chunks[Q]
        args = jax.tree_util.tree_map(
            lambda x: x.reshape(chunks, nq // chunks, *x.shape[1:]),
            (s, tb["label"], tb["gain"], tb["cnt"], tb["inv_max_dcg"]))
        lam, hes = jax.lax.map(one_chunk, args)
        return lam.reshape(nq, Q), hes.reshape(nq, Q)


class RankXENDCG(RankingObjective):
    """reference: RankXENDCG (rank_objective.hpp:288, arxiv 1911.09798)."""

    name = "rank_xendcg"

    def __init__(self, config: Config):
        super().__init__(config)
        self._key = jax.random.PRNGKey(config.objective_seed)

    def get_gradients(self, score, tables):
        # fresh per-call randomness (reference: rands_[query].NextFloat())
        self._key, sub = jax.random.split(self._key)
        self._cur_key = sub
        return super().get_gradients(score, tables)

    def _query_gradients(self, Q, s, tb):
        labels = tb["label"]
        valid = jnp.arange(Q, dtype=jnp.int32) < tb["cnt"][:, None]
        key = jax.random.fold_in(self._cur_key, Q)
        gammas = jax.random.uniform(key, s.shape)
        rho = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=1)
        rho = jnp.where(valid, rho, 0.0)
        phi = jnp.exp2(jnp.maximum(labels, 0.0)) - gammas
        phi = jnp.where(valid, phi, 0.0)
        sum_labels = jnp.maximum(phi.sum(axis=1, keepdims=True), K_EPSILON)
        l1 = jnp.where(valid, -phi / sum_labels + rho, 0.0)
        sum_l1 = l1.sum(axis=1, keepdims=True)
        denom = jnp.maximum(1.0 - rho, K_EPSILON)
        l2 = jnp.where(valid, (sum_l1 - l1) / denom, 0.0)
        sum_l2 = l2.sum(axis=1, keepdims=True)
        l3 = jnp.where(valid, (sum_l2 - l2) / denom, 0.0)
        cnt = valid.sum(axis=1, keepdims=True)
        lam_many = l1 + rho * l2 + rho * rho * l3
        lam = jnp.where(cnt <= 1, l1, lam_many)
        hes = rho * (1.0 - rho)
        return jnp.where(valid, lam, 0.0), jnp.where(valid, hes, 0.0)
