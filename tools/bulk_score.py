#!/usr/bin/env python
"""Bulk offline scoring smoke: blockstore -> scores, with a
crash-resume drill.

Builds a synthetic float32 feature BlockStore (streamed to disk in
chunks — the matrix never lives in RAM whole), trains a small booster,
and drives ``data/score.BulkScorer`` through it twice:

1. **full run** into sink A — the throughput number
   (``bulk_rows_per_sec_per_device``) plus predicted-vs-measured peaks
   on both memories and the AOT program source ("aot" on the second
   ever run of a digest, the compile-free resume story);
2. **crash drill** into sink B — score only the first third of the
   blocks (``max_blocks``, the clean stand-in for a SIGKILL between
   manifest commits), then resume with a FRESH scorer; the resumed run
   must skip exactly the banked blocks, and every block file in sink B
   must be byte-identical to sink A's (``cmp``-level equality of the
   score bytes — the resume acceptance bar).

Off-accelerator the row count is capped (interpret-mode fused kernels
and a single host core make 10M rows pointless); on an accelerator
``--rows`` runs the real >= 10M-row shape.

The LAST stdout line is a single JSON object (``stage: bulk_score``).

Usage:
    JAX_PLATFORMS=cpu python tools/bulk_score.py \
        [--rows 10000000] [--features 12] [--block-rows 65536] \
        [--leaves 31] [--rounds 12] [--keep DIR]
"""

import argparse
import filecmp
import json
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CPU_ROWS_CAP = 200_000


def _build_feature_store(path, rows, features, block_rows, seed=0):
    """Stream a synthetic [rows, F] float32 matrix into a BlockStore in
    block-sized chunks — bounded RSS regardless of ``rows``."""
    from lightgbm_tpu.data.blockstore import BlockStore

    rng = np.random.RandomState(seed)
    st = BlockStore.create(path, rows, features, np.float32, block_rows)
    done = 0
    while done < rows:
        r = min(block_rows, rows - done)
        chunk = rng.randn(r, features).astype(np.float32)
        chunk[:, 0] = rng.randint(0, 8, size=r)        # categorical
        chunk[rng.rand(r) < 0.1, 2] = np.nan           # missing routing
        st.append_rows(chunk)
        done += r
    return st.finalize()


def _train_booster(features, leaves, rounds, seed=0, train_rows=4000):
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(seed)
    X = rng.randn(train_rows, features).astype(np.float32).astype(np.float64)
    X[:, 0] = rng.randint(0, 8, size=train_rows)
    y = (X[:, 1] + X[:, 3] * X[:, 4] > 0).astype(float)
    bst = lgb.train(
        {"objective": "binary", "verbosity": -1, "num_leaves": leaves},
        lgb.Dataset(X, label=y, categorical_feature=[0]),
        num_boost_round=rounds, verbose_eval=False)
    return bst._forest(0, len(bst.models) // bst.num_tree_per_iteration)


def _sink_files(path):
    return sorted(n for n in os.listdir(path) if n.endswith(".bin"))


def run_bulk(rows=10_000_000, features=12, block_rows=65_536, leaves=31,
             rounds=12, workdir=None) -> dict:
    from lightgbm_tpu.data.score import BulkScorer, ScoreSink
    from lightgbm_tpu.fleet.aot import AOTStore, aot_dir_from_env
    from lightgbm_tpu.ops.histogram import on_accelerator
    from lightgbm_tpu.predict import DeviceForest
    from lightgbm_tpu.serving.registry import forest_digest

    accel = on_accelerator()
    if not accel:
        rows = min(int(rows), CPU_ROWS_CAP)
    rows = max(int(rows), 1)
    block_rows = max(min(int(block_rows), rows), 1)

    own_tmp = workdir is None
    root = workdir or tempfile.mkdtemp(prefix="lgbm_tpu_bulk_")
    os.makedirs(root, exist_ok=True)
    try:
        store = _build_feature_store(
            os.path.join(root, "features"), rows, features, block_rows)
        forest = _train_booster(features, leaves, rounds)
        dev = DeviceForest(forest)
        digest = forest_digest(forest)
        aot_dir = aot_dir_from_env()
        aot_store = AOTStore(aot_dir) if aot_dir else None

        def scorer(sink):
            return BulkScorer(dev, store, os.path.join(root, sink),
                              aot_store=aot_store, digest=digest)

        # ---- full run: the throughput number --------------------------
        stats = scorer("sink_a").run()
        nb = int(store.num_blocks)

        # ---- crash drill: partial run, then resume with a new scorer --
        cut = max(nb // 3, 1)
        partial = scorer("sink_b").run(max_blocks=cut)
        resumed = scorer("sink_b").run()
        sink_b = ScoreSink.open_or_create(
            os.path.join(root, "sink_b"), rows, 1, block_rows, nb, digest)

        files_a = _sink_files(os.path.join(root, "sink_a"))
        files_b = _sink_files(os.path.join(root, "sink_b"))
        byte_identical = files_a == files_b and all(
            filecmp.cmp(os.path.join(root, "sink_a", n),
                        os.path.join(root, "sink_b", n), shallow=False)
            for n in files_a)
        resume_ok = (byte_identical and sink_b.complete
                     and partial["blocks_scored"] == cut
                     and resumed["skipped_blocks"] == cut
                     and resumed["blocks_scored"] == nb - cut)
        if not resume_ok:
            raise RuntimeError(
                "bulk-score crash-resume FAILED: "
                f"byte_identical={byte_identical} "
                f"complete={sink_b.complete} partial={partial} "
                f"resumed={{'skipped': {resumed['skipped_blocks']}, "
                f"'scored': {resumed['blocks_scored']}}}")

        stats.update({
            "accelerator": accel,
            "features": int(features),
            "block_rows": int(block_rows),
            "resume_ok": True,
            "resume_cut_blocks": cut,
            "resume_skipped_blocks": int(resumed["skipped_blocks"]),
            "resume_byte_identical": byte_identical,
            "aot_store": bool(aot_store),
        })
        return stats
    finally:
        if own_tmp:
            shutil.rmtree(root, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--features", type=int, default=12)
    ap.add_argument("--block-rows", type=int, default=65_536)
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--keep", default=None, metavar="DIR",
                    help="work under DIR and keep it (default: temp dir, "
                         "removed)")
    args = ap.parse_args()
    out = run_bulk(args.rows, args.features, args.block_rows, args.leaves,
                   args.rounds, workdir=args.keep)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
