"""AOT-serialized serving programs: compile-free cold start.

Training persists its compiled programs in the compile-cache directory
(``utils.platform.compile_cache_dir``); this module extends the same
directory to SERVING buckets.  ``AOTStore.export_device_forest`` serializes each
(model digest, bucket) routing program with ``jax.export`` — the traced,
lowered StableHLO with the forest arrays baked in as constants — into
``<cache>/serving/``; a fresh replica then builds its bucket programs by
DESERIALIZING instead of re-tracing, and the backend compile of the
restored module rides the persistent compile cache, so the replica's
first request pays neither a trace nor a fresh XLA compile.  The program
registry counts restored programs as ``aot_program_loads`` instead of
``compile_events`` — "first request with zero compile events" is the
cold-start acceptance bar (tools/fleet_smoke.py, tests/test_fleet.py).

Only the LEAF-ROUTING half of a serving program is exported (the
device-side ``DeviceForest._leaves``): the float64 leaf gather stays on
the host via the shared ``predict.gather_leaf_sum`` epilogue, which is
what keeps an AOT-restored replica bit-identical to the live-compiled
one.  Everything here fails SOFT: a corrupt, foreign-platform, or
version-skewed entry is a cache MISS (the program compiles normally),
never a serving failure.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import numpy as np

from ..utils.log import log_warning

AOT_VERSION = 1
_SUBDIR = "serving"


def aot_dir_from_env() -> str:
    """The default AOT store: ``serving/`` inside the compile-cache
    directory (``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache`` — ``utils.platform.compile_cache_dir``)."""
    from ..utils.platform import compile_cache_dir
    return os.path.join(compile_cache_dir(), _SUBDIR)


class AOTStore:
    """Directory of serialized serving programs, keyed
    ``(model digest, bucket_rows)``.

    One entry is two atomic sibling files (utils.file_io.write_atomic):
    ``<digest>-b<bucket>.bin`` — the ``jax.export`` blob — and
    ``<digest>-b<bucket>.json`` — {version, platforms, jax} metadata
    checked BEFORE the expensive deserialize so a foreign-platform or
    version-skewed blob is rejected cheaply.
    """

    def __init__(self, root: str):
        self.root = str(root)

    # ------------------------------------------------------------- layout

    def _base(self, digest: str, bucket_rows: int) -> str:
        return os.path.join(self.root, f"{digest}-b{int(bucket_rows)}")

    def entries(self) -> list:
        """Sorted [(digest, bucket_rows)] of complete entries on disk."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        out = []
        for n in names:
            if not n.endswith(".json"):
                continue
            stem = n[:-len(".json")]
            digest, sep, b = stem.rpartition("-b")
            if not sep or not b.isdigit():
                continue
            if os.path.exists(os.path.join(self.root, stem + ".bin")):
                out.append((digest, int(b)))
        return sorted(out)

    def buckets_for(self, digest: str) -> list:
        return sorted(b for d, b in self.entries() if d == digest)

    # -------------------------------------------------------------- export

    def save_leaves(self, digest: str, bucket_rows: int, exported) -> str:
        """Serialize one exported routing program; returns the blob path."""
        import jax

        from ..utils.file_io import write_atomic
        base = self._base(digest, bucket_rows)
        write_atomic(base + ".bin", exported.serialize())
        write_atomic(base + ".json", json.dumps({
            "version": AOT_VERSION,
            "digest": digest,
            "bucket_rows": int(bucket_rows),
            "platforms": [p.lower() for p in exported.platforms],
            "jax": jax.__version__,
        }, indent=1, sort_keys=True))
        return base + ".bin"

    def export_device_forest(self, device_forest, features: int,
                             buckets, digest: str) -> int:
        """Export ``device_forest``'s routing program for every bucket in
        ``buckets``; returns the number of entries written."""
        import jax
        import jax.numpy as jnp
        from jax import export as jax_export
        # a fused-variant forest keeps a fixed-trip fori twin as its
        # export arm (bit-identical leaves, serializes cleanly — Pallas
        # kernels do not); plain variants export their own jit
        fn = getattr(device_forest, "_leaves_export",
                     device_forest._leaves_jit)
        n = 0
        for b in sorted({int(b) for b in buckets}):
            exp = jax_export.export(fn)(
                jax.ShapeDtypeStruct((b, int(features)), jnp.float32))
            self.save_leaves(digest, b, exp)
            n += 1
        return n

    # ------------------------------------------------------------- restore

    def load_leaves(self, digest: str, bucket_rows: int):
        """Deserialize the (digest, bucket) routing program into a
        jit-wrapped callable ``[bucket, F] f32 -> [T, bucket] i32``, or
        None on ANY miss/mismatch/corruption — the caller compiles
        normally, serving never fails on a bad cache entry."""
        base = self._base(digest, bucket_rows)
        try:
            with open(base + ".json") as fh:
                meta = json.load(fh)
            if meta.get("version") != AOT_VERSION:
                return None
            import jax
            if jax.default_backend().lower() not in meta.get("platforms", []):
                return None
            with open(base + ".bin", "rb") as fh:
                blob = fh.read()
            from jax import export as jax_export
            exported = jax_export.deserialize(bytearray(blob))
            # one jit wrapper per restored program: the executable is
            # cached across calls exactly like a live-compiled bucket
            return jax.jit(exported.call)
        except FileNotFoundError:
            return None
        except Exception as e:  # noqa: BLE001 — any corruption is a miss
            log_warning(
                f"AOT serving cache entry {os.path.basename(base)} "
                f"unusable ({type(e).__name__}: {str(e)[:120]}); "
                "recompiling this bucket")
            return None


def make_aot_program(store: "AOTStore", model, bucket_rows: int):
    """Build a serving program for ``(model, bucket)`` from the AOT
    store, or None on miss.  The returned callable matches
    ``CompiledModel.make_program``'s contract ([bucket, F] f64 padded
    batch -> [K, bucket] f64 raw scores) and is tagged ``aot=True`` so
    the program registry counts it as a restore, not a compile."""
    fn = store.load_leaves(model.digest, bucket_rows)
    if fn is None:
        return None
    from ..predict import gather_leaf_sum
    forest = model.forest
    K = model.num_class

    def run(Xpad: np.ndarray) -> np.ndarray:
        leaves = np.asarray(fn(np.asarray(Xpad, np.float32)))
        return gather_leaf_sum(forest, leaves, K)

    run.aot = True
    return run


def make_bulk_program(device_forest, features: int, block_rows: int,
                      digest: str, store: Optional["AOTStore"] = None):
    """Fixed-shape routing program for the bulk scorer (data/score.py):
    ``[block_rows, F] f32 -> [T, block_rows] i32`` leaves, at the bulk
    pipeline's ONE block-sized bucket.

    Tries the AOT store first (compile-free start, same bit-parity story
    as serving buckets); on a miss it exports the bucket so the NEXT run
    — a resumed crash included — restores instead of re-tracing, and
    serves this run with the freshly restored program.  Export is
    best-effort: any failure falls back to the live jit, never fails the
    scoring run.  Returns ``(callable, source)``, source in
    {"aot", "jit"}.
    """
    if store is not None:
        fn = store.load_leaves(digest, block_rows)
        if fn is not None:
            return fn, "aot"
        try:
            os.makedirs(store.root, exist_ok=True)
            store.export_device_forest(device_forest, features,
                                       [block_rows], digest)
            fn = store.load_leaves(digest, block_rows)
            if fn is not None:
                return fn, "aot"
        except Exception as e:  # noqa: BLE001 — export is best-effort
            log_warning(f"bulk AOT export failed ({type(e).__name__}: "
                        f"{str(e)[:120]}); scoring with the live jit")
    return getattr(device_forest, "_leaves_export",
                   device_forest._leaves_jit), "jit"
