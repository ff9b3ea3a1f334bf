"""Checksummed atomic checkpoint bundles with bit-identical resume.

A checkpoint is ONE file (a zip container) holding three members:

- ``manifest.json`` — format tag, iteration, and a sha256 + size per
  member; verified on every load, so a truncated or bit-flipped bundle is
  detected before any state is trusted;
- ``model.txt``   — the reference-format model text at the checkpoint
  iteration (human-readable, loadable by stock LightGBM on its own);
- ``state.pkl``   — the exact mutable training state captured by
  ``GBDT.capture_state`` (host trees, device score arrays, every RNG
  stream, DART drop/weight state, engine-level eval history and
  early-stopping state), so a resumed run replays the SAME random
  decisions and produces a bit-identical model (boosting/gbdt.py).

The reference has no training checkpoint at all — its ``snapshot_freq``
writes a bare model file in place (gbdt.cpp:259-263), which a crash
mid-write truncates and which cannot restore bagging/DART RNG state.
Bundles are written via ``utils.file_io.write_atomic`` (temp sibling +
``os.replace`` locally; the ``open_file``/``register_file_system`` seam
for remote schemes), so ``snapshot_out`` pointing at gs://... works the
moment a file system is registered for it.

``CheckpointManager`` adds a keep-last-K retention policy driven by an
``index.json`` (also written atomically, so bundle discovery never needs
a directory listing — remote schemes stay listable-free) and
``latest_verified()``, which walks newest-to-oldest skipping corrupt
bundles with a loud warning.

In the container ``manifest.json`` and ``model.txt`` are deflated (text:
it shrinks several-fold) and ``state.pkl`` is STORED as it is: nearly all
of it is the float32 train score, one value a row, which deflate shrinks by
13-39% for seconds of one core (5.0 s of a 5.7 s save at 25M rows, against
rounds of 2.2 s: docs/RESILIENCE.md), with the trainer and the device
standing still behind it.  No key chooses: incompressible binary state is
never worth deflating.  The format tag stays ``lgbt-ckpt/1``: a zip reader
takes either method member by member, so bundles written with a deflated
``state.pkl`` (before this note) load and verify as they did.

A save and a load are seams on the flight ring (docs/OBSERVABILITY.md):
``checkpoint.save`` (``it``, ``bytes``) with its parts ``checkpoint.capture``
/ ``.encode`` / ``.write`` under it, and ``checkpoint.load`` (``bytes``).

``state.pkl`` is a pickle: only resume from checkpoint directories you
trust, exactly like any other pickle-bearing format.
"""

from __future__ import annotations

import hashlib
import io
import json
import pickle
import time
import zipfile
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from ..obs.metrics import global_registry as _obs_registry
from ..obs.trace import span as _span
from ..utils.file_io import exists, open_file, remove, write_atomic
from ..utils.log import log_info, log_warning

FORMAT = "lgbt-ckpt/1"
BUNDLE_SUFFIX = ".lgbckpt"
INDEX_NAME = "index.json"


class CheckpointError(RuntimeError):
    """Base class for checkpoint load failures."""


class CheckpointCorruptError(CheckpointError):
    """The bundle exists but fails structural or checksum verification."""


class CheckpointNotFoundError(CheckpointError):
    """No (verifiable) bundle at the requested location."""


@dataclass
class Checkpoint:
    """A verified, decoded bundle."""

    iteration: int
    model_str: str
    boosting_state: dict
    booster_state: dict = field(default_factory=dict)
    engine_state: dict = field(default_factory=dict)
    manifest: dict = field(default_factory=dict)
    path: Optional[str] = None
    nbytes: int = 0          # the bundle's size as read


def _sha256(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def build_bundle_bytes(booster, iteration: int,
                       engine_state: Optional[dict] = None) -> bytes:
    """Serialize ``booster``'s full training state into bundle bytes."""
    with _span("checkpoint.capture", ring=True, it=int(iteration)):
        state = {
            "boosting": booster.boosting.capture_state(),
            "booster": {
                "best_iteration": booster.best_iteration,
                "best_score": booster.best_score,
                "attr": dict(booster._attr),
            },
            "engine": dict(engine_state or {}),
        }
    with _span("checkpoint.encode", ring=True, it=int(iteration)):
        return _encode_bundle(booster, iteration, state)


def _encode_bundle(booster, iteration: int, state: dict) -> bytes:
    """Model text, pickle, a sha256 a member, the zip container."""
    model_txt = booster.model_to_string(num_iteration=-1).encode()
    state_pkl = pickle.dumps(state, protocol=4)
    # provenance only, never validated on restore: resumed runs replay
    # bit-identically under ANY chunk decomposition (the macro-step loop
    # body is chunk-size-invariant, boosting/macro.py), so a bundle from
    # a chunked run restores into a per-iteration run and vice versa
    from ..boosting.macro import chunk_cap
    # hist_plan likewise: row tiling is bit-invariant (pinned tile-major
    # accumulation, ops/planner.py), so a bundle from a tiled run
    # restores into an untiled one and vice versa — recorded so an OOM
    # post-mortem can see what the planner chose
    plan = getattr(booster.boosting, "hist_plan", None)
    # out-of-core provenance (lightgbm_tpu/data/): streamed == resident
    # is bit-invariant (pinned block order), so a bundle from a streamed
    # run restores into a resident one and vice versa; the plan + the
    # spill store's block geometry are recorded so a mid-stream resume's
    # post-mortem can see what the pump was doing
    splan = getattr(booster.boosting, "stream_plan", None)
    sctx = getattr(booster.boosting, "_stream", None)
    stream_prov = None
    if splan is not None:
        stream_prov = dict(splan.summary())
        if sctx is not None:
            stream_prov["store_path"] = sctx.store.path
            stream_prov["store_block_rows"] = int(sctx.store.block_rows)
            stream_prov["store_num_blocks"] = int(sctx.store.num_blocks)
    # pod-scale provenance (parallel/collectives.py): the mesh shape and
    # the elected reduction schedule this bundle trained under.  Never
    # validated on restore — hierarchical == flat is bit-invariant for
    # quantized payloads and pinned f32, and an ELASTIC resume (slice
    # loss, docs/RESILIENCE.md) restores into a re-planned SMALLER mesh
    # on purpose; recorded so a shrink post-mortem can see both worlds
    cplan = getattr(booster.boosting, "collective_plan", None)
    manifest = {
        "format": FORMAT,
        "iteration": int(iteration),
        "chunk_cap": chunk_cap(),
        "hist_plan": plan.summary() if plan is not None else None,
        "stream_plan": stream_prov,
        "collective_plan": cplan.summary() if cplan is not None else None,
        "members": {
            "model.txt": {"sha256": _sha256(model_txt),
                          "size": len(model_txt)},
            "state.pkl": {"sha256": _sha256(state_pkl),
                          "size": len(state_pkl)},
        },
    }
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("manifest.json", json.dumps(manifest, indent=1))
        zf.writestr("model.txt", model_txt)
        # incompressible floats: stored, not deflated (module docstring)
        zf.writestr("state.pkl", state_pkl, compress_type=zipfile.ZIP_STORED)
    return buf.getvalue()


def decode_bundle_bytes(blob: bytes, path: Optional[str] = None) -> Checkpoint:
    """Verify manifest checksums and decode; raises CheckpointCorruptError
    on ANY structural or checksum mismatch."""
    where = path or "<bytes>"
    try:
        zf = zipfile.ZipFile(io.BytesIO(blob))
        manifest = json.loads(zf.read("manifest.json").decode())
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint {where}: unreadable container ({e})") from e
    if manifest.get("format") != FORMAT:
        raise CheckpointCorruptError(
            f"checkpoint {where}: format {manifest.get('format')!r} != "
            f"{FORMAT!r}")
    members = {}
    for name, meta in manifest.get("members", {}).items():
        try:
            data = zf.read(name)
        except Exception as e:
            raise CheckpointCorruptError(
                f"checkpoint {where}: missing member {name!r} ({e})") from e
        if len(data) != meta.get("size") or _sha256(data) != meta.get("sha256"):
            raise CheckpointCorruptError(
                f"checkpoint {where}: member {name!r} fails its manifest "
                "checksum (truncated or bit-flipped)")
        members[name] = data
    if "model.txt" not in members or "state.pkl" not in members:
        raise CheckpointCorruptError(
            f"checkpoint {where}: manifest lists no model/state members")
    try:
        state = pickle.loads(members["state.pkl"])
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint {where}: state.pkl checksummed OK but failed to "
            f"unpickle ({e})") from e
    return Checkpoint(
        iteration=int(manifest["iteration"]),
        model_str=members["model.txt"].decode(),
        boosting_state=state["boosting"],
        booster_state=state.get("booster", {}),
        engine_state=state.get("engine", {}),
        manifest=manifest,
        path=path,
        nbytes=len(blob),
    )


def save_checkpoint(booster, path: str, iteration: Optional[int] = None,
                    engine_state: Optional[dict] = None,
                    publish=None) -> str:
    """Write one atomic bundle to ``path``; returns the path.  ``publish``
    (the manager's index and retention) runs once the bundle is on disk,
    inside the save's ``checkpoint.write`` part."""
    if iteration is None:
        iteration = booster.current_iteration()
    t0 = time.perf_counter()
    with _span("checkpoint.save", ring=True, it=int(iteration)) as save:
        blob = build_bundle_bytes(booster, iteration, engine_state)
        save.set(bytes=len(blob))
        with _span("checkpoint.write", ring=True):
            write_atomic(path, blob)
            if publish is not None:
                publish()
    _obs_registry.histogram("checkpoint_save_ms").observe(
        (time.perf_counter() - t0) * 1e3)
    _obs_registry.counter("checkpoint_saves_total").inc()
    _obs_registry.counter("checkpoint_bytes_total").inc(len(blob))
    return str(path)


def load_checkpoint(path: str) -> Checkpoint:
    """Read + verify one bundle."""
    if not exists(path):
        raise CheckpointNotFoundError(f"no checkpoint at {path!r}")
    t0 = time.perf_counter()
    with _span("checkpoint.load", ring=True, path=str(path)) as load:
        try:
            with open_file(path, "rb") as fh:
                blob = fh.read()
        except CheckpointError:
            raise
        except Exception as e:
            raise CheckpointCorruptError(
                f"checkpoint {path}: unreadable ({e})") from e
        load.set(bytes=len(blob))
        ck = decode_bundle_bytes(blob, path=str(path))
    _obs_registry.histogram("checkpoint_load_ms").observe(
        (time.perf_counter() - t0) * 1e3)
    return ck


def restore_booster(booster, ckpt: Checkpoint) -> None:
    """Push a verified checkpoint's state back into a freshly-built
    Booster (same params / train_set / valid sets as the original run)."""
    booster.boosting.restore_state(ckpt.boosting_state)
    bs = ckpt.booster_state
    booster.best_iteration = bs.get("best_iteration", -1)
    booster.best_score = bs.get("best_score", {})
    booster._attr = dict(bs.get("attr", {}))


class CheckpointManager:
    """Keep-last-K bundle directory with an atomically-updated index.

    Layout::

        <directory>/ckpt_iter_00000010.lgbckpt
        <directory>/index.json      {"format": ..., "bundles": [oldest..newest]}
    """

    def __init__(self, directory: str, prefix: str = "ckpt",
                 keep_last: int = 3):
        self.directory = str(directory).rstrip("/")
        self.prefix = prefix
        self.keep_last = max(1, int(keep_last))

    # ----------------------------------------------------------- paths/index

    def path_for(self, iteration: int) -> str:
        return (f"{self.directory}/{self.prefix}_iter_"
                f"{int(iteration):08d}{BUNDLE_SUFFIX}")

    @property
    def index_path(self) -> str:
        return f"{self.directory}/{INDEX_NAME}"

    def _read_index(self) -> List[str]:
        try:
            with open_file(self.index_path, "r") as fh:
                idx = json.loads(fh.read())
            return [str(b) for b in idx.get("bundles", [])]
        except Exception:
            return []

    def _write_index(self, bundles: List[str]) -> None:
        write_atomic(self.index_path,
                     json.dumps({"format": FORMAT, "bundles": bundles},
                                indent=1))

    def bundles(self) -> List[str]:
        """Bundle FILENAMES oldest-to-newest: the index when readable,
        plus (local paths only) anything on disk the index missed — a
        crash between bundle write and index write must not orphan the
        newest checkpoint."""
        names = self._read_index()
        if "://" not in self.directory:
            import os
            try:
                on_disk = sorted(
                    f for f in os.listdir(self.directory)
                    if f.startswith(self.prefix) and f.endswith(BUNDLE_SUFFIX))
            except OSError:
                on_disk = []
            known = set(names)
            for f in on_disk:
                if f not in known:
                    names.append(f)
            names.sort()
        return names

    # ----------------------------------------------------------- save / load

    def save(self, booster, iteration: int,
             engine_state: Optional[dict] = None) -> str:
        path = self.path_for(iteration)
        save_checkpoint(booster, path, iteration, engine_state,
                        publish=lambda: self._publish(path))
        log_info(f"checkpoint: wrote {path} (keep_last={self.keep_last})")
        return path

    def _publish(self, path: str) -> None:
        """The bundle at ``path`` is on disk: index it, drop what
        retention no longer keeps."""
        names = [n for n in self.bundles()
                 if n != path.rsplit("/", 1)[-1]]
        names.append(path.rsplit("/", 1)[-1])
        # retention: drop oldest beyond keep_last (index first, so a
        # reader never sees an indexed-but-deleted bundle)
        drop, keep = names[:-self.keep_last], names[-self.keep_last:]
        self._write_index(keep)
        for name in drop:
            if not remove(f"{self.directory}/{name}"):
                log_warning(f"checkpoint retention: could not delete "
                            f"{self.directory}/{name} (no remover for the "
                            "backend, or delete refused); leaving it")

    def latest_verified(self, before: Optional[str] = None) -> Checkpoint:
        """Newest bundle that passes verification; corrupt ones are
        skipped with a loud warning.  Raises CheckpointNotFoundError when
        nothing survives.

        ``before`` (a bundle path/filename, or an iteration number)
        restricts the walk to bundles strictly OLDER than it — the
        lifecycle rollback pin: "the newest verified bundle older than
        the failed candidate", so a rollback can never race a
        concurrent save into re-promoting the model it is rolling
        back (docs/LIFECYCLE.md)."""
        names = self.bundles()
        if before is not None:
            cutoff = (self.path_for(before) if isinstance(before, int)
                      else str(before)).rsplit("/", 1)[-1]
            names = [n for n in names if n < cutoff]
        errors: List[Tuple[str, str]] = []
        for name in reversed(names):
            path = f"{self.directory}/{name}"
            try:
                ck = load_checkpoint(path)
                if errors:
                    log_warning(
                        "checkpoint: newest bundle(s) CORRUPT, falling back "
                        f"to {path}: "
                        + "; ".join(f"{n}: {e}" for n, e in errors))
                return ck
            except CheckpointError as e:
                log_warning(f"checkpoint: skipping corrupt bundle {path}: {e}")
                errors.append((name, str(e)))
        raise CheckpointNotFoundError(
            f"no verifiable checkpoint bundle under {self.directory!r} "
            f"(saw {len(names)}, all corrupt)" if names else
            f"no checkpoint bundles under {self.directory!r}")


def resolve_resume_point(resume_from: str) -> Checkpoint:
    """``resume_from`` may be a bundle FILE or a manager DIRECTORY; a
    directory resolves to its newest verified bundle."""
    p = str(resume_from)
    if p.endswith(BUNDLE_SUFFIX):
        return load_checkpoint(p)
    if "://" not in p:
        import os
        if os.path.isfile(p):
            return load_checkpoint(p)
        if not os.path.isdir(p):
            raise CheckpointNotFoundError(f"resume_from={p!r}: no such "
                                          "bundle file or directory")
    return CheckpointManager(p).latest_verified()
