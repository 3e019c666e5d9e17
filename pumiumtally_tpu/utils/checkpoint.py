"""Checkpoint / resume for tally runs.

The reference has no persistence besides the final VTK write (SURVEY.md §5:
"Checkpoint / resume. Absent.") — but its state is additive, so the natural
checkpoint is exactly (flux accumulator, particle state, iteration counter).
This module saves/restores that as a single compressed ``.npz`` with a mesh
fingerprint so a checkpoint can never be resumed against a different mesh.

Durability contract (the resilience subsystem's foundation,
``resilience/``):

  * every write is ATOMIC — serialized to a same-directory temp file,
    fsync'd, then ``os.replace``d over the target, so a crash or ENOSPC
    mid-write can never leave a truncated ``.npz`` under the real name;
  * every array carries a sha256 digest in the meta block, verified on
    load BEFORE any tally state is overwritten (``verify_checkpoint`` /
    ``CheckpointIntegrityError``), so silent bit-rot or a torn copy is
    detected instead of resumed;
  * restore validates format/kind/mesh/dtype/sd_mode/run-shape and
    raises on any mismatch rather than silently resuming (or silently
    CASTING — an f64 checkpoint into an f32 tally would lose the
    precision contract) a different run.

Sharded generations (two-phase commit; the elastic-recovery layer's
foundation, ``resilience/coordinator.py``/``elastic.py``): a generation
named ``<name>.shards`` is a DIRECTORY of per-mesh-part ``shard-*.npz``
payload splits (each an atomic, digest-carrying npz like the single
file, written concurrently) plus a ``MANIFEST.json`` committed LAST.
The manifest names every shard with its whole-file sha256, so the
generation is valid only once the commit record exists and every named
shard hashes clean — a torn multi-shard write (crash before the
manifest, or a shard corrupted after it) can never produce a
Frankenstein restore: the whole generation is rejected atomically and
the resilience layer falls back to an older one. Single-file ``.npz``
generations remain fully supported (backward compatible): every
``save_*``/``restore_*``/``verify_checkpoint`` entry point dispatches
on the ``.shards`` suffix / directory form.

``snapshot_state``/``restore_state`` expose the same payload as
in-memory host copies — the ``ResilientRunner``'s retry anchor, no
serialization.

Used by ``PumiTally.save_checkpoint`` / ``PumiTally.restore_checkpoint``
(and the partitioned equivalents); host-side glue, not a hot path.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile

import numpy as np

FORMAT_VERSION = 1

#: Suffix marking a sharded (directory) generation; everything else is
#: the single-file ``.npz`` layout.
SHARD_SUFFIX = ".shards"

#: The two-phase-commit record of a sharded generation, written LAST.
MANIFEST_NAME = "MANIFEST.json"


class CheckpointIntegrityError(ValueError):
    """A checkpoint file failed its integrity check (truncated container,
    missing array, or per-array sha256 mismatch). Distinct from the
    plain ``ValueError`` of a *mismatched* (wrong mesh/config) but
    intact checkpoint: the resilience layer skips corrupt generations
    and falls back, while a genuine mismatch propagates to the caller."""


def mesh_fingerprint(mesh) -> str:
    """Stable content hash of the mesh the tally ran on (connectivity +
    coordinates + region ids)."""
    h = hashlib.sha256()
    for arr in (mesh.tet2vert, mesh.coords, mesh.class_id):
        a = np.ascontiguousarray(np.asarray(arr))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _array_digest(arr) -> str:
    """sha256 over dtype + shape + raw bytes — the per-array integrity
    unit stored in the meta block and re-checked on load."""
    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()


def _normalize(filename: str) -> str:
    # np.savez_compressed silently appends ".npz"; normalize on both the
    # save and load side so any filename round-trips.
    return filename if filename.endswith(".npz") else filename + ".npz"


def is_sharded(path: str) -> bool:
    """True when ``path`` names a sharded (directory) generation —
    either by the ``.shards`` suffix (save side, may not exist yet) or
    by being a directory on disk (restore side)."""
    return path.endswith(SHARD_SUFFIX) or os.path.isdir(path)


def fsync_dir(directory: str) -> None:
    """Best-effort fsync of a DIRECTORY, making the renames/unlinks
    inside it durable across power loss (a data fsync alone only makes
    the file contents durable — the directory entry pointing at them
    lives in the directory's own metadata block). Shared by
    ``atomic_savez`` (after the rename) and ``CheckpointStore``'s
    keep-N rotation (after the deletions): without the latter, a power
    cut after rotation could resurrect a deleted older generation AND
    lose the rename of the newest, leaving ``find_latest`` a stale
    view. Unsupported filesystems (some network mounts) are tolerated —
    the data fsync + rename already rule out torn files there."""
    try:
        dfd = os.open(directory, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass


def atomic_savez(filename: str, **arrays) -> str:
    """``np.savez_compressed`` with crash-safe semantics: write to a
    same-directory temp file, flush + fsync, then ``os.replace`` over
    the target (and fsync the directory so the rename itself is
    durable). A crash/ENOSPC at any point leaves either the old file or
    nothing — never a truncated ``.npz`` under the real name."""
    filename = _normalize(filename)
    directory = os.path.dirname(os.path.abspath(filename)) or "."
    fd, tmp = tempfile.mkstemp(
        dir=directory, prefix=os.path.basename(filename) + ".tmp-"
    )
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, filename)
        fsync_dir(directory)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return filename


def _write_checkpoint(filename: str, meta: dict, arrays: dict) -> str:
    meta = dict(
        meta,
        array_sha256={k: _array_digest(v) for k, v in arrays.items()},
    )
    return atomic_savez(
        filename,
        meta=np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8),
        **arrays,
    )


def _verify_integrity(arrays: dict, meta: dict, filename: str) -> None:
    """Re-hash every loaded array against the meta block's digests
    (arrays are hashed in memory — each member is decompressed exactly
    once per restore). Pre-digest files (no ``array_sha256`` key) pass
    — their container CRC is the only protection they ever had."""
    digests = meta.get("array_sha256")
    if digests is None:
        return
    for name, want in digests.items():
        if name not in arrays:
            raise CheckpointIntegrityError(
                f"checkpoint {filename}: array {name!r} missing"
            )
        got = _array_digest(arrays[name])
        if got != want:
            raise CheckpointIntegrityError(
                f"checkpoint {filename}: array {name!r} sha256 mismatch "
                f"(stored {want[:12]}…, recomputed {got[:12]}…) — the "
                "file is corrupt; falling back to an older generation "
                "is the resilience layer's job (CheckpointStore)"
            )


def verify_checkpoint(filename: str) -> dict:
    """Standalone integrity check: load the meta block and re-hash every
    array. Returns the meta dict on success; raises
    ``CheckpointIntegrityError`` (or the container's own zip/OS errors)
    on corruption. Does not touch any tally. Sharded generations
    (directories) route through the manifest check."""
    if is_sharded(filename):
        return verify_sharded_checkpoint(filename)
    filename = _normalize(filename)
    with np.load(filename) as z:
        meta = json.loads(bytes(z["meta"].tobytes()).decode())
        if meta.get("format_version") != FORMAT_VERSION:
            # An intact file of another format is a MISMATCH, not
            # corruption — plain ValueError, so CheckpointStore's
            # lookup rules treat it exactly like restore would.
            raise ValueError(
                f"checkpoint {filename}: format "
                f"{meta.get('format_version')} != {FORMAT_VERSION}"
            )
        arrays = {k: z[k] for k in z.files if k != "meta"}
        _verify_integrity(arrays, meta, filename)
    return meta


def load_meta(filename: str) -> dict:
    if is_sharded(filename):
        return _read_manifest(filename)["meta"]
    with np.load(_normalize(filename)) as z:
        return json.loads(bytes(z["meta"].tobytes()).decode())


# --------------------------------------------------------------------- #
# Sharded generations: per-part payload splits + two-phase manifest
# --------------------------------------------------------------------- #
def _file_digest(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def atomic_write_bytes(filename: str, data: bytes) -> None:
    """The ``atomic_savez`` durability contract for a small opaque blob
    (the manifest, the journal document, committed JSON baselines):
    tmp + fsync + rename + directory fsync.  Public alongside
    ``atomic_savez``/``fsync_dir`` — every module that persists durable
    state routes through one of these (graft-check PUMI008)."""
    directory = os.path.dirname(os.path.abspath(filename)) or "."
    fd, tmp = tempfile.mkstemp(
        dir=directory, prefix=os.path.basename(filename) + ".tmp-"
    )
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, filename)
        fsync_dir(directory)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_write_json(filename: str, obj) -> None:
    """Committed-JSON convenience over ``atomic_write_bytes``: the
    repo's canonical serialization for captures/baselines/journals
    (indent=1, sorted keys, trailing newline) in one place, so the six
    writers cannot drift apart."""
    atomic_write_bytes(
        filename,
        (json.dumps(obj, indent=1, sort_keys=True) + "\n").encode(),
    )


def shard_name(index: int) -> str:
    return f"shard-{int(index):03d}.npz"


def save_sharded_checkpoint(
    dirname: str, tally, n_shards: int | None = None
) -> int:
    """Write one SHARDED generation with two-phase commit semantics.

    Phase 1 splits the facade payload into ``n_shards`` leading-axis
    chunks (one per mesh part by default — every payload array is
    per-particle, per-element, or per-slot, so a first-axis split is
    layout-independent and reassembly is a concatenation) and writes
    one digest-carrying npz per shard CONCURRENTLY through the
    existing atomic tmp+fsync+rename path. Phase 2 commits
    ``MANIFEST.json`` — the facade meta plus every shard's whole-file
    sha256 — atomically, LAST. A pre-existing manifest is removed
    BEFORE any shard is touched (un-commit), so a crash mid-rewrite
    leaves an invalid (manifest-less) directory — detected and
    skipped, never a manifest naming half-overwritten shards. NOTE:
    that means rewriting an existing generation IN PLACE sacrifices
    the old copy for the duration of the write; callers that must
    never lose the previous generation write to a fresh path (the
    ``CheckpointStore``'s per-iteration naming, plus the runner's
    skip of re-flushes onto valid generations, guarantee this).
    Returns the shard count written."""
    if hasattr(tally, "flux_slabs"):
        meta, arrays = _partitioned_payload(tally)
    else:
        meta, arrays = _plain_payload(tally)
    if n_shards is None:
        n_shards = int(getattr(tally, "n_parts", 1))
    n_shards = max(1, int(n_shards))
    os.makedirs(dirname, exist_ok=True)
    manifest_path = os.path.join(dirname, MANIFEST_NAME)
    if os.path.exists(manifest_path):
        os.unlink(manifest_path)
        fsync_dir(dirname)
    chunks = {
        name: np.array_split(np.asarray(a), n_shards)
        for name, a in arrays.items()
    }

    def _write(i: int) -> str:
        shard_meta = {
            "format_version": FORMAT_VERSION,
            "shard": int(i),
            "n_shards": int(n_shards),
        }
        shard_arrays = {
            name: np.ascontiguousarray(chunks[name][i]) for name in arrays
        }
        return _write_checkpoint(
            os.path.join(dirname, shard_name(i)), shard_meta, shard_arrays
        )

    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=min(n_shards, 8)) as ex:
        paths = list(ex.map(_write, range(n_shards)))
    manifest = {
        "format_version": FORMAT_VERSION,
        "meta": meta,
        "n_shards": int(n_shards),
        "shards": {os.path.basename(p): _file_digest(p) for p in paths},
    }
    atomic_write_bytes(
        manifest_path, json.dumps(manifest, indent=1).encode()
    )
    return n_shards


def _read_manifest(dirname: str) -> dict:
    """Load the commit record; its ABSENCE (torn multi-shard write:
    the crash came before phase 2) is corruption by definition — the
    resilience layer must skip the whole generation."""
    manifest_path = os.path.join(dirname, MANIFEST_NAME)
    if not os.path.exists(manifest_path):
        raise CheckpointIntegrityError(
            f"sharded checkpoint {dirname}: {MANIFEST_NAME} missing — "
            "the generation was never committed (torn multi-shard "
            "write); falling back to an older generation is the "
            "resilience layer's job (CheckpointStore)"
        )
    try:
        with open(manifest_path, "rb") as f:
            manifest = json.loads(f.read().decode())
    except (OSError, ValueError) as e:
        raise CheckpointIntegrityError(
            f"sharded checkpoint {dirname}: unreadable manifest ({e})"
        ) from e
    if manifest.get("format_version") != FORMAT_VERSION:
        raise ValueError(
            f"sharded checkpoint {dirname}: format "
            f"{manifest.get('format_version')} != {FORMAT_VERSION}"
        )
    return manifest


def _verify_shard_files(dirname: str, manifest: dict) -> list[str]:
    """Every shard the manifest names must exist and hash clean; any
    miss rejects the WHOLE generation (atomic torn-write semantics).
    Returns shard paths in shard order."""
    shards = manifest.get("shards", {})
    if len(shards) != int(manifest.get("n_shards", -1)):
        raise CheckpointIntegrityError(
            f"sharded checkpoint {dirname}: manifest names "
            f"{len(shards)} shard(s) but declares "
            f"n_shards={manifest.get('n_shards')}"
        )
    def _index(name: str) -> int:
        # Numeric shard order, NOT lexicographic: %03d padding stops
        # helping past 999 shards ('shard-1000' < 'shard-101'
        # lexically), and a wrong order would concatenate the restore
        # silently scrambled — the exact Frankenstein class the
        # manifest exists to prevent.
        digits = "".join(c for c in name if c.isdigit())
        return int(digits) if digits else -1

    paths = []
    for name in sorted(shards, key=_index):
        path = os.path.join(dirname, name)
        if not os.path.exists(path):
            raise CheckpointIntegrityError(
                f"sharded checkpoint {dirname}: shard {name!r} missing"
            )
        got = _file_digest(path)
        if got != shards[name]:
            raise CheckpointIntegrityError(
                f"sharded checkpoint {dirname}: shard {name!r} sha256 "
                f"mismatch (manifest {shards[name][:12]}…, recomputed "
                f"{got[:12]}…) — torn or bit-rotted shard; the whole "
                "generation is rejected"
            )
        paths.append(path)
    return paths


def _load_sharded_arrays(dirname: str, manifest: dict) -> dict:
    """Digest-verify every shard file, then load and concatenate the
    per-shard chunks back into the full payload arrays (all BEFORE any
    tally state is overwritten)."""
    parts = []
    for path in _verify_shard_files(dirname, manifest):
        with np.load(path) as z:
            smeta = json.loads(bytes(z["meta"].tobytes()).decode())
            arrays = {k: z[k] for k in z.files if k != "meta"}
            _verify_integrity(arrays, smeta, path)
            parts.append(arrays)
    return {
        name: np.concatenate([p[name] for p in parts], axis=0)
        for name in parts[0]
    }


def verify_sharded_checkpoint(dirname: str) -> dict:
    """Standalone integrity check of a sharded generation: manifest
    present + every named shard exists and hashes clean. Returns the
    facade meta on success; ``CheckpointIntegrityError`` on any torn/
    corrupt condition (the whole generation is invalid)."""
    manifest = _read_manifest(dirname)
    _verify_shard_files(dirname, manifest)
    return manifest["meta"]


def _restore_sharded(dirname: str, tally, expected_kind) -> None:
    manifest = _read_manifest(dirname)
    meta = manifest["meta"]
    _validate_meta(meta, tally, expected_kind=expected_kind)
    arrays = _load_sharded_arrays(dirname, manifest)
    if expected_kind == "partitioned":
        _apply_partitioned(tally, meta, arrays)
    else:
        _apply_plain(tally, meta, arrays)


def _validate_meta(meta: dict, tally, expected_kind: str | None) -> None:
    """Shared restore-side validation: format, kind, mesh identity, dtype,
    run shape. Raises on any mismatch rather than silently resuming a
    different run (both facades)."""
    if meta["format_version"] != FORMAT_VERSION:
        raise ValueError(
            f"checkpoint format {meta['format_version']} != "
            f"{FORMAT_VERSION}"
        )
    kind = meta.get("kind")
    if kind != expected_kind:
        raise ValueError(
            f"checkpoint kind {kind!r} does not match this facade "
            f"(expected {expected_kind!r}: use "
            f"{'PartitionedTally' if kind == 'partitioned' else 'PumiTally'}"
            ".restore_checkpoint for this file)"
        )
    if meta["mesh_fingerprint"] != mesh_fingerprint(tally.mesh):
        raise ValueError("checkpoint was written against a different mesh")
    ck_dt = meta.get("dtype")
    if ck_dt is not None and np.dtype(ck_dt) != np.dtype(
        tally.config.dtype
    ):
        raise ValueError(
            f"checkpoint dtype is {ck_dt} but this tally is configured "
            f"dtype={np.dtype(tally.config.dtype)}; restoring would "
            "silently cast the accumulator (e.g. f64 → f32 loses the "
            "precision contract) — rebuild the tally with the "
            "checkpoint's dtype"
        )
    ck_sd = meta.get("sd_mode", "segment")  # pre-r5 files: segment
    if ck_sd != getattr(tally.config, "sd_mode", "segment"):
        raise ValueError(
            f"checkpoint slot-1 statistic is sd_mode={ck_sd!r} but this "
            f"tally is configured sd_mode={tally.config.sd_mode!r}; "
            "per-segment and per-move batch squares cannot be mixed"
        )
    if meta["num_particles"] != tally.num_particles:
        raise ValueError(
            f"checkpoint has {meta['num_particles']} particles, tally "
            f"has {tally.num_particles}"
        )
    if meta["n_groups"] != tally.config.n_groups:
        raise ValueError(
            f"checkpoint has {meta['n_groups']} energy groups, config "
            f"has {tally.config.n_groups}"
        )


# --------------------------------------------------------------------- #
# Plain (single-chip) facade payload
# --------------------------------------------------------------------- #
def _replanned_ladder(tally) -> bool:
    """Whether the tally's ladder was replanned from its first move by
    compact_stages="adaptive": the one schedule a checkpoint carries."""
    return tally.config.compact_stages == "adaptive" and tally._replanned


def _plain_payload(tally) -> tuple[dict, dict]:
    s = tally.state
    meta = {
        "format_version": FORMAT_VERSION,
        "mesh_fingerprint": mesh_fingerprint(tally.mesh),
        "num_particles": tally.num_particles,
        "n_groups": tally.config.n_groups,
        "iter_count": tally.iter_count,
        "total_segments": tally.total_segments,
        "initialized": tally._initialized,
        "dtype": str(np.dtype(tally.config.dtype)),
        # Slot-1 statistic: per-segment squares vs per-move batch
        # squares are NOT mixable — validated on restore.
        "sd_mode": tally.config.sd_mode,
        # Adaptive-replan state: compact_stages='adaptive' replans the
        # ladder once from the FIRST move's measured stats; a resumed
        # run must reuse that ladder, not replan from a later move's
        # stats (different ladder -> different scatter grouping ->
        # ~1e-15 flux drift, breaking the bitwise-resume guarantee).
        # Only that replanned ladder is stored: every other schedule
        # (a string such as "fit" included) comes from the config.
        "replanned": bool(tally._replanned),
        "compact_stages_planned": (
            [list(s) for s in tally._compact_stages]
            if _replanned_ladder(tally) and tally._compact_stages is not None
            else None
        ),
    }
    arrays = {
        # Canonical on-disk shape is [ntet, n_groups, 2] regardless of the
        # device layout (flat since round 4), so checkpoints stay portable
        # across layout changes.
        #
        # Every device-derived array is COPIED, never viewed:
        # np.asarray of a jax array can be a zero-copy view of the
        # device buffer on CPU, and the flux buffer is DONATED to the
        # next trace — a viewed "snapshot" would silently morph into
        # the post-move flux, doubling the move on a retry rollback
        # (snapshot_state is the ResilientRunner's retry anchor).
        "flux": np.array(tally.raw_flux, copy=True),
        "origin": np.array(s.origin, copy=True),
        "dest": np.array(s.dest, copy=True),
        "elem": np.array(s.elem, copy=True),
        "in_flight": np.array(s.in_flight, copy=True),
        "weight": np.array(s.weight, copy=True),
        "group": np.array(s.group, copy=True),
        "material_id": np.array(s.material_id, copy=True),
        "particle_id": np.array(s.particle_id, copy=True),
        "perm": (
            np.asarray(tally._perm)
            if tally._perm is not None
            else np.empty(0, np.int64)
        ),
        # Per-lane quarantine counts are resumable state: a resumed (or
        # retry-rolled-back) run must not lose or double its degraded-
        # mode report. Empty when the quarantine is off.
        "quarantined": (
            tally._quarantined.copy()
            if getattr(tally, "_quarantined", None) is not None
            else np.empty(0, np.int64)
        ),
    }
    return meta, arrays


def _apply_plain(tally, meta: dict, arrays: dict) -> None:
    import jax.numpy as jnp

    dtype = tally.config.dtype
    # Device accumulator is flat (api make_flux flat=True); accept
    # both 3-D (canonical/older) and flat on-disk arrays.
    tally.flux = jnp.asarray(arrays["flux"], dtype).reshape(-1)
    tally.state = tally.state._replace(
        origin=jnp.asarray(arrays["origin"], dtype),
        dest=jnp.asarray(arrays["dest"], dtype),
        elem=jnp.asarray(arrays["elem"], jnp.int32),
        in_flight=jnp.asarray(arrays["in_flight"], bool),
        weight=jnp.asarray(arrays["weight"], dtype),
        group=jnp.asarray(arrays["group"], jnp.int32),
        material_id=jnp.asarray(arrays["material_id"], jnp.int32),
        particle_id=jnp.asarray(arrays["particle_id"], jnp.int32),
    )
    tally.iter_count = int(meta["iter_count"])
    tally.total_segments = int(meta["total_segments"])
    tally._initialized = bool(meta["initialized"])
    perm = arrays["perm"]
    tally._perm = None if perm.size == 0 else perm.astype(np.int64)
    # Packed-pipeline derived state: re-derive the device-resident slot
    # permutation from the restored particle_id, and force the next
    # periodic sort to recompute its cached artifacts.
    if hasattr(tally, "_refresh_perm_device"):
        tally._refresh_perm_device()
    if hasattr(tally, "_traces_since_sort"):
        tally._traces_since_sort = 1
    if "replanned" in meta:
        tally._replanned = bool(meta["replanned"])
        planned = meta.get("compact_stages_planned")
        if _replanned_ladder(tally) and planned is not None:
            tally._compact_stages = tuple(
                tuple(int(x) for x in s) for s in planned
            )
    if hasattr(tally, "_reset_convergence"):
        # Batch statistics are monitor state, not resumable tally state:
        # re-base them on the restored accumulator (obs/convergence.py).
        tally._reset_convergence()
    _apply_quarantined(tally, arrays)
    if getattr(tally, "_prev_even", None) is not None:
        # sd_mode="batch": the even-entry snapshot is derived state —
        # the per-move fold runs after every move, so at any
        # checkpoint boundary it equals the current even entries.
        tally._prev_even = tally.flux[0::2]


def save_checkpoint(filename: str, tally, n_shards: int | None = None) -> None:
    """Serialize a PumiTally's resumable state (atomic write + per-array
    digests, see module docstring). A ``.shards`` filename writes the
    sharded two-phase layout instead (``n_shards`` splits)."""
    if is_sharded(filename):
        save_sharded_checkpoint(filename, tally, n_shards=n_shards)
        return
    meta, arrays = _plain_payload(tally)
    _write_checkpoint(_normalize(filename), meta, arrays)


def restore_checkpoint(filename: str, tally) -> None:
    """Restore state saved by save_checkpoint into a PumiTally constructed
    with the same mesh and config. Raises on any mismatch or integrity
    failure BEFORE overwriting any tally state."""
    if is_sharded(filename):
        _restore_sharded(filename, tally, expected_kind=None)
        return
    with np.load(_normalize(filename)) as z:
        meta = json.loads(bytes(z["meta"].tobytes()).decode())
        _validate_meta(meta, tally, expected_kind=None)
        arrays = {k: z[k] for k in z.files if k != "meta"}
        _verify_integrity(arrays, meta, filename)
    _apply_plain(tally, meta, arrays)


def _apply_quarantined(tally, arrays: dict) -> None:
    """Restore the per-lane quarantine counts where both sides track
    them (quarantine on, payload carries a matching array)."""
    q = arrays.get("quarantined")
    if (
        getattr(tally, "_quarantined", None) is not None
        and q is not None
        and q.size == tally._quarantined.size
    ):
        tally._quarantined = np.asarray(q, np.int64).copy()


# --------------------------------------------------------------------- #
# Partitioned facade payload
# --------------------------------------------------------------------- #
def _partitioned_payload(tally) -> tuple[dict, dict]:
    # Device-sourced megastep state folds back to the host mirrors
    # first (run_source_moves keeps slot state device-resident between
    # dispatches); the slot layout itself is ALSO persisted below so a
    # same-layout restore resumes bitwise (re-distributing from the
    # per-particle fields would re-bucket slots and change the flux
    # summation order).
    if getattr(tally, "_src", None) is not None:
        tally._sync_source_state()
    meta = {
        "format_version": FORMAT_VERSION,
        "kind": "partitioned",
        "mesh_fingerprint": mesh_fingerprint(tally.mesh),
        "num_particles": tally.num_particles,
        "n_groups": tally.config.n_groups,
        "iter_count": tally.iter_count,
        "total_segments": tally.total_segments,
        "total_rounds": tally.total_rounds,
        "initialized": tally._initialized,
        "dtype": str(np.dtype(tally.config.dtype)),
        "sd_mode": tally.config.sd_mode,
    }
    arrays = {
        # raw_flux assembles a fresh host array, but copy defensively
        # for the same donation-aliasing reason as the plain payload.
        "flux": np.array(tally.raw_flux, copy=True),
        "positions": tally.positions.copy(),
        "elem_global": tally.elem_global.copy(),
        "material_id": tally.material_id.copy(),
        "quarantined": (
            tally._quarantined.copy()
            if getattr(tally, "_quarantined", None) is not None
            else np.empty(0, np.int64)
        ),
    }
    if hasattr(tally, "weights"):
        # Persistent physics lanes of the device-sourced move loop.
        arrays["weights"] = np.asarray(tally.weights).copy()
        arrays["groups"] = np.asarray(tally.groups).copy()
        arrays["alive"] = np.asarray(tally.alive).copy()
    if getattr(tally, "_src", None) is not None:
        meta["src_layout"] = [int(tally.n_parts), int(tally.cap)]
        for name, arr in tally._src.items():
            arrays[f"src_{name}"] = np.array(
                np.asarray(arr), copy=True
            )
    return meta, arrays


def _apply_partitioned(tally, meta: dict, arrays: dict) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh_partition import disassemble_global_flux
    from ..parallel.particle_sharding import PARTICLE_AXIS

    slabs = disassemble_global_flux(
        tally.partition,
        np.asarray(arrays["flux"]).astype(np.dtype(tally.config.dtype)),
    )
    # Device slabs are FLAT per chip (partitioned_api flux_slabs).
    tally.flux_slabs = jax.device_put(
        jnp.asarray(slabs.reshape(slabs.shape[0], -1)),
        NamedSharding(tally.device_mesh, P(PARTICLE_AXIS)),
    )
    tally.positions = np.asarray(arrays["positions"]).copy()
    tally.elem_global = np.asarray(arrays["elem_global"]).copy()
    tally.material_id = np.asarray(arrays["material_id"]).copy()
    if hasattr(tally, "weights") and "weights" in arrays:
        tally.weights = np.asarray(arrays["weights"], np.float64).copy()
        tally.groups = np.asarray(arrays["groups"], np.int32).copy()
        tally.alive = np.asarray(arrays["alive"]).astype(bool).copy()
    if hasattr(tally, "_src"):
        # Megastep slot state: rebuild the exact device layout when the
        # checkpoint's partition shape matches (bitwise resume of the
        # device-sourced loop); otherwise drop it — the next
        # run_source_moves re-distributes from the per-particle fields
        # (correct, but the flux summation order may differ).
        layout = meta.get("src_layout")
        if layout is not None and layout == [
            int(tally.n_parts), int(tally.cap)
        ]:
            sh = NamedSharding(tally.device_mesh, P(PARTICLE_AXIS))
            dtype = tally.config.dtype
            src = {}
            for name in ("pos", "elem", "material_id", "weight",
                         "group", "pid", "valid", "alive"):
                arr = jnp.asarray(arrays[f"src_{name}"])
                if name in ("pos", "weight"):
                    arr = arr.astype(dtype)
                src[name] = jax.device_put(arr, sh)
            tally._src = src
        else:
            tally._src = None
    tally.iter_count = int(meta["iter_count"])
    tally.total_segments = int(meta["total_segments"])
    tally.total_rounds = int(meta["total_rounds"])
    tally._initialized = bool(meta["initialized"])
    _apply_quarantined(tally, arrays)
    if getattr(tally, "_prev_even", None) is not None:
        # Batch-sd snapshot is derived state (== current even
        # entries at any move boundary), re-slabbed alongside flux.
        tally._prev_even = tally.flux_slabs[:, 0::2]
    if hasattr(tally, "_reset_convergence"):
        # Batch statistics re-base on the restored slabs (see
        # _apply_plain).
        tally._reset_convergence()


def save_partitioned_checkpoint(
    filename: str, tally, n_shards: int | None = None
) -> None:
    """Serialize a PartitionedTally's resumable state.

    The flux is stored ASSEMBLED (global element order), so a checkpoint
    is partition-layout independent: it can resume under a different
    part count or halo depth (the owned-slab layout is derived state).
    Particle state is the facade's host-side arrays. Atomic write +
    per-array digests like the plain facade. A ``.shards`` filename
    writes the sharded two-phase layout (one npz per mesh part by
    default + manifest committed last) instead.
    """
    if is_sharded(filename):
        save_sharded_checkpoint(filename, tally, n_shards=n_shards)
        return
    meta, arrays = _partitioned_payload(tally)
    _write_checkpoint(_normalize(filename), meta, arrays)


def restore_partitioned_checkpoint(filename: str, tally) -> None:
    """Restore state saved by save_partitioned_checkpoint into a
    PartitionedTally on the same mesh (any partition layout). Validation
    and integrity checks run BEFORE any state is overwritten."""
    if is_sharded(filename):
        _restore_sharded(filename, tally, expected_kind="partitioned")
        return
    with np.load(_normalize(filename)) as z:
        meta = json.loads(bytes(z["meta"].tobytes()).decode())
        _validate_meta(meta, tally, expected_kind="partitioned")
        arrays = {k: z[k] for k in z.files if k != "meta"}
        _verify_integrity(arrays, meta, filename)
    _apply_partitioned(tally, meta, arrays)


# --------------------------------------------------------------------- #
# In-memory snapshots (the ResilientRunner's retry anchor)
# --------------------------------------------------------------------- #
def snapshot_state(tally) -> tuple:
    """Host-side copy of the resumable state — the same payload a
    checkpoint file carries, without serialization. Cheap relative to a
    checkpoint write; the runner takes one after every successful move
    so a transient device failure can roll back WITHOUT losing the
    moves since the last on-disk generation."""
    if hasattr(tally, "flux_slabs"):
        meta, arrays = _partitioned_payload(tally)
        return ("partitioned", meta, arrays)
    meta, arrays = _plain_payload(tally)
    return ("plain", meta, arrays)


def restore_state(tally, snap: tuple) -> None:
    """Apply a ``snapshot_state`` payload back onto the tally it came
    from (no validation — same-process, same-object roll-back)."""
    kind, meta, arrays = snap
    if kind == "partitioned":
        _apply_partitioned(tally, meta, arrays)
    else:
        _apply_plain(tally, meta, arrays)
