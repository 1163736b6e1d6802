"""Sharded checkpoint / resume (orbax-backed) with integrity manifests.

The reference has no checkpointing at all — its op graph is in-memory
only, with type-erased closures that cannot serialize (SURVEY.md §5,
deferred_init.cc:165).  The TPU framework closes that gap at the right
level: recordings themselves stay ephemeral (they are cheap to re-record
from config), while *materialized, sharded training state* checkpoints
through orbax with each host writing only its own shards, and restores
directly into the target sharding layout (so a resume can change mesh
shape).

On top of the orbax payload every checkpoint carries a **manifest**
(``tdx_manifest.json``: the state's leaf tree plus per-file size + CRC32)
and an explicit **commit marker** (``TDX_COMMITTED``, written last, with
the manifest's own checksum).  Together they make three guarantees the
bare orbax layout cannot:

* a checkpoint without the marker was never fully written — resume code
  skips it instead of crashing mid-restore on a torn write;
* a committed checkpoint whose payload later rots (truncation, bit
  flips) fails :func:`verify_checkpoint` *before* restore deserializes
  garbage into training state;
* a bad checkpoint is :func:`quarantine_checkpoint`-renamed to
  ``<dir>.corrupt`` — kept for forensics, invisible to resume scans.

Since round 13 the manifest also records a **topology block** — the mesh
axis names/sizes, each leaf's PartitionSpec string, and a plan digest
(:func:`state_topology`) — so a restore can detect that the checkpoint
was written under a different ``ShardingPlan``/mesh and route through
:mod:`torchdistx_tpu.reshard` instead of crashing.  Old manifests
without the block still verify: the reader is schema-tolerant.

Verification telemetry: ``ckpt.save`` / ``ckpt.restore`` / ``ckpt.verify``
spans, ``tdx.ckpt.verify_fail`` / ``tdx.ckpt.quarantined`` counters
(see docs/robustness.md for the full vocabulary).
"""

from __future__ import annotations

import json
import os
import zlib
from pathlib import Path
from typing import Any, Iterator, List, Optional, Tuple

import jax

from .. import observe

MANIFEST_NAME = "tdx_manifest.json"
COMMIT_MARKER = "TDX_COMMITTED"
QUARANTINE_SUFFIX = ".corrupt"

__all__ = [
    "AsyncCheckpointSaver",
    "CheckpointCorruptError",
    "checkpoint_version",
    "iter_payload_files",
    "leaf_storage_name",
    "quarantine_checkpoint",
    "read_manifest",
    "restore_checkpoint",
    "save_checkpoint",
    "state_topology",
    "verify_checkpoint",
    "write_manifest",
]


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed integrity verification (or has no commit
    marker).  Carries the human-readable reason in ``args[0]``."""


def _orbax():
    """``orbax.checkpoint``, imported where a payload is written or read
    and nowhere else: it pulls in tensorstore and ``google.cloud.logging``
    (seconds of import), and the manifest, marker and verification code
    that serving and resharding use need none of it."""
    try:
        import orbax.checkpoint as ocp
    except Exception as e:  # pragma: no cover
        raise RuntimeError("orbax-checkpoint is not installed.") from e
    return ocp


# ---------------------------------------------------------------------------
# manifest + commit marker


def iter_payload_files(path: "str | Path") -> Iterator[str]:
    """Relative paths of every file under ``path`` except our own
    manifest/marker — i.e. the orbax payload the checksums cover."""
    path = Path(path)
    for root, _dirs, files in os.walk(path):
        for name in files:
            if name in (MANIFEST_NAME, COMMIT_MARKER):
                continue
            yield str((Path(root) / name).relative_to(path))


def _crc32_file(f: Path) -> Tuple[int, int]:
    """(size, crc32) streamed in chunks — checkpoints can dwarf RAM."""
    crc = 0
    size = 0
    with open(f, "rb") as fh:
        while True:
            chunk = fh.read(1 << 20)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
            size += len(chunk)
    return size, crc


def _leaf_tree(state: Any) -> List[dict]:
    out: List[dict] = []
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        entry: dict = {"path": jax.tree_util.keystr(keypath)}
        if hasattr(leaf, "shape"):
            entry["shape"] = list(leaf.shape)
            entry["dtype"] = str(getattr(leaf, "dtype", ""))
        out.append(entry)
    return out


def leaf_storage_name(keypath) -> str:
    """The orbax/tensorstore storage name of a leaf: keypath components
    joined with ``.`` (dict keys and namedtuple fields by name, sequence
    positions by index) — ``['opt'][0].mu['dense']['kernel']`` stores as
    ``opt.0.mu.dense.kernel``.  This is the key the reshard engine uses
    to address individual leaves inside the checkpoint's OCDBT kvstore,
    and the key of the manifest topology block's per-leaf spec table."""
    parts = []
    for k in keypath:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(str(k.name))
        elif isinstance(k, jax.tree_util.FlattenedIndexKey):
            parts.append(str(k.key))
        else:  # pragma: no cover - future key types
            parts.append(str(k))
    return ".".join(parts)


def state_topology(state: Any) -> Optional[dict]:
    """The manifest ``topology`` block for a pytree of (possibly sharded)
    arrays: mesh axis names/sizes, per-leaf PartitionSpec string (keyed by
    storage name), and a plan digest over both.  ``None`` when the tree
    has no array leaves.  Leaves without a ``NamedSharding`` (host scalars,
    single-device arrays) record as replicated — ``"()"``."""
    from ..parallel.sharding import plan_digest, spec_str  # lazy: no cycle

    mesh_axes: dict = {}
    specs: dict = {}
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if not hasattr(leaf, "shape"):
            continue
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, jax.sharding.NamedSharding):
            m = sh.mesh
            mesh_axes = {
                str(a): int(s) for a, s in zip(m.axis_names, m.devices.shape)
            }
            specs[leaf_storage_name(keypath)] = spec_str(sh.spec)
        else:
            specs[leaf_storage_name(keypath)] = spec_str(None)
    if not specs:
        return None
    return {
        "mesh_axes": mesh_axes,
        "specs": specs,
        "plan_digest": plan_digest(mesh_axes, specs),
    }


def write_manifest(
    path: "str | Path",
    state: Any = None,
    *,
    tree: Optional[List[dict]] = None,
    topology: Optional[dict] = None,
) -> dict:
    """Checksum the payload, write ``tdx_manifest.json``, then commit by
    writing ``TDX_COMMITTED`` (containing the manifest's CRC32) LAST —
    marker presence therefore implies the manifest, and the manifest
    implies every payload byte it lists.  The leaf tree and topology
    block come from ``state``, or precomputed via ``tree`` / ``topology``
    (async savers stash them at save time instead of pinning arrays).
    Old manifests without a topology block stay valid — verification
    ignores keys it does not know.  Returns the manifest dict."""
    path = Path(path)
    files = {}
    for rel in sorted(iter_payload_files(path)):
        size, crc = _crc32_file(path / rel)
        files[rel] = {"size": size, "crc32": f"{crc:08x}"}
    manifest = {"version": 1, "files": files}
    if state is not None:
        if tree is None:
            tree = _leaf_tree(state)
        if topology is None:
            topology = state_topology(state)
    if tree is not None:
        manifest["tree"] = tree
    if topology is not None:
        manifest["topology"] = topology
    payload = json.dumps(manifest, indent=1, sort_keys=True).encode()
    tmp = path / (MANIFEST_NAME + ".tmp")
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path / MANIFEST_NAME)
    with open(path / COMMIT_MARKER, "w") as f:
        f.write(f"{zlib.crc32(payload):08x}\n")
        f.flush()
        os.fsync(f.fileno())
    return manifest


def is_committed(path: "str | Path") -> bool:
    """Cheap commit check: marker file present (no payload verification)."""
    return (Path(path) / COMMIT_MARKER).is_file()


def read_manifest(path: "str | Path") -> Optional[dict]:
    """The parsed ``tdx_manifest.json`` of a checkpoint, or ``None`` when
    there is no (readable) manifest — pre-manifest checkpoints restore
    fine, they just carry no integrity or topology metadata."""
    mf = Path(path) / MANIFEST_NAME
    try:
        return json.loads(mf.read_bytes())
    except (OSError, ValueError):
        return None


def checkpoint_version(path: "str | Path") -> str:
    """The serving weight-version stamp of a checkpoint:
    ``<dirname>@<manifest-digest>`` — e.g. ``step_12@a1b2c3d4``.

    The directory name carries the training step (``run_elastic`` lays
    checkpoints out as ``step_N``); the digest is the commit marker's
    CRC32 of the manifest bytes, which transitively covers every payload
    byte (the manifest checksums the payload, the marker checksums the
    manifest).  Two checkpoints with the same step but different weights
    therefore stamp differently.  Uncommitted checkpoints stamp as
    ``<dirname>@uncommitted`` — rollover refuses them anyway."""
    path = Path(path)
    try:
        digest = (path / COMMIT_MARKER).read_text().strip()[:8]
    except OSError:
        digest = ""
    return f"{path.name}@{digest or 'uncommitted'}"


def verify_checkpoint(path: "str | Path") -> Tuple[bool, str]:
    """Integrity-check a checkpoint against its manifest.

    Returns ``(ok, reason)``; ``reason`` names the first failure
    (uncommitted, manifest/marker mismatch, missing file, size or CRC
    mismatch).  Extra files beyond the manifest are tolerated — orbax
    versions differ in auxiliary metadata.  Increments
    ``tdx.ckpt.verify_fail`` on failure."""
    path = Path(path)
    with observe.span("ckpt.verify", category="ckpt", path=str(path)) as sp:
        ok, reason = _verify(path)
        sp.set(ok=ok, **({} if ok else {"reason": reason}))
    if not ok:
        observe.counter("tdx.ckpt.verify_fail").inc()
        observe.instant("ckpt.verify_fail", category="ckpt",
                        path=str(path), reason=reason)
    return ok, reason


def _verify(path: Path) -> Tuple[bool, str]:
    if not path.is_dir():
        return False, f"not a directory: {path}"
    marker = path / COMMIT_MARKER
    if not marker.is_file():
        return False, "no commit marker (save never completed)"
    mf = path / MANIFEST_NAME
    if not mf.is_file():
        return False, "commit marker without manifest"
    raw = mf.read_bytes()
    try:
        want = marker.read_text().strip()
    except OSError as e:
        return False, f"unreadable commit marker: {e}"
    if f"{zlib.crc32(raw):08x}" != want:
        return False, "manifest checksum does not match commit marker"
    try:
        manifest = json.loads(raw)
    except ValueError as e:
        return False, f"unparseable manifest: {e}"
    for rel, meta in manifest.get("files", {}).items():
        f = path / rel
        if not f.is_file():
            return False, f"missing payload file: {rel}"
        size, crc = _crc32_file(f)
        if size != meta["size"]:
            return False, f"size mismatch for {rel}: {size} != {meta['size']}"
        if f"{crc:08x}" != meta["crc32"]:
            return False, f"crc mismatch for {rel}"
    return True, "ok"


def quarantine_checkpoint(path: "str | Path") -> Path:
    """Rename a bad checkpoint out of the resume scan's sight
    (``step_N`` → ``step_N.corrupt``, suffixed ``.2``, ``.3``… if a prior
    quarantine of the same step exists).  Returns the new path."""
    path = Path(path)
    dst = path.with_name(path.name + QUARANTINE_SUFFIX)
    n = 1
    while dst.exists():
        n += 1
        dst = path.with_name(path.name + f"{QUARANTINE_SUFFIX}.{n}")
    os.replace(path, dst)
    observe.counter("tdx.ckpt.quarantined").inc()
    observe.instant("ckpt.quarantined", category="ckpt",
                    path=str(path), quarantined_to=str(dst))
    return dst


# ---------------------------------------------------------------------------
# save / restore


def save_checkpoint(
    path: "str | Path", state: Any, *, force: bool = True, manifest: bool = True
) -> None:
    """Save a pytree of (possibly sharded) jax.Arrays, then write the
    integrity manifest + commit marker (``manifest=False`` skips them —
    the pre-manifest layout, kept for interop)."""
    ocp = _orbax()
    path = Path(path).absolute()
    with observe.span("ckpt.save", category="ckpt", path=str(path)):
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(path, state, force=force)
        ckptr.wait_until_finished()
        if manifest:
            write_manifest(path, state)


class AsyncCheckpointSaver:
    """Non-blocking sharded saves: :meth:`save` kicks off the device→host
    copy and returns; serialization to disk proceeds on orbax's background
    thread while training continues — the standard TPU pattern for hiding
    checkpoint latency behind compute.  Call :meth:`wait_until_finished`
    (or use as a context manager) before reading the files or exiting.

    Integrity manifests cannot be written until orbax finishes the
    payload, so a pending save COMMITS (gains its manifest + marker) at
    the next :meth:`wait_until_finished`.  Until then the directory has
    no ``TDX_COMMITTED`` and resume scans ignore it — an in-flight save
    is not yet durable, and the marker's absence says exactly that.
    """

    def __init__(self, *, manifest: bool = True) -> None:
        ocp = _orbax()
        self._ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        self._manifest = manifest
        # (path, leaf tree, topology) saved by orbax but not yet
        # committed.  Both are captured at save time — cheap metadata
        # (shapes + sharding specs), no array refs.
        self._pending: List[Tuple[Path, List[dict], Optional[dict]]] = []

    def save(self, path: "str | Path", state: Any, *, force: bool = True) -> None:
        path = Path(path).absolute()
        self._ckptr.save(
            path, args=_orbax().args.StandardSave(state), force=force
        )
        if self._manifest:
            self._pending.append((path, _leaf_tree(state), state_topology(state)))

    def wait_until_finished(self) -> None:
        self._ckptr.wait_until_finished()
        pending, self._pending = self._pending, []
        for path, tree, topology in pending:
            if path.is_dir():  # a force-overwrite may have replaced it
                write_manifest(path, tree=tree, topology=topology)

    def close(self) -> None:
        self._ckptr.close()

    def __enter__(self) -> "AsyncCheckpointSaver":
        return self

    def __exit__(self, *exc) -> None:
        try:
            self.wait_until_finished()
        finally:
            self.close()  # always release orbax's background thread


def restore_checkpoint(
    path: "str | Path",
    *,
    target: Optional[Any] = None,
    verify: bool = False,
) -> Any:
    """Restore; if ``target`` is a pytree of ShapeDtypeStruct with
    shardings (or of arrays), values land directly in that layout.

    ``verify=True`` integrity-checks the manifest first and raises
    :class:`CheckpointCorruptError` instead of deserializing a damaged
    payload (``run_elastic`` does this and falls back to an older step)."""
    ocp = _orbax()
    path = Path(path).absolute()
    if verify:
        ok, reason = verify_checkpoint(path)
        if not ok:
            raise CheckpointCorruptError(f"{path}: {reason}")
    with observe.span("ckpt.restore", category="ckpt", path=str(path)):
        ckptr = ocp.StandardCheckpointer()
        if target is not None:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)
                )
                if hasattr(x, "shape")
                else x,
                target,
            )
            return ckptr.restore(path, abstract)
        return ckptr.restore(path)
