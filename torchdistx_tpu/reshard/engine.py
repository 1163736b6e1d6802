"""Reshard execution: streaming checkpoint redistribution over tensorstore.

Why this works without orbax cooperation: an orbax OCDBT checkpoint
stores each leaf as a *logical* zarr array (keyed by dotted storage
name), chunked by the save-time shard shape — the topology lives in the
chunk grid and metadata, not in the values.  So the offline reshard is a
rechunk-copy: open each source leaf read-only, create the same leaf in
the destination kvstore with a chunk grid equal to plan B's shard
blocks, and stream budget-bounded slabs between them.  The orbax
structural metadata files (``_METADATA``, ``_sharding``,
``_CHECKPOINT_METADATA``) are copied verbatim, so the destination
restores through the normal :func:`~..utils.checkpoint.restore_checkpoint`
path with the original pytree structure (optax namedtuples included) —
proven bitwise-equal by the verify stage before the manifest + commit
marker are written.

Memory bound (arXiv:2112.01075): every host-side staging buffer is a
chunk of at most ``TDX_RESHARD_CHUNK_MB`` (tracked by
:class:`_MemTracker`; :func:`last_transfer_peak_bytes` exposes the peak
for tests).  The online path assembles destination shards on-device from
slab-sized pieces, so a full unsharded leaf never exists on one host.

Failure contract (degrade-never-corrupt): any fault — including injected
``reshard``-site chaos — leaves the destination without a commit marker
(offline) or the target state unpublished (online), never quarantines
anything, leaves the source untouched, and raises
:class:`~.diff.ReshardError`.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, List, Optional

import numpy as np

from .. import chaos, observe
from ..utils.checkpoint import (
    is_committed,
    leaf_storage_name,
    read_manifest,
    state_topology,
    verify_checkpoint,
    write_manifest,
)
from ..utils.logging import get_logger
from .diff import (
    MeshSpec,
    ReshardError,
    ReshardPlan,
    chunk_boxes,
    leaf_blocks,
    np_dtype,
    plan_from_manifest,
)

__all__ = [
    "last_transfer_peak_bytes",
    "needs_reshard",
    "plan_reshard",
    "reshard_checkpoint",
    "restore_resharded",
    "verify_reshard",
]

# Kvstore/top-level names the rechunk-copy must NOT carry over verbatim:
# the OCDBT database files are rebuilt by the destination writes, and the
# integrity manifest/marker are re-derived from the destination payload.
_SKIP_TOPLEVEL = ("d", "manifest.ocdbt", "tdx_manifest.json", "TDX_COMMITTED")


def _ts():
    try:
        import tensorstore

        return tensorstore
    except Exception as e:  # pragma: no cover - ts ships with orbax
        raise ReshardError(f"tensorstore is required for resharding: {e}")


def _kvstore(dirpath: Path):
    ts = _ts()
    return ts.KvStore.open(
        {"driver": "ocdbt", "base": f"file://{dirpath}"}
    ).result()


def _open_leaf(dirpath: Path, name: str, *, create: bool = False):
    ts = _ts()
    return ts.open(
        {
            "driver": "zarr",
            "kvstore": {
                "driver": "ocdbt",
                "base": f"file://{dirpath}",
                "path": f"{name}/",
            },
        },
        open=True,
        create=create,
    ).result()


def _leaf_names(kv) -> List[str]:
    return sorted({
        k.decode().split("/", 1)[0] for k in kv.list().result()
        if "/" in k.decode()
    })


def _slices(box):
    if not box:
        return Ellipsis  # rank-0 leaf
    return tuple(slice(lo, hi) for lo, hi in box)


def _box_bytes(box, itemsize: int) -> int:
    n = itemsize
    for lo, hi in box:
        n *= hi - lo
    return n


class _MemTracker:
    """Host staging-buffer accounting for the memory-bound contract."""

    __slots__ = ("current", "peak")

    def __init__(self) -> None:
        self.current = 0
        self.peak = 0

    def alloc(self, nbytes: int) -> None:
        self.current += nbytes
        if self.current > self.peak:
            self.peak = self.current

    def free(self, nbytes: int) -> None:
        self.current -= nbytes


_last_tracker: Optional[_MemTracker] = None


def last_transfer_peak_bytes() -> int:
    """Peak tracked host staging bytes of the most recent reshard
    transfer in this process (0 if none ran) — the test hook behind the
    "peak host memory stays bounded by the chunk budget" guarantee."""
    return _last_tracker.peak if _last_tracker else 0


def _budget_bytes(chunk_mb: Optional[float]) -> int:
    if chunk_mb is None:
        from .. import config

        chunk_mb = config.get().reshard_chunk_mb
    return max(1, int(float(chunk_mb) * (1 << 20)))


def _flip_chunk(buf: np.ndarray) -> None:
    """The ``reshard`` site's ``corrupt`` kind: damage the in-flight
    chunk buffer (torn-DMA model) — never a file."""
    flat = buf.reshape(-1)
    if flat.size:
        raw = flat.view(np.uint8)
        raw[0] ^= 0xFF


class _ChunkPump:
    """Shared read-chunk → chaos → account loop for both transfer paths."""

    def __init__(self, tracker: _MemTracker, chaos_plan) -> None:
        self.tracker = tracker
        self.chaos_plan = chaos_plan
        self.chunk_no = 0
        self.bytes_moved = 0

    def read(self, src_arr, box, itemsize: int) -> np.ndarray:
        nbytes = _box_bytes(box, itemsize)
        self.tracker.alloc(nbytes)
        buf = src_arr[_slices(box)].read().result()
        self.chunk_no += 1
        fired = chaos.maybe_inject(
            "reshard", self.chunk_no, plan=self.chaos_plan
        )
        if any(f.kind == "corrupt" for f in fired):
            _flip_chunk(buf)
        self.bytes_moved += nbytes
        observe.counter("tdx.reshard.chunks").inc()
        observe.counter("tdx.reshard.bytes_moved").inc(nbytes)
        return buf

    def release(self, box, itemsize: int) -> None:
        self.tracker.free(_box_bytes(box, itemsize))


def plan_reshard(src_dir, plan_b, mesh_b, *, chunk_mb: Optional[float] = None
                 ) -> ReshardPlan:
    """Compute the transfer schedule for redistributing ``src_dir`` to
    ``plan_b`` over ``mesh_b`` (a Mesh, :class:`MeshSpec`, or axes dict).
    Pure metadata — safe on hosts with no devices.  Emits a
    ``reshard.plan`` span."""
    src = Path(src_dir).absolute()
    budget = _budget_bytes(chunk_mb)
    with observe.span("reshard.plan", category="reshard", path=str(src)) as sp:
        manifest = read_manifest(src)
        if manifest is None:
            raise ReshardError(f"{src}: no manifest (is this a checkpoint?)")
        plan = plan_from_manifest(
            str(src), manifest, plan_b, mesh_b, budget_bytes=budget
        )
        sp.set(leaves=len(plan.leaves), chunks=plan.total_chunks,
               bytes=plan.total_bytes)
    return plan


def reshard_checkpoint(
    src_dir,
    plan_b,
    mesh_b,
    dst_dir=None,
    *,
    chunk_mb: Optional[float] = None,
    verify: bool = True,
    chaos_plan=None,
) -> Path:
    """Redistribute a committed checkpoint to plan B's layout, offline.

    Streams each leaf from the source into a destination checkpoint whose
    zarr chunk grid equals plan B's shard blocks, copies the orbax
    structural metadata verbatim, bitwise-verifies leaf-by-leaf against a
    direct (chunked) gather of the source, and only then writes the
    manifest — with plan B's topology block — and the commit marker.
    Returns the destination path.

    On ANY failure the destination is removed (it never carried a commit
    marker), nothing is quarantined, the source is untouched, and a
    :class:`ReshardError` raises.
    """
    global _last_tracker
    src = Path(src_dir).absolute()
    ok, reason = verify_checkpoint(src)
    if not ok:
        raise ReshardError(f"source checkpoint failed verification: {reason}")
    plan = plan_reshard(src, plan_b, mesh_b, chunk_mb=chunk_mb)
    dst = Path(
        dst_dir
        if dst_dir is not None
        else src.with_name(f"{src.name}.reshard-{plan.dst_digest}")
    ).absolute()
    if dst == src:
        raise ReshardError(f"destination equals source: {dst}")
    log = get_logger()
    tracker = _MemTracker()
    _last_tracker = tracker
    pump = _ChunkPump(tracker, chaos_plan)
    try:
        if dst.exists():
            shutil.rmtree(dst)
        dst.mkdir(parents=True)
        by_name = plan.by_name
        skv = _kvstore(src)
        dkv = _kvstore(dst)
        with observe.span(
            "reshard.transfer", category="reshard",
            src=str(src), dst=str(dst), mode="offline",
        ) as sp:
            for name in _leaf_names(skv):
                zsrc = json.loads(
                    skv.read(f"{name}/.zarray").result().value.decode()
                )
                shape = tuple(zsrc["shape"])
                entry = by_name.get(name)
                block = entry.dst_block_shape if entry else shape
                znew = dict(zsrc)
                if shape:
                    znew["chunks"] = [max(1, int(c)) for c in block]
                dkv.write(
                    f"{name}/.zarray", json.dumps(znew).encode()
                ).result()
                src_arr = _open_leaf(src, name)
                dst_arr = _open_leaf(dst, name)
                itemsize = src_arr.dtype.numpy_dtype.itemsize
                grid = tuple(
                    s // b for s, b in zip(shape, block)
                ) if shape else ()
                for bbox in leaf_blocks(shape, grid):
                    for cbox in chunk_boxes(bbox, itemsize, plan.budget_bytes):
                        buf = pump.read(src_arr, cbox, itemsize)
                        try:
                            dst_arr[_slices(cbox)] = buf
                        finally:
                            del buf
                            pump.release(cbox, itemsize)
                observe.counter("tdx.reshard.leaves").inc()
            # Non-leaf kv entries (none today, but schema-tolerant).
            for k in skv.list().result():
                key = k.decode()
                if "/" not in key:
                    dkv.write(key, skv.read(key).result().value).result()
            # Orbax structural metadata: verbatim files, so the
            # destination restores with the original pytree structure.
            for p in src.iterdir():
                if p.name in _SKIP_TOPLEVEL or p.name.startswith("ocdbt."):
                    continue
                if p.is_dir():
                    shutil.copytree(p, dst / p.name)
                else:
                    shutil.copy2(p, dst / p.name)
            sp.set(leaves=len(plan.leaves), chunks=pump.chunk_no,
                   bytes=pump.bytes_moved, peak_host_bytes=tracker.peak)
        if verify:
            vok, vreason = verify_reshard(src, dst, chunk_mb=chunk_mb)
            if not vok:
                raise ReshardError(
                    f"bitwise verify failed after reshard: {vreason}"
                )
        write_manifest(
            dst,
            tree=read_manifest(src).get("tree"),
            topology=plan.to_topology(),
        )
        log.info(
            "reshard: %s -> %s (%d leaves, %d chunks, %d bytes, peak %d B)",
            src, dst, len(plan.leaves), pump.chunk_no, pump.bytes_moved,
            tracker.peak,
        )
        return dst
    except ReshardError:
        shutil.rmtree(dst, ignore_errors=True)
        raise
    except Exception as e:
        shutil.rmtree(dst, ignore_errors=True)
        raise ReshardError(f"reshard {src} -> {dst} failed: {e}") from e


def verify_reshard(src_dir, dst_dir, *, chunk_mb: Optional[float] = None,
                   ) -> "tuple[bool, str]":
    """Streaming bitwise leaf-by-leaf comparison of two checkpoints'
    stored values (chunked — bounded host memory; layout-independent, so
    a resharded copy compares clean against its source).  Committed
    sides additionally pass their own integrity manifest (whole-file
    CRCs), so damage to bytes no leaf read happens to touch — OCDBT
    slack, superseded btree nodes — still fails the verify.  Returns
    ``(ok, reason)``; increments ``tdx.reshard.verify_fail`` on mismatch."""
    src, dst = Path(src_dir).absolute(), Path(dst_dir).absolute()
    budget = _budget_bytes(chunk_mb)
    with observe.span(
        "reshard.verify", category="reshard", src=str(src), dst=str(dst)
    ) as sp:
        for side, label in ((src, "src"), (dst, "dst")):
            if is_committed(side):
                iok, ireason = verify_checkpoint(side)
                if not iok:
                    sp.set(ok=False)
                    observe.counter("tdx.reshard.verify_fail").inc()
                    observe.instant(
                        "reshard.verify_fail", category="reshard",
                        side=label, reason=str(ireason)[:200],
                    )
                    return False, f"{label} integrity: {ireason}"
        src_names = _leaf_names(_kvstore(src))
        dst_names = _leaf_names(_kvstore(dst))
        if src_names != dst_names:
            sp.set(ok=False)
            observe.counter("tdx.reshard.verify_fail").inc()
            return False, (
                f"leaf sets differ: {sorted(set(src_names) ^ set(dst_names))}"
            )
        for name in src_names:
            a = _open_leaf(src, name)
            b = _open_leaf(dst, name)
            if tuple(a.shape) != tuple(b.shape):
                observe.counter("tdx.reshard.verify_fail").inc()
                sp.set(ok=False)
                return False, f"{name}: shape {a.shape} != {b.shape}"
            itemsize = a.dtype.numpy_dtype.itemsize
            whole = tuple((0, s) for s in a.shape)
            for cbox in chunk_boxes(whole, itemsize, budget):
                sl = _slices(cbox)
                ba = a[sl].read().result().reshape(-1).view(np.uint8)
                bb = b[sl].read().result().reshape(-1).view(np.uint8)
                if not np.array_equal(ba, bb):
                    observe.counter("tdx.reshard.verify_fail").inc()
                    observe.instant(
                        "reshard.verify_fail", category="reshard",
                        leaf=name, box=str(cbox),
                    )
                    sp.set(ok=False)
                    return False, f"{name}: bitwise mismatch in box {cbox}"
        sp.set(ok=True, leaves=len(src_names))
    return True, "ok"


# ---------------------------------------------------------------------------
# online path: stream a checkpoint directly into a differently-sharded state


def needs_reshard(path, target: Any) -> bool:
    """Does ``path``'s recorded topology differ from the layout of the
    live ``target`` pytree?  ``False`` for manifests without a topology
    block (pre-round-13 checkpoints keep the legacy restore path)."""
    manifest = read_manifest(path)
    topo = (manifest or {}).get("topology")
    if not topo:
        return False
    cur = state_topology(target)
    if cur is None:
        return False
    return (
        topo.get("mesh_axes") != cur["mesh_axes"]
        or topo.get("specs") != cur["specs"]
    )


def restore_resharded(
    src_dir,
    target: Any,
    *,
    chunk_mb: Optional[float] = None,
    chaos_plan=None,
    verify: bool = True,
) -> Any:
    """Stream a committed checkpoint directly into ``target``'s layout —
    the in-flight elastic path when a relaunch lands on a different mesh.

    Small leaves (≤ the chunk budget) ride
    :func:`~..transport.batched_device_put` — one dispatch per
    distinct target sharding; larger leaves are assembled shard-by-shard
    on device from budget-bounded slab reads, so no host ever stages a
    full unsharded leaf.  ``verify=True`` re-reads the source and
    bitwise-compares every leaf against the assembled arrays before
    returning (transfer-path corruption — including injected ``reshard``
    chaos — surfaces as :class:`ReshardError`, never as silently wrong
    training state)."""
    global _last_tracker
    import jax

    src = Path(src_dir).absolute()
    if not is_committed(src):
        raise ReshardError(f"{src}: not a committed checkpoint")
    budget = _budget_bytes(chunk_mb)
    tracker = _MemTracker()
    _last_tracker = tracker
    pump = _ChunkPump(tracker, chaos_plan)
    flat, treedef = jax.tree_util.tree_flatten_with_path(target)
    kv = _kvstore(src)
    on_disk = set(_leaf_names(kv))

    out: List[Any] = []
    small: List[tuple] = []  # (slot, buf, sharding, nbytes)
    small_bytes = 0

    def flush_small() -> None:
        nonlocal small, small_bytes
        if not small:
            return
        from .. import transport  # lazy: it imports jax, as this module does

        values, _n = transport.batched_device_put(
            [b for _slot, b, _sh, _nb in small],
            [sh for _slot, _b, sh, _nb in small],
        )
        for (slot, _b, _sh, nb), v in zip(small, values):
            out[slot] = v
            tracker.free(nb)
        small, small_bytes = [], 0

    try:
        with observe.span(
            "reshard.transfer", category="reshard",
            src=str(src), mode="online",
        ) as sp:
            for keypath, leaf in flat:
                if not hasattr(leaf, "shape"):
                    out.append(leaf)
                    continue
                name = leaf_storage_name(keypath)
                if name not in on_disk:
                    raise ReshardError(f"{src}: leaf {name!r} not stored")
                src_arr = _open_leaf(src, name)
                if tuple(src_arr.shape) != tuple(leaf.shape):
                    raise ReshardError(
                        f"{name}: stored shape {tuple(src_arr.shape)} != "
                        f"target shape {tuple(leaf.shape)}"
                    )
                dt = src_arr.dtype.numpy_dtype
                if dt != np_dtype(str(leaf.dtype)):
                    raise ReshardError(
                        f"{name}: stored dtype {dt} != target {leaf.dtype}"
                    )
                sharding = getattr(leaf, "sharding", None)
                nbytes = dt.itemsize * int(np.prod(leaf.shape or (1,)))
                if nbytes <= budget or sharding is None:
                    whole = tuple((0, s) for s in leaf.shape)
                    buf = pump.read(src_arr, whole, dt.itemsize)
                    if sharding is None:
                        out.append(jax.numpy.asarray(buf))
                        pump.release(whole, dt.itemsize)
                    else:
                        small.append((len(out), buf, sharding, nbytes))
                        out.append(None)
                        small_bytes += nbytes
                        if small_bytes > budget:
                            flush_small()
                else:
                    out.append(_assemble_sharded(
                        jax, src_arr, leaf.shape, dt, sharding, budget, pump
                    ))
                observe.counter("tdx.reshard.leaves").inc()
            flush_small()
            sp.set(leaves=len(flat), chunks=pump.chunk_no,
                   bytes=pump.bytes_moved, peak_host_bytes=tracker.peak)
        restored = jax.tree_util.tree_unflatten(treedef, out)
        if verify:
            _verify_restored(jax, src, restored, budget, tracker)
        return restored
    except ReshardError:
        raise
    except Exception as e:
        raise ReshardError(f"online reshard from {src} failed: {e}") from e


def _assemble_sharded(jax, src_arr, shape, dt, sharding, budget, pump):
    """Build one sharded jax.Array from budget-bounded slab reads: each
    distinct shard box is read in chunks, device_put piece-by-piece, and
    concatenated ON DEVICE — host memory stays ≤ one chunk; replicas get
    device-to-device copies of the assembled block."""
    import jax.numpy as jnp

    itemsize = dt.itemsize
    groups: dict = {}
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        box = tuple(
            (sl.start or 0, sl.stop if sl.stop is not None else dim)
            for sl, dim in zip(idx, shape)
        ) if idx else ()
        groups.setdefault(box, []).append(dev)
    shards = []
    for box, devs in groups.items():
        block_bytes = _box_bytes(box, itemsize)
        extent0 = (box[0][1] - box[0][0]) if box else 1
        slab_ok = extent0 > 0 and (block_bytes // max(1, extent0)) <= budget
        if block_bytes <= budget:
            buf = pump.read(src_arr, box, itemsize)
            block = jax.device_put(buf, devs[0])
            del buf
            pump.release(box, itemsize)
        elif slab_ok:
            pieces = []
            for cbox in chunk_boxes(box, itemsize, budget):
                buf = pump.read(src_arr, cbox, itemsize)
                pieces.append(jax.device_put(buf, devs[0]))
                del buf
                pump.release(cbox, itemsize)
            block = pieces[0] if len(pieces) == 1 else jnp.concatenate(
                pieces, axis=0
            )
        else:
            # Pathological: even one leading-index slab exceeds the
            # budget — host-stage the block whole (tracked, so tests see
            # the excess; minimum transfer granularity).
            pump.tracker.alloc(block_bytes)
            buf = _staged_block(src_arr, box, dt, budget, pump)
            block = jax.device_put(buf, devs[0])
            del buf
            pump.tracker.free(block_bytes)
        for dev in devs:
            shards.append(
                block if dev == devs[0] else jax.device_put(block, dev)
            )
    return jax.make_array_from_single_device_arrays(
        tuple(shape), sharding, shards
    )


def _staged_block(src_arr, box, dt, budget, pump):
    """Host-stage one block bigger than any slab can bound (single
    leading index over budget): chunked reads into a preallocated
    buffer.  The caller accounts the block allocation."""
    buf = np.empty(tuple(hi - lo for lo, hi in box), dtype=dt)
    origin = tuple(lo for lo, _hi in box)
    for cbox in chunk_boxes(box, dt.itemsize, budget):
        piece = pump.read(src_arr, cbox, dt.itemsize)
        local = tuple(
            slice(lo - o, hi - o) for (lo, hi), o in zip(cbox, origin)
        )
        buf[local] = piece
        del piece
        pump.release(cbox, dt.itemsize)
    return buf


def _verify_restored(jax, src: Path, restored: Any, budget: int,
                     tracker: _MemTracker) -> None:
    """Bitwise-compare every restored array against a fresh chunked read
    of the source — the online degrade-never-corrupt gate."""
    with observe.span(
        "reshard.verify", category="reshard", src=str(src), mode="online"
    ) as sp:
        flat = jax.tree_util.tree_flatten_with_path(restored)[0]
        for keypath, leaf in flat:
            if not hasattr(leaf, "shape"):
                continue
            name = leaf_storage_name(keypath)
            src_arr = _open_leaf(src, name)
            itemsize = src_arr.dtype.numpy_dtype.itemsize
            whole = tuple((0, s) for s in leaf.shape)
            for cbox in chunk_boxes(whole, itemsize, budget):
                nbytes = 2 * _box_bytes(cbox, itemsize)
                tracker.alloc(nbytes)
                try:
                    want = src_arr[_slices(cbox)].read().result()
                    got = np.asarray(leaf[_slices(cbox)])
                    same = np.array_equal(
                        want.reshape(-1).view(np.uint8),
                        got.reshape(-1).view(np.uint8),
                    )
                finally:
                    tracker.free(nbytes)
                if not same:
                    observe.counter("tdx.reshard.verify_fail").inc()
                    observe.instant(
                        "reshard.verify_fail", category="reshard",
                        leaf=name, box=str(cbox), mode="online",
                    )
                    sp.set(ok=False)
                    raise ReshardError(
                        f"online reshard verify failed for leaf {name!r} "
                        f"(box {cbox}) — restored state discarded, source "
                        f"checkpoint untouched"
                    )
        sp.set(ok=True)
