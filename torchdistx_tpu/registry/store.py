"""Content-addressed compile-artifact store shared across a pod.

The persistent XLA compilation cache (``TDX_CACHE_DIR``) makes repeat
materializations on ONE host cheap; this store makes them cheap across a
FLEET: hosts publish the serialized executables they compile into a
shared directory (``TDX_REGISTRY_DIR`` — NFS, GCS-fuse, anything with
atomic rename), and every other host fetches, verifies, and installs
them into its local cache instead of re-deriving the same programs.
Cold pod bring-up goes from O(model × hosts) compiles to O(model /
hosts) (see docs/registry.md and the ROADMAP north star).

Key schema — an artifact is addressed by::

    registry_key = sha1(program_fp  ‖  env_key)

* ``program_fp`` (:func:`..jax_bridge.materialize._registry_program_fp`)
  is the cross-process-stable content fingerprint of one init program's
  recorded computation (``compile.group_fingerprint``) composed with its
  output contract (cast policy, planned ``NamedSharding``s) — everything
  the compiled executable depends on EXCEPT the runtime PRNG key, so one
  artifact serves every seed;
* ``env_key`` (:func:`env_key`) pins the compile environment: jax /
  jaxlib versions, backend platform + platform version, device kind and
  count, and the accepted init compiler options.  Two hosts produce the
  same registry key iff the executable one compiles is loadable and
  correct on the other.

Entry layout (one directory per key)::

    <root>/<key>/meta.json          # files manifest (name, bytes, crc32),
                                    # env fingerprint, jax cache keys
    <root>/<key>/<jaxkey>-cache     # payload: the bytes exactly as jax's
                                    # persistent cache stores them

Contract:

* **publish is atomic** — payload + manifest are written to a private
  tmp directory and ``rename``\\ d into place, so a reader either sees a
  complete entry or no entry; concurrent publishers of one key race on
  the rename and exactly one wins (the loser discards its tmp dir).
* **fetch is self-verifying** — every payload file is CRC32-checked
  against the manifest; any mismatch (bit rot, torn write, a damaged
  shared filesystem) QUARANTINES the entry (``<key>.corrupt``, kept for
  forensics like checkpoint/compile-cache quarantine) and reports a
  miss, so the caller degrades to a local compile — registry trouble is
  never an error, only lost savings.
* **install reuses jax's own loader path** — payload files land in the
  local ``TDX_CACHE_DIR`` under the exact names jax's persistent cache
  uses, so the very next ``lowered.compile()`` is an ordinary local
  cache hit (and the PR 5 corrupt-entry guard still backstops them).

Telemetry: ``tdx.registry.{publish,publish_races,publish_errors,
fetch_hit,fetch_miss,verify_fail,bytes_published,bytes_fetched,steals}``
counters and ``registry.publish`` / ``registry.fetch`` spans
(docs/observability.md).  Chaos: both operations run the ``registry``
fault site (kinds raise / slow / corrupt, keyed by the 1-based program
group number; see docs/robustness.md).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import socket
import threading
import time
import zlib
from typing import Dict, List, Optional

from .. import chaos, observe
from ..utils.logging import get_logger

__all__ = [
    "ArtifactRegistry",
    "env_fingerprint",
    "env_key",
    "registry_key",
]

_META = "meta.json"


def env_fingerprint() -> Dict[str, str]:
    """The compile-environment identity fields composed into every
    registry key.  Human-readable; stored verbatim in each entry's
    manifest so a mismatch is diagnosable, not just a different hash."""
    import jax

    info: Dict[str, str] = {"jax": jax.__version__}
    try:
        import jaxlib

        info["jaxlib"] = getattr(jaxlib, "__version__", "unknown")
    except Exception:  # pragma: no cover — jaxlib always ships with jax
        info["jaxlib"] = "unknown"
    info["platform"] = jax.default_backend()
    try:
        dev = jax.devices()[0]
        info["platform_version"] = str(dev.client.platform_version)
        info["device_kind"] = str(dev.device_kind)
    except Exception:
        info["platform_version"] = info["device_kind"] = "unknown"
    info["n_devices"] = str(jax.device_count())
    # The accepted init compiler options are part of the executable's
    # identity: an artifact compiled WITH xla_allow_excess_precision=False
    # must not serve a host whose backend rejected the knob.
    from ..compile_service import compiler_options  # lazy: it imports us

    info["compiler_options"] = json.dumps(
        compiler_options() or {}, sort_keys=True
    )
    return info


_env_key_lock = threading.Lock()
_env_key_cache: Optional[str] = None
_env_fp_cache: Optional[Dict[str, str]] = None


def _env_fingerprint_cached() -> Dict[str, str]:
    """Memoized :func:`env_fingerprint` (the backend cannot change
    mid-process; per-publish recomputation would re-probe jax for an
    identical dict)."""
    global _env_fp_cache
    with _env_key_lock:
        if _env_fp_cache is None:
            _env_fp_cache = env_fingerprint()
        return _env_fp_cache


def env_key() -> str:
    """sha1 digest of :func:`env_fingerprint`, memoized per process (the
    backend cannot change mid-process)."""
    global _env_key_cache
    with _env_key_lock:
        if _env_key_cache is None:
            h = hashlib.sha1(b"tdx-registry-env-v1")
            for k, v in sorted(env_fingerprint().items()):
                h.update(f"{k}={v}\n".encode())
            _env_key_cache = h.hexdigest()
        return _env_key_cache


def _reset_env_key() -> None:
    """Drop the memoized env key (tests that monkeypatch identity fields)."""
    global _env_key_cache, _env_fp_cache
    with _env_key_lock:
        _env_key_cache = None
        _env_fp_cache = None


def registry_key(program_fp: str) -> str:
    """The content address of one init program's artifact in this
    environment: ``sha1(program_fp ‖ env_key)``."""
    h = hashlib.sha1(b"tdx-registry-key-v1")
    h.update(program_fp.encode())
    h.update(env_key().encode())
    return h.hexdigest()


def _safe_name(name: str) -> bool:
    """Whether a manifest-listed payload filename is safe to create under
    a cache directory (no separators, no dot-prefixed specials)."""
    return (
        bool(name)
        and "/" not in name
        and os.sep not in name
        and (os.altsep is None or os.altsep not in name)
        and not name.startswith(".")
        and name != _META
    )


class _VerifyError(ValueError):
    """A fetched entry failed self-verification (CRC/size/manifest)."""


class ArtifactRegistry:
    """One shared registry directory.  Stateless — cheap to construct per
    operation; all durable state lives on the filesystem."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)

    # -- addressing --------------------------------------------------------

    def entry_dir(self, key: str) -> str:
        return os.path.join(self.root, key)

    def has(self, key: str) -> bool:
        """Whether a COMPLETE entry exists (publish renames the manifest
        into place with the payload, so manifest presence ⇒ complete)."""
        try:
            return os.path.isfile(os.path.join(self.entry_dir(key), _META))
        except OSError:
            return False

    def read_meta(self, key: str) -> Optional[dict]:
        """The entry's manifest, or None when absent/unreadable (never
        raises — a flaky shared filesystem degrades to a miss)."""
        try:
            with open(os.path.join(self.entry_dir(key), _META)) as f:
                doc = json.load(f)
            return doc if isinstance(doc, dict) else None
        except (OSError, ValueError):
            return None

    # -- publish -----------------------------------------------------------

    def publish(self, key: str, files: Dict[str, bytes],
                meta: Optional[dict] = None, *, gno: int = 1,
                plan=None) -> bool:
        """Atomically publish one artifact; True iff THIS call created the
        entry.  Losing a concurrent-publish race, an already-present
        entry, and any filesystem error all return False — publishing is
        an amenity, never a failure of the caller's materialization."""
        with observe.span(
            "registry.publish", category="registry", key=key[:12]
        ) as sp:
            try:
                chaos.maybe_inject("registry", gno, path=self.root, plan=plan)
                if self.has(key):
                    sp.set(outcome="present")
                    return False
                os.makedirs(self.root, exist_ok=True)
                tmp = os.path.join(
                    self.root,
                    f".tmp-pub-{key[:16]}-{os.getpid()}-{threading.get_ident()}",
                )
                n_bytes = 0
                try:
                    os.makedirs(tmp)
                    recs: List[dict] = []
                    for name, data in files.items():
                        if not _safe_name(name):
                            raise ValueError(f"unsafe payload name {name!r}")
                        with open(os.path.join(tmp, name), "wb") as f:
                            f.write(data)
                        recs.append({"name": name, "bytes": len(data),
                                     "crc32": zlib.crc32(data)})
                        n_bytes += len(data)
                    doc = {
                        "version": 1, "key": key, "files": recs,
                        "created": time.time(),
                        "host": socket.gethostname(), "pid": os.getpid(),
                        **(meta or {}),
                    }
                    with open(os.path.join(tmp, _META), "w") as f:
                        json.dump(doc, f)
                    # The atomic commit: a reader sees the whole entry or
                    # nothing.  Renaming onto an existing non-empty dir
                    # fails — exactly one concurrent publisher wins.
                    os.rename(tmp, self.entry_dir(key))
                except Exception as e:  # noqa: BLE001 — tmp must not leak
                    # ANY failure (fs error, unsafe name, unserializable
                    # meta) removes the private tmp dir: the shared
                    # registry has no GC, so leaked partials would
                    # accumulate fleet-wide.
                    shutil.rmtree(tmp, ignore_errors=True)
                    if isinstance(e, OSError) and self.has(key):
                        # lost the rename race: the winner's entry is up
                        observe.counter("tdx.registry.publish_races").inc()
                        sp.set(outcome="lost_race")
                        return False
                    raise
                observe.counter("tdx.registry.publish").inc()
                observe.counter("tdx.registry.bytes_published").inc(n_bytes)
                sp.set(outcome="published", bytes=n_bytes)
                return True
            except Exception as e:  # noqa: BLE001 — degrade, never fail the caller
                observe.counter("tdx.registry.publish_errors").inc()
                get_logger().warning(
                    "registry: publish of %s failed (%s: %s); continuing "
                    "without publishing", key[:12], type(e).__name__,
                    str(e)[:120],
                )
                sp.set(outcome="error")
                return False

    def publish_from_cache(self, key: str, cache_dir: str,
                           cache_keys: List[str], *, gno: int = 1,
                           plan=None, meta: Optional[dict] = None) -> bool:
        """Publish the local persistent-cache entries for ``cache_keys``
        (the jax cache keys one compile touched) under ``key``.  Entries
        jax declined to persist (below its min-compile-time / min-size
        thresholds) simply aren't there — nothing is published and the
        caller loses nothing."""
        if self.has(key):
            return False
        files: Dict[str, bytes] = {}
        for ck in cache_keys:
            # jax's LRUCache stores `<key>-cache`; other CacheInterface
            # impls store the bare key — tolerate both, exactly like the
            # PR 5 quarantine helper (compile_service._quarantine_cache_entry).
            for name in (f"{ck}-cache", ck):
                try:
                    with open(os.path.join(cache_dir, name), "rb") as f:
                        files[name] = f.read()
                    break
                except OSError:
                    continue
            else:
                get_logger().debug(
                    "registry: no local cache entry for %s to publish "
                    "(below jax's persist threshold?)", ck,
                )
        if not files:
            return False
        doc = dict(meta or {})
        doc["jax_cache_keys"] = list(cache_keys)
        doc.setdefault("env", _env_fingerprint_cached())
        return self.publish(key, files, doc, gno=gno, plan=plan)

    # -- fetch -------------------------------------------------------------

    def fetch(self, key: str, *, gno: int = 1, plan=None
              ) -> Optional[Dict[str, bytes]]:
        """Payload bytes by filename, CRC32-verified against the manifest.

        ``None`` is a miss: absent entry, unreadable shared filesystem
        (degrade — the entry may be fine), or FAILED VERIFICATION (the
        entry is quarantined to ``<key>.corrupt`` and counted in
        ``tdx.registry.verify_fail``).  The caller compiles locally."""
        with observe.span(
            "registry.fetch", category="registry", key=key[:12]
        ) as sp:
            try:
                chaos.maybe_inject("registry", gno, path=self.root, plan=plan)
                meta_path = os.path.join(self.entry_dir(key), _META)
                if not os.path.isfile(meta_path):
                    observe.counter("tdx.registry.fetch_miss").inc()
                    sp.set(outcome="miss")
                    return None
            except Exception as e:  # noqa: BLE001 — flaky shared fs: a miss
                observe.counter("tdx.registry.fetch_miss").inc()
                get_logger().warning(
                    "registry: fetch of %s failed (%s: %s); compiling "
                    "locally", key[:12], type(e).__name__, str(e)[:120],
                )
                sp.set(outcome="error")
                return None
            try:
                out, n_bytes = self._read_verified(key, meta_path)
            except (_VerifyError, ValueError, KeyError, TypeError) as e:
                # The entry itself is bad (torn manifest, CRC mismatch,
                # unsafe names): quarantine so no later process trips
                # over it, then degrade to a miss.
                moved = self.quarantine(key)
                observe.counter("tdx.registry.verify_fail").inc()
                observe.counter("tdx.registry.fetch_miss").inc()
                observe.instant(
                    "registry.verify_fail", category="registry",
                    key=key[:12], error=f"{type(e).__name__}: {e}"[:200],
                )
                get_logger().warning(
                    "registry: entry %s failed verification (%s: %s); "
                    "quarantined to %s and compiling locally",
                    key[:12], type(e).__name__, str(e)[:120],
                    moved or "(already gone)",
                )
                sp.set(outcome="verify_fail")
                return None
            except OSError as e:
                # Read error mid-fetch: could be the filesystem, not the
                # entry — miss WITHOUT quarantine.
                observe.counter("tdx.registry.fetch_miss").inc()
                get_logger().warning(
                    "registry: fetch of %s failed (%s: %s); compiling "
                    "locally", key[:12], type(e).__name__, str(e)[:120],
                )
                sp.set(outcome="error")
                return None
            observe.counter("tdx.registry.fetch_hit").inc()
            observe.counter("tdx.registry.bytes_fetched").inc(n_bytes)
            sp.set(outcome="hit", bytes=n_bytes)
            return out

    @staticmethod
    def _verified_files(base_dir: str, recs) -> Dict[str, bytes]:
        """Read the manifest-listed payload files from ``base_dir``,
        enforcing safe names and CRC32/size — THE verification rule,
        shared by the registry read and the local fast path so the two
        checks can never drift.  Raises :class:`_VerifyError` on any
        mismatch (IO errors propagate as OSError)."""
        if not isinstance(recs, list) or not recs:
            raise _VerifyError("manifest lists no payload files")
        out: Dict[str, bytes] = {}
        for rec in recs:
            name = rec["name"]
            if not _safe_name(name):
                raise _VerifyError(f"unsafe payload name {name!r}")
            with open(os.path.join(base_dir, name), "rb") as f:
                data = f.read()
            if len(data) != rec["bytes"] or zlib.crc32(data) != rec["crc32"]:
                raise _VerifyError(f"payload {name} failed CRC32/size check")
            out[name] = data
        return out

    def _read_verified(self, key: str, meta_path: str):
        with open(meta_path) as f:
            doc = json.load(f)
        out = self._verified_files(self.entry_dir(key), doc["files"])
        return out, sum(len(d) for d in out.values())

    def fetch_for_compile(self, key: str, cache_dir: str, *, gno: int = 1,
                          plan=None) -> Optional[Dict[str, bytes]]:
        """Fetch → verify → install for one program compile; returns the
        payload bytes (or None on a registry miss).

        The payload is BOTH installed into the local persistent cache
        under its published jax cache-key names (the common case: the
        consumer computes the same key and plain-hits) AND returned to
        the caller, which hands it to the compile via a thread-local so
        the cache-load wrapper can serve the executable DIRECTLY when
        this process computes a different jax cache key — jax's key is
        not perfectly stable across traces/processes, while the
        registry's content address is, and the content address is what
        decides correctness here.  Already-installed entries
        short-circuit by reading the local copies (no registry traffic,
        no fetch counters)."""
        meta = self.read_meta(key)
        if meta is not None:
            # Fast path: every payload already installed locally — but
            # only if the local bytes pass the SAME verification rule
            # the registry read applies.  A stale or colliding local
            # file must fall through to the verified registry copy,
            # never masquerade as this program.
            try:
                return self._verified_files(cache_dir, meta["files"])
            except (OSError, _VerifyError, ValueError, KeyError, TypeError):
                pass
        files = self.fetch(key, gno=gno, plan=plan)
        if files is None:
            return None
        try:
            # jax only creates its cache dir lazily at the first WRITE;
            # an install that precedes every compile must not depend on
            # that.
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            self._warn_install(cache_dir, e)
            return files  # direct-serve still possible
        for name, data in files.items():
            # Unconditional atomic replace: reaching this loop means the
            # fast path found the local copy absent OR mismatching the
            # manifest — leaving a divergent local file in place would
            # force a full registry re-fetch on every later
            # materialization.  Concurrent installers write the same
            # verified bytes; os.replace keeps readers torn-free.
            dst = os.path.join(cache_dir, name)
            tmp = f"{dst}.tdx-tmp-{os.getpid()}-{threading.get_ident()}"
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, dst)
            except OSError as e:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                self._warn_install(dst, e)
                break
        return files

    @staticmethod
    def _warn_install(path: str, e: OSError) -> None:
        get_logger().warning(
            "registry: installing into %s failed (%s: %s); the fetched "
            "artifact can still serve this compile directly", path,
            type(e).__name__, str(e)[:120],
        )

    def fetch_into_cache(self, key: str, cache_dir: str, *, gno: int = 1,
                         plan=None) -> bool:
        """Bool convenience over :meth:`fetch_for_compile`: True when the
        artifact was available (fetched or already installed)."""
        return self.fetch_for_compile(
            key, cache_dir, gno=gno, plan=plan
        ) is not None

    # -- hygiene -----------------------------------------------------------

    def quarantine(self, key: str) -> Optional[str]:
        """Move a bad entry aside (``<key>.corrupt``, kept for forensics);
        None when it already vanished or a prior quarantine holds the
        name (the bad dir is then just removed)."""
        edir = self.entry_dir(key)
        dst = edir + ".corrupt"
        try:
            if os.path.isdir(dst):
                shutil.rmtree(edir, ignore_errors=True)
                return None
            os.replace(edir, dst)
            return dst
        except OSError:
            return None

    def keys(self) -> List[str]:
        """All complete entry keys currently in the registry."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(n for n in names
                      if not n.startswith(".") and not n.endswith(".corrupt")
                      and self.has(n))
