"""``PersistentExecutableCache``: a disk tier under ``ExecutableCache``.

The paper's whole premise is that re-running synthesis for every design
iteration is the bottleneck; our analog is XLA compilation, and a
gateway restart used to replay the entire compile storm.  This cache
serializes each AOT executable (``jax.experimental.serialize_executable``)
to disk the first time it is compiled and deserializes it on the next
process's first request — a warm restart *loads* instead of compiling:

    cache = PersistentExecutableCache("state/exec-cache")
    model = runtime.compile_plan(plan, params=params, exec_cache=cache)
    # first process: compiles, stores .exe files
    # after restart: zero compiles — every bucket deserialized

Keying and safety:

* Entries are keyed on the existing content-addressed layer keys (the
  ``(layer spec, bucket)`` tuples backends already use) **plus a
  fingerprint** of (cache format, jax version, backend, device
  topology).  An artifact produced by a different jax build or device
  layout never deserializes into this process — a fingerprint mismatch
  is treated as a miss and the slot is overwritten with a fresh
  compile.
* Stale/corrupt/unreadable entries **silently fall back to a live
  compile**: a corrupt file is renamed to ``*.corrupt`` and an entry
  whose embedded fingerprint drifted from the current environment (a
  jax upgrade or topology change under an unchanged path — possible
  when a shared dir outlives a deploy) is renamed to ``*.stale``; both
  are quarantined for inspection, never deserialized, and serving
  proceeds exactly as with a cold cache.
  Persistence failures on the write side are likewise swallowed — the
  disk tier is an accelerator, never a point of failure.
* Writes are atomic (tmp + fsync + ``os.replace``), so two processes
  sharing one cache directory can race without torn files.

Executables that are not jax ``Compiled`` objects (some backends cache
plain callables) are skipped — they compile live, as before.

JAX's own persistent compilation cache is chosen here too, and only
here: a launcher turns it on with ``enable_jax_compilation_cache``, and
building this tier turns it off for the whole process
(``disable_jax_compilation_cache``) — the tier must serialize fresh
compiles, never executables JAX loaded from its own cache.
"""

from __future__ import annotations

import hashlib
import os
import pickle
from pathlib import Path
from typing import Callable, Optional, Tuple, Union

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.runtime.compiled import ExecutableCache
from repro.runtime.plan_io import _fsync_dir

#: JAX's compilation cache when ``$JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed directory of the checkout (the path is part of the cache key)
CHECKOUT_JAX_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_jax_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return where it
    lives.  ``$JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX,
    which reads it itself; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_JAX_CACHE))
    return str(CHECKOUT_JAX_CACHE)


def disable_jax_compilation_cache() -> None:
    """Turn JAX's persistent compilation cache off for the rest of the
    process (JAX decides once per process whether it uses the cache, so
    it cannot be turned off for one compile).  An executable JAX loaded
    from its cache serializes without its code: XLA:CPU fails when the
    restored copy runs ("Function ... not found")."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

__all__ = ["PersistentExecutableCache", "cache_fingerprint",
           "CACHE_FORMAT_VERSION"]

CACHE_FORMAT_VERSION = 1


def cache_fingerprint() -> tuple:
    """Identity of the compile environment a serialized executable is
    only valid for: cache format, jax version, backend, topology."""
    devs = jax.devices()
    kinds = sorted({(d.platform, getattr(d, "device_kind", "?"))
                    for d in devs})
    return (CACHE_FORMAT_VERSION, jax.__version__, jax.default_backend(),
            len(devs), tuple(kinds))


def _stable_token(obj) -> object:
    """Reduce a cache-key element to something ``repr``-stable across
    processes.  Primitives pass through; tuples recurse; a ``Mesh``
    (identity-hashed, so its repr varies per process) is replaced by
    its shape and device names; anything else falls back to repr."""
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, tuple):
        return tuple(_stable_token(o) for o in obj)
    if isinstance(obj, jax.sharding.Mesh):
        return ("mesh", tuple(obj.shape.items()),
                tuple(str(d) for d in obj.devices.flat))
    return ("repr", repr(obj))


class PersistentExecutableCache(ExecutableCache):
    """Disk-backed ``ExecutableCache`` (see module docstring).

    Inherits single-flight semantics: a key being loaded/compiled by
    one thread is waited on by the others.  ``stats()`` gains
    ``disk_hits`` / ``disk_stores`` / ``disk_errors``.

    Process-wide effect: building one turns JAX's own persistent
    compilation cache off for every later compile of the process
    (``disable_jax_compilation_cache``); this tier replaces it.
    """

    def __init__(self, cache_dir: Union[str, Path], *,
                 on_event: Optional[Callable[[str, dict], None]] = None):
        super().__init__(on_event=on_event)
        disable_jax_compilation_cache()
        self.cache_dir = Path(cache_dir)
        self.cache_dir.mkdir(parents=True, exist_ok=True)
        self.fingerprint = cache_fingerprint()
        self.disk_hits = 0     # executables deserialized instead of compiled
        self.disk_stores = 0   # executables serialized to disk
        self.disk_errors = 0   # corrupt/unwritable entries fallen back from
        self.disk_stale = 0    # fingerprint-drift entries quarantined

    # -- key → file --------------------------------------------------

    def _entry_path(self, key: tuple) -> Path:
        token = repr((self.fingerprint, _stable_token(key)))
        digest = hashlib.sha256(token.encode("utf-8")).hexdigest()
        return self.cache_dir / f"{digest[:32]}.exe"

    # -- disk read ---------------------------------------------------

    def _load_entry(self, key: tuple):
        """Deserialize the on-disk executable for ``key``; None on any
        miss (absent, wrong fingerprint, corrupt — corrupt files are
        quarantined as ``*.corrupt``)."""
        path = self._entry_path(key)
        try:
            blob = path.read_bytes()
        except FileNotFoundError:
            return None
        except OSError:
            with self._lock:
                self.disk_errors += 1
            return None
        try:
            entry = pickle.loads(blob)
            if entry["fingerprint"] != self.fingerprint:
                # drifted build/topology under an unchanged path:
                # quarantine, never deserialize, recompile fresh
                with self._lock:
                    self.disk_stale += 1
                try:
                    os.replace(path, path.with_suffix(".stale"))
                except OSError:
                    pass
                self._emit("cache_disk_stale", path=str(path))
                return None
            from jax.experimental.serialize_executable import (
                deserialize_and_load)
            return deserialize_and_load(entry["payload"],
                                        entry["in_tree"],
                                        entry["out_tree"])
        except Exception:
            with self._lock:
                self.disk_errors += 1
            try:
                os.replace(path, path.with_suffix(".corrupt"))
            except OSError:
                pass
            self._emit("cache_disk_fallback", path=str(path))
            return None

    # -- disk write --------------------------------------------------

    def _store_entry(self, key: tuple, exe) -> None:
        """Best-effort atomic persist; failures never surface."""
        try:
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(exe)
            blob = pickle.dumps({
                "format": CACHE_FORMAT_VERSION,
                "fingerprint": self.fingerprint,
                "key": repr(_stable_token(key)),
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            path = self._entry_path(key)
            tmp = path.with_name(f".{path.name}.{os.getpid()}.tmp")
            with open(tmp, "wb") as fh:
                fh.write(blob)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, path)
            _fsync_dir(path.parent)
        except Exception:
            with self._lock:
                self.disk_errors += 1
            self._emit("cache_disk_store_failed", key=repr(key)[:160])
            return
        with self._lock:
            self.disk_stores += 1
        self._emit("cache_disk_store", path=str(path), bytes=len(blob))

    # -- the ExecutableCache production seam -------------------------

    def _produce(self, key: tuple, build: Callable[[], object]
                 ) -> Tuple[object, bool]:
        exe = self._load_entry(key)
        if exe is not None:
            with self._lock:
                self.disk_hits += 1
            self._emit("cache_disk_hit", key=repr(key)[:160])
            return exe, False
        exe, compiled = super()._produce(key, build)
        # only jax Compiled objects serialize; plain callables skip disk
        if hasattr(exe, "as_text") or type(exe).__name__ == "Compiled":
            self._store_entry(key, exe)
        return exe, compiled

    def stats(self) -> dict:
        out = super().stats()
        out.update({"disk_hits": self.disk_hits,
                    "disk_stores": self.disk_stores,
                    "disk_errors": self.disk_errors,
                    "disk_stale": self.disk_stale})
        return out
