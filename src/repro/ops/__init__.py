"""``repro.ops`` — durable serving state and ops telemetry.

The serving stack (``repro.runtime`` → ``repro.serve`` → ``repro.fleet``)
is fast once warm, but a process restart used to forget everything:
every AOT executable recompiled, every registered plan re-planned.
This package makes that state durable and observable:

* ``PlanStore`` — crash-safe on-disk plan repository
  (save/load/retire/quarantine, atomic writes);
* ``PersistentExecutableCache`` — disk tier under
  ``runtime.ExecutableCache`` via JAX AOT executable serialization, so
  a warm restart deserializes instead of compiling;
* ``Tracker`` / ``JsonlTracker`` / ``StatsSampler`` — background-
  threaded telemetry that records lifecycle events and periodic
  ``stats()`` snapshots without ever blocking the serving path
  (``read_log`` parses a file back with its seal totals);
* ``StoreRoot`` — one shared plan-store + executable-cache location
  for a whole fleet, with per-worker lease files so a respawned
  worker warm-starts from its dead predecessor's compiles.

Live reload lives on the serving objects themselves
(``AsyncCNNGateway.register_plan``/``retire_plan``,
``Fleet.rollout``/``Fleet.retire_plan``); this package supplies the
durable state they read from and report into.  See ``docs/ops.md``.
"""

from repro.ops.cache import (CACHE_FORMAT_VERSION, PersistentExecutableCache,
                             cache_fingerprint, enable_jax_compilation_cache)
from repro.ops.root import Lease, LeaseHeld, StoreRoot
from repro.ops.store import (PlanCorrupt, PlanNotFound, PlanRetired,
                             PlanStore, PlanStoreError)
from repro.ops.tracker import (JsonlTracker, NullTracker, StatsSampler,
                               Tracker, TrackerLog, read_events, read_log)

__all__ = [
    "PlanStore", "PlanStoreError", "PlanNotFound", "PlanRetired",
    "PlanCorrupt",
    "PersistentExecutableCache", "cache_fingerprint",
    "CACHE_FORMAT_VERSION", "enable_jax_compilation_cache",
    "StoreRoot", "Lease", "LeaseHeld",
    "Tracker", "NullTracker", "JsonlTracker", "StatsSampler",
    "TrackerLog", "read_log", "read_events",
]
