"""``repro.ops.PersistentExecutableCache`` + the single-flight
``ExecutableCache``: warm restarts deserialize instead of compiling,
stale/corrupt entries fall back silently, and concurrent builders of
one key coalesce into a single compile."""

import pickle
import threading

import numpy as np
import pytest

from repro.core import deploy
from repro.core.cnn import CNNConfig, ConvLayerSpec, fitted_block_models
from repro.ops import (CACHE_FORMAT_VERSION, PersistentExecutableCache,
                       cache_fingerprint)
from repro.runtime import CompiledCNN, ExecutableCache


def _cfg():
    return CNNConfig(layers=(
        ConvLayerSpec(1, 4, data_bits=8, coeff_bits=6, block="conv4"),
        ConvLayerSpec(4, 3, data_bits=6, coeff_bits=4, block="conv3"),
    ), img_h=16, img_w=64)


@pytest.fixture(scope="module")
def plan():
    return deploy.plan_deployment(_cfg(), fitted_block_models(),
                                  target=0.8, on_infeasible="fallback")


# ---------------------------------------------------------------------------
# single-flight compilation (in-memory tier)
# ---------------------------------------------------------------------------

def test_single_flight_counting_build():
    """N threads racing one missing key must call the build fn once;
    the losers wait and reuse (``coalesced`` counts them).  The build
    is held open until every loser is provably parked in the wait, so
    the coalescing path is exercised deterministically."""
    import time

    cache = ExecutableCache()
    calls = []
    building = threading.Event()
    release = threading.Event()

    def build():
        calls.append(1)                # only the winner runs this
        building.set()
        release.wait(timeout=10)
        return "the-executable"

    results = []

    def racer():
        results.append(cache.get_or_build(("k",), build))

    winner = threading.Thread(target=racer)
    winner.start()
    assert building.wait(timeout=10)   # the key is now claimed
    losers = [threading.Thread(target=racer) for _ in range(4)]
    for t in losers:
        t.start()
    deadline = time.monotonic() + 10   # all four must reach the wait
    while cache.stats()["coalesced"] < 4:
        assert time.monotonic() < deadline, "losers never coalesced"
        time.sleep(0.005)
    release.set()                      # let the winning build finish
    for t in [winner] + losers:
        t.join(timeout=10)
    assert results == ["the-executable"] * 5
    assert len(calls) == 1
    s = cache.stats()
    assert s["compiles"] == 1 and s["coalesced"] >= 4


def test_single_flight_failed_build_releases_waiters():
    """A failing producer must not wedge the key: waiters retry and one
    of them becomes the next builder."""
    cache = ExecutableCache()
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) == 1:
            raise RuntimeError("first build dies")
        return "ok"

    with pytest.raises(RuntimeError, match="first build dies"):
        cache.get_or_build(("k",), flaky)
    assert cache.get_or_build(("k",), flaky) == "ok"
    assert len(attempts) == 2 and ("k",) in cache


def test_cache_on_event_observer():
    cache = ExecutableCache()
    seen = []
    cache.on_event = lambda ev, fields: seen.append((ev, fields))
    cache.get_or_build(("k",), lambda: "x")
    assert [e for e, _ in seen] == ["cache_compile"]
    assert seen[0][1]["seconds"] >= 0
    # observer exceptions never reach the caller
    cache.on_event = lambda ev, fields: 1 / 0
    assert cache.get_or_build(("k2",), lambda: "y") == "y"


# ---------------------------------------------------------------------------
# persistent tier: warm restart skips the compiler
# ---------------------------------------------------------------------------

def test_warm_restart_zero_recompiles(tmp_path, plan):
    cold_cache = PersistentExecutableCache(tmp_path)
    cold = CompiledCNN.from_plan(plan, _cfg(), max_batch=2,
                                 exec_cache=cold_cache)
    assert cold.compiles > 0
    assert cold_cache.stats()["disk_stores"] == cold.compiles
    assert cold_cache.stats()["disk_hits"] == 0

    warm_cache = PersistentExecutableCache(tmp_path)  # "new process"
    warm = CompiledCNN.from_plan(plan, _cfg(), max_batch=2,
                                 exec_cache=warm_cache)
    assert warm.compiles == 0          # the acceptance headline
    s = warm_cache.stats()
    assert s["compiles"] == 0
    assert s["disk_hits"] == cold_cache.stats()["disk_stores"]
    assert warm.warmed_up

    x = np.stack([np.asarray(i, cold.in_dtype)
                  for i in cold.sample_inputs(2, seed=3)])
    np.testing.assert_array_equal(np.asarray(cold(x)), np.asarray(warm(x)))


@pytest.fixture
def warm_jax_cache(tmp_path):
    """JAX's own persistent compilation cache on (every compile cached),
    restored afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    for name, value in zip(names, (True, str(tmp_path / "jax"), 0, 0)):
        jax.config.update(name, value)
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_warm_restart_under_a_warm_jax_cache(tmp_path, plan,
                                             warm_jax_cache):
    """With JAX's persistent cache on and already holding every layer,
    the disk tier still stores executables that run after a restart: it
    turns JAX's cache off, so it serializes fresh compiles and never
    copies JAX loaded from its cache (which XLA:CPU cannot run)."""
    CompiledCNN.from_plan(plan, _cfg(), max_batch=2)   # fills JAX's cache
    cold = CompiledCNN.from_plan(
        plan, _cfg(), max_batch=2,
        exec_cache=PersistentExecutableCache(tmp_path / "exe"))
    warm = CompiledCNN.from_plan(
        plan, _cfg(), max_batch=2,
        exec_cache=PersistentExecutableCache(tmp_path / "exe"))
    assert cold.compiles > 0 and warm.compiles == 0
    x = np.stack([np.asarray(i, cold.in_dtype)
                  for i in cold.sample_inputs(2, seed=3)])
    np.testing.assert_array_equal(np.asarray(cold(x)), np.asarray(warm(x)))


def test_fingerprint_mismatch_falls_back_to_compile(tmp_path, plan):
    cold = PersistentExecutableCache(tmp_path)
    CompiledCNN.from_plan(plan, _cfg(), max_batch=1, exec_cache=cold)
    stored = cold.stats()["disk_stores"]
    assert stored > 0

    alien = PersistentExecutableCache(tmp_path)
    alien.fingerprint = ("other-jax", "other-backend")  # env changed
    CompiledCNN.from_plan(plan, _cfg(), max_batch=1, exec_cache=alien)
    s = alien.stats()
    assert s["disk_hits"] == 0         # mismatched entries ignored
    assert s["compiles"] > 0           # silent fallback to live compile


def test_fingerprint_drift_at_same_path_is_quarantined_not_loaded(
        tmp_path, plan):
    """Env-fingerprint drift under an *unchanged* entry path (a cache
    dir carried across builds whose key scheme coincided): the embedded
    fingerprint is the authority — the entry is quarantined as
    ``*.stale`` and recompiled; its payload is never deserialized (it
    is poisoned here, so any attempt would raise)."""
    cold = PersistentExecutableCache(tmp_path)
    CompiledCNN.from_plan(plan, _cfg(), max_batch=1, exec_cache=cold)
    entries = sorted(tmp_path.glob("*.exe"))
    assert entries
    for p in entries:
        entry = pickle.loads(p.read_bytes())
        entry["fingerprint"] = ("drifted-jax", "drifted-backend")
        entry["payload"] = b"not a serialized executable"
        p.write_bytes(pickle.dumps(entry))

    events = []
    warm = PersistentExecutableCache(tmp_path)
    warm.on_event = lambda ev, fields: events.append(ev)
    model = CompiledCNN.from_plan(plan, _cfg(), max_batch=1,
                                  exec_cache=warm)
    s = warm.stats()
    assert model.compiles > 0 and s["disk_hits"] == 0
    assert s["disk_stale"] == len(entries)
    assert "cache_disk_stale" in events
    stale = sorted(tmp_path.glob("*.stale"))
    assert len(stale) == len(entries)      # moved aside, not deleted
    assert pickle.loads(stale[0].read_bytes())["fingerprint"] \
        == ("drifted-jax", "drifted-backend")
    # the fallback compiles re-stored fresh entries at the live paths
    assert s["disk_stores"] == model.compiles


def test_corrupt_entry_quarantined_and_recompiled(tmp_path, plan):
    cold = PersistentExecutableCache(tmp_path)
    CompiledCNN.from_plan(plan, _cfg(), max_batch=1, exec_cache=cold)
    entries = sorted(tmp_path.glob("*.exe"))
    assert entries
    for p in entries:
        p.write_bytes(b"garbage that is not a pickle")

    events = []
    warm = PersistentExecutableCache(tmp_path)
    warm.on_event = lambda ev, fields: events.append(ev)
    warm_model = CompiledCNN.from_plan(plan, _cfg(), max_batch=1,
                                       exec_cache=warm)
    assert warm_model.compiles > 0     # fell back to live compiles
    assert warm.stats()["disk_errors"] > 0
    assert "cache_disk_fallback" in events
    assert list(tmp_path.glob("*.corrupt"))   # moved aside, not trusted
    # the fallback compiles re-stored fresh entries
    assert warm.stats()["disk_stores"] == warm_model.compiles


def test_disk_entry_format(tmp_path, plan):
    cache = PersistentExecutableCache(tmp_path)
    CompiledCNN.from_plan(plan, _cfg(), max_batch=1, exec_cache=cache)
    entry = pickle.loads(sorted(tmp_path.glob("*.exe"))[0].read_bytes())
    assert entry["format"] == CACHE_FORMAT_VERSION
    assert entry["fingerprint"] == cache_fingerprint()
    assert {"payload", "in_tree", "out_tree"} <= set(entry)


def test_non_jax_values_not_persisted(tmp_path):
    """Only real compiled executables go to disk — plain values built
    through the cache stay in the memory tier."""
    cache = PersistentExecutableCache(tmp_path)
    assert cache.get_or_build(("plain",), lambda: 42) == 42
    assert cache.stats()["disk_stores"] == 0
    assert not list(tmp_path.glob("*.exe"))


def test_shared_dir_across_plans_shares_layers(tmp_path, plan):
    """Content addressing: two *plans* whose layer identities coincide
    share disk entries — the second cache instance over the same dir
    deserializes them regardless of which plan stored them."""
    a = PersistentExecutableCache(tmp_path)
    CompiledCNN.from_plan(plan, _cfg(), max_batch=2, exec_cache=a)
    b = PersistentExecutableCache(tmp_path)
    model_b = CompiledCNN.from_plan(plan, _cfg(), max_batch=2,
                                    exec_cache=b)
    assert model_b.compiles == 0
    assert b.stats()["disk_hits"] > 0
