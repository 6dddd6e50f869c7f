"""Async continuous-batching gateway: admission bound and deadline
invariants (property-tested on the synchronous scheduling core),
end-to-end bit-exactness, backpressure, cancellation, multi-plan
routing, and cross-plan executable sharing."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import deploy
from repro.core.cnn import (CNNConfig, ConvLayerSpec, cnn_forward_ref,
                            fitted_block_models, init_cnn)
from repro.runtime import CompiledCNN, DispatchAborted, ExecutableCache
from repro.serve import (AdmissionQueue, AsyncCNNGateway, AsyncRequest,
                         AsyncServeConfig, DeadlineExpired, GatewayBacklog,
                         get_policy)


def _cfg():
    return CNNConfig(layers=(
        ConvLayerSpec(1, 4, data_bits=8, coeff_bits=6, block="conv4"),
        ConvLayerSpec(4, 3, data_bits=6, coeff_bits=4, block="conv3"),
    ), img_h=16, img_w=64)


def _plan(cfg=None):
    cfg = cfg if cfg is not None else _cfg()
    return deploy.plan_deployment(cfg, fitted_block_models(), target=0.8,
                                  on_infeasible="fallback")


def _images(compiled, k, seed=0):
    return compiled.sample_inputs(k, seed)


def _req(i, *, plan_id="p", priority=0, deadline=None, now=0.0):
    return AsyncRequest(image=np.zeros(1), plan_id=plan_id, request_id=i,
                        priority=priority, deadline=deadline,
                        arrived_at=now)


# ---------------------------------------------------------------------------
# the synchronous scheduling core (no event loop)
# ---------------------------------------------------------------------------

def test_admission_queue_bound_and_rejection():
    q = AdmissionQueue(max_pending=3, policy="edf")
    assert all(q.admit(_req(i), 0.0) for i in range(3))
    assert q.full and len(q) == 3
    assert not q.admit(_req(3), 0.0)        # at the bound: refused
    _, batch = q.pop_batch(2, 0.0)
    assert [r.request_id for r in batch] == [0, 1]
    assert len(q) == 1 and not q.full
    assert q.admit(_req(4), 0.0)


def test_admission_queue_expires_instead_of_serving_late():
    q = AdmissionQueue(max_pending=8, policy="edf")
    on_time = _req(0, deadline=10.0)
    late = _req(1, deadline=2.0)
    assert q.admit(on_time, 0.0) and q.admit(late, 0.0)
    _, batch = q.pop_batch(8, now=5.0)      # past late's deadline
    assert [r.request_id for r in batch] == [0]
    assert late.status == "expired"
    assert isinstance(late.error, DeadlineExpired)
    assert q.expired == 1
    # already-expired on admission: terminal immediately, never queued
    dead = _req(2, deadline=1.0)
    assert q.admit(dead, now=5.0)           # handled, not refused
    assert dead.status == "expired" and len(q) == 0


def test_admission_queue_edf_order_and_priority_tiers():
    q = AdmissionQueue(max_pending=8, policy="edf")
    q.admit(_req(0, deadline=9.0), 0.0)
    q.admit(_req(1, deadline=3.0), 0.0)
    q.admit(_req(2), 0.0)                   # no deadline: last in tier
    q.admit(_req(3, deadline=99.0, priority=1), 0.0)   # higher tier
    _, batch = q.pop_batch(8, 0.0)
    assert [r.request_id for r in batch] == [3, 1, 0, 2]


def test_admission_queue_single_plan_batches_hold_others_back():
    q = AdmissionQueue(max_pending=8, policy="fifo")
    q.admit(_req(0, plan_id="a"), 0.0)
    q.admit(_req(1, plan_id="b"), 0.0)
    q.admit(_req(2, plan_id="a"), 0.0)
    pid, batch = q.pop_batch(8, 0.0)
    assert pid == "a" and [r.request_id for r in batch] == [0, 2]
    # plan b's request kept its place and forms the next batch
    pid, batch = q.pop_batch(8, 0.0)
    assert pid == "b" and [r.request_id for r in batch] == [1]
    assert len(q) == 0


def test_admission_queue_cancelled_entries_never_pop():
    q = AdmissionQueue(max_pending=4, policy="fifo")
    reqs = [_req(i) for i in range(3)]
    for r in reqs:
        q.admit(r, 0.0)
    assert reqs[1].cancel()
    q.note_terminal()                       # the gateway's cancel hook
    assert len(q) == 2
    _, batch = q.pop_batch(8, 0.0)
    assert [r.request_id for r in batch] == [0, 2]


if HAVE_HYPOTHESIS:
    _ops = st.lists(st.tuples(
        st.sampled_from(["submit", "pop", "tick", "cancel"]),
        st.integers(0, 7),                  # pop width / cancel index
        st.one_of(st.none(), st.floats(0.0, 4.0)),   # relative deadline
    ), min_size=1, max_size=60)
else:                                        # pragma: no cover
    _ops = None


@settings(max_examples=60, deadline=None)
@given(ops_list=_ops, bound=st.integers(1, 6))
def test_admission_bound_and_deadline_invariants(ops_list, bound):
    """Property: under any interleaving of submits, pops, clock ticks
    and cancels, (a) the live pending count never exceeds the bound,
    (b) a popped batch never contains an expired or cancelled request,
    and (c) every request ends served-able, expired, cancelled, or
    refused — never silently late."""
    q = AdmissionQueue(max_pending=bound, policy="edf")
    now = 0.0
    submitted, popped, refused = [], [], []
    for op, arg, dl in ops_list:
        if op == "submit":
            r = _req(len(submitted),
                     deadline=None if dl is None else now + dl, now=now)
            if q.admit(r, now):
                if r.status == "pending":
                    submitted.append(r)
            else:
                refused.append(r)
            assert len(q) <= bound
        elif op == "pop":
            _, batch = q.pop_batch(arg + 1, now)
            for r in batch:
                assert r.status == "pending"
                assert r.deadline is None or r.deadline >= now
                popped.append(r)
            assert len(q) <= bound
        elif op == "tick":
            now += 0.5 + (0.0 if dl is None else dl)
        elif op == "cancel":
            pending = [r for r in submitted
                       if r.status == "pending" and r not in popped]
            if pending:
                r = pending[arg % len(pending)]
                assert r.cancel()
                q.note_terminal()
        assert 0 <= len(q) <= bound
    # drain: nothing left behind in a non-terminal, non-poppable state
    _, batch = q.pop_batch(10 ** 6, now)
    popped.extend(batch)
    assert len(q) == 0
    for r in submitted:
        assert (r in popped and r.status == "pending") \
            or r.status in ("expired", "cancelled")
    for r in refused:
        assert r.status == "pending" and r not in popped


# ---------------------------------------------------------------------------
# adaptive admission: terminal-admit guard, shedding, resize, conservation
# ---------------------------------------------------------------------------

def test_admission_queue_refuses_terminal_requests():
    """Regression: a request that reached a terminal state before
    admission (e.g. its future was cancelled while ``submit`` awaited
    backpressure) must never be queued — pre-fix, ``admit`` pushed it
    and bumped the live count for an entry whose terminal hook had
    already run, leaking one slot of the bound per occurrence until
    the gateway refused all traffic."""
    q = AdmissionQueue(max_pending=2, policy="edf")
    r = _req(0)
    assert r.cancel()
    assert q.admit(r, 0.0)              # handled (already terminal)...
    assert len(q) == 0                  # ...but never queued
    _, batch = q.pop_batch(8, 0.0)
    assert batch == []
    # the full bound is still admissible afterwards
    assert q.admit(_req(1), 0.0) and q.admit(_req(2), 0.0)
    assert q.full and len(q) == 2


def test_admission_queue_shed_victim_and_probe():
    """Class-aware shedding: at the bound a higher-priority arrival
    ejects the least-urgent pending entry; a same-class arrival is
    refused (``outranked_by`` answers without building the request)."""
    q = AdmissionQueue(max_pending=2, policy="edf")
    lo0, lo1 = _req(0, priority=0), _req(1, priority=0)
    assert q.admit(lo0, 0.0) and q.admit(lo1, 0.0) and q.full
    # same class: nothing pending sheds below it
    assert not q.outranked_by(_req(2, priority=0), 0.0)
    assert q.shed_victim(_req(2, priority=0), 0.0) is None
    # higher class: the latest same-class arrival is the victim
    hi = _req(3, priority=9)
    assert q.outranked_by(hi, 0.0)
    victim = q.shed_victim(hi, 0.0)
    assert victim is lo1 and victim.status == "shed"
    assert isinstance(victim.error, GatewayBacklog)
    assert q.shed == 1 and len(q) == 1
    assert q.admit(hi, 0.0) and q.full
    # the cached shed ceiling stays correct across the removal: the
    # same-class fast path still refuses, the scan path still sheds
    assert not q.outranked_by(_req(4, priority=0), 0.0)
    assert q.outranked_by(_req(5, priority=10), 0.0)
    _, batch = q.pop_batch(8, 0.0)
    assert [r.request_id for r in batch] == [3, 0]


def test_admission_queue_resize_bound():
    q = AdmissionQueue(max_pending=4, policy="fifo")
    assert all(q.admit(_req(i), 0.0) for i in range(4))
    q.resize(2)                   # shrink below live: nothing evicted
    assert q.max_pending == 2 and len(q) == 4 and q.full
    assert not q.admit(_req(9), 0.0)
    _, batch = q.pop_batch(3, 0.0)
    assert len(batch) == 3
    assert q.admit(_req(4), 0.0) and q.full    # back under the bound
    q.resize(0)
    assert q.max_pending == 1                  # clamped: never zero
    # above a shrunk bound a higher class sheds no one: one victim
    # would leave the queue still over the bound
    edf = AdmissionQueue(max_pending=2, policy="edf")
    assert edf.admit(_req(0), 0.0) and edf.admit(_req(1), 0.0)
    edf.resize(1)
    assert edf.shed_victim(_req(2, priority=1), 0.0) is None
    assert edf.shed == 0 and len(edf) == 2


if HAVE_HYPOTHESIS:
    _conserve_ops = st.lists(st.tuples(
        st.sampled_from(["admit", "admit_terminal", "cancel", "pop",
                         "evict", "resize", "shed"]),
        st.integers(0, 7),
    ), min_size=1, max_size=80)
else:                                        # pragma: no cover
    _conserve_ops = None


@settings(max_examples=80, deadline=None)
@given(ops_list=_conserve_ops, bound=st.integers(1, 5))
def test_admission_live_count_conservation(ops_list, bound):
    """Property (the terminal-admit leak, generalized): across any
    interleaving of admissions — including already-terminal requests —
    cancellations, batch pops, drain evictions, bound resizes and
    class-aware sheds, the live count always equals the number of
    pending entries in the heap: the admission bound can neither leak
    shut nor over-admit, and a full drain restores the whole bound."""
    q = AdmissionQueue(max_pending=bound, policy="edf")
    n = 0
    hi_bound = bound                  # high-water admission bound seen
    for op, arg in ops_list:
        if op == "admit":
            q.admit(_req(n), 0.0)
            n += 1
        elif op == "admit_terminal":
            r = _req(n)
            n += 1
            assert r.cancel()
            assert q.admit(r, 0.0)      # handled, never queued
        elif op == "cancel":
            pending = [r for _, _, r in q._heap
                       if r.status == "pending"]
            if pending:
                assert pending[arg % len(pending)].cancel()
                q.note_terminal()       # the gateway's terminal hook
        elif op == "pop":
            q.pop_batch(arg + 1, 0.0)
        elif op == "evict":
            for r in q.evict_pending():
                # the gateway drain seam cancels each evicted request;
                # its terminal hook frees the admission slot
                assert r.cancel()
                q.note_terminal()
        elif op == "resize":
            q.resize(arg + 1)
            hi_bound = max(hi_bound, q.max_pending)
        elif op == "shed":
            r = _req(n, priority=arg)
            n += 1
            if not q.admit(r, 0.0):
                v = q.shed_victim(r, 0.0)
                if v is not None:
                    assert v.status == "shed"
                    assert q.admit(r, 0.0)
        live_in_heap = sum(1 for _, _, r in q._heap
                           if r.status == "pending")
        assert len(q) == live_in_heap
        assert 0 <= len(q) <= hi_bound
    q.resize(bound)
    q.pop_batch(10 ** 6, 0.0)
    assert len(q) == 0
    assert all(q.admit(_req(n + i), 0.0) for i in range(bound))
    assert q.full


# ---------------------------------------------------------------------------
# the asyncio gateway end-to-end
# ---------------------------------------------------------------------------

def test_gateway_serves_bit_exact():
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=4, max_pending=16))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 9)

    async def main():
        async with gw:
            futs = [await gw.submit(img) for img in imgs]
            return await asyncio.gather(*futs)

    outs = asyncio.run(main())
    pcfg = deploy.plan_config(plan)
    for img, out in zip(imgs, outs):
        ref = cnn_forward_ref(compiled.params, jnp.asarray(img), pcfg)
        np.testing.assert_array_equal(out, np.asarray(ref))
    stats = gw.stats()
    assert stats["served"] == 9 and stats["pending"] == 0
    assert sum(k * v for k, v in stats["occupancy_hist"].items()) == 9


def test_gateway_backpressure_and_load_shedding():
    """submit_nowait sheds load at the bound; submit awaits space and
    completes once the drain frees it."""
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=3))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 12, seed=3)

    async def main():
        async with gw:
            # stall the drain so the queue actually fills: submit from
            # inside one loop iteration without yielding
            futs, shed = [], 0
            for img in imgs:
                try:
                    futs.append(gw.submit_nowait(img))
                except GatewayBacklog:
                    shed += 1
            assert shed > 0                  # the bound engaged
            assert gw.stats()["pending"] <= 3
            # backpressure path: waits for space instead of raising
            futs.append(await gw.submit(imgs[0]))
            outs = await asyncio.gather(*futs)
            return outs, shed

    outs, shed = asyncio.run(main())
    stats = gw.stats()
    assert stats["rejected"] == shed
    assert stats["served"] == len(outs)
    assert len(outs) == 12 - shed + 1


def test_gateway_expired_requests_fail_not_served_late():
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=32))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 3, seed=4)

    async def main():
        async with gw:
            # deadline already in the past on admission
            dead = await gw.submit(imgs[0], deadline=-1.0)
            ok = await gw.submit(imgs[1], deadline=60.0)
            with pytest.raises(DeadlineExpired):
                await dead
            return await ok

    out = asyncio.run(main())
    ref = cnn_forward_ref(compiled.params, jnp.asarray(imgs[1]),
                          deploy.plan_config(plan))
    np.testing.assert_array_equal(out, np.asarray(ref))
    assert gw.stats()["expired"] == 1


def test_gateway_cancellation_releases_bound_and_skips_serve():
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=4))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 4, seed=5)

    async def main():
        async with gw:
            futs = [gw.submit_nowait(img) for img in imgs]
            futs[2].cancel()
            done = await asyncio.gather(*futs, return_exceptions=True)
            return done

    done = asyncio.run(main())
    assert isinstance(done[2], asyncio.CancelledError)
    assert [isinstance(d, np.ndarray) for d in done] \
        == [True, True, False, True]
    stats = gw.stats()
    assert stats["cancelled"] == 1 and stats["served"] == 3


def test_gateway_multi_plan_routing_and_shared_cache():
    """Two plans with identical layer specs share every compiled
    executable (the regression the shared ExecutableCache exists for);
    requests route to their plan and both serve bit-exactly."""
    plan = _plan()
    gw = AsyncCNNGateway(AsyncServeConfig(max_batch=4, max_pending=16))
    gw.register_plan(plan, plan_id="a")
    compiles_after_a = gw.exec_cache.compiles
    assert compiles_after_a > 0
    gw.register_plan(plan, plan_id="b", key=jax.random.PRNGKey(7))
    # identical layer specs → zero new executables for plan b
    assert gw.exec_cache.compiles == compiles_after_a
    assert gw.plans["b"].compiled.compiles == 0
    assert gw.plans["b"].compiled.warmed_up

    ca, cb = gw.plans["a"].compiled, gw.plans["b"].compiled
    imgs = _images(ca, 6, seed=6)

    async def main():
        async with gw:
            fa = [await gw.submit(img, plan_id="a") for img in imgs[:3]]
            fb = [await gw.submit(img, plan_id="b") for img in imgs[3:]]
            return (await asyncio.gather(*fa), await asyncio.gather(*fb))

    outs_a, outs_b = asyncio.run(main())
    pcfg = deploy.plan_config(plan)
    for img, out in zip(imgs[:3], outs_a):
        np.testing.assert_array_equal(out, np.asarray(
            cnn_forward_ref(ca.params, jnp.asarray(img), pcfg)))
    for img, out in zip(imgs[3:], outs_b):
        np.testing.assert_array_equal(out, np.asarray(
            cnn_forward_ref(cb.params, jnp.asarray(img), pcfg)))
    stats = gw.stats()
    assert stats["plans"] == {"a": 3, "b": 3}


def test_gateway_failed_dispatch_fails_futures_instead_of_hanging():
    """Regression: a dispatch error other than DispatchAborted must
    propagate into every affected future — stranding them pending would
    hang clients forever."""
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=4))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 2)

    class _Exploding:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def __call__(self, *a, **k):
            raise RuntimeError("device exploded")

    gw.plans["plan0"].compiled = _Exploding(compiled)

    async def main():
        async with gw:
            futs = [await gw.submit(img) for img in imgs]
            return await asyncio.gather(*futs, return_exceptions=True)

    done = asyncio.run(main())
    assert all(isinstance(d, RuntimeError)
               and "device exploded" in str(d) for d in done)
    assert gw.stats()["served"] == 0 and gw.stats()["pending"] == 0


def test_gateway_has_no_sync_drain():
    """The gateway reuses SlotPool bookkeeping but not its sync serving
    interface — run()/step() fail loudly instead of mis-admitting."""
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=4))
    with pytest.raises(TypeError, match="no sync drain"):
        gw.run([])
    with pytest.raises(TypeError, match="continuously"):
        gw.step()


def test_gateway_validates_images_at_the_door():
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=4))

    async def main():
        async with gw:
            with pytest.raises(ValueError, match="image shape"):
                gw.submit_nowait(np.zeros((3, 3, 1), np.int8))
            with pytest.raises(ValueError, match="non-integral"):
                gw.submit_nowait(np.full(
                    gw.plans["plan0"].compiled.in_shape, 0.5, np.float32))
            with pytest.raises(ValueError, match="unknown plan id"):
                gw.submit_nowait(np.zeros((3, 3, 1), np.int8),
                                 plan_id="nope")

    asyncio.run(main())
    assert gw.stats()["served"] == 0


def test_gateway_policy_matches_sync_engine_ordering():
    """The gateway and the sync drain schedule identically: same policy
    object, same keys, same realized order."""
    pol = get_policy("edf")
    reqs = [_req(0, deadline=9.0), _req(1, deadline=3.0),
            _req(2), _req(3, priority=2)]
    q = AdmissionQueue(max_pending=8, policy=pol)
    for r in reqs:
        q.admit(r, 0.0)
    _, batch = q.pop_batch(8, 0.0)
    assert [r.request_id for r in batch] \
        == [r.request_id for r in pol.order(reqs, 0.0)]


# ---------------------------------------------------------------------------
# gateway lifecycle regressions + adaptive admission end-to-end
# ---------------------------------------------------------------------------

def test_gateway_cancel_under_backpressure_recovers_full_bound():
    """Regression, hammered: repeatedly fill the admission bound,
    cancel every queued future, refill.  Each cancellation must free
    exactly one slot of the bound — a leak shows up as the bound
    shrinking round over round until nothing is admissible."""
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=4))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 4, seed=13)

    async def main():
        async with gw:
            for _ in range(5):
                # fill the bound without yielding to the drain task
                futs = [gw.submit_nowait(img) for img in imgs]
                with pytest.raises(GatewayBacklog):
                    gw.submit_nowait(imgs[0])
                for f in futs:
                    f.cancel()
                await asyncio.gather(*futs, return_exceptions=True)
                assert len(gw.queue) == 0
            # the whole bound is still admissible after the hammering
            futs = [gw.submit_nowait(img) for img in imgs]
            return await asyncio.gather(*futs)

    outs = asyncio.run(main())
    assert all(isinstance(o, np.ndarray) for o in outs)
    stats = gw.stats()
    assert stats["cancelled"] == 20 and stats["served"] == 4
    assert stats["pending"] == 0


def test_gateway_close_resolves_backpressured_submitters():
    """Regression: submitters parked at the admission bound when the
    gateway closes must all resolve — a waiter woken by ``close()``
    that re-tried admission first could slip into the queue after the
    drain task had already exited and pend forever."""
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=2))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 8, seed=14)

    async def main():
        async with gw:
            queued = [gw.submit_nowait(img) for img in imgs[:2]]
            waiters = [asyncio.ensure_future(gw.submit(img))
                       for img in imgs[2:]]
            await asyncio.sleep(0)      # park them at the bound
        # __aexit__ → close(): every waiter must resolve promptly —
        # either admitted-and-served before the drain exited, or
        # failed with "gateway is closing"; none may hang
        futs = await asyncio.wait_for(asyncio.gather(*waiters), 10.0)
        return await asyncio.wait_for(
            asyncio.gather(*queued, *futs, return_exceptions=True),
            10.0)

    outs = asyncio.run(main())
    assert all(isinstance(o, (np.ndarray, RuntimeError)) for o in outs)
    failed = [o for o in outs if isinstance(o, RuntimeError)]
    assert sum(isinstance(o, np.ndarray) for o in outs) \
        + len(failed) == 8
    assert all("closing" in str(e) for e in failed)
    assert gw.stats()["pending"] == 0


def test_gateway_class_aware_shedding_at_the_bound():
    """At the bound a higher-class arrival ejects the least-urgent
    pending request instead of being refused: the victim's future
    raises ``GatewayBacklog``, the arrival is served, and a same-class
    arrival is still the one refused."""
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=2,
                               policy="edf"))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 4, seed=15)

    async def main():
        async with gw:
            lo = [gw.submit_nowait(img, priority=0)
                  for img in imgs[:2]]
            hi = gw.submit_nowait(imgs[2], priority=5)
            with pytest.raises(GatewayBacklog):
                gw.submit_nowait(imgs[3], priority=0)
            return await asyncio.gather(*lo, hi,
                                        return_exceptions=True)

    done = asyncio.run(main())
    shed = [d for d in done[:2] if isinstance(d, GatewayBacklog)]
    assert len(shed) == 1                  # exactly one victim
    assert isinstance(done[2], np.ndarray)  # the high-class arrival
    assert sum(isinstance(d, np.ndarray) for d in done) == 2
    stats = gw.stats()
    assert stats["shed"] == 1 and stats["rejected"] == 1
    assert stats["served"] == 2


def test_gateway_submit_chunk_partial_admission():
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=3))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 5, seed=16)

    async def main():
        async with gw:
            futs, refused = gw.submit_chunk(imgs)  # no yields: bound=3
            assert len(futs) == 3 and refused == 2
            outs = await asyncio.gather(*futs)
            # with the queue drained the whole chunk fits
            futs2, refused2 = gw.submit_chunk(imgs[:2])
            assert refused2 == 0
            return outs, await asyncio.gather(*futs2)

    outs, outs2 = asyncio.run(main())
    assert len(outs) == 3 and len(outs2) == 2
    assert gw.stats()["rejected"] == 1     # chunk stops at the refusal


def test_slot_pool_rate_estimator_busy_runs_and_idle_gaps():
    from repro.serve.slots import SlotPool

    t = [0.0]
    pool = SlotPool(max_batch=8, clock=lambda: t[0])
    assert pool.service_rate == 0.0 and pool.service_rate_slow == 0.0
    # a full batch launched at t=0 completing at t=0.1 → 80 img/s
    t[0] = 0.1
    pool._note_step(8, launched_at=0.0)
    assert pool.service_rate == pytest.approx(80.0)
    assert pool.service_rate_slow == pytest.approx(80.0)
    # a long idle gap, then a fresh run at the same speed: idle time
    # must not dilute the estimate (a lull is not slowness)
    t[0] = 100.1
    pool._note_step(8, launched_at=100.0)
    assert pool.service_rate == pytest.approx(80.0)
    # sustained faster service: the fast horizon converges within the
    # sliding window; the slow horizon (capacity commitments) lags
    for _ in range(6):
        t0 = t[0]
        t[0] += 0.01                   # 8 images / 10 ms = 800 img/s
        pool._note_step(8, launched_at=t0)
    assert pool.service_rate > 400.0
    assert pool.service_rate_slow < pool.service_rate
    # est_wait derives from the fast rate in the same snapshot
    snap = pool.snapshot(queue_depth=40)
    assert snap.service_rate == pool.service_rate
    assert snap.est_wait == pytest.approx(40 / pool.service_rate)


def test_gateway_adaptive_bound_tracks_measured_rate():
    t = [0.0]
    gw = AsyncCNNGateway(
        AsyncServeConfig(max_batch=4, max_pending=64, min_pending=6,
                         wait_budget_s=0.5),
        clock=lambda: t[0])
    # no rate measured yet: the bound floors at min_pending
    gw._adapt_bound(force=True)
    assert gw.queue.max_pending == 6
    # measured 40 img/s → bound = ceil(40 × 0.5) = 20
    t[0] = 0.1
    gw._note_step(4, launched_at=0.0)
    gw._adapt_bound(force=True)
    assert gw.queue.max_pending == 20
    # a *sustained* faster rate grows it, capped at max_pending
    for _ in range(200):
        t0 = t[0]
        t[0] += 0.001                  # 4000 img/s, far past the cap
        gw._note_step(4, launched_at=t0)
    gw._adapt_bound(force=True)
    assert gw.queue.max_pending == 64
    # without a wait budget the bound is static
    gw2 = AsyncCNNGateway(AsyncServeConfig(max_batch=4, max_pending=7))
    gw2._adapt_bound(force=True)
    assert gw2.queue.max_pending == 7


def test_async_serve_config_validation_and_pool_sizing():
    with pytest.raises(ValueError, match="max_inflight"):
        AsyncCNNGateway(AsyncServeConfig(max_batch=2, max_inflight=0))
    with pytest.raises(ValueError, match="wait_budget_s"):
        AsyncCNNGateway(AsyncServeConfig(max_batch=2,
                                         wait_budget_s=0.0))
    with pytest.raises(ValueError, match="min_pending"):
        AsyncCNNGateway(AsyncServeConfig(max_batch=2, min_pending=0))
    with pytest.raises(ValueError, match="batch_linger"):
        AsyncCNNGateway(AsyncServeConfig(max_batch=2,
                                         batch_linger=-0.1))
    # the slot pool is max_inflight dispatch-widths wide so the next
    # batch can stage (and prep) while one is on-device
    gw = AsyncCNNGateway(AsyncServeConfig(max_batch=4, max_inflight=2))
    assert gw.free_slots() == 8 and gw.cfg.max_batch == 4


# ---------------------------------------------------------------------------
# runtime: shared cache + cancellation-safe dispatch
# ---------------------------------------------------------------------------

def test_compiled_cnn_shares_executables_across_instances():
    cfg = _cfg()
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    blocks = [s.block for s in cfg.layers]
    cache = ExecutableCache()
    a = CompiledCNN(cfg, params, blocks, max_batch=4, exec_cache=cache)
    n = cache.compiles
    assert n == len(cache) == len(a.buckets) * len(cfg.layers)
    b = CompiledCNN(cfg, params, blocks, max_batch=4, exec_cache=cache)
    assert cache.compiles == n and b.compiles == 0   # all cache hits
    assert b.warmed_up
    x = np.stack(_images(a, 3, seed=8))
    np.testing.assert_array_equal(np.asarray(a(x)), np.asarray(b(x)))


def test_compiled_cnn_dispatch_abort():
    cfg = _cfg()
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    cnn = CompiledCNN(cfg, params, [s.block for s in cfg.layers],
                      max_batch=2)
    x = np.stack(_images(cnn, 1, seed=9))
    with pytest.raises(DispatchAborted):
        cnn(x, should_abort=lambda: True)
    # a non-firing hook changes nothing
    y = cnn(x, should_abort=lambda: False)
    ref = cnn_forward_ref(params, jnp.asarray(x), cfg)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


# ---------------------------------------------------------------------------
# the GatewayStats snapshot seam (shared by SlotPool and the gateway)
# ---------------------------------------------------------------------------

def test_slot_pool_and_gateway_share_the_snapshot_seam():
    """`GatewayStats` is the one stats capture both serving layers (and
    the fleet's health heartbeats) read: the raw SlotPool emits it, the
    gateway's override layers its terminal counters on, and stats() is
    derived from one snapshot rather than assembled field-by-field."""
    from repro.serve import GatewayStats
    from repro.serve.slots import SlotPool

    pool = SlotPool(max_batch=3)
    snap = pool.snapshot(clock=lambda: 12.5)
    assert isinstance(snap, GatewayStats)
    assert snap.timestamp == 12.5
    assert snap.queue_depth == 0 and snap.inflight == 0
    assert snap.depth == 0 and snap.max_batch == 3
    assert pool.stats()["occupancy_hist"] == {}

    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=8))
    gsnap = gw.snapshot()
    assert isinstance(gsnap, GatewayStats)
    assert gsnap.max_batch == 2 and gsnap.depth == 0
    d = gsnap.asdict()
    for key in ("timestamp", "queue_depth", "inflight", "max_batch",
                "steps", "occupancy_hist", "served", "rejected",
                "expired", "cancelled", "failed"):
        assert key in d, key
    # the flattened stats() carries the same terminal counters
    stats = gw.stats()
    assert stats["served"] == 0 and stats["failed"] == 0
    assert stats["inflight"] == 0


def test_gateway_snapshot_tracks_queue_and_terminals():
    plan = _plan()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=2, max_pending=8))
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 5, seed=11)

    async def main():
        async with gw:
            futs = [gw.submit_nowait(img) for img in imgs]
            # before yielding to dispatch, all five sit in the queue
            pre = gw.snapshot()
            assert pre.queue_depth == 5 and pre.depth == 5
            outs = await asyncio.gather(*futs)
            return pre, outs

    pre, outs = asyncio.run(main())
    post = gw.snapshot()
    assert post.queue_depth == 0 and post.inflight == 0
    assert post.served == len(outs) == 5
    assert post.steps >= 3            # max_batch=2 → ≥ ceil(5/2) steps
    assert sum(k * v for k, v in post.occupancy_hist.items()) == 5
