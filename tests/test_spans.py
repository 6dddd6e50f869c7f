"""Host spans and counters of the gateway and the executor: exact counts
under a fake clock, thread-safe totals, and the spans on the profiler's
own timeline, each on the thread that does the work."""

import asyncio
import glob
import sys
import threading

import jax
import pytest

from repro.runtime.compiled import SpanTotals
from repro.serve import AsyncCNNGateway, AsyncServeConfig

from test_async_serve import _images, _plan


def test_span_totals_count_and_time():
    totals = SpanTotals()
    for i in range(3):
        with totals.span("gateway.submit", request_id=i):
            pass
    totals.add("gateway.handoff", 0.25)
    totals.add("gateway.handoff", 0.5)
    snap = totals.snapshot()
    assert snap["gateway.submit"][0] == 3
    assert snap["gateway.submit"][1] >= 0.0
    assert snap["gateway.handoff"] == (2, 0.75)
    # a span records even when its body raises
    with pytest.raises(ValueError):
        with totals.span("executor.pad"):
            raise ValueError("boom")
    assert totals.snapshot()["executor.pad"][0] == 1


def test_span_totals_lose_no_count_across_threads():
    totals = SpanTotals()
    threads, per_thread = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with totals.span("executor.launch", layer=0):
                    pass
                totals.add("gateway.handoff", 1.0)

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    snap = totals.snapshot()
    assert snap["executor.launch"][0] == threads * per_thread
    assert snap["gateway.handoff"] == (threads * per_thread,
                                       float(threads * per_thread))


def test_gateway_counters_advance_exactly_under_a_fake_clock():
    """k submits and one dispatch of n = k < bucket: k submit spans, one
    of each per-dispatch span, n launched with their exact queue wait on
    the gateway's clock, and bucket − n padded rows."""
    t = [0.0]
    gw = AsyncCNNGateway.from_plan(
        _plan(), AsyncServeConfig(max_batch=4, max_pending=8),
        clock=lambda: t[0])
    compiled = gw.plans["plan0"].compiled
    imgs = _images(compiled, 3, seed=5)
    g0, c0 = gw.snapshot(), compiled.stats()

    async def main():
        async with gw:
            futs = []
            for i, img in enumerate(imgs):
                t[0] = float(i)            # arrivals at 0, 1, 2
                futs.append(gw.submit_nowait(img))
            t[0] = 10.0                    # the one batch launches at 10
            return await asyncio.gather(*futs)

    asyncio.run(main())
    g1, c1 = gw.snapshot(), compiled.stats()
    assert g0.spans == {} and g0.launched == 0
    counts = {k: v[0] for k, v in g1.spans.items()}
    assert counts == {"gateway.submit": 3, "gateway.form_batch": 1,
                      "gateway.stack": 1, "gateway.handoff": 1,
                      "executor.device_wait": 1, "executor.d2h": 1,
                      "gateway.resolve": 1}
    assert g1.launched == 3
    assert g1.queue_wait_s == pytest.approx(10.0 + 9.0 + 8.0)
    bucket = compiled.bucket_for(3)
    assert bucket == 4
    assert c1["rows"] - c0["rows"] == bucket
    assert c1["padded_rows"] - c0["padded_rows"] == bucket - 3
    ecounts = {k: v[0] for k, v in c1["spans"].items()}
    assert ecounts == {"executor.h2d": 1, "executor.pad": 1,
                       "executor.launch": compiled.num_layers}
    stats = gw.stats()
    assert stats["launched"] == 3 and stats["spans"] == g1.spans
    assert stats["queue_wait_s"] == g1.queue_wait_s


def _repro_lines(pd):
    """{line index: [(name, start, end)]} of the ``repro.*`` spans on
    the host plane, plus the line of each ``test.*`` marker."""
    spans, markers = {}, {}
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    for i, line in enumerate(host.lines):
        for e in line.events:
            s, d = int(e.start_ns), int(e.duration_ns)
            if e.name.startswith("repro."):
                spans.setdefault(i, []).append((e.name, s, s + d))
            elif e.name.startswith("test."):
                markers[e.name] = i
    return spans, markers


def test_spans_land_on_the_profiler_timeline_per_thread(tmp_path):
    """In a profiler trace the gateway's spans sit on the event loop's
    thread and the executor's on the dispatch worker's, and no two of
    them overlap on one thread, so their sums count no time twice."""
    gw = AsyncCNNGateway.from_plan(
        _plan(), AsyncServeConfig(max_batch=4, max_pending=16))
    imgs = _images(gw.plans["plan0"].compiled, 6, seed=3)

    def mark(name):
        with jax.profiler.TraceAnnotation(name):
            pass

    async def main():
        async with gw:
            mark("test.loop")
            await asyncio.get_running_loop().run_in_executor(
                gw._executor, mark, "test.worker")
            futs = [gw.submit_nowait(img) for img in imgs]
            await asyncio.gather(*futs)

    with jax.profiler.trace(str(tmp_path)):
        asyncio.run(main())
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans, markers = _repro_lines(jax.profiler.ProfileData.from_file(path))
    loop, worker = markers["test.loop"], markers["test.worker"]
    assert loop != worker
    on_loop = {n for n, _, _ in spans[loop]}
    on_worker = {n for n, _, _ in spans[worker]}
    assert set(spans) == {loop, worker}
    assert on_loop == {"repro.gateway.submit", "repro.gateway.form_batch",
                       "repro.gateway.stack", "repro.gateway.resolve"}
    assert on_worker == {"repro.executor.h2d", "repro.executor.launch",
                         "repro.executor.device_wait",
                         "repro.executor.d2h"}
    for events in spans.values():
        events.sort(key=lambda ev: ev[1])
        for (_, _, end), (_, start, _) in zip(events, events[1:]):
            assert start >= end
    # the two dispatches (4 and 2 requests) each left their spans
    assert sum(n == "repro.gateway.resolve" for n, _, _ in spans[loop]) == 2
    assert gw.stats()["served"] == 6


def test_layer_executables_have_stable_names_and_moe_scopes():
    """Each layer executable is named for what it computes (the trace's
    module name): block, bits and channels, or experts, top-k and bits;
    and the MoE layers carry the
    router, dispatch, held experts' FFN and combine scopes in their
    metadata."""
    from repro.runtime.workloads import compile_plan, plan_moe_deployment
    from test_workloads import tiny_moe_spec

    cnn = compile_plan(_plan(), max_batch=2)
    want = {f"jit_cnn_{b.name}_d{s.data_bits}c{s.coeff_bits}"
            f"_{s.in_channels}to{s.out_channels}"
            for s, b in zip(cnn.cfg.layers, cnn.blocks)}
    got = {cnn._compile_layer(i, b).as_text().split(",")[0].split()[1]
           for b in cnn.buckets for i in range(cnn.num_layers)}
    assert got == want and len(want) == cnn.num_layers

    moe = compile_plan(plan_moe_deployment(tiny_moe_spec(), "v5e"),
                       max_batch=2)
    s = moe.spec.layers[0]
    name = f"jit_moe_e4_k2_d{s.data_bits}c{s.coeff_bits}"
    for bucket in moe.buckets:         # flat (1) and grouped (2) routing
        text = moe._compile_layer(0, bucket).as_text()
        assert text.startswith(f"HloModule {name},")
        for scope in ("router", "dispatch", "held_ffn", "combine"):
            assert f"/{scope}/" in text, (bucket, scope)
