"""Serving launcher: batched requests through the serving engines on
reduced (CPU-runnable) configs.

LM workload — continuous-batching decode:

  PYTHONPATH=src python -m repro.launch.serve --workload lm \
      --arch gemma2-2b --requests 6 --prompt-len 16 --new-tokens 24

CNN workload — plan-driven dynamic batching via ``repro.runtime``: the
deployment planner picks each layer's block/bits for the device (or a
saved plan artifact is loaded verbatim), every batch bucket is
AOT-compiled before serving, and each tick dispatches the live images
to the smallest bucket that fits:

  PYTHONPATH=src python -m repro.launch.serve --workload cnn \
      --requests 64 --max-batch 16 [--device v5e] [--shard] \
      [--save-plan plan.json]

  # serve a previously planned artifact (possibly from another machine)
  PYTHONPATH=src python -m repro.launch.serve --workload cnn \
      --plan plan.json --requests 64

Async CNN workload — the continuous-batching gateway under Poisson
arrivals: bounded admission (overload is shed at the door), deadline-
aware batch formation, a new bucket dispatch the moment slots free:

  PYTHONPATH=src python -m repro.launch.serve --workload cnn --async \
      --requests 128 --max-batch 8 --occupancy 2.0 \
      [--deadline-ms 250] [--max-pending 32] \
      [--wait-budget-ms 100] [--max-inflight 2]

Fleet workload — the multi-worker front door from ``repro.fleet``: one
gateway per device profile (edge / v5e / v5p, each serving the plan the
deployment planner picked for that profile), tiered Poisson traffic
placed by a pluggable router, optional mid-trace graceful drain:

  PYTHONPATH=src python -m repro.launch.serve --workload cnn --fleet \
      --requests 96 --occupancy 1.5 [--router plan_aware] [--drain]

MoE workload — the same plan→compile→serve stack, different backend:
``plan_moe_deployment`` picks per-layer (data_bits, coeff_bits) for the
quantized expert FFNs, ``compile_plan`` builds the bucketed AOT
``CompiledMoE``, and the identical engines serve token blocks instead
of images:

  PYTHONPATH=src python -m repro.launch.serve --workload moe \
      --requests 32 --max-batch 8 [--device v5e] [--arch qwen3-moe-30b-a3b] \
      [--save-plan moe_plan.json] [--async --occupancy 2.0]
"""

from __future__ import annotations

import argparse
import asyncio
import time

import jax
import numpy as np


def _percentiles(lat_s):
    p = np.percentile(np.asarray(lat_s) * 1e3, [50, 95, 99])
    return {"p50_ms": p[0], "p95_ms": p[1], "p99_ms": p[2]}


# -- durable serving state (repro.ops) flags --------------------------------
def _apply_store_root(args):
    """``--store-root`` → one shared ``repro.ops.StoreRoot`` standing in
    for both ``--plan-store`` and ``--cache-dir``: every worker process
    pointed at the same DIR shares one plan repository and one
    content-addressed executable cache — which is what lets a respawned
    worker rebuild its predecessor's serving state with zero recompiles
    (see ``repro.chaos.respawn_gateway``)."""
    if not getattr(args, "store_root", None):
        return
    if args.plan_store or args.cache_dir:
        raise SystemExit("--store-root replaces --plan-store and "
                         "--cache-dir; give one or the other")
    from repro.ops import StoreRoot
    root = StoreRoot(args.store_root)
    args.plan_store = str(root.root)
    args.cache_dir = str(root.exec_cache_dir)
    print(f"[ops] shared store root at {args.store_root!r} "
          f"(plans + exec cache + leases)")


def _ops_cache(args):
    """``--cache-dir`` → a ``PersistentExecutableCache`` every compile
    in this process writes through; None without the flag (the callers
    fall back to an in-memory cache)."""
    if not getattr(args, "cache_dir", None):
        return None
    from repro.ops import PersistentExecutableCache
    cache = PersistentExecutableCache(args.cache_dir)
    print(f"[ops] persistent executable cache at {args.cache_dir!r} "
          f"(replaces JAX's compilation cache)")
    return cache


def _ops_tracker(args):
    """``--metrics-out`` → a ``JsonlTracker``; None without the flag."""
    if not getattr(args, "metrics_out", None):
        return None
    from repro.ops import JsonlTracker
    tracker = JsonlTracker(args.metrics_out)
    print(f"[ops] metrics JSONL → {args.metrics_out!r}")
    return tracker


def _ops_sampler(tracker, sources, interval_s=0.5):
    if tracker is None:
        return None
    from repro.ops import StatsSampler
    return StatsSampler(tracker, sources, interval_s=interval_s)


def _ops_finish(tracker, sampler=None, cache=None):
    """Flush ops state at the end of a run and say where it went."""
    if sampler is not None:
        sampler.close()
    if tracker is not None:
        tracker.close()
        print(f"[ops] metrics: {tracker.recorded} records "
              f"({tracker.dropped} dropped) → {tracker.path}")
    if cache is not None:
        s = cache.stats()
        print(f"[ops] exec cache: {s['compiles']} compiled, "
              f"{s['disk_hits']} loaded from disk, "
              f"{s['disk_stores']} persisted")


def _plan_from_store(args, workload: str, compute):
    """Resolve the plan through ``--plan-store`` when set: serve the
    stored plan under ``<workload>-<device>`` if present, otherwise run
    ``compute()`` and persist the result — the next launch loads it."""
    from repro.ops import PlanStore
    store = PlanStore(args.plan_store)
    store_id = f"{workload}-{args.device}"
    if store_id in store:
        plan = store.load(store_id)
        print(f"[serve] loaded plan {store_id!r} from store "
              f"{args.plan_store!r}")
        return plan
    plan = compute()
    store.save(plan, store_id)
    print(f"[serve] plan {store_id!r} saved to store {args.plan_store!r}")
    return plan


def run_lm(args) -> None:
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serve import Engine, Request, ServeConfig

    cfg = smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(
        max_batch=args.max_batch, max_len=args.prompt_len + args.new_tokens
        + 8, max_new_tokens=args.new_tokens))

    tracker = _ops_tracker(args)
    sampler = _ops_sampler(
        tracker, {"engine": lambda: engine.snapshot().asdict()})
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                             args.prompt_len)),
                    request_id=i) for i in range(args.requests)]
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    total = sum(len(r.out_tokens) for r in reqs)
    print(f"[serve] {args.requests} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s on {len(jax.devices())} host device(s))")
    for r in reqs[:3]:
        print(f"  req{r.request_id}: {r.out_tokens[:12]}...")
    _ops_finish(tracker, sampler)


def quickstart_cnn_plan(device: str, cfg=None):
    """The planner's deployment of the quickstart CNN (or ``cfg``) on
    one catalog device profile — what the CNN workloads serve unless a
    plan artifact is given."""
    from repro.core import allocate, deploy
    from repro.core.cnn import fitted_block_models, quickstart_cnn_config

    cfg = quickstart_cnn_config() if cfg is None else cfg
    return deploy.plan_deployment(cfg, fitted_block_models(),
                                  allocate.get_device(device), target=0.8,
                                  on_infeasible="fallback")


def _cnn_plan(args):
    """Load or compute the deployment plan the CNN workloads serve."""
    from repro import runtime

    def compute():
        return quickstart_cnn_plan(args.device)

    if args.plan:
        plan = runtime.load_plan(args.plan)
        print(f"[serve] loaded plan artifact {args.plan!r} "
              f"(planned for device {plan.device.name})")
    elif args.plan_store:
        plan = _plan_from_store(args, "cnn", compute)
    else:
        plan = compute()
    if args.save_plan:                 # also re-exports a loaded --plan
        runtime.save_plan(plan, args.save_plan)
        print(f"[serve] plan artifact saved to {args.save_plan!r}")
    print(f"[serve] plan for {plan.device.name}: "
          + ", ".join(f"L{a.index}={a.block}@d{a.data_bits}/c{a.coeff_bits}"
                      for a in plan.layers))
    return plan


def _moe_plan(args):
    """Load or plan the quantized-MoE deployment the MoE workload
    serves.  ``--arch`` (a zoo MoE config, shrunk via ``smoke_config``)
    seeds the workload spec; ``--plan``/``--save-plan`` round-trip the
    v2 plan artifact exactly like the CNN path."""
    from repro import runtime
    from repro.configs import smoke_config
    from repro.runtime import moe_workload_from_config, plan_moe_deployment

    def compute():
        spec = moe_workload_from_config(smoke_config(args.arch))
        return plan_moe_deployment(spec, args.device, target=0.8,
                                   on_infeasible="fallback")

    if args.plan:
        plan = runtime.load_plan(args.plan)
        print(f"[serve] loaded plan artifact {args.plan!r} "
              f"(planned for device {plan.device.name}, "
              f"workload {plan.workload.kind!r})")
    elif args.plan_store:
        plan = _plan_from_store(args, "moe", compute)
    else:
        plan = compute()
    if args.save_plan:
        runtime.save_plan(plan, args.save_plan)
        print(f"[serve] plan artifact saved to {args.save_plan!r}")
    print(f"[serve] plan for {plan.device.name}: "
          + ", ".join(f"L{a.index}={a.block}@d{a.data_bits}/c{a.coeff_bits}"
                      for a in plan.layers)
          + f"  (quant rel-err {plan.quant_error:.4f})")
    return plan


def run_moe(args) -> None:
    """Quantized-MoE serving through the *same* engine as the CNN path:
    ``CNNEngine.from_plan`` dispatches on the plan's workload kind, so
    the tick loop, bucketing, and stats below are untouched code."""
    from repro.serve import CNNEngine, CNNServeConfig, ImageRequest

    plan = _moe_plan(args)
    cache = _ops_cache(args)
    tracker = _ops_tracker(args)
    t0 = time.time()
    engine = CNNEngine.from_plan(
        plan, serve_cfg=CNNServeConfig(max_batch=args.max_batch),
        exec_cache=cache)
    sampler = _ops_sampler(tracker, {"engine": engine.stats})
    compiled = engine.compiled
    print(f"[serve] AOT warmup: {len(compiled.buckets)} buckets × "
          f"{compiled.num_layers} MoE layers compiled in "
          f"{time.time() - t0:.2f}s (off the serving critical path)")

    reqs = [ImageRequest(image=x, request_id=i) for i, x in
            enumerate(compiled.sample_inputs(args.requests))]
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    stats = engine.stats()
    seq_len = compiled.in_shape[0]
    print(f"[serve] {len(reqs)} token blocks ({len(reqs) * seq_len} "
          f"tokens) in {dt:.2f}s ({len(reqs) * seq_len / dt:.0f} tok/s, "
          f"{stats['images_per_step']:.1f} blocks/step)")
    print(f"[serve] occupancy histogram: {stats['occupancy_hist']}  "
          f"bucket hits: {stats['bucket_hits']}")
    _ops_finish(tracker, sampler, cache)


def run_moe_async(args) -> None:
    """The async gateway serving MoE token blocks — identical driver to
    ``run_cnn_async`` because the gateway is plan-type-blind."""
    from repro.serve import (AsyncCNNGateway, AsyncServeConfig,
                            DeadlineExpired, GatewayBacklog)

    plan = _moe_plan(args)
    cache = _ops_cache(args)
    tracker = _ops_tracker(args)
    t0 = time.time()
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=args.max_batch,
                               max_pending=args.max_pending,
                               max_inflight=args.max_inflight),
        plan_id="moe", exec_cache=cache, tracker=tracker)
    compiled = gw.plans["moe"].compiled
    sampler = _ops_sampler(tracker, {"gateway": gw.stats,
                                     "executor": compiled.stats})
    print(f"[serve] AOT warmup: {len(compiled.buckets)} buckets × "
          f"{compiled.num_layers} MoE layers in {time.time() - t0:.2f}s")

    blocks = compiled.sample_inputs(args.requests)
    xb = np.stack([np.asarray(b, compiled.in_dtype)
                   for b in blocks[:args.max_batch]])
    compiled(xb)                                   # touch
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(xb))
    step_s = time.perf_counter() - t0
    rate = args.occupancy * args.max_batch / step_s
    print(f"[serve] full-batch step {step_s * 1e3:.2f}ms → offered load "
          f"{rate:.0f} blocks/s (occupancy {args.occupancy:g})")

    deadline = args.deadline_ms / 1e3 if args.deadline_ms else None
    rng = np.random.default_rng(1)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, args.requests))

    async def drive():
        latencies, shed = [], 0
        async with gw:
            t_start = time.monotonic()

            async def one(i, at):
                nonlocal shed
                await asyncio.sleep(max(0.0, at - (time.monotonic()
                                                   - t_start)))
                t_sub = time.monotonic()
                try:
                    fut = gw.submit_nowait(blocks[i], deadline=deadline)
                    await fut
                    latencies.append(time.monotonic() - t_sub)
                except GatewayBacklog:
                    shed += 1
                except DeadlineExpired:
                    pass
            await asyncio.gather(*(one(i, a)
                                   for i, a in enumerate(arrivals)))
            return latencies, shed, time.monotonic() - t_start

    latencies, shed, wall = asyncio.run(drive())
    stats = gw.stats()
    pct = _percentiles(latencies) if latencies else {}
    seq_len = compiled.in_shape[0]
    print(f"[serve] {stats['served']} served / {shed} shed / "
          f"{stats['expired']} expired of {args.requests} in {wall:.2f}s "
          f"({stats['served'] * seq_len / wall:.0f} tok/s)")
    if pct:
        print(f"[serve] latency p50={pct['p50_ms']:.1f}ms "
              f"p95={pct['p95_ms']:.1f}ms p99={pct['p99_ms']:.1f}ms")
    _ops_finish(tracker, sampler, cache)


def run_cnn(args) -> None:
    from repro.parallel.sharding import cnn_data_mesh
    from repro.serve import CNNEngine, CNNServeConfig, ImageRequest

    plan = _cnn_plan(args)
    cache = _ops_cache(args)
    tracker = _ops_tracker(args)
    mesh = cnn_data_mesh() if args.shard else None
    t0 = time.time()
    engine = CNNEngine.from_plan(           # AOT-compiles every bucket
        plan, serve_cfg=CNNServeConfig(max_batch=args.max_batch),
        mesh=mesh, exec_cache=cache)
    sampler = _ops_sampler(tracker, {"engine": engine.stats})
    print(f"[serve] AOT warmup: {len(engine.compiled.buckets)} buckets × "
          f"{len(engine.cfg.layers)} layers compiled in "
          f"{time.time() - t0:.2f}s (off the serving critical path)")

    reqs = [ImageRequest(image=img, request_id=i) for i, img in
            enumerate(engine.compiled.sample_inputs(args.requests))]
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    stats = engine.stats()
    print(f"[serve] {len(reqs)} images in {dt:.2f}s "
          f"({len(reqs)/dt:.1f} images/s, "
          f"{stats['images_per_step']:.1f} images/step) on "
          f"{len(jax.devices())} host device(s)"
          + (f", batch sharded over mesh {dict(mesh.shape)}" if mesh
             else ""))
    print(f"[serve] occupancy histogram: {stats['occupancy_hist']}  "
          f"bucket hits: {stats['bucket_hits']}")
    _ops_finish(tracker, sampler, cache)


def run_cnn_async(args) -> None:
    """Continuous-batching gateway under Poisson arrivals at an offered
    load of ``--occupancy`` × the measured full-batch service capacity.
    Reports tail latency (p50/p95/p99 over *served* requests), shed and
    expired counts — the front-door view the tick loop cannot give."""
    from repro.parallel.sharding import cnn_data_mesh
    from repro.serve import (AsyncCNNGateway, AsyncServeConfig,
                             DeadlineExpired, GatewayBacklog)

    plan = _cnn_plan(args)
    cache = _ops_cache(args)
    tracker = _ops_tracker(args)
    mesh = cnn_data_mesh() if args.shard else None
    t0 = time.time()
    wait_budget = (args.wait_budget_ms / 1e3
                   if args.wait_budget_ms else None)
    gw = AsyncCNNGateway.from_plan(
        plan, AsyncServeConfig(max_batch=args.max_batch,
                               max_pending=args.max_pending,
                               max_inflight=args.max_inflight,
                               wait_budget_s=wait_budget),
        mesh=mesh, exec_cache=cache, tracker=tracker)
    compiled = gw.plans["plan0"].compiled
    sampler = _ops_sampler(tracker, {"gateway": gw.stats,
                                     "executor": compiled.stats})
    print(f"[serve] AOT warmup: {len(compiled.buckets)} buckets × "
          f"{len(compiled.cfg.layers)} layers compiled in "
          f"{time.time() - t0:.2f}s (shared exec cache: "
          f"{len(gw.exec_cache)} executables)")

    imgs = compiled.sample_inputs(args.requests)
    # service capacity: one timed full-batch dispatch → arrival rate
    xb = np.stack([np.asarray(i, compiled.in_dtype)
                   for i in imgs[:args.max_batch]])
    compiled(xb)                                   # touch
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(xb))
    step_s = time.perf_counter() - t0
    rate = args.occupancy * args.max_batch / step_s
    print(f"[serve] full-batch step {step_s * 1e3:.2f}ms → offered load "
          f"{rate:.0f} images/s (occupancy {args.occupancy:g})")

    deadline = args.deadline_ms / 1e3 if args.deadline_ms else None
    rng = np.random.default_rng(1)
    gaps = rng.exponential(1.0 / rate, args.requests)

    async def drive():
        latencies, shed = [], 0
        async with gw:
            t_start = time.monotonic()

            async def one(i, at):
                nonlocal shed
                await asyncio.sleep(max(0.0, at - (time.monotonic()
                                                   - t_start)))
                t_sub = time.monotonic()
                try:
                    fut = gw.submit_nowait(imgs[i], deadline=deadline)
                    await fut
                    latencies.append(time.monotonic() - t_sub)
                except GatewayBacklog:
                    shed += 1
                except DeadlineExpired:
                    pass                           # counted by stats()

            arrivals = np.cumsum(gaps)
            await asyncio.gather(*(one(i, a)
                                   for i, a in enumerate(arrivals)))
            return latencies, shed, time.monotonic() - t_start

    latencies, shed, wall = asyncio.run(drive())
    stats = gw.stats()
    pct = _percentiles(latencies) if latencies else {}
    print(f"[serve] {stats['served']} served / {shed} shed / "
          f"{stats['expired']} expired of {args.requests} in {wall:.2f}s "
          f"({stats['served'] / wall:.1f} images/s)")
    if pct:
        print(f"[serve] latency p50={pct['p50_ms']:.1f}ms "
              f"p95={pct['p95_ms']:.1f}ms p99={pct['p99_ms']:.1f}ms")
    print(f"[serve] occupancy histogram: {stats['occupancy_hist']}  "
          f"policy: {stats['policy']}  pending bound: "
          f"{stats['max_pending']}"
          + (f" (adaptive, budget "
             f"{stats['wait_budget_s'] * 1e3:.0f}ms)"
             if stats['wait_budget_s'] else " (static)"))
    print(f"[serve] measured service rate "
          f"{stats['service_rate']:.0f} images/s, est wait "
          f"{stats['est_wait'] * 1e3:.1f}ms, shed at bound: "
          f"{stats['shed']}")
    _ops_finish(tracker, sampler, cache)


def run_cnn_fleet(args) -> None:
    """Plan-aware fleet front door: one gateway per device profile
    (each serving the plan the deployment planner picked for *that*
    profile under one shared plan id), tiered Poisson traffic routed
    by ``--router``, per-tier tail latency reported.  ``--drain``
    gracefully drains the v5e worker halfway through — queued requests
    re-route, in-flight batches finish, nothing is lost."""
    from repro.fleet import DEFAULT_TIERS, Fleet, FleetWorker
    from repro.serve import (AsyncCNNGateway, AsyncServeConfig,
                             DeadlineExpired, GatewayBacklog)

    profiles = ("edge", "v5e", "v5p")
    # one shared persistent cache across all profile gateways: the disk
    # entries are content-addressed by layer key, so layers identical
    # across the three per-profile plans deserialize once each
    cache = _ops_cache(args)
    tracker = _ops_tracker(args)
    t0 = time.time()
    workers = []
    for name in profiles:
        plan = quickstart_cnn_plan(name)
        gw = AsyncCNNGateway.from_plan(
            plan, AsyncServeConfig(max_batch=args.max_batch,
                                   max_pending=args.max_pending),
            plan_id="cnn", exec_cache=cache, tracker=tracker)
        workers.append(FleetWorker(f"{name}0", gw, name))
    print(f"[fleet] {len(workers)} workers "
          f"({', '.join(f'{w.worker_id}:{w.profile.name}' for w in workers)})"
          f" AOT-warmed in {time.time() - t0:.2f}s")

    compiled = workers[1].gateway.plans["cnn"].compiled
    imgs = compiled.sample_inputs(args.requests)
    xb = np.stack([np.asarray(i, compiled.in_dtype)
                   for i in imgs[:args.max_batch]])
    compiled(xb)                                   # touch
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(xb))
    step_s = time.perf_counter() - t0
    rate = args.occupancy * args.max_batch / step_s
    print(f"[fleet] offered load {rate:.0f} images/s "
          f"(occupancy {args.occupancy:g} of one worker), "
          f"router {args.router!r}")

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, args.requests))
    tiers = list(DEFAULT_TIERS)
    shares = [t.share for t in DEFAULT_TIERS.values()]
    tier_of = rng.choice(len(tiers), size=args.requests, p=shares)

    async def drive():
        per_tier = {t: [] for t in tiers}
        expired = 0
        fleet = Fleet(workers, router=args.router, tracker=tracker)
        sampler = _ops_sampler(tracker, {"fleet": fleet.stats})
        async with fleet:
            t_start = time.monotonic()

            async def one(i):
                nonlocal expired
                await asyncio.sleep(max(0.0, arrivals[i]
                                        - (time.monotonic() - t_start)))
                tier = tiers[tier_of[i]]
                spec = DEFAULT_TIERS[tier]
                t_sub = time.monotonic()
                try:
                    fut = await fleet.submit(imgs[i], tier=tier,
                                             deadline=spec.deadline_s)
                    await fut
                    per_tier[tier].append(time.monotonic() - t_sub)
                except (DeadlineExpired, GatewayBacklog):   # incl. the
                    expired += 1        # fleet's FleetSaturated shed

            async def drainer():
                await asyncio.sleep(arrivals[args.requests // 2])
                print("[fleet] draining v5e0 ...")
                await fleet.drain("v5e0")
                print("[fleet] v5e0 drained (in-flight finished, "
                      "queue re-routed)")

            tasks = [one(i) for i in range(args.requests)]
            if args.drain:
                tasks.append(drainer())
            await asyncio.gather(*tasks)
            stats = fleet.stats()
        if sampler is not None:
            sampler.close()
        return per_tier, expired, stats, time.monotonic() - t_start

    per_tier, expired, stats, wall = asyncio.run(drive())
    total = sum(len(v) for v in per_tier.values())
    print(f"[fleet] {total} served / {expired} expired-or-shed of "
          f"{args.requests} in {wall:.2f}s  (rerouted={stats['rerouted']}"
          f", retried={stats['retried']}, drains={stats['drains']})")
    for tier, lats in per_tier.items():
        if not lats:
            continue
        pct = _percentiles(lats)
        print(f"[fleet]   {tier:<12} n={len(lats):<5} "
              f"p50={pct['p50_ms']:.1f}ms p95={pct['p95_ms']:.1f}ms "
              f"p99={pct['p99_ms']:.1f}ms")
    for wid, w in stats["workers"].items():
        snap = w["snapshot"] or {}
        print(f"[fleet]   {wid:<8} profile={w['profile']:<5} "
              f"served={snap.get('served', 0):<5} "
              f"draining={w['draining']}")
    _ops_finish(tracker, cache=cache)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "cnn", "moe"),
                    default="lm")
    ap.add_argument("--arch", default=None,
                    help="zoo architecture (lm: any; moe: one with MoE "
                         "blocks; default llama3.2-3b / "
                         "qwen3-moe-30b-a3b)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--device", default="v5e",
                    help="deployment-planner device profile (cnn)")
    ap.add_argument("--plan", default=None,
                    help="serve a saved DeploymentPlan JSON artifact "
                         "instead of re-planning (cnn)")
    ap.add_argument("--save-plan", default=None,
                    help="write the computed plan to this JSON path (cnn)")
    ap.add_argument("--shard", action="store_true",
                    help="shard the image batch over host devices (cnn)")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="serve through the continuous-batching gateway "
                         "under Poisson arrivals (cnn)")
    ap.add_argument("--occupancy", type=float, default=1.0,
                    help="offered load as a multiple of full-batch "
                         "service capacity (cnn --async)")
    ap.add_argument("--max-pending", type=int, default=32,
                    help="gateway admission bound — the hard cap when "
                         "--wait-budget-ms makes it adaptive "
                         "(cnn --async)")
    ap.add_argument("--wait-budget-ms", type=float, default=None,
                    help="adaptive admission: size the pending bound to "
                         "measured service rate × this wait budget "
                         "(cnn --async)")
    ap.add_argument("--max-inflight", type=int, default=1,
                    help="concurrent gateway dispatches; 2 overlaps the "
                         "next batch with the one on-device "
                         "(cnn --async)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; late requests are "
                         "expired, never served late (cnn --async)")
    ap.add_argument("--fleet", action="store_true",
                    help="serve tiered traffic through a heterogeneous "
                         "edge/v5e/v5p fleet front door (cnn)")
    ap.add_argument("--router", default="plan_aware",
                    help="fleet routing policy: plan_aware, "
                         "least_loaded, or round_robin (cnn --fleet)")
    ap.add_argument("--drain", action="store_true",
                    help="gracefully drain the v5e worker halfway "
                         "through the trace (cnn --fleet)")
    ap.add_argument("--seed", type=int, default=1,
                    help="rng seed for generated traffic (cnn --fleet)")
    ap.add_argument("--store-root", default=None, metavar="DIR",
                    help="shared store root (repro.ops.StoreRoot): one "
                         "DIR holding the plan store, the executable "
                         "cache, and worker leases — point every worker "
                         "of a fleet here so a respawn rebuilds from its "
                         "predecessor's state (replaces --plan-store "
                         "and --cache-dir)")
    ap.add_argument("--plan-store", default=None, metavar="DIR",
                    help="durable plan repository (repro.ops.PlanStore): "
                         "load the workload's plan from DIR if present, "
                         "else plan once and save it (cnn/moe, all paths)")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="persistent executable cache "
                         "(repro.ops.PersistentExecutableCache): warm "
                         "restarts deserialize their AOT executables "
                         "from DIR instead of recompiling (all paths)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="stream lifecycle events and periodic stats "
                         "snapshots to FILE as JSON lines "
                         "(repro.ops.JsonlTracker; all workloads)")
    args = ap.parse_args()
    _apply_store_root(args)
    from repro.ops import enable_jax_compilation_cache
    print(f"[serve] JAX compilation cache at "
          f"{enable_jax_compilation_cache()!r}")
    if args.arch is None:
        args.arch = ("qwen3-moe-30b-a3b" if args.workload == "moe"
                     else "llama3.2-3b")
    if args.workload == "cnn":
        if args.fleet:
            run_cnn_fleet(args)
        elif args.async_:
            run_cnn_async(args)
        else:
            run_cnn(args)
    elif args.workload == "moe":
        run_moe_async(args) if args.async_ else run_moe(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
