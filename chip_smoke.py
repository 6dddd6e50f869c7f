#!/usr/bin/env python3
"""Smoke run of the served path on a TPU: plan → ``compile_plan`` →
gateway → device, through the entry points a user calls.

    python chip_smoke.py              # one chip: CNN plans + MoE plan
    python chip_smoke.py --chips 4    # the batch-sharded CNN on 4 chips

One chip runs three phases in one process:

* **device** — what JAX sees; anything but a TPU exits non-zero (there
  is no CPU fallback);
* **cnn** — the quickstart CNN planned for the ``v5e`` profile, and the
  same network with every layer pinned to ``conv1`` (the compiled Pallas
  kernel, including its narrow-accumulator regime), both served by one
  ``AsyncCNNGateway``; every output must equal ``cnn_forward_ref`` bit
  for bit, and the conv1 executables must hold a ``tpu_custom_call``;
* **moe** — Qwen3-30B-A3B's MoE layers at published widths (d_model
  2048, 128 experts, top-8, expert d_ff 768) planned for ``v5e`` and
  served by a gateway; outputs must match the eager quantized stack
  within ``validate_moe_plan``'s tolerance.

``--chips 4`` runs only the CNN served batch-sharded over every device
(``CompiledCNN.from_plan(..., mesh=cnn_data_mesh())``), for both CNN
plans, and compares each bit for bit with the same plan on one device.

Times printed here are host-clock timings of a smoke run, not device
metrics.  The last line of standard output is one JSON object naming
the device; it is printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
CNN_IMAGES = 16                  # per plan
CNN_MAX_BATCH = 8
MOE_ARCH = "qwen3-moe-30b-a3b"
MOE_LAYERS = 2
MOE_BLOCKS = 8
MOE_MAX_BATCH = 4
MOE_RTOL = MOE_ATOL = 1e-5       # validate_moe_plan's tolerance


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info():
    """``(platform, kind, count)`` of the default backend, after printing
    what JAX found."""
    import jax
    devices = jax.devices()
    log(f"[device] {devices}")
    d0 = devices[0]
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    return d0.platform, d0.device_kind, len(devices)


async def _serve(gw, requests):
    """Submit ``(plan_id, input)`` pairs to ``gw``; outputs in order."""
    async with gw:
        futs = [await gw.submit(x, plan_id=pid) for pid, x in requests]
        return [await f for f in futs]


def _cnn_reference(compiled, images):
    """``cnn_forward_ref`` on each image, jitted once per network."""
    import jax
    import numpy as np
    from repro.core.cnn import cnn_forward_ref

    cfg = compiled.cfg
    ref = jax.jit(lambda p, x: cnn_forward_ref(p, x, cfg))
    return [np.asarray(ref(compiled.params, x)) for x in images]


def _has_kernel(compiled) -> bool:
    """Whether every layer executable of ``compiled`` holds a compiled
    Pallas kernel (``tpu_custom_call``), at every bucket."""
    return all("tpu_custom_call" in compiled._compile_layer(i, b).as_text()
               for b in compiled.buckets
               for i in range(compiled.num_layers))


def _cnn_plans(tag: str) -> dict:
    """The quickstart CNN planned for ``v5e``, and the same network with
    every layer pinned to conv1 (the compiled Pallas kernel)."""
    from repro.core.cnn import CNNConfig, quickstart_cnn_config
    from repro.launch.serve import quickstart_cnn_plan

    cfg = quickstart_cnn_config()
    plans = {
        "v5e": quickstart_cnn_plan("v5e"),
        "conv1": quickstart_cnn_plan("v5e", CNNConfig(
            layers=tuple(dataclasses.replace(s, block="conv1")
                         for s in cfg.layers),
            img_h=cfg.img_h, img_w=cfg.img_w)),
    }
    for pid, plan in plans.items():
        log(f"[{tag}] plan {pid}: " + ", ".join(
            f"L{a.index}={a.block}@d{a.data_bits}/c{a.coeff_bits}"
            for a in plan.layers))
    return plans


def cnn_phase() -> None:
    import jax
    import numpy as np
    from repro.serve import AsyncCNNGateway, AsyncServeConfig

    t0 = time.perf_counter()
    plans = _cnn_plans("cnn")
    t_plan = time.perf_counter()

    gw = AsyncCNNGateway(AsyncServeConfig(max_batch=CNN_MAX_BATCH))
    for pid, plan in plans.items():
        gw.register_plan(plan, plan_id=pid, key=jax.random.PRNGKey(SEED))
    t_compile = time.perf_counter()
    log(f"[cnn] compiled {gw.exec_cache.stats()['compiles']} executables "
        f"in {t_compile - t_plan:.3f}s (planning {t_plan - t0:.3f}s)")

    images = {pid: gw.plans[pid].compiled.sample_inputs(CNN_IMAGES, SEED)
              for pid in plans}
    requests = [(pid, x) for pid in plans for x in images[pid]]
    outs = asyncio.run(_serve(gw, requests))
    t_serve = time.perf_counter()
    log(f"[cnn] served {len(outs)} images in {t_serve - t_compile:.3f}s")

    failures = []
    for k, pid in enumerate(plans):
        compiled = gw.plans[pid].compiled
        got = outs[k * CNN_IMAGES:(k + 1) * CNN_IMAGES]
        want = _cnn_reference(compiled, images[pid])
        bad = sum(not np.array_equal(np.asarray(g), w)
                  for g, w in zip(got, want))
        log(f"[cnn] plan {pid}: {CNN_IMAGES - bad}/{CNN_IMAGES} outputs "
            f"bit-exact against cnn_forward_ref")
        if bad:
            failures.append(f"plan {pid}: {bad} outputs differ")
    kernel = _has_kernel(gw.plans["conv1"].compiled)
    log(f"[cnn] conv1 plan executables hold tpu_custom_call: {kernel}")
    if not kernel:
        failures.append("conv1 executables hold no compiled Pallas kernel")
    log(f"[cnn] phase wall {time.perf_counter() - t0:.3f}s")
    if failures:
        raise AssertionError("; ".join(failures))


def _log_memory(when: str) -> None:
    """Device memory now and its high-water mark so far."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[moe] {when}: bytes_in_use {stats.get('bytes_in_use')}, "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}")


def moe_phase() -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.runtime import moe_workload_from_config, plan_moe_deployment
    from repro.runtime.workloads import _eager_forward
    from repro.serve import AsyncCNNGateway, AsyncServeConfig

    t0 = time.perf_counter()
    spec = moe_workload_from_config(get_config(MOE_ARCH),
                                    n_layers=MOE_LAYERS)
    layer = spec.layers[0]
    log(f"[moe] {MOE_ARCH}: {len(spec.layers)} layers, d_model "
        f"{spec.d_model}, {layer.num_experts} experts, top-"
        f"{layer.top_k}, expert d_ff {layer.d_ff_expert}, seq_len "
        f"{spec.seq_len}")
    # as the launcher plans it: the least-demanding bits when no
    # candidate fits the profile's budgets
    plan = plan_moe_deployment(spec, "v5e", target=0.8,
                               on_infeasible="fallback")
    log("[moe] plan v5e: " + ", ".join(
        f"L{a.index}=d{a.data_bits}/c{a.coeff_bits}" for a in plan.layers)
        + f" (feasible={plan.feasible}, quant rel-err "
        f"{plan.quant_error:.6f})")
    _log_memory("after planning")
    t_plan = time.perf_counter()

    gw = AsyncCNNGateway(AsyncServeConfig(max_batch=MOE_MAX_BATCH))
    pid = gw.register_plan(plan, plan_id="moe",
                           key=jax.random.PRNGKey(SEED))
    compiled = gw.plans[pid].compiled
    t_compile = time.perf_counter()
    log(f"[moe] compiled {gw.exec_cache.stats()['compiles']} executables "
        f"in {t_compile - t_plan:.3f}s (planning {t_plan - t0:.3f}s)")
    _log_memory("after compiling")

    blocks = compiled.sample_inputs(MOE_BLOCKS, SEED)
    outs = asyncio.run(_serve(gw, [(pid, x) for x in blocks]))
    t_serve = time.perf_counter()
    log(f"[moe] served {len(outs)} token blocks in "
        f"{t_serve - t_compile:.3f}s")

    want = np.asarray(_eager_forward(compiled.spec, compiled.params,
                                     jax.numpy.asarray(np.stack(blocks))))
    got = np.stack([np.asarray(o) for o in outs])
    close = np.isclose(got, want, rtol=MOE_RTOL, atol=MOE_ATOL)
    ok = bool(close.all())
    log(f"[moe] gateway vs eager stack: max |diff| "
        f"{float(np.max(np.abs(got - want)))}, {int((~close).sum())} of "
        f"{close.size} values outside rtol={MOE_RTOL} atol={MOE_ATOL}, "
        f"finite={bool(np.isfinite(got).all())}")
    _log_memory("after serving and the eager stack")
    log(f"[moe] phase wall {time.perf_counter() - t0:.3f}s")
    if not ok:
        raise AssertionError("MoE gateway outputs differ from the eager "
                             "quantized stack")


def sharded_cnn_phase() -> None:
    import jax
    import numpy as np
    from repro.parallel.sharding import cnn_data_mesh
    from repro.runtime import CompiledCNN

    t0 = time.perf_counter()
    mesh = cnn_data_mesh()
    log(f"[shard] mesh {dict(mesh.shape)}")
    key = jax.random.PRNGKey(SEED)
    failures = []
    for pid, plan in _cnn_plans("shard").items():
        t1 = time.perf_counter()
        sharded = CompiledCNN.from_plan(plan, key=key, mesh=mesh,
                                        max_batch=CNN_MAX_BATCH)
        single = CompiledCNN.from_plan(plan, key=key,
                                       max_batch=CNN_MAX_BATCH)
        log(f"[shard] plan {pid}: compiled "
            f"{sharded.compiles + single.compiles} executables in "
            f"{time.perf_counter() - t1:.3f}s")
        # a full bucket splits over the devices; the tail bucket of 3
        # does not divide them and runs replicated
        x = np.stack(single.sample_inputs(2 * CNN_MAX_BATCH + 3, SEED))
        same = bool(np.array_equal(np.asarray(sharded(x)),
                                   np.asarray(single(x))))
        log(f"[shard] plan {pid}: {len(x)} images over "
            f"{mesh.devices.size} devices bit-exact against one device: "
            f"{same}")
        if not same:
            failures.append(f"plan {pid}: sharded differs from one device")
    log(f"[shard] phase wall {time.perf_counter() - t0:.3f}s")
    if failures:
        raise AssertionError("; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the batch-sharded CNN over four "
                         "chips, compared with one chip")
    args = ap.parse_args(argv)

    platform, kind, count = device_info()
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX default backend is "
              f"{platform!r}); this smoke run needs a chip",
              file=sys.stderr)
        return 2
    if count != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {count} "
              f"TPU device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.ops import enable_jax_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    log(f"[device] JAX compilation cache at "
        f"{enable_jax_compilation_cache()!r}")

    phases = ([("shard", sharded_cnn_phase)] if args.chips == 4
              else [("cnn", cnn_phase), ("moe", moe_phase)])
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
            log(f"[{name}] PASS ({time.perf_counter() - t0:.3f}s)")
        except Exception:            # noqa: BLE001 — reported, then fails
            traceback.print_exc()
            log(f"[{name}] FAIL ({time.perf_counter() - t0:.3f}s)")
            failed.append(name)
    if failed:
        print(f"chip_smoke: phases failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    log(json.dumps({"ok": True, "device": {"platform": platform,
                                           "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
