"""One run of one cell: set-up, the measured window, the check, and the
result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:

* configuration ``<name>`` → its ``file`` (JSON), whose ``family``
  names ``chipbench/families/<family>.py``;
* traffic ``<name>`` → ``chipbench/traffic/<name>.json``, read by
  ``chipbench/generator.py``;
* per-layer metric ``<name>`` → ``chipbench/metrics/<name>.py``, whose
  ``read(ctx)`` returns the value or None when it finds nothing.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import re
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import generator, trace as trace_mod

HERE = Path(__file__).resolve().parent
OUT_DIR = ".chipbench_out"


class Refused(RuntimeError):
    """The run cannot produce a result (no chip, no program, bad cell)."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise Refused(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    family: object                   # module with a ``Model`` class
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, object] = field(default_factory=dict)


def resolve(root: Path, workload: str, manifest: Optional[dict] = None
            ) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, with its
    configuration, traffic, family and metric readers loaded by name."""
    if manifest is None:
        manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; BENCHMARK.json "
                      f"has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())
    family = load_module(HERE / "families" / f"{config['family']}.py",
                         f"chipbench.families.{config['family']}")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if applies(m) and m["moves"] in reported]
    readers = {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py",
                                      f"chipbench_metric_{m['name']}")
               for m in per_layer}
    return Cell(workload, w["chips"], w["config"], config, traffic, family,
                e2e, per_layer, readers)


def check_devices(chips: int):
    """The devices a cell runs on; ``Refused`` without a TPU or with
    fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's default backend is "
                      f"{devices[0].platform!r}; this benchmark measures "
                      f"the chip and has no CPU fallback")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devices)}")
    return devices[:chips]


def import_program(root: Path):
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"the program under test is not in {src}")
    sys.path.insert(0, str(src))
    import repro  # noqa: F401


def compile_cache(root: Path) -> Path:
    """Keep JAX's persistent compilation cache in the checkout, at a
    fixed path (part of the cache's key), whatever
    ``JAX_COMPILATION_CACHE_DIR`` says: two checkouts measured side by
    side share no cache.  Every program goes in, however fast it
    compiled, so only a checkout's first run of a cell compiles.  Nothing
    is evicted (the directory holds one checkout's programs): under a
    size limit from the environment JAX evicts by access-time files, and
    one entry without its file turns every later write away."""
    import jax
    path = root / ".jax_cache"
    path.mkdir(exist_ok=True)       # JAX writes no entry without it
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def apply_precision(config: dict, log) -> None:
    """Run the program at the matmul precision its configuration states
    (``matmul_precision``), where it states one: JAX's process-wide
    default, the only option the program has.  On a TPU that default is
    otherwise one bfloat16 pass for float32 operands."""
    precision = config.get("matmul_precision")
    if precision is None:
        return
    import jax
    jax.config.update("jax_default_matmul_precision", precision)
    log(f"[setup] matmul precision {precision!r}, as the configuration "
        f"states, for the whole process")


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The ``q``-quantile by nearest rank (q in (0, 1])."""
    v = np.sort(values)
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


@dataclass
class TraceContext:
    """What a per-layer metric reader may read: the reduced trace, a few
    quantities derived for the readers there are, and the whole run
    besides, so that a new reader needs only its own file."""
    summary: "trace_mod.Summary"
    device_kind: str
    chips: int
    ops_bits: int
    dispatches: Dict[int, int]       # live batch size → dispatches
    dispatch_work: Callable          # n → [work.Work per layer]
    request_ops: float               # model operations per request
    answers: int                     # answers that arrived in the span
    span_s: float                    # host-clock length of the span
    layer_modules: frozenset         # names of the layer executables
    outcome: "generator.Outcome"     # every request of the window
    marks: dict                      # gateway snapshot and executor
    #                                  stats at the span's start and stop
    model: object                    # the family's Model
    compiled: object                 # the program's compiled model
    cell: Cell


class Session:
    """One cell set up for runs: the program imported, the model's plan
    registered on a gateway with the benchmark's weights, the input
    pool drawn and every bucket executed once."""

    def __init__(self, root: Path, cell: Cell, seed: int, *,
                 require_chip: bool = True, log=print):
        import jax
        self.root, self.cell, self.seed, self.log = root, cell, seed, log
        self.devices = (check_devices(cell.chips) if require_chip
                        else jax.devices()[:cell.chips])
        import_program(root)
        from repro.serve import AsyncCNNGateway, AsyncServeConfig

        log(f"[setup] JAX compilation cache at {compile_cache(root)}")
        apply_precision(cell.config, log)

        self.model = cell.family.Model(cell.config, seed, root)
        self.gateway = AsyncCNNGateway(
            AsyncServeConfig(**cell.traffic["gateway"]))
        self.plan_id = self.model.register(self.gateway)
        self.compiled = self.gateway.plans[self.plan_id].compiled
        log(f"[setup] plan {self.model.describe()}; "
            f"{self.gateway.exec_cache.stats()['compiles']} executables")
        self.pool = self.model.inputs(cell.traffic["input_pool"])
        # every live batch size once: each bucket, and the padding and
        # slicing a partial batch compiles op by op on first use
        for n in range(1, self.compiled.max_batch + 1):
            np.asarray(self.compiled(np.stack(self.pool[:n])))

    def window(self, seconds: float, trace: bool):
        """Drive the traffic's warm-up and window; returns the
        ``generator.Outcome`` and, traced, the per-layer readings."""
        import jax
        cache = self.gateway.exec_cache
        compiles = cache.stats()["compiles"]
        annotate = tracer = None
        logdir = self.root / OUT_DIR / "trace" / self.cell.name
        if trace:
            shutil.rmtree(logdir, ignore_errors=True)
            tracer = _profiler(logdir, self.gateway)
            annotate = jax.profiler.TraceAnnotation
        out = generator.Traffic(self.cell.traffic, self.seed, self.pool).run(
            self.gateway, self.plan_id, seconds, annotate=annotate,
            trace=tracer)
        if cache.stats()["compiles"] != compiles:
            raise RuntimeError("the program compiled inside the window")
        per_layer = (_per_layer(self.cell, self.model, self.compiled,
                                logdir, tracer.marks, out, self.devices)
                     if trace else None)
        return out, per_layer

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def close_program(self) -> None:
        """Free the program's state before the reference runs."""
        self.gateway = self.compiled = None
        gc.collect()

    def numbers(self, out, control: bool = False) -> dict:
        """The compared numbers of the sampled answers against the plain
        reference; with ``control`` the reference at the precision
        below stands in for the answers."""
        limits = self.cell.config["check"]["limits"]
        if not out.sampled:
            return {k: math.inf for k in limits}
        xs = np.stack([self.pool[i] for i, _ in out.sampled])
        want = self.model.reference(xs)
        got = (self.model.reference(xs, control=True) if control
               else np.stack([a for _, a in out.sampled]))
        return self.model.compare(got, want, xs)


def run(root: Path, cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, control: bool = False, require_chip: bool = True,
        log=print) -> dict:
    """One run; returns the result object (the last stdout line)."""
    session = Session(root, cell, seed, require_chip=require_chip, log=log)
    out, per_layer = session.window(seconds, trace)
    setup_s = out.t0 - t_start
    memory_peak = session.memory_peak()
    session.close_program()
    limits = cell.config["check"]["limits"]
    numbers = session.numbers(out, control)
    failed = int(np.sum(~np.isfinite(out.done)))
    correct = (all(numbers[k] <= v for k, v in limits.items())
               and failed == out.shed)

    d0 = session.devices[0]
    result = {"correct": bool(correct), "attempted": int(len(out.due)),
              "failed": failed}
    if trace:
        result["metrics"] = per_layer["metrics"]
    else:
        lat = np.where(np.isfinite(out.done), out.done - out.due,
                       out.t1 + generator.ANSWER_WAIT_S - out.due)
        values = {"throughput": out.answers_in_window / seconds,
                  "p50_ms": 1e3 * nearest_rank(lat, 0.50),
                  "p95_ms": 1e3 * nearest_rank(lat, 0.95),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(session.devices),
                        "memory_peak_bytes": memory_peak}
    if trace:
        result["device"].update(busy_s=per_layer["busy_s"],
                                window_s=per_layer["window_s"])
        result["breakdown"] = per_layer["breakdown"]
    lag = (1e3 * nearest_rank(out.lag_s, 0.95) if len(out.lag_s) else 0.0)
    log(f"[run] {len(out.due)} requests in the window, "
        f"{out.answers_in_window} answers in it, {failed} failed "
        f"({out.shed} shed), {len(out.sampled)} answers checked, "
        f"setup {setup_s:.3f}s, generator lag p95 {lag:.3f} ms")
    result["check"] = {k: {"value": numbers[k], "limit": v}
                       for k, v in limits.items()}
    return result


def _profiler(logdir: Path, gw):
    """``tracer(start)`` for the generator: starts or stops the JAX
    profiler and returns the host clock inside the profiled span, with
    the gateway's snapshot and the executors' stats at each end."""
    import jax
    marks = {}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # Python calls untraced: cheap

    def tracer(start: bool) -> float:
        if start:
            jax.profiler.start_trace(str(logdir),
                                     profiler_options=options)
            marks["start"] = _marks(gw)
            return time.perf_counter()
        t = time.perf_counter()
        marks["stop"] = _marks(gw)
        jax.profiler.stop_trace()
        return t

    tracer.marks = marks
    return tracer


def _marks(gw) -> dict:
    return {"gateway": gw.snapshot(),
            "stats": {pid: p.compiled.stats()
                      for pid, p in gw.plans.items()}}


def _layer_modules(compiled) -> frozenset:
    """The HLO module names of every layer executable."""
    names = set()
    for b in compiled.buckets:
        for i in range(compiled.num_layers):
            m = re.match(r"HloModule (\S+?),",
                         compiled._compile_layer(i, b).as_text())
            if m:
                names.add(m.group(1))
    return frozenset(names)


def _per_layer(cell, model, compiled, logdir, marks, out, devices
               ) -> dict:
    summary = trace_mod.reduce_dir(logdir, len(devices))
    shutil.rmtree(logdir, ignore_errors=True)
    h0 = marks["start"]["gateway"].occupancy_hist
    h1 = marks["stop"]["gateway"].occupancy_hist
    dispatches = {n: h1.get(n, 0) - h0.get(n, 0)
                  for n in h1 if h1.get(n, 0) > h0.get(n, 0)}
    start, stop = out.trace_span
    answers = int(np.sum((out.done >= start) & (out.done < stop)))
    ctx = TraceContext(
        summary=summary, device_kind=devices[0].device_kind,
        chips=len(devices), ops_bits=model.ops_bits,
        dispatches=dispatches, dispatch_work=model.dispatch_work,
        request_ops=sum(w.ops for w in model.dispatch_work(1)),
        answers=answers, span_s=stop - start,
        layer_modules=_layer_modules(compiled), outcome=out, marks=marks,
        model=model, compiled=compiled, cell=cell)
    metrics = {}
    for m in cell.per_layer:
        v = cell.readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return {"metrics": metrics, "busy_s": summary.busy_s,
            "window_s": summary.window_s,
            "breakdown": summary.breakdown()}
