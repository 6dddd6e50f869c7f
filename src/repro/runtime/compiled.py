"""``CompiledModel``: AOT batch-bucketed executables for any planned
workload — with ``CompiledCNN`` as the convolution backend.

The serving hot path used to pay two avoidable costs:

* **first-request compile stalls** — ``jax.jit`` traces and compiles on
  the first call, inside the serving critical path;
* **fixed-batch padding waste** — the engine always ran the full
  ``(max_batch, ...)`` tensor, so a single live request paid for
  ``max_batch`` (16× the arithmetic at occupancy 1).

``CompiledModel`` removes both, for *every* registered workload.  At
construction (or an explicit ``warmup()``) it AOT-compiles each layer
via ``jax.jit(...).lower(...).compile()`` across a **bucket ladder** of
power-of-two batch sizes (1, 2, 4, …, max_batch), caching executables
keyed on ``(layer spec, bucket)`` — two layers with identical spec
share one executable per bucket.  A call then dispatches to the
*smallest bucket ≥ the live batch*: occupancy 1 runs the size-1
executable, occupancy 5 pads to 8, and a full pool still runs
max_batch — every shape pre-compiled, zero traces at serve time.

Subclasses supply the workload: the layer count, the per-layer compile
key/function/params, the input contract (``in_shape``/``in_dtype`` +
``validate_input``) and the canonical request generator
(``sample_inputs``).  ``CompiledCNN`` is the convolution backend;
``repro.runtime.workloads.CompiledMoE`` is the quantized
mixture-of-experts backend, and ``repro.runtime.workloads.compile_plan``
dispatches a ``DeploymentPlan`` of any registered kind to its backend.

Construction is plan-first: ``CompiledCNN.from_plan`` consumes a
``deploy.DeploymentPlan`` (including one loaded from JSON on a machine
that never ran the planner) and executes exactly the per-layer
(block, data_bits, coeff_bits) assignment the planner chose.  Outputs
are bit-exact against ``cnn_forward_ref`` — bucket padding rides along
as zero images that are sliced off, never summed.

Data parallelism: pass a device mesh and each bucket's executable runs
its layer on every device's share of the batch
(``sharding.cnn_data_parallel``: batch over the data axes when
divisible, replicated otherwise).

Multi-plan serving: executables live in an ``ExecutableCache`` — pass
one cache to several ``CompiledModel`` instances (the async gateway
does) and plans whose layer specs coincide share compiles instead of
paying per plan; CNN and MoE plans coexist in one cache because every
key leads with the workload-specific identity.  Dispatch is
cancellation-safe: ``__call__(x, should_abort=...)`` polls the callback
between layers and raises ``DispatchAborted`` instead of finishing work
nobody is waiting for, and all telemetry counters are lock-protected so
``stats()`` snapshots are consistent under the async drain thread.
"""

from __future__ import annotations

import contextlib
import threading
import time
import warnings
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.blocks import BlockLike, get_block
from repro.core.cnn import CNNConfig, cnn_layer, init_cnn
from repro.kernels import conv2d


class DispatchAborted(RuntimeError):
    """A bucketed dispatch was abandoned mid-flight: every request it
    was serving has been cancelled, so finishing the remaining layers
    would be pure waste.  Raised by ``CompiledModel.__call__`` when its
    ``should_abort`` callback returns True between layers."""


class ExecutableCache:
    """Shareable ``(layer spec, bucket) → compiled executable`` map.

    Backends key executables on the full layer identity — for a CNN
    layer (block, bits, shift, channels, geometry, mesh, bucket); for an
    MoE layer (kind, expert geometry, bits, mesh, bucket) — so the
    cache is content-addressed: two *plans* whose layers coincide can
    safely share one cache and every coinciding (layer, bucket) pair
    compiles exactly once, even across workload kinds.  The async
    gateway routes every registered plan through one ``ExecutableCache``
    for exactly this reason.

    Thread-safe and **single-flight**: lookups/inserts take a lock,
    production runs outside it, and a key already being produced by
    another thread is *waited on* (condition variable), never produced
    twice — two plans registering concurrently over coinciding layers
    pay for one compile, with the loser parked instead of burning a
    core on a duplicate build (``coalesced`` counts those waits).

    Subclass seam: ``_produce(key, build)`` turns a missing key into an
    executable (base class: call ``build()``); a disk tier like
    ``repro.ops.PersistentExecutableCache`` overrides it to try a
    deserialization load first and compile only on a true miss.
    ``on_event`` (``callable(event: str, fields: dict)``) receives the
    *rare* cache transitions — compiles and disk loads/stores/fallbacks
    — never per-dispatch memory hits, so wiring a tracker here costs
    nothing on the serving hot path.
    """

    def __init__(self, *, on_event: Optional[Callable[[str, dict],
                                                      None]] = None):
        self._execs: Dict[tuple, object] = {}
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._building: set = set()    # keys with a production in flight
        self.compiles = 0              # builds that entered the cache
        self.hits = 0                  # lookups served without building
        self.coalesced = 0             # waits piggybacked on another build
        self.on_event = on_event

    def __len__(self) -> int:
        with self._lock:
            return len(self._execs)

    def __contains__(self, key: tuple) -> bool:
        with self._lock:
            return key in self._execs

    def _emit(self, event: str, **fields) -> None:
        """Report a rare cache transition to ``on_event`` (tracker
        seam).  A misbehaving observer must never break serving."""
        cb = self.on_event
        if cb is None:
            return
        try:
            cb(event, fields)
        except Exception:              # noqa: BLE001 — observer only
            pass

    def _produce(self, key: tuple, build: Callable[[], object]
                 ) -> Tuple[object, bool]:
        """Produce the executable for a missing ``key`` — called
        outside the lock, single-flighted per key.  Returns
        ``(executable, compiled)`` where ``compiled`` says ``build()``
        actually ran (a disk tier returns False for a load)."""
        t0 = time.perf_counter()
        exe = build()
        self._emit("cache_compile", key=repr(key)[:160],
                   seconds=time.perf_counter() - t0)
        return exe, True

    def get_or_build(self, key: tuple, build: Callable[[], object]):
        with self._cond:
            while True:
                exe = self._execs.get(key)
                if exe is not None:
                    self.hits += 1
                    return exe
                if key not in self._building:
                    self._building.add(key)
                    break
                # another thread is producing this very key: wait for
                # it instead of compiling a duplicate (single-flight)
                self.coalesced += 1
                self._cond.wait()
        try:
            exe, compiled = self._produce(key, build)   # outside the lock
        except BaseException:
            with self._cond:
                # failed production frees the key: a parked waiter (or
                # the next caller) becomes the new producer and retries
                self._building.discard(key)
                self._cond.notify_all()
            raise
        with self._cond:
            self._building.discard(key)
            self._execs[key] = exe
            if compiled:
                self.compiles += 1
            self._cond.notify_all()
        return exe

    def stats(self) -> dict:
        with self._lock:
            return {"executables": len(self._execs),
                    "compiles": self.compiles, "hits": self.hits,
                    "coalesced": self.coalesced}


class _Span:
    """One open span of a ``SpanTotals`` (see ``SpanTotals.span``)."""

    __slots__ = ("_totals", "_name", "_trace", "_t0")

    def __init__(self, totals: "SpanTotals", name: str, trace):
        self._totals, self._name, self._trace = totals, name, trace

    def __enter__(self) -> "_Span":
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        self._totals.add(self._name, seconds)


class SpanTotals:
    """Host time spent inside named spans, and how many there were.

    ``with totals.span("gateway.submit", request_id=7):`` times its body
    on ``time.perf_counter`` and opens a ``jax.profiler.TraceAnnotation``
    named ``repro.gateway.submit``: a TraceMe on the profiler's own
    clock, so a traced run shows the span on the device's timeline.
    Names are static strings and keyword arguments ints, so nothing is
    formatted while the profiler is off.  ``add`` counts time that no
    one thread's span can hold (a hop between threads).

    ``snapshot()`` is ``{name: (count, seconds)}``.  Every update takes
    one lock, so the event loop and the dispatch worker never lose each
    other's counts."""

    TRACE_PREFIX = "repro."

    def __init__(self):
        self._lock = threading.Lock()
        self._totals: Dict[str, list] = {}
        self._trace_names: Dict[str, str] = {}

    def span(self, name: str, **ids: int) -> _Span:
        trace_name = self._trace_names.get(name)
        if trace_name is None:
            trace_name = self._trace_names.setdefault(
                name, self.TRACE_PREFIX + name)
        return _Span(self, name, jax.profiler.TraceAnnotation(trace_name,
                                                              **ids))

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            entry = self._totals.get(name)
            if entry is None:
                self._totals[name] = [1, seconds]
            else:
                entry[0] += 1
                entry[1] += seconds

    def snapshot(self) -> Dict[str, Tuple[int, float]]:
        with self._lock:
            return {k: (c, s) for k, (c, s) in self._totals.items()}


def count_live(totals, counts, n):
    """``totals`` (C,) plus a layer's per-request ``counts`` (bucket, C)
    summed over the dispatch's ``n`` live rows: padding rows are not
    counted.  Traced inside a counting layer (``counter_names``)."""
    live = (jnp.arange(counts.shape[0]) < n)[:, None]
    return totals + jnp.sum(jnp.where(live, counts, 0), axis=0)


def bucket_ladder(max_batch: int) -> Tuple[int, ...]:
    """Power-of-two batch buckets up to ``max_batch`` (which is always
    the top rung, even when it is not itself a power of two)."""
    if max_batch < 1:
        raise ValueError(f"max_batch={max_batch} must be ≥ 1")
    rungs = []
    b = 1
    while b < max_batch:
        rungs.append(b)
        b <<= 1
    rungs.append(max_batch)
    return tuple(rungs)


def validate_container_input(x, in_shape, in_dtype, request_id=0, *,
                             noun: str = "input") -> np.ndarray:
    """Shape + dtype admission check for integer-container workloads
    (the CNN input contract).  A float array must carry exact
    container-range integers — silent ``np.asarray(x, in_dtype)``
    truncation (0.9 → 0, 200.0 → -56 for int8) is a ``ValueError``
    here, as is any value that would wrap in the container.

    An integer dtype whose whole range fits the container (int8 into
    int8) is admitted on its dtype alone: no pass over the values.
    Otherwise the range check is two reductions, which build no
    array-sized temporary."""
    x = np.asarray(x)
    if tuple(x.shape) != tuple(in_shape):
        raise ValueError(
            f"request {request_id}: {noun} shape {tuple(x.shape)} "
            f"!= engine input {tuple(in_shape)}")
    info = np.iinfo(in_dtype)
    if np.issubdtype(x.dtype, np.integer):
        held = np.iinfo(x.dtype)
        if info.min <= held.min and held.max <= info.max:
            return x
    elif not np.all(np.isfinite(x)) or np.any(x != np.round(x)):
        raise ValueError(
            f"request {request_id}: {noun} dtype {x.dtype} "
            f"carries non-integral values — quantize explicitly "
            f"(e.g. ops.quantize_fixed) before submitting")
    if x.size and (int(x.min()) < info.min or int(x.max()) > info.max):
        raise ValueError(
            f"request {request_id}: {noun} values outside the "
            f"{np.dtype(in_dtype).name} container range "
            f"[{info.min}, {info.max}] — would wrap, not clamp")
    return x


class CompiledModel:
    """AOT-compiled, batch-bucketed executor for one planned workload.

    The generic machinery — bucket ladder, ``ExecutableCache``, AOT
    warmup, smallest-bucket dispatch with padding, chunking above
    ``max_batch``, between-layer ``should_abort`` polling, telemetry —
    lives here.  A backend subclass supplies:

    ``num_layers``            how many sequential executables a forward is
    ``in_shape``/``in_dtype`` the per-request input contract
    ``input_noun``            what a request payload is called in errors
    ``_layer_key(i, bucket)`` the full-identity cache key (incl. mesh)
    ``_layer_fn(i)``          ``(params, x) -> y`` traced per bucket
                              (with counters: ``(params, x, totals, n)
                              -> (y, totals)``)
    ``_layer_params(i)``      the pytree passed as ``params``
    ``_layer_in_sds(i, b)``   the ShapeDtypeStruct the layer is lowered at
    ``_empty_output()``       the zero-batch result
    ``_place_batch(xb, b)``   optional device placement (mesh sharding)
    ``sample_inputs(k)``      canonical request generator
    ``validate_input(x)``     per-workload admission check
    ``counter_names``         counts each layer returns beside its output
    """

    kind = "model"                 # registry name of the workload
    input_noun = "input"           # request payload, as named in errors
    #: names of the int32 counts that the layer executables keep: each
    #: takes the running totals ``(len(counter_names),)`` and the
    #: dispatch's live row count ``n`` beside its input, and returns
    #: its output and the totals with its own counts of the live rows
    #: added (``count_live``).  The totals stay on the device, with no
    #: host sync per dispatch, and ``stats()`` reads them by these
    #: names.  Empty: a layer maps its input alone (the CNN).
    counter_names: Tuple[str, ...] = ()

    # subclass contract: these must be set before delegating to
    # ``CompiledModel.__init__`` (warmup compiles through them)
    num_layers: int
    in_shape: Tuple[int, ...]
    in_dtype = None

    def __init__(self, *, max_batch: int = 16, mesh=None,
                 warmup: bool = True,
                 exec_cache: Optional[ExecutableCache] = None):
        self.max_batch = max_batch
        self.buckets = bucket_ladder(max_batch)
        self.mesh = mesh
        # executables shard differently per mesh, so the mesh is part of
        # the cache key.  The mesh object itself (hashable, compared by
        # devices + axis names) — not id(), whose recycled addresses
        # could alias two different meshes in a long-lived shared cache
        self._mesh_token = mesh

        # (layer key, bucket) → compiled executable; identical layer
        # specs share one compile per bucket — across *instances* too
        # when an ``exec_cache`` is passed in (multi-plan serving)
        self.cache = exec_cache if exec_cache is not None \
            else ExecutableCache()
        self.compiles = 0              # compiles this instance performed
        self.bucket_hits: Dict[int, int] = {b: 0 for b in self.buckets}
        self.calls = 0
        self.rows = 0                  # rows the buckets ran, padding too
        self.padded_rows = 0           # of which padding
        self.spans = SpanTotals()      # executor.* host time
        self._stats_lock = threading.Lock()
        # the counters' totals, advanced by one dispatch at a time
        self._counts = jnp.zeros(len(self.counter_names), jnp.int32)
        self._counts_lock = threading.Lock()
        if warmup:
            self.warmup()

    # -- backend hooks ----------------------------------------------------
    def _layer_key(self, i: int, bucket: int) -> tuple:
        raise NotImplementedError

    def _layer_fn(self, i: int):
        """The traceable ``(params, x) -> y`` for layer ``i``."""
        raise NotImplementedError

    def _layer_params(self, i: int):
        raise NotImplementedError

    def _layer_in_sds(self, i: int, bucket: int) -> jax.ShapeDtypeStruct:
        raise NotImplementedError

    def _empty_output(self):
        raise NotImplementedError

    def _place_batch(self, xb, bucket: int):
        """Optional pre-dispatch device placement (mesh sharding)."""
        return xb

    def sample_inputs(self, k: int, seed: int = 0):
        """``k`` random requests matching this executor's input contract
        (shape + dtype) — the canonical workload generator shared by the
        launcher, benchmarks, and examples, so the input rules live in
        one place."""
        raise NotImplementedError

    def validate_input(self, x, request_id: int = 0) -> np.ndarray:
        """Admission check: shape + dtype-compatibility.  Backends
        override to enforce their quantization contract (the CNN
        backend rejects non-integral floats and container overflow; the
        MoE backend rejects non-finite activations)."""
        x = np.asarray(x)
        if tuple(x.shape) != tuple(self.in_shape):
            raise ValueError(
                f"request {request_id}: {self.input_noun} shape "
                f"{tuple(x.shape)} != engine input {tuple(self.in_shape)}")
        return x

    # -- AOT compilation --------------------------------------------------
    def _compile_layer(self, i: int, bucket: int):
        def build():
            fn = self._layer_fn(i)
            w_sds = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._layer_params(i))
            with self._stats_lock:
                self.compiles += 1
            return jax.jit(fn).lower(w_sds, self._layer_in_sds(i, bucket),
                                     *self._counter_sds()).compile()

        return self.cache.get_or_build(self._layer_key(i, bucket), build)

    def _counter_sds(self) -> list:
        """A counting layer's arguments after its input: the totals and
        the live row count (``counter_names``); none for the others."""
        if not self.counter_names:
            return []
        return [jax.ShapeDtypeStruct(self._counts.shape, jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)]

    def warmup(self) -> "CompiledModel":
        """AOT-compile every (layer, bucket) executable now, so no call
        ever compiles on the serving critical path."""
        for b in self.buckets:
            for i in range(self.num_layers):
                self._compile_layer(i, b)
        return self

    @property
    def warmed_up(self) -> bool:
        return all(self._layer_key(i, b) in self.cache
                   for b in self.buckets
                   for i in range(self.num_layers))

    # -- dispatch ----------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket ≥ n (n must be ≤ max_batch)."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"batch {n} exceeds max_batch={self.max_batch}")

    def _run_bucket(self, xb, should_abort=None):
        """xb: (n, *in_shape) with n ≤ max_batch → (n, *out_shape)."""
        n = xb.shape[0]
        bucket = self.bucket_for(n)
        if n < bucket:
            with self.spans.span("executor.pad"):
                pad = jnp.zeros((bucket - n,) + xb.shape[1:], xb.dtype)
                xb = jnp.concatenate([xb, pad])
        xb = self._place_batch(xb, bucket)
        act = xb
        # a counting dispatch advances the totals layer by layer, so
        # dispatches that overlap take their turns
        with (self._counts_lock if self.counter_names
              else contextlib.nullcontext()):
            totals = self._counts
            for i in range(self.num_layers):
                if should_abort is not None and should_abort():
                    raise DispatchAborted(
                        f"dispatch abandoned before layer {i} "
                        f"(all served requests cancelled)")
                # dispatch is asynchronous: this times the host's launch
                with self.spans.span("executor.launch", layer=i):
                    exe = self._compile_layer(i, bucket)
                    if self.counter_names:
                        act, totals = exe(self._layer_params(i), act,
                                          totals, np.int32(n))
                    else:
                        act = exe(self._layer_params(i), act)
            with self._stats_lock:
                self._counts = totals
                self.bucket_hits[bucket] += 1
                self.rows += bucket
                self.padded_rows += bucket - n
        return act[:n]

    def __call__(self, x, *, should_abort=None):
        """x: one ``in_shape`` request or an ``(N, *in_shape)`` batch.
        Batches larger than ``max_batch`` run in max_batch-sized chunks
        (the tail dispatching to its own bucket).

        ``should_abort`` (optional zero-arg callable) is polled between
        layers; returning True raises ``DispatchAborted`` — the async
        gateway's cancellation hook, so a flight whose every request was
        cancelled mid-execution stops paying for the remaining layers."""
        with self.spans.span("executor.h2d"):
            x = jnp.asarray(x)
        single = x.ndim == len(self.in_shape)
        if single:
            x = x[None]
        if x.shape[1:] != tuple(self.in_shape):
            raise ValueError(
                f"{self.input_noun} shape {tuple(x.shape[1:])} != "
                f"compiled input {tuple(self.in_shape)}")
        if x.dtype != self.in_dtype:
            raise ValueError(
                f"{self.input_noun} dtype {x.dtype} != compiled input "
                f"{np.dtype(self.in_dtype).name}")
        with self._stats_lock:
            self.calls += 1
        if x.shape[0] == 0:            # empty queue tick: nothing to run
            return self._empty_output()
        outs = [self._run_bucket(x[s:s + self.max_batch], should_abort)
                for s in range(0, x.shape[0], self.max_batch)]
        y = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        return y[0] if single else y

    # -- observability -----------------------------------------------------
    def stats(self) -> dict:
        """Dispatch + compile telemetry.  ``executables``/``cache_*``
        describe the (possibly shared) ``ExecutableCache``; ``compiles``
        counts builds *this instance* performed — with a shared cache,
        a second plan over identical layers reports 0.  ``rows`` and
        ``padded_rows`` count the rows the buckets ran and how many of
        them were padding; ``spans`` is the executor's host time per
        span (``SpanTotals.snapshot``); each of ``counter_names`` gives
        its sum over every dispatch so far (reading it waits for the
        last dispatch: ``executor.counts``).  Snapshot is
        lock-consistent under the async drain."""
        with self._stats_lock:
            hits = dict(self.bucket_hits)
            calls = self.calls
            compiles = self.compiles
            rows, padded_rows = self.rows, self.padded_rows
            totals = self._counts
        counted = {}
        if self.counter_names:
            with self.spans.span("executor.counts"):
                counted = dict(zip(self.counter_names,
                                   np.asarray(totals).tolist()))
        cache = self.cache.stats()
        return {**counted,
            "kind": self.kind,
            "buckets": list(self.buckets),
            "bucket_hits": hits,
            "executables": cache["executables"],
            "compiles": compiles,
            "cache_compiles": cache["compiles"],
            "cache_hits": cache["hits"],
            "calls": calls,
            "rows": rows,
            "padded_rows": padded_rows,
            "spans": self.spans.snapshot(),
            "warmed_up": self.warmed_up,
        }


class CompiledCNN(CompiledModel):
    """The convolution backend: AOT-compiled, batch-bucketed executor
    for one planned CNN deployment.  Bit-exact vs ``cnn_forward_ref``
    at every batch size."""

    kind = "cnn"
    input_noun = "image"

    def __init__(self, cfg: CNNConfig, params, blocks: Sequence[BlockLike],
                 *, max_batch: int = 16, mesh=None, warmup: bool = True,
                 exec_cache: Optional[ExecutableCache] = None):
        blocks = [get_block(b) for b in blocks]
        if len(blocks) != len(cfg.layers):
            raise ValueError(
                f"need one block per layer: {len(blocks)} blocks "
                f"for {len(cfg.layers)} layers")
        self.cfg = cfg
        self.params = params
        self.blocks = blocks
        self.num_layers = len(cfg.layers)

        spec0 = cfg.layers[0]
        self.in_shape = (cfg.img_h, cfg.img_w, spec0.in_channels)
        self.in_dtype = conv2d.container_dtype(spec0.data_bits)
        super().__init__(max_batch=max_batch, mesh=mesh, warmup=warmup,
                         exec_cache=exec_cache)

    # -- construction from a deployment plan -----------------------------
    @classmethod
    def from_plan(cls, plan, cfg: Optional[CNNConfig] = None, *,
                  params=None, key=None, max_batch: int = 16, mesh=None,
                  warmup: bool = True,
                  exec_cache: Optional[ExecutableCache] = None
                  ) -> "CompiledCNN":
        """Executor for a planned deployment: each layer runs the
        (block, bits) the planner assigned.  ``cfg`` defaults to the
        network embedded in the plan (always present on planner output
        and on plans loaded from JSON); ``params`` default to a fresh
        ``init_cnn`` draw at the planned precisions."""
        from repro.core import deploy
        pcfg = deploy.plan_config(plan, cfg)
        if params is None:
            key = key if key is not None else jax.random.PRNGKey(0)
            params = init_cnn(key, pcfg)
        return cls(pcfg, params, plan.block_names(), max_batch=max_batch,
                   mesh=mesh, warmup=warmup, exec_cache=exec_cache)

    @classmethod
    def from_json(cls, text: str, **kw) -> "CompiledCNN":
        """Executor straight from a serialized plan artifact."""
        from repro.core import deploy
        return cls.from_plan(deploy.DeploymentPlan.from_json(text), **kw)

    # -- backend hooks ----------------------------------------------------
    def _layer_key(self, i: int, bucket: int) -> tuple:
        spec = self.cfg.layers[i]
        return (self.blocks[i].name, spec.data_bits, spec.coeff_bits,
                spec.shift, spec.in_channels, spec.out_channels,
                self.cfg.img_h, self.cfg.img_w, self._mesh_token, bucket)

    def _layer_fn(self, i: int):
        spec, block = self.cfg.layers[i], self.blocks[i]
        fn = cnn_layer(spec, block, self.mesh)
        # the executable's name in a trace, e.g. jit_cnn_conv3_d8c8_64to128:
        # from the layer's content, as the cache key is, since identical
        # layers share one executable
        fn.__name__ = (f"cnn_{block.name}_d{spec.data_bits}"
                       f"c{spec.coeff_bits}_{spec.in_channels}to"
                       f"{spec.out_channels}")
        return fn

    def _layer_params(self, i: int):
        return self.params[i]

    def _layer_in_sds(self, i: int, bucket: int) -> jax.ShapeDtypeStruct:
        spec = self.cfg.layers[i]
        return jax.ShapeDtypeStruct(
            (bucket, self.cfg.img_h, self.cfg.img_w, spec.in_channels),
            conv2d.container_dtype(spec.data_bits))

    def _empty_output(self):
        last = self.cfg.layers[-1]
        return jnp.zeros(
            (0, self.cfg.img_h, self.cfg.img_w, last.out_channels),
            conv2d.container_dtype(last.data_bits))

    def _place_batch(self, xb, bucket: int):
        if self.mesh is not None:
            from repro.parallel.sharding import cnn_batch_sharding
            xb = jax.device_put(xb, cnn_batch_sharding(self.mesh, bucket))
        return xb

    # -- workload helpers --------------------------------------------------
    def sample_inputs(self, k: int, seed: int = 0):
        """``k`` random quantized images matching this executor's input
        contract (shape + container dtype)."""
        from repro.kernels import ops
        rng = np.random.default_rng(seed)
        d0 = self.cfg.layers[0].data_bits
        return [np.asarray(ops.quantize_fixed(
            rng.integers(0, 1 << (d0 - 1),
                         self.in_shape).astype(np.float32), d0))
            for _ in range(k)]

    def sample_images(self, k: int, seed: int = 0):
        """.. deprecated:: use the workload-generic ``sample_inputs``."""
        warnings.warn(
            "CompiledCNN.sample_images is deprecated; use the "
            "workload-generic CompiledModel.sample_inputs",
            DeprecationWarning, stacklevel=2)
        return self.sample_inputs(k, seed)

    def validate_input(self, x, request_id: int = 0) -> np.ndarray:
        return validate_container_input(
            x, self.in_shape, self.in_dtype, request_id,
            noun=self.input_noun)
