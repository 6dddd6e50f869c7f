"""Workload registry: typed ``WorkloadSpec``s behind ``DeploymentPlan``.

A deployment plan used to *be* a CNN plan — ``ConvLayerSpec`` was wired
through the planner, the AOT runtime, the gateway, and the fleet.  This
module is the seam that breaks that coupling: a plan now carries a
typed, versioned **workload spec** (schema v2), and every layer above
the kernels speaks the spec's protocol instead of assuming images:

``WorkloadSpec``     the protocol: a frozen, JSON-round-trippable
                     description of *what* is being served (network
                     geometry + per-layer quantization), with a
                     ``compile`` hook that builds the matching
                     ``CompiledModel`` backend for a plan.
``register_workload``/``get_workload``/``list_workloads``
                     the kind → spec-class registry ``DeploymentPlan``
                     serialization dispatches through.
``CNNWorkloadSpec``  wraps the embedded ``CNNConfig`` — v1 plans
                     upgrade to this spec bit-identically.
``MoEWorkloadSpec``  quantized mixture-of-experts inference: expert
                     weights fake-quantized to the plan's coeff_bits
                     grid (``models.moe.quantize_moe_params``),
                     activations to data_bits, validated against
                     ``moe_layer_dense_ref`` the way ``validate_plan``
                     re-traces conv kernels.
``compile_plan``     one call from any plan to its AOT executor —
                     the entry point the serving engines use, so
                     ``CNNEngine``/``AsyncCNNGateway``/``Fleet`` are
                     plan-type-blind.
``plan_moe_deployment``
                     the per-layer (bits) search under a
                     ``DeviceProfile``'s budgets for MoE workloads —
                     the same greedy predict-then-deploy loop as
                     ``deploy.plan_deployment``, driven by an analytic
                     demand model (matmul MACs, quantized weight
                     bytes, expert-buffer working set).

A request payload for an MoE plan is one ``(seq_len, d_model)`` float32
block of token activations (the per-request analogue of an image); the
compiled forward runs ``num_layers`` residual MoE layers over the
bucketed batch.  All ``CompiledModel`` machinery — bucket ladder, AOT
warmup, ``ExecutableCache`` sharing, chunking, ``should_abort`` — is
inherited, so MoE plans serve through exactly the same gateway code
paths as CNNs.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.core.allocate import BUDGET_RESOURCES
from repro.core.cnn import CNNConfig, ConvLayerSpec
from repro.core.deploy import (DEFAULT_BIT_CANDIDATES, DeploymentError,
                               DeploymentPlan, LayerAssignment, _as_device,
                               device_profile)
from repro.models import moe as moe_mod
from repro.models.layers import split_keys
from repro.runtime.compiled import (CompiledModel, ExecutableCache,
                                    count_live)

#: registry block name for an MoE layer's assignment (LayerAssignment
#: .block is a string either way; conv blocks come from repro.blocks,
#: MoE layers are all the one batched expert-FFN kernel)
MOE_BLOCK_NAME = "moe_ffn"

#: rate resources (additive across layers); vmem_bytes is the capacity
_RATE_RESOURCES = tuple(r for r in BUDGET_RESOURCES if r != "vmem_bytes")

# op by op, every full-size intermediate is allocated when its op is
# queued and freed only after its consumer ran, so at published widths
# the queued temporaries of a few layers would fill the device: the
# quantization runs as one XLA program (bit-identical), and each layer's
# draw is waited for before the next is queued (jitting the draw would
# fuse its scaling and change the weights)
_quantize_moe = jax.jit(moe_mod.quantize_moe_params, static_argnums=1)


# ---------------------------------------------------------------------------
# the protocol + registry
# ---------------------------------------------------------------------------

class WorkloadSpec:
    """What a ``DeploymentPlan`` deploys, as a typed value.

    Implementations are frozen dataclasses with a ``kind`` class
    attribute and three obligations:

    * ``to_payload()`` / ``from_payload(payload)`` — an exact JSON
      round-trip (the plan schema embeds the payload under
      ``workload.spec``; goldens pin it).
    * ``compile(plan, ...)`` — build the ``CompiledModel`` backend that
      executes ``plan`` (same keyword surface as
      ``CompiledCNN.from_plan`` so the serving layers stay generic).
    * value semantics — ``==`` must hold across a round-trip (the
      golden-fixture tests rely on it).

    Register implementations with ``register_workload`` so
    ``DeploymentPlan.from_json`` can dispatch on ``kind``.
    """

    kind: str = "workload"

    def to_payload(self) -> dict:
        raise NotImplementedError

    @classmethod
    def from_payload(cls, payload: dict) -> "WorkloadSpec":
        raise NotImplementedError

    def compile(self, plan, *, params=None, key=None, max_batch: int = 16,
                mesh=None, warmup: bool = True,
                exec_cache: Optional[ExecutableCache] = None
                ) -> CompiledModel:
        raise NotImplementedError


_WORKLOADS: Dict[str, Type[WorkloadSpec]] = {}


def register_workload(cls: Type[WorkloadSpec]) -> Type[WorkloadSpec]:
    """Class decorator: make ``cls`` the spec for its ``kind``."""
    kind = cls.kind
    if not kind or kind == WorkloadSpec.kind:
        raise ValueError(f"{cls.__name__} must define a concrete kind")
    if kind in _WORKLOADS and _WORKLOADS[kind] is not cls:
        raise ValueError(f"workload kind {kind!r} already registered "
                         f"by {_WORKLOADS[kind].__name__}")
    _WORKLOADS[kind] = cls
    return cls


def get_workload(kind: str) -> Type[WorkloadSpec]:
    try:
        return _WORKLOADS[kind]
    except KeyError:
        raise ValueError(
            f"unknown workload kind {kind!r}; registered: "
            f"{sorted(_WORKLOADS)}") from None


def list_workloads() -> List[str]:
    return sorted(_WORKLOADS)


def workload_spec(plan: DeploymentPlan) -> WorkloadSpec:
    """The typed spec of any plan: the ``workload`` field when present,
    else the embedded ``CNNConfig`` wrapped as a ``CNNWorkloadSpec``
    (every v1 plan and every planner-produced CNN plan)."""
    if plan.workload is not None:
        return plan.workload
    if plan.cnn is not None:
        return CNNWorkloadSpec(cnn=plan.cnn)
    raise ValueError(
        "plan carries neither a workload spec nor a CNNConfig — it "
        "cannot be compiled (re-plan, or attach a spec)")


def compile_plan(plan: DeploymentPlan, *, params=None, key=None,
                 max_batch: int = 16, mesh=None, warmup: bool = True,
                 exec_cache: Optional[ExecutableCache] = None
                 ) -> CompiledModel:
    """Any plan → its AOT batch-bucketed executor, dispatched through
    the workload registry.  This is the one construction path the
    serving layers use — ``CNNEngine.from_plan``, ``AsyncCNNGateway.
    register_plan`` and the fleet all stay plan-type-blind."""
    return workload_spec(plan).compile(
        plan, params=params, key=key, max_batch=max_batch, mesh=mesh,
        warmup=warmup, exec_cache=exec_cache)


# ---------------------------------------------------------------------------
# CNN: the legacy workload, wrapped
# ---------------------------------------------------------------------------

@register_workload
@dataclass(frozen=True)
class CNNWorkloadSpec(WorkloadSpec):
    """The convolution workload: exactly the network the v1 schema
    embedded as ``plan.cnn`` — the upgrade path wraps it unchanged, so
    executable-cache keys and ``plan_config`` are bit-identical across
    the v1 → v2 bump."""

    cnn: CNNConfig
    kind = "cnn"

    def to_payload(self) -> dict:
        return {
            "img_h": int(self.cnn.img_h),
            "img_w": int(self.cnn.img_w),
            "layers": [{
                "in_channels": int(s.in_channels),
                "out_channels": int(s.out_channels),
                "data_bits": int(s.data_bits),
                "coeff_bits": int(s.coeff_bits),
                "shift": int(s.shift),
                "block": s.block,
            } for s in self.cnn.layers],
        }

    @classmethod
    def from_payload(cls, payload: dict) -> "CNNWorkloadSpec":
        return cls(cnn=CNNConfig(
            layers=tuple(ConvLayerSpec(
                in_channels=int(s["in_channels"]),
                out_channels=int(s["out_channels"]),
                data_bits=int(s["data_bits"]),
                coeff_bits=int(s["coeff_bits"]),
                shift=int(s["shift"]), block=s["block"])
                for s in payload["layers"]),
            img_h=int(payload["img_h"]), img_w=int(payload["img_w"])))

    def compile(self, plan, *, params=None, key=None, max_batch: int = 16,
                mesh=None, warmup: bool = True,
                exec_cache: Optional[ExecutableCache] = None
                ) -> CompiledModel:
        from repro.runtime.compiled import CompiledCNN
        return CompiledCNN.from_plan(
            plan, self.cnn, params=params, key=key, max_batch=max_batch,
            mesh=mesh, warmup=warmup, exec_cache=exec_cache)


# ---------------------------------------------------------------------------
# MoE: quantized mixture-of-experts inference
# ---------------------------------------------------------------------------

#: the routing fields of an ``MoELayerSpec`` (``models.moe.route``) and
#: the held share of its experts, with their JSON types and the tags
#: that name them in an executable's name
_ROUTING = {"scoring": (str, ""), "n_group": (int, "g"),
            "topk_group": (int, "t"), "routed_scaling_factor": (float, "x"),
            "experts_held": (int, "h"), "expert_offset": (int, "o")}


def _opt_float(v):
    return None if v is None else float(v)


@dataclass(frozen=True)
class MoELayerSpec:
    """One MoE layer's geometry + planned quantization.  The typed
    per-layer spec the v2 plan schema carries for MoE workloads (the
    analogue of ``ConvLayerSpec``).

    ``num_experts`` is the router's width.  The layer holds
    ``experts_held`` of them (None: all) from id ``expert_offset``, as
    one chip of an expert-parallel deployment does, and routes with
    ``scoring`` (``"softmax"``, or DeepSeek-V3's ``"sigmoid"`` with a
    correction bias and ``n_group``/``topk_group`` group-limited
    top-k) and ``routed_scaling_factor``, the weights renormalized over
    the k.  The defaults are a softmax router over experts that are all
    held.  ``capacity_factor`` None drops nothing (``models.moe``'s
    dropless path), as DeepSeek-V3 serves."""
    d_ff_expert: int
    num_experts: int
    top_k: int
    data_bits: int = 8             # activation fake-quant grid
    coeff_bits: int = 8            # expert-weight fake-quant grid
    n_shared_experts: int = 0
    capacity_factor: Optional[float] = 2.0
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    experts_held: Optional[int] = None
    expert_offset: int = 0

    def __post_init__(self):
        if self.top_k < 1 or self.top_k > self.num_experts:
            raise ValueError(
                f"top_k={self.top_k} must be in [1, num_experts="
                f"{self.num_experts}]")
        for name in ("data_bits", "coeff_bits"):
            v = getattr(self, name)
            if not 2 <= v <= 16:
                raise ValueError(f"{name}={v} outside [2, 16]")
        if self.capacity_factor is not None and self.capacity_factor <= 0:
            raise ValueError(f"capacity_factor={self.capacity_factor}: "
                             f"positive, or None for no capacity")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}: 'softmax' or "
                             f"'sigmoid'")
        if (self.n_group < 1 or self.num_experts % self.n_group
                or not 1 <= self.topk_group <= self.n_group
                or self.top_k > self.topk_group * self.num_experts
                // self.n_group):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                f"the groups must split {self.num_experts} experts evenly "
                f"and the kept groups hold top_k={self.top_k}")
        if self.n_group > 1 and self.scoring != "sigmoid":
            raise ValueError("group-limited routing is sigmoid scoring's")
        if ((self.experts_held is not None and self.experts_held < 1)
                or not 0 <= self.expert_offset
                <= self.num_experts - self.held):
            raise ValueError(
                f"experts_held={self.experts_held} from expert_offset="
                f"{self.expert_offset} does not lie within "
                f"{self.num_experts} experts")

    @property
    def held(self) -> int:
        """How many experts the layer holds."""
        return self.experts_held or self.num_experts

    def routing(self) -> dict:
        """The routing and held-share fields that differ from their
        defaults, by name: what the plan's payload, the executable's
        cache key and its name add for this layer (a default layer's
        read as they did before these fields existed)."""
        defaults = {f.name: f.default for f in dataclasses.fields(self)}
        return {name: getattr(self, name) for name in _ROUTING
                if getattr(self, name) != defaults[name]}


@register_workload
@dataclass(frozen=True)
class MoEWorkloadSpec(WorkloadSpec):
    """A stack of residual MoE layers serving ``(seq_len, d_model)``
    float32 token blocks — one block per request, the MoE analogue of
    one image."""

    layers: Tuple[MoELayerSpec, ...]
    d_model: int
    seq_len: int = 32
    act: str = "silu"
    mlp_gated: bool = True
    kind = "moe"

    def __post_init__(self):
        if not self.layers:
            raise ValueError("MoE workload needs at least one layer")
        if self.d_model < 1 or self.seq_len < 1:
            raise ValueError(
                f"d_model={self.d_model} and seq_len={self.seq_len} "
                f"must be ≥ 1")

    def to_payload(self) -> dict:
        return {
            "d_model": int(self.d_model),
            "seq_len": int(self.seq_len),
            "act": self.act,
            "mlp_gated": bool(self.mlp_gated),
            "layers": [{
                "d_ff_expert": int(s.d_ff_expert),
                "num_experts": int(s.num_experts),
                "top_k": int(s.top_k),
                "data_bits": int(s.data_bits),
                "coeff_bits": int(s.coeff_bits),
                "n_shared_experts": int(s.n_shared_experts),
                "capacity_factor": _opt_float(s.capacity_factor),
                **{name: _ROUTING[name][0](v)
                   for name, v in s.routing().items()},
            } for s in self.layers],
        }

    @classmethod
    def from_payload(cls, payload: dict) -> "MoEWorkloadSpec":
        return cls(
            layers=tuple(MoELayerSpec(
                d_ff_expert=int(s["d_ff_expert"]),
                num_experts=int(s["num_experts"]),
                top_k=int(s["top_k"]),
                data_bits=int(s["data_bits"]),
                coeff_bits=int(s["coeff_bits"]),
                n_shared_experts=int(s["n_shared_experts"]),
                capacity_factor=_opt_float(s["capacity_factor"]),
                **{name: cast(s[name]) for name, (cast, _) in
                   _ROUTING.items() if name in s})
                for s in payload["layers"]),
            d_model=int(payload["d_model"]),
            seq_len=int(payload["seq_len"]),
            act=payload["act"], mlp_gated=bool(payload["mlp_gated"]))

    def compile(self, plan, *, params=None, key=None, max_batch: int = 16,
                mesh=None, warmup: bool = True,
                exec_cache: Optional[ExecutableCache] = None
                ) -> CompiledModel:
        return CompiledMoE.from_plan(
            plan, params=params, key=key, max_batch=max_batch, mesh=mesh,
            warmup=warmup, exec_cache=exec_cache)

    # -- model-config shim + params --------------------------------------
    def layer_cfg(self, i: int) -> "_MoELayerModelCfg":
        """The config view ``models.moe`` expects, for layer ``i``."""
        s = self.layers[i]
        return _MoELayerModelCfg(
            moe=MoEConfig(num_experts=s.num_experts, top_k=s.top_k,
                          d_ff_expert=s.d_ff_expert,
                          n_shared_experts=s.n_shared_experts,
                          capacity_factor=s.capacity_factor,
                          **{name: getattr(s, name) for name in _ROUTING}),
            d_model=self.d_model, act=self.act, mlp_gated=self.mlp_gated)

    def init_params(self, key, *, quantized: bool = True) -> list:
        """Per-layer ``init_moe`` draws (float32: the held experts, the
        router over all of them and a sigmoid router's bias), expert weights
        fake-quantized to each layer's ``coeff_bits`` grid unless
        ``quantized=False`` (the float oracle draw)."""
        return list(self.iter_params(key, quantized=quantized))

    def iter_params(self, key, *, quantized: bool = True):
        """``init_params`` one layer at a time, drawn only when asked
        for: a caller that drops each layer before the next holds one
        layer's weights (GBs at published widths), not the stack's."""
        for i, k in enumerate(split_keys(key, len(self.layers))):
            p = jax.block_until_ready(moe_mod.init_moe(k, self.layer_cfg(i)))
            yield (_quantize_moe(p, self.layers[i].coeff_bits)
                   if quantized else p)


@dataclass(frozen=True)
class _MoELayerModelCfg:
    """The slice of ``configs.base.ModelConfig`` that ``models.moe``
    reads, so a workload spec can drive ``moe_layer`` without
    fabricating a whole transformer config.  Serving runs float32, with
    ``moe_groups`` set to the blocks of a dispatch (``_route_per_block``)."""
    moe: MoEConfig
    d_model: int
    act: str = "silu"
    mlp_gated: bool = True
    moe_groups: int = 1

    @property
    def jnp_dtype(self):
        return jnp.float32


def _route_per_block(p, x, cfg):
    """One MoE layer over a batch of token blocks with each block routed
    on its own (``moe_groups`` = blocks, so expert capacity, where the
    layer has one, is per block): a block's output never depends on which blocks share its
    dispatch or on bucket padding — the routing twin of ``_fake_quant``'s
    per-token scale.  Returns the output and, per block, the
    assignments routed to held experts and those kept (``(B, 2)``
    int32).  The aux (load-balancing) loss is a training quantity;
    inference drops it."""
    y, _aux, counts = moe_mod.moe_layer_counted(
        p, x, dataclasses.replace(cfg, moe_groups=x.shape[0]))
    return y, counts


def _fake_quant(x, bits: int):
    """Symmetric ``bits``-bit fake quantization with a dynamic
    **per-token** scale: each token's max magnitude maps to
    ``2^(bits-1) - 1`` levels — the activation-side twin of
    ``quantize_moe_params``.  Per-token (not per-tensor) scaling is
    what makes bucketed dispatch sound: a token's quantization grid
    never depends on which batch — or how much padding — it shares a
    dispatch with, so padding to a bucket cannot perturb real
    outputs."""
    hi = float((1 << (bits - 1)) - 1)
    s = hi / jnp.maximum(
        jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-6)
    return jnp.round(x * s) / s


class CompiledMoE(CompiledModel):
    """The quantized-MoE backend: each layer is one AOT-compiled
    residual MoE block — activations fake-quantized to the layer's
    ``data_bits``, expert weights pre-quantized to ``coeff_bits`` —
    bucketed/batched/cached exactly like ``CompiledCNN``."""

    kind = "moe"
    input_noun = "token block"
    counter_names = ("moe_routed_held", "moe_kept_held")

    def __init__(self, spec: MoEWorkloadSpec, params, *,
                 max_batch: int = 16, mesh=None, warmup: bool = True,
                 exec_cache: Optional[ExecutableCache] = None):
        if len(params) != len(spec.layers):
            raise ValueError(
                f"need one param dict per layer: {len(params)} for "
                f"{len(spec.layers)} layers")
        self.spec = spec
        self.params = list(params)
        self.num_layers = len(spec.layers)
        self.in_shape = (spec.seq_len, spec.d_model)
        self.in_dtype = jnp.float32
        super().__init__(max_batch=max_batch, mesh=mesh, warmup=warmup,
                         exec_cache=exec_cache)

    @classmethod
    def from_plan(cls, plan, *, params=None, key=None,
                  max_batch: int = 16, mesh=None, warmup: bool = True,
                  exec_cache: Optional[ExecutableCache] = None
                  ) -> "CompiledMoE":
        """Executor for a planned MoE deployment: the spec with each
        layer's planned (data_bits, coeff_bits) baked in; ``params``
        default to a fresh quantized ``init_moe`` draw per layer."""
        spec = moe_plan_spec(plan)
        if params is None:
            key = key if key is not None else jax.random.PRNGKey(0)
            params = spec.init_params(key)
        return cls(spec, params, max_batch=max_batch, mesh=mesh,
                   warmup=warmup, exec_cache=exec_cache)

    # -- backend hooks ----------------------------------------------------
    def _layer_key(self, i: int, bucket: int) -> tuple:
        s = self.spec.layers[i]
        return (MOE_BLOCK_NAME, self.spec.d_model, s.d_ff_expert,
                s.num_experts, s.top_k, s.n_shared_experts,
                _opt_float(s.capacity_factor), s.data_bits, s.coeff_bits,
                self.spec.seq_len, self.spec.act, self.spec.mlp_gated
                ) + tuple(s.routing().items()) + (self._mesh_token, bucket)

    def _layer_fn(self, i: int):
        cfg = self.spec.layer_cfg(i)
        data_bits = self.spec.layers[i].data_bits

        def layer(p, x, totals, n):
            # residual MoE block over the quantized activation grid, and
            # the totals with the live blocks' counts (``counter_names``)
            y, counts = _route_per_block(p, _fake_quant(x, data_bits), cfg)
            return x + y, count_live(totals, counts, n)

        # the executable's name in a trace, e.g. jit_moe_e128_k8_d4c4:
        # from the layer's content, as the cache key is, since identical
        # layers share one executable; routing fields that differ from
        # their defaults follow (..._sigmoid_g8_t4_x2p5_h8)
        s = self.spec.layers[i]
        tags = "".join(f"_{_ROUTING[name][1]}{str(v).replace('.', 'p')}"
                       for name, v in s.routing().items())
        layer.__name__ = (f"moe_e{s.num_experts}_k{s.top_k}"
                          f"_d{s.data_bits}c{s.coeff_bits}{tags}")
        return layer

    def _layer_params(self, i: int):
        return self.params[i]

    def _layer_in_sds(self, i: int, bucket: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(
            (bucket, self.spec.seq_len, self.spec.d_model), jnp.float32)

    def _empty_output(self):
        return jnp.zeros((0,) + self.in_shape, jnp.float32)

    # -- workload helpers --------------------------------------------------
    def sample_inputs(self, k: int, seed: int = 0):
        """``k`` random float32 token blocks (unit-normal activations)
        matching this executor's ``(seq_len, d_model)`` contract."""
        rng = np.random.default_rng(seed)
        return [rng.standard_normal(self.in_shape).astype(np.float32)
                for _ in range(k)]

    def validate_input(self, x, request_id: int = 0) -> np.ndarray:
        """Shape + finiteness admission check: token activations must be
        real finite floats (NaN/Inf would propagate through every
        expert); any real dtype is accepted and served as float32."""
        x = np.asarray(x)
        if tuple(x.shape) != tuple(self.in_shape):
            raise ValueError(
                f"request {request_id}: {self.input_noun} shape "
                f"{tuple(x.shape)} != engine input {tuple(self.in_shape)}")
        if not np.issubdtype(x.dtype, np.floating) \
                and not np.issubdtype(x.dtype, np.integer):
            raise ValueError(
                f"request {request_id}: {self.input_noun} dtype {x.dtype} "
                f"is not a real numeric type")
        if not np.all(np.isfinite(x)):
            raise ValueError(
                f"request {request_id}: {self.input_noun} carries "
                f"non-finite values (NaN/Inf) — they would propagate "
                f"through every routed expert")
        return x


# ---------------------------------------------------------------------------
# the MoE planner: per-layer bit search under device budgets
# ---------------------------------------------------------------------------

def moe_layer_demand(spec: MoEWorkloadSpec, layer: MoELayerSpec,
                     data_bits: int, coeff_bits: int) -> Dict[str, float]:
    """Analytic per-request demand of one MoE layer in the device
    budget units: matmul MACs (``mxu_cost``), weight traffic at the
    quantized container width plus activation traffic (``hbm_bytes``),
    elementwise work (``vpu_ops``), and the expert-buffer + one-weight
    working set (``vmem_bytes``, a capacity), of the experts the layer
    holds (the router spans all of them).  The MoE twin of
    ``deploy.predict_layer_demand`` — analytic rather than sweep-fitted
    because the expert FFN is dense matmul, the regime the roofline
    model is exact in."""
    S, d = spec.seq_len, spec.d_model
    fe, e, k = layer.d_ff_expert, layer.num_experts, layer.top_k
    held = layer.held
    fs = fe * layer.n_shared_experts
    nmats = 3 if spec.mlp_gated else 2
    routed = S * k * held / e           # assignments to held experts
    mxu = (S * d * e                    # router projection (every expert)
           + nmats * routed * d * fe    # expert FFN on dispatched tokens
           + nmats * S * d * fs)        # always-on shared experts
    weight_bytes = (nmats * held * d * fe + nmats * d * fs) * coeff_bits / 8
    act_bytes = S * d * data_bits / 8
    vpu = S * (e + k * fe + d)          # router + act + combine
    # the expert buffer: held·C rows, or a dropless layer's one tile
    buf = (moe_mod.DROPLESS_TILE if layer.capacity_factor is None else
           held * int(max(k, round(layer.capacity_factor * S * k / e))))
    vmem = float(buf * d * 4 + held * d * fe * 4)
    return {"mxu_cost": float(mxu),
            "hbm_bytes": float(weight_bytes + act_bytes),
            "vpu_ops": float(vpu), "vmem_bytes": vmem}


def plan_moe_deployment(spec: MoEWorkloadSpec, device=None, *,
                        bit_candidates=DEFAULT_BIT_CANDIDATES,
                        target: float = 0.8,
                        on_infeasible: str = "raise") -> DeploymentPlan:
    """Greedy per-layer (data_bits, coeff_bits) assignment for an MoE
    workload under one device's budgets — ``deploy.plan_deployment``'s
    loop with the analytic MoE demand model.  Each layer takes the
    highest-precision candidate that fits the remaining budget
    (lexicographically: data+coeff bits, then lowest normalized
    demand); ``bit_candidates=None`` pins every layer to its spec's
    bits.  ``on_infeasible="fallback"`` assigns the least-over-budget
    candidate and marks the plan ``feasible=False`` instead of raising.
    The returned plan embeds the spec with assigned bits baked in
    (``plan.workload``) — the MoE analogue of ``plan.cnn``."""
    if on_infeasible not in ("raise", "fallback"):
        raise ValueError(f"on_infeasible={on_infeasible!r}")
    dev = (device_profile(device) if isinstance(device, str)
           else _as_device(device))
    budgets = {r: float(dev.budgets[r]) for r in BUDGET_RESOURCES}
    remaining = {r: target * budgets[r] for r in _RATE_RESOURCES}
    vmem_cap = target * budgets["vmem_bytes"]
    eps = 1e-9

    assignments: List[LayerAssignment] = []
    planned_layers: List[MoELayerSpec] = []
    feasible = True
    for i, layer in enumerate(spec.layers):
        cands = ([(layer.data_bits, layer.coeff_bits)]
                 if bit_candidates is None
                 else list(dict.fromkeys(tuple(b) for b in bit_candidates)))
        best = best_key = None
        cheapest, cheapest_over = None, float("inf")
        for d_bits, c_bits in cands:
            demand = moe_layer_demand(spec, layer, d_bits, c_bits)
            over = max(
                max((demand[r] - remaining[r]) / budgets[r]
                    for r in _RATE_RESOURCES),
                (demand["vmem_bytes"] - vmem_cap) / budgets["vmem_bytes"])
            norm = sum(demand[r] / budgets[r] for r in _RATE_RESOURCES)
            if over < cheapest_over:
                cheapest, cheapest_over = (d_bits, c_bits, demand), over
            if over > eps:
                continue
            key = (d_bits + c_bits, -norm)
            if best_key is None or key > best_key:
                best, best_key = (d_bits, c_bits, demand), key
        if best is None:
            if on_infeasible == "raise":
                d_bits, c_bits, cdem = cheapest
                raise DeploymentError(
                    f"MoE layer {i} (E={layer.num_experts}, "
                    f"ff={layer.d_ff_expert}, k={layer.top_k}) does not "
                    f"fit device {dev.name!r} at target {target:.0%}: "
                    f"least-demanding candidate d{d_bits}/c{c_bits} "
                    f"exceeds the budget by {cheapest_over:.1%}")
            best = cheapest
            feasible = False
        d_bits, c_bits, demand = best
        for r in _RATE_RESOURCES:
            remaining[r] = max(0.0, remaining[r] - demand[r])
        assignments.append(LayerAssignment(
            index=i, block=MOE_BLOCK_NAME, data_bits=d_bits,
            coeff_bits=c_bits, calls=spec.seq_len * layer.top_k,
            demand=demand))
        planned_layers.append(dataclasses.replace(
            layer, data_bits=d_bits, coeff_bits=c_bits))

    totals = {r: sum(a.demand[r] for a in assignments)
              for r in _RATE_RESOURCES}
    totals["vmem_bytes"] = max(
        (a.demand["vmem_bytes"] for a in assignments), default=0.0)
    usage = {r: 100.0 * totals[r] / budgets[r] for r in BUDGET_RESOURCES}
    planned = dataclasses.replace(spec, layers=tuple(planned_layers))
    plan = DeploymentPlan(
        device=dev, target=target, layers=tuple(assignments),
        demand=totals, usage_pct=usage,
        convs_per_step=float(spec.seq_len),    # tokens per request
        feasible=feasible, cnn=None, workload=planned)
    plan.quant_error = moe_quantization_error(planned)
    return plan


def moe_plan_spec(plan: DeploymentPlan) -> MoEWorkloadSpec:
    """The plan baked back into a runnable spec: each layer gets the
    planned (data_bits, coeff_bits) — the MoE analogue of
    ``deploy.plan_config``."""
    spec = workload_spec(plan)
    if not isinstance(spec, MoEWorkloadSpec):
        raise ValueError(
            f"plan carries a {spec.kind!r} workload, not 'moe'")
    if len(spec.layers) != len(plan.layers):
        raise ValueError(
            f"plan has {len(plan.layers)} assignments for "
            f"{len(spec.layers)} spec layers")
    layers = tuple(dataclasses.replace(s, data_bits=a.data_bits,
                                       coeff_bits=a.coeff_bits)
                   for s, a in zip(spec.layers, plan.layers))
    return dataclasses.replace(spec, layers=layers)


# ---------------------------------------------------------------------------
# validation vs the dense oracle (the MoE twin of deploy.validate_plan)
# ---------------------------------------------------------------------------

def _eager_forward(spec: MoEWorkloadSpec, params, x, *,
                   quant_act: bool = True):
    """Un-jitted residual stack over the spec's layers."""
    act = x
    for i in range(len(spec.layers)):
        xi = (_fake_quant(act, spec.layers[i].data_bits)
              if quant_act else act)
        act = act + _route_per_block(params[i], xi, spec.layer_cfg(i))[0]
    return act


def _dense_ref_forward(spec: MoEWorkloadSpec, params, x):
    """Residual stack through ``moe_layer_dense_ref`` — every expert on
    every token, no capacity drops, no quantization: the float oracle."""
    act = x
    for i in range(len(spec.layers)):
        act = act + moe_mod.moe_layer_dense_ref(
            params[i], act, spec.layer_cfg(i))
    return act


def moe_quantization_error(spec: MoEWorkloadSpec, *, key=None,
                           seed: int = 0) -> float:
    """Relative RMSE of the quantized MoE stack against the float
    dense-reference oracle on a deterministic probe block (the per-plan
    Pareto axis — ``deploy.quantization_error``'s MoE twin)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(
        (1, spec.seq_len, spec.d_model)), jnp.float32)
    # the two stacks advance layer by layer (the ops of _eager_forward
    # and _dense_ref_forward), so one layer's float and quantized
    # weights are live at a time
    yq = yf = x
    for i, pf in enumerate(spec.iter_params(key, quantized=False)):
        s, cfg = spec.layers[i], spec.layer_cfg(i)
        pq = _quantize_moe(pf, s.coeff_bits)
        yq = yq + _route_per_block(pq, _fake_quant(yq, s.data_bits),
                                   cfg)[0]
        yf = yf + moe_mod.moe_layer_dense_ref(pf, yf, cfg)
        del pf, pq
    num = float(jnp.sqrt(jnp.mean((yq - yf) ** 2)))
    den = float(jnp.sqrt(jnp.mean(yf ** 2)))
    return num / max(den, 1e-9)


@dataclass
class MoEPlanValidation:
    """Validation verdict for one MoE plan: the compiled (bucketed,
    AOT) path must match the eager quantized stack, and the quantized
    stack must track the dense float oracle within quantization
    tolerance."""
    compiled_matches_eager: bool
    dense_ref_rel_err: float
    quant_error: float             # the probe-seed Pareto number


def validate_moe_plan(plan: DeploymentPlan, *, key=None, seed: int = 0,
                      max_batch: int = 4, batch: int = 3,
                      atol: float = 1e-5) -> MoEPlanValidation:
    """Close the loop for an MoE plan the way ``deploy.validate_plan``
    does for CNNs: execute the plan through ``CompiledMoE`` (bucketed
    AOT dispatch, including a padded bucket) and check it against the
    un-jitted quantized stack, then score quantization against
    ``moe_layer_dense_ref``."""
    key = key if key is not None else jax.random.PRNGKey(0)
    spec = moe_plan_spec(plan)
    params = spec.init_params(key)
    compiled = CompiledMoE(spec, params, max_batch=max_batch)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(
        (batch, spec.seq_len, spec.d_model)), jnp.float32)
    y_compiled = np.asarray(compiled(x))
    y_eager = np.asarray(_eager_forward(spec, params, x))
    matches = bool(np.allclose(y_compiled, y_eager,
                               rtol=1e-5, atol=atol))
    float_params = spec.init_params(key, quantized=False)
    y_ref = np.asarray(_dense_ref_forward(spec, float_params, x))
    denom = float(np.sqrt(np.mean(y_ref ** 2)))
    rel = float(np.sqrt(np.mean((y_eager - y_ref) ** 2))) / max(denom,
                                                                1e-9)
    return MoEPlanValidation(
        compiled_matches_eager=matches, dense_ref_rel_err=rel,
        quant_error=moe_quantization_error(spec, key=key, seed=seed))


# ---------------------------------------------------------------------------
# bridge from the config zoo
# ---------------------------------------------------------------------------

def moe_workload_from_config(cfg, *, n_layers: int = 2,
                             seq_len: int = 32,
                             data_bits: int = 8, coeff_bits: int = 8,
                             capacity_factor: Optional[float] = None
                             ) -> MoEWorkloadSpec:
    """An ``MoEWorkloadSpec`` from a registry ``ModelConfig`` (e.g.
    ``smoke_config("qwen3-moe-30b-a3b")``): ``n_layers`` MoE blocks at
    the config's expert geometry, planned at the given starting bits.
    ``capacity_factor`` defaults to a generous 2.0 — serving validates
    against the no-drop dense oracle, so the capacity bound should not
    be the thing dropping tokens."""
    if cfg.moe is None:
        raise ValueError(
            f"config {cfg.name!r} (family {cfg.family!r}) has no MoE "
            f"block — pick an arch with cfg.moe set")
    m = cfg.moe
    layer = MoELayerSpec(
        d_ff_expert=m.d_ff_expert, num_experts=m.num_experts,
        top_k=m.top_k, data_bits=data_bits, coeff_bits=coeff_bits,
        n_shared_experts=m.n_shared_experts,
        capacity_factor=(2.0 if capacity_factor is None
                         else capacity_factor))
    return MoEWorkloadSpec(
        layers=(layer,) * n_layers, d_model=cfg.d_model,
        seq_len=seq_len, act=cfg.act, mlp_gated=cfg.mlp_gated)
