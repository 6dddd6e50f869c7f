"""Sharding rules: param/batch/cache PartitionSpecs for DP / TP / EP / SP.

Two weight-sharding modes:

* ``tp``   — tensor parallelism only: heads / FFN-hidden / experts / vocab
             sharded over the ``model`` axis; weights replicated across the
             data axes.  Matches the classic Megatron layout.
* ``fsdp`` — additionally shards every weight's largest remaining dimension
             over the data axes (ZeRO-3 style); XLA inserts per-cycle
             all-gathers.  Required for the ~400B configs to fit v5e HBM.

Rules are *path-driven* over the parameter pytree, so they apply uniformly
to every architecture in the zoo.  Any dimension that does not divide the
mesh axis stays unsharded (e.g. Granite's single KV head).
"""

from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, ...], str]:
    """Returns (data_axes, model_axis) for single- or multi-pod meshes."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data"), "model"
    return ("data",), "model"


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _divides(dim: int, size: int) -> bool:
    return size > 0 and dim % size == 0


class ShardingRules:
    def __init__(self, cfg: ModelConfig, mesh: Mesh, mode: str = "tp"):
        assert mode in ("tp", "fsdp")
        self.cfg = cfg
        self.mesh = mesh
        self.mode = mode
        self.dp, self.tp = mesh_axes(mesh)
        self.tp_size = mesh.shape[self.tp]
        self.dp_size = 1
        for a in self.dp:
            self.dp_size *= mesh.shape[a]

    # -- helpers ---------------------------------------------------------
    def _fsdp_wrap(self, spec: Tuple, shape: Tuple[int, ...]) -> P:
        """In fsdp mode, shard the largest unsharded dim over the data axes.

        Leading stacked-cycle dims (handled by caller) are not candidates.
        """
        if self.mode != "fsdp":
            return P(*spec)
        spec = list(spec)
        cands = sorted(
            (i for i in range(len(spec))
             if spec[i] is None and _divides(shape[i], self.dp_size)),
            key=lambda i: -shape[i])
        if cands:
            spec[cands[0]] = self.dp if len(self.dp) > 1 else self.dp[0]
        return P(*spec)

    def _leaf_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        tp, cfg = self.tp, self.cfg
        stacked = path.startswith("stack/") or path.startswith("enc_stack/")
        core = shape[1:] if stacked else shape

        def out(*spec):
            spec = self._fsdp_wrap(spec, core)
            if stacked:
                return P(None, *spec)
            return spec

        leaf = path.rsplit("/", 1)[-1]
        # --- embeddings ------------------------------------------------
        if leaf == "embed":
            if cfg.tie_embeddings and _divides(shape[0], self.tp_size):
                return P(tp, None)       # vocab-sharded: free tied unembed
            if _divides(shape[1], self.tp_size):
                return P(None, tp)       # d_model-sharded: free gather
            return P(None, None)
        if leaf == "unembed":
            return P(None, tp) if _divides(shape[1], self.tp_size) \
                else P(None, None)
        # --- attention ---------------------------------------------------
        if leaf == "wq" or (leaf in ("wk", "wv")):
            h = core[1]
            return out(None, tp if _divides(h, self.tp_size) else None, None)
        if leaf == "wo":
            h = core[0]
            return out(tp if _divides(h, self.tp_size) else None, None, None)
        # --- MoE -----------------------------------------------------------
        if re.search(r"moe/(w_up|w_gate)$", path):
            return out(tp if _divides(core[0], self.tp_size) else None,
                       None, None)
        if re.search(r"moe/w_down$", path):
            return out(tp if _divides(core[0], self.tp_size) else None,
                       None, None)
        if leaf == "router":
            return out(None, None)
        if leaf in ("shared_up", "shared_gate"):
            return out(None, tp if _divides(core[1], self.tp_size) else None)
        if leaf == "shared_down":
            return out(tp if _divides(core[0], self.tp_size) else None, None)
        # --- dense MLP ------------------------------------------------------
        if leaf in ("w_up", "w_gate"):
            return out(None, tp if _divides(core[1], self.tp_size) else None)
        if leaf == "w_down":
            return out(tp if _divides(core[0], self.tp_size) else None, None)
        # --- mamba ------------------------------------------------------------
        if leaf in ("w_z", "w_x"):
            return out(None, tp if _divides(core[1], self.tp_size) else None)
        if leaf in ("w_B", "w_C", "conv_B", "conv_C"):
            return out(*(None,) * len(core))
        if leaf == "w_dt":
            return out(None, tp if _divides(core[1], self.tp_size) else None)
        if leaf == "conv_x":
            return out(None, tp if _divides(core[1], self.tp_size) else None)
        if leaf in ("dt_bias", "a_log", "d_skip"):
            return out(tp if _divides(core[0], self.tp_size) else None)
        if leaf == "norm" and len(core) == 1 and core[0] != cfg.d_model:
            return out(tp if _divides(core[0], self.tp_size) else None)
        if leaf == "w_out":
            return out(tp if _divides(core[0], self.tp_size) else None, None)
        # --- norms & everything else: replicated ---------------------------
        return out(*(None,) * len(core))

    # -- public ------------------------------------------------------------
    def params_spec(self, params_shapes):
        def spec(path, leaf):
            return self._leaf_spec(_path_str(path), leaf.shape)
        return jax.tree_util.tree_map_with_path(spec, params_shapes)

    def params_sharding(self, params_shapes):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.params_spec(params_shapes))

    # -- activations ---------------------------------------------------------
    def batch_spec(self, batch_shapes):
        dp = self.dp if len(self.dp) > 1 else self.dp[0]

        def spec(path, leaf):
            b = leaf.shape[0]
            lead = dp if _divides(b, self.dp_size) else None
            return P(lead, *(None,) * (len(leaf.shape) - 1))
        return jax.tree_util.tree_map_with_path(spec, batch_shapes)

    def cache_spec(self, cache_shapes):
        """Decode cache: batch over data if divisible, else sequence (SP);
        head-like dims over model when divisible."""
        dp = self.dp if len(self.dp) > 1 else self.dp[0]

        def spec(path, leaf):
            shape = leaf.shape  # leading dim = n_cycles
            p = _path_str(path).rsplit("/", 1)[-1]
            s = [None] * len(shape)
            if len(shape) >= 2:
                if _divides(shape[1], self.dp_size):
                    s[1] = dp            # batch over data axes
                elif p in ("k", "v", "ck", "cv") and len(shape) == 5 and \
                        _divides(shape[2], self.dp_size):
                    s[2] = dp            # SP: sequence over data axes
            if p in ("k", "v", "ck", "cv") and len(shape) == 5 and \
                    _divides(shape[3], self.tp_size):
                s[3] = self.tp           # kv heads over model
            if p == "ssm" and len(shape) == 5 and \
                    _divides(shape[2], self.tp_size):
                s[2] = self.tp           # ssm heads over model
            if p in ("conv_x",) and len(shape) == 4 and \
                    _divides(shape[3], self.tp_size):
                s[3] = self.tp           # inner channels over model
            return P(*s)
        return jax.tree_util.tree_map_with_path(spec, cache_shapes)

    def opt_spec(self, opt_shapes, params_spec):
        """Optimizer-state specs: fp32 moments mirror the param specs;
        int8 block codecs shard the block dim over the data axes (ZeRO-1)."""
        flat_pspec = {
            _path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(params_spec)[0]}
        dp = self.dp if len(self.dp) > 1 else self.dp[0]

        def leaf(path, x):
            ps = _path_str(path)
            if ps == "step":
                return P()
            rest = ps.split("/", 1)[1]
            if rest.endswith("/codes") or rest.endswith("/scale"):
                lead = dp if _divides(x.shape[0], self.dp_size) else None
                return P(lead, *(None,) * (len(x.shape) - 1))
            if rest in flat_pspec:
                return flat_pspec[rest]
            return P(*(None,) * len(x.shape))
        return jax.tree_util.tree_map_with_path(leaf, opt_shapes)

    def to_sharding(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree)


def choose_mode(cfg: ModelConfig, mesh: Mesh) -> str:
    """Default policy: fsdp when TP-only weights would blow past ~8GB/chip."""
    tp_size = mesh.shape["model"]
    bytes_per_chip = cfg.param_count() * 2 / tp_size
    return "fsdp" if bytes_per_chip > 8e9 else "tp"


# ---------------------------------------------------------------------------
# CNN image batches (data-parallel multi-image serving)
#
# The CNN hot path has no tensor-parallel dimension worth sharding (whole
# layers fit one chip by construction — that is the deployment planner's
# job), so serving parallelism is pure DP: the (N, H, W, C) batch
# dimension over the data axes.  ``core.cnn.cnn_layer`` — the layer of
# ``cnn_forward(mesh=)`` and of the AOT bucketed runtime
# (``repro.runtime.CompiledCNN``, which the serve engine executes
# through) — runs each device's share under ``cnn_data_parallel``; the
# runtime places its bucket-sized batch with ``cnn_batch_sharding``.
# ---------------------------------------------------------------------------

def cnn_data_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D all-``data`` mesh over the host's devices for CNN serving."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), ("data",))


def cnn_batch_sharding(mesh: Mesh, batch: int) -> NamedSharding:
    """Sharding for an (N, H, W, C) image batch: N over the mesh's data
    axes when it divides their product, else replicated (the same
    divisibility rule every other spec here follows)."""
    if "data" in mesh.axis_names:
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    else:                          # bespoke mesh: first axis is the batch axis
        axes = (mesh.axis_names[0],)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    lead = None
    if _divides(batch, size):
        lead = axes if len(axes) > 1 else axes[0]
    return NamedSharding(mesh, P(lead, None, None, None))


def cnn_data_parallel(layer, mesh: Mesh, batch: int):
    """``layer(w, x)`` run on each device's share of the image batch
    ``x`` (split as ``cnn_batch_sharding`` splits it) with the weights
    replicated.  Explicit per-device execution: the compiler cannot
    partition a Pallas (Mosaic) kernel, and a CNN layer needs no
    communication across the batch."""
    spec = cnn_batch_sharding(mesh, batch).spec
    return jax.shard_map(layer, mesh=mesh, in_specs=(P(), spec),
                         out_specs=spec, check_vma=False)
