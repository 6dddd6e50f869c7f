"""Compile the served path's kernels and layers for a described TPU v5e.

No chip is attached: the topology is described (``v5e:2x2``) and each
program is lowered and compiled for one of its chips, which refuses what
the chip's compiler would refuse — an unsupported Pallas construct, a
kernel that does not fit VMEM, a layer that does not fit HBM.  Nothing
runs, so these tests say nothing about results or times.

The code reads the backend (the CPU here) to choose interpret mode; the
``tpu_compile`` fixture steers it to compiled kernels for these tests
only, and clears JAX's trace caches before and after so no interpreted
trace is reused here and no compiled one leaks into other tests.
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.blocks import get_block
from repro.configs import get_config
from repro.core.cnn import CNNConfig, ConvLayerSpec, init_cnn
from repro.kernels import conv2d
from repro.runtime import CompiledCNN, CompiledMoE, moe_workload_from_config

HBM_BYTES = 16e9                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:         # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_compile(one_chip):
    """``compile(fn, *shapes)`` for one described v5e chip, with Pallas
    kernels compiled and JAX's persistent compilation cache off (an
    entry written for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setattr(conv2d, "interpret_mode", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()

    def compile_(fn, *shapes):
        sds = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), shapes)
        return jax.jit(fn).lower(*sds).compile()

    yield compile_
    mp.undo()
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(shape, bits):
    return jax.ShapeDtypeStruct(shape, conv2d.container_dtype(bits))


def _apply(tpu_compile, name, d, c):
    blk = get_block(name)
    return tpu_compile(
        lambda x, w: blk.apply(x, w, data_bits=d, coeff_bits=c),
        _sds((32, 128), d), _sds(blk.weight_shape(c), c))


@pytest.mark.parametrize("name", ["conv1", "conv2", "conv3", "conv4"])
def test_block_kernel_compiles_int8(tpu_compile, name):
    """Every block's kernel at 32×128 in the int8 container."""
    assert "tpu_custom_call" in _apply(tpu_compile, name, 8, 8).as_text()


def test_conv1_narrow_accumulator_compiles(tpu_compile):
    """d6/c4: two 16-bit accumulators per int32 lane (the v5e VPU has
    no 16-bit arithmetic)."""
    assert conv2d._narrow_acc(6, 4)
    assert "tpu_custom_call" in _apply(tpu_compile, "conv1", 6, 4).as_text()


def test_conv3_unpacked_wide_compiles(tpu_compile):
    """d12/c10: int16 containers, no packing, int8-limb MXU passes."""
    assert not conv2d.conv3_packed_ok(12, 10)
    assert "tpu_custom_call" in _apply(tpu_compile, "conv3", 12,
                                       10).as_text()


def _served_layer(tpu_compile, block, spec, bucket=8):
    cfg = CNNConfig(layers=(spec,), img_h=32, img_w=128)
    params = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0), cfg))
    model = CompiledCNN(cfg, params, [block], max_batch=bucket,
                        warmup=False)
    return tpu_compile(model._layer_fn(0), params[0],
                       model._layer_in_sds(0, bucket))


def test_conv1_served_layer_compiles(tpu_compile):
    """The conv1 layer as ``CompiledCNN`` serves it: batch 8, 8→8
    channels, the kernel vmapped over images and planes."""
    exe = _served_layer(tpu_compile, "conv1",
                        ConvLayerSpec(8, 8, data_bits=8, coeff_bits=6))
    assert "tpu_custom_call" in exe.as_text()


def test_conv1_served_layer_compiles_over_four_chips(topo, tpu_compile):
    """The conv1 layer batch-sharded over a 2x2 mesh, as ``--shard``
    serves it: the compiler cannot partition a Mosaic kernel, so each
    chip runs it on its own images (``cnn_data_parallel``)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.parallel.sharding import cnn_batch_sharding

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    cfg = CNNConfig(layers=(ConvLayerSpec(8, 4, data_bits=6, coeff_bits=4),),
                    img_h=32, img_w=128)
    params = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0), cfg))
    model = CompiledCNN(cfg, params, ["conv1"], max_batch=8, mesh=mesh,
                        warmup=False)
    x = model._layer_in_sds(0, 8)
    exe = jax.jit(model._layer_fn(0)).lower(
        jax.ShapeDtypeStruct(params[0].shape, params[0].dtype,
                             sharding=NamedSharding(mesh, P())),
        jax.ShapeDtypeStruct(x.shape, x.dtype,
                             sharding=cnn_batch_sharding(mesh, 8))).compile()
    text = exe.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text


def test_conv3_packed_served_layer_compiles(tpu_compile):
    """Conv3's layer-fused packed dot (int32 operands in XLA)."""
    exe = _served_layer(tpu_compile, "conv3",
                        ConvLayerSpec(8, 8, data_bits=6, coeff_bits=4))
    assert exe.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_moe_layer_full_width_compiles(tpu_compile):
    """One Qwen3-30B-A3B MoE layer at published widths (d_model 2048,
    128 experts, top-8, expert d_ff 768) at bucket 4 fits one chip."""
    spec = moe_workload_from_config(get_config("qwen3-moe-30b-a3b"),
                                    n_layers=1)
    assert (spec.d_model, spec.layers[0].num_experts,
            spec.layers[0].top_k, spec.layers[0].d_ff_expert) == \
        (2048, 128, 8, 768)
    params = jax.eval_shape(
        lambda: spec.init_params(jax.random.PRNGKey(0)))
    model = CompiledMoE(spec, params, max_batch=4, warmup=False)
    exe = tpu_compile(model._layer_fn(0), params[0],
                      model._layer_in_sds(0, 4), *model._counter_sds())
    mem = exe.memory_analysis()
    assert mem.argument_size_in_bytes > 2e9          # the expert weights
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
