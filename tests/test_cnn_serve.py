"""Plan-driven CNN serving engine: slot batching, bit-exact outputs,
plan construction, scheduling-policy ordering, SlotPool telemetry
bounds/thread-safety, and data-parallel sharded execution."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import deploy
from repro.core.cnn import (CNNConfig, ConvLayerSpec, cnn_forward_ref,
                            fitted_block_models, init_cnn)
from repro.kernels import ops
from repro.launch.mesh import auto_mesh
from repro.parallel.sharding import cnn_batch_sharding, cnn_data_mesh
from repro.serve import CNNEngine, CNNServeConfig, ImageRequest


def _cfg():
    return CNNConfig(layers=(
        ConvLayerSpec(1, 4, data_bits=8, coeff_bits=6, block="conv4"),
        ConvLayerSpec(4, 3, data_bits=6, coeff_bits=4, block="conv3"),
    ), img_h=16, img_w=64)


def _engine(max_batch=4):
    cfg = _cfg()
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    return CNNEngine(cfg, params, [s.block for s in cfg.layers],
                     CNNServeConfig(max_batch=max_batch))


def _requests(engine, k, seed=0):
    rng = np.random.default_rng(seed)
    d0 = engine.cfg.layers[0].data_bits
    return [ImageRequest(
        image=np.asarray(ops.quantize_fixed(
            rng.integers(0, 1 << (d0 - 1),
                         engine.in_shape).astype(np.float32), d0)),
        request_id=i) for i in range(k)]


def test_engine_outputs_bit_exact_vs_oracle():
    """7 requests through a 4-slot pool: 2 steps, every output equals the
    per-image integer oracle."""
    eng = _engine(max_batch=4)
    reqs = _requests(eng, 7)
    eng.run(reqs)
    for r in reqs:
        assert r.done
        yr = cnn_forward_ref(eng.params, jnp.asarray(r.image), eng.cfg)
        np.testing.assert_array_equal(r.output, np.asarray(yr))
    stats = eng.stats()
    assert stats["images_served"] == 7 and stats["steps"] == 2


def test_engine_zero_slot_isolation():
    """The same image served solo (3 empty zero slots) and in a full
    pool must produce identical outputs."""
    eng = _engine(max_batch=4)
    reqs = _requests(eng, 4, seed=1)
    solo = ImageRequest(image=reqs[2].image.copy(), request_id=99)
    eng.run([solo])
    eng.run(reqs)
    np.testing.assert_array_equal(solo.output, reqs[2].output)


def test_engine_pool_overflow_and_validation():
    eng = _engine(max_batch=2)
    reqs = _requests(eng, 3)
    assert eng.submit(reqs[0]) and eng.submit(reqs[1])
    assert not eng.submit(reqs[2])          # pool full → caller requeues
    eng.step()
    assert eng.submit(reqs[2])
    with pytest.raises(ValueError, match="image shape"):
        eng.submit(ImageRequest(image=np.zeros((8, 8, 1), np.int8)))


def test_engine_from_plan_runs_planned_assignment():
    """from_plan bakes the planner's (block, bits) into the engine and
    the served outputs match the oracle at the planned precisions."""
    cfg = CNNConfig(layers=(
        ConvLayerSpec(1, 4, data_bits=8, coeff_bits=6),
        ConvLayerSpec(4, 2, data_bits=6, coeff_bits=6),
    ), img_h=16, img_w=64)
    bm = fitted_block_models()
    plan = deploy.plan_deployment(cfg, bm, target=0.8,
                                  on_infeasible="fallback")
    eng = CNNEngine.from_plan(plan, cfg,
                              serve_cfg=CNNServeConfig(max_batch=2))
    assert [b.name for b in eng.blocks] == plan.block_names()
    assert [(s.data_bits, s.coeff_bits) for s in eng.cfg.layers] \
        == plan.bits()
    reqs = _requests(eng, 3, seed=2)
    eng.run(reqs)
    pcfg = deploy.plan_config(plan, cfg)
    for r in reqs:
        yr = cnn_forward_ref(eng.params, jnp.asarray(r.image), pcfg)
        np.testing.assert_array_equal(r.output, np.asarray(yr))


def test_engine_block_count_mismatch():
    cfg = _cfg()
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="one block per layer"):
        CNNEngine(cfg, params, ["conv2"])


def test_engine_rejects_non_integral_float_images():
    """A float image with fractional values used to be silently truncated
    by the int cast in step(); submit now rejects it."""
    eng = _engine(max_batch=2)
    good = _requests(eng, 1)[0]
    # float dtype but exactly integral values: accepted (cast is exact)
    float_img = np.asarray(good.image, np.float32)
    assert eng.submit(ImageRequest(image=float_img, request_id=1))
    with pytest.raises(ValueError, match="non-integral"):
        eng.submit(ImageRequest(image=float_img + 0.5, request_id=2))
    with pytest.raises(ValueError, match="non-integral"):
        eng.submit(ImageRequest(
            image=np.full(eng.in_shape, np.nan, np.float32), request_id=3))
    # values outside the container range would wrap, not clamp: rejected
    hi = np.iinfo(eng.in_dtype).max
    with pytest.raises(ValueError, match="container range"):
        eng.submit(ImageRequest(
            image=np.full(eng.in_shape, hi + 1, np.int32), request_id=4))
    # the accepted float image still serves bit-exactly
    eng.step()
    yr = cnn_forward_ref(eng.params, jnp.asarray(good.image), eng.cfg)
    req = ImageRequest(image=float_img, request_id=5)
    eng.submit(req)
    eng.step()
    np.testing.assert_array_equal(req.output, np.asarray(yr))


def test_engine_large_queue_drains_in_order():
    """Deque regression (the run loop used list.pop(0), O(n²) over a
    workload): a queue much larger than the pool drains completely, in
    FIFO waves, every output bit-exact."""
    eng = _engine(max_batch=4)
    reqs = _requests(eng, 257, seed=7)
    out = eng.run(reqs)
    assert out is not None and len(out) == 257
    assert all(r.done for r in reqs)
    stats = eng.stats()
    assert stats["images_served"] == 257
    assert stats["steps"] == 65            # 64 full waves + the tail of 1
    # FIFO: the first pool-load is exactly the first 4 requests, etc.
    ref = cnn_forward_ref(eng.params, jnp.asarray(reqs[-1].image), eng.cfg)
    np.testing.assert_array_equal(reqs[-1].output, np.asarray(ref))


def test_engine_occupancy_and_bucket_telemetry():
    """stats() exposes the live-slot histogram and the CompiledCNN
    bucket-hit counts — the observable face of bucketed batching."""
    eng = _engine(max_batch=4)
    reqs = _requests(eng, 7)
    eng.run(reqs)                          # waves of 4 then 3
    stats = eng.stats()
    assert stats["occupancy_hist"] == {4: 1, 3: 1}
    # occupancy 4 → bucket 4; occupancy 3 → smallest bucket ≥ 3 is 4
    assert stats["bucket_hits"] == {1: 0, 2: 0, 4: 2}
    assert stats["aot_warmed_up"]
    solo = _requests(eng, 1, seed=9)[0]
    eng.submit(solo)
    eng.step()
    stats = eng.stats()
    assert stats["occupancy_hist"][1] == 1
    assert stats["bucket_hits"][1] == 1    # a lone image no longer pays
    assert stats["images_per_step"] == 8 / 3


def test_engine_no_warmup_still_serves():
    cfg = _cfg()
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    eng = CNNEngine(cfg, params, [s.block for s in cfg.layers],
                    CNNServeConfig(max_batch=2, aot_warmup=False))
    assert not eng.stats()["aot_warmed_up"]
    reqs = _requests(eng, 3, seed=4)
    eng.run(reqs)
    for r in reqs:
        yr = cnn_forward_ref(eng.params, jnp.asarray(r.image), eng.cfg)
        np.testing.assert_array_equal(r.output, np.asarray(yr))


def test_engine_rejects_empty_slot_pool():
    """max_batch < 1 would make run() spin forever (submit always False,
    step always 0) — must be rejected at construction."""
    cfg = _cfg()
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="max_batch"):
        CNNEngine(cfg, params, [s.block for s in cfg.layers],
                  CNNServeConfig(max_batch=0))


# ---------------------------------------------------------------------------
# shared scheduling policies + SlotPool telemetry
# ---------------------------------------------------------------------------

def test_engine_run_edf_policy_orders_waves():
    """The sync drain accepts the same scheduling policies as the async
    gateway: under policy="edf" the first wave is the most urgent
    requests, not arrival order."""
    eng = _engine(max_batch=2)
    reqs = _requests(eng, 4)
    reqs[0].deadline = 9.0
    reqs[1].deadline = 1.0
    reqs[2].deadline = 2.0
    reqs[3].priority = 1               # higher tier: runs first
    order = []
    orig_step = eng.step

    def spy_step():
        order.append([r.request_id for _, r in eng.live()])
        return orig_step()

    eng.step = spy_step
    eng.run(reqs, policy="edf", clock=lambda: 0.0)
    assert order == [[3, 1], [2, 0]]
    assert all(r.done for r in reqs)


def test_engine_run_fifo_default_unchanged():
    eng = _engine(max_batch=2)
    reqs = _requests(eng, 3)
    reqs[0].deadline = 99.0            # ignored under FIFO
    order = []
    orig_step = eng.step

    def spy_step():
        order.append([r.request_id for _, r in eng.live()])
        return orig_step()

    eng.step = spy_step
    eng.run(reqs)
    assert order == [[0, 1], [2]]


def test_slot_pool_occupancy_hist_is_bounded_and_clamped():
    """Regression: the histogram used to be an unbounded dict keyed on
    whatever a subclass reported.  It is now a fixed max_batch-sized
    array — bogus occupancies clamp into range instead of growing it."""
    eng = _engine(max_batch=2)
    eng._note_step(1)
    eng._note_step(10 ** 9)            # clamps to max_batch
    eng._note_step(-5)                 # clamps to 1
    hist = eng.occupancy_hist
    assert hist == {1: 2, 2: 1}
    assert len(eng._occupancy) == 2    # fixed backing store


def test_slot_pool_stats_thread_safe_under_concurrent_steps():
    """Two threads hammering _note_step while another snapshots: no
    lost counts, every snapshot internally consistent."""
    import threading

    eng = _engine(max_batch=4)
    N = 2000

    def noter():
        for _ in range(N):
            eng._note_step(3)

    threads = [threading.Thread(target=noter) for _ in range(2)]
    snapshots = []

    def reader():
        for _ in range(200):
            snapshots.append(eng.occupancy_hist.get(3, 0))

    r = threading.Thread(target=reader)
    for t in threads + [r]:
        t.start()
    for t in threads + [r]:
        t.join()
    assert eng.occupancy_hist[3] == 2 * N
    assert eng.steps == 2 * N
    assert snapshots == sorted(snapshots)  # monotone non-decreasing


# ---------------------------------------------------------------------------
# data-parallel sharding
# ---------------------------------------------------------------------------

def test_cnn_batch_sharding_divisibility():
    mesh = cnn_data_mesh()                       # 1-D all-data mesh
    n = len(jax.devices())
    assert cnn_batch_sharding(mesh, 4 * n).spec \
        == P("data", None, None, None)
    # 2-D train-style mesh: batch over the data axis only
    mesh2 = auto_mesh((1, 1), ("data", "model"))
    assert cnn_batch_sharding(mesh2, 8).spec == P("data", None, None, None)


def test_engine_sharded_multidevice():
    """8 host devices: the mesh-sharded engine serves bit-identically to
    the unsharded single-device forward (SPMD correctness end-to-end)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core.cnn import (CNNConfig, ConvLayerSpec, cnn_forward,
                                    cnn_forward_ref, init_cnn)
        from repro.kernels import ops
        from repro.parallel.sharding import cnn_batch_sharding, cnn_data_mesh
        from repro.serve import CNNEngine, CNNServeConfig, ImageRequest

        assert len(jax.devices()) == 8
        cfg = CNNConfig(layers=(
            ConvLayerSpec(1, 4, data_bits=8, coeff_bits=6, block="conv4"),
            ConvLayerSpec(4, 3, data_bits=6, coeff_bits=4, block="conv3"),
        ), img_h=16, img_w=64)
        params = init_cnn(jax.random.PRNGKey(0), cfg)
        blocks = [s.block for s in cfg.layers]
        mesh = cnn_data_mesh()

        rng = np.random.default_rng(0)
        xb = ops.quantize_fixed(jnp.asarray(
            rng.integers(0, 128, (8, 16, 64, 1)), jnp.float32), 8)
        y_ref = cnn_forward_ref(params, xb, cfg)

        from jax.sharding import PartitionSpec as P
        assert cnn_batch_sharding(mesh, 3).spec \
            == P(None, None, None, None)   # 3 images over 8: replicated
        sh = cnn_batch_sharding(mesh, 8)
        xs = jax.device_put(xb, sh)
        fwd = jax.jit(lambda p, x: cnn_forward(p, x, cfg, blocks,
                                               mesh=mesh))
        y_sh = fwd(params, xs)
        assert len(y_sh.sharding.device_set) == 8, y_sh.sharding
        assert bool(jnp.all(y_sh == y_ref))

        eng = CNNEngine(cfg, params, blocks,
                        CNNServeConfig(max_batch=8), mesh=mesh)
        reqs = [ImageRequest(image=np.asarray(xb[i % 8]), request_id=i)
                for i in range(12)]
        eng.run(reqs)
        for i, r in enumerate(reqs):
            assert np.array_equal(
                r.output, np.asarray(y_ref[i % 8])), i
        print("CNN_SHARDED_OK")
    """)
    out = subprocess.run([sys.executable, "-c", prog], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "CNN_SHARDED_OK" in out.stdout, out.stdout + out.stderr
