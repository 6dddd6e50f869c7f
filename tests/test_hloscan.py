"""HLO analyzer + jaxpr census: trip counts, collective factors, op
classification on known workloads."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hloscan


def test_jaxpr_dot_flops():
    fn = lambda a, b: a @ b
    x = jnp.zeros((64, 32))
    y = jnp.zeros((32, 16))
    res = hloscan.jaxpr_resources(fn, x, y)
    assert res["mxu_flops"] == 2 * 64 * 32 * 16


def test_jaxpr_scan_multiplier():
    def fn(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    x = jnp.zeros((16, 16))
    res = hloscan.jaxpr_resources(fn, x)
    assert res["mxu_flops"] == 7 * 2 * 16 ** 3


def test_jaxpr_elementwise_census():
    fn = lambda a: jnp.tanh(a) + a
    x = jnp.zeros((128,))
    res = hloscan.jaxpr_resources(fn, x)
    assert res["vpu_count"] >= 256          # tanh + add
    assert res["add_chain"] >= 128


def test_shape_bytes():
    assert hloscan._shape_bytes("bf16[4,8]{1,0}") == 64
    assert hloscan._shape_bytes("f32[10]") == 40
    assert hloscan._shape_bytes("(f32[2], s8[16])") == 24
    assert hloscan._shape_bytes("pred[]") == 1


def test_analyzer_on_scanned_sharded_matmul():
    """End-to-end: 8 host devices, scan(10) of a sharded matmul; the
    analyzer must count 10× what cost_analysis reports."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import hloscan
        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((8,), ("m",))
        sh = NamedSharding(mesh, P(None, "m"))
        wsh = NamedSharding(mesh, P(None, None, "m"))

        def f(x, w):
            def body(c, wi):
                return c @ wi, None
            y, _ = jax.lax.scan(body, x, w)
            return y

        x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        w = jax.ShapeDtypeStruct((10, 512, 512), jnp.float32)
        comp = jax.jit(f, in_shardings=(sh, wsh),
                       out_shardings=sh).lower(x, w).compile()
        res = hloscan.analyze_hlo(comp.as_text())
        expect = 2 * 10 * 512**3 / 8
        assert abs(res["flops"] - expect) / expect < 0.01, res["flops"]
        assert res.get("coll_all-gather", 0) > 0
        print("ANALYZER_OK", res["flops"])
    """)
    out = subprocess.run([sys.executable, "-c", prog], cwd=".",
                         capture_output=True, text=True, timeout=300)
    assert "ANALYZER_OK" in out.stdout, out.stdout + out.stderr


def test_collective_factors():
    text = """
ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %ar = f32[64]{0} all-reduce(%p), to_apply=%add
  ROOT %ag = f32[64]{0} all-gather(%ar), dimensions={0}
}
"""
    got = hloscan.collective_bytes(text)
    assert got["all-reduce"] == 2 * 256      # 2× factor
    assert got["all-gather"] == 256
    assert got["total"] == 3 * 256
