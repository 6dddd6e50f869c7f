"""Pipeline parallelism: shard_map GPipe schedule == sequential reference
(subprocess with 4 host devices)."""

import subprocess
import sys
import textwrap

from repro.parallel.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-9


def test_pipeline_matches_sequential():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import auto_mesh
        from repro.parallel.pipeline import pipeline_forward

        S, M, mb, d = 4, 8, 2, 16
        rng = np.random.default_rng(0)
        # one linear layer per stage
        W = jnp.asarray(rng.normal(size=(S, d, d)) / np.sqrt(d),
                        jnp.float32)
        xs = jnp.asarray(rng.normal(size=(M, mb, d)), jnp.float32)

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        mesh = auto_mesh((4,), ("pipe",))
        out = pipeline_forward(stage_fn, W, xs, mesh=mesh, axis="pipe")

        # sequential reference
        ref = xs
        for s in range(S):
            ref = jnp.tanh(ref @ W[s])
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-5, err
        print("PIPELINE_OK", err)
    """)
    out = subprocess.run([sys.executable, "-c", prog], cwd=".",
                         capture_output=True, text=True, timeout=300)
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr
