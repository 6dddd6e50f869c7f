"""Model families: how a configuration file of each family becomes a
served plan, its weights and inputs, and its plain reference.

A configuration's JSON names its ``family``; the harness loads
``chipbench/families/<family>.py`` by that name.  Each family module
defines ``Model``, built as ``Model(config, seed, root)``, with:

* ``plan`` — the ``DeploymentPlan`` the gateway serves;
* ``register(gateway)`` — register that plan with the benchmark's own
  weights (drawn on the device from the seed in one jitted call) and
  return its plan id;
* ``inputs(n)`` — ``n`` distinct requests drawn from the seed;
* ``reference(xs, control=False)`` — the plain reference over a batch
  of requests, at the configuration's stated precision, or at the
  precision below it (the control);
* ``compare(got, want, xs)`` — the numbers the check compares, by
  name (the configuration's ``check.limits`` picks the compared ones);
* ``faults(xs)`` (optional) — answers with faults planted in the
  reference, by name, which ``probe.py`` reads against each limit;
* ``dispatch_work(n)`` — per layer, the ``work.Work`` of one dispatch of
  ``n`` requests at the stated precision;
* ``ops_bits`` — the operand width whose peak bounds the model.

The reference imports nothing of the program under test.
"""


def seed_key(seed: int, stream: int):
    """A JAX key for ``stream`` (weights, inputs, ...) of ``seed``.
    ``PRNGKey`` keeps only the low 32 bits of a seed, so the high bits
    are folded in: seeds that differ anywhere draw different data."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)

