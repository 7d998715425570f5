"""How ``tiny4.xplane.pb`` was recorded (this PR's four-chip call): on a
host with four TPU chips,

    python3 benchmark/tests/data/record_tiny4.py chiprun_out/tiny4

Two jitted programs over a four-chip ``tp`` mesh, python tracer off, four
launches each: ``step_a``, a bf16 matmul whose contracted dimension is
split over the chips, so that it ends in an all-reduce, then a tanh;
``step_b``, a scale and a row sum that every chip does whole. Copies the
capture's ``.xplane.pb`` to ``<out>.xplane.pb``.
"""
import glob
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out: str) -> None:
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    whole = NamedSharding(mesh, P())
    x = jax.device_put(jnp.ones((256, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P(None, "tp")))
    w = jax.device_put(jnp.full((1024, 256), 0.01, jnp.bfloat16),
                       NamedSharding(mesh, P("tp", None)))

    @jax.jit
    def step_a(x, w):
        return jax.lax.with_sharding_constraint(jnp.tanh(x @ w), whole)

    @jax.jit
    def step_b(y):
        return (y * 2).sum(axis=1)

    y = step_a(x, w)
    jax.block_until_ready(step_b(y))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(4):
        y = step_a(x, w)
        jax.block_until_ready(step_b(y))
    jax.profiler.stop_trace()
    found = sorted(glob.glob(out + "/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(found, out.rstrip("/") + ".xplane.pb")
    print("recorded", found)


if __name__ == "__main__":
    main(sys.argv[1])
