#!/usr/bin/env python3
"""Record the small trace that ``test_xplane.py`` reads: eight calls of one
jitted program on the attached chip.

    python3 benchmarks/tests/record_trace.py <output directory>
"""
import sys

import jax
import jax.numpy as jnp


def main() -> int:
    out = sys.argv[1]

    @jax.jit
    def recorded_step(x, w):
        return jnp.tanh(x @ w) + x

    x = jnp.ones((256, 256), jnp.bfloat16)
    w = jnp.full((256, 256), 0.01, jnp.bfloat16)
    recorded_step(x, w).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(8):
        x = recorded_step(x, w)
    x.block_until_ready()
    jax.profiler.stop_trace()
    print(jax.devices()[0].device_kind, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
