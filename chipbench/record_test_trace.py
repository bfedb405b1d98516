"""Record the small profiler trace that ``tests/chipbench`` reduces.

    python3 chipbench/record_test_trace.py <output dir>

On a TPU: a few matmuls inside harness spans, with host work between them
so that the device idles, traced in a span named ``chipbench.traced``.
The ``.xplane.pb`` lands under ``<output dir>/plugins/profile/``.
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.steady"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.event"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
