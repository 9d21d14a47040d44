#!/usr/bin/env python3
"""Record the small TPU trace that ``test_xplane.py`` reads.

  python bench/tests/record_trace.py bench/tests/data/v5e_small.xplane.pb

On a TPU: two jitted programs, ``step`` (a matmul) and ``kernel`` (the
program's Pallas flash attention at a small shape), run three times each
inside the benchmark's window annotation, with host work annotated
``bench.host_work`` between them, so the device idles there.
"""

import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import xplane
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record on a TPU")
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 2, 128),
                          jnp.bfloat16)

    def kernel(q):
        return ops.flash_attention(q, q, q, causal=True)

    def step(x):
        return (x @ x.T).sum()

    kernel, step = jax.jit(kernel), jax.jit(step)
    x = q[0, :, 0]
    kernel(q).block_until_ready()
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            for _ in range(3):
                kernel(q).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.host_work"):
                    time.sleep(0.002)
                step(x).block_until_ready()
        jax.profiler.stop_trace()
        shutil.copy(xplane.find(d), out)
    print(xplane.reduce_file(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
