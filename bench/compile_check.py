#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip attached.

  JAX_PLATFORMS=cpu python bench/compile_check.py granite-3-2b.chat ...

For each cell: the program's prefill at the mix's prompt length and its
decode step at the cell's cache length, as ``LMAdapter`` builds them, with
the Pallas kernels the chip would run; and one unit of the float32
reference at the shape it checks (``check.ref_shape``).  Prints the
compile seconds and ``memory_analysis()`` of each.  A compile that passes
here is not a chip run; it shows what the chip's compiler would refuse.
"""

from __future__ import annotations

import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(cells) -> int:
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import check
    import harness
    from repro.runtime import steps
    from repro.runtime.sharding import ShardingPolicy

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    one = NamedSharding(mesh, PartitionSpec())
    policy = ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def report(what, fn, *args):
        t = time.monotonic()
        c = fn.lower(*args).compile()
        m = c.memory_analysis()
        print(f"{what}: compile {time.monotonic() - t:.1f} s; "
              f"arguments {m.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"outputs {m.output_size_in_bytes / 2**30:.3f} GiB, "
              f"temporaries {m.temp_size_in_bytes / 2**30:.3f} GiB; "
              f"Pallas kernel: {'tpu_custom_call' in c.as_text()}",
              flush=True)

    for name in cells:
        cell = harness.load_cell(name)
        cfg = dataclasses.replace(harness.model_config(cell),
                                  kernel_impl="pallas")
        mix = cell.mix
        P, T = mix["prompt_len"], mix["prompt_len"] + mix["output"]["max"]
        params = shaped(steps.abstract_params(cfg))
        with mesh:
            prefill = steps.build_prefill_step(
                cfg, mesh, policy, abstract_batch={
                    "tokens": jax.ShapeDtypeStruct((1, P), jnp.int32)})
            report(f"{name} prefill S={P}", prefill, params,
                   {"tokens": jax.ShapeDtypeStruct((1, P), jnp.int32,
                                                   sharding=one)})
            decode, a_cache = steps.build_decode_step(
                cfg, mesh, policy, batch=1, cache_len=T, donate=False,
                abstract_batch={"tokens": jax.ShapeDtypeStruct(
                    (1, 1), jnp.int32)})
            report(f"{name} decode T={T}", decode, params, shaped(a_cache),
                   {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32,
                                                   sharding=one)},
                   jax.ShapeDtypeStruct((), jnp.int32, sharding=one))
        ref = harness.reference(cell)
        n, length, _ = check.ref_shape(mix)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        top = shaped(jax.eval_shape(lambda k: ref.init_top(cell.mc, k), key))
        unit = shaped(jax.eval_shape(lambda k: ref.init_unit(cell.mc, k),
                                     key))
        x = jax.ShapeDtypeStruct((n, length, cfg.d_model), jnp.float32,
                                 sharding=one)
        with jax.default_matmul_precision("highest"):
            report(f"{name} reference unit B={n} S={length}",
                   jax.jit(lambda up, x, x0, top: ref.apply_unit(
                       cell.mc, up, x, x0, top, False)), unit, x, x, top)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
