"""Compile-only tests: the Pallas kernels at real widths, for a TPU v5e.

Nothing runs here.  Each test lowers and compiles one kernel against a
described (not attached) ``v5e:2x2`` topology, so the TPU compiler refuses
here what it would refuse on the chip: block shapes that break the
tiling rule, stores it cannot lower, more VMEM than a kernel may use.
The interpret-mode tests in ``test_kernels.py`` cannot see any of that.
One test compiles granite-3-2b's decode round the same way and reads
what the compiler makes of its cache.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and the test
workers all import this file.  Keep these tests in this one file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro import configs
from repro.kernels import collective_stages as stages
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_ssd import mamba2_ssd
from repro.kernels.mlstm_chunk import mlstm_chunk
from repro.kernels.moe_gmm import moe_gmm
from repro.runtime import steps
from repro.runtime.sharding import ShardingPolicy


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8


def test_flash_attention_granite_prefill(one_chip):
    # granite-3-2b: 32 query heads, 8 KV heads, head_dim 64; 128-token
    # prompt (the serving smoke shape).
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
             one_chip, ((1, 128, 32, 64), BF16), ((1, 128, 8, 64), BF16),
             ((1, 128, 8, 64), BF16))


def test_flash_attention_sliding_window_long(one_chip):
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             window=512),
             one_chip, ((1, 2048, 32, 64), BF16), ((1, 2048, 8, 64), BF16),
             ((1, 2048, 8, 64), BF16))


def test_mamba2_ssd_zamba2(one_chip):
    # zamba2-2.7b: d_inner 5120 = 80 heads x 64, ssm_state 64, chunk 256.
    b, s, h, p, n = 1, 512, 80, 64, 64
    _compile(lambda x, dt, A, B, C: mamba2_ssd(x, dt, A, B, C, chunk=256),
             one_chip, ((b, s, h, p), BF16), ((b, s, h), BF16), ((h,), F32),
             ((b, s, n), BF16), ((b, s, n), BF16))


def test_mlstm_chunk_xlstm(one_chip):
    # xlstm-350m: d_inner 2048 = 4 heads x 512, chunk 256.
    b, s, h, d = 1, 512, 4, 512
    _compile(lambda q, k, v, i, f: mlstm_chunk(q, k, v, i, f, chunk=256),
             one_chip, ((b, s, h, d), BF16), ((b, s, h, d), BF16),
             ((b, s, h, d), BF16), ((b, s, h), F32), ((b, s, h), F32))


def test_moe_gmm_olmoe(one_chip):
    # olmoe-1b-7b: 64 experts, d_model 2048, expert d_ff 1024.
    _compile(moe_gmm, one_chip, ((64, 128, 2048), BF16),
             ((64, 2048, 1024), BF16))


def test_decode_round_writes_cache_in_place(topo):
    """granite-3-2b's round at full width (4 slots, cache 2048, the Pallas
    prefill kernels) reads each KV cache where it lies and writes only the
    new positions: no temporaries of a cache's size, and no ``copy`` of a
    whole stacked KV leaf, (40, 4, 1, 2048, 8, 64) in any order."""
    cfg = dataclasses.replace(configs.get("granite-3-2b"),
                              kernel_impl="pallas")
    slots, T = 4, 2048
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    one = NamedSharding(mesh, PartitionSpec())
    policy = ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    with mesh:
        decode, _, a_cache = steps.build_round_step(
            cfg, mesh, policy, slots=slots, cache_len=T)
        rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
        compiled = decode.lower(shaped(steps.abstract_params(cfg)),
                                shaped(a_cache), rows, rows).compile()
    kv_dims = sorted(a_cache["L0_0_attn"]["k"].shape)
    assert kv_dims == sorted((cfg.repeats, slots, 1, T, cfg.n_kv_heads,
                              cfg.head_dim))
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20
    copies = [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(",
                                          compiled.as_text())
              if sorted(int(d) for d in dims.split(",")) == kv_dims]
    assert not copies, copies


def test_fused_combine(one_chip):
    m = 1 << 20
    _compile(lambda a, g: stages.fused_combine(a, g), one_chip,
             ((m,), F32), ((m,), BF16))
    _compile(lambda a, g, s: stages.fused_combine(a, g, s), one_chip,
             ((m,), F32), ((m,), I8), ((), F32))


def test_quantize_dequantize_wire(one_chip):
    m = 1 << 20
    _compile(stages.quantize_wire, one_chip, ((m,), F32), ((), F32))
    _compile(stages.dequantize_wire, one_chip, ((m,), I8), ((), F32))


def test_gs_stencil_512(one_chip):
    # 512 x 512 fp32: the 1 MiB block the stencil kernel keeps in VMEM.
    _compile(stages.gs_stencil, one_chip, ((512, 512), F32), ((512,), F32),
             ((512,), F32), ((512,), F32), ((512,), F32))
