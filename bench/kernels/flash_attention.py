"""The program's prefill attention kernel (``repro.kernels.flash_attention``)
as the benchmark sees it: how to find its calls in a device trace, and the
operations and bytes one call needs, from shapes.

The kernel runs heads-major and carries no name of its own into the
compiled program, so a call is found by its signature: a custom call whose
result is ``<dtype>[1, n_heads, S, head_dim]`` at the prompt length ``S``.
"""

import re

import jax.numpy as jnp

_CALL = re.compile(r"= (\w+)\[1,(\d+),(\d+),(\d+)\]\{[^}]*\} custom-call\(")


def matches(hlo_text: str, mc, S: int) -> bool:
    m = _CALL.search(hlo_text)
    if not m:
        return False
    dt, H, s, D = m.group(1), *map(int, m.groups()[1:])
    return (dt == jnp.dtype(mc["dtype"]).name.replace("bfloat16", "bf16")
            and H == mc["n_heads"] and s == S
            and D == mc["d_model"] // mc["n_heads"])


def cost(mc, S: int):
    """(operations, bytes) of one causal call over ``S`` positions: the two
    matmuls over the causal half (with its diagonal), reading q, k and v
    once and writing the output once."""
    H, Hkv = mc["n_heads"], mc["n_kv_heads"]
    D = mc["d_model"] // H
    flops = 4 * H * D * S * (S + 1) // 2
    item = jnp.dtype(mc["dtype"]).itemsize
    return flops, item * S * D * (2 * H + 2 * Hkv)
