"""The program's Mamba2 scan kernel (``repro.kernels.mamba2_ssd``) as the
benchmark sees it: how to find its calls in a device trace, and the
operations and bytes one call needs, from shapes.

The kernel runs heads-major and returns the outputs and the final state,
so a call is found by its result signature: a custom call whose result is
the pair ``(<dtype>[1, heads, S, head_width], f32[1, heads, head_width,
state])`` at the prompt length ``S``.
"""

import re

import jax.numpy as jnp

_CALL = re.compile(r"= \((\w+)\[1,(\d+),(\d+),(\d+)\]\{[^}]*\}, "
                   r"f32\[1,(\d+),(\d+),(\d+)\]\{[^}]*\}\) custom-call\(")


def _widths(mc):
    din = mc["ssm_expand"] * mc["d_model"]
    H = mc["ssm_heads"]
    return H, din // H, mc["ssm_state"]


def matches(hlo_text: str, mc, S: int) -> bool:
    m = _CALL.search(hlo_text)
    if not m:
        return False
    dt = m.group(1)
    H, s, P, H2, P2, n = map(int, m.groups()[1:])
    return (dt == jnp.dtype(mc["dtype"]).name.replace("bfloat16", "bf16")
            and (H, s, P) == (_widths(mc)[0], S, _widths(mc)[1])
            and (H2, P2, n) == _widths(mc))


def cost(mc, S: int):
    """(operations, bytes) of one call over ``S`` positions in chunks of
    ``ssm_chunk``: C.B over the causal pairs within each chunk (the one
    group's heads share it), that product applied to every head's inputs,
    and each head's state handed on and read back (two (head_width x
    state) products a position); reading x, dt, B and C and the initial
    state once, and writing the outputs and the final state once."""
    H, P, n = _widths(mc)
    l = min(mc["ssm_chunk"], S)
    pairs = (S // l) * l * (l + 1) // 2 + (S % l) * (S % l + 1) // 2
    flops = 2 * pairs * (n + H * P) + 4 * S * H * P * n
    item = jnp.dtype(mc["dtype"]).itemsize
    nbytes = (2 * S * H * P * item + 4 * S * H + 2 * S * n * item
              + 2 * 4 * H * P * n)
    return flops, nbytes
