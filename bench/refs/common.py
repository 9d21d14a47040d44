"""Pieces shared by the plain float32 references and the benchmark's weights.

Nothing here imports the program under test.  A configuration is the
``model`` mapping of ``bench/configs/<name>.json``; weights are made by the
benchmark from the run's seed, in the layout the program's parameter tree
uses, and in the dtype the configuration serves them in.  The reference
regenerates them unit by unit from the same keys, so it never holds the
whole model in float32 and never reads what the program made.

Every matmul runs at ``Precision.HIGHEST`` (true float32 on a TPU).  With
``quant=True`` the operands of every weight matmul go through
:func:`fp8` first: the control, one precision step below bfloat16.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn
Q_BLOCK = 512


def pattern(mc: Dict[str, Any]) -> Tuple[Tuple[str, ...], ...]:
    return tuple(tuple(layer) for layer in mc["pattern"])


def repeats(mc: Dict[str, Any]) -> int:
    n, per = mc["n_layers"], len(mc["pattern"])
    if n % per:
        raise ValueError(f"n_layers {n} is not a multiple of the pattern "
                         f"length {per}")
    return n // per


def head_dim(mc: Dict[str, Any]) -> int:
    return mc["d_model"] // mc["n_heads"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def dense(key, shape, dtype, fan_in=None):
    """N(0, 1/fan_in), made in float32 and served in ``dtype``."""
    fan_in = shape[0] if fan_in is None else fan_in
    return (jax.random.normal(key, shape, F32) / np.sqrt(fan_in)).astype(dtype)


def around_one(key, shape):
    """A float32 scale vector near 1 (norm gains), so that a scale the
    program dropped or applied twice shows."""
    return 1.0 + 0.1 * jax.random.normal(key, shape, F32)


def unit_keys(key, mc):
    return jax.random.split(key, repeats(mc))


def seed_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for ``stream`` from a seed of any size."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# float32 math
# ---------------------------------------------------------------------------
def fp8(x):
    """Round to float8_e4m3fn with one per-tensor scale (the control)."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(x, w, quant: bool):
    x, w = x.astype(F32), w.astype(F32)
    if quant:
        x, w = fp8(x), fp8(w)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary embedding, split-half form.  x: (B, S, H, D), positions 0..S-1."""
    S, D = x.shape[1], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(p, x, mc, quant: bool):
    """GQA causal self-attention over the whole sequence.  x: (B, S, d)."""
    B, S, _ = x.shape
    H, Hkv, D = mc["n_heads"], mc["n_kv_heads"], head_dim(mc)
    q = mm(x, p["wq"], quant).reshape(B, S, H, D)
    k = mm(x, p["wk"], quant).reshape(B, S, Hkv, D)
    v = mm(x, p["wv"], quant).reshape(B, S, Hkv, D)
    q, k = rope(q, mc["rope_theta"]), rope(k, mc["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    outs = []
    for q0 in range(0, S, Q_BLOCK):  # blocks of queries keep scores small
        qb = q[:, q0:q0 + Q_BLOCK]
        s = jnp.einsum("bshd,bthd->bhst", qb, k, precision=HI) / np.sqrt(D)
        qpos = q0 + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, -jnp.inf)
        outs.append(jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v,
                               precision=HI))
    o = jnp.concatenate(outs, axis=1)
    return mm(o.reshape(B, S, H * D), p["wo"], quant)


def swiglu(p, x, quant: bool):
    return mm(jax.nn.silu(mm(x, p["wg"], quant)) * mm(x, p["wu"], quant),
              p["wd"], quant)


# ---------------------------------------------------------------------------
# weights for the program, and the forward driver: one unit at a time
# ---------------------------------------------------------------------------
def init_params(mc, key, init_top: Callable, init_unit: Callable):
    """The whole parameter tree, as the program takes it (call under jit)."""
    params = init_top(mc, jax.random.fold_in(key, 0))
    keys = unit_keys(jax.random.fold_in(key, 1), mc)
    params["layers"] = jax.vmap(lambda k: init_unit(mc, k))(keys)
    return params


def forward(mc: Dict[str, Any], key, seqs: Sequence[np.ndarray],
            positions: Sequence[np.ndarray], *, init_top: Callable,
            init_unit: Callable, apply_unit: Callable, head: Callable,
            quant: bool = False, shape=(0, 0, 0)) -> List[np.ndarray]:
    """Float32 logits of each sequence at the given positions.

    ``seqs[i]`` is a token sequence, ``positions[i]`` the positions whose
    next-token logits are wanted.  Sequences are right-padded to one
    length, and the batch with empty rows, to at least ``shape`` (rows,
    length, positions), so that every run of a cell compiles one shape;
    the model is causal, so padding never reaches a wanted position.  The
    weights of :func:`init_params` are regenerated from ``key``: the top
    level (embeddings, head, shared blocks) once, then unit by unit, so
    that at most one unit is held in float32.
    """
    rows = max(len(seqs), shape[0])
    lmax = max(max(len(s) for s in seqs), shape[1])
    npos = max(max(len(p) for p in positions), shape[2])
    toks = np.zeros((rows, lmax), np.int32)
    pos = np.zeros((rows, npos), np.int32)
    for i, (s, p) in enumerate(zip(seqs, positions)):
        toks[i, :len(s)] = s
        pos[i, :len(p)] = p
        pos[i, len(p):] = p[-1]

    with jax.default_matmul_precision("highest"):
        top = jax.jit(lambda k: init_top(mc, k))(jax.random.fold_in(key, 0))
        x0 = jnp.take(top["embed"], jnp.asarray(toks), axis=0).astype(F32)
        keys = unit_keys(jax.random.fold_in(key, 1), mc)
        make = jax.jit(lambda k: init_unit(mc, k))
        step = jax.jit(lambda up, x, x0, top:
                       apply_unit(mc, up, x, x0, top, quant))
        x = x0
        for r in range(repeats(mc)):
            x = step(make(keys[r]), x, x0, top)
        logits = jax.jit(lambda x, pos, top: head(
            mc, top, jnp.take_along_axis(x, pos[..., None], axis=1), quant))(
                x, jnp.asarray(pos), top)
    logits = np.asarray(logits)
    return [logits[i, :len(p)] for i, p in enumerate(positions)]
