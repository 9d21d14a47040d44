"""Plain float32 reference of Zamba2 (zamba2-2.7b), after the equations of
``transformers.models.zamba2.modeling_zamba2`` (transformers 4.57.6).

The stack is ``n_layers`` Mamba2 layers.  Layer ``l`` is

    t = shared_i(x, x0) if l is the i-th of hybrid_layers else 0
    x = x + mamba2(rmsnorm(x + t))

where ``x0`` are the token embeddings and ``shared_i`` is invocation ``i``
of shared block ``i mod shared_blocks``:

    h = rmsnorm(concat(x, x0))                       # width 2d
    h = rmsnorm(attention(h) W_o)                    # causal, no rotary
    [g, u] = h W_gate_up + (h A_i) B_i               # LoRA of invocation i
    shared_i = ((gelu(g) * u) W_down) W_linear_i     # exact GELU

with scores scaled by ``(head_dim / 2)^-1/2``.  The shared block has no
residual: its output enters the Mamba2 layer's input only.  A Mamba2 layer
projects ``z``, ``x``, ``B``, ``C`` and ``dt`` from its input, runs a causal
depthwise convolution with bias and SiLU over ``concat(x, B, C)``,
discretises with ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``,
scans the state-space recurrence with one group, adds ``D * x``, and ends
with the gated RMSNorm ``rmsnorm(y * silu(z))`` and the output projection.
The head is tied to the embeddings.

The scan runs chunked in float32 (the state-space dual form); ``dt`` is
not clamped below, as in the model's CUDA kernel path, whose
``time_step_limit`` is None (the pure-torch fallback clamps it at
``time_step_min``).

Also here: the weights the benchmark serves the program (one jitted call
from the seed, in the program's parameter layout and serving dtype), and
the operations and bytes of a prefill, of one row's decode step and of a
decode round over several rows, computed from shapes alone.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

Model = Dict[str, Any]
F32 = C.F32
HI = C.HI
MAMBA, NORM = "L0_0_mamba2", "L0_0_norm"


def check_model(mc: Model) -> None:
    if C.pattern(mc) != (("mamba2",),) or not mc.get("hybrid_layers"):
        raise ValueError(f"the Zamba2 reference needs the pattern "
                         f"[[mamba2]] and hybrid_layers, got "
                         f"{mc['pattern']}, {mc.get('hybrid_layers')}")


def _ssm(mc: Model):
    """(d_inner, heads, head width, state, conv width) of a Mamba2 layer."""
    din = mc["ssm_expand"] * mc["d_model"]
    H = mc["ssm_heads"]
    return din, H, din // H, mc["ssm_state"], mc["ssm_conv"]


def _attn(mc: Model):
    return mc["n_heads"], mc["n_kv_heads"], mc["d_head"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def block_init(key, mc: Model, dt) -> Dict[str, Any]:
    d, ff = mc["d_model"], mc["d_ff"]
    H, Hkv, D = _attn(mc)
    k = jax.random.split(key, 8)
    return {"norm": {"scale": C.around_one(k[0], (2 * d,))},
            "attn": {"wq": C.dense(k[1], (2 * d, H * D), dt),
                     "wk": C.dense(k[2], (2 * d, Hkv * D), dt),
                     "wv": C.dense(k[3], (2 * d, Hkv * D), dt),
                     "wo": C.dense(k[4], (H * D, d), dt)},
            "norm2": {"scale": C.around_one(k[5], (d,))},
            "mlp": {"gate_up": C.dense(k[6], (d, 2 * ff), dt),
                    "down": C.dense(k[7], (ff, d), dt)}}


def adapter_init(key, mc: Model, dt) -> Dict[str, Any]:
    d, ff, r = mc["d_model"], mc["d_ff"], mc["adapter_rank"]
    k = jax.random.split(key, 3)
    return {"lora_a": C.dense(k[0], (d, r), dt),
            "lora_b": C.dense(k[1], (r, 2 * ff), dt),
            "linear": C.dense(k[2], (d, d), dt)}


def init_top(mc: Model, key) -> Dict[str, Any]:
    """Embeddings, final norm, and the shared blocks with the adapters of
    every invocation (stacked)."""
    dt = jnp.dtype(mc["dtype"])
    k = jax.random.split(key, 4)
    nb, ni = mc["shared_blocks"], len(mc["hybrid_layers"])
    return {"embed": (0.02 * jax.random.normal(
                k[0], (mc["vocab"], mc["d_model"]), F32)).astype(dt),
            "final_norm": {"scale": C.around_one(k[1], (mc["d_model"],))},
            "shared": {
                "blocks": jax.vmap(lambda kk: block_init(kk, mc, dt))(
                    jax.random.split(k[2], nb)),
                "adapters": jax.vmap(lambda kk: adapter_init(kk, mc, dt))(
                    jax.random.split(k[3], ni))}}


def init_unit(mc: Model, key) -> Dict[str, Any]:
    """One Mamba2 layer and its input norm.  ``A = -exp(A_log)`` is drawn
    from [-16, -1] (Mamba2's initial range) and ``dt_bias`` is the inverse
    softplus of a step drawn log-uniform in [0.001, 0.1], as in the
    published initialisation."""
    dt = jnp.dtype(mc["dtype"])
    d = mc["d_model"]
    din, H, _, n, K = _ssm(mc)
    k = jax.random.split(key, 14)
    step = jnp.exp(jax.random.uniform(k[6], (H,), F32, np.log(1e-3),
                                      np.log(1e-1)))
    return {MAMBA: {"wz": C.dense(k[0], (d, din), dt),
                    "wx": C.dense(k[1], (d, din), dt),
                    "wB": C.dense(k[2], (d, n), dt),
                    "wC": C.dense(k[3], (d, n), dt),
                    "wdt": C.dense(k[4], (d, H), dt),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "A_log": jnp.log(jax.random.uniform(k[7], (H,), F32,
                                                        1.0, 16.0)),
                    "D": C.around_one(k[8], (H,)),
                    "conv_w": C.dense(k[9], (K, din + 2 * n), dt),
                    "conv_b": 0.1 * jax.random.normal(k[10], (din + 2 * n,),
                                                      F32),
                    "gn": C.around_one(k[11], (din,)),
                    "out": C.dense(k[12], (din, d), dt)},
            NORM: {"scale": C.around_one(k[13], (d,))}}


def init(mc: Model, key) -> Dict[str, Any]:
    """The served weights (call under ``jax.jit``)."""
    check_model(mc)
    return C.init_params(mc, key, init_top, init_unit)


# ---------------------------------------------------------------------------
# the float32 forward
# ---------------------------------------------------------------------------
def ssd(x, dt, A, B, Cm, chunk: int):
    """The state-space scan in its chunked (dual) form, float32.

    x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, n) (one group).
    ``y_t = sum_{j<=t} C_t . B_j exp(sum_{j<k<=t} dt_k A) dt_j x_j``.
    """
    b, s, h, p = x.shape
    pad = (-s) % chunk
    if pad:  # dt = 0 past the end: no decay, no input, outputs dropped
        x, dt, B, Cm = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] *
                                (a.ndim - 2)) for a in (x, dt, B, Cm))
    c = (s + pad) // chunk

    def chunks(a):
        return a.reshape((b, c, chunk) + a.shape[2:])

    xd = chunks(x * dt[..., None])                           # (b,c,l,h,p)
    acum = jnp.cumsum(chunks(dt * A), axis=2)                # (b,c,l,h)
    Bc, Cc = chunks(B), chunks(Cm)                           # (b,c,l,n)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    seg = acum[:, :, :, None, :] - acum[:, :, None, :, :]    # (b,c,l,m,h)
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jnp.einsum("bcln,bcmn->bclm", Cc, Bc, precision=HI)
    y = jnp.einsum("bclm,bclmh,bcmhp->bclhp", cb, decay, xd, precision=HI)
    # each chunk's contribution to the state it hands on
    to_end = jnp.exp(acum[:, :, -1:, :] - acum)              # (b,c,l,h)
    states = jnp.einsum("bcln,bclh,bclhp->bchpn", Bc, to_end, xd,
                        precision=HI)
    total = jnp.exp(acum[:, :, -1, :])                       # (b,c,h)
    state = jnp.zeros((b, h, p, Bc.shape[-1]), F32)
    entering = []
    for i in range(c):
        entering.append(state)
        state = state * total[:, i, :, None, None] + states[:, i]
    entering = jnp.stack(entering, axis=1)                   # (b,c,h,p,n)
    y = y + jnp.einsum("bcln,bchpn,bclh->bclhp", Cc, entering, jnp.exp(acum),
                       precision=HI)
    return y.reshape(b, c * chunk, h, p)[:, :s]


def mamba_layer(mc: Model, up, x, t, quant: bool):
    """``x + mamba2(rmsnorm(x + t))``: one layer of the stack."""
    p = up[MAMBA]
    din, H, P, n, K = _ssm(mc)
    b, s, _ = x.shape
    h = C.rmsnorm(x + t, up[NORM]["scale"], mc["norm_eps"])
    z = C.mm(h, p["wz"], quant)
    xbc = jnp.concatenate([C.mm(h, p["wx"], quant), C.mm(h, p["wB"], quant),
                           C.mm(h, p["wC"], quant)], axis=-1)
    dt = jax.nn.softplus(C.mm(h, p["wdt"], quant) + p["dt_bias"])
    w = p["conv_w"].astype(F32)
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + s] * w[i] for i in range(K))
                      + p["conv_b"])
    xs, Bm, Cm = jnp.split(xbc, [din, din + n], axis=-1)
    xs = xs.reshape(b, s, H, P)
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, mc["ssm_chunk"])
    y = (y + xs * p["D"][:, None]).reshape(b, s, din)
    g = C.rmsnorm(y * jax.nn.silu(z), p["gn"], mc["norm_eps"])
    return x + C.mm(g, p["out"], quant)


def shared_block(mc: Model, top, i, x, x0, quant: bool):
    """Invocation ``i`` (block ``i mod shared_blocks``), its linear last."""
    H, Hkv, D = _attn(mc)
    blk = jax.tree.map(lambda a: a[i % mc["shared_blocks"]],
                       top["shared"]["blocks"])
    ad = jax.tree.map(lambda a: a[i], top["shared"]["adapters"])
    eps = mc["norm_eps"]
    b, s, _ = x.shape
    h = C.rmsnorm(jnp.concatenate([x, x0], -1), blk["norm"]["scale"], eps)
    w = blk["attn"]
    q = C.mm(h, w["wq"], quant).reshape(b, s, H, D)
    k = jnp.repeat(C.mm(h, w["wk"], quant).reshape(b, s, Hkv, D), H // Hkv,
                   axis=2)
    v = jnp.repeat(C.mm(h, w["wv"], quant).reshape(b, s, Hkv, D), H // Hkv,
                   axis=2)
    outs = []
    for q0 in range(0, s, C.Q_BLOCK):  # blocks of queries keep scores small
        qb = q[:, q0:q0 + C.Q_BLOCK]
        sc = jnp.einsum("bshd,bthd->bhst", qb, k, precision=HI) \
            * (D / 2) ** -0.5
        qpos = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos, sc, -jnp.inf)
        outs.append(jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v,
                               precision=HI))
    o = jnp.concatenate(outs, axis=1).reshape(b, s, H * D)
    h = C.rmsnorm(C.mm(o, w["wo"], quant), blk["norm2"]["scale"], eps)
    gu = C.mm(h, blk["mlp"]["gate_up"], quant) + C.mm(
        C.mm(h, ad["lora_a"], quant), ad["lora_b"], quant)
    g, u = jnp.split(gu, 2, axis=-1)
    m = C.mm(jax.nn.gelu(g, approximate=False) * u, blk["mlp"]["down"], quant)
    return C.mm(m, ad["linear"], quant)


def head(mc: Model, top, x, quant: bool):
    x = C.rmsnorm(x, top["final_norm"]["scale"], mc["norm_eps"])
    return C.mm(x, top["embed"].T, quant)


def forward(mc: Model, key, seqs, positions, *, quant: bool = False,
            shape=(0, 0, 0)):
    """Float32 next-token logits of ``seqs`` at ``positions``.

    As :func:`refs.common.forward`: sequences right-padded to one length
    and the batch to at least ``shape``; the top level (embeddings, shared
    blocks, adapters) made once from ``key``, then the Mamba2 layers
    regenerated one at a time, so that at most one layer is held in
    float32 beside the shared blocks.
    """
    check_model(mc)
    rows = max(len(seqs), shape[0])
    lmax = max(max(len(s) for s in seqs), shape[1])
    npos = max(max(len(p) for p in positions), shape[2])
    toks = np.zeros((rows, lmax), np.int32)
    pos = np.zeros((rows, npos), np.int32)
    for i, (s, p) in enumerate(zip(seqs, positions)):
        toks[i, :len(s)] = s
        pos[i, :len(p)] = p
        pos[i, len(p):] = p[-1]
    hybrid = {l: i for i, l in enumerate(mc["hybrid_layers"])}

    with jax.default_matmul_precision("highest"):
        top = jax.jit(lambda k: init_top(mc, k))(jax.random.fold_in(key, 0))
        x0 = jnp.take(top["embed"], jnp.asarray(toks), axis=0).astype(F32)
        keys = C.unit_keys(jax.random.fold_in(key, 1), mc)
        make = jax.jit(lambda k: init_unit(mc, k))
        layer = jax.jit(lambda up, x, t: mamba_layer(mc, up, x, t, quant))
        invoke = jax.jit(lambda top, i, x, x0:
                         shared_block(mc, top, i, x, x0, quant))
        x = x0
        for l in range(mc["n_layers"]):
            t = invoke(top, jnp.int32(hybrid[l]), x, x0) if l in hybrid \
                else jnp.zeros_like(x)
            x = layer(make(keys[l]), x, t)
        logits = jax.jit(lambda x, pos, top: head(
            mc, top, jnp.take_along_axis(x, pos[..., None], axis=1), quant))(
                x, jnp.asarray(pos), top)
    logits = np.asarray(logits)
    return [logits[i, :len(p)] for i, p in enumerate(positions)]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------
def _item(mc: Model) -> int:
    return jnp.dtype(mc["dtype"]).itemsize


def _mamba_weights(mc: Model):
    """(matrix elements, float32 elements) of one Mamba2 layer and its
    norm."""
    d = mc["d_model"]
    din, H, _, n, K = _ssm(mc)
    mats = d * (2 * din + 2 * n + H) + K * (din + 2 * n) + din * d
    return mats, 3 * H + (din + 2 * n) + din + d


def _block_weights(mc: Model):
    """(matrix elements, float32 elements) of one shared block."""
    d, ff = mc["d_model"], mc["d_ff"]
    H, Hkv, D = _attn(mc)
    return 2 * d * (H + 2 * Hkv) * D + H * D * d + 3 * d * ff, 3 * d


def _adapter_weights(mc: Model) -> int:
    d, ff, r = mc["d_model"], mc["d_ff"], mc["adapter_rank"]
    return d * r + r * 2 * ff + d * d


def param_count(mc: Model) -> int:
    """Parameters of the whole model, from shapes."""
    mm, mf = _mamba_weights(mc)
    bm, bf = _block_weights(mc)
    return (mc["n_layers"] * (mm + mf) + mc["shared_blocks"] * (bm + bf)
            + len(mc["hybrid_layers"]) * _adapter_weights(mc)
            + mc["vocab"] * mc["d_model"] + mc["d_model"])


def weight_bytes(mc: Model) -> int:
    """Bytes of weights one pass of a token through the model reads: the
    Mamba2 layers and the tied head once, and a shared block once at each
    of its invocations, with that invocation's adapter."""
    mm, mf = _mamba_weights(mc)
    bm, bf = _block_weights(mc)
    ni, item = len(mc["hybrid_layers"]), _item(mc)
    return (mc["n_layers"] * (mm * item + mf * 4)
            + ni * ((bm + _adapter_weights(mc)) * item + bf * 4)
            + mc["vocab"] * mc["d_model"] * item + mc["d_model"] * 4)


def _token_flops(mc: Model) -> int:
    """Weight-matmul and convolution operations of one token through the
    whole stack (attention over the cache and the scan counted apart)."""
    mm, _ = _mamba_weights(mc)
    bm, _ = _block_weights(mc)
    return (mc["n_layers"] * 2 * mm
            + len(mc["hybrid_layers"]) * 2 * (bm + _adapter_weights(mc)))


def ssd_flops(mc: Model, S: int) -> int:
    """Operations of one layer's chunked scan over ``S`` positions: C.B
    over the causal pairs within each chunk (shared by the heads of the
    one group), that product applied to every head's inputs, and each
    head's state handed on and read back (two (p x n) products a
    position)."""
    _, H, P, n, _ = _ssm(mc)
    l = min(mc["ssm_chunk"], S)
    pairs = (S // l) * l * (l + 1) // 2 + (S % l) * (S % l + 1) // 2
    return 2 * pairs * (n + H * P) + 4 * S * H * P * n


def row_state_bytes(mc: Model, cache_len: int):
    """(KV cache, recurrent state) bytes of one row: each invocation's k
    and v over ``cache_len`` positions; each Mamba2 layer's float32 state
    and its convolution's last ``conv - 1`` inputs."""
    din, H, P, n, K = _ssm(mc)
    _, Hkv, D = _attn(mc)
    item = _item(mc)
    kv = len(mc["hybrid_layers"]) * 2 * cache_len * Hkv * D * item
    ssm = mc["n_layers"] * (H * P * n * 4 + (K - 1) * (din + 2 * n) * item)
    return kv, ssm


def prefill_cost(mc: Model, S: int):
    """(operations, bytes) of a prefill of ``S`` tokens: every position
    through every layer and invocation, causal attention at each
    invocation, the head at the last position; weights read once per
    invocation, the KV caches and states written."""
    H, _, D = _attn(mc)
    ni = len(mc["hybrid_layers"])
    attn = ni * 4 * H * D * S * (S + 1) // 2
    flops = S * _token_flops(mc) + mc["n_layers"] * ssd_flops(mc, S) + attn \
        + 2 * mc["d_model"] * mc["vocab"]
    kv, ssm = row_state_bytes(mc, S)
    return flops, weight_bytes(mc) + kv + ssm


def decode_cost(mc: Model, cache_len: int):
    """(operations, bytes) of one row's decode step against a cache of
    ``cache_len`` positions, read whole; each layer's state read and
    written."""
    _, H, P, n, _ = _ssm(mc)
    Ha, _, D = _attn(mc)
    ni = len(mc["hybrid_layers"])
    flops = _token_flops(mc) + mc["n_layers"] * 4 * H * P * n \
        + ni * 4 * Ha * D * cache_len + 2 * mc["d_model"] * mc["vocab"]
    kv, ssm = row_state_bytes(mc, cache_len)
    return flops, weight_bytes(mc) + kv + 2 * ssm


def round_cost(mc: Model, cache_len: int, rows: int):
    """(operations, bytes) of one decode round over ``rows`` rows: the
    weights once (each shared block once per invocation), and every row's
    KV cache read and state read and written."""
    flops, _ = decode_cost(mc, cache_len)
    kv, ssm = row_state_bytes(mc, cache_len)
    return rows * flops, weight_bytes(mc) + rows * (kv + 2 * ssm)
