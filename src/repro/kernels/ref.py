"""Pure-jnp oracle implementations for every Pallas kernel.

These are the ground truth the kernels are validated against
(``tests/test_kernels.py`` sweeps shapes/dtypes with ``interpret=True``),
what ``ops`` runs for a kernel with ``impl="ref"`` (the CPU default), and
the layers with no Pallas variant.  The dense :func:`attention` is an
oracle only: the models decode through ``decode_attention``, which the
tests compare against it.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Attention (GQA, causal / bidirectional, sliding window, logit softcap)
# ---------------------------------------------------------------------------
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              window: Optional[int] = None,
              softcap: float = 0.0,
              kv_valid_len: Optional[jax.Array] = None,
              q_offset: Optional[jax.Array] = None,
              scale: Optional[float] = None) -> jax.Array:
    """Reference multi-head attention.

    q: (B, S, H, D); k, v: (B, T, Hkv, D) with H % Hkv == 0.
    Scores are scaled by ``scale`` (default ``D^-1/2``).
    ``kv_valid_len``: (B,) — only cache positions < len attend (decode).
    ``q_offset``: (B,) — absolute position of q[:, 0] (decode: cache index).
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    dtype = q.dtype
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if rep > 1:
        kf = jnp.repeat(kf, rep, axis=2)
        vf = jnp.repeat(vf, rep, axis=2)
    if scale is None:
        scores = jnp.einsum("bshd,bthd->bhst", qf, kf) / jnp.sqrt(D)
    else:
        scores = jnp.einsum("bshd,bthd->bhst", qf, kf) * scale
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap

    q_pos = jnp.arange(S)[:, None]  # (S, 1)
    if q_offset is not None:
        q_pos = q_pos[None] + q_offset[:, None, None]  # (B, S, 1)
    else:
        q_pos = q_pos[None]
    k_pos = jnp.arange(T)[None, None, :]  # (1, 1, T)
    mask = jnp.ones((B if q_offset is not None else 1, S, T), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    if kv_valid_len is not None:
        mask &= k_pos < kv_valid_len[:, None, None]
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, vf)
    return out.astype(dtype)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      chunk: int = 512, constrain=None,
                      q_offset: int = 0,
                      scale: Optional[float] = None) -> jax.Array:
    """Flash-style attention in pure jnp with a flash-style custom VJP.

    Forward: ``lax.scan`` over key chunks with online-softmax running
    statistics — O(S·chunk) memory instead of O(S·T).  Backward: the
    standard flash recomputation — saves only (q, k, v, out, lse) and
    rebuilds each chunk's probabilities from lse, so autodiff does NOT
    stack per-chunk score tensors (which at 4k×4k×heads was the dominant
    activation-memory term).  Numerics match :func:`attention`.

    ``constrain``: optional hook with ``.attn_acc`` ((B,H,S,D)) and
    ``.attn_stats`` ((B,H,S)) sharding constraints — GSPMD's while-loop
    sharding propagation otherwise REPLICATES the scan carries, which at
    (256,48,4096,128) fp32 is a 24 GiB-per-device bug, not a perf knob.
    Scores are scaled by ``scale`` (default ``D^-1/2``).
    """
    return _attn_vjp(q, k, v, causal, window, chunk, constrain, q_offset,
                     scale)


def attention_causal_split(q, k, v, *, chunk: int = 512, constrain=None,
                           scale: Optional[float] = None):
    """One-level causal split: the first half of q attends only the first
    half of k/v — removes the fully-masked lower-left quadrant, cutting
    causal attention flops by 25% (and its kernel-tile traffic likewise).
    The halves are independent, so GSPMD parallelism is unaffected."""
    B, S, H, D = q.shape
    half = S // 2
    o1 = attention_chunked(q[:, :half], k[:, :half], v[:, :half],
                           causal=True, chunk=chunk, constrain=constrain,
                           scale=scale)
    o2 = attention_chunked(q[:, half:], k, v, causal=True, chunk=chunk,
                           constrain=constrain, q_offset=half, scale=scale)
    return jnp.concatenate([o1, o2], axis=1)


def _chunk_mask(ci, chunk, T, S, causal, window, q_offset=0):
    q_pos = q_offset + jnp.arange(S)[:, None]
    k_pos = ci * chunk + jnp.arange(chunk)[None, :]
    mask = k_pos < T
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    return mask  # (S, chunk)


def _c_acc(constrain, x):
    return constrain.attn_acc(x) if constrain is not None else x


def _c_stats(constrain, x):
    return constrain.attn_stats(x) if constrain is not None else x


def _attn_fwd_impl(q, k, v, causal, window, chunk, constrain=None,
                   q_offset=0, scale=None):
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (T + pad) // chunk
    kc = jnp.moveaxis(k.reshape(B, nc, chunk, Hkv, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nc, chunk, Hkv, D), 1, 0)

    qf = q.astype(jnp.float32)
    scale = 1.0 / (D ** 0.5) if scale is None else scale

    def body(carry, xs):
        # named_scope tags every op in this body as kernel-resident: on the
        # TPU Pallas kernel these tiles never touch HBM, and the roofline
        # analyzer reports a kernel-adjusted memory term (hlo_cost.py).
        with jax.named_scope("vmem_resident_flash"):
            m, l, acc = carry
            kb, vb, ci = xs
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
            if rep > 1:
                kb = jnp.repeat(kb, rep, axis=2)
                vb = jnp.repeat(vb, rep, axis=2)
            s = jnp.einsum("bshd,bthd->bhst", qf, kb) * scale
            mask = _chunk_mask(ci, chunk, T, S, causal, window, q_offset)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bhst,bthd->bhsd",
                                                      p, vb)
            return (_c_stats(constrain, m_new), _c_stats(constrain, l),
                    _c_acc(constrain, acc)), None

    m0 = _c_stats(constrain, jnp.full((B, H, S), NEG_INF, jnp.float32))
    l0 = _c_stats(constrain, jnp.zeros((B, H, S), jnp.float32))
    a0 = _c_acc(constrain, jnp.zeros((B, H, S, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(nc)))
    lsafe = jnp.where(l == 0.0, 1.0, l)
    out = acc / lsafe[..., None]
    lse = m + jnp.log(lsafe)                          # (B,H,S)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attn_vjp(q, k, v, causal, window, chunk, constrain=None, q_offset=0,
              scale=None):
    out, _ = _attn_fwd_impl(q, k, v, causal, window, chunk, constrain,
                            q_offset, scale)
    return out


def _attn_vjp_fwd(q, k, v, causal, window, chunk, constrain=None,
                  q_offset=0, scale=None):
    out, lse = _attn_fwd_impl(q, k, v, causal, window, chunk, constrain,
                              q_offset, scale)
    return out, (q, k, v, out, lse)


def _attn_vjp_bwd(causal, window, chunk, constrain, q_offset, scale, res,
                  dout):
    """Flash backward: recompute per-chunk probabilities from lse."""
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (T + pad) // chunk
    kcs = jnp.moveaxis(k.reshape(B, nc, chunk, Hkv, D), 1, 0)
    vcs = jnp.moveaxis(v.reshape(B, nc, chunk, Hkv, D), 1, 0)

    qf = q.astype(jnp.float32)
    do = dout.astype(jnp.float32)
    of = out.astype(jnp.float32)
    scale = 1.0 / (D ** 0.5) if scale is None else scale
    # delta[b,s,h] = sum_d dout*out — the softmax-jacobian diagonal term
    delta = jnp.einsum("bshd,bshd->bhs", do, of)       # (B,H,S)

    def body(dq, xs):
        with jax.named_scope("vmem_resident_flash"):
            kb, vb, ci = xs
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
            if rep > 1:
                kbr = jnp.repeat(kb, rep, axis=2)
                vbr = jnp.repeat(vb, rep, axis=2)
            else:
                kbr, vbr = kb, vb
            s = jnp.einsum("bshd,bthd->bhst", qf, kbr) * scale
            mask = _chunk_mask(ci, chunk, T, S, causal, window, q_offset)
            p = jnp.exp(s - lse[..., None])                # (B,H,S,chunk)
            p = jnp.where(mask[None, None], p, 0.0)
            dv_c = jnp.einsum("bhst,bshd->bthd", p, do)    # (B,chunk,H,D)
            dp = jnp.einsum("bshd,bthd->bhst", do, vbr)
            ds = p * (dp - delta[..., None]) * scale
            dq = dq + jnp.einsum("bhst,bthd->bshd", ds, kbr)
            if constrain is not None:
                dq = constrain.heads(dq)
            dk_c = jnp.einsum("bhst,bshd->bthd", ds, qf)   # (B,chunk,H,D)
            if rep > 1:
                dk_c = dk_c.reshape(B, chunk, Hkv, rep, D).sum(3)
                dv_c = dv_c.reshape(B, chunk, Hkv, rep, D).sum(3)
            return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((B, S, H, D), jnp.float32)
    if constrain is not None:
        dq0 = constrain.heads(dq0)
    dq, (dks, dvs) = jax.lax.scan(body, dq0,
                                  (kcs, vcs, jnp.arange(nc)))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, nc * chunk, Hkv, D)[:, :T]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, nc * chunk, Hkv, D)[:, :T]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_attn_vjp.defvjp(_attn_vjp_fwd, _attn_vjp_bwd)


# ---------------------------------------------------------------------------
# Mamba2 / SSD (state space dual) — chunked scan
# ---------------------------------------------------------------------------
def _segsum(x: jax.Array) -> jax.Array:
    """(..., T) -> (..., T, T) lower-triangular segment sums.

    out[i, j] = sum_{j < k <= i} x[k]  (i >= j), -inf above the diagonal.
    """
    T = x.shape[-1]
    c = jnp.cumsum(x, axis=-1)
    d = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, *, chunk: int,
                init_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan (Mamba2, "ssd_minimal_discrete" algorithm).

    x: (b, s, h, p) — per-head inputs;   dt: (b, s, h) — timestep (>0);
    A: (h,) — negative per-head decay;   B, C: (b, s, n) — shared across heads
    (single-group).  Returns (y: (b, s, h, p), final_state: (b, h, p, n)).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    f32 = jnp.float32

    xd = x.astype(f32) * dt.astype(f32)[..., None]     # discretized input
    dA = (dt.astype(f32) * A).astype(f32)              # (b, s, h) — negative

    def ch(t, extra=()):  # (b, s, ...) -> (b, nc, chunk, ...)
        return t.reshape((b, nc, chunk) + t.shape[2:])

    xd_c = ch(xd)                                      # (b,c,l,h,p)
    dA_c = jnp.transpose(ch(dA), (0, 3, 1, 2))          # (b,h,c,l)
    B_c = ch(B.astype(f32))                            # (b,c,l,n)
    C_c = ch(C.astype(f32))                            # (b,c,l,n)

    # 1. intra-chunk (diagonal block) outputs — in the Pallas kernel the
    # (l,l) decay matrices live in VMEM; tagged for the adjusted roofline.
    with jax.named_scope("vmem_resident_ssd"):
        L = jnp.exp(_segsum(dA_c))                     # (b,h,c,l,l)
        Y_diag = jnp.einsum("bcln,bcmn,bhclm,bcmhp->bclhp",
                            C_c, B_c, L, xd_c)

    # 2. per-chunk final states
    dA_cum = jnp.cumsum(dA_c, axis=-1)                 # (b,h,c,l)
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)  # (b,h,c,l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", B_c, decay_states, xd_c)

    # 3. inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(dA_cum[..., -1])             # (b,h,c)
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), f32)
    else:
        init_state = init_state.astype(f32)

    def step(carry, inp):
        st, dec = inp                                  # (b,h,p,n), (b,h)
        new = carry * dec[..., None, None] + st
        return new, carry                              # emit state ENTERING chunk

    final_state, prev_states = jax.lax.scan(
        step, init_state,
        (jnp.transpose(states, (1, 0, 2, 3, 4)),        # (c,b,h,p,n)
         jnp.transpose(chunk_decay, (2, 0, 1))))        # (c,b,h)
    prev_states = jnp.transpose(prev_states, (1, 0, 2, 3, 4))  # (b,c,h,p,n)

    # 4. chunk-input contribution
    state_decay_out = jnp.exp(dA_cum)                  # (b,h,c,l)
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", C_c, prev_states,
                       state_decay_out)

    y = (Y_diag + Y_off).reshape(b, s, h, p).astype(x.dtype)
    return y, final_state


def ssd_sequential(x, dt, A, B, C, *, init_state=None):
    """Stepwise oracle for :func:`ssd_chunked` (and the decode path)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    def step(state, inp):
        xt, dtt, Bt, Ct = inp  # (b,h,p), (b,h), (b,n), (b,n)
        state, yt = ssd_decode_step(state, xt, dtt, A, Bt, Ct)
        return state, yt

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    final, ys = jax.lax.scan(step, init_state.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def ssd_decode_step(state, xt, dtt, A, Bt, Ct):
    """One recurrent SSD step.  state: (b,h,p,n) fp32."""
    f32 = jnp.float32
    xt, dtt, Bt, Ct = (t.astype(f32) for t in (xt, dtt, Bt, Ct))
    decay = jnp.exp(dtt * A)[..., None, None]            # (b,h,1,1)
    upd = jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], Bt)
    state = state * decay + upd
    yt = jnp.einsum("bhpn,bn->bhp", state, Ct)
    return state, yt


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix-memory cell) — sequential oracle + chunked parallel
# ---------------------------------------------------------------------------
def mlstm_sequential(q, k, v, i_gate, f_gate, *, init=None):
    """Stabilised mLSTM recurrence (xLSTM eq. 19-27).

    q,k,v: (b, s, h, d);  i_gate, f_gate: (b, s, h) — pre-activation logits.
    Returns (y: (b,s,h,d), state=(C: (b,h,d,d), n: (b,h,d), m: (b,h))).
    """
    b, s, h, d = q.shape
    f32 = jnp.float32
    if init is None:
        C0 = jnp.zeros((b, h, d, d), f32)
        n0 = jnp.zeros((b, h, d), f32)
        m0 = jnp.full((b, h), -jnp.inf, f32)
    else:
        C0, n0, m0 = (t.astype(f32) for t in init)

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, it, ft = inp
        qt, kt, vt = qt.astype(f32), kt.astype(f32), vt.astype(f32)
        it, ft = it.astype(f32), ft.astype(f32)
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        fs = jnp.exp(logf + m - m_new)
        is_ = jnp.exp(it - m_new)
        C = C * fs[..., None, None] + \
            is_[..., None, None] * (kt[..., :, None] * vt[..., None, :])
        n = n * fs[..., None] + is_[..., None] * kt
        num = jnp.einsum("bhdj,bhd->bhj", C, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, qt)),
                          jnp.exp(-m_new))
        return (C, n, m_new), num / den[..., None]

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, i_gate, f_gate))
    (C, n, m), ys = jax.lax.scan(step, (C0, n0, m0), xs)
    return jnp.moveaxis(ys, 0, 1).astype(q.dtype), (C, n, m)


def mlstm_chunked(q, k, v, i_gate, f_gate, *, chunk: int, init=None):
    """Chunkwise-parallel mLSTM (matmul-heavy; quadratic inside chunks).

    Same interface as :func:`mlstm_sequential`; validated against it.
    """
    b, s, h, d = q.shape
    assert s % chunk == 0
    nc = s // chunk
    f32 = jnp.float32

    qf = q.astype(f32).reshape(b, nc, chunk, h, d)
    kf = k.astype(f32).reshape(b, nc, chunk, h, d)
    vf = v.astype(f32).reshape(b, nc, chunk, h, d)
    ig = i_gate.astype(f32).reshape(b, nc, chunk, h).transpose(0, 3, 1, 2)
    lf = jax.nn.log_sigmoid(f_gate.astype(f32)) \
        .reshape(b, nc, chunk, h).transpose(0, 3, 1, 2)   # (b,h,c,l)

    if init is None:
        C0 = jnp.zeros((b, h, d, d), f32)
        n0 = jnp.zeros((b, h, d), f32)
        m0 = jnp.full((b, h), -jnp.inf, f32)
    else:
        C0, n0, m0 = (t.astype(f32) for t in init)

    lf_cum = jnp.cumsum(lf, axis=-1)                       # (b,h,c,l)
    # local (within-chunk) stabilizer candidates: decay-to-t + gate at source
    # a[i,j] = sum_{j<k<=i} logf_k + i_j   (j <= i)
    seg = _segsum(lf)                                      # (b,h,c,l,l)
    a_local = seg + ig[..., None, :]                       # (b,h,c,l,l)
    m_local = jnp.max(jnp.where(jnp.isfinite(a_local), a_local, -jnp.inf),
                      axis=-1)                             # (b,h,c,l)

    # sequential scan over chunks for carry state (C, n, m)
    def chunk_step(carry, idx):
        C, n, m = carry
        qc = qf[:, idx]
        kc = kf[:, idx]
        vc = vf[:, idx]
        igc = ig[:, :, idx]                                # (b,h,l)
        lfc = lf[:, :, idx]
        lf_cumc = lf_cum[:, :, idx]                        # (b,h,l)
        segc = seg[:, :, idx]                              # (b,h,l,l)
        a_loc = a_local[:, :, idx]                         # (b,h,l,l)

        # incoming-state contribution has log-scale lf_cum + m_prev
        m_in = lf_cumc + m[..., None]                      # (b,h,l)
        m_new = jnp.maximum(m_local[:, :, idx], m_in)      # (b,h,l)

        # intra-chunk attention-style term
        w = jnp.exp(a_loc - m_new[..., None])              # (b,h,l,l)
        scores = jnp.einsum("blhd,bmhd->bhlm", qc, kc) * w
        num_local = jnp.einsum("bhlm,bmhd->blhd", scores, vc)
        den_local_q = jnp.sum(scores, axis=-1)             # (b,h,l) = q·n_loc

        # inter-chunk contribution
        scale_in = jnp.exp(m_in - m_new)                   # (b,h,l)
        num_in = jnp.einsum("blhd,bhde->blhe", qc, C) * scale_in.transpose(
            0, 2, 1)[..., None]
        den_in_q = jnp.einsum("blhd,bhd->bhl", qc, n) * scale_in

        num = num_local + num_in
        den = den_local_q + den_in_q                       # (b,h,l)
        den = jnp.maximum(jnp.abs(den), jnp.exp(-m_new))
        y = num / den.transpose(0, 2, 1)[..., None]

        # update carry to end of chunk: the stabiliser at the chunk's last
        # position is exactly the sequential m there, so reuse it.
        total = lf_cumc[..., -1]                           # (b,h)
        m_end = m_new[..., -1]                             # (b,h)
        # contribution of each position j to the end-of-chunk state:
        # exp(i_j + sum_{j<k<=L} logf_k - m_end)
        w_end = jnp.exp(igc + total[..., None] - lf_cumc - m_end[..., None])
        C_new = C * jnp.exp(total + m - m_end)[..., None, None] + \
            jnp.einsum("bhl,blhd,blhe->bhde", w_end, kc, vc)
        n_new = n * jnp.exp(total + m - m_end)[..., None] + \
            jnp.einsum("bhl,blhd->bhd", w_end, kc)
        return (C_new, n_new, m_end), y

    (C, n, m), ys = jax.lax.scan(chunk_step, (C0, n0, m0), jnp.arange(nc))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, d)
    return y.astype(q.dtype), (C, n, m)


def mlstm_decode_step(state, qt, kt, vt, it, ft):
    """One mLSTM step. state=(C,n,m) fp32; qt/kt/vt: (b,h,d); it/ft: (b,h)."""
    C, n, m = state
    f32 = jnp.float32
    qt, kt, vt = qt.astype(f32), kt.astype(f32), vt.astype(f32)
    logf = jax.nn.log_sigmoid(ft.astype(f32))
    m_new = jnp.maximum(logf + m, it.astype(f32))
    fs = jnp.exp(logf + m - m_new)
    is_ = jnp.exp(it.astype(f32) - m_new)
    C = C * fs[..., None, None] + is_[..., None, None] * (
        kt[..., :, None] * vt[..., None, :])
    n = n * fs[..., None] + is_[..., None] * kt
    num = jnp.einsum("bhdj,bhd->bhj", C, qt)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, qt)),
                      jnp.exp(-m_new))
    return (C, n, m_new), num / den[..., None]


# ---------------------------------------------------------------------------
# Grouped matmul over expert segments (MoE)
# ---------------------------------------------------------------------------
def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Grouped matmul: rows of ``x`` are sorted by expert; ``group_sizes[e]``
    consecutive rows use ``w[e]``.

    x: (T, K);  w: (E, K, N);  group_sizes: (E,) int32 summing to T.
    Returns (T, N).
    """
    T = x.shape[0]
    E = w.shape[0]
    starts = jnp.cumsum(group_sizes) - group_sizes
    # expert id per row
    row = jnp.arange(T)
    eid = jnp.sum(row[:, None] >= starts[None, :], axis=1) - 1
    eid = jnp.clip(eid, 0, E - 1)
    w_rows = w[eid]                       # (T, K, N) — gather (oracle only)
    return jnp.einsum("tk,tkn->tn", x.astype(jnp.float32),
                      w_rows.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Collective-stage oracles (fused combine / wire cast / Gauss–Seidel block)
# ---------------------------------------------------------------------------
def combine_stage(acc, got, scale=None, *, accumulate: bool = True):
    """Oracle for :func:`repro.kernels.collective_stages.fused_combine`:
    ``acc + dequant(got)`` (or just the dequant with ``accumulate=False``)
    as plain jnp — what the unfused Level-B path computes across separate
    elementwise stages."""
    if scale is None:
        got = got.astype(acc.dtype)
    else:
        got = (got.astype(jnp.float32) * scale).astype(acc.dtype)
    return acc + got if accumulate else got


def quantize_stage(x, scale):
    """Symmetric int8 quantisation oracle (round, clip, cast)."""
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def dequantize_stage(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def gs_stencil(block, top, left, bottom, right):
    """Oracle for the fused Gauss–Seidel block stage: 4-point update,
    L1 residual and boundary edges, mirroring
    ``benchmarks/gauss_seidel.gs_block`` plus its residual/edge reads."""
    b = block.astype(jnp.float32)
    H, W = b.shape
    up = jnp.concatenate([top.reshape(1, W).astype(jnp.float32),
                          b[:-1, :]], axis=0)
    down = jnp.concatenate([b[1:, :],
                            bottom.reshape(1, W).astype(jnp.float32)],
                           axis=0)
    lft = jnp.concatenate([left.reshape(H, 1).astype(jnp.float32),
                           b[:, :-1]], axis=1)
    rgt = jnp.concatenate([b[:, 1:],
                           right.reshape(H, 1).astype(jnp.float32)],
                          axis=1)
    new = 0.25 * (up + down + lft + rgt)
    res = jnp.sum(jnp.abs(new - b))
    new = new.astype(block.dtype)
    return new, res, (new[0, :], new[-1, :], new[:, 0], new[:, -1])
