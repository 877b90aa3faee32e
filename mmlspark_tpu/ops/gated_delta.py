"""The gated delta rule (Gated DeltaNet's recurrence) in chunked form.

Per head, with a (dk, dv) state S that is zero at the start of a sequence,
for each position t:

    S = exp(g_t) S;  r = k_t^T S;  d = beta_t (v_t - r);  S = S + k_t d^T
    o_t = q_t^T S

A scan over positions does S steps of rank-one work. The chunked form
(Yang et al., "Gated Delta Networks", 2024; the WY representation of
"Parallelizing Linear Transformers with the Delta Rule") does the same
arithmetic with matrix products. Inside a chunk of C positions that starts
from state S0, with gamma_i the running sum of g up to and including i and
D_ij = exp(gamma_i - gamma_j) for j <= i:

    A_ij = beta_i D_ij (k_i . k_j)            j < i   (strictly lower)
    T = (I + A)^-1;  W = T (beta exp(gamma) K);  U0 = T (beta V)
    U  = U0 - W S0                        the C writes of the chunk
    O  = (exp(gamma) Q) S0 + ((Q K^T) * D) U
    S' = exp(gamma_C) S0 + (K * exp(gamma_C - gamma))^T U

Every exponent is of a difference that is <= 0, so nothing overflows
however long the sequence. Everything that does not depend on S0 (A, the
solve, Q K^T) is computed for all chunks at once; only the three products
with the state run in a `lax.scan` over chunks. The backward pass is
`jax.grad` of this: it keeps one state per CHUNK (as the bfloat16 operand of
the chunk's products), never one per position.

Precision: decays, the solve and every accumulation are float32; the
products take their operands in the inputs' dtype (bfloat16 in the trainer's
mixed precision, float32 in tests), the state is carried in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k (S, H, dk), v (S, H, dv), g and beta (S, H) -> o (S, H, dv).

    q and k as the layer prepares them (L2-normalised, q scaled, key heads
    already repeated to H); g <= 0 is the log decay. S need not divide by
    `chunk`: the tail is padded with positions that write nothing."""
    seq, heads, dk = q.shape
    dv = v.shape[-1]
    cdt = q.dtype
    f32 = jnp.float32
    pad = (-seq) % chunk
    n = (seq + pad) // chunk

    def chunks(t):                    # (S, H, ...) -> (H, n, C, ...)
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        t = t.reshape((n, chunk) + t.shape[1:])
        return jnp.moveaxis(t, 2, 0)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc, bc = chunks(g.astype(f32)), chunks(beta.astype(f32))
    gamma = jnp.cumsum(gc, axis=-1)                       # (H, n, C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kk = jnp.einsum("hnid,hnjd->hnij", kc, kc, preferred_element_type=f32)
    a = bc[..., :, None] * decay * kk
    a = jnp.where(jnp.tril(lower, -1), a, 0.0) + jnp.eye(chunk, dtype=f32)
    eg = jnp.exp(gamma)
    # (I + A)^-1 itself (C x C a chunk), then two products on the MXU: a
    # solve against the (C, dk + dv) right-hand side would hold it in f32
    inv = jax.lax.linalg.triangular_solve(
        a, jnp.broadcast_to(jnp.eye(chunk, dtype=f32), a.shape),
        left_side=True, lower=True, unit_diagonal=True).astype(cdt)
    w = jnp.einsum("hnij,hnjd->hnid", inv,
                   ((bc * eg)[..., None] * kc.astype(f32)).astype(cdt),
                   preferred_element_type=f32).astype(cdt)
    u0 = jnp.einsum("hnij,hnjd->hnid", inv,
                    (bc[..., None] * vc.astype(f32)).astype(cdt),
                    preferred_element_type=f32).astype(cdt)
    q_in = (eg[..., None] * qc.astype(f32)).astype(cdt)
    qk = jnp.einsum("hnid,hnjd->hnij", qc, kc, preferred_element_type=f32)
    qk = (qk * decay).astype(cdt)
    k_out = (jnp.exp(gamma[..., -1:] - gamma)[..., None]
             * kc.astype(f32)).astype(cdt)
    g_end = eg[..., -1]                                   # (H, n)

    def step(state, xs):
        w_c, u0_c, q_c, qk_c, k_c, g_c = xs
        s_in = state.astype(cdt)
        u = u0_c - jnp.einsum("hik,hkv->hiv", w_c, s_in,
                              preferred_element_type=f32)
        u_in = u.astype(cdt)
        o = jnp.einsum("hik,hkv->hiv", q_c, s_in,
                       preferred_element_type=f32) \
            + jnp.einsum("hij,hjv->hiv", qk_c, u_in,
                         preferred_element_type=f32)
        state = g_c[:, None, None] * state + jnp.einsum(
            "hik,hiv->hkv", k_c, u_in, preferred_element_type=f32)
        return state, o.astype(cdt)

    per_chunk = tuple(jnp.moveaxis(t, 1, 0)
                      for t in (w, u0, q_in, qk, k_out, g_end))
    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), f32), per_chunk)
    o = jnp.moveaxis(o, 1, 0).reshape(heads, n * chunk, dv)   # (H, S', dv)
    return jnp.moveaxis(o, 0, 1)[:seq]
