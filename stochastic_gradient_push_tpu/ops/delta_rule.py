"""The gated delta rule (Gated DeltaNet, Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464) in its chunked form.

Per head, with a state ``S`` of ``[d_k, d_v]`` and ``S_0 = 0``::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The state is corrected by what it already holds for the key, so step by
step it is ``T`` sequential updates.  Cut into chunks of ``C`` steps, with
``g`` the cumulative sum of ``log alpha`` inside a chunk,
``G_ij = exp(g_i - g_j)`` for ``i >= j`` and ``S`` the state entering the
chunk, the corrections of one chunk are one unit-lower-triangular solve::

    A  = strictly_lower(diag(beta) (K K^T * G))
    W  = (I + A)^-1 diag(beta) (K * exp(g))
    U  = (I + A)^-1 diag(beta) V
    V' = U - W S                         what each step writes, net
    O  = (Q * exp(g)) S + lower(Q K^T * G) V'
    S  <- exp(g_C) S + (K * exp(g_C - g))^T V'

Everything but ``V'``, ``O`` and the state is computed for all chunks at
once; the states entering the chunks follow by a scan over the ``T / C``
chunks.  The products take operands in ``operand_dtype`` with float32
accumulation; the gates, the cumulative sums, the decay masks, the solve
and the carried state stay float32.  Autodiff through the solve and the
scan gives the backward.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["delta_rule_chunked"]


def _chunk_states(w, u, kd, decay, operand_dtype):
    """The scan over chunks: ``w``, ``u``, ``kd`` ``[B, N, H, C, *]``
    float32, ``decay`` ``[B, N, H]`` (``exp(g_C)``).  Returns the states
    entering the chunks ``[B, N, H, K, V]`` and ``V'`` ``[B, N, H, C, V]``,
    float32."""
    f32 = jnp.float32

    def chunk(state, inputs):
        w_c, u_c, kd_c, decay_c = inputs
        written = u_c - jnp.einsum(
            "bhck,bhkv->bhcv", w_c.astype(operand_dtype),
            state.astype(operand_dtype), preferred_element_type=f32)
        new = decay_c[..., None, None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", kd_c.astype(operand_dtype),
            written.astype(operand_dtype), preferred_element_type=f32)
        return new, (state, written)

    along = lambda x: x.swapaxes(0, 1)
    # zeros typed like the inputs (inside a shard_map: varying over its
    # axes, as the carry the body returns)
    zeros = jnp.zeros_like(w[:, 0, :, 0, :, None] * u[:, 0, :, 0, None, :])
    _, (entering, written) = lax.scan(
        chunk, zeros, (along(w), along(u), along(kd), along(decay)))
    return along(entering), along(written)


def delta_rule_chunked(q, k, v, log_alpha, beta, chunk: int,
                       operand_dtype=jnp.float32):
    """``q``, ``k`` ``[B, T, H, K]``; ``v`` ``[B, T, H, V]``;
    ``log_alpha`` (``<= 0``) and ``beta`` ``[B, T, H]``.  Returns ``o``
    ``[B, T, H, V]`` float32.  A length ``chunk`` does not divide is padded
    with steps of ``k = 0``, ``beta = 0`` and ``alpha = 1``, which leave the
    state alone."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    size = min(chunk, t)
    pad = -t % size
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, log_alpha, beta))
    n = (t + pad) // size

    def heads_first(x):             # [B, T, H, ...] -> [B, N, H, C, ...]
        return x.reshape(bsz, n, size, h, *x.shape[3:]).swapaxes(2, 3)

    q, k, v = (heads_first(x.astype(operand_dtype)) for x in (q, k, v))
    g = jnp.cumsum(heads_first(log_alpha.astype(f32)), axis=-1)
    beta = heads_first(beta.astype(f32))

    # the mask goes in before the exponential: above the diagonal the
    # difference is positive and as large as the chunk's whole decay
    causal = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(causal, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                  # [B, N, H, C, C]
    kk = jnp.einsum("bnhik,bnhjk->bnhij", k, k, preferred_element_type=f32)
    a = jnp.where(jnp.tril(causal, -1), beta[..., None] * kk * decay, 0.0)
    rhs = beta[..., None] * jnp.concatenate(
        [k.astype(f32) * jnp.exp(g)[..., None], v.astype(f32)], axis=-1)
    solved = lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    w, u = solved[..., :dk], solved[..., dk:]

    kd = k.astype(f32) * jnp.exp(g[..., -1:] - g)[..., None]
    entering, written = _chunk_states(w, u, kd, jnp.exp(g[..., -1]),
                                      operand_dtype)

    scores = jnp.einsum("bnhik,bnhjk->bnhij", q, k,
                        preferred_element_type=f32) * decay
    o = jnp.einsum("bnhck,bnhkv->bnhcv",
                   (q.astype(f32) * jnp.exp(g)[..., None]).astype(
                       operand_dtype),
                   entering.astype(operand_dtype), preferred_element_type=f32)
    o = o + jnp.einsum("bnhij,bnhjv->bnhiv", scores.astype(operand_dtype),
                       written.astype(operand_dtype),
                       preferred_element_type=f32)
    return o.swapaxes(2, 3).reshape(bsz, n * size, h, -1)[:, :t]
