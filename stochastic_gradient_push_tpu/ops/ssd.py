"""The selective state-space recurrence of Mamba-2 in its chunked form
(state-space duality, Dao & Gu 2024, arXiv:2405.21060).

Per head, with a state ``S`` of ``[head_dim, d_state]`` and ``S_0 = 0``::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t

Step by step that is ``T`` sequential updates of the state.  Cut into
chunks of ``L`` steps it is three batched matrix products and a short
recurrence: inside a chunk, ``y`` is a causal ``[L, L]`` score block
``(C B^T) * decay`` times ``dt * x``; each chunk's contribution to the
state is one product over its steps; the states entering the chunks follow
by a scan over the ``T / L`` chunks; and what the entering state gives
each step is one more product.  The matrix units take the products, with
operands in ``operand_dtype`` and float32 accumulation; the decays, the
cumulative sums and the carried state stay float32.  Plain ``jax.numpy``
and autodiff: no kernel (PERF.md §5 says what that costs).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["ssd_chunked"]


def ssd_chunked(x, dt, a, b, c, chunk: int, operand_dtype=jnp.float32):
    """``x`` ``[B, T, H, P]``; ``dt`` ``[B, T, H]`` (positive, after its
    softplus); ``a`` ``[H]`` (negative); ``b``, ``c`` ``[B, T, G, N]``,
    each group of ``H / G`` heads sharing its ``B_t``, ``C_t``.  Returns
    ``y`` ``[B, T, H, P]`` float32.  A length ``chunk`` does not divide
    is padded with steps of ``dt = 0``, which leave the state alone."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    r = h // g
    size = min(chunk, t)
    pad = -t % size
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // size

    dt = dt.astype(f32)
    # per-step log decay, <= 0, as [B, chunks, G, R, L]; cum_l is the sum
    # over the chunk's steps up to and including l
    log_decay = (dt * a.astype(f32)).reshape(bsz, nc, size, g, r)
    cum = jnp.cumsum(log_decay.transpose(0, 1, 3, 4, 2), axis=-1)
    xdt = (x.astype(f32) * dt[..., None]).reshape(bsz, nc, size, g, r, p)
    b = b.astype(operand_dtype).reshape(bsz, nc, size, g, n)
    c = c.astype(operand_dtype).reshape(bsz, nc, size, g, n)

    # inside a chunk: y_l = sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) xdt_s.
    # The mask goes in before the exponential: above the diagonal the
    # difference is positive and as large as the chunk's whole decay
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                        preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    weights = (scores[:, :, :, None] * decay).astype(operand_dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", weights,
                   xdt.astype(operand_dtype), preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)               # [B, C, G, R, L]
    fed = (xdt * to_end.transpose(0, 1, 4, 2, 3)[..., None]) \
        .astype(operand_dtype)
    chunk_states = jnp.einsum("bcsgrp,bcsgn->bcgrpn", fed, b,
                              preferred_element_type=f32)

    # the state entering each chunk: the short recurrence, float32
    def enter(state, inputs):
        decay_c, own = inputs
        return decay_c[..., None, None] * state + own, state

    chunk_decay = jnp.exp(cum[..., -1])                 # [B, C, G, R]
    _, entering = lax.scan(
        enter, jnp.zeros_like(chunk_states[:, 0]),
        (chunk_decay.swapaxes(0, 1), chunk_states.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                  # [B, C, G, R, P, N]

    # what the entering state gives each step of its chunk
    carried = jnp.einsum("bclgn,bcgrpn->bclgrp", c,
                         entering.astype(operand_dtype),
                         preferred_element_type=f32)
    y = y + carried * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(bsz, nc * size, h, p)[:, :t]
