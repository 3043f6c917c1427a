"""The Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060), as the published
hybrid LMs lay it out (``GraniteMoeHybridMambaLayer``): one in-projection
to ``z | xBC | dt``, a causal depthwise convolution and SiLU over ``xBC``,
the selective state-space recurrence per head (``ops/ssd.py``, chunked),
a skip ``D * x``, an RMSNorm gated by ``silu(z)``, and the out-projection.
Decays, ``dt``, the recurrence's sums and both norms are float32; the
projections and the scan's matrix products take ``dtype`` operands.
"""

from __future__ import annotations

import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.ssd import ssd_chunked
from ..telemetry import names

__all__ = ["SSMConfig", "Mamba2Mixer"]


class SSMConfig(tp.NamedTuple):
    """Sizes of the mixer, under the source config's names less their
    ``mamba_`` prefix.  The mixer's width is ``n_heads * d_head``."""

    n_heads: int = 64
    d_head: int = 64
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    chunk_size: int = 256
    conv_bias: bool = True


def causal_depthwise_conv(x, kernel, bias):
    """``y_t = sum_k kernel[k] * x_{t - (K-1) + k} (+ bias)`` per channel,
    with zeros before the sequence.  ``x`` ``[B, T, C]``, ``kernel``
    ``[K, C]``: ``K`` shifted multiply-adds, which the compiler fuses."""
    taps = kernel.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, k:k + t] * kernel[k] for k in range(taps))
    return y if bias is None else y + bias


class Mamba2Mixer(nn.Module):
    ssm: SSMConfig
    d_model: int
    dtype: tp.Any = jnp.float32
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, u):
        ssm, f32 = self.ssm, jnp.float32
        h, p = ssm.n_heads, ssm.d_head
        inner = h * p
        bc = ssm.n_groups * ssm.d_state
        conv_dim = inner + 2 * bc
        with jax.named_scope(names.SCOPE_SSM_MIXER):
            zxbcdt = nn.Dense(inner + conv_dim + h, use_bias=False,
                              dtype=self.dtype, name="in_proj")(u)
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], -1)

            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (ssm.d_conv, conv_dim), f32)
            conv_bias = (self.param("conv_bias", nn.initializers.zeros,
                                    (conv_dim,), f32)
                         if ssm.conv_bias else None)
            with jax.named_scope(names.SCOPE_CONV1D):
                xbc = jax.nn.silu(causal_depthwise_conv(
                    xbc.astype(f32), kernel, conv_bias))
            x, b, c = jnp.split(xbc, [inner, inner + bc], -1)

            # A = -exp(A_log) with A_log = log(1..H), dt_bias = 1, D = 1:
            # the source's initialisation (assumed in the benchmark's file)
            a_log = self.param(
                "A_log", lambda *_: jnp.log(jnp.arange(1, h + 1, dtype=f32)))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (h,), f32)
            skip = self.param("D", nn.initializers.ones, (h,), f32)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)

            lead = x.shape[:2]
            with jax.named_scope(names.SCOPE_SSD):
                y = ssd_chunked(
                    x.reshape(lead + (h, p)), dt, -jnp.exp(a_log),
                    b.reshape(lead + (ssm.n_groups, ssm.d_state)),
                    c.reshape(lead + (ssm.n_groups, ssm.d_state)),
                    ssm.chunk_size, operand_dtype=self.dtype)
            # the skip on [B, T, H * P], the layout the projections and
            # the scan's kernels share: a [B, T, H, P] view is, tiled for
            # the TPU, another array
            y = y.reshape(lead + (inner,)) + jnp.repeat(skip, p) * x

            y = y * jax.nn.silu(z.astype(f32))
            y = nn.RMSNorm(epsilon=self.norm_eps, dtype=f32, name="norm")(y)
            return nn.Dense(self.d_model, use_bias=False, dtype=self.dtype,
                            name="out_proj")(y)
