"""The gated short-convolution mixer of the LFM2 family (``Lfm2ShortConv``):
one in-projection to ``B | C | x``, the product ``B * x`` through a causal
depthwise convolution of a few taps (no bias, no activation), the gate
``C`` on its output, and the out-projection.  The convolution is the one
``models/ssm.py`` has (shifted multiply-adds, float32); the projections
take ``dtype`` operands.
"""

from __future__ import annotations

import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..telemetry import names
from .ssm import causal_depthwise_conv

__all__ = ["ShortConvMixer"]


class ShortConvMixer(nn.Module):
    d_model: int
    taps: int = 3                     # the source's conv_L_cache
    dtype: tp.Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        f32, d = jnp.float32, self.d_model
        with jax.named_scope(names.SCOPE_CONV_MIXER):
            bcx = nn.Dense(3 * d, use_bias=False, dtype=self.dtype,
                           name="in_proj")(u)
            b, c, x = jnp.split(bcx.astype(f32), 3, axis=-1)
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (self.taps, d), f32)
            with jax.named_scope(names.SCOPE_CONV1D):
                v = causal_depthwise_conv(b * x, kernel, None)
            return nn.Dense(d, use_bias=False, dtype=self.dtype,
                            name="out_proj")(c * v)
