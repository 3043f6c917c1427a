"""The gated delta-rule mixer (Gated DeltaNet, Yang, Kautz & Hatamizadeh
2024, arXiv:2412.06464), as the FLA ``GatedDeltaNet`` layer lays it out:
projections to ``q | k | v`` and to the output gate, a causal depthwise
convolution and SiLU over ``q | k | v``, ``q`` and ``k`` L2-normalised per
head and ``q`` scaled by ``d_k ** -0.5``, per head and step a decay
``alpha = exp(-exp(A_log) * softplus(a + dt_bias))`` and a write strength
``beta = sigmoid(b)`` — twice that where negative eigenvalues are allowed
(Grazzi et al. 2024, arXiv:2411.12537: ``I - beta k k^T`` then has them in
``(-1, 1]``) —, the delta rule (``ops/delta_rule.py``, chunked), an
RMSNorm over each head's output gated by ``silu(x W_g)``, and the
out-projection.  The gates, the convolution, the norms and the rule's
solve and state are float32; the projections and the rule's products take
``dtype`` operands.
"""

from __future__ import annotations

import math
import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.delta_rule import delta_rule_chunked
from ..telemetry import names
from .ssm import causal_depthwise_conv

__all__ = ["DeltaNetConfig", "GatedDeltaNetMixer"]


class DeltaNetConfig(tp.NamedTuple):
    """Sizes of the mixer, under the source config's ``linear_*`` names
    less their prefix."""

    n_heads: int = 16
    key_head_dim: int = 128
    value_head_dim: int = 256
    conv_kernel_dim: int = 4
    allow_neg_eigval: bool = False
    chunk_size: int = 64


def _l2_normalised(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A`` uniform in (0, 16] a head, as FLA's layer draws it."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, dtype)))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The softplus inverse of ``dt`` log-uniform in [1e-3, 1e-1] a head
    (floored at 1e-4), as FLA's layer draws it."""
    low, high = math.log(1e-3), math.log(1e-1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, dtype, low, high)), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedDeltaNetMixer(nn.Module):
    delta: DeltaNetConfig
    d_model: int
    dtype: tp.Any = jnp.float32
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, u):
        cfg, f32 = self.delta, jnp.float32
        h, dk, dv = cfg.n_heads, cfg.key_head_dim, cfg.value_head_dim
        conv_dim = h * (2 * dk + dv)
        with jax.named_scope(names.SCOPE_DELTA_MIXER):
            qkvg = nn.Dense(conv_dim + h * dv, use_bias=False,
                            dtype=self.dtype, name="in_proj")(u)
            qkv, gate = jnp.split(qkvg, [conv_dim], axis=-1)
            a, b = jnp.split(nn.Dense(2 * h, use_bias=False, dtype=self.dtype,
                                      name="ab_proj")(u).astype(f32), 2, -1)

            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (cfg.conv_kernel_dim, conv_dim), f32)
            with jax.named_scope(names.SCOPE_CONV1D):
                qkv = jax.nn.silu(causal_depthwise_conv(
                    qkv.astype(f32), kernel, None))
            lead = u.shape[:2]
            q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
            q = _l2_normalised(q.reshape(lead + (h, dk))) * dk ** -0.5
            k = _l2_normalised(k.reshape(lead + (h, dk)))
            v = v.reshape(lead + (h, dv))

            a_log = self.param("A_log", _a_log_init, (h,), f32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,), f32)
            log_alpha = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
            beta = jax.nn.sigmoid(b) * (2.0 if cfg.allow_neg_eigval else 1.0)
            # the share of (token, head) pairs whose write overshoots: 0
            # wherever the factor 2 is missing
            self.sow("delta_metrics", "beta_above_one",
                     jnp.mean((beta > 1.0).astype(f32)))

            with jax.named_scope(names.SCOPE_DELTA_RULE):
                o = delta_rule_chunked(q, k, v, log_alpha, beta,
                                       cfg.chunk_size,
                                       operand_dtype=self.dtype)
            o = nn.RMSNorm(epsilon=self.norm_eps, dtype=f32, name="norm")(o)
            y = o * jax.nn.silu(gate.astype(f32)).reshape(lead + (h, dv))
            return nn.Dense(self.d_model, use_bias=False, dtype=self.dtype,
                            name="out_proj")(y.reshape(lead + (h * dv,)))
