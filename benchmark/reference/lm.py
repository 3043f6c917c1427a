"""Plain reference of ``models/transformer.py``'s dense decoder: float32
``jax.numpy``, no kernel, no flax.  Pre-norm blocks; rotary positions on
q and k (halves rotated, base 10000); causal softmax attention scaled by
``head_dim ** -0.5``; projections without bias; a tanh-GELU MLP with
biases; LayerNorm (epsilon 1e-6, scale and bias) before each sublayer and
before the untied, bias-free output head.  Departures from GPT-2 itself
are the program's and are listed in ``configs/gpt2_medium.json``.
"""

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, base=10000.0):
    # x: [B, H, T, D]
    t, half = x.shape[2], x.shape[3] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, p, n_heads):
    b, t, e = x.shape
    d = e // n_heads
    heads = lambda w: (x @ w["kernel"]).reshape(b, t, n_heads, d) \
        .transpose(0, 2, 1, 3)
    q, k, v = _rope(heads(p["q"])), _rope(heads(p["k"])), heads(p["v"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    return out.transpose(0, 2, 1, 3).reshape(b, t, e) @ p["o"]["kernel"]


def lm_logits(params, tokens, n_heads: int):
    """``[B, T]`` tokens to ``[B, T, vocab]`` float32 logits."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = params["embed"]["embedding"][tokens]
    n_layers = sum(1 for k in params if k.startswith("block_"))
    for i in range(n_layers):
        p = params[f"block_{i}"]
        x = x + _attention(_layer_norm(x, p["ln1"]), p["attn"], n_heads)
        h = _layer_norm(x, p["ln2"]) @ p["up"]["kernel"] + p["up"]["bias"]
        h = jax.nn.gelu(h, approximate=True)
        x = x + h @ p["down"]["kernel"] + p["down"]["bias"]
    return _layer_norm(x, params["ln_f"]) @ params["lm_head"]["kernel"]


def lm_loss(logits, targets):
    """Mean next-token cross-entropy, nats."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()
