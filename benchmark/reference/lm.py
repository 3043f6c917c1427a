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


Q_BLOCK = 1024      # query rows whose scores are held at once


def _same(a):
    return a


def _attention(x, p, n_heads, q_block=Q_BLOCK, operand=_same):
    """Causal softmax attention.  The scores of ``q_block`` query rows
    against every key are held at a time (``[B, H, q_block, T]``; whole,
    ``[16, 8192, 8192]`` float32 is 4.3 GB a layer): each row's softmax is
    over its own keys, so the blocks are the whole form's rows, computed
    one block after another.  ``q_block=None``, a sequence no longer than
    a block or one it does not divide: all rows at once."""
    b, t, e = x.shape
    d = e // n_heads
    heads = lambda w: (operand(x) @ operand(w["kernel"])) \
        .reshape(b, t, n_heads, d).transpose(0, 2, 1, 3)
    q, k, v = _rope(heads(p["q"])), _rope(heads(p["k"])), heads(p["v"])
    q, k, v = operand(q), operand(k), operand(v)

    def rows(q_rows, first):
        # q_rows: [B, H, n, D], the queries at positions first .. first+n-1
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) * d ** -0.5
        at = first + jnp.arange(q_rows.shape[2])
        causal = at[:, None] >= jnp.arange(t)[None]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                                 axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", operand(weights), v)

    if q_block is None or t <= q_block or t % q_block:
        out = rows(q, 0)
    else:
        blocks = q.reshape(b, n_heads, t // q_block, q_block, d)
        out = jax.lax.map(lambda a: rows(*a), (
            blocks.transpose(2, 0, 1, 3, 4), jnp.arange(0, t, q_block)))
        out = out.transpose(1, 2, 0, 3, 4).reshape(b, n_heads, t, d)
    return operand(out.transpose(0, 2, 1, 3).reshape(b, t, e)) \
        @ operand(p["o"]["kernel"])


def lm_logits(params, tokens, n_heads: int, q_block=Q_BLOCK, operand=_same):
    """``[B, T]`` tokens to ``[B, T, vocab]`` float32 logits.  ``operand``
    is applied to both operands of every matrix product: the identity for
    the reference, a rounding to a lower precision for its control
    (``compare.rounded_to``)."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = params["embed"]["embedding"][tokens]
    n_layers = sum(1 for k in params if k.startswith("block_"))

    def block(x, p):
        x = x + _attention(_layer_norm(x, p["ln1"]), p["attn"], n_heads,
                           q_block, operand)
        h = operand(_layer_norm(x, p["ln2"])) @ operand(p["up"]["kernel"]) \
            + p["up"]["bias"]
        h = jax.nn.gelu(h, approximate=True)
        return x + operand(h) @ operand(p["down"]["kernel"]) \
            + p["down"]["bias"], None

    # the blocks are alike, so one is compiled and run over the stack of
    # their weights: unrolled, 24 float32 blocks at precision highest are
    # a 240 MB program that takes the compiler half a minute
    x, _ = jax.lax.scan(block, x, jax.tree.map(
        lambda *a: jnp.stack(a),
        *(params[f"block_{i}"] for i in range(n_layers))))
    return operand(_layer_norm(x, params["ln_f"])) \
        @ operand(params["lm_head"]["kernel"])


def lm_loss(logits, targets):
    """Mean next-token cross-entropy, nats."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()
