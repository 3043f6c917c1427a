"""Plain reference of the Olmo hybrid decoder (``olmo_hybrid``: gated
delta-rule and full attention layers): float32 ``jax.numpy``, no kernel, no
flax, and the delta rule **token by token** — one ``lax.scan`` over the
positions, exactly the equation — so that it shares nothing with the
program's chunked form (``stochastic_gradient_push_tpu/ops/delta_rule.py``).

``config`` holds the source's ``config.json`` keys; ``params`` is the
program's tree (``models/transformer.py`` under ``config_from_source``).
With every norm an RMSNorm (learned scale, ``rms_norm_eps``) and Olmo 2's
order, the norm after each sublayer and none in front::

    h0     = E[tokens]
    h      = h + RMSNorm(Mixer_i(h))
    h      = h + RMSNorm(W_down(silu(W_gate h) * W_up h))
    logits = RMSNorm(h_L) @ W_head                        (untied)

``full_attention``: ``q = RMSNorm(h W_q)``, ``k = RMSNorm(h W_k)`` over
the whole projection, then heads of ``hidden_size / num_attention_heads``,
no position term, causal ``softmax(q k^T / sqrt(d)) v``, ``W_o``; no bias.
``linear_attention`` (Gated DeltaNet, arXiv:2412.06464):
``[q | k | v] = silu(conv1d_causal(h [W_q | W_k | W_v]))``, depthwise, no
bias; per head ``q`` and ``k`` L2-normalised, ``q`` scaled by
``d_k ** -0.5``; ``alpha = exp(-exp(A_log) softplus(h W_a + dt_bias))``,
``beta = 2 sigmoid(h W_b)`` (``linear_allow_neg_eigval``); per head, with
``S`` ``[d_k, d_v]`` and ``S_0 = 0``::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``y = RMSNorm_head(o) * silu(h W_g)``, ``W_out``.  What the source's
``config.json`` does not settle is listed under ``assumed`` in
``configs/olmo_hybrid_7b.json``.
"""

import jax
import jax.numpy as jnp

# the granite reference's helpers: the one-line norm and loss, the runs of
# like layers a scan takes, the identity rounding
from benchmark.reference.granite_hybrid import (  # noqa: F401 (lm_loss)
    Q_BLOCK, _rms_norm, _runs, _same, lm_loss)


def delta_rule_recurrence(q, k, v, alpha, beta):
    """The gated delta rule, one position after another.  ``q``, ``k``
    ``[B, T, H, K]``, ``v`` ``[B, T, H, V]``, ``alpha``, ``beta``
    ``[B, T, H]``.  Returns ``o`` ``[B, T, H, V]``."""
    bsz, _, h, dk = q.shape

    def step(state, at):
        q_t, k_t, v_t, alpha_t, beta_t = at
        state = alpha_t[..., None, None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + beta_t[..., None, None] * k_t[..., :, None] \
            * (v_t - held)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    along_t = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(
        step, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32),
        tuple(along_t(x) for x in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1)


def _linear_attention(u, p, config, operand):
    h, dk = config["linear_num_key_heads"], config["linear_key_head_dim"]
    dv, taps = config["linear_value_head_dim"], config["linear_conv_kernel_dim"]
    t, lead = u.shape[1], u.shape[:2]
    projected = operand(u) @ operand(p["in_proj"]["kernel"])
    qkv, gate = projected[..., :h * (2 * dk + dv)], \
        projected[..., h * (2 * dk + dv):]
    ab = operand(u) @ operand(p["ab_proj"]["kernel"])
    # causal depthwise convolution: the output at t sees t-3 .. t
    before = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = 0.0
    for i in range(taps):
        conv = conv + before[:, i:i + t] * p["conv_kernel"][i]
    qkv = jax.nn.silu(conv)
    unit = lambda x: x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = unit(qkv[..., :h * dk].reshape(lead + (h, dk))) * dk ** -0.5
    k = unit(qkv[..., h * dk:2 * h * dk].reshape(lead + (h, dk)))
    v = qkv[..., 2 * h * dk:].reshape(lead + (h, dv))
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ab[..., :h] + p["dt_bias"]))
    beta = jax.nn.sigmoid(ab[..., h:]) \
        * (2.0 if config["linear_allow_neg_eigval"] else 1.0)
    o = delta_rule_recurrence(operand(q), operand(k), operand(v), alpha, beta)
    y = _rms_norm(o, p["norm"]["scale"], config["rms_norm_eps"]) \
        * jax.nn.silu(gate).reshape(lead + (h, dv))
    return operand(y.reshape(lead + (h * dv,))) \
        @ operand(p["out_proj"]["kernel"])


def _attention(u, p, config, q_block, operand):
    """Causal attention without positions, q and k normed over the whole
    projection, ``q_block`` query rows against every key at a time."""
    bsz, t, _ = u.shape
    n = config["num_attention_heads"]
    d = config["hidden_size"] // n
    eps = config["rms_norm_eps"]
    proj = lambda name: operand(u) @ operand(p[name]["kernel"])
    heads = lambda x: x.reshape(bsz, t, n, d).transpose(0, 2, 1, 3)
    q = heads(_rms_norm(proj("q"), p["q_norm"]["scale"], eps))
    k = heads(_rms_norm(proj("k"), p["k_norm"]["scale"], eps))
    q, k, v = operand(q), operand(k), operand(heads(proj("v")))

    def rows(q_rows, first):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) * d ** -0.5
        at = first + jnp.arange(q_rows.shape[2])
        causal = at[:, None] >= jnp.arange(t)[None]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", operand(weights), v)

    if q_block is None or t <= q_block or t % q_block:
        out = rows(q, 0)
    else:
        blocks = q.reshape(bsz, n, t // q_block, q_block, d)
        out = jax.lax.map(lambda a: rows(*a), (
            jnp.moveaxis(blocks, 2, 0), jnp.arange(0, t, q_block)))
        out = jnp.moveaxis(out, 0, 2).reshape(bsz, n, t, d)
    out = out.transpose(0, 2, 1, 3).reshape(bsz, t, n * d)
    return operand(out) @ operand(p["o"]["kernel"])


def lm_logits(params, tokens, config, q_block=Q_BLOCK, operand=_same):
    """``[B, T]`` tokens to ``[B, T, vocab]`` float32 logits.  ``operand``
    is applied to both operands of every matrix product and to ``q``, ``k``,
    ``v`` of the recurrence: the identity for the reference, a rounding to
    a lower precision for its control (``compare.rounded_to``)."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps = config["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]

    def layer(kind):
        def apply(x, p):
            mixed = (_linear_attention(x, p["delta"], config, operand)
                     if kind == "linear_attention"
                     else _attention(x, p["attn"], config, q_block, operand))
            x = x + _rms_norm(mixed, p["ln1"]["scale"], eps)
            gate, up = jnp.split(
                operand(x) @ operand(p["gate_up"]["kernel"]), 2, axis=-1)
            mlp = operand(jax.nn.silu(gate) * up) @ operand(p["down"]["kernel"])
            return x + _rms_norm(mlp, p["ln2"]["scale"], eps), None
        return apply

    # a run of like layers is one layer scanned over the stack of their
    # weights (as reference/lm.py scans its blocks): a small program
    for kind, first, count in _runs(config["layer_types"]):
        stack = jax.tree.map(lambda *a: jnp.stack(a), *(
            params[f"block_{i}"] for i in range(first, first + count)))
        x, _ = jax.lax.scan(layer(kind), x, stack)
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    return operand(x) @ operand(params["lm_head"]["kernel"])


def loss_and_grads(params, tokens, targets, config, q_block=Q_BLOCK):
    """The loss and its gradient in the parameters' own tree, by
    ``jax.grad`` of the forward pass above."""
    return jax.value_and_grad(lambda p: lm_loss(
        lm_logits(p, tokens, config, q_block), targets))(params)
