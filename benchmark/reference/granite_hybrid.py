"""Plain reference of the hybrid state-space / attention decoder
(``granitemoehybrid`` with no experts: IBM Granite 4.0-H): float32
``jax.numpy``, no kernel, no flax, and the state-space recurrence **step
by step** — one ``lax.scan`` over the positions, exactly the equation —
so that it shares nothing with the program's chunked scan
(``stochastic_gradient_push_tpu/ops/ssd.py``).

``config`` holds the source's ``config.json`` keys; ``params`` is the
program's tree (``models/transformer.py`` under ``config_from_source``).
With ``x`` ``[B, T, hidden]`` and every norm an RMSNorm (learned scale)::

    h0     = embedding_multiplier * E[tokens]
    h      = h + residual_multiplier * Mixer_i(RMSNorm(h))
    h      = h + residual_multiplier * W_down(silu(W_gate u) * W_up u)
    logits = RMSNorm(h_L) @ E^T / logits_scaling          (one tied table)

``attention``: q of ``num_attention_heads``, k and v of
``num_key_value_heads`` heads, no position term at all, causal
``softmax(q k^T * attention_multiplier) v``, ``W_o``; no bias.
``mamba`` (Mamba-2, Dao & Gu 2024, as ``GraniteMoeHybridMambaLayer``):
``[z | xBC | dt] = W_in u``; ``xBC = silu(conv1d_causal(xBC))``, depthwise,
with bias; ``x`` ``[T, H, P]``, ``B``, ``C`` ``[T, G, N]``;
``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t`` with ``S_0 = 0``,
``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z))``; ``W_out``.
What the source's ``config.json`` does not settle is listed under
``assumed`` in ``configs/granite_4_0_h_micro.json``.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 1024      # query rows whose scores are held at once


def _same(a):
    return a


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def ssm_recurrence(x, dt, a, b, c, operand=_same):
    """The selective state-space recurrence, one position after another.
    ``x`` ``[B, T, H, P]``, ``dt`` ``[B, T, H]``, ``a`` ``[H]``, ``b`` and
    ``c`` ``[B, T, G, N]`` (a group's heads share them).  Returns ``y``
    ``[B, T, H, P]`` without the skip term.  ``operand`` rounds what the
    program hands its matrix products (``dt * x``, ``B``, ``C``); the
    decays and the state are float32 whatever it is."""
    bsz, _, h, p = x.shape
    per_group = h // b.shape[2]
    heads = lambda v: jnp.repeat(v, per_group, axis=2)      # [B, T, H, N]
    fed = operand(x * dt[..., None])
    b, c = operand(heads(b)), operand(heads(c))

    def step(state, at):
        fed_t, decay_t, b_t, c_t = at
        state = decay_t[..., None, None] * state \
            + fed_t[..., :, None] * b_t[..., None, :]
        return state, (state * c_t[..., None, :]).sum(-1)

    along_t = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32),
        (along_t(fed), along_t(jnp.exp(dt * a)), along_t(b), along_t(c)))
    return jnp.moveaxis(y, 0, 1)


def _mamba(u, p, config, operand):
    h, hp = config["mamba_n_heads"], config["mamba_d_head"]
    g, n = config["mamba_n_groups"], config["mamba_d_state"]
    taps = config["mamba_d_conv"]
    inner, t = h * hp, u.shape[1]
    zxbcdt = operand(u) @ operand(p["in_proj"]["kernel"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * g * n]
    dt = zxbcdt[..., 2 * inner + 2 * g * n:]
    # causal depthwise convolution: the output at t sees t-3 .. t
    before = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = p["conv_bias"] if config["mamba_conv_bias"] else 0.0
    for k in range(taps):
        conv = conv + before[:, k:k + t] * p["conv_kernel"][k]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(u.shape[:2] + (h, hp))
    b = xbc[..., inner:inner + g * n].reshape(u.shape[:2] + (g, n))
    c = xbc[..., inner + g * n:].reshape(u.shape[:2] + (g, n))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_recurrence(x, dt, -jnp.exp(p["A_log"]), b, c, operand)
    y = (y + p["D"][:, None] * x).reshape(u.shape[:2] + (inner,))
    y = _rms_norm(y * jax.nn.silu(z), p["norm"]["scale"],
                  config["rms_norm_eps"])
    return operand(y) @ operand(p["out_proj"]["kernel"])


def _attention(u, p, config, q_block, operand):
    """Grouped-query causal attention without positions, ``q_block`` query
    rows against every key at a time (as ``reference/lm.py``)."""
    bsz, t, _ = u.shape
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // n_q
    rep = n_q // n_kv
    proj = lambda name: operand(u) @ operand(p[name]["kernel"])
    # [B, kv, rep, T, D]: query head kv * rep + j reads key-value head kv
    q = proj("q").reshape(bsz, t, n_kv, rep, d).transpose(0, 2, 3, 1, 4)
    k = proj("k").reshape(bsz, t, n_kv, d).transpose(0, 2, 1, 3)
    v = proj("v").reshape(bsz, t, n_kv, d).transpose(0, 2, 1, 3)
    q, k, v = operand(q), operand(k), operand(v)

    def rows(q_rows, first):
        scores = jnp.einsum("bgrqd,bgkd->bgrqk", q_rows, k) \
            * config["attention_multiplier"]
        at = first + jnp.arange(q_rows.shape[3])
        causal = at[:, None] >= jnp.arange(t)[None]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bgkd->bgrqd", operand(weights), v)

    if q_block is None or t <= q_block or t % q_block:
        out = rows(q, 0)
    else:
        blocks = q.reshape(bsz, n_kv, rep, t // q_block, q_block, d)
        out = jax.lax.map(lambda a: rows(*a), (
            jnp.moveaxis(blocks, 3, 0), jnp.arange(0, t, q_block)))
        out = jnp.moveaxis(out, 0, 3).reshape(bsz, n_kv, rep, t, d)
    out = out.transpose(0, 3, 1, 2, 4).reshape(bsz, t, n_q * d)
    return operand(out) @ operand(p["o"]["kernel"])


def _runs(layer_types):
    """``[(type, first, count)]`` of the pattern's runs of like layers."""
    runs = []
    for i, kind in enumerate(layer_types):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1])
    return runs


def lm_logits(params, tokens, config, q_block=Q_BLOCK, operand=_same):
    """``[B, T]`` tokens to ``[B, T, vocab]`` float32 logits.  ``operand``
    is applied to both operands of every matrix product: the identity for
    the reference, a rounding to a lower precision for its control
    (``compare.rounded_to``)."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps, res = config["rms_norm_eps"], config["residual_multiplier"]
    table = params["embed"]["embedding"]
    x = config["embedding_multiplier"] * table[tokens]

    def layer(kind):
        def apply(x, p):
            u = _rms_norm(x, p["ln1"]["scale"], eps)
            mixed = (_mamba(u, p["ssm"], config, operand) if kind == "mamba"
                     else _attention(u, p["attn"], config, q_block, operand))
            x = x + res * mixed
            u = _rms_norm(x, p["ln2"]["scale"], eps)
            gate, up = jnp.split(
                operand(u) @ operand(p["gate_up"]["kernel"]), 2, axis=-1)
            return x + res * (operand(jax.nn.silu(gate) * up)
                              @ operand(p["down"]["kernel"])), None
        return apply

    # a run of like layers is one layer scanned over the stack of their
    # weights (as reference/lm.py scans its blocks): a small program
    for kind, first, count in _runs(config["layer_types"]):
        stack = jax.tree.map(lambda *a: jnp.stack(a), *(
            params[f"block_{i}"] for i in range(first, first + count)))
        x, _ = jax.lax.scan(layer(kind), x, stack)
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    return operand(x) @ operand(table).T / config["logits_scaling"]


def lm_loss(logits, targets):
    """Mean next-token cross-entropy, nats."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def loss_and_grads(params, tokens, targets, config, q_block=Q_BLOCK):
    """The loss and its gradient in the parameters' own tree, by
    ``jax.grad`` of the forward pass above."""
    return jax.value_and_grad(lambda p: lm_loss(
        lm_logits(p, tokens, config, q_block), targets))(params)
