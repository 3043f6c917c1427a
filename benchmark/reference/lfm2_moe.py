"""Plain reference of the ``lfm2_moe`` decoder (Liquid AI LFM2-8B-A1B):
float32 ``jax.numpy``, no kernel, no flax, no sort and no grouped product
— the experts are a loop over those held with a 0/1 mask — so that it
shares nothing with ``stochastic_gradient_push_tpu/models/``.

``config`` holds the source's ``config.json`` keys and, in a cut file,
``experts_held`` (``[first, end)``; the router's width and the number
held are the parameters' own shapes); ``params`` is the program's tree (``models/transformer.py`` under
``config_from_source``).  With ``h = RMSNorm(x)`` (learned weight) before
every mixer and every feed-forward::

    conv:            [B | C | X] = h W_in;  u = B * X
                     v_t = sum_j w_j * u_{t-2+j}  (depthwise, causal,
                     zeros before the sequence, no bias, no activation)
                     x += (C * v) W_out
    full_attention:  q, k normed per head (RMSNorm over the 64), rotary
                     at rope_theta (half-split), causal softmax at
                     1/sqrt(64), query head i reads key-value head
                     i // (n_q / n_kv);  x += o W_o
    layers < num_dense_layers:  x += W_down(silu(W_gate h) * W_up h)
    the others:      s = sigmoid(h W_g);  S = top_k(s + b)
                     g_e = s_e / (sum_S s + 1e-6)
                     x += sum_{e in S, e held} g_e W_down^e(silu(W_gate^e
                     h) * W_up^e h)
    logits = RMSNorm(x_L) @ E^T                     (one tied table)

``S`` and the normalisation are over every expert the router knows; what
the experts not held would add is left out.  What ``config.json`` does
not settle is listed under ``assumed`` in ``configs/lfm2_8b_a1b.json``.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 1024      # query rows whose scores are held at once


def _same(a):
    return a


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _conv_mixer(u, p, config, operand):
    taps, t = config["conv_L_cache"], u.shape[1]
    bcx = operand(u) @ operand(p["in_proj"]["kernel"])
    b, c, x = jnp.split(bcx, 3, axis=-1)
    before = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    v = sum(before[:, j:j + t] * p["conv_kernel"][j] for j in range(taps))
    return operand(c * v) @ operand(p["out_proj"]["kernel"])


def _rotary(x, base):
    """``x`` ``[..., T, D]``: the half-split rotation, position = row."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(u, p, config, q_block, operand):
    """Grouped-query causal attention, ``q_block`` query rows against
    every key at a time."""
    bsz, t, _ = u.shape
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // n_q
    rep, eps = n_q // n_kv, config["norm_eps"]
    proj = lambda name: operand(u) @ operand(p[name]["kernel"])
    # [B, kv, rep, T, D]: query head kv * rep + j reads key-value head kv
    q = proj("q").reshape(bsz, t, n_kv, rep, d).transpose(0, 2, 3, 1, 4)
    k = proj("k").reshape(bsz, t, n_kv, d).transpose(0, 2, 1, 3)
    v = proj("v").reshape(bsz, t, n_kv, d).transpose(0, 2, 1, 3)
    q = _rotary(_rms_norm(q, p["q_norm"]["scale"], eps), config["rope_theta"])
    k = _rotary(_rms_norm(k, p["k_norm"]["scale"], eps), config["rope_theta"])
    q, k, v = operand(q), operand(k), operand(v)

    def rows(q_rows, first):
        scores = jnp.einsum("bgrqd,bgkd->bgrqk", q_rows, k) * d ** -0.5
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


def _gated_mlp(u, w_gate_up, w_down, operand):
    gate, up = jnp.split(operand(u) @ operand(w_gate_up), 2, axis=-1)
    return operand(jax.nn.silu(gate) * up) @ operand(w_down)


def route(u, p, config, selection=None):
    """Scores, selection and weights of one expert layer, float32.
    Returns ``(selection [B, T, k], gates [B, T, k], chooser [B, T, E])``:
    ``chooser`` is ``s + b``, what the selection is the top ``k`` of;
    a ``selection`` handed in takes the place of that top ``k`` and is
    weighed by this run's own scores."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ p["router"])
    chooser = scores + p["expert_bias"]
    if selection is None:
        selection = jax.lax.top_k(chooser, k)[1]
    gates = jnp.take_along_axis(scores, selection, axis=-1)
    return selection, gates / (gates.sum(-1, keepdims=True) + 1e-6), chooser


def experts_ffn(u, p, config, operand=_same, selection=None):
    """One expert layer over the experts held: every held expert's gated
    MLP over every token, times the weight the token gives it (zero where
    it did not choose it).  Returns ``(y, routing)``."""
    first = config.get("experts_held", (0, None))[0]
    selection, gates, chooser = route(u, p, config, selection)

    def one(y, at):
        e, w_gate_up, w_down = at
        weight = (gates * (selection == e)).sum(-1)          # [B, T]
        return y + weight[..., None] * _gated_mlp(
            u, w_gate_up, w_down, operand), None

    held = p["experts_gate_up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        first + jnp.arange(held), p["experts_gate_up"], p["experts_down"]))
    return y, {"selection": selection, "chooser": chooser}


def lm_logits(params, tokens, config, operand=None, selection=None,
              q_block=Q_BLOCK):
    """``[B, T]`` tokens to ``([B, T, vocab]`` float32 logits,
    ``routing)``: one ``{"selection", "chooser"}`` an expert layer, in
    layer order.  ``operand`` is applied to both operands of every matrix
    product but the router's (a float32 island of the configuration): the
    identity for the reference, a rounding to a lower precision for its
    control (``compare.rounded_to``).  ``selection`` — one ``[B, T, k]``
    an expert layer — takes the place of the layers' own top ``k``."""
    operand = operand or _same
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps, dense = config["norm_eps"], config["num_dense_layers"]
    table = params["embed"]["embedding"]
    x = table[tokens]
    routing = []
    for i, kind in enumerate(config["layer_types"]):
        p = params[f"block_{i}"]
        u = _rms_norm(x, p["ln1"]["scale"], eps)
        x = x + (_conv_mixer(u, p["conv"], config, operand) if kind == "conv"
                 else _attention(u, p["attn"], config, q_block, operand))
        u = _rms_norm(x, p["ln2"]["scale"], eps)
        if i < dense:
            x = x + _gated_mlp(u, p["gate_up"]["kernel"],
                               p["down"]["kernel"], operand)
        else:
            y, routed = experts_ffn(
                u, p["moe"], config, operand,
                None if selection is None else selection[len(routing)])
            x = x + y
            routing.append(routed)
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    return operand(x) @ operand(table).T, routing


def lm_loss(logits, targets):
    """Mean next-token cross-entropy, nats."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def loss_and_grads(params, tokens, targets, config, selection=None,
                   q_block=Q_BLOCK):
    """The loss and its gradient in the parameters' own tree, by
    ``jax.grad`` of the forward pass above (the selection's indices carry
    no gradient; the selection bias receives none)."""
    return jax.value_and_grad(lambda p: lm_loss(
        lm_logits(p, tokens, config, selection=selection,
                  q_block=q_block)[0], targets))(params)


def selection_gap(chooser, selection):
    """How far a handed selection is from this run's own, a (token,
    layer): the ``k``-th largest of ``chooser`` less the smallest
    ``chooser`` among the ``selection``'s experts.  Zero where the sets are
    one; otherwise the margin by which the worst choice missed, which is
    small where the choice was a near-tie."""
    k = selection.shape[-1]
    kth = jax.lax.top_k(chooser, k)[0][..., -1]
    worst = jnp.take_along_axis(chooser, selection, axis=-1).min(-1)
    return kth - worst
