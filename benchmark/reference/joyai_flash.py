"""Plain reference of the ``joyai_llm_flash`` decoder (JoyAI-LLM-Flash,
DeepSeek-V3's layers, arXiv:2412.19437 §2.1–2.2): float32 ``jax.numpy``,
no kernel, no flax, no sort and no grouped product — the experts are a
loop over those held with a 0/1 mask — so that it shares nothing with
``stochastic_gradient_push_tpu/models/``.

``config`` holds the source's ``config.json`` keys and, in a cut file,
``experts_held`` (``[first, end)``); ``params`` is the program's tree
(``models/transformer.py`` under ``config_from_source``).  With ``h =
RMSNorm(x)`` (learned weight) before every mixer and every feed-forward::

    latent attention:  c_q = RMSNorm(h W_qa);  q = c_q W_qb, a head
                       [q_nope (128) | q_pe (64)]
                       [c_kv | k_pe] = h W_kva;  [k_nope | v] =
                       RMSNorm(c_kv) W_kvb a head; k_pe one head for all
                       q_pe, k_pe rotated in interleaved pairs (lanes 2i,
                       2i+1 by position * theta^(-2i/64))
                       scores = (q_nope . k_nope + q_pe . k_pe) / sqrt(192),
                       causal softmax over v;  x += o W_o
    layers < first_k_dense_replace:  x += W_down(silu(W_gate h) * W_up h)
    the others:        s = sigmoid(h W_g);  S = top_k(s + b)
                       g_e = scale * s_e / (sum_S s + 1e-6)
                       x += sum_{e in S, e held} g_e W_down^e(silu(W_gate^e
                       h) * W_up^e h)  +  the shared expert of h
    logits = RMSNorm(x_L) W_head
    MTP:               h' = W_eh [RMSNorm(E[t_{i+1}]) ; RMSNorm(x_L)];
                       one more block of the last layer's kinds;
                       mtp_logits = RMSNorm(h') W_head (t_{i+2})

``S`` and the normalisation are over every expert the router knows; what
the experts not held would add is left out.  The loss is ``L_main +
MTP_WEIGHT * L_mtp``, ``L_mtp`` over every position but the last.  What
``config.json`` does not settle is listed under ``assumed`` in
``configs/joyai_llm_flash.json``.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 1024      # query rows whose scores are held at once
MTP_WEIGHT = 0.3    # lambda of the configuration's ``assumed``


def _same(a):
    return a


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary_pairs(x, base):
    """``x`` ``[..., T, D]``: lanes ``2i`` and ``2i + 1`` rotated as a pair
    by ``row * base ** (-2i / D)``, in place."""
    d = x.shape[-1]
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _latent_attention(u, p, config, q_block, operand):
    bsz, t, _ = u.shape
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    d_v, rank = config["v_head_dim"], config["kv_lora_rank"]
    mm = lambda a, w: operand(a) @ operand(w)
    c_q = _rms_norm(mm(u, p["q_a"]["kernel"]), p["q_a_norm"]["scale"], eps)
    # [B, H, T, w]
    q = mm(c_q, p["q_b"]["kernel"]).reshape(
        bsz, t, heads, nope + rope).transpose(0, 2, 1, 3)
    kv_a = mm(u, p["kv_a"]["kernel"])
    c_kv, k_pe = kv_a[..., :rank], kv_a[..., rank:]
    kv = mm(_rms_norm(c_kv, p["kv_a_norm"]["scale"], eps),
            p["kv_b"]["kernel"]).reshape(
        bsz, t, heads, nope + d_v).transpose(0, 2, 1, 3)
    k_nope, v = operand(kv[..., :nope]), operand(kv[..., nope:])
    q_nope = operand(q[..., :nope])
    q_pe = operand(_rotary_pairs(q[..., nope:], config["rope_theta"]))
    k_pe = operand(_rotary_pairs(k_pe, config["rope_theta"]))  # [B, T, r]
    scale = (nope + rope) ** -0.5

    def rows(at):
        qn, qp, first = at
        scores = (jnp.einsum("bhqd,bhkd->bhqk", qn, k_nope)
                  + jnp.einsum("bhqd,bkd->bhqk", qp, k_pe)) * scale
        seen = first + jnp.arange(qn.shape[2])
        causal = seen[:, None] >= jnp.arange(t)[None]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", operand(weights), v)

    if q_block is None or t <= q_block or t % q_block:
        out = rows((q_nope, q_pe, 0))
    else:
        n = t // q_block
        split = lambda a: jnp.moveaxis(
            a.reshape(bsz, heads, n, q_block, a.shape[-1]), 2, 0)
        out = jax.lax.map(rows, (split(q_nope), split(q_pe),
                                 jnp.arange(0, t, q_block)))
        out = jnp.moveaxis(out, 0, 2).reshape(bsz, heads, t, d_v)
    out = out.transpose(0, 2, 1, 3).reshape(bsz, t, heads * d_v)
    return mm(out, p["o"]["kernel"])


def _gated_mlp(u, w_gate_up, w_down, operand):
    gate, up = jnp.split(operand(u) @ operand(w_gate_up), 2, axis=-1)
    return operand(jax.nn.silu(gate) * up) @ operand(w_down)


def route(u, p, config, selection=None):
    """Scores, selection and weights of one expert layer, float32.
    Returns ``(selection [B, T, k], gates [B, T, k], chooser [B, T, E])``:
    ``chooser`` is ``s + b``, what the selection is the top ``k`` of; a
    ``selection`` handed in takes the place of that top ``k`` and is
    weighed by this run's own scores."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ p["router"])
    chooser = scores + p["expert_bias"]
    if selection is None:
        selection = jax.lax.top_k(chooser, k)[1]
    gates = jnp.take_along_axis(scores, selection, axis=-1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
    return selection, gates * config["routed_scaling_factor"], chooser


def experts_ffn(u, p, config, operand=_same, selection=None):
    """One expert layer over the experts held, and the shared expert:
    every held expert's gated MLP over every token, times the weight the
    token gives it (zero where it did not choose it).  Returns ``(y,
    routing)``."""
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
    if "shared_gate_up" in p:
        y = y + _gated_mlp(u, p["shared_gate_up"], p["shared_down"],
                           operand)
    return y, {"selection": selection, "chooser": chooser}


def _block(x, p, config, dense, q_block, operand, selection):
    eps = config["rms_norm_eps"]
    u = _rms_norm(x, p["ln1"]["scale"], eps)
    x = x + _latent_attention(u, p["mla"], config, q_block, operand)
    u = _rms_norm(x, p["ln2"]["scale"], eps)
    if dense:
        return x + _gated_mlp(u, p["gate_up"]["kernel"], p["down"]["kernel"],
                              operand), None
    y, routed = experts_ffn(u, p["moe"], config, operand, selection)
    return x + y, routed


def lm_logits(params, tokens, config, operand=None, selection=None,
              q_block=Q_BLOCK):
    """``[B, T]`` tokens to ``(logits, mtp_logits, routing)``: both
    ``[B, T, vocab]`` float32 (``mtp_logits`` ``None`` without the
    module), one ``{"selection", "chooser"}`` an expert layer in layer
    order, the module's last.  ``operand`` is applied to both operands of
    every matrix product but the router's (a float32 island of the
    configuration): the identity for the reference, a rounding to a lower
    precision for its control (``compare.rounded_to``).  ``selection`` —
    one ``[B, T, k]`` an expert layer — takes the place of the layers' own
    top ``k``."""
    operand = operand or _same
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps, n = config["rms_norm_eps"], config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    table = params["embed"]["embedding"]
    head = lambda h: operand(h) @ operand(params["lm_head"]["kernel"])
    routing = []

    def block(x, p, is_dense):
        handed = None if selection is None or is_dense \
            else selection[len(routing)]
        x, routed = _block(x, p, config, is_dense, q_block, operand, handed)
        if routed is not None:
            routing.append(routed)
        return x

    x = table[tokens]
    for i in range(n):
        x = block(x, params[f"block_{i}"], i < dense)
    logits = head(_rms_norm(x, params["ln_f"]["scale"], eps))
    if not config.get("num_nextn_predict_layers", 0):
        return logits, None, routing
    ahead = table[jnp.roll(tokens, -1, axis=1)]
    h = jnp.concatenate([_rms_norm(ahead, params["mtp_enorm"]["scale"], eps),
                         _rms_norm(x, params["mtp_hnorm"]["scale"], eps)], -1)
    h = operand(h) @ operand(params["eh_proj"]["kernel"])
    h = block(h, params["mtp_block"], n - 1 < dense)
    return logits, head(_rms_norm(h, params["mtp_norm"]["scale"], eps)), \
        routing


def lm_loss(logits, targets):
    """Mean next-token cross-entropy, nats."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def mtp_loss(mtp_logits, targets):
    """Cross-entropy of the token after next, ``targets[:, i + 1]`` from
    position ``i``, over every position but the last."""
    return lm_loss(mtp_logits[:, :-1], targets[:, 1:])


def loss(logits, mtp_logits, targets):
    out = lm_loss(logits, targets)
    if mtp_logits is not None:
        out = out + MTP_WEIGHT * mtp_loss(mtp_logits, targets)
    return out


def loss_and_grads(params, tokens, targets, config, selection=None,
                   q_block=Q_BLOCK):
    """The loss and its gradient in the parameters' own tree, by
    ``jax.grad`` of the forward pass above (the selection's indices carry
    no gradient; the selection bias receives none)."""
    def objective(p):
        logits, ahead, _ = lm_logits(p, tokens, config, selection=selection,
                                     q_block=q_block)
        return loss(logits, ahead, targets)

    return jax.value_and_grad(objective)(params)


def selection_gap(chooser, selection):
    """How far a handed selection is from this run's own, a (token,
    layer): the ``k``-th largest of ``chooser`` less the smallest
    ``chooser`` among the ``selection``'s experts.  Zero where the sets are
    one; otherwise the margin by which the worst choice missed."""
    k = selection.shape[-1]
    kth = jax.lax.top_k(chooser, k)[0][..., -1]
    worst = jnp.take_along_axis(chooser, selection, axis=-1).min(-1)
    return kth - worst
