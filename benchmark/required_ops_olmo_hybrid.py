"""Operations and bytes an Olmo hybrid decoder (gated delta-rule and full
attention layers) *requires*, from shapes alone, by ``required_ops.py``'s
conventions: one multiply-accumulate is two operations, a backward pass
costs twice its forward pass, only matrix products are counted (the
convolution's four taps, the norms, the gates and the optimizer are not),
and recomputation is the program's own to pay.  Sizes are named as the
source's ``config.json`` names them.

The delta rule is counted in its chunked form at ``CHUNK`` positions a
chunk, whatever chunk (or form) the program runs: the count is the
yardstick's, so a program that changes how it computes the rule moves the
time and not the work it is held to.
"""

from __future__ import annotations

from benchmark import required_ops

# the chunk of the rule's count (the FLA layer's and the configuration's
# ``assumed.chunk_size``): fixed here, never read from the program
CHUNK = 64


def _pairs(seq_len: int, chunk: int, diagonal: bool = True) -> int:
    """(row, column) pairs inside the chunks, on and below the diagonal or
    strictly below it; a last chunk the length leaves short counts by its
    own length."""
    full, rest = divmod(seq_len, chunk)
    causal = full * required_ops._causal_pairs(chunk) \
        + required_ops._causal_pairs(rest)
    return causal if diagonal else causal - seq_len


def delta_rule_flops(*, batch: int, seq_len: int, heads: int, d_key: int,
                     d_value: int, chunk: int = CHUNK) -> dict:
    """The gated delta rule in its chunked form, per head: ``K K^T`` over a
    chunk's pairs below the diagonal, the triangular solve applied to
    ``[K | V]`` (one product over the same pairs), ``Q K^T`` and the
    scores times ``V'`` over the causal pairs, and per step ``W S``,
    ``(Q exp(g)) S`` and the state's update ``(K exp(g_C - g))^T V'``."""
    strict = _pairs(seq_len, chunk, diagonal=False)
    causal = _pairs(seq_len, chunk)
    forward = 2.0 * batch * heads * (
        strict * d_key                          # K K^T
        + strict * (d_key + d_value)            # the solve on [K | V]
        + causal * (d_key + d_value)            # Q K^T, scores V'
        + 3 * seq_len * d_key * d_value)        # W S, Q S, K^T V'
    return {"forward": forward, "backward": 2.0 * forward}


def delta_rule_bytes(*, batch: int, seq_len: int, heads: int, d_key: int,
                     d_value: int, itemsize: int = 2) -> dict:
    """Least HBM traffic: forward reads ``q``, ``k``, ``v`` (``itemsize``)
    and ``alpha``, ``beta`` (float32) and writes ``o``; backward reads
    those and ``dO`` and writes the five gradients."""
    qkv = batch * seq_len * heads * (2 * d_key + d_value) * itemsize
    gates = 2 * batch * seq_len * heads * 4
    o = batch * seq_len * heads * d_value * itemsize
    inputs = qkv + gates
    return {"forward": float(inputs + o), "backward": float(2 * inputs + o)}


def linear_layer_flops(t: int, c: dict, chunk: int = CHUNK) -> float:
    """One ``linear_attention`` layer's forward pass over ``t`` positions,
    without its MLP: the in-projections (``q | k | v | gate``, ``a | b``),
    the rule, the out-projection."""
    h = c["linear_num_key_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    projections = 2.0 * t * c["hidden_size"] * (
        h * (2 * dk + dv) + h * dv + 2 * h + h * dv)
    return projections + delta_rule_flops(
        batch=1, seq_len=t, heads=h, d_key=dk, d_value=dv,
        chunk=chunk)["forward"]


def attention_layer_flops(t: int, c: dict) -> float:
    """One ``full_attention`` layer's forward pass, without its MLP: q, k,
    v, o and the two products over the causal pairs."""
    d = c["hidden_size"] // c["num_attention_heads"]
    q_width = c["num_attention_heads"] * d
    kv_width = c["num_key_value_heads"] * d
    return (2.0 * t * c["hidden_size"] * (2 * q_width + 2 * kv_width)
            + 2.0 * 2.0 * required_ops._causal_pairs(t) * q_width)


def forward_flops_per_sequence(c: dict, seq_len: int,
                               chunk: int = CHUNK) -> float:
    """One sequence's forward pass: every layer's mixer and gated MLP
    (three products), and the untied head.  The embedding is a lookup."""
    t = seq_len
    mlp = 2.0 * t * 3 * c["hidden_size"] * c["intermediate_size"]
    mixers = sum(linear_layer_flops(t, c, chunk)
                 if kind == "linear_attention"
                 else attention_layer_flops(t, c)
                 for kind in c["layer_types"])
    return mixers + len(c["layer_types"]) * mlp \
        + 2.0 * t * c["hidden_size"] * c["vocab_size"]


def train_flops(sequences: int, c: dict, seq_len: int,
                chunk: int = CHUNK) -> float:
    return 3.0 * forward_flops_per_sequence(c, seq_len, chunk) * sequences
