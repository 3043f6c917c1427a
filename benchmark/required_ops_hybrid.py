"""Operations and bytes a hybrid state-space / attention decoder
*requires*, from shapes alone, by ``required_ops.py``'s conventions: one
multiply-accumulate is two operations, a backward pass costs twice its
forward pass, only matrix products are counted (the convolution's four
taps, the norms, the gates, the decays and the optimizer are not), and
recomputation is the program's own to pay.  Sizes are named as the
source's ``config.json`` names them.
"""

from __future__ import annotations

from benchmark import required_ops


def ssd_flops(*, batch: int, seq_len: int, heads: int, head_dim: int,
              state: int, groups: int, chunk: int) -> dict:
    """The state-space recurrence in its chunked form (state-space
    duality) at the published chunk: inside a chunk ``C B^T`` once a
    group and the masked product with ``x`` per head, both over the
    chunk's causal pairs; each chunk's state (``B^T x``) and the state's
    output (``C S``) per head.  A last chunk the length leaves short
    counts by its own length."""
    full, rest = divmod(seq_len, chunk)
    pairs = full * required_ops._causal_pairs(chunk) \
        + required_ops._causal_pairs(rest)
    forward = 2.0 * batch * (
        pairs * state * groups              # C B^T
        + pairs * heads * head_dim          # (C B^T * decay) x
        + seq_len * heads * head_dim * state * 2)   # B^T x, C S
    return {"forward": forward, "backward": 2.0 * forward}


def ssd_bytes(*, batch: int, seq_len: int, heads: int, head_dim: int,
              state: int, groups: int, itemsize: int = 2) -> dict:
    """Least HBM traffic: forward reads ``x``, ``B``, ``C`` (``itemsize``)
    and ``dt`` (float32) and writes ``y``; backward reads those and
    ``dy`` and writes the four gradients."""
    x = batch * seq_len * heads * head_dim * itemsize
    bc = 2 * batch * seq_len * groups * state * itemsize
    dt = batch * seq_len * heads * 4
    inputs = x + bc + dt
    return {"forward": float(inputs + x), "backward": float(2 * inputs + x)}


def mamba_layer_flops(t: int, c: dict) -> float:
    """One ``mamba`` layer's forward pass over ``t`` positions, without
    its MLP: in-projection, the scan, out-projection."""
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    bc = 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    projections = 2.0 * t * c["hidden_size"] * (
        2 * inner + bc + c["mamba_n_heads"] + inner)
    return projections + ssd_flops(
        batch=1, seq_len=t, heads=c["mamba_n_heads"],
        head_dim=c["mamba_d_head"], state=c["mamba_d_state"],
        groups=c["mamba_n_groups"], chunk=c["mamba_chunk_size"])["forward"]


def attention_layer_flops(t: int, c: dict) -> float:
    """One ``attention`` layer's forward pass, without its MLP: q and o
    at the query heads, k and v at the key-value heads, and the two
    products over the causal pairs."""
    d = c["hidden_size"] // c["num_attention_heads"]
    q_width = c["num_attention_heads"] * d
    kv_width = c["num_key_value_heads"] * d
    return (2.0 * t * c["hidden_size"] * (2 * q_width + 2 * kv_width)
            + 2.0 * 2.0 * required_ops._causal_pairs(t) * q_width)


def hybrid_forward_flops_per_sequence(c: dict, seq_len: int) -> float:
    """One sequence's forward pass: every layer's mixer and gated MLP
    (three products), and the output head.  The embedding is a lookup."""
    t = seq_len
    mlp = 2.0 * t * 3 * c["hidden_size"] * c["shared_intermediate_size"]
    mixers = sum(mamba_layer_flops(t, c) if kind == "mamba"
                 else attention_layer_flops(t, c)
                 for kind in c["layer_types"])
    return mixers + len(c["layer_types"]) * mlp \
        + 2.0 * t * c["hidden_size"] * c["vocab_size"]


def hybrid_train_flops(sequences: int, c: dict, seq_len: int) -> float:
    return 3.0 * hybrid_forward_flops_per_sequence(c, seq_len) * sequences
