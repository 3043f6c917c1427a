"""Operations and bytes a sparse-expert decoder of the ``lfm2_moe`` family
*requires*, from shapes alone, by ``required_ops.py``'s conventions: one
multiply-accumulate is two operations, a backward pass costs twice its
forward pass, only matrix products are counted (the convolution's taps,
the norms, the gates, the sort and the optimizer are not), and
recomputation is the program's own to pay.  Sizes are named as the
source's ``config.json`` names them; ``experts_held`` and
``experts_routed`` are the cut file's (models/transformer.py reads them).

The rows the held experts receive depend on the routing, so the count
takes their **expectation** under an even router: every token sends
``num_experts_per_tok`` pairs, of which the share ``held / routed`` lands
on an expert held here.
"""

from __future__ import annotations

from benchmark import required_ops, required_ops_hybrid


def routed_experts(c: dict) -> int:
    """The router's width."""
    return c.get("experts_routed", c["num_experts"])


def held_experts(c: dict) -> int:
    first, end = c.get("experts_held", (0, routed_experts(c)))
    return end - first


def expected_rows_held(tokens: int, c: dict) -> float:
    """(token, choice) pairs that land on a held expert, in expectation."""
    return tokens * c["num_experts_per_tok"] * held_experts(c) \
        / routed_experts(c)


def experts_flops(rows: float, c: dict) -> dict:
    """The three products of the gated experts over ``rows`` rows (gate,
    up, down), whichever experts they went to."""
    forward = 2.0 * rows * 3 * c["hidden_size"] * c["moe_intermediate_size"]
    return {"forward": forward, "backward": 2.0 * forward}


def experts_bytes(rows: float, c: dict, itemsize: int = 2) -> dict:
    """Least HBM traffic of one expert layer: forward reads the rows and
    every held expert's three blocks and writes the rows' outputs (the
    gate and up values can stay on the chip); backward reads rows, output
    gradients and blocks and writes the rows' and the blocks' gradients."""
    d = c["hidden_size"]
    x = rows * d * itemsize
    blocks = held_experts(c) * 3 * d * c["moe_intermediate_size"] * itemsize
    return {"forward": float(2 * x + blocks),
            "backward": float(3 * x + 2 * blocks)}


def conv_mixer_flops(t: int, c: dict) -> float:
    """One ``conv`` mixer's forward pass over ``t`` positions: the
    in-projection to ``B | C | x`` and the out-projection."""
    d = c["hidden_size"]
    return 2.0 * t * d * 3 * d + 2.0 * t * d * d


def forward_flops_per_sequence(c: dict, seq_len: int) -> float:
    """One sequence's forward pass: every layer's mixer, the leading
    layers' dense gated MLP, the other layers' router and expected expert
    rows, and the tied head.  The embedding is a lookup."""
    t, d = seq_len, c["hidden_size"]
    mixers = sum(conv_mixer_flops(t, c) if kind == "conv"
                 else required_ops_hybrid.attention_layer_flops(t, c)
                 for kind in c["layer_types"])
    dense = c["num_dense_layers"]
    mlp = 2.0 * t * 3 * d * c["intermediate_size"]
    router = 2.0 * t * d * routed_experts(c)
    experts = experts_flops(expected_rows_held(t, c), c)["forward"]
    return mixers + dense * mlp \
        + (len(c["layer_types"]) - dense) * (router + experts) \
        + 2.0 * t * d * c["vocab_size"]


def train_flops(sequences: int, c: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_sequence(c, seq_len) * sequences


def experts_least_seconds(rows_by_layer, c: dict, peak: dict,
                          itemsize: int = 2) -> float:
    """Least time of the expert products of one step, forward and
    backward: one entry of ``rows_by_layer`` an expert layer, the rows its
    held experts received."""
    return sum(
        required_ops.roofline_seconds(
            experts_flops(rows, c)[p], experts_bytes(rows, c, itemsize)[p],
            peak)["seconds"]
        for rows in rows_by_layer for p in ("forward", "backward"))
