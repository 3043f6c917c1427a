"""Operations and bytes a decoder of the ``joyai_llm_flash`` family
(DeepSeek-V3's layers: latent attention, top-k experts with a shared one,
one multi-token-prediction module) *requires*, from shapes alone, by
``required_ops.py``'s conventions: one multiply-accumulate is two
operations, a weight's product costs twice its forward pass backward,
only matrix products are counted, and recomputation is the program's own
to pay.  Sizes are named as the source's ``config.json`` names them;
``experts_held`` and ``experts_routed`` are the cut file's.

Attention's core — ``q · k`` over ``d_qk`` and ``p · v`` over ``d_v`` on
the causal pairs — is counted apart, forward ``2 · pairs · H · (d_qk +
d_v)`` and backward ``2 · pairs · H · (3 d_qk + 2 d_v)`` (the scores
again, dV, dP, dQ, dK), since the widths differ.  The experts' rows are
the **expectation** under an even router, as ``required_ops_moe.py``
takes them.
"""

from __future__ import annotations

from benchmark import required_ops, required_ops_moe


def _pairs(t: int) -> float:
    return t * (t + 1) / 2.0


def core_flops(*, batch: int, heads: int, seq_len: int, d_qk: int,
               d_v: int) -> dict:
    """The latent core's required operations, forward and backward, over
    the pairs a causal mask keeps."""
    pairs = batch * heads * _pairs(seq_len)
    return {"forward": 2.0 * pairs * (d_qk + d_v),
            "backward": 2.0 * pairs * (3 * d_qk + 2 * d_v)}


def core_bytes(*, batch: int, heads: int, seq_len: int, d_qk: int, d_v: int,
               itemsize: int = 2) -> dict:
    """Least HBM traffic of the core: forward reads q, k (``d_qk``) and v
    and writes o (``d_v``); backward reads q, k, v, o, dO and writes dQ,
    dK, dV (the ``[B, H, T]`` softmax statistics are left out)."""
    row = batch * heads * seq_len * itemsize
    return {"forward": float(row * (2 * d_qk + 2 * d_v)),
            "backward": float(row * (4 * d_qk + 4 * d_v))}


def core_least_seconds(shape: dict, peak: dict) -> float:
    """Least time of one step's latent cores, forward and backward, every
    layer: ``shape`` holds ``batch``, ``heads``, ``seq_len``, ``d_qk``,
    ``d_v``, ``layers`` and ``itemsize``."""
    size = {k: shape[k] for k in ("batch", "heads", "seq_len", "d_qk",
                                  "d_v")}
    flops = core_flops(**size)
    nbytes = core_bytes(itemsize=shape["itemsize"], **size)
    return shape["layers"] * sum(
        required_ops.roofline_seconds(flops[p], nbytes[p], peak)["seconds"]
        for p in ("forward", "backward"))


def d_qk(c: dict) -> int:
    return c["qk_nope_head_dim"] + c["qk_rope_head_dim"]


def latent_layers(c: dict) -> int:
    """Every layer's mixer is latent attention, the MTP module's too."""
    return c["num_hidden_layers"] + c.get("num_nextn_predict_layers", 0)


def projection_flops(t: int, c: dict) -> float:
    """One latent-attention layer's projections, forward, ``t`` rows:
    ``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb`` and ``W_o``."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    macs = (d * c["q_lora_rank"] + c["q_lora_rank"] * heads * d_qk(c)
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads
            * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + heads * c["v_head_dim"] * d)
    return 2.0 * t * macs


def moe_config(c: dict) -> dict:
    """The keys ``required_ops_moe`` reads, from this family's names:
    what a builder hands ``readers/moe.py`` as ``shapes["moe"]
    ["config"]``."""
    return {"hidden_size": c["hidden_size"],
            "moe_intermediate_size": c["moe_intermediate_size"],
            "num_experts_per_tok": c["num_experts_per_tok"],
            "num_experts": c["n_routed_experts"],
            "experts_routed": c.get("experts_routed", c["n_routed_experts"]),
            "experts_held": c.get("experts_held",
                                  (0, c["n_routed_experts"]))}


def weight_flops_per_sequence(c: dict, seq_len: int) -> float:
    """One sequence's forward pass through every product with a weight:
    the latent projections, the leading dense MLPs, the expert layers'
    router, expected held rows and shared expert, the MTP module's
    ``W_eh`` and the head twice (trunk and module).  The embedding is a
    lookup."""
    t, d = seq_len, c["hidden_size"]
    moe = moe_config(c)
    mtp = c.get("num_nextn_predict_layers", 0)
    dense = c["first_k_dense_replace"]
    expert_layers = c["num_hidden_layers"] - dense + mtp
    mlp = 2.0 * t * 3 * d * c["intermediate_size"]
    router = 2.0 * t * d * required_ops_moe.routed_experts(moe)
    shared = 2.0 * t * 3 * d * c["n_shared_experts"] \
        * c["moe_intermediate_size"]
    experts = required_ops_moe.experts_flops(
        required_ops_moe.expected_rows_held(t, moe), moe)["forward"]
    return (latent_layers(c) * projection_flops(t, c) + dense * mlp
            + expert_layers * (router + shared + experts)
            + mtp * 2.0 * t * 2 * d * d
            + (1 + mtp) * 2.0 * t * d * c["vocab_size"])


def train_flops(sequences: int, c: dict, seq_len: int) -> float:
    """One training step over ``sequences`` sequences: three times the
    weights' forward, and every latent core forward and backward."""
    core = core_flops(batch=1, heads=c["num_attention_heads"],
                      seq_len=seq_len, d_qk=d_qk(c), d_v=c["v_head_dim"])
    per_sequence = 3.0 * weight_flops_per_sequence(c, seq_len) \
        + latent_layers(c) * (core["forward"] + core["backward"])
    return per_sequence * sequences
