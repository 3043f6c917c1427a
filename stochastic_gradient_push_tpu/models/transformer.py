"""Decoder-only transformer LM with optional ring-attention sequence
parallelism.

The reference's transformer experiments (WMT16, paper §5) ran in an external
fairseq fork — the repo itself ships only the log parser
(visualization/plotting.py:137-192).  This module makes the transformer a
first-class in-repo model family, built TPU-first:

* pre-norm blocks, bf16-friendly compute with fp32 LN/softmax
* rotary position embeddings (no learned position table to shard)
* attention backends: ``full`` (plain causal), ``blockwise``
  (O(block²) memory, single device), or ``ring`` — exact attention over a
  sequence-sharded mesh axis (parallel/ring_attention.py), with every rank
  holding ``seq/world`` tokens
* pointwise sublayers (embedding, LN, MLP, logits) act per-token, so under
  sequence sharding they need no communication at all
* optional switch-MoE feed-forward blocks with experts sharded over an
  ``ep`` mesh axis (models/moe.py): set ``moe_experts > 0`` and every
  ``moe_every``-th block routes tokens to experts via all_to_all
* a layer *pattern* (``layer_types``): each block's mixer is causal
  attention or the Mamba-2 state-space mixer (models/ssm.py), and the
  hybrid families' other parts — grouped-query heads, RMSNorm, a
  SiLU-gated MLP, no positions, a tied head, the four multipliers — are
  fields of the one config; ``config_from_source`` fills them from a
  published ``config.json``.  The defaults are the GPT-2-shaped model
* the ``lfm2_moe`` family's parts: a ``conv`` mixer (the gated short
  convolution, models/shortconv.py), a feed-forward *pattern*
  (``ffn_types``: dense, the switch layer, or the top-k expert layer of
  models/moe.py over the experts held here), per-head RMSNorm of q and k
  and a configurable rotary base
* the ``olmo_hybrid`` family's parts: a ``linear_attention`` mixer (the
  gated delta rule, models/gated_deltanet.py), norms after each sublayer
  in place of before it (``post_norm``: ``x + norm(f(x))``, Olmo 2's
  order) and RMSNorm of q and k over the whole projection
  (``qk_norm="projection"``)
* the ``joyai_llm_flash`` family's parts, DeepSeek-V3's (arXiv:2412.19437
  §2.1–2.2): a ``latent_attention`` mixer (queries and keys-values through
  low-rank latents, q·k heads of 192 beside v heads of 128, one rotated
  64-lane key head shared by all heads, rotary in the interleaved
  convention), top-k experts with a scale on their weights and a shared
  expert, and a multi-token-prediction module (``mtp_layers``) whose
  logits for the token after next the loss (train/lm.py::mtp_loss) adds
"""

from __future__ import annotations

import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ring_attention import blockwise_attention, ring_attention
from ..telemetry import names
from .gated_deltanet import DeltaNetConfig, GatedDeltaNetMixer
from .moe import ExpertsConfig, topk_moe_ffn
from .shortconv import ShortConvMixer
from .ssm import Mamba2Mixer, SSMConfig

__all__ = ["TransformerLM", "TransformerConfig", "config_from_source"]

LAYER_TYPES = ("attention", "mamba", "conv", "linear_attention",
               "latent_attention")
FFN_TYPES = ("dense", "switch", "experts")


def _rope(x: jnp.ndarray, positions: jnp.ndarray,
          base: float = 10000.0) -> jnp.ndarray:
    """Rotary embeddings. x: [B, H, T, D]; positions: [T] global indices."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, None]      # [1,1,T,half]
    sin = jnp.sin(angles)[None, None]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def _rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                      base: float) -> jnp.ndarray:
    """Rotary embeddings in the interleaved convention: lanes ``2i`` and
    ``2i + 1`` are the pair rotated by ``positions * base ** (-2i / D)``.
    As the source's code does it, the lanes are first laid out evens then
    odds, and then rotated half-split: the output is in that order, for q
    and k alike, which leaves every product ``q · k`` as the pairs'
    rotation gives it."""
    *lead, d = x.shape
    x = x.reshape(*lead, d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*lead, d)
    return _rope(x, positions, base)


class MLAConfig(tp.NamedTuple):
    """Sizes of the latent-attention mixer, as the source's ``config.json``
    names them: ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


class TransformerConfig(tp.NamedTuple):
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    d_ff: int = 2048
    max_len: int = 2048
    dtype: tp.Any = jnp.float32
    attn_impl: str = "full"     # full | blockwise | flash | ring | ring_flash
    # block size for blockwise/flash/ring_flash; None = the measured
    # auto rule (ops.flash_attention.default_block) on the local length
    attn_block_size: int | None = None
    # flash only: a different K/V-side block (None = attn_block_size).
    # The fenced kernel sweep found asymmetric (bq 512, bk 256) best for
    # the t=1024 backward (docs/tpu_runs/20260731T071733_retry)
    attn_block_k: int | None = None
    seq_axis: str | None = None       # mesh axis for ring attention
    remat: bool = False               # jax.checkpoint each block
    moe_experts: int = 0              # total experts (0 = dense FFN)
    moe_every: int = 2                # every k-th block uses MoE
    ep_axis: str | None = None        # mesh axis experts shard over
    moe_capacity_factor: float = 1.25
    # -- the hybrid families' parts; every default is the dense model's --
    # one mixer a layer, from LAYER_TYPES (None: attention throughout)
    layer_types: tuple[str, ...] | None = None
    ssm: SSMConfig | None = None      # the "mamba" layers' sizes
    n_kv_heads: int | None = None     # grouped-query heads (None: n_heads)
    norm: str = "layernorm"           # layernorm | rmsnorm
    norm_eps: float = 1e-6
    mlp: str = "gelu"                 # gelu (biased) | swiglu (no bias)
    positions: str = "rotary"         # rotary | none
    tie_embeddings: bool = False      # logits from the embedding table
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    # the softmax scale (None: head_dim ** -0.5)
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0       # logits are divided by it
    # -- the lfm2_moe family's parts ------------------------------------
    # one feed-forward a layer, from FFN_TYPES (None: the switch layer
    # every moe_every-th block where moe_experts > 0, else dense)
    ffn_types: tuple[str, ...] | None = None
    experts: ExpertsConfig | None = None   # the "experts" layers' sizes
    conv_taps: int = 3                # the "conv" mixer's taps
    # RMSNorm of q and k: None, "head" (one weight of head_dim, lfm2) or
    # "projection" (one of all heads' widths, before the split: Olmo 2)
    qk_norm: str | None = None
    rope_theta: float = 10000.0
    # -- the olmo_hybrid family's parts ---------------------------------
    delta: DeltaNetConfig | None = None   # the "linear_attention" layers'
    post_norm: bool = False           # x + norm(f(x)), no norm before f
    # -- the joyai_llm_flash family's parts -----------------------------
    mla: MLAConfig | None = None      # the "latent_attention" layers'
    mtp_layers: int = 0               # multi-token-prediction modules

    def layer_type(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else "attention"

    def ffn_type(self, i: int) -> str:
        if self.ffn_types:
            return self.ffn_types[i]
        switch = (self.moe_experts > 0
                  and i % self.moe_every == self.moe_every - 1)
        return "switch" if switch else "dense"

    def check_pattern(self) -> None:
        """Raises ``ValueError`` for a pattern this config cannot build."""
        for what, types, known in (
                ("layer_types", self.layer_types, LAYER_TYPES),
                ("ffn_types", self.ffn_types, FFN_TYPES)):
            if types is None:
                continue
            if len(types) != self.n_layers:
                raise ValueError(f"{what} names {len(types)} layers, "
                                 f"n_layers is {self.n_layers}")
            unknown = sorted(set(types) - set(known))
            if unknown:
                raise ValueError(f"{what} {unknown} are none of {known}")
        if "mamba" in (self.layer_types or ()) and self.ssm is None:
            raise ValueError("a 'mamba' layer needs the mixer's sizes "
                             "(TransformerConfig.ssm)")
        if "linear_attention" in (self.layer_types or ()) \
                and self.delta is None:
            raise ValueError("a 'linear_attention' layer needs the mixer's "
                             "sizes (TransformerConfig.delta)")
        if "latent_attention" in (self.layer_types or ()) \
                and self.mla is None:
            raise ValueError("a 'latent_attention' layer needs the mixer's "
                             "sizes (TransformerConfig.mla)")
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"{self.mtp_layers} multi-token-prediction "
                             "modules: the model builds 0 or 1")
        if self.mtp_layers and (self.seq_axis is not None or self.attn_impl
                                in ("ring", "ring_flash")):
            # each shard would read its own first token as its last row's
            # next one, and drop a row the whole sequence does not
            raise ValueError("the multi-token-prediction module takes the "
                             "next token from the rows it holds: it runs "
                             "on an unsharded sequence only, not under "
                             "seq_axis or ring attention")
        if "experts" in (self.ffn_types or ()):
            if self.experts is None:
                raise ValueError("an 'experts' feed-forward needs the "
                                 "layer's sizes (TransformerConfig.experts)")
            self.experts.check()


def _check_source(src: dict, wanted: dict) -> None:
    for key, values in wanted.items():
        if src.get(key, values[0]) not in values:
            raise ValueError(f"source config {key}={src[key]!r}: the model "
                             f"computes {key} in {values} only")


def _granitemoehybrid_config(src: dict, **runtime) -> TransformerConfig:
    """The ``granitemoehybrid`` family, dense: Mamba-2 and grouped-query
    attention layers, each followed by a SiLU-gated MLP."""
    _check_source(src, {
        "hidden_act": ("silu",), "normalization_function": ("rmsnorm",),
        "position_embedding_type": ("nope", "rope"),
        "num_local_experts": (0,), "attention_bias": (False,),
        "mamba_proj_bias": (False,)})
    d_model = src["hidden_size"]
    ssm = SSMConfig(
        n_heads=src["mamba_n_heads"], d_head=src["mamba_d_head"],
        d_state=src["mamba_d_state"], n_groups=src["mamba_n_groups"],
        d_conv=src["mamba_d_conv"], chunk_size=src["mamba_chunk_size"],
        conv_bias=src["mamba_conv_bias"])
    if ssm.n_heads * ssm.d_head != src["mamba_expand"] * d_model:
        raise ValueError(
            f"mamba_n_heads * mamba_d_head = {ssm.n_heads * ssm.d_head} "
            f"is not mamba_expand * hidden_size = "
            f"{src['mamba_expand'] * d_model}")
    return TransformerConfig(
        vocab_size=src["vocab_size"], d_model=d_model,
        n_layers=src["num_hidden_layers"],
        n_heads=src["num_attention_heads"],
        n_kv_heads=src["num_key_value_heads"],
        d_ff=src["shared_intermediate_size"],
        layer_types=tuple(src["layer_types"]), ssm=ssm,
        norm="rmsnorm", norm_eps=src["rms_norm_eps"], mlp="swiglu",
        positions={"nope": "none", "rope": "rotary"}[
            src["position_embedding_type"]],
        tie_embeddings=src["tie_word_embeddings"],
        embedding_multiplier=src["embedding_multiplier"],
        residual_multiplier=src["residual_multiplier"],
        attention_multiplier=src["attention_multiplier"],
        logits_scaling=src["logits_scaling"], **runtime)


def _lfm2_moe_config(src: dict, **runtime) -> TransformerConfig:
    """The ``lfm2_moe`` family: gated short-convolution and grouped-query
    attention layers (q and k normed per head, rotary), ``num_dense_layers``
    SiLU-gated MLPs and then top-k experts chosen by biased sigmoid
    scores; one tied table.  Two keys of a cut file, not of the source:
    ``experts_held`` — ``[first, end)`` — says which of the experts live
    here (none given: all of them), and ``experts_routed`` is the router's
    width where it is not ``num_experts``."""
    mixers = {"conv": "conv", "full_attention": "attention"}
    unknown = sorted(set(src["layer_types"]) - set(mixers))
    if unknown:
        raise ValueError(f"source config layer_types {unknown} are none of "
                         f"{tuple(mixers)}")
    _check_source(src, {
        "conv_bias": (False,), "norm_topk_prob": (True,),
        "use_expert_bias": (True,), "routed_scaling_factor": (1, 1.0)})
    n_layers, dense = src["num_hidden_layers"], src["num_dense_layers"]
    held = src.get("experts_held")
    experts = ExpertsConfig(
        n_experts=src.get("experts_routed", src["num_experts"]),
        per_token=src["num_experts_per_tok"],
        d_ff=src["moe_intermediate_size"],
        held=tuple(held) if held is not None else None)
    return TransformerConfig(
        vocab_size=src["vocab_size"], d_model=src["hidden_size"],
        n_layers=n_layers, n_heads=src["num_attention_heads"],
        n_kv_heads=src["num_key_value_heads"], d_ff=src["intermediate_size"],
        layer_types=tuple(mixers[k] for k in src["layer_types"]),
        ffn_types=("dense",) * dense + ("experts",) * (n_layers - dense),
        experts=experts, conv_taps=src["conv_L_cache"], qk_norm="head",
        rope_theta=float(src["rope_theta"]), norm="rmsnorm",
        norm_eps=src["norm_eps"], mlp="swiglu", tie_embeddings=True,
        **runtime)


def _olmo_hybrid_config(src: dict, **runtime) -> TransformerConfig:
    """The ``olmo_hybrid`` family: gated delta-rule (``linear_attention``)
    and full attention layers, each followed by a SiLU-gated MLP, every
    sublayer's RMSNorm after it (Olmo 2), q and k normed over the whole
    projection, no bias, an untied head, and no position term at all
    (``rope_parameters.rope_theta`` null)."""
    mixers = {"linear_attention": "linear_attention",
              "full_attention": "attention"}
    unknown = sorted(set(src["layer_types"]) - set(mixers))
    if unknown:
        raise ValueError(f"source config layer_types {unknown} are none of "
                         f"{tuple(mixers)}")
    _check_source(src, {"hidden_act": ("silu",), "attention_bias": (False,)})
    if src["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("source config rope_parameters.rope_theta="
                         f"{src['rope_parameters']['rope_theta']!r}: the "
                         "model computes no positions for this family")
    if src["linear_num_value_heads"] != src["linear_num_key_heads"]:
        raise ValueError("source config linear_num_value_heads="
                         f"{src['linear_num_value_heads']!r}: the model "
                         "computes one value head a key head only")
    delta = DeltaNetConfig(
        n_heads=src["linear_num_key_heads"],
        key_head_dim=src["linear_key_head_dim"],
        value_head_dim=src["linear_value_head_dim"],
        conv_kernel_dim=src["linear_conv_kernel_dim"],
        allow_neg_eigval=src["linear_allow_neg_eigval"])
    return TransformerConfig(
        vocab_size=src["vocab_size"], d_model=src["hidden_size"],
        n_layers=src["num_hidden_layers"],
        n_heads=src["num_attention_heads"],
        n_kv_heads=src["num_key_value_heads"], d_ff=src["intermediate_size"],
        layer_types=tuple(mixers[k] for k in src["layer_types"]),
        delta=delta, qk_norm="projection",
        post_norm=True, norm="rmsnorm", norm_eps=src["rms_norm_eps"],
        mlp="swiglu", tie_embeddings=src["tie_word_embeddings"],
        positions="none", **runtime)


def _joyai_llm_flash_config(src: dict, **runtime) -> TransformerConfig:
    """The ``joyai_llm_flash`` family, DeepSeek-V3's layers: latent
    attention in every layer, ``first_k_dense_replace`` SiLU-gated MLPs
    and then top-k experts chosen by sigmoid scores and a selection bias
    (``noaux_tc``, one group), their weights normalised and scaled by
    ``routed_scaling_factor``, beside ``n_shared_experts`` shared ones;
    RMSNorm, an untied head and ``num_nextn_predict_layers``
    multi-token-prediction modules.  ``experts_held`` and
    ``experts_routed`` are the cut file's keys, as in ``lfm2_moe``."""
    _check_source(src, {
        "hidden_act": ("silu",), "attention_bias": (False,),
        "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
        "n_group": (1,), "topk_group": (1,), "norm_topk_prob": (True,),
        "moe_layer_freq": (1,), "rope_scaling": (None,),
        "num_nextn_predict_layers": (0, 1)})
    heads = src["num_attention_heads"]
    if src["num_key_value_heads"] != heads:
        raise ValueError("source config num_key_value_heads="
                         f"{src['num_key_value_heads']!r}: latent attention "
                         "has one key-value head a query head")
    mla = MLAConfig(
        q_lora_rank=src["q_lora_rank"], kv_lora_rank=src["kv_lora_rank"],
        qk_nope_dim=src["qk_nope_head_dim"],
        qk_rope_dim=src["qk_rope_head_dim"], v_head_dim=src["v_head_dim"])
    if src.get("qk_head_dim", mla.qk_nope_dim + mla.qk_rope_dim) \
            != mla.qk_nope_dim + mla.qk_rope_dim:
        raise ValueError(f"source config qk_head_dim={src['qk_head_dim']!r}"
                         " is not qk_nope_head_dim + qk_rope_head_dim")
    n_layers, dense = src["num_hidden_layers"], src["first_k_dense_replace"]
    held = src.get("experts_held")
    experts = ExpertsConfig(
        n_experts=src.get("experts_routed", src["n_routed_experts"]),
        per_token=src["num_experts_per_tok"],
        d_ff=src["moe_intermediate_size"],
        held=tuple(held) if held is not None else None,
        scale=float(src["routed_scaling_factor"]),
        d_shared=src["n_shared_experts"] * src["moe_intermediate_size"])
    mtp = src.get("num_nextn_predict_layers", 0)
    return TransformerConfig(
        vocab_size=src["vocab_size"], d_model=src["hidden_size"],
        n_layers=n_layers, n_heads=heads, d_ff=src["intermediate_size"],
        layer_types=("latent_attention",) * n_layers,
        ffn_types=("dense",) * dense + ("experts",) * (n_layers - dense),
        experts=experts, mla=mla, rope_theta=float(src["rope_theta"]),
        norm="rmsnorm", norm_eps=src["rms_norm_eps"], mlp="swiglu",
        tie_embeddings=src["tie_word_embeddings"], mtp_layers=mtp,
        **runtime)


# model_type -> (the family's config, the source's key of the dense width)
SOURCE_FAMILIES = {
    "granitemoehybrid": (_granitemoehybrid_config,
                         "shared_intermediate_size"),
    "lfm2_moe": (_lfm2_moe_config, "intermediate_size"),
    "olmo_hybrid": (_olmo_hybrid_config, "intermediate_size"),
    "joyai_llm_flash": (_joyai_llm_flash_config, "intermediate_size"),
}


def source_family(src: dict):
    """``SOURCE_FAMILIES``' entry for a source's ``model_type`` (a file
    that names none is the first family's, as before there were two)."""
    kind = src.get("model_type", "granitemoehybrid")
    if kind not in SOURCE_FAMILIES:
        raise ValueError(f"source config model_type={kind!r}: the model "
                         f"computes model_type in {tuple(SOURCE_FAMILIES)} "
                         "only")
    return SOURCE_FAMILIES[kind]


def config_from_source(src: dict, **runtime) -> TransformerConfig:
    """The config of a model described by its source's ``config.json``
    keys, by its ``model_type`` (``SOURCE_FAMILIES``).  ``runtime`` gives
    what no source states (``dtype``, ``attn_impl``, ``remat``, ...).
    Keys beside the source's own are ignored; a value the model code does
    not compute raises ``ValueError``."""
    cfg = source_family(src)[0](src, **runtime)
    cfg.check_pattern()
    return cfg


def _norm(cfg: TransformerConfig, name: str):
    """The config's normalisation, float32."""
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)
    if cfg.norm != "layernorm":
        raise ValueError(f"unknown norm {cfg.norm}")
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)


def _scaled(x, by: float):
    return x if by == 1.0 else x * by


def _attend(cfg: TransformerConfig, q, k, v):
    """Causal attention of ``q``, ``k`` ``[B, H, T, d_qk]`` and ``v``
    ``[B, H, T, d_v]`` by ``cfg.attn_impl``, at the scale ``d_qk ** -0.5``;
    ``[B, H, T, d_v]``."""
    if cfg.attn_impl == "ring":
        if cfg.seq_axis is None:
            raise ValueError("ring attention requires seq_axis")
        return ring_attention(q, k, v, cfg.seq_axis, causal=True)
    if cfg.attn_impl == "ring_flash":
        # flash-kernel ticks: O(attn_block_size²) memory per device
        # regardless of shard length — the long-context production
        # path (ops/ring_flash.py)
        if cfg.seq_axis is None:
            raise ValueError("ring attention requires seq_axis")
        from ..ops.flash_attention import default_block
        from ..ops.ring_flash import ring_flash_attention
        return ring_flash_attention(
            q, k, v, cfg.seq_axis, causal=True,
            block=cfg.attn_block_size or default_block(q.shape[2]))
    if cfg.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention
        return flash_attention(
            q, k, v, causal=True,
            block_q=cfg.attn_block_size,
            block_k=cfg.attn_block_k or cfg.attn_block_size)
    if cfg.attn_impl == "blockwise":
        return blockwise_attention(
            q, k, v, min(cfg.attn_block_size or 128, q.shape[2]),
            causal=True)
    if cfg.attn_impl == "full":
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
        s = s * q.shape[-1] ** -0.5
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(jnp.float32)).astype(cfg.dtype)
    raise ValueError(f"unknown attn_impl {cfg.attn_impl}")


class _Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        head_dim = cfg.d_model // cfg.n_heads
        kv_heads = cfg.n_kv_heads or cfg.n_heads
        if cfg.n_heads % kv_heads:
            raise ValueError(f"{cfg.n_heads} query heads do not divide "
                             f"over {kv_heads} key-value heads")
        dense = lambda name, heads: nn.Dense(
            heads * head_dim, use_bias=False, dtype=cfg.dtype, name=name)
        q = dense("q", cfg.n_heads)(x)
        k = dense("k", kv_heads)(x)
        v = dense("v", kv_heads)(x)

        def split(t):  # [B,T,heads·D] → [B,heads,T,D]
            b, s, e = t.shape
            return t.reshape(b, s, e // head_dim, head_dim).transpose(
                0, 2, 1, 3)

        # one learned weight for q, one for k, over the head's width or
        # the whole projection's, before the rotation
        qk_norm = lambda name, t: nn.RMSNorm(
            epsilon=cfg.norm_eps, dtype=jnp.float32,
            name=name)(t).astype(cfg.dtype)
        if cfg.qk_norm == "projection":
            q, k = qk_norm("q_norm", q), qk_norm("k_norm", k)
        q, k, v = split(q), split(k), split(v)
        if cfg.qk_norm == "head":
            q, k = qk_norm("q_norm", q), qk_norm("k_norm", k)
        if cfg.positions == "rotary":
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        elif cfg.positions != "none":
            raise ValueError(f"unknown positions {cfg.positions}")
        if cfg.attention_multiplier is not None:
            # every backend below scales by head_dim ** -0.5; the factor
            # that makes that the config's scale goes into q (an exact
            # power of two for the published 1/64 at heads of 64)
            q = q * (cfg.attention_multiplier * head_dim ** 0.5)
        if kv_heads != cfg.n_heads:
            # grouped-query heads by repeating k, v: query head h reads
            # key-value head h // (n_heads / kv_heads); the repeat's
            # transpose sums the group's gradients
            k = jnp.repeat(k, cfg.n_heads // kv_heads, axis=1)
            v = jnp.repeat(v, cfg.n_heads // kv_heads, axis=1)

        out = _attend(cfg, q, k, v)
        b, h, s, d = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="o")(out)


class _LatentAttention(nn.Module):
    """DeepSeek-V3's multi-head latent attention (arXiv:2412.19437
    §2.1.1), trained as the source computes it (no absorbed products):

    * q: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``, each head
      ``q_nope`` | ``q_pe``;
    * kv: ``[c_kv | k_pe] = x W_kva``, ``[k_nope | v] = RMSNorm(c_kv)
      W_kvb`` a head; ``k_pe`` is one head, rotated and shared by all;
    * rotary (interleaved) on the ``q_pe`` / ``k_pe`` lanes alone, causal
      attention of ``[q_nope | q_pe]`` on ``[k_nope | k_pe]`` at
      ``(nope + rope) ** -0.5`` over ``v``, and ``W_o``.

    Norms float32; the products bf16 operands where ``cfg.dtype`` is."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg, m = self.cfg, self.cfg.mla
        heads, d_qk = cfg.n_heads, m.qk_nope_dim + m.qk_rope_dim
        dense = lambda width, name: nn.Dense(
            width, use_bias=False, dtype=cfg.dtype, name=name)
        norm = lambda name, t: nn.RMSNorm(
            epsilon=cfg.norm_eps, dtype=jnp.float32,
            name=name)(t).astype(cfg.dtype)
        b, t, _ = x.shape

        def split(y):  # [B, T, heads·w] -> [B, heads, T, w]
            return y.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)

        with jax.named_scope(names.SCOPE_MLA):
            q = split(dense(heads * d_qk, "q_b")(
                norm("q_a_norm", dense(m.q_lora_rank, "q_a")(x))))
            c_kv, k_pe = jnp.split(
                dense(m.kv_lora_rank + m.qk_rope_dim, "kv_a")(x),
                [m.kv_lora_rank], axis=-1)
            kv = split(dense(heads * (m.qk_nope_dim + m.v_head_dim),
                             "kv_b")(norm("kv_a_norm", c_kv)))
            k_nope, v = jnp.split(kv, [m.qk_nope_dim], axis=-1)
            q_nope, q_pe = jnp.split(q, [m.qk_nope_dim], axis=-1)
            q_pe = _rope_interleaved(q_pe, positions, cfg.rope_theta)
            k_pe = _rope_interleaved(k_pe[:, None], positions,
                                     cfg.rope_theta)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, q_pe.shape)], axis=-1)
            out = _attend(cfg, q, k, v)
            out = out.transpose(0, 2, 1, 3).reshape(
                b, t, heads * m.v_head_dim)
            return dense(cfg.d_model, "o")(out)


class _MoEFFN(nn.Module):
    """Switch-MoE feed-forward (models/moe.py) as a flax module.

    Expert weights carry the *local* slice when ``ep_axis`` is set — the
    state layout shards the expert dimension over ``ep`` (see
    ``train/lm.py::ep_state_specs``); the router is replicated.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from .moe import switch_moe_ffn

        cfg = self.cfg
        ep = 1
        if cfg.ep_axis is not None:
            ep = lax.axis_size(cfg.ep_axis)
        if cfg.moe_experts % ep:
            raise ValueError(
                f"moe_experts {cfg.moe_experts} not divisible by ep {ep}")
        e_local = cfg.moe_experts // ep
        router = self.param(
            "router", nn.initializers.normal(0.02),
            (cfg.d_model, cfg.moe_experts), jnp.float32)
        w1 = self.param("experts_up", nn.initializers.lecun_normal(),
                        (e_local, cfg.d_model, cfg.d_ff), jnp.float32)
        w2 = self.param("experts_down", nn.initializers.lecun_normal(),
                        (e_local, cfg.d_ff, cfg.d_model), jnp.float32)

        b, t, d = x.shape
        flat = x.reshape(b * t, d)
        y, aux = switch_moe_ffn(
            flat, router, w1, w2, ep_axis=cfg.ep_axis,
            capacity_factor=cfg.moe_capacity_factor)
        self.sow("losses", "load_balance", aux["load_balance_loss"])
        self.sow("moe_metrics", "dropped_fraction",
                 aux["dropped_fraction"])
        return y.reshape(b, t, d)


class _ExpertsFFN(nn.Module):
    """The top-k expert feed-forward (models/moe.py::topk_moe_ffn) over
    the experts ``cfg.experts`` says are held here, and its shared expert
    where it has one.  The selection bias is a leaf no gradient reaches;
    the layer's counters are sown under ``moe_metrics``, its selection
    under ``moe_selection`` and the router's scores under ``moe_scores``
    (collections only a comparison or the bias's balancing asks for)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg, ex = self.cfg, self.cfg.experts
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.normal(0.02),
                            (cfg.d_model, ex.n_experts), jnp.float32)
        bias = self.param("expert_bias", nn.initializers.normal(0.02),
                          (ex.n_experts,), jnp.float32)
        gate_up = self.param("experts_gate_up", per_expert,
                             (ex.n_held, cfg.d_model, 2 * ex.d_ff),
                             jnp.float32)
        down = self.param("experts_down", per_expert,
                          (ex.n_held, ex.d_ff, cfg.d_model), jnp.float32)
        shared = None
        if ex.d_shared:
            shared = (self.param("shared_gate_up",
                                 nn.initializers.lecun_normal(),
                                 (cfg.d_model, 2 * ex.d_shared), jnp.float32),
                      self.param("shared_down", nn.initializers.lecun_normal(),
                                 (ex.d_shared, cfg.d_model), jnp.float32))
        b, t, d = x.shape
        with jax.named_scope(names.SCOPE_MOE):
            y, aux = topk_moe_ffn(
                x.reshape(b * t, d), router, bias, gate_up, down,
                per_token=ex.per_token, first=ex.first, dtype=cfg.dtype,
                scale=ex.scale, shared=shared)
        self.sow("moe_metrics", "expert_rows", aux["expert_rows"])
        self.sow("moe_metrics", "pairs_not_held", aux["pairs_not_held"])
        self.sow("moe_selection", "experts",
                 aux["selection"].reshape(b, t, ex.per_token))
        self.sow("moe_scores", "scores", aux["scores"])
        return y.reshape(b, t, d)


class _Block(nn.Module):
    cfg: TransformerConfig
    ffn_type: str = "dense"           # the feed-forward, from FFN_TYPES
    layer_type: str = "attention"     # the mixer, from LAYER_TYPES

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        res = cfg.residual_multiplier
        # a sublayer's norm: before it (h = norm(x)), or after it, on its
        # output (post_norm), with no norm in front
        before = lambda name, t: t if cfg.post_norm else _norm(cfg, name)(t)
        after = lambda name, t: _norm(cfg, name)(t) if cfg.post_norm else t
        h = before("ln1", x)
        if self.layer_type == "mamba":
            mixed = Mamba2Mixer(cfg.ssm, cfg.d_model, dtype=cfg.dtype,
                                norm_eps=cfg.norm_eps, name="ssm")(h)
        elif self.layer_type == "conv":
            mixed = ShortConvMixer(cfg.d_model, cfg.conv_taps,
                                   dtype=cfg.dtype, name="conv")(h)
        elif self.layer_type == "linear_attention":
            mixed = GatedDeltaNetMixer(cfg.delta, cfg.d_model,
                                       dtype=cfg.dtype,
                                       norm_eps=cfg.norm_eps, name="delta")(h)
        elif self.layer_type == "latent_attention":
            mixed = _LatentAttention(cfg, name="mla")(h, positions)
        else:
            mixed = _Attention(cfg, name="attn")(h, positions)
        x = x + _scaled(after("ln1", mixed), res)
        h = before("ln2", x)
        if self.ffn_type == "switch":
            # dropped (over-capacity) tokens contribute zero here and ride
            # the residual connection through unchanged
            h = _MoEFFN(cfg, name="moe")(h)
        elif self.ffn_type == "experts":
            h = _ExpertsFFN(cfg, name="moe")(h)
        elif cfg.mlp == "swiglu":
            # one product for gate and up, as the source's input_linear
            gate, up = jnp.split(nn.Dense(
                2 * cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                name="gate_up")(h), 2, axis=-1)
            h = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                         name="down")(nn.silu(gate) * up)
        elif cfg.mlp == "gelu":
            h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="up")(h)
            h = nn.gelu(h)
            h = nn.Dense(cfg.d_model, dtype=cfg.dtype, name="down")(h)
        else:
            raise ValueError(f"unknown mlp {cfg.mlp}")
        return x + _scaled(after("ln2", h), res)


class TransformerLM(nn.Module):
    """Causal LM.  ``__call__(tokens, train)`` → logits ``[B, T, vocab]``.

    Under sequence sharding (``attn_impl='ring'``), ``tokens`` is this
    rank's contiguous block and global positions are derived from the
    rank's position on the sequence axis.

    With ``cfg.mtp_layers`` the multi-token-prediction module
    (arXiv:2412.19437 eq. 21–25) runs after the blocks: ``h' = W_eh
    [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]`` with ``h_i`` the last block's
    output before the final norm, one more block (the last layer's
    kinds), its own RMSNorm, and the shared head; its logits for the token
    after next are sown under ``mtp`` (``train/lm.py::mtp_loss``).  The
    last row's ``t_{i+1}`` lies past the block: it reads the first token,
    and the loss leaves that row out.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        del train  # no dropout in the base recipe
        cfg = self.cfg
        if cfg.moe_experts > 0 and cfg.moe_every < 1:
            raise ValueError("moe_every must be >= 1 when moe_experts > 0")
        cfg.check_pattern()
        b, t = tokens.shape
        if cfg.attn_impl in ("ring", "ring_flash"):
            offset = lax.axis_index(cfg.seq_axis) * t
        else:
            offset = 0
        positions = offset + jnp.arange(t)

        embed = nn.Embed(cfg.vocab_size, cfg.d_model,
                         embedding_init=nn.initializers.normal(0.02),
                         dtype=cfg.dtype, name="embed")
        x = _scaled(embed(tokens), cfg.embedding_multiplier)
        block = _Block
        if cfg.remat:
            block = nn.remat(_Block)
        for i in range(cfg.n_layers):
            x = block(cfg, ffn_type=cfg.ffn_type(i),
                      layer_type=cfg.layer_type(i),
                      name=f"block_{i}")(x, positions)
        head = embed.attend if cfg.tie_embeddings else nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype, name="lm_head")

        def logits_of(h):
            # the loss (train/lm.py::lm_loss) carries the same scope:
            # between them they hold every pass over an array of the
            # logits' size
            with jax.named_scope(names.SCOPE_LM_HEAD):
                return _scaled(jnp.asarray(head(h), jnp.float32),
                               1.0 / cfg.logits_scaling)

        if cfg.mtp_layers:
            last = cfg.n_layers - 1
            with jax.named_scope(names.SCOPE_MTP):
                ahead = _scaled(embed(jnp.roll(tokens, -1, axis=1)),
                                cfg.embedding_multiplier)
                h = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                             name="eh_proj")(jnp.concatenate(
                    [_norm(cfg, "mtp_enorm")(ahead),
                     _norm(cfg, "mtp_hnorm")(x)], axis=-1))
                h = block(cfg, ffn_type=cfg.ffn_type(last),
                          layer_type=cfg.layer_type(last),
                          name="mtp_block")(h, positions)
                h = _norm(cfg, "mtp_norm")(h)
            self.sow("mtp", "logits", logits_of(h))
        return logits_of(_norm(cfg, "ln_f")(x))
