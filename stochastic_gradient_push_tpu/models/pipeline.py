"""Pipeline-stage transformer LM: one shard's slice of the layer stack.

Pairs with ``parallel/pipeline.py`` (the tick schedule) and ``train/pp.py``
(mesh/init/step).  Each pipe shard holds

* ``embed`` / ``ln_f`` / ``lm_head`` — replicated over the pipe axis; only
  stage 0 (embed) and the last stage (head) produce live outputs, and their
  gradients are shared with a ``psum`` in the train step;
* ``stack`` — ``n_local_layers`` transformer blocks stacked on a leading
  axis (``nn.scan``), *stage-local*: shard ``s`` holds layers
  ``[s·L/S, (s+1)·L/S)``.  Globally the stacked leaf is sharded over the
  pipe axis, so a gathered checkpoint holds the full ``L``-layer model.

The block itself is the shared ``_Block`` from models/transformer.py —
pipeline parallelism changes the layout, not the math.  Ring attention
composes (pp × sp): the tick's ppermute moves activations over ``pipe``
while each block's ring rotation moves KV over ``seq`` — different manual
axes, both uniform collectives inside the scanned tick body, so they
nest cleanly (tests/test_pipeline.py pins parity with the stacked ring
model).  MoE composes too (``moe_every=1`` so the scanned stack stays
uniform; tokens route per microbatch inside the ticks) — replicated
experts, expert-sharded dispatch over an ``ep`` axis (the all_to_all is
uniform across ticks), per-block routing under ``seq`` sharding, and
the full 4-D pp × ep × sp mesh.  The only constraint left is
structural: MoE requires ``moe_every=1`` (composition matrix,
ARCHITECTURE.md).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from .transformer import TransformerConfig, _Block

__all__ = ["PipelineStageLM"]


class _ScanBlock(nn.Module):
    """Carry-style wrapper so ``nn.scan`` stacks block params on axis 0."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        ffn_type = "switch" if self.cfg.moe_experts > 0 else "dense"
        return _Block(self.cfg, ffn_type=ffn_type,
                      name="block")(x, positions), None


class PipelineStageLM(nn.Module):
    """One pipeline stage of a decoder-only LM.

    ``n_local_layers`` is ``cfg.n_layers // n_stages`` — the model object
    never references the mesh; stage identity comes entirely from which
    parameter values the shard holds (train/pp.py initializes each shard's
    stack with a pipe-index-folded RNG).
    """

    cfg: TransformerConfig
    n_local_layers: int

    def setup(self):
        cfg = self.cfg
        if cfg.layer_types is not None:
            raise ValueError(
                "a layer pattern × pipeline is not built: the stage stack "
                "is one uniform nn.scan of the dense block")
        if cfg.moe_experts > 0 and cfg.moe_every != 1:
            raise ValueError(
                "MoE × pipeline requires moe_every=1: the stage stack is "
                "one uniform nn.scan, so every layer must share the block "
                "structure — see ARCHITECTURE.md composition matrix")
        self.embed = nn.Embed(cfg.vocab_size, cfg.d_model,
                              embedding_init=nn.initializers.normal(0.02),
                              dtype=cfg.dtype)
        target = _ScanBlock
        if cfg.remat:
            target = nn.remat(target, prevent_cse=False)
        # sown MoE collections ("losses"/"moe_metrics") stack per-layer on
        # axis 0 like the params; harmless when nothing is sown
        self.stack = nn.scan(
            target,
            variable_axes={"params": 0, "losses": 0, "moe_metrics": 0},
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            length=self.n_local_layers)(cfg)
        self.ln_f = nn.LayerNorm(dtype=jnp.float32)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=cfg.dtype)

    def embed_tokens(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """``[..., T] -> [..., T, D]`` — applied to all microbatches."""
        return self.embed(tokens)

    def blocks(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        """This stage's slice of the layer stack (the pipeline tick body)."""
        x, _ = self.stack(x, positions)
        return x

    def head(self, x: jnp.ndarray) -> jnp.ndarray:
        """Final LN + logits in fp32."""
        return jnp.asarray(self.lm_head(self.ln_f(x)), jnp.float32)

    def __call__(self, tokens: jnp.ndarray, train: bool = True):
        """Init/reference path: embed → local stack → head.

        This is NOT the pipelined forward (that lives in train/pp.py —
        it interleaves ``blocks`` with ``ppermute``); calling it exercises
        every parameter group once so ``init`` builds the full tree.
        """
        del train
        tokens = tokens.reshape(-1, tokens.shape[-1])  # merge microbatch dims
        positions = jnp.arange(tokens.shape[-1])
        if self.cfg.seq_axis is not None:
            # ring attention: this shard holds one contiguous block; its
            # global positions start at the block offset
            from jax import lax
            positions = positions + lax.axis_index(
                self.cfg.seq_axis) * tokens.shape[-1]
        x = self.embed_tokens(tokens)
        x = self.blocks(x, positions)
        return self.head(x)
