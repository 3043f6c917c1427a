"""Transformer-step MFU decomposition at the LM bench's flagship config
(d768/L12/h12/t1024/b8, vocab 32k, bf16) — the LM counterpart of
docs/MFU_ANALYSIS.md's ResNet roofline.

Round-4 measured 21.6 % MFU at t1024 vs 35.3 % at t2048 with the SAME
token count — so the attention isn't the bottleneck at t1024; something
that doesn't scale with t² dominates.  This probe attributes the step by
measuring, each as its own jitted program (fwd and fwd+bwd, amortized
over STEPS dispatches):

  full      — the complete train-relevant fwd(+bwd) (model apply + CE)
  embed+head— the same model with n_layers=0 (embed -> LN -> 32k-wide
              head -> lean CE): the vocab path, whose logits tensor
              [b, t, 32k] is the single largest activation in the step
  attn x12  — the flash kernel at the exact per-layer shapes
  ffn  x12  — the two [b*t, d] x [d, 4d] matmul chains

``blocks = full - embed+head`` cross-checks ``12*(attn + ffn)``; the
remainder is QKV/proj matmuls, layernorms and residual traffic.
Roofline predictions from public v5e specs print beside each
measurement.  Run on the chip:
``PYTHONPATH=. python examples/bench_lm_phases.py``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from stochastic_gradient_push_tpu.models import (TransformerConfig,
                                                 TransformerLM)
from stochastic_gradient_push_tpu.ops.flash_attention import (
    default_block, flash_attention)
from stochastic_gradient_push_tpu.train.lm import lm_loss
from stochastic_gradient_push_tpu.utils.profiling import fenced_ms

D, L, H, T, B, VOCAB = 768, 12, 12, 1024, 8, 32000
STEPS = int(os.environ.get("LMBENCH_STEPS", "20"))
PEAK_TFLOPS = 197.0  # v5e dense bf16
HBM_GBPS = 819.0


def timeit(fn, *args):
    # fenced (host readback) timing
    return fenced_ms(fn, *args, steps=STEPS)


def model_ms(n_layers):
    cfg = TransformerConfig(vocab_size=VOCAB, d_model=D, n_layers=n_layers,
                            n_heads=H, d_ff=4 * D, max_len=T,
                            dtype=jnp.bfloat16, attn_impl="flash")
    model = TransformerLM(cfg)
    tokens = jnp.zeros((B, T), jnp.int32)
    targets = jnp.ones((B, T), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens, train=True)

    def loss_fn(p):
        logits = model.apply(p, tokens, train=True)
        return lm_loss(logits, targets)

    fwd = timeit(jax.jit(loss_fn), params)
    bwd = timeit(jax.jit(jax.grad(loss_fn)), params)
    return fwd, bwd


def attn_ms():
    dh = D // H
    blk = default_block(T)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, T, dh),
                          jnp.bfloat16)

    def one(q):
        return flash_attention(q, q, q, causal=True, block_q=blk,
                               block_k=blk)

    def loss(q):
        return jnp.sum(jnp.square(one(q)))

    return timeit(jax.jit(one), q), timeit(jax.jit(jax.grad(loss)), q), blk


def ffn_ms():
    x = jax.random.normal(jax.random.PRNGKey(0), (B * T, D), jnp.bfloat16)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (D, 4 * D),
                           jnp.bfloat16) * 0.02
    w2 = jax.random.normal(jax.random.PRNGKey(2), (4 * D, D),
                           jnp.bfloat16) * 0.02

    def one(x, w1, w2):
        return jax.nn.gelu(x @ w1) @ w2

    def loss(x, w1, w2):
        return jnp.sum(jnp.square(one(x, w1, w2)))

    return (timeit(jax.jit(one), x, w1, w2),
            timeit(jax.jit(jax.grad(loss, argnums=(1, 2))), x, w1, w2))


def main():
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    tokens = B * T

    # roofline: per-phase FLOPs (fwd; train ~ 3x) and dominant traffic
    ffn_flops = 2 * tokens * D * 4 * D * 2            # two matmuls
    qkvo_flops = 2 * tokens * D * D * 4               # q,k,v,o projections
    attn_flops = 4 * B * T * T * D / 2                # causal: half the pairs
    head_flops = 2 * tokens * D * VOCAB
    logits_bytes = tokens * VOCAB * 2                 # bf16 logits tensor
    print(json.dumps({
        "roofline_fwd_ms": {
            "ffn_x12": round(12 * ffn_flops / PEAK_TFLOPS / 1e9, 3),
            "qkvo_x12": round(12 * qkvo_flops / PEAK_TFLOPS / 1e9, 3),
            "attn_x12": round(12 * attn_flops / PEAK_TFLOPS / 1e9, 3),
            "head": round(head_flops / PEAK_TFLOPS / 1e9, 3),
            "logits_traffic": round(logits_bytes / HBM_GBPS / 1e6, 3),
        }}), flush=True)

    full_f, full_b = model_ms(L)
    eh_f, eh_b = model_ms(0)
    at_f, at_b, blk = attn_ms()
    ff_f, ff_b = ffn_ms()
    print(json.dumps({
        "config": f"d{D} L{L} h{H} t{T} b{B} v{VOCAB} blk{blk}",
        "full_fwd_ms": round(full_f, 3), "full_fwdbwd_ms": round(full_b, 3),
        "embed_head_fwd_ms": round(eh_f, 3),
        "embed_head_fwdbwd_ms": round(eh_b, 3),
        "blocks_fwd_ms": round(full_f - eh_f, 3),
        "blocks_fwdbwd_ms": round(full_b - eh_b, 3),
        "attn_x12_fwd_ms": round(12 * at_f, 3),
        "attn_x12_fwdbwd_ms": round(12 * at_b, 3),
        "ffn_x12_fwd_ms": round(12 * ff_f, 3),
        "ffn_x12_fwdbwd_ms": round(12 * ff_b, 3),
    }), flush=True)


if __name__ == "__main__":
    main()
