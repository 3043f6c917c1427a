"""Real-TPU transformer-LM benchmark: SGP train-step tokens/sec + MFU.

The image headline bench (bench.py) covers ResNet-50; this drives the
transformer family — the TPU-native extension the reference lacks — on one
chip: full SGP train step (fwd, bwd, torch-semantics SGD, push-sum round)
over a decoder-only LM with the Pallas flash-attention kernels, bf16
compute.  Emits one JSON line per config.

Usage (needs the real chip): PYTHONPATH=. python examples/bench_lm_tpu.py
Env knobs: LMBENCH_STEPS, LMBENCH_CONFIGS ("d_model,layers,heads,seq,batch;..").
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.models import (TransformerConfig,
                                                 TransformerLM)
from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS, \
    make_gossip_mesh
from stochastic_gradient_push_tpu.topology import (
    NPeerDynamicDirectedExponentialGraph, build_schedule)
from stochastic_gradient_push_tpu.train import LRSchedule, sgd
from stochastic_gradient_push_tpu.train.lm import (build_lm_train_step,
                                                   init_lm_state,
                                                   shard_lm_train_step,
                                                   shard_scanned_lm_step)

STEPS = int(os.environ.get("LMBENCH_STEPS", "20"))
SCAN = int(os.environ.get("LMBENCH_SCAN", "4"))
# override the flash/blockwise attention block size (None = the
# default_block auto rule) — the t1024 block A/B for docs/LM_MFU.md
BLOCK = int(os.environ.get("LMBENCH_BLOCK", "0")) or None
# flash K/V-side block override (None = symmetric with BLOCK)
BLOCK_K = int(os.environ.get("LMBENCH_BLOCK_K", "0")) or None

# (d_model, n_layers, n_heads, seq_len, batch) — a ~125M GPT-small-shaped
# config and a long-context variant
DEFAULT_CONFIGS = [
    (768, 12, 12, 1024, 8),
    (768, 12, 12, 2048, 4),
    (512, 8, 8, 4096, 2),
]


def parse_configs():
    raw = os.environ.get("LMBENCH_CONFIGS")
    if not raw:
        return DEFAULT_CONFIGS
    out = []
    for part in raw.split(";"):
        d, l, h, t, b = (int(x) for x in part.split(","))
        out.append((d, l, h, t, b))
    return out


def peak_tflops(kind: str) -> float | None:
    import bench
    return bench.peak_tflops(kind)


def run(d_model, n_layers, n_heads, seq, batch, vocab=32000,
        attn="flash", moe_experts=0):
    world = jax.device_count()
    mesh = make_gossip_mesh(world)
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, d_ff=4 * d_model, max_len=seq,
        dtype=jnp.bfloat16, attn_impl=attn,
        attn_block_size=BLOCK, attn_block_k=BLOCK_K,
        moe_experts=moe_experts)
    model = TransformerLM(cfg)
    alg = sgp(build_schedule(NPeerDynamicDirectedExponentialGraph(
        world, peers_per_itr=1) if world > 1 else
        NPeerDynamicDirectedExponentialGraph(1)), GOSSIP_AXIS)
    tx = sgd(momentum=0.9, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=3e-2, batch_size=batch, world_size=world,
                     decay_schedule={}, warmup=False)
    step = build_lm_train_step(model, alg, tx, lrs, itr_per_epoch=1000,
                               seq_axis=None)
    state = init_lm_state(model, mesh, alg, tx, dp=world, sp=1,
                          batch_size=batch, block_len=seq, seq_axis=None)
    if SCAN > 1:
        train_fn = shard_scanned_lm_step(step, mesh, n_steps=SCAN,
                                         seq_axis=None)
    else:
        train_fn = shard_lm_train_step(step, mesh, seq_axis=None)

    rng = np.random.default_rng(0)
    shape = (world, batch, seq)
    if SCAN > 1:
        shape = (SCAN,) + shape
    toks = rng.integers(0, vocab, size=shape).astype(np.int32)
    tgts = rng.integers(0, vocab, size=shape).astype(np.int32)
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(None, GOSSIP_AXIS) if SCAN > 1 else P(GOSSIP_AXIS)
    sh = NamedSharding(mesh, spec)
    toks = jax.device_put(toks, sh)
    tgts = jax.device_put(tgts, sh)

    flops = None
    try:
        compiled = train_fn.lower(state, toks, tgts).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        f = ca.get("flops")
        flops = float(f) if f and f > 0 else None
        run_fn = compiled
    except Exception:
        run_fn = train_fn

    def call(st, tk, tg):
        # the AOT executable can reject argument shardings on
        # multi-device CPU meshes (its output state shardings need not
        # match its inputs'); fall back to the jit path permanently —
        # it re-infers shardings per call.  1-chip TPU never hits this.
        nonlocal run_fn
        try:
            return run_fn(st, tk, tg)
        except ValueError:
            if run_fn is train_fn:
                raise
            run_fn = train_fn
            return run_fn(st, tk, tg)

    m = None
    for _ in range(3):
        state, m = call(state, toks, tgts)
    loss = float(np.min(np.asarray(jax.device_get(m["loss"]))))
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, m = call(state, toks, tgts)
    loss = float(np.min(np.asarray(jax.device_get(m["loss"]))))
    # one dispatch runs SCAN fused steps; XLA's cost analysis counts the
    # scan body once, so `flops` is already per-iteration (see bench.py)
    time_per_itr = (time.perf_counter() - t0) / (STEPS * SCAN)
    assert np.isfinite(loss), "non-finite loss"

    n_params = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(
        jax.tree.map(lambda a: a[0], state.params)))
    tokens_per_sec = world * batch * seq / time_per_itr
    out = {"config": f"d{d_model} L{n_layers} h{n_heads} t{seq} b{batch}",
           "attn": attn, **({"block": BLOCK} if BLOCK else {}),
           **({"block_k": BLOCK_K} if BLOCK_K else {}),
           "moe_experts": moe_experts,
           "params_m": round(n_params / 1e6, 1), "scan": SCAN,
           "tokens_per_sec_per_chip": round(tokens_per_sec / world),
           "step_ms": round(time_per_itr * 1e3, 2), "loss": round(loss, 3)}
    peak = peak_tflops(jax.devices()[0].device_kind)
    if flops and peak:
        out["mfu"] = round(flops / time_per_itr / (peak * 1e12 * world), 4)
        # 6·N·T rule-of-thumb for comparison with the XLA-counted number
        # (dense only: top-1 routing activates ~1/E of MoE FFN params,
        # so total-N would overstate model FLOPs several-fold)
        if moe_experts == 0:
            out["mfu_6nd"] = round(
                6 * n_params * batch * seq / time_per_itr / (peak * 1e12),
                4)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    backend = jax.default_backend()
    print(f"backend: {backend} ({jax.devices()[0].device_kind})",
          flush=True)
    assert backend == "tpu", "needs the real chip"
    # Order: every config's flash number first, then MoE, then the
    # redundant blockwise comparisons (bench_flash_tpu.py already
    # isolates flash-vs-XLA at the kernel level, so blockwise full-step
    # numbers are corroboration, not primary evidence).  A config that
    # fails raises: there is no retry and no error row.
    configs = parse_configs()
    for cfg in configs:
        run(*cfg, attn="flash")
    # MoE throughput on one chip: the full switch dispatch (router,
    # capacity slots, dispatch/combine einsums) with all experts local —
    # the ep>1 meshes need multiple devices, but the routing machinery's
    # cost is visible here (VERDICT r3 item 1c, single-chip variant)
    run(768, 12, 12, 1024, 8, attn="flash", moe_experts=8)
    for cfg in configs:
        run(*cfg, attn="blockwise")
