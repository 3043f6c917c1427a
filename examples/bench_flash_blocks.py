"""Asymmetric (block_q, block_k) sweep for the Pallas flash kernels.

The round-4 capture showed symmetric block 512 beating both block 128 and
XLA for the backward at t in {2048, 4096}; this finer sweep (run on the
real chip) covers asymmetric combinations, t=1024, and the non-causal
case, and is the data source for the auto block-size rule in
ops/flash_attention.py.

Usage (on the chip): PYTHONPATH=. python examples/bench_flash_blocks.py
"""

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np

from stochastic_gradient_push_tpu.ops.flash_attention import flash_attention
from stochastic_gradient_push_tpu.utils.profiling import fenced_ms

STEPS = 10


def timed(fn, *args):
    # fenced by a host readback; an unfenced earlier sweep reported
    # 0.02 ms for a 26 ms kernel
    # (docs/tpu_runs/20260731T062828_mfu/flashblocks.txt is that garbage)
    return fenced_ms(fn, *args, steps=STEPS)


def sweep(b, h, t, d, causal):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, t, d)) * 0.5,
                           jnp.bfloat16) for _ in range(3))
    best = {}
    for bq, bk in itertools.product((128, 256, 512), repeat=2):
        if t % bq or t % bk:
            continue

        def loss(q, k, v, bq=bq, bk=bk):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           block_q=bq, block_k=bk)
                           .astype(jnp.float32) ** 2)

        fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk))
        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        try:
            r = {"t": t, "causal": causal, "bq": bq, "bk": bk,
                 "fwd_ms": round(timed(fwd, q, k, v), 3),
                 "bwd_ms": round(timed(bwd, q, k, v), 3)}
        except Exception as e:
            r = {"t": t, "causal": causal, "bq": bq, "bk": bk,
                 "error": repr(e)[:160]}
        print(json.dumps(r), flush=True)
        if "fwd_ms" in r:
            for key in ("fwd_ms", "bwd_ms"):
                if key not in best or r[key] < best[key][0]:
                    best[key] = (r[key], bq, bk)
    print(json.dumps({"t": t, "causal": causal, "best": {
        k: {"ms": v[0], "bq": v[1], "bk": v[2]} for k, v in best.items()}}),
        flush=True)


if __name__ == "__main__":
    print(f"backend: {jax.default_backend()} "
          f"({jax.devices()[0].device_kind})", flush=True)
    assert jax.default_backend() == "tpu", "needs the real chip"
    for t in (1024, 2048, 4096):
        sweep(4, 8, t, 64, causal=True)
    sweep(4, 8, 2048, 64, causal=False)
