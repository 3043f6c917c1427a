"""Ring attention == full attention, sharded over a sequence mesh axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.parallel.ring_attention import (
    blockwise_attention,
    ring_attention,
)

WORLD = 8
B, H, T, D = 2, 4, 64, 16  # T across all ranks; block = T // WORLD


def full_attention(q, k, v, causal=False):
    s = np.einsum("bhqd,bhkd->bhqk", q.astype(np.float64),
                  k.astype(np.float64)) * (D ** -0.5)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v.astype(np.float64))


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    return [rng.normal(size=(B, H, T, D)).astype(np.float32)
            for _ in range(3)]


@pytest.fixture(scope="module")
def mesh():
    from stochastic_gradient_push_tpu.parallel import make_gossip_mesh
    return make_gossip_mesh(WORLD)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(mesh, qkv, causal):
    q, k, v = qkv
    block = T // WORLD

    def shard_seq(x):
        # [B,H,T,D] → [WORLD, B, H, block, D] (contiguous block layout)
        return np.moveaxis(
            x.reshape(B, H, WORLD, block, D), 2, 0).copy()

    def f(qb, kb, vb):
        return ring_attention(qb[0], kb[0], vb[0], "gossip",
                              causal=causal)[None]

    sharded = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("gossip"), P("gossip"), P("gossip")),
        out_specs=P("gossip")))
    out_blocks = np.asarray(sharded(shard_seq(q), shard_seq(k),
                                    shard_seq(v)))
    # [WORLD, B, H, block, D] → [B, H, T, D]
    got = np.moveaxis(out_blocks, 0, 2).reshape(B, H, T, D)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [8, 16, 64])
def test_blockwise_attention_matches_full(qkv, causal, block):
    q, k, v = qkv
    got = np.asarray(jax.jit(
        lambda q, k, v: blockwise_attention(q, k, v, block, causal=causal)
    )(q, k, v))
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ring_attention_gradients_flow(mesh, qkv):
    """Differentiability: ring attention participates in backprop."""
    q, k, v = qkv
    block = T // WORLD

    def shard_seq(x):
        return np.moveaxis(x.reshape(B, H, WORLD, block, D), 2, 0).copy()

    def loss_fn(qb, kb, vb):
        out = ring_attention(qb[0], kb[0], vb[0], "gossip", causal=True)
        return jnp.sum(out ** 2)

    def f(qb, kb, vb):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            qb, kb, vb)
        return loss[None], grads

    sharded = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("gossip"), P("gossip"), P("gossip")),
        out_specs=(P("gossip"), (P("gossip"), P("gossip"), P("gossip")))))
    loss, grads = sharded(shard_seq(q), shard_seq(k), shard_seq(v))
    for g in grads:
        g = np.asarray(g)
        assert np.all(np.isfinite(g))
        assert np.abs(g).max() > 0


class TestRingFlash:
    """ring_flash_attention (ops/ring_flash.py): the flash-kernel-tick
    ring — values AND analytic custom-vjp gradients must match full
    attention / autodiff through the reference ring."""

    @staticmethod
    def _shard_seq(x, world=4):
        b, h, t, d = x.shape
        block = t // world
        return np.moveaxis(x.reshape(b, h, world, block, d), 2, 0).copy()

    @staticmethod
    def _unshard(blocks):
        w, b, h, blk, d = blocks.shape
        return np.moveaxis(blocks, 0, 2).reshape(b, h, w * blk, d)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_matches_full_attention(self, qkv, causal, use_pallas):
        from stochastic_gradient_push_tpu.ops.ring_flash import (
            ring_flash_attention)
        from stochastic_gradient_push_tpu.parallel import make_gossip_mesh

        world = 4
        mesh = make_gossip_mesh(world)
        q, k, v = qkv

        def f(qb, kb, vb):
            return ring_flash_attention(
                qb[0], kb[0], vb[0], "gossip", causal=causal, block=8,
                interpret=use_pallas, use_pallas=use_pallas)[None]

        sharded = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P("gossip"),) * 3, out_specs=P("gossip")))
        got = self._unshard(np.asarray(sharded(
            self._shard_seq(q), self._shard_seq(k), self._shard_seq(v))))
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_gradients_match_reference_ring(self, qkv, causal,
                                            use_pallas):
        """The custom-vjp ring backward (global-lse per-tick kernels +
        homeward dk/dv rotation) equals autodiff through the reference
        ring implementation."""
        from stochastic_gradient_push_tpu.ops.ring_flash import (
            ring_flash_attention)
        from stochastic_gradient_push_tpu.parallel import make_gossip_mesh

        world = 4
        mesh = make_gossip_mesh(world)
        q, k, v = qkv

        def loss_flash(qb, kb, vb):
            out = ring_flash_attention(
                qb, kb, vb, "gossip", causal=causal, block=8,
                interpret=use_pallas, use_pallas=use_pallas)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def loss_ref(qb, kb, vb):
            out = ring_attention(qb, kb, vb, "gossip", causal=causal)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def make(loss_fn):
            def f(qb, kb, vb):
                g = jax.grad(loss_fn, argnums=(0, 1, 2))(
                    qb[0], kb[0], vb[0])
                return tuple(x[None] for x in g)
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(P("gossip"),) * 3,
                out_specs=(P("gossip"),) * 3))

        args = (self._shard_seq(q), self._shard_seq(k),
                self._shard_seq(v))
        got = make(loss_flash)(*args)
        want = make(loss_ref)(*args)
        for name, a, b in zip("dq dk dv".split(), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
                err_msg=name)


@pytest.mark.slow
def test_long_context_16x_blocks_trains(tmp_path):
    """Long-context evidence: 8192 tokens over the sp=8 ring_flash mesh
    train end-to-end through the CLI (peak attention memory per device is
    O(block²) in the 1024-token shard, not O(seq²))."""
    import subprocess
    import sys

    from tests.test_run_layer import CLI_ENV

    cmd = [sys.executable, "-m",
           "stochastic_gradient_push_tpu.run.gossip_lm",
           "--world_size", "8", "--sp", "8", "--attn", "ring_flash",
           "--seq_len", "8192", "--d_model", "32", "--n_layers", "1",
           "--n_heads", "4", "--d_ff", "64", "--batch_size", "1",
           "--num_steps", "2", "--corpus_tokens", "100000",
           "--checkpoint_dir", str(tmp_path)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=420,
                       env=CLI_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    assert '"final_loss"' in r.stdout + r.stderr
