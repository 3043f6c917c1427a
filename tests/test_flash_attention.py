"""Pallas flash-attention kernel vs the pure-JAX oracle (interpret mode on
CPU; the same kernel compiles for real on TPU)."""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stochastic_gradient_push_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_backward,
    flash_attention_forward,
    fused_backward_fits,
)
from stochastic_gradient_push_tpu.telemetry import names
from stochastic_gradient_push_tpu.parallel.ring_attention import (
    blockwise_attention,
)

# the module itself: the package attribute of that name is the function
fa = importlib.import_module(
    "stochastic_gradient_push_tpu.ops.flash_attention")

B, H, T, D = 2, 2, 64, 16


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(7)
    return [jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
            for _ in range(3)]


@pytest.fixture(params=["fused", "pair"])
def backward(request, monkeypatch):
    """Both sides of the backward's shape rule at the tests' small
    shapes: as the rule picks (fused), and with no VMEM budget at all
    (the dq + dk/dv pair)."""
    if request.param == "pair":
        monkeypatch.setattr(fa, "FUSED_BWD_VMEM_BUDGET", 0)
    assert fused_backward_fits(T, D) == (request.param == "fused")
    return request.param


def _backward_calls(shape, dtype, d_v=None):
    """The Pallas calls ``flash_attention_backward`` makes at ``shape``
    (values ``d_v`` wide, or as wide as ``shape``'s heads), traced, not
    run: their jaxpr equations."""
    x = jax.ShapeDtypeStruct(shape, dtype)
    v = jax.ShapeDtypeStruct(shape[:3] + (d_v or shape[3],), dtype)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
    block = fa.default_block(shape[2])
    jaxpr = jax.make_jaxpr(lambda q, k, v, out, lse, do: (
        flash_attention_backward(q, k, v, out, lse, do, causal=True,
                                 block_q=block, block_k=block)))(
        x, x, v, v, lse, v)
    return [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]


def _backward_kernels(shape, dtype):
    """Names of the Pallas kernels ``flash_attention_backward`` calls at
    ``shape`` (traced, not run)."""
    return [e.params["name"] for e in _backward_calls(shape, dtype)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_flash_kernel_matches_blockwise(qkv, causal, block):
    q, k, v = qkv
    got = flash_attention_forward(q, k, v, causal=causal, block_q=block,
                                  block_k=block, interpret=True)
    want = blockwise_attention(q, k, v, block, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_wide_head_dim():
    """head_dim 128 (v5e lane width) through forward AND backward: the
    production LM shapes use d=64; this pins the d=128 layouts the
    kernels' scratch/accumulators must also support."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 64, 128)), jnp.float32)
               for _ in range(3))
    got = flash_attention_forward(q, k, v, causal=True, block_q=32,
                                  block_k=32, interpret=True)
    want = blockwise_attention(q, k, v, 32, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    out, lse = flash_attention_forward(q, k, v, causal=True, block_q=32,
                                       block_k=32, interpret=True,
                                       return_lse=True)
    do = jnp.asarray(rng.normal(size=out.shape), jnp.float32)
    dq, dk, dv = flash_attention_backward(q, k, v, out, lse, do,
                                          causal=True, block_q=32,
                                          block_k=32, interpret=True)

    def loss(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, 32, causal=True) * do)

    wq, wk, wv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in ((dq, wq), (dk, wk), (dv, wv)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_default_block_rule():
    from stochastic_gradient_push_tpu.ops.flash_attention import (
        default_block)

    # largest tiling block wins at every measured length (the round-5
    # step-level A/B: t1024 block 512 is 2.0x block 128)
    assert default_block(64) == 64
    assert default_block(1024) == 512
    assert default_block(2048) == 512
    assert default_block(4096) == 512
    assert default_block(1024 + 256) == 256  # not divisible by 512
    assert default_block(2048 + 128) == 128  # only 128 tiles it


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (32, 16)])
def test_flash_kernel_mixed_block_sizes(qkv, block_q, block_k):
    """Both aspect ratios exercise the causal visit list at unequal
    blocks (a wrong floor in either direction leaves a visible tile out
    or reads a masked one)."""
    q, k, v = qkv
    got = flash_attention_forward(q, k, v, causal=True, block_q=block_q,
                                  block_k=block_k, interpret=True)
    want = blockwise_attention(q, k, v, 16, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_gradient_matches_blockwise(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, 16, causal=True) ** 2)

    # on CPU flash_attention falls back to blockwise; gradients must agree
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_kernel_bf16(qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    got = flash_attention_forward(q, k, v, causal=True, block_q=32,
                                  block_k=32, interpret=True)
    want = blockwise_attention(q, k, v, 32, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [16, 32])
def test_flash_backward_kernels_match_oracle(qkv, causal, block, backward):
    q, k, v = qkv
    out, lse = flash_attention_forward(q, k, v, causal=causal,
                                       block_q=block, block_k=block,
                                       interpret=True, return_lse=True)
    rng = np.random.default_rng(3)
    do = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    dq, dk, dv = flash_attention_backward(
        q, k, v, out, lse, do, causal=causal, block_q=block,
        block_k=block, interpret=True)
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, block, causal=causal),
        q, k, v)
    for got, want in zip((dq, dk, dv), vjp(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (32, 16)])
def test_flash_backward_mixed_block_sizes(qkv, block_q, block_k, backward):
    q, k, v = qkv
    out, lse = flash_attention_forward(q, k, v, causal=True,
                                       block_q=block_q, block_k=block_k,
                                       interpret=True, return_lse=True)
    rng = np.random.default_rng(4)
    do = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    dq, dk, dv = flash_attention_backward(
        q, k, v, out, lse, do, causal=True, block_q=block_q,
        block_k=block_k, interpret=True)
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, 16, causal=True),
        q, k, v)
    for got, want in zip((dq, dk, dv), vjp(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_forward_lse_matches_reference(qkv):
    q, k, v = qkv
    _, lse = flash_attention_forward(q, k, v, causal=False, block_q=32,
                                     block_k=32, interpret=True,
                                     return_lse=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
    want = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(16, 16), (64, 64), (16, 32),
                                             (32, 16)])
def test_fused_backward_equals_the_pair(qkv, dtype, causal, block_q,
                                        block_k):
    """The fused kernel is the pair's arithmetic in the pair's order (dq
    over k-blocks ascending, dk/dv over q-blocks ascending): the same
    bits, whether dq accumulates in scratch (bf16) or in its own block
    (fp32)."""
    q, k, v = (x.astype(dtype) for x in qkv)
    out, lse = flash_attention_forward(q, k, v, causal=causal,
                                       block_q=block_q, block_k=block_k,
                                       interpret=True, return_lse=True)
    rng = np.random.default_rng(5)
    do = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype)
    flat = [x.reshape(B * H, T, -1) for x in (q, k, v, do)]
    rows = [lse.reshape(B * H, T, 1),
            jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    -1).reshape(B * H, T, 1)]
    fused = fa._backward_fused(*flat, *rows, causal, block_q, block_k, True)
    pair = fa._backward_pair(*flat, *rows, causal, block_q, block_k, True)
    for got, want in zip(fused, pair):
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_fused_backward_shape_rule():
    """Fused while dq for the whole sequence fits the VMEM budget, the
    pair beyond; ``t`` and ``d`` decide, nothing else."""
    assert fused_backward_fits(T, D)
    assert fused_backward_fits(1024, 64)          # the flagship LM
    assert fused_backward_fits(8192, 64)
    assert fused_backward_fits(8192, 128)
    assert not fused_backward_fits(16384, 64)
    assert not fused_backward_fits(32768, 64)     # a 32k ring shard
    assert fused_backward_fits(1024, 256)         # two registers a row
    assert not fused_backward_fits(8192, 384)     # three, at 8192 tokens
    one, two = [names.KERNEL_FLASH_BWD], [names.KERNEL_FLASH_DQ,
                                          names.KERNEL_FLASH_DKV]
    for dtype in (jnp.bfloat16, jnp.float32):
        for heads in (1, 4):
            assert _backward_kernels((1, heads, 8192, 64), dtype) == one
            assert _backward_kernels((1, heads, 16384, 64), dtype) == two
        assert _backward_kernels((2, 2, 1024, 128), dtype) == one
        assert _backward_kernels((2, 2, 1024, 256), dtype) == one
        assert _backward_kernels((1, 2, 8192, 384), dtype) == two


@pytest.mark.parametrize("t,d_qk,d_v,block,dtype,fits", [
    (8192, 192, 128, 512, jnp.bfloat16, True),     # joyai_sgp_w1_t8192
    (8192, 192, 128, 512, jnp.float32, True),
    (8192, 192, 128, 256, jnp.bfloat16, True),
    (12288, 192, 128, 512, jnp.bfloat16, True),    # 32 MiB reckoned
    (16384, 192, 128, 512, jnp.bfloat16, False),   # longer: the pair
    (8192, 256, 256, 512, jnp.float32, True),
    (8192, 384, 128, 512, jnp.bfloat16, False),    # wider: the pair
    (1024, 1024, 1024, 512, jnp.bfloat16, True),
    (1024, 1024, 1024, 512, jnp.float32, False),
], ids=["cell", "cell_fp32", "cell_b256", "t12288", "t16384", "d256_fp32",
        "d384", "d1024", "d1024_fp32"])
def test_fused_backward_shape_rule_at_two_widths(t, d_qk, d_v, block,
                                                 dtype, fits):
    """Heads over one register: fused while what the kernel holds fits
    the wider scope it asks for, the pair beyond, on both sides of the
    edge in length and in width; the reckoning is the buffers' bytes
    (every side of it compiled for a described v5e: PERF.md §6)."""
    held = fa.fused_backward_vmem(t, d_qk, d_v, block, block, dtype)
    assert (held <= fa.FUSED_BWD_WIDE_VMEM) == fits
    assert fused_backward_fits(t, d_qk, d_v, block, block, dtype) == fits
    # dq for the whole sequence, eight bytes a lane, is most of it
    assert held > t * 8 * -(-d_qk // 128) * 128


@pytest.mark.parametrize("shape,d_v,limit", [
    ((1, 2, 8192, 64), 64, None),
    ((1, 2, 8192, 128), 128, None),
    ((2, 2, 1024, 64), 64, None),
    ((1, 2, 8192, 192), 128, fa.FUSED_BWD_WIDE_VMEM),
    ((2, 2, 1024, 256), 256, fa.FUSED_BWD_WIDE_VMEM),
    ((1, 2, 16384, 64), 64, None),
    ((1, 2, 16384, 192), 128, None),
], ids=["t8192", "d128", "t1024", "latent", "d256", "t16384_pair",
        "latent_t16384_pair"])
def test_only_wide_heads_ask_for_scoped_vmem(shape, d_v, limit):
    """Every call the one-register rule admits, and the pair at any
    width, leaves the compiler's default scope: the fused kernel at heads
    over one register alone asks for :data:`FUSED_BWD_WIDE_VMEM`."""
    for call in _backward_calls(shape, jnp.bfloat16, d_v):
        params = call.params["compiler_params"]["mosaic_tpu"]
        assert params.vmem_limit_bytes == limit, call.params["name"]
        assert params.dimension_semantics == ("parallel", "arbitrary")


def test_the_benchmark_reads_the_fused_backward_by_its_name():
    """``flash_bwd_ms`` is a pattern on the custom call's name: it must
    match what this module calls its fused kernel, with or without the
    compiler's ``.N``, and none of the other flash kernels."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "layer_metrics", "flash_bwd_ms.json")
    with open(path) as f:
        pattern = re.compile(json.load(f)["params"]["pattern"])
    assert pattern.search(names.KERNEL_FLASH_BWD)
    assert pattern.search(names.KERNEL_FLASH_BWD + ".23")
    for other in (names.KERNEL_FLASH_FWD, names.KERNEL_FLASH_DQ,
                  names.KERNEL_FLASH_DKV, names.KERNEL_FLASH_BWD + "_x.1"):
        assert not pattern.search(other)


# -- one k-sweep crossing every kind of tile ---------------------------------
#
# The forward's running state (the maximum in every lane, the denominator
# as lane partials) is handed from tile to tile; these shapes make one
# call hold tiles wholly under the diagonal, tiles the diagonal crosses
# and tiles above it, so a row's state passes through unmasked and masked
# bodies both, first tile to last.

T4 = 128    # 4 x 4 blocks of 32


@pytest.fixture(scope="module")
def qkv4():
    rng = np.random.default_rng(11)
    return [jnp.asarray(rng.normal(size=(1, H, T4, D)), jnp.float32)
            for _ in range(4)]


def _tile_kinds(t, block_q, block_k):
    """(full, diagonal, skipped) tiles of a causal call, counted on the
    mask itself."""
    mask = np.tril(np.ones((t, t), bool)).reshape(
        t // block_q, block_q, t // block_k, block_k)
    some, every = mask.any((1, 3)), mask.all((1, 3))
    return int(every.sum()), int((some & ~every).sum()), int((~some).sum())


def _lse_oracle(q, k, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (16, 32), (32, 16)])
def test_all_tile_kinds_in_one_call_match_the_oracle(qkv4, dtype, block_q,
                                                     block_k, backward):
    """Forward, ``lse`` and all three gradients where one call holds
    full, diagonal and skipped tiles (at least 4 x 4 blocks), at both
    dtypes, through the fused backward and the pair."""
    full, diagonal, skipped = _tile_kinds(T4, block_q, block_k)
    assert full and diagonal and skipped
    assert T4 // block_q >= 4 and T4 // block_k >= 4
    q, k, v, do = (x.astype(dtype) for x in qkv4)
    out, lse = flash_attention_forward(q, k, v, causal=True,
                                       block_q=block_q, block_k=block_k,
                                       interpret=True, return_lse=True)
    dq, dk, dv = flash_attention_backward(
        q, k, v, out, lse, do, causal=True, block_q=block_q,
        block_k=block_k, interpret=True)
    want, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, 16, causal=True),
        q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert out.dtype == dtype and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_lse_oracle(q, k, True)),
                               rtol=1e-5, atol=1e-5)
    for got, ref in zip((dq, dk, dv), vjp(do)):
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=10 * tol, atol=10 * tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_a_row_that_starts_full_and_ends_on_the_diagonal(qkv, dtype):
    """Two blocks a side: the second q-block's sweep opens on a tile with
    no masked pair and closes on the diagonal's, so its denominator is
    the sum of an unmasked and a masked tile's partials."""
    assert _tile_kinds(T, 32, 32) == (1, 2, 1)
    q, k, v = (x.astype(dtype) for x in qkv)
    out, lse = flash_attention_forward(q, k, v, causal=True, block_q=32,
                                       block_k=32, interpret=True,
                                       return_lse=True)
    want = blockwise_attention(q, k, v, 32, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[:, :, 32:],
                               np.asarray(want, np.float32)[:, :, 32:],
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse)[:, :, 32:],
                               np.asarray(_lse_oracle(q, k, True))[:, :, 32:],
                               rtol=1e-5, atol=1e-5)


# -- the visit lists ---------------------------------------------------------

VISIT_SHAPES = [(8192, 512, 512), (4096, 512, 512), (1024, 512, 512),
                (1024, 256, 512), (1024, 512, 256), (64, 16, 32),
                (64, 32, 16)]


def _tiles_with_a_pair(t, block_q, block_k, causal):
    """``[q-blocks, k-blocks]`` bools, counted on the mask itself."""
    mask = np.tril(np.ones((t, t), bool)) if causal \
        else np.ones((t, t), bool)
    return mask.reshape(t // block_q, block_q, t // block_k,
                        block_k).any((1, 3))


@pytest.mark.parametrize("major", ["q", "k"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t,block_q,block_k", VISIT_SHAPES)
def test_visits_are_the_tiles_the_mask_leaves(t, block_q, block_k, causal,
                                              major):
    """``tile_visits`` lists a tile exactly where the mask leaves it a
    pair, once — at the benchmark's three shapes (136, 36 and 3 visits a
    head where the rectangle has 256, 64 and 4) and at unequal blocks both
    ways —, a row of the major axis after another with the other axis
    ascending to (q-major) or from (k-major) the diagonal, no row without
    a visit; and the kernels' comparison with the neighbouring entry finds
    each row's first and last visit."""
    q_blocks, k_blocks = fa.tile_visits(t, block_q, block_k, causal, major)
    some = _tiles_with_a_pair(t, block_q, block_k, causal)
    nq, nk = some.shape
    # a step's trace asks 72 times: one list a shape, read-only for it
    assert fa.tile_visits(t, block_q, block_k, causal, major)[0] is q_blocks
    for blocks in (q_blocks, k_blocks):
        assert blocks.dtype == np.int32 and blocks.ndim == 1
        assert not blocks.flags.writeable
    n = len(q_blocks)
    assert len(k_blocks) == n == int(some.sum())
    seen = np.zeros_like(some)
    seen[q_blocks, k_blocks] = True
    np.testing.assert_array_equal(seen, some)
    if causal:
        full, diagonal, skipped = _tile_kinds(t, block_q, block_k)
        assert n == full + diagonal == nq * nk - skipped
        if block_q == block_k == 512:
            assert n == nq * (nq + 1) // 2
            assert (t, n, nq * nk) in ((8192, 136, 256), (4096, 36, 64),
                                       (1024, 3, 4))
    else:
        assert n == nq * nk

    rows, steps, of_row = (q_blocks, k_blocks, some) if major == "q" \
        else (k_blocks, q_blocks, some.T)
    # as ``_visit`` decides them in the kernels
    i = np.arange(n)
    first = (i == 0) | (rows[np.maximum(i - 1, 0)] != rows)
    last = (i == n - 1) | (rows[np.minimum(i + 1, n - 1)] != rows)
    np.testing.assert_array_equal(rows[first], np.arange(len(of_row)))
    np.testing.assert_array_equal(rows[last], rows[first])
    assert (np.diff(rows) >= 0).all()
    assert (np.diff(steps)[~last[:-1]] == 1).all()     # no gap in a row
    np.testing.assert_array_equal(steps[first], of_row.argmax(1))
    np.testing.assert_array_equal(
        steps[last], of_row.shape[1] - 1 - of_row[:, ::-1].argmax(1))
    if major == "q":
        assert (steps[first] == 0).all()               # to the diagonal
    else:
        assert (steps[last] == nq - 1).all()           # from the diagonal


def _grids(shape, dtype, causal=True):
    """``{kernel name: grid}`` of the forward's and the backward's Pallas
    calls at ``shape`` and the auto block (traced, not run)."""
    x = jax.ShapeDtypeStruct(shape, dtype)
    lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
    block = fa.default_block(shape[2])

    def both(q, k, v, lse, do):
        out = flash_attention_forward(q, k, v, causal=causal,
                                      block_q=block, block_k=block)
        return flash_attention_backward(q, k, v, out, lse, do,
                                        causal=causal, block_q=block,
                                        block_k=block)

    jaxpr = jax.make_jaxpr(both)(x, x, x, lse, x)
    return {e.params["name"]: tuple(e.params["grid_mapping"].grid)
            for e in jaxpr.eqns if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("shape,visits,rectangle", [
    ((1, 16, 8192, 64), 136, 256),      # gpt2m_sgp_w1_t8192's call
    ((1, 32, 4096, 64), 36, 64),        # the granite and lfm2 cells'
    ((4, 16, 1024, 64), 3, 4),          # gpt2m_sgp_w1_t1024's
    ((1, 4, 16384, 64), 528, 1024),     # beyond the budget: the pair
], ids=["t8192", "t4096", "t1024", "t16384_pair"])
def test_a_causal_call_takes_no_grid_step_without_a_tile(shape, visits,
                                                         rectangle):
    """The counter that says the walk engages is static: every kernel's
    grid is (batch·head, visits), the visits those of the list and fewer
    than the rectangle's steps; with no mask, the whole rectangle."""
    bh = shape[0] * shape[1]
    backward = [names.KERNEL_FLASH_BWD] if fused_backward_fits(
        *shape[2:]) else [names.KERNEL_FLASH_DQ, names.KERNEL_FLASH_DKV]
    grids = _grids(shape, jnp.bfloat16)
    assert grids == {name: (bh, visits)
                     for name in [names.KERNEL_FLASH_FWD] + backward}
    assert visits < rectangle
    assert set(_grids(shape, jnp.bfloat16, causal=False).values()) == {
        (bh, rectangle)}


@pytest.mark.parametrize("cols,width", [(512, 128), (128, 128), (256, 128),
                                        (64, 64), (16, 16), (192, 64)])
def test_lane_partials_sum_to_the_row_sum(cols, width):
    """The denominator's lane partials: one register's lanes wide (or a
    narrower block's own width), their sum over lanes the row sum."""
    p = jnp.asarray(np.random.default_rng(cols).uniform(size=(8, cols)),
                    jnp.float32)
    part = fa._lane_partials(p)
    assert part.shape == (8, width)
    np.testing.assert_allclose(
        np.asarray(part),
        np.asarray(p).reshape(8, cols // width, width).sum(1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(part.sum(-1)),
                               np.asarray(p.sum(-1)), rtol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k,d", [(128, 256, 16), (64, 128, 16),
                                               (32, 32, 256)])
def test_forward_state_wider_than_one_register(causal, block_q, block_k, d):
    """Blocks of two registers' lanes (the running maximum is repeated
    across them, the partials fold two column groups) and a head of two
    (``alpha`` repeated over the accumulator)."""
    rng = np.random.default_rng(13)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 512, d)), jnp.float32)
               for _ in range(3))
    out, lse = flash_attention_forward(q, k, v, causal=causal,
                                       block_q=block_q, block_k=block_k,
                                       interpret=True, return_lse=True)
    want = blockwise_attention(q, k, v, 32, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_lse_oracle(q, k, causal)),
                               rtol=1e-5, atol=1e-5)
