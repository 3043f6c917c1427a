"""The grouped matrix products of the top-k expert layer
(ops/grouped_matmul.py): the Pallas kernels, interpreted on the CPU,
against ``lax.ragged_dot`` — values, the rows' gradient and the blocks'
gradient — over splits that put two groups in one row tile, leave groups
empty, use every row or none; and the rule that picks them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from stochastic_gradient_push_tpu.ops import grouped_matmul as gm

M, K, N, G = 1024, 256, 384, 5


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Eight row tiles of 128 at a toy size (the chip's are 512)."""
    monkeypatch.setattr(gm, "ROW_TILE", 128)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("sizes", [
    [100, 0, 300, 28, 200],       # shared tiles, an empty group, a tail
    [0, 0, 1024, 0, 0],           # one group holds every row
    [128, 128, 128, 128, 128],    # groups on tile boundaries
    [1, 2, 3, 4, 5],              # five groups in one tile
    [0, 0, 0, 0, 0],              # no row held: no tile entered
    [500, 524, 0, 0, 0],          # every row used, groups empty behind
], ids=["uneven", "one_group", "aligned", "one_tile", "none", "full"])
def test_kernels_are_the_ragged_product(sizes):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (M, K))
    w = jax.random.normal(k2, (G, K, N)) * 0.1
    s = jnp.asarray(sizes, jnp.int32)
    held = (jnp.arange(M) < s.sum())[:, None]
    d_out = jax.random.normal(k3, (M, N)) * held

    def through(dot):
        def loss(x, w):
            out = jnp.where(held, dot(x, w), 0)    # as the layer masks it
            return jnp.sum(out * d_out), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            x, w)
        return out, jnp.where(held, grads[0], 0), grads[1]

    ours = through(lambda x, w: gm.grouped_matmul(x, w, s, True))
    theirs = through(lambda x, w: lax.ragged_dot(x, w, s))
    for got, want, what in zip(ours, theirs, ("out", "d rows", "d blocks")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5,
            atol=1e-5 * max(1.0, float(jnp.abs(want).max())), err_msg=what)
    # an empty group's gradient is written, as zero
    for e, size in enumerate(sizes):
        if size == 0:
            assert float(jnp.abs(ours[2][e]).max()) == 0.0


@pytest.mark.parametrize("sizes", [
    [256, 256, 0, 0, 0],          # empty groups visit a tile never written
    [100, 0, 300, 28, 200],       # the last held tile is half garbage
    [0, 0, 0, 0, 0],
], ids=["trailing_empty_on_a_boundary", "uneven", "none"])
def test_what_lies_past_the_held_rows_reaches_no_result(sizes):
    """Rows past the last group are written by no product: whatever is in
    memory there, NaN here, in the rows and in the output's gradient
    alike.  None of it reaches a held row or a block's gradient, an empty
    group's included (its one visit lands on the first tile past the
    held rows)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k1, (M, K))
    w = jax.random.normal(k2, (G, K, N)) * 0.1
    d_out = jax.random.normal(k3, (M, N))
    s = jnp.asarray(sizes, jnp.int32)
    held = (jnp.arange(M) < s.sum())[:, None]
    dirty = lambda a: jnp.where(held, a, jnp.nan)
    clean = lambda a: jnp.where(held, a, 0)
    want_out, vjp = jax.vjp(lambda x, w: lax.ragged_dot(x, w, s),
                            clean(x), w)
    want_rows, want_blocks = vjp(clean(d_out))
    got = (clean(gm._gmm(dirty(x), w, s, interpret=True)),
           clean(gm._gmm(dirty(d_out), w, s, transpose_rhs=True,
                         interpret=True)),
           gm._tgmm(dirty(x), dirty(d_out), s, interpret=True))
    for a, b, what in zip(got, (want_out, clean(want_rows), want_blocks),
                          ("out", "d rows", "d blocks")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5,
            atol=1e-5 * max(1.0, float(jnp.abs(b).max())), err_msg=what)


def test_the_visits_walk_each_groups_tiles_in_row_order():
    sizes = jnp.asarray([100, 0, 300, 28, 200], jnp.int32)
    offsets, groups, tiles, visits = gm._visits(sizes, 1024, 128, False)
    assert offsets.tolist() == [0, 100, 100, 400, 428, 628]
    # rows 0-99 | 100-399 | 400-427 | 428-627 over tiles of 128
    assert int(visits) == 1 + 4 + 1 + 2
    assert groups[:8].tolist() == [0, 2, 2, 2, 2, 3, 4, 4]
    assert tiles[:8].tolist() == [0, 0, 1, 2, 3, 3, 3, 4]
    _, groups, tiles, visits = gm._visits(sizes, 1024, 128, True)
    assert int(visits) == 9 and groups[:3].tolist() == [0, 1, 2]


def test_the_rule_takes_the_kernels_on_a_tpu_at_whole_tiles_only(
        monkeypatch):
    monkeypatch.setattr(gm, "ROW_TILE", 512)
    shape = lambda s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(s, dtype)
    rows, blocks = shape((32768, 2048)), shape((16, 2048, 3584))
    assert gm.kernel_fits("tpu", rows, blocks)
    assert not gm.kernel_fits("cpu", rows, blocks)
    assert not gm.kernel_fits("tpu", shape((192, 2048)), blocks)
    assert not gm.kernel_fits("tpu", shape((1024, 32)), shape((4, 32, 48)))
    assert not gm.kernel_fits("tpu", shape((32768, 2048), jnp.float32),
                              blocks)
    # off the rule, the same call is lax.ragged_dot
    x = jnp.ones((8, 4))
    w = jnp.ones((2, 4, 3))
    s = jnp.asarray([3, 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gm.grouped_dot(x, w, s)),
        np.asarray(lax.ragged_dot(x, w, s)))

