"""The chunked gated delta rule (ops/delta_rule.py) against the rule token
by token, forward and ``jax.grad``: lengths a chunk does not divide, one
chunk, writes with ``beta`` above 1, decays near 0 and near 1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.olmo_hybrid import delta_rule_recurrence
from stochastic_gradient_push_tpu.ops.delta_rule import delta_rule_chunked

B, H, K, V = 2, 3, 8, 12


def token_by_token(q, k, v, log_alpha, beta):
    """The plain reference's rule, one step after another, float32."""
    return delta_rule_recurrence(q, k, v, jnp.exp(log_alpha), beta)


def inputs(t, seed=0, decay=(0.0, 3.0), beta_max=2.0):
    """L2-normalised q and k (the mixer's), ``-log alpha`` uniform in
    ``decay``, ``beta`` uniform in (0, ``beta_max``)."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True))
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return (f32(unit(r.normal(size=(B, t, H, K)))),
            f32(unit(r.normal(size=(B, t, H, K)))),
            f32(r.normal(size=(B, t, H, V))),
            f32(-r.uniform(*decay, size=(B, t, H))),
            f32(r.uniform(0.0, beta_max, size=(B, t, H))))


CASES = {
    # T, chunk, -log alpha range
    "length_not_a_multiple": (37, 8, (0.0, 3.0)),
    "one_chunk": (20, 64, (0.0, 3.0)),
    "exact_chunks": (32, 8, (0.0, 3.0)),
    "last_chunk_of_one": (17, 8, (0.0, 3.0)),
    "alpha_near_zero": (24, 8, (20.0, 60.0)),
    "alpha_near_one": (24, 8, (0.0, 1e-3)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_is_the_rule_token_by_token(case):
    t, chunk, decay = CASES[case]
    args = inputs(t, decay=decay)
    assert float(args[4].max()) > 1.5         # writes that overshoot
    with jax.default_matmul_precision("highest"):
        got = delta_rule_chunked(*args, chunk)
        want = token_by_token(*args)
    assert got.shape == (B, t, H, V) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_gradient_is_the_rules(case):
    t, chunk, decay = CASES[case]
    args = inputs(t, seed=1, decay=decay)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=(B, t, H, V)),
                        jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: (delta_rule_chunked(*a, chunk)
                                   * probe).sum(), argnums=range(5))(*args)
        want = jax.grad(lambda *a: (token_by_token(*a) * probe).sum(),
                        argnums=range(5))(*args)
    # on the scale of the largest gradient: where alpha is near 0 the
    # decay's own gradient is a product with alpha, 1e-10 and below
    scale = max(float(jnp.abs(w).max()) for w in want)
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"), got, want):
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5 * scale, err_msg=name)


def test_a_chunk_length_does_not_change_the_result():
    args = inputs(48, seed=3)
    with jax.default_matmul_precision("highest"):
        outs = [np.asarray(delta_rule_chunked(*args, c))
                for c in (4, 16, 48)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5)


def test_bf16_operands_stay_near_float32():
    args = inputs(64, seed=4)
    with jax.default_matmul_precision("highest"):
        exact = token_by_token(*args)
        rounded = delta_rule_chunked(*args, 16, operand_dtype=jnp.bfloat16)
    error = float(jnp.abs(rounded - exact).max() / jnp.abs(exact).max())
    assert 1e-4 < error < 3e-2


def test_padding_steps_leave_the_state_alone():
    """The steps a short last chunk is padded with write nothing and decay
    nothing: the outputs before them are those of the shorter sequence."""
    args = inputs(21, seed=5)
    with jax.default_matmul_precision("highest"):
        short = delta_rule_chunked(*(a[:, :13] for a in args), 8)
        whole = delta_rule_chunked(*args, 8)
    np.testing.assert_allclose(np.asarray(short), np.asarray(whole[:, :13]),
                               atol=1e-5)
