"""The chunked delta rule's Pallas kernel pair (ops/delta_rule.py) on the
CPU in interpret mode, against both of its oracles: the XLA path of
``delta_rule_chunked`` and the step-by-step rule of the benchmark's plain
reference, forward and ``jax.grad`` of all five inputs; the rule that
picks the pair; and, on the chip only, the compiled pair at the Olmo
hybrid cell's sizes (run there with ``python -c "import sys;
sys.path.insert(0, 'tests'); import test_delta_rule_kernel as t;
t.test_compiled_pair_is_the_xla_path_at_the_cells_sizes()"`` from the
repository's root: pytest holds the suite to the CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.olmo_hybrid import delta_rule_recurrence
from stochastic_gradient_push_tpu.ops import delta_rule as dr

NAMES = ("o", "q", "k", "v", "log_alpha", "beta")

# test_delta_rule.py's cases: T, chunk, -log alpha range
CASES = {
    "length_not_a_multiple": (37, 8, (0.0, 3.0)),
    "one_chunk": (20, 64, (0.0, 3.0)),
    "exact_chunks": (32, 8, (0.0, 3.0)),
    "last_chunk_of_one": (17, 8, (0.0, 3.0)),
    "alpha_near_zero": (24, 8, (20.0, 60.0)),
    "alpha_near_one": (24, 8, (0.0, 1e-3)),
}


@pytest.fixture
def kernel_path(monkeypatch):
    """``delta_rule_chunked`` takes the kernel pair, interpreted: the rule
    is answered for it, nothing else of the path is changed."""
    monkeypatch.setattr(dr, "kernel_fits", lambda *args: True)
    monkeypatch.setattr(dr, "rule_kernel", functools.partial(
        dr.rule_kernel, interpret=True))


def _inputs(t, seed=0, decay=(0.0, 3.0), beta_max=2.0, b=2, h=3, dk=8,
            dv=16):
    """L2-normalised q and k (the mixer's), ``-log alpha`` uniform in
    ``decay``, ``beta`` uniform in (0, ``beta_max``), and a probe for
    ``o``."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True))
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return (f32(unit(r.normal(size=(b, t, h, dk)))),
            f32(unit(r.normal(size=(b, t, h, dk)))),
            f32(r.normal(size=(b, t, h, dv))),
            f32(-r.uniform(*decay, size=(b, t, h))),
            f32(r.uniform(0.0, beta_max, size=(b, t, h)))), \
        f32(r.normal(size=(b, t, h, dv)))


def _through(rule, args, probe):
    """``o`` and the five gradients of ``sum(o * probe)``, by name."""
    @jax.jit
    def both(*args):
        with jax.default_matmul_precision("highest"):
            return rule(*args), jax.grad(
                lambda *a: (rule(*a) * probe).sum(), argnums=range(5))(*args)

    o, grads = both(*args)
    return dict(zip(NAMES, (o,) + tuple(grads)))


def _xla(rule, args, probe):
    with pytest.MonkeyPatch.context() as m:
        m.setattr(dr, "kernel_fits", lambda *a: False)
        return _through(rule, args, probe)


def _recurrence(q, k, v, log_alpha, beta):
    return delta_rule_recurrence(q, k, v, jnp.exp(log_alpha), beta)


def _worst(ours, theirs):
    """By name, the largest difference over the largest value."""
    return {name: float(jnp.abs(ours[name] - theirs[name]).max()
                        / (jnp.abs(theirs[name]).max() + 1e-30))
            for name in theirs}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_pair_is_the_xla_path_and_the_recurrence(kernel_path, case):
    """Values and all five gradients in float32: lengths a chunk does not
    divide, one chunk (of 20 steps), a last chunk of one, decays near 0
    and near 1, writes with ``beta`` up to 2; two batch rows (the state
    starts from zero in each) and three heads."""
    t, chunk, decay = CASES[case]
    args, probe = _inputs(t, seed=1, decay=decay)
    assert float(args[4].max()) > 1.5         # writes that overshoot
    rule = functools.partial(dr.delta_rule_chunked, chunk=chunk)
    ours = _through(rule, args, probe)
    assert ours["o"].shape == args[2].shape and ours["o"].dtype == jnp.float32
    # on the scale of the largest gradient, as test_delta_rule.py holds
    # the XLA path: where alpha is near 0 the decay's own gradient is a
    # product with alpha, 1e-10 and below
    for oracle in (_xla(rule, args, probe),
                   _through(_recurrence, args, probe)):
        assert _worst(ours, oracle)["o"] < 2e-5
        scale = max(float(jnp.abs(oracle[n]).max()) for n in NAMES[1:])
        for name in NAMES[1:]:
            worst = float(jnp.abs(ours[name] - oracle[name]).max()) / scale
            assert worst < 2e-5, (name, worst)


def test_kernel_pair_at_the_cells_head_sizes(kernel_path):
    """Keys of 96 and values of 192, the Olmo hybrid cell's, over eight
    chunks of 64 (two grid steps of four): neither is a whole 128-lane
    register."""
    assert dr.chunks_a_step(8) == 4
    args, probe = _inputs(512, seed=2, b=1, h=2, dk=96, dv=192)
    rule = functools.partial(dr.delta_rule_chunked, chunk=64)
    ours = _through(rule, args, probe)
    for name, worst in _worst(ours, _xla(rule, args, probe)).items():
        assert worst < 1e-4, (name, worst)


def test_kernel_pair_with_bf16_operands(kernel_path):
    """Within the band that test_delta_rule.py sets the XLA path with
    bfloat16 operands, and near the XLA path's own rounding: the two paths
    round ``dO``, ``dV'`` and ``dS`` at different places (the module's
    docstring)."""
    args, probe = _inputs(64, seed=4)
    rule = functools.partial(dr.delta_rule_chunked, chunk=16,
                             operand_dtype=jnp.bfloat16)
    ours = _through(rule, args, probe)
    exact = _through(_recurrence, args, probe)
    error = _worst(ours, exact)["o"]
    assert 1e-4 < error < 3e-2
    for name, worst in _worst(ours, exact).items():
        assert worst < 3e-2, (name, worst)
    for name, worst in _worst(ours, _xla(rule, args, probe)).items():
        assert worst < 2e-2, (name, worst)


def test_padding_steps_leave_the_state_alone(kernel_path):
    args, _ = _inputs(21, seed=5)
    short = dr.delta_rule_chunked(*(a[:, :13] for a in args), 8)
    whole = dr.delta_rule_chunked(*args, 8)
    np.testing.assert_allclose(np.asarray(short), np.asarray(whole[:, :13]),
                               atol=1e-5)


def test_interpreted_pair_runs_inside_a_checked_shard_map(kernel_path):
    """Per rank inside a vma-checked ``shard_map``, as the step holds the
    rule, through ``jax.grad``: the interpreted bodies run in a branch
    that is always taken, and each rank's gradients are the XLA path's."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("ranks",))
    spec = jax.sharding.PartitionSpec("ranks")
    args, _ = _inputs(32, seed=6)

    def grads(*args):
        return jax.grad(lambda *a: dr.delta_rule_chunked(*a, 8).sum(),
                        argnums=range(5))(*args)

    ranked = jax.jit(jax.shard_map(grads, mesh=mesh, in_specs=spec,
                                   out_specs=(spec,) * 5))
    with jax.default_matmul_precision("highest"):
        ours = ranked(*args)
        with pytest.MonkeyPatch.context() as m:
            m.setattr(dr, "kernel_fits", lambda *a: False)
            theirs = jax.jit(grads)(*args)
    for name, g, w in zip(NAMES[1:], ours, theirs):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   err_msg=name)


def test_the_inverse_is_the_solves():
    """``(I + A)^-1`` by merged diagonal blocks against a float64 inverse,
    at chunks of powers of two and not, with ``A`` as large as the cell's
    can be (``beta`` up to 2, keys alike, no decay)."""
    for size in (8, 20, 64):
        r = np.random.default_rng(size)
        keys = r.normal(size=(size, 4)) + 3.0
        keys /= np.linalg.norm(keys, axis=1, keepdims=True)
        a = np.tril(r.uniform(0, 2, (size, 1)) * keys @ keys.T, -1)
        down, along = dr._steps(size)
        with jax.default_matmul_precision("highest"):
            got, = dr._inverses([jnp.asarray(a, jnp.float32)], down, along)
        want = np.linalg.inv(np.eye(size) + a)
        assert float(np.abs(got - want).max()
                     / np.abs(want).max()) < 1e-5, size


def test_the_rule_is_a_pure_function_of_platform_and_shapes():
    """The cell's shapes take the kernels; the CPU, a chunk or head that
    is no whole sublane tile, a chunk over a register's lanes and a state
    over the budget do not."""
    cell = dict(chunk=64, key_dim=96, value_dim=192)
    assert dr.kernel_fits("tpu", **cell)
    assert dr.kernel_fits("tpu", **{**cell, "chunk": 128})
    assert dr.kernel_fits("tpu", key_dim=128, value_dim=256, chunk=64)
    for platform in ("cpu", "gpu"):
        assert not dr.kernel_fits(platform, **cell)
    for change in ({"chunk": 4}, {"chunk": 20}, {"chunk": 256},
                   {"key_dim": 12}, {"value_dim": 100},
                   {"key_dim": 512, "value_dim": 1024}):
        assert not dr.kernel_fits("tpu", **{**cell, **change}), change
    assert dr.kernel_fits("tpu", **cell) == dr.kernel_fits("tpu", **cell)


def test_a_grid_step_takes_the_most_chunks_that_divide_a_head():
    assert [dr.chunks_a_step(n) for n in (1, 2, 3, 5, 6, 8, 64)] == \
        [1, 2, 3, 1, 3, 4, 4]


def test_the_cpu_takes_the_xla_path(monkeypatch):
    """On this backend ``delta_rule_chunked`` never reaches a kernel,
    whatever the shapes: the cell's own sizes lower without a
    ``pallas_call``."""
    def refuse(*args, **kwargs):
        raise AssertionError("the kernel path on the CPU")

    monkeypatch.setattr(dr, "rule_kernel", refuse)
    f = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype)
    jax.jit(functools.partial(
        dr.delta_rule_chunked, chunk=64, operand_dtype=jnp.bfloat16)).lower(
        f(jnp.float32, 1, 4096, 30, 96), f(jnp.float32, 1, 4096, 30, 96),
        f(jnp.float32, 1, 4096, 30, 192), f(jnp.float32, 1, 4096, 30),
        f(jnp.float32, 1, 4096, 30))


def test_compiled_pair_is_the_xla_path_at_the_cells_sizes():
    """On the chip: the compiled pair against the XLA path at the Olmo
    hybrid cell's sizes (4096 steps, 30 heads, keys of 96 and values of
    192, bf16 products, the mixer's decays and writes), forward and all
    five gradients. ``correct`` compares a forward pass only, so this is
    what holds the backward there."""
    if jax.default_backend() != "tpu":
        pytest.skip("the compiled pair needs the chip")
    b, t, h, dk, dv = 1, 4096, 30, 96, 192
    keys = jax.random.split(jax.random.PRNGKey(41), 6)
    unit = lambda x: x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True))
    args = (unit(jax.random.normal(keys[0], (b, t, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(keys[1], (b, t, h, dk))),
            jax.random.normal(keys[2], (b, t, h, dv)),
            -jnp.exp(jax.random.uniform(keys[3], (b, t, h), minval=-7.0,
                                        maxval=0.5)),
            2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h))))
    probe = jax.random.normal(keys[5], (b, t, h, dv))
    rule = functools.partial(dr.delta_rule_chunked, chunk=64,
                             operand_dtype=jnp.bfloat16)
    assert dr.kernel_fits("tpu", 64, dk, dv)
    ours = _through(rule, args, probe)
    worst = _worst(ours, _xla(rule, args, probe))
    for name in NAMES:
        assert worst[name] < 2e-2, (name, worst[name])
    return worst
