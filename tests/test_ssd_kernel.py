"""The state-space scan's Pallas kernel pair (ops/ssd.py) on the CPU in
interpret mode, against both of its oracles: the ``jax.numpy`` path of
``ssd_chunked`` and the step-by-step recurrence of the benchmark's plain
reference; the rule that picks the pair; the names it gives the trace."""

import functools

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import granite_hybrid as plain
from stochastic_gradient_push_tpu.ops import ssd

# the smallest sizes the kernels tile: a chunk and a state of one 128-lane
# register, heads of 64 two to a register, one register a group
CHUNK, STATE, HEAD = 128, 128, 64


@pytest.fixture
def kernel_path(monkeypatch):
    """``ssd_chunked`` takes the kernel pair, interpreted: the rule is
    answered for it, nothing else of the path is changed."""
    monkeypatch.setattr(ssd, "kernel_fits", lambda *args: True)
    monkeypatch.setattr(ssd, "chunks_kernel", functools.partial(
        ssd.chunks_kernel, interpret=True))


def _inputs(t, groups, seed, batch=2):
    h = 2 * groups
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (batch, t, h, HEAD)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, t, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (batch, t, groups, STATE)),
            jax.random.normal(k[4], (batch, t, groups, STATE)),
            jax.random.normal(k[5], (batch, t, h, HEAD)))


def _through(scan, x, dt, a, b, c, probe):
    """``y`` and the five gradients of ``sum(y * probe)``."""
    @jax.jit
    def both(x, dt, a, b, c):
        with jax.default_matmul_precision("highest"):
            return scan(x, dt, a, b, c), jax.grad(
                lambda *args: (scan(*args) * probe).sum(),
                argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)

    y, grads = both(x, dt, a, b, c)
    return dict(zip(("y", "x", "dt", "a", "b", "c"), (y,) + grads))


def _worst(ours, theirs):
    """By name, the largest difference over the largest value."""
    return {name: float(jnp.abs(ours[name] - theirs[name]).max()
                        / (jnp.abs(theirs[name]).max() + 1e-12))
            for name in theirs}


# float32 against float32 differ by the order of the sums; with bfloat16
# operands the two paths round dW, the chunk's decay to its end and d cum
# at different places (the module's docstring), and the recurrence rounds
# nothing
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("chunk,chunks,groups", [
    (CHUNK, chunks, groups) for chunks in (1, 2, 3) for groups in (1, 2)
] + [(2 * CHUNK, 2, 1)])
def test_kernel_pair_is_the_xla_path_and_the_recurrence(
        kernel_path, chunk, chunks, groups, dtype, tol):
    """Values and all five gradients; two batch rows (the state starts
    from zero in each), one to three chunks (the state is carried, and
    its gradient carried back), one and two groups (``S`` and ``dS`` are
    a group's), and a chunk of two sub-blocks (one of them under the
    diagonal)."""
    *args, probe = _inputs(chunk * chunks, groups, seed=chunks + groups)
    scan = functools.partial(ssd.ssd_chunked, chunk=chunk,
                             operand_dtype=dtype)
    ours = _through(scan, *args, probe)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(ssd, "kernel_fits", lambda *args: False)
        xla = _through(scan, *args, probe)
    recurrence = _through(plain.ssm_recurrence, *args, probe)
    assert ours["y"].dtype == jnp.float32
    # a's gradient sums terms of both signs over every step and head: at
    # these sizes the jax.numpy path itself stands 1.2e-4 from the
    # recurrence in float32, so it alone gets five times the room
    room = lambda name: 5 * tol if name == "a" else tol
    for name, worst in _worst(ours, xla).items():
        assert worst < room(name), f"{name}, the jax.numpy path: {worst}"
    for name, worst in _worst(ours, recurrence).items():
        assert worst < room(name), f"{name}, the recurrence: {worst}"


def test_kernel_pair_takes_the_fast_decay_initialisation(kernel_path):
    """The published initialisation through the kernels: dt ~ 1.3 and A
    down to -64, so a chunk's whole log decay reaches -2e4 and every
    difference above the diagonal is as large and positive; it is masked
    before the exponential, and ``d cum``'s two row sums, each as large as
    the diagonal's term, cancel to the bit there."""
    h, t = 8, 512
    x = jnp.ones((1, t, h, HEAD))
    dt = jnp.full((1, t, h), 1.3)
    a = -jnp.linspace(1.0, 64.0, h)
    b = c = jnp.ones((1, t, 1, STATE))
    scan = functools.partial(ssd.ssd_chunked, chunk=256,
                             operand_dtype=jnp.bfloat16)
    ours = _through(scan, x, dt, a, b, c, jnp.ones_like(x))
    assert all(bool(jnp.isfinite(v).all()) for v in ours.values())
    with pytest.MonkeyPatch.context() as m:
        m.setattr(ssd, "kernel_fits", lambda *args: False)
        xla = _through(scan, x, dt, a, b, c, jnp.ones_like(x))
    for name, worst in _worst(ours, xla).items():
        assert worst < 2e-2, f"{name}: {worst}"


def test_kernel_pair_pads_a_length_its_chunk_does_not_divide(kernel_path):
    *args, probe = _inputs(200, 1, seed=0, batch=1)
    ours = _through(functools.partial(ssd.ssd_chunked, chunk=CHUNK),
                    *args, probe)
    assert ours["y"].shape == (1, 200, 2, HEAD)
    recurrence = _through(plain.ssm_recurrence, *args, probe)
    for name, worst in _worst(ours, recurrence).items():
        assert worst < 1e-4, f"{name}: {worst}"


def test_the_rule_is_a_pure_function_of_platform_and_shapes():
    """The cell's shapes take the kernels, 16 heads a grid step; the CPU,
    a toy chunk, a toy state, a head that does not divide a register's
    lanes and heads that do not fill one do not."""
    cell = dict(chunk=256, d_state=128, head_dim=64, heads=64, groups=1,
                itemsize=2)
    assert ssd.kernel_fits("tpu", **cell)
    assert ssd.head_block(**cell) == 16
    assert ssd.kernel_fits("tpu", **{**cell, "itemsize": 4})
    assert ssd.kernel_fits("tpu", **{**cell, "groups": 8})
    assert ssd.head_block(**{**cell, "groups": 8}) == 8
    for platform in ("cpu", "gpu"):
        assert not ssd.kernel_fits(platform, **cell)
    for change in ({"chunk": 4}, {"chunk": 24}, {"d_state": 16},
                   {"head_dim": 48}, {"head_dim": 256},
                   {"heads": 64, "groups": 64}, {"heads": 63}):
        assert not ssd.kernel_fits("tpu", **{**cell, **change}), change


def test_the_cpu_takes_the_xla_path(monkeypatch):
    """On this backend ``ssd_chunked`` never reaches a kernel, whatever
    the shapes: the cell's own sizes lower without a ``pallas_call``."""
    def refuse(*args, **kwargs):
        raise AssertionError("the kernel path on the CPU")

    monkeypatch.setattr(ssd, "chunks_kernel", refuse)
    *args, _ = _inputs(2 * CHUNK, 1, seed=0, batch=1)
    jax.jit(functools.partial(ssd.ssd_chunked, chunk=CHUNK)).lower(*args)
