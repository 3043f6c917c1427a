"""Ask the chip's compiler, without the chip.

The TPU compiler is installed next to the CPU backend and compiles for a
topology that is *described*, not attached (``v5e:2x2``, the four-chip
host).  Interpret mode cannot see what Mosaic refuses — a slice off the
``(8, 128)`` tiling, a block over the VMEM limit, too many semaphores —
so the kernels of the main path are compiled here at their real widths.
Nothing runs: these tests say a program *builds* for the chip, never that
it is right or fast.

All chip compiles live in THIS file and the topology is described inside
a fixture: the process that loads the TPU library keeps it until exit, so
a second xdist worker must never try (a second file could land on one).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.models.moe import topk_moe_ffn
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from stochastic_gradient_push_tpu.ops import gossip_kernel as gk
from stochastic_gradient_push_tpu.ops import grouped_matmul as gm
from stochastic_gradient_push_tpu.ops.delta_rule import delta_rule_chunked
from stochastic_gradient_push_tpu.ops.delta_rule import (
    kernel_fits as delta_kernel_fits)
from stochastic_gradient_push_tpu.ops.flash_attention import (
    default_block, flash_attention, flash_attention_backward,
    flash_attention_forward, fused_backward_fits, tile_visits)
from stochastic_gradient_push_tpu.ops.ring_flash import ring_flash_attention
from stochastic_gradient_push_tpu.ops.ssd import kernel_fits, ssd_chunked
from stochastic_gradient_push_tpu.parallel import (
    GOSSIP_AXIS, collectives, make_gossip_mesh, wire)
from stochastic_gradient_push_tpu.serve.engine import ServeConfig
from stochastic_gradient_push_tpu.serve.paged_attention import (
    paged_attention_decode)
from stochastic_gradient_push_tpu.telemetry import names
from stochastic_gradient_push_tpu.topology import (
    NPeerDynamicDirectedExponentialGraph, build_schedule)
from stochastic_gradient_push_tpu.train.lm import lm_loss

WORLD = 4
# ResNet-50's parameter count: the flat payload one gossip round moves
RESNET50_PARAMS = 25_557_032


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR")
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir
    # a chip compile is written to the persistent cache but cannot be read
    # back without a chip: keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return make_gossip_mesh(WORLD, devices=topo.devices)


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernel auto rules ask ``jax.default_backend()``, which still
    says cpu here; steer them onto their TPU branch for the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _per_rank(mesh, shape, dtype):
    return jax.ShapeDtypeStruct(
        (WORLD,) + shape, dtype,
        sharding=NamedSharding(mesh, P(GOSSIP_AXIS)))


def _sharded(f, mesh, n_out):
    """jit(shard_map) of a per-rank ``f`` over world-stacked arguments."""
    def wrapped(*args):
        out = f(*(a[0] for a in args))
        return tuple(o[None] for o in out)

    return jax.jit(jax.shard_map(
        wrapped, mesh=mesh, in_specs=P(GOSSIP_AXIS),
        out_specs=(P(GOSSIP_AXIS),) * n_out))


def _kernel_names(compiled_text: str) -> set[str]:
    """What the chip's trace will call the program's Pallas kernels: the
    names of the compiled ``tpu_custom_call`` instructions, without the
    compiler's ``.N``.  (A ``pallas_call`` without ``name=`` is called
    after the enclosing module scope: ``attn.12``.)"""
    return {m.group(1) for m in re.finditer(
        r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*'
        r'custom_call_target="tpu_custom_call"', compiled_text)}


@pytest.mark.parametrize("shape,backward", [
    ((8, 12, 1024, 64), {names.KERNEL_FLASH_BWD}),
    ((2, 8, 4096, 64), {names.KERNEL_FLASH_BWD}),
    ((1, 8, 8192, 64), {names.KERNEL_FLASH_BWD}),
    ((1, 4, 16384, 64), {names.KERNEL_FLASH_DQ, names.KERNEL_FLASH_DKV}),
    ((1, 30, 4096, 128), {names.KERNEL_FLASH_BWD}),
], ids=["t1024", "t4096", "t8192", "t16384_pair", "t4096_d128"])
def test_flash_forward_and_backward_compile(one_chip, on_tpu, shape,
                                            backward):
    """The flagship LM's attention, the longest captured length and the
    longest the fused backward holds dq for (a 4 MB accumulator under a
    two-deep 2 MB block), at the auto block via ``jax.grad``: the forward
    kernel and ONE backward kernel; one length beyond the budget, where
    the dq + dk/dv pair takes over; and the Olmo hybrid cell's heads of
    128, the widest the fused backward takes."""
    assert default_block(shape[2]) == 512
    assert fused_backward_fits(*shape[2:]) == (
        backward == {names.KERNEL_FLASH_BWD})
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        # under the step's forward scope, as in the program: autodiff's
        # jvp(…)/transpose(…) wrap the outermost scope, and the kernels'
        # own names stay bare
        with jax.named_scope(names.SCOPE_FORWARD):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 1 + len(backward)
    assert _kernel_names(text) == {names.KERNEL_FLASH_FWD} | backward


@pytest.mark.parametrize("shape,dtype,return_lse", [
    ((1, 16, 8192, 64), jnp.bfloat16, True),     # gpt2m_sgp_w1_t8192's call
    ((1, 16, 8192, 64), jnp.bfloat16, False),
    ((4, 16, 1024, 64), jnp.float32, True),
    ((1, 8, 2048, 128), jnp.bfloat16, True),     # alpha over a whole register
    ((1, 4, 2048, 256), jnp.bfloat16, True),     # ... repeated over two
    ((2, 4, 64, 64), jnp.bfloat16, True),        # a block under 128 lanes
], ids=["t8192_lse", "t8192", "t1024_fp32", "d128", "d256", "t64"])
def test_flash_forward_compiles(one_chip, shape, dtype, return_lse):
    """The forward alone at the auto block, with and without the ``lse``
    output: its running maximum repeated over a 512-wide ``s`` and its
    ``alpha`` cut or repeated to the head's width are register moves the
    interpreter cannot refuse and Mosaic can."""
    block = default_block(shape[2])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(lambda q, k, v: flash_attention_forward(
        q, k, v, causal=True, block_q=block, block_k=block,
        return_lse=return_lse)).lower(x, x, x).compile().as_text()
    assert _kernel_names(text) == {names.KERNEL_FLASH_FWD}


def test_flash_compiles_inside_the_steps_shard_map(mesh, on_tpu):
    """``gpt2m_sgp_w1_t8192``'s attention through ``jax.grad`` as the step
    holds it: per rank inside a vma-checked ``shard_map``, where the
    operands vary over the mesh's axis and the kernels' visit arrays are
    constants with no axis: the compiled ``pallas_call`` takes the mix,
    with no cast."""
    def grads(q, k, v):
        def loss(q, k, v):
            with jax.named_scope(names.SCOPE_FORWARD):
                return flash_attention(q, k, v, causal=True).astype(
                    jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    x = _per_rank(mesh, (1, 16, 8192, 64), jnp.bfloat16)
    text = _sharded(grads, mesh, 3).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_names(text) == {names.KERNEL_FLASH_FWD,
                                   names.KERNEL_FLASH_BWD}


def test_flash_compiles_at_the_longest_visit_lists(one_chip):
    """What grows with the length is the two visit arrays in SMEM: 32768
    tokens in blocks of 128 with no mask, a long ring-flash shard's tick
    at the smallest block Mosaic tiles, make 65536 visits a list, and the
    forward and the dq + dk/dv pair compile with them."""
    t, block = 32768, 128
    assert len(tile_visits(t, block, block, False, "q")[0]) == 2 ** 16
    x = jax.ShapeDtypeStruct((1, 1, t, 64), jnp.bfloat16, sharding=one_chip)

    def both(q, k, v):
        out, lse = flash_attention_forward(
            q, k, v, block_q=block, block_k=block, return_lse=True)
        return flash_attention_backward(q, k, v, out, lse, out,
                                        block_q=block, block_k=block)

    text = jax.jit(both).lower(x, x, x).compile().as_text()
    assert _kernel_names(text) == {
        names.KERNEL_FLASH_FWD, names.KERNEL_FLASH_DQ,
        names.KERNEL_FLASH_DKV}


def _latent_grad_kernels(one_chip, t, dtype):
    """The kernels latent attention's call (32 heads, q·k 192 beside v
    128, ``t`` tokens) compiles to through ``jax.grad`` at the auto
    block."""
    heads = 32
    qk = jax.ShapeDtypeStruct((1, heads, t, 192), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, heads, t, 128), dtype, sharding=one_chip)

    def loss(q, k, v):
        with jax.named_scope(names.SCOPE_FORWARD):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    return text.count("tpu_custom_call"), _kernel_names(text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_latent_widths_compile_to_one_fused_backward(one_chip, on_tpu,
                                                     dtype):
    """joyai_sgp_w1_t8192's call: the forward with a value block narrower
    than the query's, and ONE fused backward holding dq for 8192 tokens at
    192 lanes (padded to 256) in the wider scope it asks for; the default
    scope refuses it (PERF.md §6)."""
    assert fused_backward_fits(8192, 192, 128, 512, 512, dtype)
    assert _latent_grad_kernels(one_chip, 8192, dtype) == (
        2, {names.KERNEL_FLASH_FWD, names.KERNEL_FLASH_BWD})


def test_latent_widths_compile_as_the_pair(one_chip, on_tpu):
    """Just past the edge, the cell's widths at 16384 tokens (dq alone
    32 MiB): the shape rule sends the backward to the dq + dk/dv pair,
    which asks for no scope and compiles."""
    assert not fused_backward_fits(16384, 192, 128)
    assert _latent_grad_kernels(one_chip, 16384, jnp.bfloat16)[1] == {
        names.KERNEL_FLASH_FWD, names.KERNEL_FLASH_DQ,
        names.KERNEL_FLASH_DKV}


@pytest.mark.parametrize("d", [64, 128])
def test_fused_backward_compiles_at_the_budget_in_fp32(one_chip, d):
    """The shape rule knows ``t`` and ``d`` only, so the longest sequence
    it admits must build at either dtype: an fp32 dq is accumulated in its
    own resident block, and costs what bf16's accumulator and block do."""
    t = 8192
    assert fused_backward_fits(t, d) and not fused_backward_fits(2 * t, d)
    x = jax.ShapeDtypeStruct((1, 32, t, d), jnp.float32, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 32, t), jnp.float32, sharding=one_chip)

    def bwd(q, k, v, out, lse, do):
        return flash_attention_backward(q, k, v, out, lse, do, causal=True,
                                        block_q=512, block_k=512)

    text = jax.jit(bwd).lower(x, x, x, x, lse, x).compile().as_text()
    assert _kernel_names(text) == {names.KERNEL_FLASH_BWD}


@pytest.mark.parametrize("dtype,groups", [
    (jnp.bfloat16, 1), (jnp.float32, 1), (jnp.bfloat16, 8)],
    ids=["bf16", "fp32", "groups8"])
def test_scan_kernel_pair_compiles(one_chip, on_tpu, dtype, groups):
    """The state-space scan at the published sizes (4096 steps in chunks
    of 256, 64 heads of 64 over a state of 128) through ``jax.grad`` of
    ``ssd_chunked``, under the mixer's scope as in the program: the rule
    takes the kernels, and the compiled text holds one ``ssd_fwd`` and one
    ``ssd_bwd``; in float32, and with the eight groups of the family's
    larger models (a group's scores over one head block a chunk)."""
    t, h, p, n, chunk = 4096, 64, 64, 128, 256
    assert kernel_fits("tpu", chunk, n, p, h, groups,
                       jnp.dtype(dtype).itemsize)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)

    def loss(x, dt, a, b, c):
        with jax.named_scope(names.SCOPE_FORWARD), \
                jax.named_scope(names.SCOPE_SSD):
            return ssd_chunked(x, dt, a, b, c, chunk,
                               operand_dtype=dtype).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        f32(1, t, h, p), f32(1, t, h), f32(h), f32(1, t, groups, n),
        f32(1, t, groups, n)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_names(text) == {names.KERNEL_SSD_FWD,
                                   names.KERNEL_SSD_BWD}
    # the compiled calls carry the scan's scope (``ssd_ms`` finds them by
    # it), through the jitted wrappers that share one trace among layers
    for call, where in (("ssd_fwd", "jvp("), ("ssd_bwd", "transpose(jvp(")):
        assert re.search(
            rf'op_name="[^"]*/{re.escape(where)}{names.SCOPE_FORWARD}\)+/'
            rf'{re.escape(names.SCOPE_SSD)}/jit\(\w+\)/{call}/pallas_call"',
            text), call


def _delta_rule_loss(q, k, v, log_alpha, beta):
    """The Olmo hybrid cell's rule (chunks of 64, bf16 products) under the
    mixer's scope inside the step's forward scope, as in the program."""
    with jax.named_scope(names.SCOPE_FORWARD), \
            jax.named_scope(names.SCOPE_DELTA_RULE):
        return delta_rule_chunked(q, k, v, log_alpha, beta, 64,
                                  operand_dtype=jnp.bfloat16).sum()


# the Olmo hybrid cell's rule: 4096 steps, 30 heads, keys of 96, values
# of 192; q, k, v arrive in bf16, the gates in float32
DELTA_SHAPES = [(jnp.bfloat16, (1, 4096, 30, 96)),
                (jnp.bfloat16, (1, 4096, 30, 96)),
                (jnp.bfloat16, (1, 4096, 30, 192)),
                (jnp.float32, (1, 4096, 30)), (jnp.float32, (1, 4096, 30))]


def test_the_delta_rule_compiles_at_the_cells_sizes(one_chip, on_tpu):
    """The gated delta rule of the Olmo hybrid cell through ``jax.grad``
    of ``delta_rule_chunked``: the rule takes the kernel pair, and the
    compiled text holds one ``delta_fwd`` and one ``delta_bwd``, under the
    rule's scope, and none of XLA's triangular-solve custom calls."""
    assert delta_kernel_fits("tpu", 64, 96, 192)
    compiled = jax.jit(jax.grad(_delta_rule_loss, argnums=(0, 1, 2, 3, 4))) \
        .lower(*(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for dtype, shape in DELTA_SHAPES)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_names(text) == {names.KERNEL_DELTA_FWD,
                                   names.KERNEL_DELTA_BWD}
    assert "InvertDiagBlocksLowerTriangular" not in text
    # the compiled calls carry the rule's scope (``delta_rule_ms`` finds
    # them by it), through the jitted wrappers that share one trace among
    # layers
    for call, where in (("delta_fwd", "jvp("),
                        ("delta_bwd", "transpose(jvp(")):
        assert re.search(
            rf'op_name="[^"]*/{re.escape(where)}{names.SCOPE_FORWARD}\)+/'
            rf'{re.escape(names.SCOPE_DELTA_RULE)}/jit\(\w+\)/{call}/'
            rf'pallas_call"', text), call
    # a layer's rule and its gradient: 0.47 GB of temporaries at jax 0.9
    # (2.05 GB on the XLA path: its scan's states and [64, 30, 64, 64]
    # blocks); the pair's residuals are the entering states, [30, 64, 96,
    # 192] float32 = 142 MB, and each chunk's inverse
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_the_delta_rule_compiles_inside_the_steps_shard_map(mesh, on_tpu):
    """The same rule through ``jax.grad`` per rank inside a vma-checked
    ``shard_map``, as the step holds it: the pair's outputs vary over the
    mesh's axis as their operands do."""
    grads = jax.grad(_delta_rule_loss, argnums=(0, 1, 2, 3, 4))
    text = _sharded(grads, mesh, 5).lower(
        *(_per_rank(mesh, shape, dtype) for dtype, shape in DELTA_SHAPES)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_names(text) == {names.KERNEL_DELTA_FWD,
                                   names.KERNEL_DELTA_BWD}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_expert_layer_compiles_with_the_grouped_kernels(one_chip, on_tpu,
                                                        dtype):
    """The top-k expert layer at the published sizes (8192 tokens, 4 of 32
    experts a token, 16 held, 2048 wide, experts of 1792) through
    ``jax.grad``, under its scopes as in the program: the rule takes the
    kernels, and the compiled text holds the forward's two grouped
    products, the rows' gradients' two, and the blocks' gradients' two."""
    t, d, f, held, routed = 8192, 2048, 1792, 16, 32
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,
                                            sharding=one_chip)
    rows = jax.ShapeDtypeStruct((t * 4, d), dtype)
    assert gm.kernel_fits("tpu", rows, jax.ShapeDtypeStruct(
        (held, d, 2 * f), dtype))

    def loss(x, router, bias, gate_up, down):
        with jax.named_scope(names.SCOPE_FORWARD), \
                jax.named_scope(names.SCOPE_MOE):
            return topk_moe_ffn(x, router, bias, gate_up, down, per_token=4,
                                first=0, dtype=dtype)[0].sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4))).lower(
        shape(t, d), shape(d, routed), shape(routed),
        shape(held, d, 2 * f), shape(held, f, d)).compile().as_text()
    assert text.count("tpu_custom_call") == 6
    assert _kernel_names(text) == {names.KERNEL_GROUPED_MATMUL,
                                   names.KERNEL_GROUPED_MATMUL_DW}
    # the compiled calls carry the experts' scope (``moe_experts_ms`` finds
    # them by it), through the jitted wrappers the layers share
    assert re.search(
        rf'op_name="[^"]*{re.escape(names.SCOPE_MOE_EXPERTS)}/jit\(\w+\)/'
        rf'{names.KERNEL_GROUPED_MATMUL}/pallas_call"', text)
    assert "ragged" not in text


def _arrays_outside_fusions(compiled_text: str, elements: int) -> list:
    """``(opcode, dtype)`` of every instruction that writes an array of
    ``elements`` elements to memory: those of the entry computation and of
    the loops' bodies, a fusion by its outputs; what is inside a fused
    computation stays in registers and is not counted."""
    fused = set(re.findall(r"fusion\([^\n]*?calls=%?([\w.\-]+)",
                           compiled_text))
    found, counted = [], False
    for line in compiled_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            counted = head.group(1) not in fused
            continue
        made = counted and re.match(
            r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not made or made.group(2) in (
                "parameter", "tuple", "get-tuple-element", "bitcast",
                "while"):
            continue            # these write nothing of their own
        for dtype, dims in re.findall(r"\b(pred|[a-z]+\d+)\[([\d,]+)\]",
                                      made.group(1)):
            if np.prod([int(d) for d in dims.split(",")]) == elements:
                found.append((made.group(2), dtype))
    return found


@pytest.mark.parametrize("batch,seq,temp_limit", [
    (1, 8192, 3.0e9), (4, 1024, 2.0e9)], ids=["t8192", "t1024"])
def test_the_lm_step_holds_the_logits_once_in_bf16(one_chip, on_tpu, batch,
                                                   seq, temp_limit):
    """GPT-2 medium's width, one block, the 50257-wide head, ``lm_loss``
    and a momentum update at the two GPT-2 cells' tokens.  The loss takes
    the target's logit by comparison, so the step keeps one array of the
    logits' size: the head's product rounded to bf16, read again by the
    sum of exponentials and by the two products of its backward.  With
    ``take_along_axis`` (to PR 33) its transpose scattered 8192 numbers
    into a float32 array of that size, which at t8192 — 50257 is no
    multiple of 128 — two loops of ``dynamic-update-slice`` re-laid flat
    and back: 5.34 GB of temporaries where this reads 1.42 GB (t1024:
    a float32 copy of the logits beside the bf16 one, 1.41 against 0.63)."""
    vocab = 50257
    model = TransformerLM(TransformerConfig(
        vocab_size=vocab, d_model=1024, n_layers=1, n_heads=16, d_ff=4096,
        max_len=seq, dtype=jnp.bfloat16, attn_impl="flash"))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, seq), jnp.int32))
        )["params"])

    def step(params, momenta, tokens, targets):
        def loss_fn(p):
            with jax.named_scope(names.SCOPE_FORWARD):
                return lm_loss(model.apply({"params": p}, tokens), targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        momenta = jax.tree.map(lambda m, g: 0.9 * m + g, momenta, grads)
        return jax.tree.map(lambda p, m: p - 0.1 * m, params,
                            momenta), momenta, loss

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, params, tokens, tokens).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == {names.KERNEL_FLASH_FWD,
                                   names.KERNEL_FLASH_BWD}
    # written once, by the head's product, in bf16: no float32 array of
    # that size (flat or not), no copy, no scatter and no
    # dynamic-update-slice over one
    assert _arrays_outside_fusions(text, batch * seq * vocab) == [
        ("fusion", "bf16")]
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit
    # the head's products and the loss's passes carry the head's scope
    for under in ("jvp(", "transpose(jvp("):
        assert re.search(
            rf'op_name="[^"]*/{re.escape(under)}{names.SCOPE_FORWARD}\)+/'
            rf'TransformerLM/{re.escape(names.SCOPE_LM_HEAD)}/lm_head/'
            rf'dot_general"', text), under


def test_push_sum_round_is_a_collective_permute(mesh):
    """The XLA lane's push-sum round on four chips is a real
    ``collective-permute`` (on one chip it degenerates to a copy)."""
    sched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))

    def round_(p, w, phase):
        return collectives.mix_push_sum(p, w, phase, sched, GOSSIP_AXIS)

    text = _sharded(round_, mesh, 2).lower(
        _per_rank(mesh, (RESNET50_PARAMS,), jnp.float32),
        _per_rank(mesh, (), jnp.float32),
        _per_rank(mesh, (), jnp.int32)).compile().as_text()
    assert "collective-permute" in text
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("codec", [None, wire.Int8Codec(64)],
                         ids=["f32", "int8"])
def test_gossip_start_wait_pair_compiles(mesh, codec):
    """The Pallas transport at ResNet-50's payload: the remote-DMA start
    kernel (entry barrier, ``collective_id``, two semaphore slots) and
    the local decode+axpy wait kernel, both Mosaic custom calls."""
    dests = np.asarray([(r + 1) % WORLD for r in range(WORLD)])

    def pair(x):
        if codec is None:
            parts, spec = (x.reshape(1, -1),), wire.F32.kernel_spec()
        else:
            parts, spec = codec.encode(x), codec.kernel_spec()
        handle = gk.gossip_edge_start(parts, dests, GOSSIP_AXIS, spec,
                                      n_decoded=x.size)
        return (gk.gossip_edge_wait(handle, x * 0.5),)

    text = _sharded(pair, mesh, 1).lower(
        _per_rank(mesh, (RESNET50_PARAMS,), jnp.float32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_names(text) == {names.KERNEL_GOSSIP_START,
                                   names.KERNEL_GOSSIP_WAIT}


def test_ring_flash_tick_compiles(mesh):
    """One ring-flash pass over four sequence shards: flash-kernel ticks
    joined by ``collective-permute`` rotations, forward and backward."""
    def grads(q, k, v):
        def loss(q, k, v):
            return ring_flash_attention(
                q, k, v, GOSSIP_AXIS, causal=True,
                use_pallas=True).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    x = _per_rank(mesh, (2, 12, 1024, 64), jnp.bfloat16)
    text = _sharded(grads, mesh, 3).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_grouped_average_compiles(mesh):
    """The intra-slice exact average (hierarchical / synthesized psum
    phases): grouped reduce-scatter + all-gather, which XLA may fuse back
    into one grouped all-reduce."""
    def average(p):
        return (collectives._grouped_average(
            p, GOSSIP_AXIS, [[0, 1], [2, 3]]),)

    text = _sharded(average, mesh, 1).lower(
        _per_rank(mesh, (RESNET50_PARAMS,), jnp.float32)
    ).compile().as_text()
    assert "all-reduce" in text or "reduce-scatter" in text


def test_paged_decode_kernel_compiles(one_chip):
    """serve/'s decode kernel at the engine's default page shape — not on
    the main path; compiled once so ROADMAP R8 starts from a known
    state."""
    batch, heads, head_dim = 8, 12, 64
    cfg = ServeConfig(n_heads=heads)
    pages = jax.ShapeDtypeStruct(
        (heads, cfg.num_pages + 1, cfg.page_size, head_dim), jnp.bfloat16,
        sharding=one_chip)
    text = jax.jit(
        lambda *a: paged_attention_decode(*a, use_pallas=True)).lower(
        jax.ShapeDtypeStruct((batch, heads, head_dim), jnp.bfloat16,
                             sharding=one_chip),
        pages, pages,
        jax.ShapeDtypeStruct((batch, cfg.max_pages_per_seq), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {names.KERNEL_PAGED_ATTENTION}
