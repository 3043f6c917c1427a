"""The ``joyai_llm_flash`` family (models/transformer.py's latent-attention
mixer and multi-token-prediction module, models/moe.py::topk_moe_ffn with
a routed scale and a shared expert, the flash kernels at q·k and v widths
apart) against its plain reference (benchmark/reference/joyai_flash.py)
at toy widths on the CPU: logits, both losses and every gradient leaf;
the shares of the experts add up to the uncut layer with the shared
expert counted once; the kernels at 192 / 128 in interpret mode (the fused
backward bit for bit the pair) and, on the chip only, compiled at the
cell's sizes; the selection bias's
balancing (a set-up step of the cell's builder); the ``--model_json``
way in and its refusals; and the steps and kernels of what was there
before, lowered as before."""

import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from benchmark.reference import joyai_flash as plain
from test_lfm2_moe import _sgp_step, _share
from test_olmo_hybrid import SOURCE as OLMO
from stochastic_gradient_push_tpu.models.moe import topk_moe_ffn
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerLM, config_from_source)
from stochastic_gradient_push_tpu.ops.flash_attention import (
    flash_attention_backward, flash_attention_forward)
from stochastic_gradient_push_tpu.run import gossip_lm
from stochastic_gradient_push_tpu.telemetry import names
from stochastic_gradient_push_tpu.train.lm import (
    MTP_LOSS_WEIGHT, lm_loss, mtp_loss)

fa = importlib.import_module(
    "stochastic_gradient_push_tpu.ops.flash_attention")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the selection bias's balancing is a set-up step of the cell's builder
_builder = spec.load_plugin(REPO, "builders", "mla_moe_trainer")
balance_bias, balance_expert_biases = (_builder.balance_bias,
                                       _builder.balance_expert_biases)

# the published model's keys at toy widths: 8 routed experts, 2 a token,
# one shared, q·k 8 + 4 beside v 6, one leading dense layer, the module
SOURCE = {
    "model_type": "joyai_llm_flash", "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "qk_head_dim": 12, "v_head_dim": 6, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "moe_layer_freq": 1, "rope_theta": 10000, "rope_interleave": True,
    "rope_scaling": None, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "vocab_size": 96, "num_nextn_predict_layers": 1, "hidden_act": "silu",
    "attention_bias": False}
SEQ = 24
EXPERT_LAYERS = ("block_1", "block_2", "mtp_block")


def _model(source=SOURCE, dtype=jnp.float32, **runtime):
    return TransformerLM(config_from_source(
        source, dtype=dtype, attn_impl="full", **runtime))


def _params(model, seed=0):
    """Seeded weights with every norm's weight moved off one and a
    selection bias as large as the scores' spread, so that both matter."""
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), tokens)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    return jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, params)


def _batch(seed=3):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.randint(k1, (2, SEQ), 0, 96),
            jax.random.randint(k2, (2, SEQ), 0, 96))


def _objective(model):
    """The step's loss: the trunk's and, at the config's weight, the
    module's."""
    def objective(p, tokens, targets):
        logits, sown = model.apply({"params": p}, tokens, mutable=["mtp"])
        return lm_loss(logits, targets) + MTP_LOSS_WEIGHT * mtp_loss(
            sown["mtp"]["logits"][0], targets)
    return objective


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_program_agrees_with_the_plain_reference_in_float32(remat):
    """Logits of the trunk and the module, both losses and every gradient
    leaf, with a non-zero bias: within 1e-4 of the largest."""
    model = _model(remat=remat)
    params = _params(model)
    tokens, targets = _batch()
    assert float(jnp.abs(params["block_1"]["moe"]["expert_bias"]).max()) > 0.1
    logits, sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, mutable=["mtp"]))(params)
    theirs, their_mtp, routing = jax.jit(
        lambda p: plain.lm_logits(p, tokens, SOURCE))(params)
    assert len(routing) == 3
    for ours, ref in ((logits, theirs), (sown["mtp"]["logits"][0],
                                         their_mtp)):
        scale = float(jnp.abs(ref).max())
        assert float(jnp.abs(ours - ref).max()) < 1e-4 * scale
    assert float(lm_loss(logits, targets)) == pytest.approx(
        float(plain.lm_loss(theirs, targets)), abs=1e-5)
    assert float(mtp_loss(sown["mtp"]["logits"][0], targets)) \
        == pytest.approx(float(plain.mtp_loss(their_mtp, targets)),
                         abs=1e-5)
    loss, grads = jax.jit(jax.value_and_grad(_objective(model)))(
        params, tokens, targets)
    their_loss, their_grads = jax.jit(lambda p: plain.loss_and_grads(
        p, tokens, targets, SOURCE))(params)
    assert float(loss) == pytest.approx(float(their_loss), abs=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(their_grads)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, ours_g), theirs_g in zip(flat, jax.tree.leaves(their_grads)):
        top = float(jnp.abs(theirs_g).max())
        where = jax.tree_util.keystr(path)
        if "expert_bias" in where:
            # it chooses through top-k's indices, which carry no gradient
            assert top == 0.0 and float(jnp.abs(ours_g).max()) == 0.0
            continue
        assert top > 0, where
        assert float(jnp.abs(ours_g - theirs_g).max()) < 1e-4 * top, where


def test_program_in_bf16_agrees_with_the_reference_given_its_selection():
    """The configuration's compute dtype: bf16 operands, float32 islands.
    Given the program's selection the reference's logits and losses lie
    within rounding of it."""
    model = _model(dtype=jnp.bfloat16)
    params = _params(model)
    tokens, targets = _batch()
    logits, sown = model.apply({"params": params}, tokens,
                               mutable=["mtp", "moe_selection"])
    selection = [sown["moe_selection"][b]["moe"]["experts"][0]
                 for b in EXPERT_LAYERS]
    theirs, their_mtp, _ = plain.lm_logits(params, tokens, SOURCE,
                                           selection=selection)
    for ours, ref in ((logits, theirs), (sown["mtp"]["logits"][0],
                                         their_mtp)):
        assert float(jnp.abs(ours - ref).max()) \
            < 0.05 * float(jnp.abs(ref).max())
    assert float(lm_loss(logits, targets)) == pytest.approx(
        float(plain.lm_loss(theirs, targets)), abs=0.02)
    assert float(mtp_loss(sown["mtp"]["logits"][0], targets)) \
        == pytest.approx(float(plain.mtp_loss(their_mtp, targets)),
                         abs=0.02)


def test_the_rotation_is_interleaved_on_the_rope_lanes_alone():
    """A key's rope lanes shifted by whole positions leave ``q · k`` as a
    rotation of pairs (2i, 2i+1) gives it, and the no-position lanes are
    untouched: the reference's pairs against the program's layout."""
    from stochastic_gradient_push_tpu.models import transformer

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 8))
    ours = transformer._rope_interleaved(x, jnp.arange(16), 10000.0)
    theirs = plain._rotary_pairs(x, 10000.0)
    # the program lays a head's lanes out evens then odds: one permutation
    # of both q and k, which leaves every product as it is
    order = jnp.concatenate([jnp.arange(0, 8, 2), jnp.arange(1, 8, 2)])
    np.testing.assert_allclose(ours, theirs[..., order], atol=1e-6)
    q, k = ours[:, 0], ours[:, 1]
    np.testing.assert_allclose(
        jnp.einsum("bqd,bkd->bqk", q, k),
        jnp.einsum("bqd,bkd->bqk", theirs[:, 0], theirs[:, 1]), atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Four chips holding two of the eight experts each: what the shares
    compute, with the shared expert every chip computes alike counted
    once, is the uncut layer's output."""
    keys = jax.random.split(jax.random.PRNGKey(4), 8)
    t, d, f, e, k = 40, 16, 8, 8, 2
    x = jax.random.normal(keys[0], (t, d))
    router = 0.3 * jax.random.normal(keys[1], (d, e))
    bias = 0.1 * jax.random.normal(keys[2], (e,))
    gate_up = 0.3 * jax.random.normal(keys[3], (e, d, 2 * f))
    down = 0.3 * jax.random.normal(keys[4], (e, f, d))
    shared = (0.3 * jax.random.normal(keys[5], (d, 2 * f)),
              0.3 * jax.random.normal(keys[6], (f, d)))
    layer = lambda first, n: topk_moe_ffn(
        x, router, bias, gate_up[first:first + n], down[first:first + n],
        per_token=k, first=first, scale=2.5, shared=shared)
    whole, _ = layer(0, e)
    shares = [layer(first, 2) for first in range(0, e, 2)]
    alone, _ = topk_moe_ffn(x, router, bias, gate_up[:0], down[:0],
                            per_token=k, shared=shared)
    total = sum(y for y, _ in shares) - (len(shares) - 1) * alone
    np.testing.assert_allclose(total, whole, atol=1e-5)
    # every pair lands on exactly one share
    assert sum(float(a["expert_rows"].sum()) for _, a in shares) == t * k
    # and the uncut layer is the reference's
    p = {"router": router, "expert_bias": bias, "experts_gate_up": gate_up,
         "experts_down": down, "shared_gate_up": shared[0],
         "shared_down": shared[1]}
    theirs, _ = plain.experts_ffn(x, p, SOURCE)
    np.testing.assert_allclose(whole, theirs, atol=1e-5)


def _full(q, k, v):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    t = q.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (32, 64)])
def test_the_kernels_take_q_k_192_beside_v_128(block_q, block_k):
    """Forward and the fused backward interpreted at the latent widths
    against plain attention at ``192 ** -0.5``: the shape rule hands a
    backward at heads over 128 the fused kernel where its VMEM fits."""
    rng = np.random.default_rng(5)
    shape = (1, 2, 128)
    q, k = (jnp.asarray(rng.normal(size=shape + (192,)), jnp.float32)
            for _ in range(2))
    v, do = (jnp.asarray(rng.normal(size=shape + (128,)), jnp.float32)
             for _ in range(2))
    out, lse = flash_attention_forward(
        q, k, v, causal=True, block_q=block_q, block_k=block_k,
        interpret=True, return_lse=True)
    assert out.shape == shape + (128,)
    np.testing.assert_allclose(out, _full(q, k, v), atol=2e-5)
    assert fa.fused_backward_fits(128, 192, 128, block_q, block_k,
                                  jnp.float32)
    grads = flash_attention_backward(
        q, k, v, out, lse, do, causal=True, block_q=block_q,
        block_k=block_k, interpret=True)
    _, vjp = jax.vjp(_full, q, k, v)
    for got, want in zip(grads, vjp(do)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-4)


def _plain_head(q, k, v, do):
    """One head of causal attention in float32 at ``HIGHEST`` and its
    vector-Jacobian product: ``(out, dq, dk, dv)``."""
    highest = jax.lax.Precision.HIGHEST

    def attend(q, k, v):
        s = jnp.dot(q, k.T, precision=highest) * q.shape[-1] ** -0.5
        t = q.shape[0]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.dot(jax.nn.softmax(s, -1), v, precision=highest)

    out, vjp = jax.vjp(attend, q, k, v)
    return (out, *vjp(do))


# the JoyAI cell's attention: 32 heads of 8192 tokens, q·k 192 beside v 128
CELL = (1, 32, 8192, 192, 128)


def _cell_operands():
    b, h, t, d_qk, d_v = CELL
    keys = jax.random.split(jax.random.PRNGKey(44), 4)
    q, k = (jax.random.normal(key, (b, h, t, d_qk), jnp.bfloat16)
            for key in keys[:2])
    v, do = (jax.random.normal(key, (b, h, t, d_v), jnp.bfloat16)
             for key in keys[2:])
    return q, k, v, do


def test_compiled_kernels_are_plain_attention_at_the_cells_sizes():
    """On the chip: the compiled forward and fused backward at the JoyAI
    cell's sizes (32 heads of 8192 tokens, q·k 192 beside v 128, bf16
    operands, causal, the auto blocks) against plain attention in
    float32, head by head: the output and all three gradients, each
    within 2 % of its largest entry.  ``correct`` compares a forward pass
    only, so this and the next test are what hold the latent backward at
    those widths (run them there with ``python -c "import sys;
    sys.path.insert(0, 'tests'); import test_joyai_flash as t;
    print(t.test_compiled_kernels_are_plain_attention_at_the_cells_sizes())"``
    from the repository's root: pytest holds the suite to the CPU)."""
    if jax.default_backend() != "tpu":
        pytest.skip("the compiled kernels need the chip")
    assert fa.fused_backward_fits(*CELL[2:])
    q, k, v, do = _cell_operands()

    @jax.jit
    def ours(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
            q, k, v)
        return (out, *vjp(do))

    @jax.jit
    def theirs(q, k, v, do):
        f32 = lambda x: x[0].astype(jnp.float32)
        outs = jax.lax.map(lambda a: _plain_head(*a),
                           (f32(q), f32(k), f32(v), f32(do)))
        return tuple(o[None] for o in outs)

    worst = {}
    for name, got, want in zip(("out", "dq", "dk", "dv"), ours(q, k, v, do),
                               theirs(q, k, v, do)):
        assert got.shape == want.shape, name
        worst[name] = float(jnp.abs(got.astype(jnp.float32) - want).max()
                            / jnp.abs(want).max())
    for name, error in worst.items():
        assert error < 2e-2, (name, error)
    return worst


def test_compiled_fused_backward_is_the_pair_at_the_cells_sizes():
    """On the chip: the compiled fused backward and the compiled
    ``flash_dq`` / ``flash_dkv`` pair at the JoyAI cell's sizes and
    blocks give the same bits in dq, dk and dv (run it as the test
    above)."""
    if jax.default_backend() != "tpu":
        pytest.skip("the compiled kernels need the chip")
    q, k, v, do = _cell_operands()
    b, h, t, d_qk, d_v = CELL
    block = fa.default_block(t)

    def rows(q, k, v, do):
        out, lse = flash_attention_forward(
            q, k, v, causal=True, block_q=block, block_k=block,
            return_lse=True)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        -1).reshape(b * h, t, 1)
        flat = [x.reshape(b * h, t, -1) for x in (q, k, v, do)]
        return (*flat, lse.reshape(b * h, t, 1), delta)

    operands = jax.jit(rows)(q, k, v, do)
    fused = jax.jit(lambda *a: fa._backward_fused(
        *a, True, block, block, False, fa.FUSED_BWD_WIDE_VMEM))(*operands)
    pair = jax.jit(lambda *a: fa._backward_pair(
        *a, True, block, block, False))(*operands)
    differing = {}
    for name, got, want in zip(("dq", "dk", "dv"), fused, pair):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        differing[name] = int(jnp.sum(got != want))
    assert differing == {"dq": 0, "dk": 0, "dv": 0}, differing
    return differing


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("block_q,block_k", [(64, 64), (32, 64)])
def test_the_fused_backward_is_the_pair_at_two_widths(dtype, block_q,
                                                      block_k):
    """At q·k 192 beside v 128 the fused kernel is the pair's arithmetic
    in the pair's order: the same bits in dq, dk and dv, whether dq
    accumulates in scratch (bf16) or in its own block (fp32)."""
    rng = np.random.default_rng(6)
    shape = (1, 2, 256)
    q, k = (jnp.asarray(rng.normal(size=shape + (192,)), dtype)
            for _ in range(2))
    v, do = (jnp.asarray(rng.normal(size=shape + (128,)), dtype)
             for _ in range(2))
    out, lse = flash_attention_forward(q, k, v, causal=True,
                                       block_q=block_q, block_k=block_k,
                                       interpret=True, return_lse=True)
    flat = [x.reshape(2, 256, -1) for x in (q, k, v, do)]
    rows = [lse.reshape(2, 256, 1),
            jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    -1).reshape(2, 256, 1)]
    fused = fa._backward_fused(*flat, *rows, True, block_q, block_k, True)
    pair = fa._backward_pair(*flat, *rows, True, block_q, block_k, True)
    for got, want, width in zip(fused, pair, (192, 192, 128)):
        assert got.shape == want.shape == (2, 256, width)
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


# sha256 of each text, taken on the tree before the latent widths, the
# routed scale and the shared expert came in: the equal-width kernels'
# jaxpr (the TPU branch, the fused backward and the pair) and the SGP
# steps of the lfm2-shaped (routed scale 1, no shared expert) and
# Olmo-shaped models, ``lower(...).as_text()`` as test_lfm2_moe.py takes
# them.  A PR that means to change them re-takes them the same way.
PARENTS_KERNELS = {
    1024: "82a4e2d085fbaa218872f7b942310a65e65767f5b3698d9c74f43e858ce4ba3a",
    16384: "0eb6ded863b569b73c654b3a56f88dd842a7bfd3542783bb3d7e1c3867a16771",
}
PARENTS_STEPS = {
    "lfm2_shaped":
        "0b4be584eb72e1d3f270e14e7ae58f9b7a8452eff7e9ab38287023764fc9bcfd",
    "olmo_shaped":
        "0f60ec2d52ca739930f34e336f6d98c7e6f3346f37e59e47b6e60377af49ed97",
}


@pytest.mark.parametrize("t", sorted(PARENTS_KERNELS))
def test_equal_widths_build_the_kernels_they_built_before(t):
    x = jax.ShapeDtypeStruct((1, 2, t, 64), jnp.bfloat16)
    loss = lambda q, k, v: fa._flash(q, k, v, True, 512, 512).astype(
        jnp.float32).sum()
    # the products' precision as the program leaves it (the file's fixture
    # asks for the highest)
    with jax.default_matmul_precision(None):
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            x, x, x))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_KERNELS[t]


@pytest.mark.parametrize("name", sorted(PARENTS_STEPS))
def test_the_earlier_expert_and_hybrid_steps_lower_as_before(name):
    source = _share(0, 4) if name == "lfm2_shaped" else OLMO
    with jax.default_matmul_precision("default"):
        train_fn, state = _sgp_step(TransformerLM(config_from_source(
            source, dtype=jnp.bfloat16, attn_impl="full", remat=True)), 24)
        tokens = jnp.zeros((1, 2, 24), jnp.int32)
        text = train_fn.lower(state, tokens, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_STEPS[name]


def test_a_scale_of_one_and_no_shared_expert_emit_no_operation():
    x = jnp.ones((8, 16))
    args = (x, jnp.ones((16, 8)), jnp.zeros(8), jnp.ones((4, 16, 8)),
            jnp.ones((4, 4, 16)))
    count = lambda **kw: len(jax.make_jaxpr(lambda *a: topk_moe_ffn(
        *a, per_token=2, **kw))(*args).eqns)
    plain_layer = count()
    assert count(scale=1.0) == plain_layer
    assert count(scale=2.5) == plain_layer + 1
    shared = (jnp.ones((16, 8)), jnp.ones((4, 16)))
    assert count(shared=shared) > plain_layer
    text = jax.jit(lambda *a: topk_moe_ffn(*a, per_token=2)).lower(
        *args).as_text(debug_info=True)
    assert names.SCOPE_MOE_SHARED not in text


def test_the_step_carries_the_new_scopes_and_the_modules_loss():
    model = _model(dtype=jnp.bfloat16, remat=True)
    train_fn, state = _sgp_step(model, SEQ)
    tokens, targets = _batch()
    text = train_fn.lower(state, tokens[None], targets[None]).as_text(
        debug_info=True)
    for scope in (names.SCOPE_MLA, names.SCOPE_MTP, names.SCOPE_MOE_SHARED,
                  names.SCOPE_MOE_EXPERTS, names.SCOPE_LM_HEAD):
        assert scope in text, scope
    with jax.default_matmul_precision("default"):
        _, metrics = train_fn(state, tokens[None], targets[None])
    # the objective is L_main + 0.3 L_mtp: above the trunk's own
    main = float(jnp.log(metrics["ppl"][0]))
    assert MTP_LOSS_WEIGHT == plain.MTP_WEIGHT == 0.3
    assert float(metrics["loss"][0]) > main + 0.3 * 0.5 * np.log(96)


def test_the_sign_rule_brings_a_skewed_router_within_its_band():
    """Scores with a skew of several times the mean load on some experts:
    the rule's bias brings every expert within 5 % of the mean, and a
    layer that starts inside the band is left as it is."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    t, e, k = 2048, 32, 4
    skew = 0.6 * jax.random.normal(keys[0], (e,))
    scores = jax.nn.sigmoid(jax.random.normal(keys[1], (t, e)) + skew)
    before = jnp.bincount(jax.lax.top_k(scores, k)[1].reshape(-1), length=e)
    mean = t * k / e
    assert float(before.max()) > 2 * mean
    bias, loads, steps = jax.jit(lambda s: balance_bias(
        s, jnp.zeros(e), k, band=0.05))(scores)
    assert int(loads.sum()) == t * k
    assert float(jnp.abs(loads - mean).max()) <= 0.05 * mean
    assert 0 < int(steps) < 4000
    # the loads it reports are the bias's own
    again = jnp.bincount(jax.lax.top_k(scores + bias, k)[1].reshape(-1),
                         length=e)
    np.testing.assert_array_equal(again, loads)
    _, _, none = balance_bias(scores, bias, k, band=0.05)
    assert int(none) == 0


def test_the_model_is_balanced_layer_after_layer_in_one_program():
    model = _model()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, SEQ),
                                                          jnp.int32))
    params = params["params"]
    tokens = jax.random.randint(jax.random.PRNGKey(9), (16, 64), 0, 96)
    scores_of = lambda p: model.apply({"params": p}, tokens,
                                      mutable=["moe_scores"])[1]["moe_scores"]
    layers = [(name, "moe") for name in EXPERT_LAYERS]
    balanced, loads, steps = jax.jit(lambda p: balance_expert_biases(
        scores_of, p, layers, 2, band=0.1))(params)
    mean = 16 * 64 * 2 / 8
    assert loads.shape == (3, 8) and steps.shape == (3,)
    assert float(jnp.abs(loads - mean).max()) <= 0.1 * mean
    # each layer's loads are those of the balanced model's own forward pass
    _, sown = model.apply({"params": balanced}, tokens,
                          mutable=["moe_metrics"])
    for i, name in enumerate(EXPERT_LAYERS):
        rows = sown["moe_metrics"][name]["moe"]["expert_rows"][0]
        np.testing.assert_array_equal(rows, loads[i])
    # only the biases moved
    moved = jax.tree.map(lambda a, b: bool((a != b).any()), params, balanced)
    assert {jax.tree_util.keystr(p) for p, m in
            jax.tree_util.tree_leaves_with_path(moved) if m} \
        <= {f"['{n}']['moe']['expert_bias']" for n in EXPERT_LAYERS}


@pytest.mark.parametrize("attn_impl", ["ring", "ring_flash"])
def test_the_module_refuses_a_sharded_sequence(attn_impl):
    """Under sequence sharding a shard's last row would take its own first
    token as the next one: the module refuses it rather than train another
    objective."""
    with pytest.raises(ValueError, match="unsharded sequence"):
        config_from_source(SOURCE, dtype=jnp.float32, attn_impl=attn_impl,
                           seq_axis="seq")
    cut = {**SOURCE, "num_nextn_predict_layers": 0}
    config_from_source(cut, dtype=jnp.float32, attn_impl=attn_impl,
                       seq_axis="seq").check_pattern()


@pytest.mark.parametrize("change, message", [
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"num_key_value_heads": 2}, "num_key_value_heads"),
    ({"qk_head_dim": 16}, "qk_head_dim"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
])
def test_a_source_the_model_does_not_compute_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        config_from_source({**SOURCE, **change})


def test_model_json_is_the_one_way_in(tmp_path):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(SOURCE))
    args = gossip_lm.parse_args(["--model_json", str(path)])
    assert (args.vocab_size, args.d_model, args.n_layers, args.n_heads,
            args.d_ff) == (96, 32, 3, 4, 48)
    model = gossip_lm.model_from_args(args, "full")
    assert model.cfg.mla.v_head_dim == 6 and model.cfg.mtp_layers == 1
    assert model.cfg.experts.scale == 2.5 and model.cfg.experts.d_shared == 16


def test_gossip_lm_trains_it_from_one_flag(tmp_path):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(SOURCE))
    with jax.default_matmul_precision("default"):
        out = gossip_lm.main([
            "--model_json", str(path), "--world_size", "2", "--seq_len",
            "32", "--batch_size", "8", "--lr", "4.0", "--num_steps", "30",
            "--corpus_tokens", "20000", "--remat", "True",
            "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(out["final_loss"])
    # the objective: the trunk's loss and 0.3 of the module's
    assert out["final_loss"] < 1.3 * np.log(96)
