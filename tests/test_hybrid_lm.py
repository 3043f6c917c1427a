"""The hybrid state-space / attention LM (models/transformer.py with a
layer pattern, models/ssm.py, ops/ssd.py) against its plain reference
(benchmark/reference/granite_hybrid.py) at toy widths on the CPU: logits,
loss and gradients; the chunked scan against the recurrence step by step;
grouped-query heads, no positions, the tied head, the sliced vocabulary;
the ``--model_json`` way in; the scopes in the lowered step."""

import functools
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_hybrid as plain
from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.models import PipelineStageLM
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerConfig, TransformerLM, _Attention, config_from_source)
from stochastic_gradient_push_tpu.ops.ssd import ssd_chunked
from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS
from stochastic_gradient_push_tpu.run import gossip_lm
from stochastic_gradient_push_tpu.telemetry import names
from stochastic_gradient_push_tpu.topology import (
    NPeerDynamicDirectedExponentialGraph, build_schedule)
from stochastic_gradient_push_tpu.train import LRSchedule, sgd
from stochastic_gradient_push_tpu.train.lm import (
    build_lm_train_step, init_lm_state, lm_loss, make_dp_sp_mesh,
    shard_lm_train_step)

# the module, not the function the package exports under the same name
fa = importlib.import_module(
    "stochastic_gradient_push_tpu.ops.flash_attention")

# the published model's keys at toy widths: two key-value heads under four
# query heads, one group of B and C under eight scan heads, chunks of 8
SOURCE = {
    "model_type": "granitemoehybrid", "hidden_size": 32,
    "shared_intermediate_size": 64, "intermediate_size": 64,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 96,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "tie_word_embeddings": True,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 8,
    "hidden_act": "silu", "normalization_function": "rmsnorm",
    "num_local_experts": 0, "attention_bias": False}
SEQ = 24        # three chunks of 8


def _model(dtype=jnp.float32, **over):
    return TransformerLM(config_from_source(
        {**SOURCE, **over}, dtype=dtype, attn_impl="full"))


def _random_params(model, tokens, seed=5):
    """Seeded random weights on every leaf: the initialisation leaves the
    scales at 1, the biases at 0 and ``A_log`` on its grid."""
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _tokens(seed=1, batch=2, seq=SEQ, vocab=96):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                                vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _worst(ours, theirs):
    """Largest difference of any leaf over that leaf's largest value."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()
                           / (jnp.abs(b).max() + 1e-12)), ours, theirs)))


# float32 against float32 differ by the order of the sums; bfloat16 keeps
# 8 bits, and a gradient passes through some thirty rounded products
@pytest.mark.parametrize("dtype,logit_tol,loss_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5, 1e-3), (jnp.bfloat16, 3e-2, 3e-3, 0.5)],
    ids=["fp32", "bf16"])
def test_program_agrees_with_the_plain_reference(dtype, logit_tol, loss_tol,
                                                 grad_tol):
    model = _model(dtype)
    tokens, targets = _tokens()
    params = _random_params(model, tokens)

    @jax.jit
    def both(params):
        with jax.default_matmul_precision("highest"):
            return (model.apply({"params": params}, tokens),
                    plain.lm_logits(params, tokens, SOURCE),
                    jax.value_and_grad(lambda p: lm_loss(
                        model.apply({"params": p}, tokens), targets))(params),
                    plain.loss_and_grads(params, tokens, targets, SOURCE))

    ours, theirs, (loss, grads), (ref_loss, ref_grads) = both(params)
    assert ours.shape == (2, SEQ, 96) and ours.dtype == jnp.float32
    assert float(jnp.abs(ours - theirs).max() / jnp.abs(theirs).max()) \
        < logit_tol
    assert abs(float(loss) - float(ref_loss)) < loss_tol
    assert float(plain.lm_loss(theirs, targets)) == pytest.approx(
        float(ref_loss), abs=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert _worst(grads, ref_grads) < grad_tol


@pytest.mark.parametrize("chunks", [1, 2, 3])
@pytest.mark.parametrize("chunk", [4, 16])
def test_chunked_scan_is_the_recurrence(chunk, chunks):
    t, h, p, g, n = chunk * chunks, 4, 8, 2, 16
    kx, kd, ka, kb, kc, ky = jax.random.split(jax.random.PRNGKey(chunk), 6)
    x = jax.random.normal(kx, (2, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(kd, (2, t, h)))
    a = -jnp.exp(jax.random.normal(ka, (h,)))
    b = jax.random.normal(kb, (2, t, g, n))
    c = jax.random.normal(kc, (2, t, g, n))
    probe = jax.random.normal(ky, (2, t, h, p))

    @jax.jit
    def through(x, dt, a, b, c):
        with jax.default_matmul_precision("highest"):
            return [(scan(x, dt, a, b, c), jax.value_and_grad(
                lambda *args: (scan(*args) * probe).sum(),
                argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c))
                for scan in (functools.partial(ssd_chunked, chunk=chunk),
                             plain.ssm_recurrence)]

    (y, (value, grads)), (ref_y, (ref_value, ref_grads)) = through(
        x, dt, a, b, c)
    np.testing.assert_allclose(y, ref_y, rtol=1e-4, atol=1e-4)
    assert float(value) == pytest.approx(float(ref_value), rel=1e-4,
                                         abs=1e-3)
    assert _worst(grads, ref_grads) < 1e-4


def test_chunked_scan_pads_a_length_its_chunk_does_not_divide():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (1, 11, 2, 4))
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, 11, 2)))
    a = -jnp.ones((2,))
    b = jax.random.normal(k[2], (1, 11, 1, 8))
    c = jax.random.normal(k[3], (1, 11, 1, 8))
    np.testing.assert_allclose(
        jax.jit(functools.partial(ssd_chunked, chunk=4))(x, dt, a, b, c),
        jax.jit(plain.ssm_recurrence)(x, dt, a, b, c), rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="groups"):
        ssd_chunked(x, dt, a, jnp.zeros((1, 11, 3, 8)), c, 4)


def test_decay_over_a_whole_chunk_leaves_values_and_gradients_finite():
    """The published initialisation decays fast: dt ~ 1.3, A down to -64,
    so a chunk's whole log decay reaches -2e4; above the diagonal the
    difference is as large and positive, and is masked before the
    exponential."""
    h, t = 64, 512
    x = jnp.ones((1, t, h, 4))
    dt = jnp.full((1, t, h), 1.3)
    a = -jnp.arange(1.0, h + 1)
    b = c = jnp.ones((1, t, 1, 8))
    value, grads = jax.jit(jax.value_and_grad(
        lambda *args: ssd_chunked(*args, 256).sum(),
        argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    assert np.isfinite(float(value))
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _interpreted_flash(q, k, v, causal, block_q, block_k):
    return fa.flash_attention_forward(q, k, v, causal=causal, block_q=16,
                                      block_k=16, interpret=True)


def _interpreted_fwd(q, k, v, causal, block_q, block_k):
    out, lse = fa.flash_attention_forward(
        q, k, v, causal=causal, block_q=16, block_k=16, interpret=True,
        return_lse=True)
    return out, (q, k, v, out, lse)


def _interpreted_bwd(causal, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    return fa.flash_attention_backward(q, k, v, out, lse, g, causal=causal,
                                       block_q=16, block_k=16,
                                       interpret=True)


_interpreted_flash.defvjp(_interpreted_fwd, _interpreted_bwd)


@pytest.mark.parametrize("impl", ["full", "flash"])
def test_grouped_query_attention_is_the_reference_with_k_v_repeated(
        impl, monkeypatch):
    """Four query heads over two key-value heads, scale 1/8 at heads of 8
    (not ``head_dim ** -0.5``), no positions; ``flash`` is the Pallas
    kernels, forward and backward, in interpret mode."""
    if impl == "flash":
        monkeypatch.setattr(fa, "flash_attention", _interpreted_flash)
    cfg = config_from_source(SOURCE, attn_impl=impl)
    attention = _Attention(cfg)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32))
    params = attention.init(jax.random.PRNGKey(3), u, jnp.arange(32))
    assert params["params"]["k"]["kernel"].shape == (32, 16)
    assert params["params"]["q"]["kernel"].shape == (32, 32)
    probe = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 32))

    def ours(p, u):
        return (attention.apply(p, u, jnp.arange(32)) * probe).sum()

    def theirs(p, u):
        # the reference's grouped einsum, and the same thing written with
        # k and v repeated to the query heads
        out = plain._attention(u, p["params"], SOURCE, None, lambda a: a)
        return (out * probe).sum()

    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(
            ours, argnums=(0, 1)))(params, u)
        ref_value, ref_grads = jax.jit(jax.value_and_grad(
            theirs, argnums=(0, 1)))(params, u)
        k = (u @ params["params"]["k"]["kernel"]).reshape(2, 32, 2, 8)
        q = (u @ params["params"]["q"]["kernel"]).reshape(2, 32, 4, 8)
        v = (u @ params["params"]["v"]["kernel"]).reshape(2, 32, 2, 8)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q,
                            jnp.repeat(k, 2, axis=2)) * 0.125
        scores = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), scores,
                           -jnp.inf)
        repeated = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
            jnp.repeat(v, 2, axis=2)).reshape(2, 32, 32) \
            @ params["params"]["o"]["kernel"]
    assert float(value) == pytest.approx(float(ref_value), rel=1e-4)
    assert float((repeated * probe).sum()) == pytest.approx(
        float(ref_value), rel=1e-4)
    assert _worst(grads, ref_grads) < 1e-3


def test_no_positions_means_no_table_and_no_order_among_earlier_tokens():
    cfg = config_from_source(SOURCE, attn_impl="full")
    attention = _Attention(cfg)
    at = jnp.arange(16)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 32))
    params = attention.init(jax.random.PRNGKey(3), u, at)
    assert set(params["params"]) == {"q", "k", "v", "o"}
    model = _model()
    tree = model.init(jax.random.PRNGKey(0), _tokens()[0])["params"]
    assert set(tree) == {"embed", "ln_f"} | {f"block_{i}" for i in range(4)}
    assert set(tree["block_2"]) == {"attn", "ln1", "ln2", "gate_up", "down"}
    # a constant sequence reads the same at every position ...
    constant = jnp.broadcast_to(u[:, :1], u.shape)
    out = attention.apply(params, constant, at)
    np.testing.assert_allclose(out, jnp.broadcast_to(out[:, :1], out.shape),
                               atol=1e-6)
    # ... and, what rotary positions would not allow, the last position
    # does not see the order of those before it
    shuffled = jnp.concatenate([u[:, 14::-1], u[:, 15:]], axis=1)
    last = lambda a, p, x: a.apply(p, x, at)[:, -1]
    np.testing.assert_allclose(last(attention, params, u),
                               last(attention, params, shuffled), atol=1e-5)
    rotary = _Attention(cfg._replace(positions="rotary"))
    assert float(jnp.abs(last(rotary, params, u)
                         - last(rotary, params, shuffled)).max()) > 1e-3


def test_tied_head_is_one_leaf_whose_gradient_sums_both_uses():
    tied = _model()
    tokens, targets = _tokens()
    params = _random_params(tied, tokens)
    assert "lm_head" not in params
    untied = TransformerLM(tied.cfg._replace(tie_embeddings=False))
    table = params["embed"]["embedding"]
    split = {**params, "lm_head": {"kernel": table.T}}
    loss = lambda model: lambda p: lm_loss(
        model.apply({"params": p}, tokens), targets)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(loss(tied)))(params)
        split_value, split_grads = jax.jit(
            jax.value_and_grad(loss(untied)))(split)
    assert float(value) == pytest.approx(float(split_value), abs=1e-6)
    np.testing.assert_allclose(
        grads["embed"]["embedding"],
        split_grads["embed"]["embedding"]
        + split_grads["lm_head"]["kernel"].T, rtol=1e-4, atol=1e-7)
    # both uses are there: neither part alone is the whole gradient
    assert float(jnp.abs(split_grads["lm_head"]["kernel"]).max()) > 0
    assert float(jnp.abs(split_grads["embed"]["embedding"]).max()) > 0


def test_sliced_vocabulary_gives_the_whole_tables_first_columns():
    """A quarter of the table's rows, ids drawn from that quarter: the
    logits are the first quarter of the whole table's columns."""
    whole, quarter = _model(), _model(vocab_size=24)
    tokens, _ = _tokens(vocab=24)
    params = _random_params(whole, tokens)
    held = {**params, "embed": {
        "embedding": params["embed"]["embedding"][:24]}}
    @jax.jit
    def logits(held, params):
        with jax.default_matmul_precision("highest"):
            return (quarter.apply({"params": held}, tokens),
                    whole.apply({"params": params}, tokens),
                    plain.lm_logits(held, tokens,
                                    {**SOURCE, "vocab_size": 24}),
                    plain.lm_logits(params, tokens, SOURCE))

    ours_held, ours_whole, ref_held, ref_whole = logits(held, params)
    assert ours_held.shape == (2, SEQ, 24)
    np.testing.assert_allclose(ours_held, ours_whole[..., :24],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ref_held, ref_whole[..., :24],
                               rtol=1e-5, atol=1e-6)


def test_defaults_are_still_the_dense_model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, d_ff=64)
    tree = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert set(tree) == {"embed", "block_0", "block_1", "ln_f", "lm_head"}
    assert set(tree["block_0"]) == {"attn", "ln1", "ln2", "up", "down"}
    assert set(tree["block_0"]["ln1"]) == {"scale", "bias"}
    assert set(tree["block_0"]["up"]) == {"kernel", "bias"}


@pytest.mark.parametrize("change,message", [
    ({"model_type": "mixtral"}, "model_type"),
    ({"num_local_experts": 8}, "num_local_experts"),
    ({"position_embedding_type": "alibi"}, "position_embedding_type"),
    ({"mamba_expand": 3}, "mamba_expand"),
    ({"layer_types": ["mamba"] * 3}, "layer_types names 3"),
    ({"layer_types": ["mamba", "window", "attention", "mamba"]}, "window"),
])
def test_a_source_the_model_does_not_compute_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        config_from_source({**SOURCE, **change})


def test_a_pattern_is_refused_where_the_stack_is_uniform():
    cfg = config_from_source(SOURCE)
    with pytest.raises(ValueError, match="uniform"):
        PipelineStageLM(cfg, n_local_layers=2).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="needs the mixer's sizes"):
        TransformerLM(cfg._replace(ssm=None)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture
def model_json(tmp_path):
    path = tmp_path / "toy_hybrid.json"
    # keys beside the source's own (a benchmark configuration's) are ignored
    path.write_text(json.dumps({**SOURCE, "builder": "x", "assumed": {}}))
    return str(path)


def test_model_json_is_the_one_way_in(model_json):
    args = gossip_lm.build_parser().parse_args(
        ["--model_json", model_json, "--precision", "bf16", "--remat",
         "True", "--seq_len", "32"])
    gossip_lm.resolve_model_json(args)
    assert (args.vocab_size, args.d_model, args.n_layers, args.n_heads,
            args.d_ff) == (96, 32, 4, 4, 64)
    cfg = gossip_lm.model_from_args(args, "full").cfg
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba")
    assert cfg.dtype == jnp.bfloat16 and cfg.remat and cfg.max_len == 32
    assert (cfg.n_kv_heads, cfg.norm, cfg.mlp, cfg.positions,
            cfg.tie_embeddings) == (2, "rmsnorm", "swiglu", "none", True)
    assert cfg.ssm.chunk_size == 8 and cfg.ssm.d_state == 16

    # without the flag: the dense model of the five size flags, as before
    args = gossip_lm.build_parser().parse_args(["--d_model", "64"])
    gossip_lm.resolve_model_json(args)
    dense = gossip_lm.model_from_args(args, "full").cfg
    assert dense == TransformerConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=8, d_ff=1024,
        max_len=256)


@pytest.mark.parametrize("flags", [["--pp", "2"], ["--sp", "2"],
                                   ["--tp", "2"], ["--moe_experts", "4"]])
def test_model_json_refuses_the_meshes_a_pattern_is_not_built_for(
        model_json, flags):
    args = gossip_lm.build_parser().parse_args(
        ["--model_json", model_json] + flags)
    with pytest.raises(SystemExit, match="flat data-parallel"):
        gossip_lm.resolve_model_json(args)


def test_gossip_lm_trains_the_pattern_from_one_flag(model_json, tmp_path):
    out = gossip_lm.main([
        "--model_json", model_json, "--world_size", "2", "--seq_len", "32",
        "--batch_size", "8", "--lr", "8.0", "--num_steps", "30",
        "--corpus_tokens", "20000", "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(out["final_loss"])
    assert out["final_loss"] < np.log(96)


def _locations(text: str) -> str:
    return "\n".join(re.findall(r'^#loc\d+ = loc\((.*)\)$', text, re.M))


def _lowered_step_locations(model, seq: int) -> str:
    """The op names in the lowered text of one SGP step of ``model``."""
    mesh = make_dp_sp_mesh(1, 1)
    alg = sgp(build_schedule(NPeerDynamicDirectedExponentialGraph(
        1, peers_per_itr=1)), GOSSIP_AXIS)
    tx = sgd(momentum=0.9, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.1, batch_size=2, world_size=1,
                     decay_schedule={}, warmup=False)
    step = build_lm_train_step(model, alg, tx, lrs, itr_per_epoch=10,
                               seq_axis=None)
    state = init_lm_state(model, mesh, alg, tx, dp=1, sp=1, batch_size=2,
                          block_len=seq, seq_axis=None)
    tokens = jnp.zeros((1, 2, seq), jnp.int32)
    return _locations(shard_lm_train_step(step, mesh, seq_axis=None).lower(
        state, tokens, tokens).as_text(debug_info=True))


def test_the_lowered_step_holds_the_mixers_scopes():
    where = _lowered_step_locations(_model(remat=True), SEQ)
    for scope in (names.SCOPE_SSM_MIXER, names.SCOPE_SSD,
                  names.SCOPE_CONV1D):
        assert re.search(rf'[("/]{re.escape(scope)}[)/"]', where), scope
    # nested: the scan and the convolution inside the mixer, the mixer
    # inside the step's forward scope
    assert re.search(
        rf"{re.escape(names.SCOPE_FORWARD)}.*"
        rf"{re.escape(names.SCOPE_SSM_MIXER)}.*{re.escape(names.SCOPE_SSD)}",
        where)
    assert re.search(rf"{re.escape(names.SCOPE_SSM_MIXER)}.*"
                     rf"{re.escape(names.SCOPE_CONV1D)}", where)
    # on this backend the scan is XLA operations: no kernel of its own
    assert names.KERNEL_SSD_FWD not in where


@pytest.mark.parametrize("tied", [False, True], ids=["dense", "tied_hybrid"])
def test_the_lowered_step_holds_the_heads_scope(tied):
    """``lm.head`` is one name of the vocabulary, around the head's
    product (an ``lm_head`` kernel, or the tied table's ``attend``) and
    around the loss, each inside the step's forward scope and in its
    transpose: where ``lm_head_ms`` looks for them."""
    assert [v for k, v in vars(names).items() if k.startswith("SCOPE_")
            ].count(names.SCOPE_LM_HEAD) == 1
    with open(os.path.join(os.path.dirname(plain.__file__), os.pardir,
                           "layer_metrics", "lm_head_ms.json")) as f:
        assert re.fullmatch(json.load(f)["params"]["pattern"],
                            names.SCOPE_LM_HEAD)
    if tied:
        model, product = _model(remat=True), "embed.attend"
    else:
        model, product = TransformerLM(TransformerConfig(
            vocab_size=96, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_len=SEQ)), "lm_head"
    assert model.cfg.tie_embeddings == tied
    where = _lowered_step_locations(model, SEQ)
    head = re.escape(names.SCOPE_LM_HEAD)
    # the loss: the sum of exponentials and the target's masked sum, and
    # in the transpose the select that the comparison turns into
    for way, of_the_loss in ((r"/jvp\(", "reduce_sum"),
                             (r"/transpose\(jvp\(", "select_n")):
        under = rf"{way}{re.escape(names.SCOPE_FORWARD)}\)+/"
        assert re.search(
            rf"{under}TransformerLM/{head}/{re.escape(product)}/dot_general",
            where), (way, product)
        assert re.search(rf"{under}{head}/{of_the_loss}", where), way
    # under the scope nothing scatters, and nothing lies outside the
    # step's forward scope
    for line in where.splitlines():
        if f"/{names.SCOPE_LM_HEAD}/" in line:
            assert "scatter" not in line, line
            assert names.SCOPE_FORWARD in line, line


def test_the_lowered_step_holds_the_scan_kernels_under_the_scans_scope(
        monkeypatch):
    """At the smallest sizes the kernel pair tiles, with its rule answered
    for it and the kernels interpreted: ``ssd_fwd`` in the forward pass
    and ``ssd_bwd`` in its transpose, each inside ``lm.ssd`` inside the
    mixer's scope inside the step's forward scope, which is where
    ``ssd_ms``, ``ssm_mixer_ms``, ``fwd_ms`` and ``bwd_ms`` look for
    them."""
    ssd = importlib.import_module("stochastic_gradient_push_tpu.ops.ssd")
    ssm = importlib.import_module("stochastic_gradient_push_tpu.models.ssm")
    monkeypatch.setattr(ssd, "kernel_fits", lambda *args: True)
    monkeypatch.setattr(ssd, "chunks_kernel", functools.partial(
        ssd.chunks_kernel, interpret=True))
    assert ssm.ssd_chunked is ssd.ssd_chunked
    # an interpreted kernel's scratch is not typed varying over the mesh
    # (jax 0.9): the step's shard_map lowers without that check here, as
    # the gossip kernel's interpreted lane does; compiled kernels keep it
    monkeypatch.setattr(jax, "shard_map", functools.partial(
        jax.shard_map, check_vma=False))
    model = _model(remat=True, hidden_size=64, mamba_n_heads=2,
                   mamba_d_head=64, mamba_d_state=128, mamba_chunk_size=128)
    where = _lowered_step_locations(model, 128)
    # the kernels' wrappers are jitted (one trace and lowering for every
    # layer): the call carries the scopes, the callee the kernel's name
    under = (rf"{re.escape(names.SCOPE_FORWARD)}\)+/.*"
             rf"{re.escape(names.SCOPE_SSM_MIXER)}/"
             rf"{re.escape(names.SCOPE_SSD)}/")
    assert re.search(rf'/jvp\({under}jit\(ssd_forward\)', where)
    assert re.search(rf'/transpose\(jvp\({under}jit\(ssd_backward\)', where)
    for kernel in (names.KERNEL_SSD_FWD, names.KERNEL_SSD_BWD):
        assert f'"{kernel}/pallas_call"' in where
