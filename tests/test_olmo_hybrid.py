"""The ``olmo_hybrid`` family (models/transformer.py with a
``linear_attention`` mixer, models/gated_deltanet.py, norms after each
sublayer and q/k norms over the whole projection) against its plain
reference (benchmark/reference/olmo_hybrid.py, the delta rule token by
token) at toy widths on the CPU: logits, loss and every gradient leaf; the
mixer's counter of writes with ``beta`` above 1; the ``--model_json`` way in
and its refusals; and the lowered steps of the models that were there
before, unchanged."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as plain
from test_lfm2_moe import SOURCE as LFM2, _sgp_step
from stochastic_gradient_push_tpu.models.gated_deltanet import DeltaNetConfig
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerConfig, TransformerLM, config_from_source)
from stochastic_gradient_push_tpu.run import gossip_lm
from stochastic_gradient_push_tpu.telemetry import names
from stochastic_gradient_push_tpu.train.lm import lm_loss

# the published model's keys at toy widths: three gated delta-rule layers
# and one full attention layer, negative eigenvalues allowed, no positions
SOURCE = {
    "model_type": "olmo_hybrid", "vocab_size": 96, "hidden_size": 32,
    "intermediate_size": 48, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "hidden_act": "silu", "max_position_embeddings": 256,
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
SEQ = 40           # five chunks of 8
LINEAR = ("block_0", "block_1", "block_2")


def _model(source=SOURCE, dtype=jnp.float32, chunk=8, **runtime):
    cfg = config_from_source(source, dtype=dtype, attn_impl="full",
                             **runtime)
    return TransformerLM(cfg._replace(
        delta=cfg.delta._replace(chunk_size=chunk)))


def _params(model, seed=0):
    """Seeded weights with every norm's weight moved off one, so that each
    one matters."""
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


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_program_agrees_with_the_plain_reference(remat):
    """Logits, loss and every gradient leaf, through ``nn.remat`` as the
    cell runs it: the chunked rule against the rule token by token."""
    model = _model(remat=remat)
    params = _params(model)
    tokens, targets = _batch()
    ours = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    theirs = jax.jit(lambda p: plain.lm_logits(p, tokens, SOURCE))(params)
    scale = float(jnp.abs(theirs).max())
    assert float(jnp.abs(ours - theirs).max()) < 2e-5 * scale
    loss, grads = jax.jit(jax.value_and_grad(lambda p: lm_loss(
        model.apply({"params": p}, tokens), targets)))(params)
    their_loss, their_grads = jax.jit(lambda p: plain.loss_and_grads(
        p, tokens, targets, SOURCE))(params)
    assert float(loss) == pytest.approx(float(their_loss), abs=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(their_grads)
    # float32's rounding alone reads up to 2.4e-4 of a leaf's largest
    # gradient here, and as much with chunks of one step (no solve, the
    # recurrence's own order), so the limit is 1e-3; a term left out
    # reads orders of magnitude more
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, ours_g), theirs_g in zip(flat, jax.tree.leaves(their_grads)):
        top = float(jnp.abs(theirs_g).max())
        where = jax.tree_util.keystr(path)
        assert top > 0, where
        assert float(jnp.abs(ours_g - theirs_g).max()) < 1e-3 * top, where


def test_the_reference_blocks_of_query_rows_are_one_product():
    model = _model()
    params = _params(model, seed=5)
    tokens, _ = _batch(7)
    whole = plain.lm_logits(params, tokens, SOURCE, q_block=None)
    blocked = plain.lm_logits(params, tokens, SOURCE, q_block=8)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-5)


def _beta_above_one(source):
    model = _model(source)
    params = _params(model)
    _, sown = model.apply({"params": params}, _batch()[0],
                          mutable=["delta_metrics"])
    return [float(sown["delta_metrics"][b]["delta"]["beta_above_one"][0])
            for b in LINEAR]


def test_the_mixer_counts_the_writes_that_overshoot():
    """``beta = 2 sigmoid(b)``: near half the (token, head) pairs write
    with ``beta > 1`` at random weights; a source that does not allow
    negative eigenvalues reads 0 in every layer."""
    shares = _beta_above_one(SOURCE)
    assert len(shares) == 3 and all(0.2 < s < 0.8 for s in shares), shares
    assert _beta_above_one({**SOURCE, "linear_allow_neg_eigval": False}) \
        == [0.0, 0.0, 0.0]


def test_the_counter_stays_out_of_the_step():
    """Only a comparison asks for ``delta_metrics``: the training step's
    metrics carry no new key, and its text holds both scopes."""
    train_fn, state = _sgp_step(_model(remat=True), SEQ)
    tokens, targets = _batch()
    text = train_fn.lower(state, tokens[None], targets[None]).as_text(
        debug_info=True)
    for scope in (names.SCOPE_DELTA_MIXER, names.SCOPE_DELTA_RULE,
                  names.SCOPE_CONV1D, names.SCOPE_LM_HEAD):
        assert scope in text, scope
    state, metrics = train_fn(state, tokens[None], targets[None])
    assert not any("beta" in k for k in metrics)
    assert np.isfinite(float(metrics["loss"][0]))


def test_config_from_source_reads_the_familys_keys():
    cfg = config_from_source(SOURCE)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("attention",)
    assert cfg.delta == DeltaNetConfig(
        n_heads=2, key_head_dim=8, value_head_dim=16, conv_kernel_dim=4,
        allow_neg_eigval=True, chunk_size=64)
    assert (cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.norm_eps) \
        == (48, 4, 4, 1e-6)
    assert cfg.post_norm and cfg.qk_norm == "projection"
    assert cfg.positions == "none" and not cfg.tie_embeddings
    assert cfg.norm == "rmsnorm" and cfg.mlp == "swiglu"


def test_the_norms_sit_where_the_family_puts_them():
    """q and k normed over the whole projection (a weight of all heads'
    width), the block's two norms on the sublayers' outputs, and every
    default as it was."""
    shapes = jax.eval_shape(_model().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, SEQ), jnp.int32))["params"]
    assert shapes["block_3"]["attn"]["q_norm"]["scale"].shape == (32,)
    assert shapes["block_3"]["attn"]["k_norm"]["scale"].shape == (32,)
    assert shapes["block_0"]["delta"]["norm"]["scale"].shape == (16,)
    assert "lm_head" in shapes
    default = TransformerConfig()
    assert (default.post_norm, default.qk_norm, default.delta) \
        == (False, None, None)
    lfm2 = config_from_source(LFM2)
    assert (lfm2.post_norm, lfm2.qk_norm) == (False, "head")


def test_post_norm_is_a_norm_on_each_sublayers_output():
    """With every sublayer's norm weight at 0 a post-normed block adds
    nothing: the logits are the final norm of the embedding through the
    head, whatever the mixers hold."""
    model = _model()
    params = _params(model)
    for block in ("block_0", "block_1", "block_2", "block_3"):
        for norm in ("ln1", "ln2"):
            params[block][norm]["scale"] = jnp.zeros_like(
                params[block][norm]["scale"])
    tokens, _ = _batch()
    logits = model.apply({"params": params}, tokens)
    x = params["embed"]["embedding"][tokens]
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6) \
        * params["ln_f"]["scale"]
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(x @ params["lm_head"]["kernel"]),
                               atol=1e-5)


@pytest.mark.parametrize("change, message", [
    ({"model_type": "olmo3"}, "model_type"),
    ({"layer_types": ["linear_attention", "sliding_attention",
                      "linear_attention", "full_attention"]},
     "sliding_attention"),
    ({"layer_types": ["linear_attention", "full_attention"]},
     "n_layers is 4"),
    # what the family's config.json could say and the model does not
    # compute
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"linear_num_value_heads": 4}, "linear_num_value_heads"),
    ({"rope_parameters": {"rope_theta": 500000}}, "rope_theta"),
])
def test_a_source_the_model_does_not_compute_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        config_from_source({**SOURCE, **change})


def test_a_linear_attention_layer_needs_its_sizes():
    cfg = TransformerConfig(n_layers=2,
                            layer_types=("linear_attention", "attention"))
    with pytest.raises(ValueError, match="TransformerConfig.delta"):
        cfg.check_pattern()


@pytest.fixture()
def model_json(tmp_path):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(SOURCE))
    return str(path)


def test_model_json_is_the_one_way_in(model_json):
    args = gossip_lm.parse_args(["--model_json", model_json])
    assert (args.vocab_size, args.d_model, args.n_layers, args.n_heads,
            args.d_ff) == (96, 32, 4, 4, 48)
    model = gossip_lm.model_from_args(args, "full")
    assert model.cfg.delta.n_heads == 2 and model.cfg.post_norm
    with pytest.raises(SystemExit, match="flat data-parallel"):
        gossip_lm.parse_args(["--model_json", model_json, "--pp", "2"])


def test_gossip_lm_trains_it_from_one_flag(model_json, tmp_path):
    with jax.default_matmul_precision("default"):
        out = gossip_lm.main([
            "--model_json", model_json, "--world_size", "2", "--seq_len",
            "32", "--batch_size", "8", "--lr", "8.0", "--num_steps", "30",
            "--corpus_tokens", "20000", "--remat", "True",
            "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(out["final_loss"])
    assert out["final_loss"] < np.log(96)


# sha256 of ``lower(...).as_text()`` of one SGP step on the CPU, taken from
# the parent's tree (c44a688) by this very function: the lfm2-shaped model
# (per-head q/k norms, norms before each sublayer) lowers to the same text
# after the block and ``_Attention`` learned the family's placements
LFM2_SHAPED_STEP = \
    "1c329269f463a6ae1df8a4a044128403f4a869a7949638010c80ba150ac28d6d"


def test_the_lfm2_shaped_model_lowers_to_the_parents_step():
    with jax.default_matmul_precision("default"):
        model = TransformerLM(config_from_source(
            LFM2, dtype=jnp.bfloat16, attn_impl="full", remat=True))
        train_fn, state = _sgp_step(model, 24)
        tokens = jnp.zeros((1, 2, 24), jnp.int32)
        text = train_fn.lower(state, tokens, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LFM2_SHAPED_STEP
