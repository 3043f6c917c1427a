"""The ``lfm2_moe`` family (models/transformer.py with a ``conv`` mixer
and an ``experts`` feed-forward, models/shortconv.py,
models/moe.py::topk_moe_ffn) against its plain reference
(benchmark/reference/lfm2_moe.py) at toy widths on the CPU: logits, loss
and every gradient leaf with a non-zero selection bias; the shares of the
experts add up to the uncut layer; no token dropped; the bias chooses and
does not weigh; the ``--model_json`` way in and its refusals; and the
lowered steps of the models that were there before, unchanged."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as plain
from test_hybrid_lm import SOURCE as GRANITE
from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.models.moe import (
    ExpertsConfig, topk_moe_ffn)
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerConfig, TransformerLM, config_from_source)
from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS
from stochastic_gradient_push_tpu.run import gossip_lm
from stochastic_gradient_push_tpu.telemetry import names
from stochastic_gradient_push_tpu.topology import (
    NPeerDynamicDirectedExponentialGraph, build_schedule)
from stochastic_gradient_push_tpu.train import LRSchedule, sgd
from stochastic_gradient_push_tpu.train.lm import (
    build_lm_train_step, init_lm_state, lm_loss, make_dp_sp_mesh,
    shard_lm_train_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the published model's keys at toy widths: 8 experts, 4 a token, two
# key-value heads under four query heads, one leading dense layer
SOURCE = {
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 32, "intermediate_size": 96,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "moe_intermediate_size": 24, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 8,
    "num_experts_per_tok": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 96}
SEQ = 24
EXPERT_LAYERS = ("block_1", "block_2", "block_3")


def _share(first, end):
    """The toy source cut to experts ``[first, end)``, as a file says it."""
    return {**SOURCE, "num_experts": end - first,
            "experts_held": [first, end], "experts_routed": 8}


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


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def test_program_agrees_with_the_plain_reference():
    """Logits, loss and every gradient leaf, with a non-zero bias, through
    ``nn.remat`` as the cell runs it."""
    model = _model(remat=True)
    params = _params(model)
    tokens, targets = _batch()
    assert float(jnp.abs(params["block_1"]["moe"]["expert_bias"]).max()) > 0.1
    ours = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
    theirs, routing = jax.jit(
        lambda p: plain.lm_logits(p, tokens, SOURCE))(params)
    assert len(routing) == 3
    scale = float(jnp.abs(theirs).max())
    assert float(jnp.abs(ours - theirs).max()) < 2e-5 * scale
    loss, grads = jax.jit(jax.value_and_grad(lambda p: lm_loss(
        model.apply({"params": p}, tokens), targets)))(params)
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


def test_the_selection_is_the_references_and_a_handed_one_reproduces_it():
    model = _model()
    params = _params(model)
    tokens, _ = _batch()
    _, sown = model.apply({"params": params}, tokens,
                          mutable=["moe_selection"])
    reference = jax.jit(lambda p, selection=None: plain.lm_logits(
        p, tokens, SOURCE, selection=selection))
    free, routing = reference(params)
    selection = [sown["moe_selection"][b]["moe"]["experts"][0]
                 for b in EXPERT_LAYERS]
    for ours, theirs in zip(selection, routing):
        assert ours.shape == (2, SEQ, 4)
        assert float(plain.selection_gap(theirs["chooser"], ours).max()) == 0
        assert (jnp.sort(ours, -1) == jnp.sort(theirs["selection"], -1)).all()
    given, _ = reference(params, selection)
    np.testing.assert_array_equal(np.asarray(given), np.asarray(free))
    # another selection is another result, and the gap says how far off
    other = [(s + 1) % 8 for s in selection]
    moved, routed = reference(params, other)
    assert float(jnp.abs(moved - free).max()) > 1e-3
    assert float(plain.selection_gap(routed[0]["chooser"], other[0]).max()) \
        > 0


def _layer(params, x, held, **over):
    ex = ExpertsConfig(n_experts=8, per_token=4, d_ff=24, held=held)
    p = params["block_2"]["moe"]
    lo, hi = held or (0, 8)
    return topk_moe_ffn(
        x, p["router"], over.get("bias", p["expert_bias"]),
        p["experts_gate_up"][lo:hi], p["experts_down"][lo:hi],
        per_token=ex.per_token, first=ex.first)


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts [0, 4) and [4, 8), each routing over all eight, give parts
    that sum to the plain reference's whole layer; so do the models."""
    params = _params(_model())
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 32))
    whole, routed = plain.experts_ffn(x[None], params["block_2"]["moe"],
                                      SOURCE)
    low, aux_low = _layer(params, x, (0, 4))
    high, aux_high = _layer(params, x, (4, 8))
    np.testing.assert_allclose(np.asarray(low + high), np.asarray(whole[0]),
                               atol=1e-5)
    assert float(jnp.abs(low).max()) > 1e-3 < float(jnp.abs(high).max())
    # every pair lands on exactly one of the two shares
    assert float(aux_low["expert_rows"].sum()
                 + aux_high["expert_rows"].sum()) == 48 * 4
    assert float(aux_low["pairs_not_held"]) \
        == float(aux_high["expert_rows"].sum())
    np.testing.assert_array_equal(np.asarray(aux_low["selection"]),
                                  np.asarray(aux_high["selection"]))
    # the reference, told its share, leaves the same part out
    part, _ = plain.experts_ffn(
        x[None], {**params["block_2"]["moe"],
                  "experts_gate_up": params["block_2"]["moe"][
                      "experts_gate_up"][4:],
                  "experts_down": params["block_2"]["moe"][
                      "experts_down"][4:]}, _share(4, 8))
    np.testing.assert_allclose(np.asarray(high), np.asarray(part[0]),
                               atol=1e-5)


def test_a_cut_model_is_the_reference_given_the_same_share():
    source = _share(4, 8)
    model = _model(source)
    params = _params(model)
    assert params["block_2"]["moe"]["router"].shape == (32, 8)
    assert params["block_2"]["moe"]["experts_down"].shape == (4, 24, 32)
    tokens, _ = _batch()
    ours, sown = model.apply({"params": params}, tokens,
                             mutable=["moe_metrics"])
    theirs, _ = plain.lm_logits(params, tokens, source)
    assert float(jnp.abs(ours - theirs).max()) \
        < 2e-5 * float(jnp.abs(theirs).max())
    counters = sown["moe_metrics"]["block_2"]["moe"]
    assert float(counters["expert_rows"][0].sum()
                 + counters["pairs_not_held"][0]) == 2 * SEQ * 4


def test_no_token_is_dropped_under_a_router_forced_onto_one_expert():
    """Every token's first choice is expert 2: it receives every token, at
    whatever the split, and each token's output is the reference's."""
    params = _params(_model())
    moe = dict(params["block_2"]["moe"])
    moe["expert_bias"] = jnp.zeros(8).at[2].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 32))
    y, aux = _layer({"block_2": {"moe": moe}}, x, None)
    assert float(aux["expert_rows"][2]) == 40          # none dropped
    assert float(aux["expert_rows"].sum()) == 160
    assert float(aux["pairs_not_held"]) == 0
    whole, _ = plain.experts_ffn(x[None], moe, SOURCE)
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole[0]),
                               atol=1e-5)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0
    # held alone, the one expert still gets every row it was sent
    _, alone = _layer({"block_2": {"moe": moe}}, x, (2, 3))
    assert float(alone["expert_rows"][0]) == 40
    assert float(alone["pairs_not_held"]) == 120


def test_the_bias_changes_the_choice_and_not_the_weight():
    params = _params(_model())
    p = params["block_2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (32, 32))
    scores = jax.nn.sigmoid(x @ p["router"])
    _, plain_aux = _layer(params, x, None, bias=jnp.zeros(8))
    _, biased_aux = _layer(params, x, None)
    assert (jnp.sort(plain_aux["selection"], -1)
            != jnp.sort(biased_aux["selection"], -1)).any()
    # the weights are the chosen experts' own scores over their sum: the
    # bias is in neither
    for aux, bias in ((plain_aux, jnp.zeros(8)), (biased_aux,
                                                  p["expert_bias"])):
        chosen = jnp.take_along_axis(scores, aux["selection"], -1)
        y, _ = _layer(params, x, None, bias=bias)
        each = jnp.stack([plain._gated_mlp(
            x, p["experts_gate_up"][e], p["experts_down"][e], plain._same)
            for e in range(8)], 1)                           # [T, 8, D]
        want = (jnp.take_along_axis(each, aux["selection"][..., None], 1)
                * (chosen / (chosen.sum(-1, keepdims=True) + 1e-6))[
                    ..., None]).sum(1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=1e-5)


def test_config_from_source_reads_the_familys_keys():
    cfg = config_from_source(SOURCE)
    assert cfg.layer_types == ("conv", "attention", "conv", "conv")
    assert cfg.ffn_types == ("dense", "experts", "experts", "experts")
    assert cfg.experts == ExpertsConfig(
        n_experts=8, per_token=4, d_ff=24, held=None)
    assert (cfg.d_ff, cfg.n_kv_heads, cfg.qk_norm, cfg.rope_theta) \
        == (96, 2, "head", 1e6)
    assert cfg.tie_embeddings and cfg.norm == "rmsnorm" \
        and cfg.mlp == "swiglu" and cfg.conv_taps == 3
    held = config_from_source(_share(4, 8)).experts
    assert (held.n_experts, held.first, held.n_held) == (8, 4, 4)


def test_the_published_keys_count_8_34_billion_parameters():
    """By shapes alone (``jax.eval_shape``): the benchmark's file with its
    ``published`` values put back is the catalog row."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2_8b_a1b.json")) as f:
        held = json.load(f)
    source = {**held, **held["published"]}
    del source["experts_held"], source["experts_routed"], \
        source["published"]
    assert source["num_hidden_layers"] == 24 and source["num_experts"] == 32
    model = TransformerLM(config_from_source(source, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(leaf.shape))
                             for leaf in jax.tree.leaves(tree))
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    layer_experts = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    total = count(shapes)
    assert total == 18 * conv + 6 * attention + 2 * 3 * 2048 * 7168 \
        + 22 * layer_experts + 48 * 2048 + 65536 * 2048 + 2048
    assert round(total / 1e9, 2) == 8.34
    experts = 22 * 32 * 3 * 2048 * 1792
    # 4 of 32 experts a token: the published "A1.5B"
    assert round((total - experts * 28 / 32) / 1e9, 2) == 1.56


@pytest.mark.parametrize("change, message", [
    ({"model_type": "lfm3"}, "model_type"),
    ({"layer_types": ["conv", "sliding", "conv", "conv"]}, "sliding"),
    ({"layer_types": ["conv", "conv"]}, "n_layers is 4"),
    ({"experts_held": [6, 10], "num_experts": 4, "experts_routed": 8},
     "no part of the router's 8"),
    # what the family's config.json could say and the model does not
    # compute
    ({"conv_bias": True}, "conv_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"routed_scaling_factor": 2.5}, "routed_scaling_factor"),
    ({"num_experts_per_tok": 9}, "9 experts a token of 8"),
])
def test_a_source_the_model_does_not_compute_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        config_from_source({**SOURCE, **change})


@pytest.fixture()
def model_json(tmp_path):
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(_share(0, 4)))
    return str(path)


def test_model_json_is_the_one_way_in(model_json):
    args = gossip_lm.parse_args(["--model_json", model_json])
    assert (args.vocab_size, args.d_model, args.n_layers, args.n_heads,
            args.d_ff) == (96, 32, 4, 4, 96)
    model = gossip_lm.model_from_args(args, "full")
    assert model.cfg.experts.held == (0, 4)
    with pytest.raises(SystemExit, match="flat data-parallel"):
        gossip_lm.parse_args(["--model_json", model_json, "--ep", "2",
                              "--moe_experts", "4"])
    bad = os.path.join(os.path.dirname(model_json), "bad.json")
    with open(bad, "w") as f:
        json.dump({**SOURCE, "model_type": "lfm3"}, f)
    with pytest.raises(SystemExit, match="model_type"):
        gossip_lm.parse_args(["--model_json", bad])


def test_gossip_lm_trains_it_from_one_flag(model_json, tmp_path, caplog):
    with jax.default_matmul_precision("default"):
        out = gossip_lm.main([
            "--model_json", model_json, "--world_size", "2", "--seq_len",
            "32", "--batch_size", "8", "--lr", "8.0", "--num_steps", "30",
            "--corpus_tokens", "20000", "--remat", "True",
            "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(out["final_loss"])
    assert out["final_loss"] < np.log(96)


def _sgp_step(model, seq, grad_accum=1):
    mesh = make_dp_sp_mesh(1, 1)
    alg = sgp(build_schedule(NPeerDynamicDirectedExponentialGraph(
        1, peers_per_itr=1)), GOSSIP_AXIS)
    tx = sgd(momentum=0.9, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.1, batch_size=2, world_size=1,
                     decay_schedule={}, warmup=False)
    step = build_lm_train_step(model, alg, tx, lrs, itr_per_epoch=10,
                               seq_axis=None, grad_accum=grad_accum)
    state = init_lm_state(model, mesh, alg, tx, dp=1, sp=1, batch_size=2,
                          block_len=seq, seq_axis=None)
    return shard_lm_train_step(step, mesh, seq_axis=None), state


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_the_step_reports_the_layers_counters_and_takes_no_balance_loss(
        grad_accum):
    model = _model(_share(0, 4), remat=True)
    train_fn, state = _sgp_step(model, SEQ, grad_accum)
    tokens, targets = _batch()
    text = train_fn.lower(state, tokens[None], targets[None]).as_text(
        debug_info=True)
    for scope in (names.SCOPE_CONV_MIXER, names.SCOPE_MOE,
                  names.SCOPE_MOE_ROUTE, names.SCOPE_MOE_EXPERTS,
                  names.SCOPE_CONV1D, names.SCOPE_LM_HEAD):
        assert scope in text, scope
    with jax.default_matmul_precision("default"):
        state, metrics = train_fn(state, tokens[None], targets[None])
    rows = np.asarray(metrics["moe_expert_rows"])
    assert rows.shape == (1, 4)
    # three expert layers of 2 x 24 tokens' four choices
    assert float(rows.sum() + metrics["moe_pairs_not_held"][0]) \
        == 3 * 2 * SEQ * 4
    assert float(metrics["moe_dropped"][0]) == 0.0
    # the loss is the cross-entropy alone
    assert float(metrics["loss"][0]) == pytest.approx(
        float(jnp.log(metrics["ppl"][0])), rel=1e-6)


# sha256 of ``lower(...).as_text()`` of one SGP step on the CPU, taken by
# this very function: the models that were there before a family lower to
# the same text, byte for byte.  A PR that means to change their step
# re-takes them the same way.  Last taken from PR 34's tree, which changed
# every LM's step (``lm_loss`` takes the target's logit by comparison, and
# the head and the loss carry the ``lm.head`` scope); PR 33 had held them
# to its parent (6451a40).
PARENTS_STEPS = {
    "gpt2_shaped":
        "1142cd33953d735832f5040e321bd14d081bc96ff6ae6fa1b902d483b2d9b222",
    "granite_shaped":
        "6e336a913c43bc6f37f4710c62e663c66ac7a9599d6009c5fc89371710b0b1f5",
    "switch_moe":
        "c3e36ca44469e3ef49f90daa9741cddd8992f163b05bf171e59251b114d9a036",
}


def _earlier_model(name):
    dense = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                 max_len=24)
    if name == "gpt2_shaped":
        return TransformerLM(TransformerConfig(
            dtype=jnp.bfloat16, attn_impl="blockwise", **dense))
    if name == "granite_shaped":
        return TransformerLM(config_from_source(
            GRANITE, dtype=jnp.bfloat16, attn_impl="full", remat=True))
    return TransformerLM(TransformerConfig(moe_experts=4, moe_every=2,
                                           **dense))


@pytest.mark.parametrize("name", sorted(PARENTS_STEPS))
def test_the_earlier_models_lower_to_the_parents_step(name):
    with jax.default_matmul_precision("default"):
        train_fn, state = _sgp_step(_earlier_model(name), 24)
        tokens = jnp.zeros((1, 2, 24), jnp.int32)
        text = train_fn.lower(state, tokens, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_STEPS[name]
