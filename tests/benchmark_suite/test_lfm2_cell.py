"""The sparse-expert configuration and its cell: the files load and keep
to the contract for a cut, the configuration is the source's but for what
``reduced`` lists, the required-operations count against a hand count,
the experts' roofline and load readers, and a toy cell of the same builder
through the harness and the control on the CPU."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_toy import REPO, _write, make_toy_root
from benchmark import control, harness, required_ops_moe, spec
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerLM, config_from_source)

CELL = "lfm2moe_sgp_w1_t4096_b2"
CONFIG = "lfm2_8b_a1b"
NEW_METRICS = ("moe_ms", "moe_route_ms", "moe_experts_ms",
               "shortconv_mixer_ms", "moe_experts_roofline_pct",
               "moe_load_max_over_mean")
# the lists of the other LM cells that this cell joins
JOINED = ("lm_head_ms", "flash_fwd_ms", "flash_bwd_ms")
# every phase metric carries no list of cells: due in a cell a later PR adds
# (a later PR may give the cell more: the set is held from below)
UNLISTED = {"dispatch_ms", "mfu_pct", "device_idle_pct", "fwd_ms", "bwd_ms",
            "optimizer_ms", "gossip_ms", "unscoped_ms"}
# the language model's settings of LiquidAI/LFM2-8B-A1B's config.json (the
# catalog row beside the model-configs guide)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}

TOY_CELL = "toy_moe_sgp_w1"
TOY_MOE = {
    "builder": "moe_lm_trainer", "precision": "fp32",
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 32, "intermediate_size": 64,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "moe_intermediate_size": 16, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 64,
    "experts_held": [0, 4], "experts_routed": 8,
    "published": {"num_experts": 8},
    "deployment": "two chips share each layer's eight experts",
    "reference": {"logit_tolerance": 1e-4, "loss_tolerance": 1e-4,
                  "selection_mismatch_tolerance": 0.01,
                  "selection_gap_tolerance": 1e-4}}


def _entry(kind, name, root=REPO):
    return next(e for e in spec.load_benchmark(root)[kind]
                if e["name"] == name)


def _held():
    with open(os.path.join(REPO, _entry("configs", CONFIG)["file"])) as f:
        return json.load(f)


def the_cell_and_every_file_it_names_load(root):
    """The cell in the ``BENCHMARK.json`` at ``root``: the repo's, or a copy
    with entries appended (test_appending.py)."""
    cell = spec.load_cell(root, CELL)       # check_cut runs in here
    assert cell.chips == 1 and cell.builder == "moe_lm_trainer"
    assert cell.flags == ["--remat", "True"] and cell.loss_n == 20
    assert cell.traffic == {
        "kind": "tokens", "ranks": 1, "batch_per_rank": 2, "seq_len": 4096,
        "vocab": 16384, "zipf_exponent": 1.1, "hidden_states": 8,
        "stay": 0.9, "resident_batches": 8}
    assert {m["name"] for m in cell.per_layer} \
        >= UNLISTED | set(NEW_METRICS) | set(JOINED)
    for m in cell.per_layer:
        assert callable(spec.load_reader(root, m)), m["name"]
    builder = spec.load_plugin(root, "builders", cell.builder)
    argv = builder.argv_of(cell, 2 ** 31 + 11)
    assert argv[:4] == ["--model_json", os.path.join(
        root, _entry("configs", CONFIG, root)["file"]), "--precision", "bf16"]
    assert argv[-2:] == ["--remat", "True"]
    for name in NEW_METRICS:
        entry = _entry("per_layer", name, root)
        assert CELL in entry["workloads"] and entry["moves"] == "step_ms"
        assert entry["layer"] == "Models"
    for name in JOINED:
        assert CELL in _entry("per_layer", name, root)["workloads"]
    assert len(_entry("workloads", CELL, root)["why"]) <= 200


def test_the_cell_and_every_file_it_names_load():
    the_cell_and_every_file_it_names_load(REPO)


def test_the_configuration_is_the_sources_but_for_what_reduced_lists():
    entry, held = _entry("configs", CONFIG), _held()
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types",
                       "num_dense_layers", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert held["published"][key] == value, key
        else:
            assert key in held and held[key] == value, key
    # published layers 1-5: one leading dense layer, then one whole period
    assert held["layer_types"] == PUBLISHED["layer_types"][1:6]
    assert held["num_hidden_layers"] == 5 and held["num_dense_layers"] == 1
    assert held["layer_types"][1:].count("conv") \
        == 3 * held["layer_types"][1:].count("full_attention")
    # half of the experts, the router as wide as published; a quarter of
    # the rows
    assert held["experts_held"] == [0, 16] and held["num_experts"] == 16
    assert held["experts_routed"] == PUBLISHED["num_experts"]
    assert held["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert held["vocab_size"] == 128 * 128
    assert {"logit_tolerance", "loss_tolerance",
            "selection_mismatch_tolerance", "selection_gap_tolerance",
            "reason"} == set(held["reference"])
    assert {"conv_mixer", "attention", "router", "expert_bias", "experts",
            "tied_table", "float32_islands", "optimizer"} \
        <= set(held["assumed"])
    assert "two chips" in held["deployment"].lower()
    spec.check_cut(entry, held)


def _count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def _shapes(source):
    model = TransformerLM(config_from_source(source, dtype=jnp.bfloat16))
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 128), jnp.int32))["params"]


def test_the_held_model_is_860_million_parameters():
    shapes = _shapes(_held())
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    held_experts = 16 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert _count(shapes["block_0"]) == conv + 3 * 2048 * 7168 + 2 * 2048
    assert _count(shapes["block_1"]) == attention + held_experts + 2 * 2048
    assert _count(shapes["block_2"]) == conv + held_experts + 2 * 2048
    assert shapes["block_2"]["moe"]["router"].shape == (2048, 32)
    assert shapes["block_2"]["moe"]["experts_gate_up"].shape \
        == (16, 2048, 3584)
    assert _count(shapes["embed"]) == 16384 * 2048
    assert "lm_head" not in shapes
    assert _count(shapes) == conv + 3 * 2048 * 7168 + attention \
        + 3 * conv + 4 * held_experts + 10 * 2048 + 16384 * 2048 + 2048 \
        == 860_141_824


def test_required_operations_against_a_hand_count():
    """t = 10, hidden 6, dense MLP 7, experts of 5, 2 of 8 a token with 4
    held, 2 query heads over 1 key-value head, a vocabulary of 11; one
    dense conv layer, one attention and one conv expert layer."""
    c = {"hidden_size": 6, "intermediate_size": 7,
         "moe_intermediate_size": 5, "num_experts": 4,
         "num_experts_per_tok": 2, "experts_held": [4, 8],
         "experts_routed": 8,
         "num_dense_layers": 1, "num_attention_heads": 2,
         "num_key_value_heads": 1, "vocab_size": 11,
         "layer_types": ["conv", "full_attention", "conv"]}
    rows = 10 * 2 * 4 / 8               # pairs that land on a held expert
    assert required_ops_moe.expected_rows_held(10, c) == rows
    experts = 2 * rows * 3 * 6 * 5
    assert required_ops_moe.experts_flops(rows, c) == {
        "forward": experts, "backward": 2 * experts}
    conv = 2 * 10 * 6 * 18 + 2 * 10 * 6 * 6
    assert required_ops_moe.conv_mixer_flops(10, c) == conv
    # q and o at 2 heads of 3, k and v at 1; two products over 55 pairs
    attention = 2 * 10 * 6 * (6 + 6 + 3 + 3) + 2 * 2 * 55 * 6
    mlp = 2 * 10 * 3 * 6 * 7
    router = 2 * 10 * 6 * 8
    head = 2 * 10 * 6 * 11
    forward = 2 * conv + attention + mlp + 2 * (router + experts) + head
    assert required_ops_moe.forward_flops_per_sequence(c, 10) == forward
    assert required_ops_moe.train_flops(3, c, 10) == 9 * forward
    # rows and their outputs at 2 bytes, the held experts' three blocks
    x, blocks = rows * 6 * 2, 4 * 3 * 6 * 5 * 2
    assert required_ops_moe.experts_bytes(rows, c) == {
        "forward": 2 * x + blocks, "backward": 3 * x + 2 * blocks}
    # the real cell: 504 MFLOP a token forward (the routers' 0.5 in it),
    # 12.4 TFLOP a step (ISSUE.md's count)
    held = _held()
    per_token = required_ops_moe.forward_flops_per_sequence(held, 4096) \
        / 4096
    assert round(per_token / 1e6) == 504
    assert round(required_ops_moe.train_flops(2, held, 4096) / 1e12, 1) \
        == 12.4


def test_the_experts_readers_from_shapes_times_and_counters():
    roofline = spec.load_reader(REPO, {"reader":
                                       "moe:moe_experts_roofline_pct"})
    load = spec.load_reader(REPO, {"reader": "moe:moe_load_max_over_mean"})
    held = _held()
    # the builder's fetch of the program's counters: [layers, held experts]
    # at the state handed in
    even = np.full((4, 16), 1024.0)
    moe = {"itemsize": 2, "config": held,
           "expert_rows": lambda state: even * state}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def reading(shapes, values, state=1.0):
        return types.SimpleNamespace(
            params={"time_metric": "moe_experts_ms"}, values=values,
            peak=peak, job=types.SimpleNamespace(shapes=shapes, state=state))

    # a layer: 360.8 GFLOP forward (1.83 ms, the operations bound it),
    # twice that backward
    least_ms = 4 * 3 * 2 * 16384 * 3 * 2048 * 1792 / 197e12 * 1e3
    assert least_ms == pytest.approx(21.97, abs=0.01)
    assert roofline(reading({"moe": moe}, {"moe_experts_ms": 50.0})) \
        == pytest.approx(100 * least_ms / 50.0)
    # the rows are the counted ones, layer by layer, not an expectation
    assert roofline(reading({"moe": moe}, {"moe_experts_ms": 50.0}, 1.25)) \
        == pytest.approx(125 * least_ms / 50.0)
    # a program without the scope, or a builder without the shapes: nothing
    assert roofline(reading({"moe": moe}, {})) is None
    assert roofline(reading({}, {"moe_experts_ms": 50.0})) is None
    assert load(reading({}, {})) is None
    assert load(reading({"moe": moe}, {})) == 1.0
    rows = np.array([[100.0, 300.0], [200.0, 200.0]])
    uneven = {**moe, "expert_rows": lambda state: rows}
    assert load(reading({"moe": uneven}, {})) == 1.5
    # no pair landed on a held expert: no ratio
    assert load(reading({"moe": moe}, {}, 0.0)) is None


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The toy root of the other tests plus a sparse-expert configuration
    and cell, added the way this PR adds the real ones: files and
    entries."""
    root = make_toy_root(str(tmp_path_factory.mktemp("moe")))
    data = os.path.join(root, "benchmark")
    _write(os.path.join(data, "configs", "toy_moe.json"), TOY_MOE)
    _write(os.path.join(data, "workloads", TOY_CELL + ".json"),
           {"flags": ["--lr", "8.0", "--remat", "True"], "loss_n": 40})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "toy_moe", "source": "test", "reduced": ["num_experts"],
         "why": "toy", "file": "benchmark/configs/toy_moe.json"})
    bench["workloads"].append(
        {"name": TOY_CELL, "config": "toy_moe",
         "traffic": "toy_tokens_w1", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append(TOY_CELL)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_toy_moe_cell_runs_through_the_harness(toy_root, trace):
    result = harness.run_cell(toy_root, TOY_CELL, 2 ** 31 + 11, 0.2, trace,
                              time.time(), min_steps=45)
    assert result["correct"] is True, result["checks"]["verdicts"]
    assert result["failed"] == 0 and result["attempted"] >= 45
    assert result["checks"]["compilations_in_window"] == 0
    reference = result["checks"]["reference"]
    assert reference["ok"] is True and 0 < reference["logit_error"] < 1e-4
    assert reference["selection_mismatch"] <= 0.01
    assert result["checks"]["loss_first"] == pytest.approx(
        jnp.log(64.0), rel=0.1)
    if trace:
        # host clocks, the required-operations count and the program's
        # counters; a CPU trace has no device plane, so the scopes'
        # readers find nothing and the line leaves their metrics out
        assert {"dispatch_ms", "mfu_pct", "moe_load_max_over_mean"} \
            <= set(result["metrics"])
        assert not (set(NEW_METRICS) - {"moe_load_max_over_mean"}) \
            & set(result["metrics"])
        assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    else:
        assert set(result["metrics"]) == {"step_ms", "step_ms_p90",
                                          "loss_at_n", "setup_s"}


def test_the_toy_moe_control_is_refused(toy_root):
    got = control.readings(toy_root, TOY_CELL, 2 ** 31 + 5, steps=5)
    assert got["program"]["ok"] is True
    assert got["control"]["ok"] is False
    assert got["control"]["logit_error"] > 30 * got["program"]["logit_error"]
