"""The Olmo hybrid configuration and its cell: the files load and keep to
the contract for a cut, the configuration is the source's but for what
``reduced`` lists, the held model's parameters against a hand count, the
required-operations count against a hand count, the delta rule's roofline
reader, and a toy cell of the same builder through the harness and the
control on the CPU."""

import json
import os
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_toy import REPO, _write, make_toy_root
from benchmark import control, harness, required_ops_olmo_hybrid, spec
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerLM, config_from_source)

CELL = "olmohyb_sgp_w1_t4096"
CONFIG = "olmo_hybrid_7b"
# every phase metric carries no list of cells: due in a cell a later PR adds
# (a later PR may give the cell more: the set is held from below)
UNLISTED = {"dispatch_ms", "mfu_pct", "device_idle_pct", "fwd_ms", "bwd_ms",
            "optimizer_ms", "gossip_ms", "unscoped_ms"}
# the language model's settings of allenai/Olmo-Hybrid-7B's config.json
# (the catalog row beside the model-configs guide)
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}

# the rule's three metrics (two scopes by files alone and the rule's
# roofline share): the text of their files, each entry as ``_delta_entry``
# makes it. The accepted metrics of the flash kernels and the head are the
# ones whose lists the cell joins.
DELTA_METRICS = {
    "delta_mixer_ms": {
        "reader": "program_trace:scope_ms",
        "params": {"pattern": "lm\\.delta_mixer"},
        "what": "trace: device self time a step of the operations under "
                "the program's lm.delta_mixer scope (models/gated_deltanet."
                "py: the in-projections, conv1d, L2 norms, gates, the "
                "chunked rule, the gated per-head norm, the out-projection), "
                "forward, recomputed and transposed alike"},
    "delta_rule_ms": {
        "reader": "program_trace:scope_ms",
        "params": {"pattern": "lm\\.delta_rule"},
        "what": "trace: device self time a step of the operations under "
                "the program's lm.delta_rule scope (ops/delta_rule.py, the "
                "chunked gated delta rule alone, nested in lm.delta_mixer), "
                "forward, recomputed and transposed alike"},
    "delta_rule_roofline_pct": {
        "reader": "delta_rule:delta_rule_roofline_pct",
        "params": {"time_metric": "delta_rule_ms"},
        "what": "least time for the gated delta rule's required operations "
                "and bytes (benchmark/required_ops_olmo_hybrid.py: forward "
                "and backward, every linear_attention layer, counted at the "
                "chunk of 64 the count fixes, recomputation not counted) "
                "over delta_rule_ms"},
}


def _delta_entry(name, cells):
    ms = not name.endswith("_pct")
    return {"name": name, "unit": "ms" if ms else "%",
            "better": "lower" if ms else "higher", "source": "device_trace",
            "layer": "Models", "moves": "step_ms", "workloads": cells}


JOINED = ("flash_ms", "flash_roofline_pct", "flash_fwd_ms", "flash_bwd_ms",
          "lm_head_ms")
# the rule's kernel pair by the calls' names, beside the flash kernels'
KERNEL_METRIC = "delta_kernel_ms"

TOY_CELL = "toy_olmohyb_sgp_w1"
TOY_OLMO = {
    "builder": "olmo_hybrid_trainer", "precision": "fp32",
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 32,
    "intermediate_size": 48, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "hidden_act": "silu", "max_position_embeddings": 256,
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "layer_types": PERIOD,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "reference": {"logit_tolerance": 5e-4, "loss_tolerance": 1e-4}}
# an untied head's random logits have unit variance and add half a nat to
# ln(vocab): under the harness's 10 % at 512 rows, not at the toy's 64
TOY_TRAFFIC = {"kind": "tokens", "ranks": 1, "batch_per_rank": 8,
               "seq_len": 32, "vocab": 512, "zipf_exponent": 1.1,
               "hidden_states": 4, "stay": 0.9, "resident_batches": 4}


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
    assert cell.chips == 1 and cell.builder == "olmo_hybrid_trainer"
    assert cell.flags == ["--remat", "True"] and cell.loss_n == 20
    assert cell.traffic == {
        "kind": "tokens", "ranks": 1, "batch_per_rank": 1, "seq_len": 4096,
        "vocab": 12544, "zipf_exponent": 1.1, "hidden_states": 8,
        "stay": 0.9, "resident_batches": 8}
    names = {m["name"] for m in cell.per_layer}
    assert names >= UNLISTED | set(JOINED) | set(DELTA_METRICS) \
        | {KERNEL_METRIC}
    for m in cell.per_layer:
        assert callable(spec.load_reader(root, m)), m["name"]
    builder = spec.load_plugin(root, "builders", cell.builder)
    argv = builder.argv_of(cell, 2 ** 31 + 11)
    assert argv[:4] == ["--model_json", os.path.join(
        root, _entry("configs", CONFIG, root)["file"]), "--precision", "bf16"]
    assert argv[-2:] == ["--remat", "True"]
    assert len(_entry("workloads", CELL, root)["why"]) <= 200
    assert _entry("configs", CONFIG, root)["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"


def the_rules_entries_and_lists_hold(root):
    """The five accepted lists the cell joined hold it; the rule's metrics
    have their entries, with the cell, and their files."""
    for name in JOINED:
        assert CELL in _entry("per_layer", name, root)["workloads"]
    for name, file in DELTA_METRICS.items():
        entry = _entry("per_layer", name, root)
        assert CELL in entry["workloads"]
        assert entry == _delta_entry(name, entry["workloads"])
        with open(spec.data_path(root, "layer_metrics", name)) as f:
            assert json.load(f) == file
    entry = _entry("per_layer", KERNEL_METRIC, root)
    assert CELL in entry["workloads"]
    assert entry == {**_delta_entry(KERNEL_METRIC, entry["workloads"]),
                     "layer": "Kernels"}
    with open(spec.data_path(root, "layer_metrics", KERNEL_METRIC)) as f:
        file = json.load(f)
    assert file["reader"] == "program_trace:kernel_ms" and file["what"]
    pattern = re.compile(file["params"]["pattern"])
    assert all(pattern.search(n) for n in ("delta_fwd", "delta_bwd.3"))
    assert not any(pattern.search(n) for n in ("flash_fwd.2", "delta_fwd_x"))


def test_the_cell_and_every_file_it_names_load():
    the_cell_and_every_file_it_names_load(REPO)


def test_the_cell_joins_five_lists_and_moves_no_entry():
    """What the entries hold; not where they stand, since a later change
    appends after them."""
    the_rules_entries_and_lists_hold(REPO)


def test_the_configuration_is_the_sources_but_for_what_reduced_lists():
    entry, held = _entry("configs", CONFIG), _held()
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert held["published"][key] == value, key
        else:
            assert key in held and held[key] == value, key
    # published layers 0-3: one whole period, three to one; an eighth of
    # the rows
    assert held["layer_types"] == PERIOD and held["num_hidden_layers"] == 4
    assert held["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert held["vocab_size"] == 98 * 128
    assert set(held["reference"]) == {"logit_tolerance", "loss_tolerance",
                                      "reason"}
    assert {"conv", "l2_norm_and_scale", "alpha", "beta", "gated_norm",
            "delta_init", "float32_islands", "chunk_size", "positions",
            "optimizer", "projection_layout"} <= set(held["assumed"])
    assert "eight" in held["deployment"]
    spec.check_cut(entry, held)


def _count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def test_the_held_model_is_929_million_parameters():
    model = TransformerLM(config_from_source(_held(), dtype=jnp.bfloat16))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    # q | k | v | gate, a | b, the convolution over q | k | v, A_log and
    # dt_bias, the gated norm's 192, the out-projection
    mixer = 3840 * (2880 + 2880 + 5760 + 5760) + 3840 * 60 + 4 * 11520 \
        + 2 * 30 + 192 + 5760 * 3840
    mlp = 3 * 3840 * 11008
    attention = 4 * 3840 * 3840 + 2 * 3840       # q, k, v, o; q/k norms
    assert mixer == 88_750_332
    assert _count(shapes["block_0"]) == mixer + mlp + 2 * 3840
    assert _count(shapes["block_3"]) == attention + mlp + 2 * 3840
    assert _count(shapes["embed"]) == _count(shapes["lm_head"]) \
        == 12544 * 3840
    assert _count(shapes) == 3 * (mixer + mlp) + attention + mlp \
        + 8 * 3840 + 2 * 12544 * 3840 + 3840 == 928_862_196


def test_required_operations_against_a_hand_count():
    """t = 10 with chunks of 4 (two whole chunks and one of 2), 2 heads with
    keys of 3 and values of 5; hidden 6, MLP 7, 2 heads of 3 in the full
    layer, a vocabulary of 11; one layer of each kind."""
    c = {"hidden_size": 6, "intermediate_size": 7,
         "linear_num_key_heads": 2, "linear_key_head_dim": 3,
         "linear_value_head_dim": 5, "num_attention_heads": 2,
         "num_key_value_heads": 2, "vocab_size": 11,
         "layer_types": ["linear_attention", "full_attention"]}
    causal = 10 + 10 + 3                # pairs inside the chunks
    strict = 6 + 6 + 1                  # of them below the diagonal
    rule = 2 * 2 * (strict * 3          # K K^T
                    + strict * (3 + 5)  # the solve on [K | V]
                    + causal * (3 + 5)  # Q K^T, the scores times V'
                    + 3 * 10 * 3 * 5)   # W S, Q S, the state's update
    flops = required_ops_olmo_hybrid.delta_rule_flops(
        batch=1, seq_len=10, heads=2, d_key=3, d_value=5, chunk=4)
    assert flops == {"forward": rule, "backward": 2 * rule}
    # in 6 -> (3 + 3 + 5 | 5) a head, a | b 6 -> 4, out 10 -> 6
    linear = 2 * 10 * 6 * (2 * 16 + 4 + 10) + rule
    attention = 2 * 10 * 6 * 24 + 2 * 2 * 55 * 6
    mlp = 2 * 10 * 3 * 6 * 7
    head = 2 * 10 * 6 * 11
    assert required_ops_olmo_hybrid.linear_layer_flops(10, c, 4) == linear
    assert required_ops_olmo_hybrid.attention_layer_flops(10, c) == attention
    forward = linear + attention + 2 * mlp + head
    assert required_ops_olmo_hybrid.forward_flops_per_sequence(c, 10, 4) \
        == forward
    assert required_ops_olmo_hybrid.train_flops(3, c, 10, 4) == 9 * forward
    # q, k, v at 2 bytes, alpha and beta at 4, read; o written; backward
    # reads them and dO and writes the five gradients
    inputs = 10 * 2 * (3 + 3 + 5) * 2 + 2 * 10 * 2 * 4
    assert required_ops_olmo_hybrid.delta_rule_bytes(
        batch=1, seq_len=10, heads=2, d_key=3, d_value=5) == {
            "forward": inputs + 10 * 2 * 5 * 2,
            "backward": 2 * inputs + 10 * 2 * 5 * 2}


def test_the_cells_step_requires_22_2_teraflops():
    held = _held()
    forward = required_ops_olmo_hybrid.forward_flops_per_sequence(
        held, 4096, 64)
    assert round(forward / 1e12, 2) == 7.40
    # the rule: 4.61 MFLOP a token and layer forward at these shapes
    rule = required_ops_olmo_hybrid.delta_rule_flops(
        batch=1, seq_len=4096, heads=30, d_key=96, d_value=192, chunk=64)
    assert round(rule["forward"] / 4096 / 1e6, 2) == 4.61
    assert round(required_ops_olmo_hybrid.train_flops(
        1, held, 4096, 64) / 1e12, 1) == 22.2


def test_the_rules_roofline_share_from_shapes_and_the_measured_time():
    reader = spec.load_reader(
        REPO, {"reader": "delta_rule:delta_rule_roofline_pct"})
    shape = {"batch": 1, "seq_len": 4096, "heads": 30, "d_key": 96,
             "d_value": 192, "layers": 3, "itemsize": 2}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def reading(shapes, values):
        return types.SimpleNamespace(
            params={"time_metric": "delta_rule_ms"}, values=values,
            peak=peak, job=types.SimpleNamespace(shapes=shapes))

    # forward 18.9 GFLOP against 143 MB and backward twice the operations
    # against 238 MB: the bytes bound both (1.39 ms over three layers)
    flops = required_ops_olmo_hybrid.delta_rule_flops(
        batch=1, seq_len=4096, heads=30, d_key=96, d_value=192, chunk=64)
    nbytes = required_ops_olmo_hybrid.delta_rule_bytes(
        batch=1, seq_len=4096, heads=30, d_key=96, d_value=192)
    assert nbytes == {"forward": 142_540_800.0, "backward": 237_895_680.0}
    assert all(flops[p] / 197e12 < nbytes[p] / 819e9
               for p in ("forward", "backward"))
    least_ms = 3 * (142_540_800 + 237_895_680) / 819e9 * 1e3
    assert least_ms == pytest.approx(1.394, abs=1e-3)
    assert reader(reading({"delta_rule": shape}, {"delta_rule_ms": 30.0})) \
        == pytest.approx(100 * least_ms / 30.0)
    # the count's chunk is its own: a chunk the program names moves nothing
    assert required_ops_olmo_hybrid.CHUNK == 64
    assert reader(reading({"delta_rule": {**shape, "chunk": 16}},
                          {"delta_rule_ms": 30.0})) \
        == pytest.approx(100 * least_ms / 30.0)
    # a program without the scope, or a builder without the shapes: nothing
    assert reader(reading({"delta_rule": shape}, {})) is None
    assert reader(reading({"head_dim": 64}, {"delta_rule_ms": 30.0})) is None


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The toy root of the other tests plus an Olmo hybrid configuration
    and cell, added the way the real ones are, and appended to the lists of
    the rule's four metrics."""
    root = make_toy_root(str(tmp_path_factory.mktemp("olmohyb")))
    data = os.path.join(root, "benchmark")
    _write(os.path.join(data, "configs", "toy_olmo_hybrid.json"), TOY_OLMO)
    _write(os.path.join(data, "workloads", TOY_CELL + ".json"),
           {"flags": ["--lr", "8.0", "--remat", "True"], "loss_n": 40})
    _write(os.path.join(data, "traffic", "toy_tokens_v512_w1.json"),
           TOY_TRAFFIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "toy_olmo_hybrid", "source": "test", "reduced": [],
         "why": "toy", "file": "benchmark/configs/toy_olmo_hybrid.json"})
    bench["workloads"].append(
        {"name": TOY_CELL, "config": "toy_olmo_hybrid",
         "traffic": "toy_tokens_v512_w1", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m["name"] in (*DELTA_METRICS, KERNEL_METRIC):
            m["workloads"].append(TOY_CELL)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def test_the_rules_metrics_load_in_the_toy_cell(toy_root):
    cell = spec.load_cell(toy_root, TOY_CELL)
    loaded = {m["name"]: m for m in cell.per_layer}
    assert set(DELTA_METRICS) | {KERNEL_METRIC} <= set(loaded)
    for name, file in DELTA_METRICS.items():
        assert loaded[name]["params"] == file["params"]
    for name in (*DELTA_METRICS, KERNEL_METRIC):
        assert callable(spec.load_reader(toy_root, loaded[name])), name


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_toy_olmo_hybrid_cell_runs_through_the_harness(toy_root, trace):
    result = harness.run_cell(toy_root, TOY_CELL, 2 ** 31 + 11, 0.2, trace,
                              time.time(), min_steps=45)
    assert result["correct"] is True, result["checks"]["verdicts"]
    assert result["failed"] == 0 and result["attempted"] >= 45
    assert result["checks"]["compilations_in_window"] == 0
    reference = result["checks"]["reference"]
    assert reference["ok"] is True and 0 < reference["logit_error"] < 5e-4
    # the program's own counter, one number a linear layer
    shares = reference["beta_above_one"]
    assert len(shares) == 3 and all(0 < s < 1 for s in shares)
    assert result["checks"]["loss_first"] == pytest.approx(
        np.log(512.0), rel=0.1)
    if trace:
        # host clocks and the required-operations count; a CPU trace has
        # no device plane, so the scopes' readers find nothing and the
        # line leaves their metrics out
        assert {"dispatch_ms", "mfu_pct"} <= set(result["metrics"])
        assert not (set(DELTA_METRICS) | {KERNEL_METRIC}) \
            & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"step_ms", "step_ms_p90",
                                          "loss_at_n", "setup_s"}


def test_the_toy_olmo_hybrid_control_is_refused(toy_root):
    got = control.readings(toy_root, TOY_CELL, 2 ** 31 + 5, steps=5)
    assert got["program"]["ok"] is True
    assert got["control"]["ok"] is False
    assert got["control"]["logit_error"] > 30 * got["program"]["logit_error"]
