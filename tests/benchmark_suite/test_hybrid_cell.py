"""The hybrid state-space configuration and its cell: the files load and
keep to the contract for a cut, the configuration is the source's but for
what ``reduced`` lists, the required-operations count against a hand
count, the scan's roofline reader, and a toy cell of the same builder
through the harness and the control on the CPU."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import pytest

from bench_toy import REPO, _write, make_toy_root
from benchmark import control, harness, required_ops_hybrid, spec
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerLM, config_from_source)

CELL = "granite4hm_sgp_w1_t4096"
CONFIG = "granite_4_0_h_micro"
# the scan's metrics: the mixer, the scan, its roofline share, its kernels
NEW_METRICS = ("ssm_mixer_ms", "ssd_ms", "ssd_roofline_pct", "ssd_kernel_ms")
# the lists of the other LM cells that this cell joins
JOINED = ("lm_head_ms", "flash_fwd_ms", "flash_bwd_ms")
# every phase metric carries no list of cells: due in a cell a later PR adds
# (a later PR may give the cell more: the set is held from below)
UNLISTED = {"dispatch_ms", "mfu_pct", "device_idle_pct", "fwd_ms", "bwd_ms",
            "optimizer_ms", "gossip_ms", "unscoped_ms"}
# the language model's settings of ibm-granite/granite-4.0-h-micro's
# config.json (the catalog row beside the model-configs guide)
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}

TOY_CELL = "toy_hybrid_sgp_w1"
TOY_HYBRID = {
    "builder": "hybrid_lm_trainer", "precision": "fp32",
    "model_type": "granitemoehybrid", "hidden_size": 32,
    "shared_intermediate_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 64,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "tie_word_embeddings": True,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 8,
    "reference": {"logit_tolerance": 1e-4, "loss_tolerance": 1e-4}}


def _entry(kind, name, root=REPO):
    return next(e for e in spec.load_benchmark(root)[kind]
                if e["name"] == name)


def the_cell_and_every_file_it_names_load(root):
    """The cell in the ``BENCHMARK.json`` at ``root``: the repo's, or a copy
    with entries appended (test_appending.py)."""
    cell = spec.load_cell(root, CELL)       # check_cut runs in here
    assert cell.chips == 1 and cell.builder == "hybrid_lm_trainer"
    assert cell.flags == ["--remat", "True"] and cell.loss_n == 20
    assert cell.traffic == {
        "kind": "tokens", "ranks": 1, "batch_per_rank": 1, "seq_len": 4096,
        "vocab": 25088, "zipf_exponent": 1.1, "hidden_states": 8,
        "stay": 0.9, "resident_batches": 8}
    names = {m["name"] for m in cell.per_layer}
    assert names >= UNLISTED | set(NEW_METRICS) | set(JOINED)
    for m in cell.per_layer:
        assert callable(spec.load_reader(root, m)), m["name"]
    builder = spec.load_plugin(root, "builders", cell.builder)
    argv = builder.argv_of(cell, 2 ** 31 + 11)
    assert argv[:4] == ["--model_json", os.path.join(
        root, _entry("configs", CONFIG, root)["file"]), "--precision", "bf16"]
    assert argv[-2:] == ["--remat", "True"]
    for name in NEW_METRICS + JOINED:
        entry = _entry("per_layer", name, root)
        assert CELL in entry["workloads"] and entry["moves"] == "step_ms"
    with open(spec.data_path(root, "layer_metrics", "ssd_kernel_ms")) as f:
        assert json.load(f)["reader"] == "program_trace:kernel_ms"
    assert _entry("per_layer", "ssd_kernel_ms", root)["layer"] == "Kernels"


def test_the_cell_and_every_file_it_names_load():
    the_cell_and_every_file_it_names_load(REPO)


def test_the_configuration_is_the_sources_but_for_what_reduced_lists():
    entry = _entry("configs", CONFIG)
    with open(os.path.join(REPO, entry["file"])) as f:
        held = json.load(f)
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert held["published"][key] == value, key
        else:
            assert key in held and held[key] == value, key
    # one whole period, in the published ratio, and a quarter of the rows
    assert held["layer_types"] == PERIOD and held["num_hidden_layers"] == 10
    assert held["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert held["vocab_size"] % 128 == 0
    assert set(held["reference"]) == {"logit_tolerance", "loss_tolerance",
                                      "reason"}
    assert {"in_proj_order", "mamba_init", "float32_islands",
            "optimizer"} <= set(held["assumed"])
    spec.check_cut(entry, held)


def test_the_held_model_is_798_million_parameters():
    with open(os.path.join(REPO, _entry("configs", CONFIG)["file"])) as f:
        held = json.load(f)
    model = TransformerLM(config_from_source(held, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 256), jnp.int32))["params"]
    count = lambda tree: sum(int(jnp.prod(jnp.asarray(leaf.shape)))
                             for leaf in jax.tree.leaves(tree))
    mlp = 3 * 2048 * 8192
    mamba = 2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert count(shapes["block_0"]) == mamba + mlp + 2 * 2048
    assert count(shapes["block_5"]) == attention + mlp + 2 * 2048
    assert count(shapes["embed"]) == 25088 * 2048
    assert "lm_head" not in shapes
    assert count(shapes) == 9 * (mamba + mlp) + attention + mlp \
        + 20 * 2048 + 25088 * 2048 + 2048 == 797_850_560


def test_required_operations_against_a_hand_count():
    """t = 10 with chunks of 4 (two whole chunks and one of 2), 2 heads of
    3 over one group with a state of 5; hidden 6, MLP 7, 2 query heads over
    1 key-value head, a vocabulary of 11; one layer of each kind."""
    c = {"hidden_size": 6, "shared_intermediate_size": 7,
         "mamba_n_heads": 2, "mamba_d_head": 3, "mamba_d_state": 5,
         "mamba_n_groups": 1, "mamba_chunk_size": 4,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "vocab_size": 11, "layer_types": ["mamba", "attention"]}
    pairs = 10 + 10 + 3                 # causal pairs inside the chunks
    scan = 2 * (pairs * 5               # C B^T, once for the one group
                + pairs * 2 * 3         # the masked product with x
                + 10 * 2 * 3 * 5 * 2)   # chunk states; the state's output
    flops = required_ops_hybrid.ssd_flops(
        batch=1, seq_len=10, heads=2, head_dim=3, state=5, groups=1, chunk=4)
    assert flops == {"forward": scan, "backward": 2 * scan}
    # in-projection 6 -> (6 | 6 + 2*5 | 2), out-projection 6 -> 6
    mamba = 2 * 10 * 6 * (6 + 16 + 2) + 2 * 10 * 6 * 6 + scan
    # q and o at 2 heads of 3, k and v at 1; two products over 55 pairs
    attention = 2 * 10 * 6 * (6 + 6 + 3 + 3) + 2 * 2 * 55 * 6
    mlp = 2 * 10 * 3 * 6 * 7
    head = 2 * 10 * 6 * 11
    assert required_ops_hybrid.mamba_layer_flops(10, c) == mamba
    assert required_ops_hybrid.attention_layer_flops(10, c) == attention
    forward = mamba + attention + 2 * mlp + head
    assert required_ops_hybrid.hybrid_forward_flops_per_sequence(c, 10) \
        == forward
    assert required_ops_hybrid.hybrid_train_flops(3, c, 10) == 9 * forward
    # x, B, C at 2 bytes, dt at 4, read; y written; backward reads them
    # and dy and writes the four gradients
    inputs = 10 * 6 * 2 + 2 * 10 * 5 * 2 + 10 * 2 * 4
    assert required_ops_hybrid.ssd_bytes(
        batch=1, seq_len=10, heads=2, head_dim=3, state=5, groups=1) == {
            "forward": inputs + 10 * 6 * 2,
            "backward": 2 * inputs + 10 * 6 * 2}


def test_the_scans_roofline_share_from_shapes_and_the_measured_time():
    reader = spec.load_reader(REPO, {"reader": "ssd:ssd_roofline_pct"})
    shape = {"batch": 1, "seq_len": 4096, "heads": 64, "head_dim": 64,
             "state": 128, "groups": 1, "chunk": 256, "layers": 9,
             "itemsize": 2}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def reading(shapes, values):
        return types.SimpleNamespace(
            params={"time_metric": "ssd_ms"}, values=values, peak=peak,
            job=types.SimpleNamespace(shapes=shapes))

    # forward 13.04 GFLOP against 70.3 MB: the bytes bound it (85.8 us);
    # backward 26.07 GFLOP against 107 MB: the operations do (132.3 us)
    least_ms = 9 * (70254592 / 819e9 + 26072842240 / 197e12) * 1e3
    assert least_ms == pytest.approx(1.963, abs=1e-3)
    assert reader(reading({"ssd": shape}, {"ssd_ms": 40.0})) \
        == pytest.approx(100 * least_ms / 40.0)
    # a program without the scope, or a builder without the shapes: nothing
    assert reader(reading({"ssd": shape}, {})) is None
    assert reader(reading({"head_dim": 64}, {"ssd_ms": 40.0})) is None


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The toy root of the other tests plus a hybrid configuration and
    cell, added the way this PR adds the real ones: files and entries."""
    root = make_toy_root(str(tmp_path_factory.mktemp("hybrid")))
    data = os.path.join(root, "benchmark")
    _write(os.path.join(data, "configs", "toy_hybrid.json"), TOY_HYBRID)
    _write(os.path.join(data, "workloads", TOY_CELL + ".json"),
           {"flags": ["--lr", "8.0", "--remat", "True"], "loss_n": 40})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "toy_hybrid", "source": "test", "reduced": [],
         "why": "toy", "file": "benchmark/configs/toy_hybrid.json"})
    bench["workloads"].append(
        {"name": TOY_CELL, "config": "toy_hybrid",
         "traffic": "toy_tokens_w1", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append(TOY_CELL)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def test_the_scans_metrics_load_in_the_toy_cell(toy_root):
    """A cell appended to the lists finds each file and its reader."""
    cell = spec.load_cell(toy_root, TOY_CELL)
    loaded = {m["name"]: m for m in cell.per_layer}
    assert set(NEW_METRICS) <= set(loaded)
    for name in NEW_METRICS:
        with open(spec.data_path(REPO, "layer_metrics", name)) as f:
            assert loaded[name].get("params") == json.load(f).get("params")
        assert callable(spec.load_reader(toy_root, loaded[name])), name


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_toy_hybrid_cell_runs_through_the_harness(toy_root, trace):
    result = harness.run_cell(toy_root, TOY_CELL, 2 ** 31 + 11, 0.2, trace,
                              time.time(), min_steps=45)
    assert result["correct"] is True, result["checks"]["verdicts"]
    assert result["failed"] == 0 and result["attempted"] >= 45
    assert result["checks"]["compilations_in_window"] == 0
    reference = result["checks"]["reference"]
    assert reference["ok"] is True and 0 < reference["logit_error"] < 1e-4
    assert result["checks"]["loss_first"] == pytest.approx(
        jnp.log(64.0), rel=0.1)
    if trace:
        # host clocks and the required-operations count; a CPU trace has
        # no device plane, so the scopes' readers find nothing and the
        # line leaves their metrics out
        assert {"dispatch_ms", "mfu_pct"} <= set(result["metrics"])
        assert not set(NEW_METRICS) & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"step_ms", "step_ms_p90",
                                          "loss_at_n", "setup_s"}


def test_the_toy_hybrid_control_is_refused(toy_root):
    got = control.readings(toy_root, TOY_CELL, 2 ** 31 + 5, steps=5)
    assert got["program"]["ok"] is True
    assert got["control"]["ok"] is False
    assert got["control"]["logit_error"] > 30 * got["program"]["logit_error"]
