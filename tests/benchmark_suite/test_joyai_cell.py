"""The latent-attention configuration and its cell: the files load and
keep to the contract for a cut, the configuration is the source's but for
what ``reduced`` lists, the held model's parameters, the
required-operations count against a hand count, the latent core's
roofline reader, and a toy cell of the same builder through the harness
and the control on the CPU."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_toy import REPO, _write, make_toy_root
from benchmark import control, harness, required_ops_mla, spec
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerLM, config_from_source)

CELL = "joyai_sgp_w1_t8192"
CONFIG = "joyai_llm_flash"
NEW_METRICS = ("mla_ms", "mla_flash_roofline_pct", "moe_shared_ms",
               "mtp_ms")
# the accepted lists this cell joins
JOINED = ("flash_ms", "flash_fwd_ms", "moe_ms", "moe_route_ms",
          "moe_experts_ms", "moe_experts_roofline_pct",
          "moe_load_max_over_mean", "lm_head_ms", "setup_trace_lower_s",
          "setup_compile_s", "setup_cache_load_s", "setup_step_program_s",
          "setup_programs", "setup_cache_misses", "setup_unaccounted_s",
          "setup_step_store_hits")
UNLISTED = {"dispatch_ms", "mfu_pct", "device_idle_pct", "fwd_ms", "bwd_ms",
            "optimizer_ms", "gossip_ms", "unscoped_ms"}
# the language model's settings of jdopensource/JoyAI-LLM-Flash's
# config.json (the catalog row beside the model-configs guide)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}

TOY_CELL = "toy_joyai_sgp_w1"
TOY_JOYAI = {
    "builder": "mla_moe_trainer", "precision": "fp32",
    "model_type": "joyai_llm_flash", "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "qk_head_dim": 12, "v_head_dim": 8, "n_routed_experts": 4,
    "experts_held": [0, 4], "experts_routed": 8, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "moe_layer_freq": 1, "rope_theta": 10000, "rope_interleave": True,
    "rope_scaling": None, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "vocab_size": 512, "num_nextn_predict_layers": 1, "hidden_act": "silu",
    "attention_bias": False,
    "published": {"n_routed_experts": 8},
    "deployment": "two chips share each layer's eight experts",
    "reference": {"logit_tolerance": 1e-4, "loss_tolerance": 1e-4,
                  "selection_mismatch_tolerance": 0.01,
                  "selection_gap_tolerance": 1e-4}}
# the toy roots' tokens over a vocabulary of 512: an untied head's logits
# of unit spread add half a nat to each of the two losses, which ln 64
# would not leave inside the first loss's tolerance
TOY_TRAFFIC = {"kind": "tokens", "ranks": 1, "batch_per_rank": 8,
               "seq_len": 32, "vocab": 512, "zipf_exponent": 1.1,
               "hidden_states": 4, "stay": 0.9, "resident_batches": 4}


def _entry(kind, name, root=REPO):
    return next(e for e in spec.load_benchmark(root)[kind]
                if e["name"] == name)


def _held():
    with open(os.path.join(REPO, _entry("configs", CONFIG)["file"])) as f:
        return json.load(f)


def test_the_cell_and_every_file_it_names_load():
    cell = spec.load_cell(REPO, CELL)       # check_cut runs in here
    assert cell.chips == 1 and cell.builder == "mla_moe_trainer"
    assert cell.flags == ["--remat", "True"] and cell.loss_n == 20
    assert cell.traffic == {
        "kind": "tokens", "ranks": 1, "batch_per_rank": 1, "seq_len": 8192,
        "vocab": 16256, "zipf_exponent": 1.1, "hidden_states": 8,
        "stay": 0.9, "resident_batches": 8}
    assert {m["name"] for m in cell.per_layer} \
        >= UNLISTED | set(NEW_METRICS) | set(JOINED)
    # the fused backward runs in no latent layer: its metric is not due
    assert "flash_bwd_ms" not in {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        assert callable(spec.load_reader(REPO, m)), m["name"]
    builder = spec.load_plugin(REPO, "builders", cell.builder)
    argv = builder.argv_of(cell, 2 ** 31 + 11)
    assert argv[:4] == ["--model_json", os.path.join(
        REPO, _entry("configs", CONFIG)["file"]), "--precision", "bf16"]
    layers = {"mla_ms": "Models", "moe_shared_ms": "Models",
              "mtp_ms": "Models", "mla_flash_roofline_pct": "Kernels"}
    for name in NEW_METRICS:
        entry = _entry("per_layer", name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "step_ms"
        assert entry["layer"] == layers[name]
    for name in JOINED:
        assert CELL in _entry("per_layer", name)["workloads"]


def test_the_new_entries_come_last():
    bench = spec.load_benchmark(REPO)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "mla_ms", "mla_flash_roofline_pct", "moe_shared_ms", "mtp_ms"]


def test_the_configuration_is_the_sources_but_for_what_reduced_lists():
    entry, held = _entry("configs", CONFIG), _held()
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert held["published"][key] == value, key
        else:
            assert key in held and held[key] == value, key
    # the leading dense layer and four expert layers (a period of one)
    assert held["num_hidden_layers"] == 5
    # a sixteenth of the experts, the router as wide as published; an
    # eighth of the padded rows
    assert held["experts_held"] == [0, 16] and held["n_routed_experts"] == 16
    assert held["experts_routed"] == PUBLISHED["n_routed_experts"]
    assert held["vocab_size"] == 127 * 128
    assert 8 * held["vocab_size"] >= PUBLISHED["vocab_size"]
    assert {"logit_tolerance", "loss_tolerance",
            "selection_mismatch_tolerance", "selection_gap_tolerance",
            "reason"} == set(held["reference"])
    assert {"latent_attention", "positions", "router", "expert_bias",
            "experts", "mtp", "mtp_loss_weight", "float32_islands",
            "optimizer"} <= set(held["assumed"])
    assert "sixteen chips" in held["deployment"].lower()
    spec.check_cut(entry, held)


def _count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def test_the_held_model_is_680_8_million_parameters():
    model = TransformerLM(config_from_source(_held(), dtype=jnp.bfloat16))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    mla = (2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
           + 512 * 32 * 256 + 4096 * 2048)
    assert mla == 26_347_520
    expert = 3 * 2048 * 768
    moe = 2048 * 256 + 256 + 16 * expert + expert
    assert _count(shapes["block_0"]) == mla + 3 * 2048 * 7168 + 2 * 2048
    for i in range(1, 5):
        assert _count(shapes[f"block_{i}"]) == mla + moe + 2 * 2048
    assert shapes["block_1"]["moe"]["router"].shape == (2048, 256)
    assert shapes["block_1"]["moe"]["experts_gate_up"].shape \
        == (16, 2048, 1536)
    assert shapes["block_1"]["moe"]["shared_gate_up"].shape == (2048, 1536)
    assert shapes["block_1"]["mla"]["q_b"]["kernel"].shape == (1536, 6144)
    assert shapes["block_1"]["mla"]["kv_b"]["kernel"].shape == (512, 8192)
    assert shapes["eh_proj"]["kernel"].shape == (4096, 2048)
    assert _count(shapes["mtp_block"]) == _count(shapes["block_1"])
    assert _count(shapes) == 680_834_304
    assert round(_count(shapes) / 1e6, 1) == 680.8


def test_required_operations_against_a_hand_count():
    """t = 10, hidden 6, 2 heads, q·k 3 + 2 = 5 beside v 4, latents 4 and
    3, dense MLP 7, experts of 5 (and one shared), 2 of 8 a token with 4
    held, a vocabulary of 11; one dense layer, one expert layer and the
    module."""
    c = {"hidden_size": 6, "num_attention_heads": 2, "q_lora_rank": 4,
         "kv_lora_rank": 3, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2,
         "v_head_dim": 4, "intermediate_size": 7,
         "moe_intermediate_size": 5, "n_routed_experts": 4,
         "experts_held": [4, 8], "experts_routed": 8,
         "num_experts_per_tok": 2, "n_shared_experts": 1,
         "first_k_dense_replace": 1, "num_hidden_layers": 2,
         "num_nextn_predict_layers": 1, "vocab_size": 11}
    pairs = 55
    core = required_ops_mla.core_flops(batch=1, heads=2, seq_len=10,
                                       d_qk=5, d_v=4)
    assert core == {"forward": 2 * pairs * 2 * (5 + 4),
                    "backward": 2 * pairs * 2 * (3 * 5 + 2 * 4)}
    projections = 2 * 10 * (6 * 4 + 4 * 2 * 5 + 6 * (3 + 2) + 3 * 2 * 7
                            + 2 * 4 * 6)
    assert required_ops_mla.projection_flops(10, c) == projections
    rows = 10 * 2 * 4 / 8
    experts = 2 * rows * 3 * 6 * 5
    shared = 2 * 10 * 3 * 6 * 5
    router = 2 * 10 * 6 * 8
    mlp = 2 * 10 * 3 * 6 * 7
    eh = 2 * 10 * 12 * 6
    heads = 2 * 2 * 10 * 6 * 11
    weights = 3 * projections + mlp + 2 * (router + shared + experts) \
        + eh + heads
    assert required_ops_mla.weight_flops_per_sequence(c, 10) == weights
    per_sequence = 3 * weights + 3 * (core["forward"] + core["backward"])
    assert required_ops_mla.train_flops(2, c, 10) == 2 * per_sequence
    # q, k at 5 and v, o at 4, 2 bytes; backward reads five, writes three
    row = 2 * 10 * 2
    assert required_ops_mla.core_bytes(batch=1, heads=2, seq_len=10, d_qk=5,
                                       d_v=4) == {
        "forward": row * (2 * 5 + 2 * 4), "backward": row * (4 * 5 + 4 * 4)}
    # the real cell: 30.3 TFLOP a step, the latent core 14.8 of it
    held = _held()
    assert round(required_ops_mla.train_flops(1, held, 8192) / 1e12, 1) \
        == 30.3
    core = required_ops_mla.core_flops(batch=1, heads=32, seq_len=8192,
                                       d_qk=192, d_v=128)
    assert round(6 * (core["forward"] + core["backward"]) / 1e12, 1) == 14.8


def test_the_latent_roofline_reader_from_shapes_and_the_measured_time():
    roofline = spec.load_reader(REPO, {"reader": "mla:mla_flash_roofline_pct"})
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    mla = {"batch": 1, "heads": 32, "seq_len": 8192, "d_qk": 192,
           "d_v": 128, "layers": 6, "itemsize": 2}

    def reading(shapes, values):
        return types.SimpleNamespace(
            params={"time_metric": "flash_ms"}, values=values, peak=peak,
            job=types.SimpleNamespace(shapes=shapes))

    # 14.85 TFLOP bound by the operations: 75.4 ms a step at the peak
    least_ms = 6 * 2 * 32 * 8192 * 8193 / 2 * (320 + 832) / 197e12 * 1e3
    assert least_ms == pytest.approx(75.36, abs=0.01)
    assert roofline(reading({"mla": mla}, {"flash_ms": 150.0})) \
        == pytest.approx(100 * least_ms / 150.0)
    assert roofline(reading({"mla": mla}, {})) is None
    assert roofline(reading({}, {"flash_ms": 150.0})) is None


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The toy root of the other tests plus a latent-attention
    configuration and cell, added the way this PR adds the real ones:
    files and entries."""
    root = make_toy_root(str(tmp_path_factory.mktemp("joyai")))
    data = os.path.join(root, "benchmark")
    _write(os.path.join(data, "configs", "toy_joyai.json"), TOY_JOYAI)
    _write(os.path.join(data, "traffic", "toy_tokens_v512_w1.json"),
           TOY_TRAFFIC)
    _write(os.path.join(data, "workloads", TOY_CELL + ".json"),
           {"flags": ["--lr", "4.0", "--remat", "True"], "loss_n": 40})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "toy_joyai", "source": "test",
         "reduced": ["n_routed_experts"], "why": "toy",
         "file": "benchmark/configs/toy_joyai.json"})
    bench["workloads"].append(
        {"name": TOY_CELL, "config": "toy_joyai",
         "traffic": "toy_tokens_v512_w1", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS + JOINED:
            m["workloads"].append(TOY_CELL)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_toy_joyai_cell_runs_through_the_harness(toy_root, trace):
    result = harness.run_cell(toy_root, TOY_CELL, 2 ** 31 + 11, 0.2, trace,
                              time.time(), min_steps=45)
    assert result["correct"] is True, result["checks"]["verdicts"]
    assert result["failed"] == 0 and result["attempted"] >= 45
    reference = result["checks"]["reference"]
    assert reference["ok"] is True and 0 < reference["logit_error"] < 1e-4
    assert reference["mtp_logit_error"] < 1e-4
    assert reference["mtp_loss_error"] < 1e-4
    assert reference["selection_mismatch"] <= 0.01
    # the held experts' share of the pairs on the state the window left
    assert 0 < reference["held_share"] <= 1
    # the module's loss joins at 0.3: a random model's first loss
    assert result["checks"]["loss_first"] == pytest.approx(
        1.3 * np.log(512.0), rel=0.1)
    # set-up balanced every expert layer, the two of the trunk and the
    # module's, to within 5 % of the mean load on resident batch 0
    resolved = result["checks"]["resolved"]
    assert len(resolved["bias_steps"]) == 3
    low, high = resolved["bias_load_over_mean"]
    assert 0.95 <= low <= 1 <= high <= 1.05
    if trace:
        assert {"dispatch_ms", "mfu_pct", "moe_load_max_over_mean"} \
            <= set(result["metrics"])
        # a CPU trace has no device plane: the scopes' readers find
        # nothing and the line leaves their metrics out
        assert not {"mla_ms", "moe_shared_ms", "mtp_ms", "flash_ms",
                    "mla_flash_roofline_pct"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"step_ms", "step_ms_p90",
                                          "loss_at_n", "setup_s"}


def test_the_toy_joyai_control_is_refused(toy_root):
    got = control.readings(toy_root, TOY_CELL, 2 ** 31 + 5, steps=5)
    assert got["program"]["ok"] is True
    assert got["control"]["ok"] is False
    assert got["control"]["logit_error"] > 30 * got["program"]["logit_error"]
