"""A toy benchmark root in a temporary directory: the benchmark's own
files plus one configuration, cell, traffic file, per-layer metric and
reader added as NEW files and NEW entries, the way a later PR adds them."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOY_CELL = "toy_cnn_sgp_w2"
TOY_LM_CELL = "toy_lm_sgp_w1"
TOY_CUT_CELL = "toy_lm_cut_sgp_w1"
TOY_LM = {"builder": "lm_trainer", "n_embd": 32, "n_layer": 2,
          "n_head": 4, "n_inner": 64, "vocab_size": 64,
          "n_positions": 32, "precision": "fp32",
          "reference": {"logit_tolerance": 1e-4, "loss_tolerance": 1e-4}}
# a configuration cut the way a model_config PR cuts one: depth and the
# vocabulary's rows held here, the source's values beside them
TOY_LM_CUT = {**TOY_LM, "published": {"n_layer": 8, "vocab_size": 512},
              "deployment": "four pipeline stages of two layers, the "
                            "vocabulary split over eight chips"}
TOY_LM_CUT_REDUCED = ["n_layer", "vocab_size"]
TOY_TOKENS = {"kind": "tokens", "ranks": 1, "batch_per_rank": 8,
              "seq_len": 32, "vocab": 64, "zipf_exponent": 1.1,
              "hidden_states": 4, "stay": 0.9, "resident_batches": 4}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def make_toy_root(root: str, cut_config: dict = TOY_LM_CUT,
                  cut_reduced: list = TOY_LM_CUT_REDUCED) -> str:
    """``cut_config`` and ``cut_reduced`` are the cut toy configuration's
    file and its entry's ``reduced``: the tests hand in malformed ones."""
    data = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), data,
                    ignore=shutil.ignore_patterns("__pycache__"))
    _write(os.path.join(data, "configs", "toy_cnn.json"),
           {"builder": "image_trainer", "model": "tiny_cnn",
            "image_size": 16, "num_classes": 10, "precision": "fp32"})
    _write(os.path.join(data, "traffic", "toy_images_w2.json"),
           {"kind": "images", "ranks": 2, "batch_per_rank": 8,
            "image_size": 16, "channels": 3, "classes": 10,
            "class_grid": 4, "signal": 2.0, "resident_batches": 4})
    _write(os.path.join(data, "workloads", TOY_CELL + ".json"),
           {"flags": ["--dataset", "synthetic", "--lr", "0.5"],
            "itr_per_epoch": 100, "loss_n": 40})
    _write(os.path.join(data, "configs", "toy_lm.json"), TOY_LM)
    _write(os.path.join(data, "configs", "toy_lm_cut.json"), cut_config)
    _write(os.path.join(data, "workloads", TOY_CUT_CELL + ".json"),
           {"flags": ["--lr", "8.0"], "loss_n": 40})
    _write(os.path.join(data, "traffic", "toy_tokens_w1.json"), TOY_TOKENS)
    _write(os.path.join(data, "workloads", TOY_LM_CELL + ".json"),
           {"flags": ["--lr", "8.0"], "loss_n": 40})
    _write(os.path.join(data, "layer_metrics", "toy_steps.json"),
           {"reader": "toy_reader:steps_per_s", "params": {"scale": 1e3}})
    _write(os.path.join(data, "readers", "toy_reader.py"),
           "def steps_per_s(reading):\n"
           "    return reading.params['scale'] / reading.step_ms\n")
    # the toy runs on the CPU, which is no chip of the table: a row of
    # made-up peaks in the copy, so that the readers have one to read
    with open(os.path.join(data, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    _write(os.path.join(data, "peaks.json"), peaks)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] += [
        {"name": "toy_cnn", "source": "test", "reduced": [], "why": "toy",
         "file": "benchmark/configs/toy_cnn.json"},
        {"name": "toy_lm", "source": "test", "reduced": [], "why": "toy",
         "file": "benchmark/configs/toy_lm.json"},
        {"name": "toy_lm_cut", "source": "test", "reduced": cut_reduced,
         "why": "toy", "file": "benchmark/configs/toy_lm_cut.json"}]
    bench["workloads"] += [
        {"name": TOY_CELL, "config": "toy_cnn", "traffic": "toy_images_w2",
         "chips": 2, "why": "toy"},
        {"name": TOY_LM_CELL, "config": "toy_lm",
         "traffic": "toy_tokens_w1", "chips": 1, "why": "toy"},
        {"name": TOY_CUT_CELL, "config": "toy_lm_cut",
         "traffic": "toy_tokens_w1", "chips": 1, "why": "toy"}]
    # the replicas' spread is read wherever there are several replicas: in
    # the toy cell too, whether or not the benchmark has such a cell today
    spread = [m for m in bench["per_layer"] if m["name"] == "consensus_spread"]
    if spread:
        spread[0]["workloads"].append(TOY_CELL)
    else:
        _write(os.path.join(data, "layer_metrics", "consensus_spread.json"),
               {"reader": "consensus:consensus_spread"})
        bench["per_layer"].append(
            {"name": "consensus_spread", "unit": "ratio", "better": "lower",
             "source": "program_counter", "layer": "Algorithms",
             "moves": "loss_at_n", "workloads": [TOY_CELL]})
    bench["per_layer"].append(
        {"name": "toy_steps", "unit": "steps/s", "better": "higher",
         "source": "host_clock", "layer": "Train loop / step",
         "moves": "step_ms", "workloads": [TOY_CELL]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root
