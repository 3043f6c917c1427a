"""The harness end to end on the CPU at toy sizes, through the same
functions ``benchmark/run.py`` calls on the chip; the traffic generator;
and ``run.py``'s refusal of anything but the cell's TPU chips."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench_toy import REPO, TOY_CELL, TOY_LM_CELL, make_toy_root
from benchmark import harness, spec
from benchmark.traffic.generate import make_batches

IMAGES = {"kind": "images", "ranks": 2, "batch_per_rank": 4,
          "image_size": 16, "channels": 3, "classes": 10, "class_grid": 4,
          "signal": 2.0, "resident_batches": 3}
TOKENS = {"kind": "tokens", "ranks": 2, "batch_per_rank": 4, "seq_len": 64,
          "vocab": 101, "zipf_exponent": 1.1, "hidden_states": 4,
          "stay": 0.9, "resident_batches": 3}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("traffic", [IMAGES, TOKENS],
                         ids=["images", "tokens"])
def test_same_seed_same_batches_and_another_seed_other_values(traffic):
    big = 2 ** 31 + 11        # the driver's seeds need more than 31 bits
    a, b = make_batches(traffic, big), make_batches(traffic, big)
    other = make_batches(traffic, big + 1)
    assert len(a) == traffic["resident_batches"]
    for (xa, ya), (xb, yb), (xo, yo) in zip(a, b, other):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
        assert xa.shape == xo.shape and ya.shape == yo.shape
        assert not np.array_equal(xa, xo)
    assert not np.array_equal(a[0][0], a[1][0])
    assert a[0][0].shape[:2] == (traffic["ranks"],
                                 traffic["batch_per_rank"])


def test_images_carry_their_class_and_tokens_their_predecessor():
    (x, y), = make_batches({**IMAGES, "resident_batches": 1,
                            "batch_per_rank": 64}, 3)
    x, y = np.asarray(x).reshape(128, -1), np.asarray(y).reshape(-1)
    means = np.stack([x[y == c].mean(0) for c in range(10)])
    nearest = np.argmin(((x[:, None] - means[None]) ** 2).sum(-1), axis=1)
    assert (nearest == y).mean() > 0.9          # learnable from the mean

    (t, nxt), = make_batches({**TOKENS, "resident_batches": 1,
                              "batch_per_rank": 64}, 3)
    t, nxt = np.asarray(t), np.asarray(nxt)
    np.testing.assert_array_equal(t[..., 1:], nxt[..., :-1])
    assert t.min() >= 0 and t.max() < TOKENS["vocab"]
    counts = np.bincount(t.reshape(-1), minlength=TOKENS["vocab"])
    p = counts / counts.sum()
    unigram = -(p[p > 0] * np.log(p[p > 0])).sum()
    assert unigram < 0.9 * np.log(TOKENS["vocab"])      # skewed


def test_unknown_traffic_kind_is_an_error():
    with pytest.raises(KeyError, match="kind"):
        make_batches({"kind": "audio", "resident_batches": 1}, 0)


@pytest.mark.parametrize("cell,trace", [(TOY_CELL, False), (TOY_CELL, True),
                                        (TOY_LM_CELL, True)])
def test_toy_cell_runs_through_the_harness_and_prints_the_contracts_line(
        toy_root, cell, trace, capsys):
    result = harness.run_cell(toy_root, cell, 2 ** 31 + 11, 1.0, trace,
                              time.time())
    harness.print_result(result)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line) and DEVICE_KEYS <= set(line["device"])
    assert line["correct"] is True, line["checks"]["verdicts"]
    assert line["failed"] == 0 and line["attempted"] >= 40
    assert line["checks"]["compilations_in_window"] == 0
    loaded = spec.load_cell(toy_root, cell)
    assert line["device"]["count"] == loaded.chips
    # the plain reference joins ``correct`` where the configuration's file
    # gives its tolerance: float32 against float32 here, so nearly equal
    assert ("reference" in line["checks"]) == ("reference" in loaded.config)
    if "reference" in loaded.config:
        assert line["checks"]["reference"]["ok"] is True
        assert 0 < line["checks"]["reference"]["logit_error"] < 1e-4
    if trace:
        # the per-layer metrics of the cell whose readers found something
        # to read: host clocks and the replicas' spread here, nothing from
        # a device plane, which a CPU trace does not have
        # (the tiny CNN has no required-operations count, so no mfu_pct)
        assert set(line["metrics"]) >= (
            {"dispatch_ms", "consensus_spread", "toy_steps"}
            if cell == TOY_CELL else {"dispatch_ms", "mfu_pct"})
        assert set(line["metrics"]) <= {m["name"] for m in loaded.per_layer}
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(rows) <= 10 for rows in line["breakdown"].values())
    else:
        assert set(line["metrics"]) == {"step_ms", "step_ms_p90",
                                        "loss_at_n", "setup_s"}
    for name, m in line["metrics"].items():
        # two replicas that swap halves agree exactly: the spread may be 0
        assert m["value"] > 0 or name == "consensus_spread"
        assert isinstance(m["unit"], str)


def test_a_window_too_short_to_reach_n_is_not_correct(toy_root):
    result = harness.run_cell(toy_root, TOY_CELL, 1, 0.0, False, time.time())
    assert result["attempted"] == 1
    assert "loss_at_n" not in result["metrics"]
    assert result["correct"] is False
    assert result["checks"]["verdicts"]["loss_fell"] is False


def test_run_py_refuses_a_backend_that_is_not_the_cells_tpu_chips():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "resnet50_sgp_w1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0
    assert "needs 1 TPU chip" in done.stderr
    assert "{" not in done.stdout           # no result line
