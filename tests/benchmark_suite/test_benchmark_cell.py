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

from bench_toy import (REPO, TOY_CELL, TOY_CUT_CELL, TOY_LM_CELL,
                       make_toy_root)
from benchmark import harness, spec
from benchmark.traffic.generate import make_batches

IMAGES = {"kind": "images", "ranks": 2, "batch_per_rank": 4,
          "image_size": 16, "channels": 3, "classes": 10, "class_grid": 4,
          "signal": 2.0, "resident_batches": 3}
TOKENS = {"kind": "tokens", "ranks": 2, "batch_per_rank": 4, "seq_len": 64,
          "vocab": 101, "zipf_exponent": 1.1, "hidden_states": 4,
          "stay": 0.9, "resident_batches": 3}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# the toy window is counted in steps, so that the machine's speed does not
# decide how far it gets: past loss_n (40) and past the traced stretch
TOY_STEPS = 45
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
                                        (TOY_LM_CELL, True),
                                        (TOY_CUT_CELL, False)])
def test_toy_cell_runs_through_the_harness_and_prints_the_contracts_line(
        toy_root, cell, trace, capsys):
    result = harness.run_cell(toy_root, cell, 2 ** 31 + 11, 0.2, trace,
                              time.time(), min_steps=TOY_STEPS)
    harness.print_result(result)
    printed = capsys.readouterr()
    line = json.loads(printed.out.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line) and DEVICE_KEYS <= set(line["device"])
    assert line["correct"] is True, line["checks"]["verdicts"]
    assert line["failed"] == 0 and line["attempted"] >= TOY_STEPS
    assert line["checks"]["compilations_in_window"] == 0
    # each number compared beside its limit: last in the line, and the
    # last lines on standard error
    assert list(line)[-1] == "compared"
    assert {"first_loss_off_random", "loss_at_n_under_first",
            "compiled_in_window"} <= set(line["compared"])
    assert all(set(c) == {"value", "limit"}
               for c in line["compared"].values())
    last = printed.err.strip().splitlines()[-len(line["compared"]):]
    assert [row.split(":")[0] for row in last] == [
        "compared " + name for name in line["compared"]]
    loaded = spec.load_cell(toy_root, cell)
    assert line["device"]["count"] == loaded.chips
    # the plain reference joins ``correct`` where the configuration's file
    # gives its tolerance: float32 against float32 here, so nearly equal
    assert ("reference" in line["checks"]) == ("reference" in loaded.config)
    if "reference" in loaded.config:
        assert line["checks"]["reference"]["ok"] is True
        assert 0 < line["checks"]["reference"]["logit_error"] < 1e-4
        assert line["compared"]["logit_error"] == {
            "value": line["checks"]["reference"]["logit_error"],
            "limit": 1e-4}
        # it runs once the window has closed, the memory peak read and
        # the step unloaded: no part of set-up
        assert line["checks"]["reference"]["seconds"] > 0
        assert "reference" not in " ".join(
            line["checks"]["setup_phases_s"])
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


class _Frozen:
    """The program's step with the timed path broken underneath: it
    computes its metrics and hands back the state it was given."""

    def __init__(self, step):
        self.step, self.lower = step, step.lower

    def __call__(self, state, x, y):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, state)     # the step donates its state
        return kept, self.step(state, x, y)[1]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        toy_root, monkeypatch):
    """The rest of a run as it is, the step frozen: nothing is learnt, the
    loss at n is the first loss, and ``correct`` comes out false."""
    load_plugin = spec.load_plugin

    def frozen_builder(root, kind, name):
        module = load_plugin(root, kind, name)
        if kind != "builders":
            return module

        def build(cell, seed):
            job = module.build(cell, seed)
            job.step = _Frozen(job.step)
            return job
        return type("frozen", (), {"build": staticmethod(build)})

    monkeypatch.setattr(spec, "load_plugin", frozen_builder)
    result = harness.run_cell(toy_root, TOY_LM_CELL, 5, 0.2, False,
                              time.time(), min_steps=TOY_STEPS)
    assert result["attempted"] >= TOY_STEPS and result["failed"] == 0
    verdicts = result["checks"]["verdicts"]
    assert verdicts["loss_fell"] is False and result["correct"] is False
    assert verdicts["agrees_with_plain_reference"] is True
    seen = result["compared"]["loss_at_n_under_first"]
    assert seen["value"] >= seen["limit"] * (1 - 1e-3)


def test_the_control_in_the_next_lower_precision_is_not_correct(toy_root):
    """The reference computed one precision below the configuration's
    (bfloat16 for the toy's float32), put in the program's place, on the
    state some steps of the program's own step leave: the cell's own
    comparison, with its own tolerance, refuses it on every seed, where
    it passes the program."""
    from benchmark import control
    from benchmark.reference import compare

    assert compare.NEXT_LOWER == {"fp32": "bfloat16", "bf16": "float8_e4m3fn"}
    for seed in (3, 2 ** 31 + 5, 77):
        got = control.readings(toy_root, TOY_LM_CELL, seed, steps=5)
        assert got["program"]["ok"] is True
        assert got["control"]["ok"] is False
        # not by a hair: bfloat16 keeps 8 bits of float32's 24
        assert got["control"]["logit_error"] > \
            10 * got["control"]["logit_tolerance"]
        assert got["control"]["logit_error"] > \
            30 * got["program"]["logit_error"]
    with pytest.raises(ValueError, match="nothing is compared"):
        control.readings(toy_root, TOY_CELL, 1, steps=0)


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
