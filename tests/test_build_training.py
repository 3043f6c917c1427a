"""One place turns flags into a training job: both harnesses reach the
same ``algorithms.gossip_algorithm``, ``gossip_lm.build_training`` is the
job ``gossip_lm.main`` runs, and a ``Trainer`` is ready when constructed.
"""

import csv
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from stochastic_gradient_push_tpu.algorithms import (
    adpsgd, all_reduce, dpsgd, sgp)
from stochastic_gradient_push_tpu.parallel import (
    GOSSIP_AXIS, make_gossip_mesh)
from stochastic_gradient_push_tpu.parallel.wire import Int8Codec
from stochastic_gradient_push_tpu.run import gossip_lm, gossip_sgd
from stochastic_gradient_push_tpu.topology import (
    NPeerDynamicDirectedExponentialGraph, build_pairing_schedule,
    build_schedule)
from stochastic_gradient_push_tpu.train.loop import Trainer
from stochastic_gradient_push_tpu.train.lr import (
    CosineLRSchedule, LRSchedule)
from stochastic_gradient_push_tpu.utils import make_logger

WORLD = 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM_TINY = ["--world_size", str(WORLD), "--vocab_size", "32", "--d_model",
           "16", "--n_layers", "1", "--n_heads", "2", "--d_ff", "32",
           "--seq_len", "16", "--batch_size", "2"]

# mode -> (the flags both CLIs take, the same algorithm by direct call)
_GRAPH = NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1)
_OVERLAP = ["--overlap", "True", "--staleness", "2",
            "--global_avg_every", "3"]
MODES = {
    "push-sum": (
        _OVERLAP + ["--wire_dtype", "int8", "--wire_block", "32",
                    "--gossip_every", "2"],
        lambda: sgp(build_schedule(_GRAPH), GOSSIP_AXIS, overlap=True,
                    staleness=2, global_avg_every=3, gossip_every=2,
                    wire=Int8Codec(32))),
    "D-PSGD": (
        _OVERLAP + ["--push_sum", "False"],
        lambda: dpsgd(build_schedule(_GRAPH), GOSSIP_AXIS, overlap=True,
                      staleness=2, global_avg_every=3)),
    "all-reduce": (["--all_reduce", "True", "--graph_type", "-1"],
                   lambda: all_reduce(GOSSIP_AXIS)),
    "bilateral": ([], lambda: adpsgd(build_pairing_schedule(_GRAPH),
                                     GOSSIP_AXIS)),
}


def _from_image_harness(flags, bilat):
    cfg, args = gossip_sgd.parse_config(
        ["--world_size", str(WORLD)] + flags)
    cfg.bilat = bilat     # as run/gossip_sgd_adpsgd.py selects the mode
    gossip_sgd._resolve_plan(cfg, args, WORLD, make_logger("t", False))
    trainer = Trainer(cfg, model=None, mesh=make_gossip_mesh(WORLD),
                      sample_input_shape=(2, 8, 8, 3))
    return trainer.make_algorithm(1)


def _from_lm_harness(flags, bilat):
    args = gossip_lm.parse_args(
        LM_TINY + flags + (["--bilat", "True"] if bilat else []))
    return gossip_lm.build_training(
        args, make_logger("t", False)).algorithm


def _described(alg):
    """What makes two algorithm objects the same job."""
    out = {"kind": type(alg).__name__}
    for name in ("overlap", "staleness", "gossip_every",
                 "global_avg_every", "error_feedback"):
        out[name] = getattr(alg, name, None)
    wire = getattr(alg, "wire", None)
    out["wire"] = None if wire is None else wire.to_dict()
    arrays = {}
    schedule = getattr(alg, "schedule", None)
    if schedule is not None:
        arrays = {k: getattr(schedule, k)
                  for k in ("perms", "self_weight", "edge_weights")}
    elif hasattr(alg, "pairing"):
        arrays = {"pairing": alg.pairing}
    return out, arrays


@pytest.mark.parametrize("harness", [_from_image_harness, _from_lm_harness],
                         ids=["image", "lm"])
@pytest.mark.parametrize("mode", list(MODES))
def test_equal_flags_give_the_same_algorithm_from_both_harnesses(
        mode, harness):
    flags, direct = MODES[mode]
    got, got_arrays = _described(harness(flags, bilat=mode == "bilateral"))
    want, want_arrays = _described(direct())
    assert got == want
    assert got_arrays.keys() == want_arrays.keys()
    for k in want_arrays:
        np.testing.assert_array_equal(got_arrays[k], want_arrays[k])


def test_build_training_is_the_job_main_runs(tmp_path):
    """Two steps of ``build_training``'s ``train_fn`` on its ``state``
    give the losses ``main`` writes to its CSV under the same flags."""
    from stochastic_gradient_push_tpu.data.lm import (
        lm_batches, synthetic_lm_corpus)

    flags = LM_TINY + ["--num_steps", "2", "--print_freq", "1",
                       "--corpus_tokens", "4000"]
    gossip_lm.main(flags + ["--checkpoint_dir", str(tmp_path)])
    with open(tmp_path / f"lm_out_n{WORLD}.csv") as f:
        logged = [row["loss"] for row in csv.DictReader(f)]

    args = gossip_lm.parse_args(flags)
    job = gossip_lm.build_training(args, make_logger("t", False))
    assert (job.dp, job.sp, job.tp, job.ep, job.pp) == (WORLD, 1, 1, 1, 1)
    assert job.eval_fn is None and job.plan is not None
    corpus = synthetic_lm_corpus(args.corpus_tokens,
                                 vocab_size=args.vocab_size, seed=args.seed)
    state, losses = job.state, []
    for _, (tokens, targets) in zip(range(2), lm_batches(
            corpus, WORLD, 1, args.batch_size, args.seq_len,
            seed=args.seed)):
        state, metrics = job.train_fn(state, tokens[:, 0], targets[:, 0])
        jax.block_until_ready(state)
        losses.append(f"{float(np.mean(metrics['loss'])):.4f}")
    assert losses == logged


@pytest.mark.parametrize("cosine", [False, True], ids=["step", "cosine"])
def test_trainer_is_ready_when_constructed(cosine):
    """No ``fit`` needed: the LR schedule is there, and ``init_state`` →
    ``_train_fn`` is a complete assembly that runs a step."""
    from stochastic_gradient_push_tpu.models import TinyCNN

    cfg, _ = gossip_sgd.parse_config(
        ["--world_size", str(WORLD), "--num_classes", "4", "--batch_size",
         "2", "--cosine_lr", str(cosine)])
    trainer = Trainer(cfg, TinyCNN(num_classes=4), make_gossip_mesh(WORLD),
                      sample_input_shape=(2, 8, 8, 3))
    assert isinstance(trainer.lr_schedule_obj,
                      CosineLRSchedule if cosine else LRSchedule)
    state = trainer.init_state()
    _, step = trainer._train_fn(1, itr_per_epoch=4)
    rng = np.random.default_rng(0)
    images = rng.standard_normal((WORLD, 2, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 4, size=(WORLD, 2)).astype(np.int32)
    state, metrics = step(state, images, labels)
    assert np.isfinite(np.asarray(metrics["loss"])).all()


def test_bench_py_without_a_mode_points_at_the_benchmark():
    done = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 2
    assert "benchmark/run.py" in done.stderr
    for mode in ("--gossip-vs-ar", "--overlap-vs-sync",
                 "--synth-vs-registry", "--sim-scale"):
        assert mode in done.stderr
