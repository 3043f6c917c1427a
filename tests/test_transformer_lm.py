"""Transformer LM: attention-backend equivalence and gossip-DP × ring-SP
end-to-end training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.data.lm import (
    lm_batches,
    synthetic_lm_corpus,
)
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS
from stochastic_gradient_push_tpu.topology import (
    DynamicDirectedExponentialGraph,
    build_schedule,
)
from stochastic_gradient_push_tpu.train import LRSchedule, sgd
from stochastic_gradient_push_tpu.train.lm import (
    SEQ_AXIS,
    build_lm_train_step,
    lm_loss,
    make_dp_sp_mesh,
    shard_lm_train_step,
)
from stochastic_gradient_push_tpu.train.state import TrainState

VOCAB, D, LAYERS, HEADS = 64, 32, 2, 4
DP, SP = 4, 2
BATCH, SEQ = 2, 32


def small_cfg(attn_impl="full", seq_axis=None):
    return TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=HEADS,
        d_ff=64, max_len=SEQ, attn_impl=attn_impl, attn_block_size=8,
        seq_axis=seq_axis)


def test_attention_backends_agree():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, size=(2, SEQ)).astype(np.int32)
    full = TransformerLM(small_cfg("full"))
    variables = full.init(jax.random.PRNGKey(0), tokens)
    out_full = full.apply(variables, tokens)
    for impl in ("blockwise", "flash"):
        other = TransformerLM(small_cfg(impl))
        out = other.apply(variables, tokens)
        np.testing.assert_allclose(np.asarray(out_full), np.asarray(out),
                                   rtol=2e-4, atol=2e-4, err_msg=impl)
    # asymmetric flash blocks (block_k != block_q) are the same function
    asym_cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=HEADS,
        d_ff=64, max_len=SEQ, attn_impl="flash", attn_block_size=16,
        attn_block_k=8)
    out = TransformerLM(asym_cfg).apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(out),
                               rtol=2e-4, atol=2e-4, err_msg="flash asym")


def test_ring_sequence_parallel_forward_matches_single_device():
    """The seq-sharded ring forward must equal the single-device full
    forward on the same weights and tokens."""
    from jax.sharding import PartitionSpec as P

    mesh = make_dp_sp_mesh(1, 8)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)

    full = TransformerLM(small_cfg("full"))
    variables = full.init(jax.random.PRNGKey(0), tokens)
    want = np.asarray(full.apply(variables, tokens))

    ring = TransformerLM(small_cfg("ring", seq_axis=SEQ_AXIS))
    block = SEQ // 8
    # [B, T] → [1, 8, B, block]
    sharded_tokens = tokens.reshape(BATCH, 8, block).transpose(1, 0, 2)
    sharded_tokens = sharded_tokens[None]

    def fwd(params, toks):
        return ring.apply({"params": params}, toks[0, 0])[None, None]

    f = jax.jit(jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(GOSSIP_AXIS, SEQ_AXIS)),
        out_specs=P(GOSSIP_AXIS, SEQ_AXIS)))
    out = np.asarray(f(variables["params"], sharded_tokens))
    # [1, 8, B, block, V] → [B, T, V]
    got = out[0].transpose(1, 0, 2, 3).reshape(BATCH, SEQ, VOCAB)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_ring_flash_forward_matches_single_device():
    """attn_impl='ring_flash' (flash-kernel ticks, ops/ring_flash.py) is
    the same function as the single-device full forward."""
    from jax.sharding import PartitionSpec as P

    mesh = make_dp_sp_mesh(1, 8)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)

    full = TransformerLM(small_cfg("full"))
    variables = full.init(jax.random.PRNGKey(0), tokens)
    want = np.asarray(full.apply(variables, tokens))

    rf = TransformerLM(small_cfg("ring_flash", seq_axis=SEQ_AXIS))
    block = SEQ // 8
    sharded_tokens = tokens.reshape(BATCH, 8, block).transpose(1, 0, 2)
    sharded_tokens = sharded_tokens[None]

    def fwd(params, toks):
        return rf.apply({"params": params}, toks[0, 0])[None, None]

    f = jax.jit(jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(GOSSIP_AXIS, SEQ_AXIS)),
        out_specs=P(GOSSIP_AXIS, SEQ_AXIS)))
    out = np.asarray(f(variables["params"], sharded_tokens))
    got = out[0].transpose(1, 0, 2, 3).reshape(BATCH, SEQ, VOCAB)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.slow
def test_gossip_dp_with_ring_sp_trains():
    """4 gossip replicas × 2 sequence shards: loss decreases well below the
    unigram entropy on a Markov corpus."""
    mesh = make_dp_sp_mesh(DP, SP)
    cfg = small_cfg("ring", seq_axis=SEQ_AXIS)
    model = TransformerLM(cfg)
    sched = build_schedule(DynamicDirectedExponentialGraph(DP,
                                                           peers_per_itr=1))
    alg = sgp(sched, GOSSIP_AXIS)
    tx = sgd(momentum=0.9, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.5, batch_size=BATCH, world_size=DP * SP,
                     decay_schedule={}, warmup=False)
    step = build_lm_train_step(model, alg, tx, lrs, itr_per_epoch=100)
    train_fn = shard_lm_train_step(step, mesh)

    block = SEQ // SP
    # ring models reference the mesh axis, so init runs under shard_map
    from jax.sharding import PartitionSpec as P

    def init_fn(toks):
        variables = model.init(jax.random.PRNGKey(0), toks[0, 0])
        return jax.tree.map(lambda a: a[None], variables["params"])

    init_sharded = jax.jit(jax.shard_map(
        init_fn, mesh=mesh,
        in_specs=(P(GOSSIP_AXIS, SEQ_AXIS),),
        out_specs=P(GOSSIP_AXIS)))
    dummy = np.zeros((DP, SP, BATCH, block), np.int32)
    params = init_sharded(dummy)
    state = TrainState(
        step=jnp.zeros((DP,), jnp.int32),
        params=params,
        batch_stats={},
        opt_state=jax.tree.map(
            lambda a: jnp.broadcast_to(jnp.asarray(a)[None],
                                       (DP,) + jnp.shape(a)).copy(),
            tx.init(jax.tree.map(lambda a: a[0], params))),
        gossip=jax.tree.map(
            lambda a: jnp.broadcast_to(jnp.asarray(a)[None],
                                       (DP,) + jnp.shape(a)).copy(),
            alg.init(jax.tree.map(lambda a: a[0], params))))

    corpus = synthetic_lm_corpus(40_000, vocab_size=VOCAB, seed=2)
    losses = []
    for epoch in range(6):
        for tokens, targets in lm_batches(corpus, DP, SP, BATCH, SEQ,
                                          seed=epoch):
            state, metrics = train_fn(state, tokens, targets)
            jax.block_until_ready(state)
            losses.append(float(np.mean(np.asarray(metrics["loss"]))))

    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < first * 0.75, (first, last)
    # unigram entropy of a 64-symbol near-uniform marginal is ~4.1 nats;
    # learning the Markov structure must beat it
    assert last < 3.5, last


def test_lm_loss_matches_manual_ce():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 8, VOCAB)).astype(np.float32)
    targets = rng.integers(0, VOCAB, size=(2, 8)).astype(np.int32)
    got = float(lm_loss(jnp.asarray(logits), jnp.asarray(targets)))
    logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))
    want = -np.mean([logp[b, t, targets[b, t]]
                     for b in range(2) for t in range(8)])
    np.testing.assert_allclose(got, want, rtol=1e-6)


def _lm_loss_by_gather(logits, targets):
    """``lm_loss`` as it read the target's logit up to PR 33: the
    reference the comparison is held to.  Its transpose is a scatter-add
    into an array of the logits' size; the values are the same."""
    logits = jnp.asarray(logits, jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("vocab", [256, 257])
def test_lm_loss_by_comparison_is_the_gathers_loss(vocab, dtype):
    """A vocabulary that is a multiple of 128 lanes and one that is not
    (as 50257), targets at both ends of the axis: the value bit for bit,
    the logits' gradient to 1e-6."""
    rng = np.random.default_rng(vocab)
    logits = jnp.asarray(4 * rng.normal(size=(2, 9, vocab)), dtype)
    targets = rng.integers(0, vocab, size=(2, 9)).astype(np.int32)
    targets[0, 0], targets[1, -1] = 0, vocab - 1
    targets = jnp.asarray(targets)
    got, dgot = jax.value_and_grad(lm_loss)(logits, targets)
    want, dwant = jax.value_and_grad(_lm_loss_by_gather)(logits, targets)
    assert got.dtype == want.dtype == jnp.float32
    assert float(got) == float(want)
    assert dgot.dtype == dtype
    np.testing.assert_allclose(np.asarray(dgot, np.float32),
                               np.asarray(dwant, np.float32), atol=1e-6)
    # nothing whose transpose scatters: the backward holds no scatter
    text = jax.jit(jax.grad(lm_loss)).lower(logits, targets).as_text()
    assert "scatter" not in text and "gather" not in text


@pytest.mark.parametrize("scaling", [1.0, 8.0])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_lm_loss_by_comparison_through_the_model(tied, scaling):
    """Through ``jax.value_and_grad`` of a two-block model, its head tied
    and not, its logits divided and not: the parameters' gradients."""
    cfg = small_cfg()._replace(vocab_size=257, dtype=jnp.bfloat16,
                               tie_embeddings=tied, logits_scaling=scaling)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 257, size=(2, SEQ)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert ("lm_head" in params) == (not tied)

    def run(loss):
        return jax.value_and_grad(lambda p: loss(
            model.apply({"params": p}, tokens), targets))(params)

    got, ggot = run(lm_loss)
    want, gwant = run(_lm_loss_by_gather)
    assert float(got) == float(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ggot),
                            jax.tree.leaves(gwant)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))


def test_lm_cli_checkpoint_and_resume(tmp_path):
    """LM CLI saves its state+step atomically and resumes from it."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    base = ["--world_size", "8", "--seq_len", "32", "--d_model", "32",
            "--n_layers", "1", "--n_heads", "4", "--d_ff", "32",
            "--vocab_size", "32", "--batch_size", "2",
            "--corpus_tokens", "20000", "--print_freq", "2",
            "--checkpoint_dir", str(tmp_path)]
    r1 = main(base + ["--num_steps", "4"])
    assert np.isfinite(r1["final_loss"])
    assert (tmp_path / "lm_checkpoint_r0_n8.ckpt").exists()

    r2 = main(base + ["--num_steps", "8", "--resume", "True"])
    assert np.isfinite(r2["final_loss"])
    csv = (tmp_path / "lm_out_n8.csv").read_text().splitlines()
    steps = [int(l.split(",")[0]) for l in csv[1:]]
    # rows from both runs, continuing past the first run's horizon
    assert 4 in steps and 8 in steps


@pytest.mark.slow
def test_lm_cli_orbax_backend_save_and_resume(tmp_path):
    """--ckpt_backend orbax through the LM CLI: per-step orbax saves with
    retention, then resume from the latest step."""
    import os

    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    base = ["--world_size", "8", "--seq_len", "32", "--d_model", "32",
            "--n_layers", "1", "--n_heads", "4", "--d_ff", "32",
            "--vocab_size", "32", "--batch_size", "2",
            "--corpus_tokens", "20000", "--print_freq", "2",
            "--ckpt_backend", "orbax", "--checkpoint_dir", str(tmp_path)]
    r1 = main(base + ["--num_steps", "4"])
    assert np.isfinite(r1["final_loss"])
    root = tmp_path / "lm_orbax_r0_n8"
    assert root.is_dir(), f"missing orbax root under {os.listdir(tmp_path)}"
    assert any(d.name == "4" for d in root.iterdir()), \
        "no step-4 orbax checkpoint"

    r2 = main(base + ["--num_steps", "8", "--resume", "True"])
    assert np.isfinite(r2["final_loss"])
    csv = (tmp_path / "lm_out_n8.csv").read_text().splitlines()
    steps = [int(l.split(",")[0]) for l in csv[1:]]
    assert 4 in steps and 8 in steps


@pytest.mark.slow
def test_scanned_lm_step_matches_sequential():
    """shard_scanned_lm_step(n) produces the same state and per-step losses
    as n individual dispatches, for plain dp and dp x sp (ring) layouts."""
    from stochastic_gradient_push_tpu.train.lm import (init_lm_state,
                                                       shard_scanned_lm_step)

    for ring in (False, True):
        sp = SP if ring else 1
        mesh = make_dp_sp_mesh(DP, SP) if ring else make_dp_sp_mesh(DP * SP,
                                                                    1)
        dp = DP if ring else DP * SP
        cfg = small_cfg("ring" if ring else "full",
                        seq_axis=SEQ_AXIS if ring else None)
        model = TransformerLM(cfg)
        alg = sgp(build_schedule(DynamicDirectedExponentialGraph(dp)),
                  GOSSIP_AXIS)
        tx = sgd(momentum=0.9, weight_decay=0.0)
        lrs = LRSchedule(ref_lr=0.1, batch_size=BATCH, world_size=dp,
                         decay_schedule={}, warmup=False)
        step = build_lm_train_step(
            model, alg, tx, lrs, itr_per_epoch=100,
            seq_axis=SEQ_AXIS if ring else None)
        seq_axis = SEQ_AXIS if ring else None
        train_fn = shard_lm_train_step(step, mesh, seq_axis=seq_axis)
        scan_fn = shard_scanned_lm_step(step, mesh, n_steps=3,
                                        seq_axis=seq_axis)
        block = SEQ // sp

        state = init_lm_state(model, mesh, alg, tx, dp=dp, sp=sp,
                              batch_size=BATCH, block_len=block,
                              seq_axis=seq_axis)
        state2 = jax.tree.map(jnp.copy, state)

        rng = np.random.default_rng(0)
        shape = ((dp, sp, BATCH, block) if ring
                 else (dp, BATCH, block))
        toks = rng.integers(0, VOCAB, size=(3,) + shape).astype(np.int32)
        tgts = rng.integers(0, VOCAB, size=(3,) + shape).astype(np.int32)

        seq_losses = []
        for i in range(3):
            state, m = train_fn(state, toks[i], tgts[i])
            jax.block_until_ready(state)
            seq_losses.append(np.asarray(m["loss"]))
        state2, ms = scan_fn(state2, toks, tgts)
        jax.block_until_ready(state2)

        np.testing.assert_allclose(
            np.stack(seq_losses, axis=1), np.asarray(ms["loss"]),
            rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(state2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)


@pytest.mark.slow
def test_lm_cli_validation(tmp_path):
    """--val_frac holds out corpus tail; val_loss/val_ppl columns appear at
    --val_every steps and at the end, for both plain and ring layouts."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    for extra in ([], ["--sp", "2"]):
        d = tmp_path / ("ring" if extra else "plain")
        r = main(["--world_size", "8", "--seq_len", "32", "--d_model",
                  "32", "--n_layers", "1", "--n_heads", "4", "--d_ff",
                  "32", "--vocab_size", "32", "--batch_size", "2",
                  "--corpus_tokens", "30000", "--print_freq", "2",
                  "--num_steps", "4", "--val_frac", "0.1",
                  "--val_every", "2", "--val_batches", "2",
                  "--checkpoint_dir", str(d)] + extra)
        assert np.isfinite(r["val_loss"])
        csv = (d / "lm_out_n8.csv").read_text().splitlines()
        assert csv[0].endswith("val_loss,val_ppl")
        val_rows = [l for l in csv[1:] if l.split(",")[5]]
        assert val_rows, csv
        for l in val_rows:
            assert np.isfinite(float(l.split(",")[5]))


def test_grad_accum_matches_full_batch():
    """grad_accum splits the batch into scanned microbatches; the LM has
    no BatchNorm, so one accumulated step must equal the full-batch step
    EXACTLY (params, loss, grad_norm)."""
    from stochastic_gradient_push_tpu.algorithms import all_reduce
    from stochastic_gradient_push_tpu.train.lm import (
        init_lm_state, shard_lm_train_step)

    dp = 2
    mesh = make_dp_sp_mesh(dp, 1)
    cfg = small_cfg("full")
    model = TransformerLM(cfg)
    alg = all_reduce(GOSSIP_AXIS)
    tx = sgd(momentum=0.0, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.1, batch_size=4, world_size=dp,
                     decay_schedule={}, warmup=False)
    rng = np.random.default_rng(5)
    toks = rng.integers(0, VOCAB, size=(dp, 4, SEQ)).astype(np.int32)
    tgts = rng.integers(0, VOCAB, size=(dp, 4, SEQ)).astype(np.int32)

    results = {}
    for ga in (1, 2, 4):
        step = build_lm_train_step(model, alg, tx, lrs,
                                   itr_per_epoch=100, seq_axis=None,
                                   grad_accum=ga)
        state = init_lm_state(model, mesh, alg, tx, dp=dp, sp=1,
                              batch_size=4, block_len=SEQ, seq_axis=None)
        fn = shard_lm_train_step(step, mesh, seq_axis=None)
        new_state, metrics = fn(state, toks, tgts)
        results[ga] = (jax.tree.map(np.asarray, new_state.params),
                       float(np.asarray(metrics["loss"])[0]),
                       float(np.asarray(metrics["grad_norm"])[0]))

    for ga in (2, 4):
        np.testing.assert_allclose(results[ga][1], results[1][1],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(results[ga][2], results[1][2],
                                   rtol=1e-4, atol=1e-6)
        for a, b in zip(jax.tree.leaves(results[ga][0]),
                        jax.tree.leaves(results[1][0])):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_grad_accum_matches_full_batch_on_sp_mesh():
    """Ring-attention collectives inside the accumulation scan: one
    grad_accum=2 step on the (gossip, seq) mesh equals the full-batch
    step exactly."""
    from jax.sharding import PartitionSpec as P

    from stochastic_gradient_push_tpu.algorithms import all_reduce
    from stochastic_gradient_push_tpu.train.lm import (
        init_lm_state, shard_lm_train_step)

    dp, sp = 2, 2
    block = SEQ // sp
    mesh = make_dp_sp_mesh(dp, sp)
    cfg = small_cfg("ring", seq_axis=SEQ_AXIS)
    model = TransformerLM(cfg)
    alg = all_reduce(GOSSIP_AXIS)
    tx = sgd(momentum=0.0, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.1, batch_size=4, world_size=dp * sp,
                     decay_schedule={}, warmup=False)
    rng = np.random.default_rng(6)
    toks = rng.integers(0, VOCAB, size=(dp, sp, 4, block)).astype(np.int32)
    tgts = rng.integers(0, VOCAB, size=(dp, sp, 4, block)).astype(np.int32)

    results = {}
    for ga in (1, 2):
        step = build_lm_train_step(model, alg, tx, lrs,
                                   itr_per_epoch=100, seq_axis=SEQ_AXIS,
                                   grad_accum=ga)
        state = init_lm_state(model, mesh, alg, tx, dp=dp, sp=sp,
                              batch_size=4, block_len=block)
        fn = shard_lm_train_step(step, mesh, seq_axis=SEQ_AXIS)
        new_state, metrics = fn(state, toks, tgts)
        results[ga] = (jax.tree.map(np.asarray, new_state.params),
                       float(np.asarray(metrics["loss"])[0]))

    np.testing.assert_allclose(results[2][1], results[1][1],
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(results[2][0]),
                    jax.tree.leaves(results[1][0])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_grad_accum_matches_full_batch_on_ep_mesh():
    """MoE all_to_all dispatch inside the accumulation scan: with
    no-drop capacity and moe_loss_coef=0 (the LB loss is nonlinear in
    the batch split), grad_accum=2 on the (gossip, ep) mesh equals the
    full-batch step exactly."""
    from stochastic_gradient_push_tpu.algorithms import all_reduce
    from stochastic_gradient_push_tpu.train.lm import (
        EP_AXIS, ep_state_specs, init_lm_state_ep, shard_lm_train_step)

    dp, ep = 1, 2
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=HEADS,
        d_ff=64, max_len=SEQ, attn_impl="full", moe_experts=4,
        moe_every=2, moe_capacity_factor=8.0, ep_axis=EP_AXIS)
    model = TransformerLM(cfg)
    from stochastic_gradient_push_tpu.train.lm import make_dp_ep_mesh
    mesh = make_dp_ep_mesh(dp, ep)
    alg = all_reduce(GOSSIP_AXIS)
    tx = sgd(momentum=0.0, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.1, batch_size=4, world_size=dp * ep,
                     decay_schedule={}, warmup=False)
    rng = np.random.default_rng(8)
    toks = rng.integers(0, VOCAB, size=(dp, ep, 4, SEQ)).astype(np.int32)
    tgts = rng.integers(0, VOCAB, size=(dp, ep, 4, SEQ)).astype(np.int32)

    results = {}
    for ga in (1, 2):
        step = build_lm_train_step(model, alg, tx, lrs,
                                   itr_per_epoch=100, seq_axis=None,
                                   ep_axis=EP_AXIS, moe_loss_coef=0.0,
                                   grad_accum=ga)
        state = init_lm_state_ep(model, mesh, alg, tx, dp=dp, ep=ep,
                                 batch_size=4, seq_len=SEQ)
        fn = shard_lm_train_step(step, mesh, seq_axis=None,
                                 state_specs=ep_state_specs(state),
                                 ep_axis=EP_AXIS)
        new_state, metrics = fn(state, toks, tgts)
        assert float(np.asarray(metrics["moe_dropped"])[0]) == 0.0
        results[ga] = (jax.tree.map(np.asarray, new_state.params),
                       float(np.asarray(metrics["loss"])[0]))

    np.testing.assert_allclose(results[2][1], results[1][1],
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(results[2][0]),
                    jax.tree.leaves(results[1][0])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
