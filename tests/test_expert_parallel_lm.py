"""MoE transformer with expert parallelism, end to end with gossip DP."""

import jax
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
    EP_AXIS,
    build_lm_train_step,
    ep_state_specs,
    init_lm_state_ep,
    make_dp_ep_mesh,
    shard_lm_train_step,
)

DP, EP = 2, 4
VOCAB, D, LAYERS, HEADS, FF, EXPERTS = 64, 32, 2, 4, 32, 8
BATCH, SEQ = 2, 32


def test_moe_lm_trains_with_gossip_and_ep():
    mesh = make_dp_ep_mesh(DP, EP)
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=HEADS,
        d_ff=FF, max_len=SEQ, attn_impl="full",
        moe_experts=EXPERTS, moe_every=2, ep_axis=EP_AXIS)
    model = TransformerLM(cfg)
    alg = sgp(build_schedule(DynamicDirectedExponentialGraph(DP)),
              GOSSIP_AXIS)
    tx = sgd(momentum=0.9, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.5, batch_size=BATCH, world_size=DP * EP,
                     decay_schedule={}, warmup=False)
    step = build_lm_train_step(model, alg, tx, lrs, itr_per_epoch=100,
                               seq_axis=None, ep_axis=EP_AXIS)
    state = init_lm_state_ep(model, mesh, alg, tx, dp=DP, ep=EP,
                             batch_size=BATCH, seq_len=SEQ)
    train_fn = shard_lm_train_step(step, mesh, seq_axis=None,
                                   state_specs=ep_state_specs(state),
                                   ep_axis=EP_AXIS)

    # expert leaves really shard over ep; router/attention replicate
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    expert_shapes = [(p, l.shape, str(l.sharding.spec)) for p, l in flat
                     if any("experts" in str(k) for k in p)]
    assert expert_shapes, "no expert leaves found"
    for p, shape, spec in expert_shapes:
        assert "ep" in spec, (p, spec)
        assert shape[1] == EXPERTS  # global expert dim intact
    # distinct expert initializations across ep shards
    up = [l for pth, l in flat
          if any("experts_up" in str(k) for k in pth)][0]
    up = np.asarray(up)[0]  # [E, D, F] for gossip rank 0
    for a in range(EXPERTS):
        for b in range(a + 1, EXPERTS):
            assert not np.allclose(up[a], up[b]), (a, b)

    corpus = synthetic_lm_corpus(30_000, vocab_size=VOCAB, seed=3)
    losses = []
    for epoch in range(3):
        for tokens, targets in lm_batches(corpus, DP * EP, 1, BATCH, SEQ,
                                          seed=epoch):
            # [dp*ep, 1, B, T] → [dp, ep, B, T]
            tokens = tokens.reshape(DP, EP, BATCH, SEQ)
            targets = targets.reshape(DP, EP, BATCH, SEQ)
            state, metrics = train_fn(state, tokens, targets)
            jax.block_until_ready(state)
            losses.append(float(np.mean(np.asarray(metrics["loss"]))))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.95, (
        losses[:5], losses[-5:])

    # the trained router (from the FINAL state — earlier buffers were
    # donated) is finite and nonzero
    final_flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    router = [l for p, l in final_flat
              if any("router" in str(k) for k in p)][0]
    r = np.asarray(router)
    assert np.all(np.isfinite(r)) and np.abs(r).max() > 0


@pytest.mark.slow
def test_ep_train_step_matches_full_expert_model():
    """One momentum-free SGD step on the (gossip=1, ep=2) mesh moves every
    param — expert slices included — by exactly ``-lr * grad`` of the
    stacked full-expert model under the mean-over-ep-shards CE.

    Pins the uniform ``/n_ep`` gradient scaling: expert grads arrive as
    the SUM over shards via the all_to_all transpose (each expert
    processes slots from every shard), so exempting them from the
    division — as round 3 did — trains experts with an effective
    ``n_ep``× learning rate while every loss/eval metric looks fine.
    """
    import jax.numpy as jnp

    from stochastic_gradient_push_tpu.algorithms import all_reduce
    from stochastic_gradient_push_tpu.train.lm import lm_loss

    dp, ep = 1, 2
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=HEADS,
        d_ff=FF, max_len=SEQ, attn_impl="full",
        moe_experts=4, moe_every=2, moe_capacity_factor=8.0,
        ep_axis=EP_AXIS)
    model = TransformerLM(cfg)
    mesh = make_dp_ep_mesh(dp, ep)
    alg = all_reduce(GOSSIP_AXIS)
    tx = sgd(momentum=0.0, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.1, batch_size=BATCH, world_size=dp * ep,
                     decay_schedule={}, warmup=False)
    step = build_lm_train_step(model, alg, tx, lrs, itr_per_epoch=100,
                               seq_axis=None, ep_axis=EP_AXIS,
                               moe_loss_coef=0.0)
    state = init_lm_state_ep(model, mesh, alg, tx, dp=dp, ep=ep,
                             batch_size=BATCH, seq_len=SEQ)
    train_fn = shard_lm_train_step(step, mesh, seq_axis=None,
                                   state_specs=ep_state_specs(state),
                                   ep_axis=EP_AXIS)
    rng = np.random.default_rng(7)
    toks = rng.integers(0, VOCAB,
                        size=(dp, ep, BATCH, SEQ)).astype(np.int32)
    tgts = rng.integers(0, VOCAB,
                        size=(dp, ep, BATCH, SEQ)).astype(np.int32)

    # rank-0 slice of the global state: expert dims are already global
    ref_params = jax.tree.map(lambda a: np.asarray(a)[0], state.params)
    ref_model = TransformerLM(cfg._replace(ep_axis=None))

    def ref_loss(p):
        ces = []
        for j in range(ep):
            logits = ref_model.apply({"params": p}, toks[0, j])
            ces.append(lm_loss(logits, tgts[0, j]))
        return jnp.mean(jnp.stack(ces))

    ref_grads = jax.grad(ref_loss)(ref_params)
    new_state, metrics = train_fn(state, toks, tgts)
    assert float(np.asarray(metrics["moe_dropped"])[0]) == 0.0
    lr = float(np.asarray(metrics["lr"])[0])
    new_ref = jax.tree.map(lambda a: np.asarray(a)[0], new_state.params)
    expect = jax.tree.map(lambda p, g: p - lr * np.asarray(g),
                          ref_params, ref_grads)
    flat_e, _ = jax.tree_util.tree_flatten_with_path(expect)
    flat_n, _ = jax.tree_util.tree_flatten_with_path(new_ref)
    for (path_e, e), (_, n) in zip(flat_e, flat_n):
        np.testing.assert_allclose(
            np.asarray(n), np.asarray(e), rtol=5e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path_e))


def test_composition_fences_raise_clean_errors():
    """Unsupported parallelism compositions fail at the CLI boundary with
    actionable messages (ARCHITECTURE.md composition matrix)."""
    import pytest

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    base = ["--world_size", "8", "--moe_experts", "4", "--num_steps", "1"]
    with pytest.raises(SystemExit, match="requires --moe_experts"):
        main(["--world_size", "8", "--ep", "2", "--num_steps", "1"])
    with pytest.raises(SystemExit, match="needs --sp"):
        main(base + ["--ep", "2", "--attn", "ring"])


@pytest.mark.slow
def test_ring_flash_composes_with_pp_sp(tmp_path):
    """attn=ring_flash inside pipeline ticks (custom-vjp ppermutes in a
    lax.cond branch of the tick scan) trains end-to-end on the 3-D
    gossip × pipe × seq mesh."""
    import subprocess
    import sys

    from tests.test_run_layer import CLI_ENV

    cmd = [sys.executable, "-m",
           "stochastic_gradient_push_tpu.run.gossip_lm",
           "--world_size", "8", "--pp", "2", "--sp", "2",
           "--attn", "ring_flash", "--seq_len", "64", "--d_model", "32",
           "--n_layers", "2", "--n_heads", "4", "--d_ff", "32",
           "--batch_size", "4", "--n_micro", "2", "--num_steps", "4",
           "--checkpoint_dir", str(tmp_path)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=420,
                       env=CLI_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    assert '"final_loss"' in r.stdout + r.stderr


@pytest.mark.slow
def test_moe_ep_sp_tp_4d_trains(tmp_path):
    """All four axes at once: gossip × ep × seq × tp on one 4-D mesh,
    with held-out validation through the same composed forward."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    r = main(["--world_size", "8", "--ep", "2", "--sp", "2", "--tp", "2",
              "--moe_experts", "4", "--moe_every", "2",
              "--seq_len", "32", "--d_model", "32", "--n_layers", "2",
              "--n_heads", "4", "--d_ff", "64", "--vocab_size", "64",
              "--batch_size", "4", "--num_steps", "4",
              "--corpus_tokens", "40000", "--print_freq", "2",
              "--val_frac", "0.1", "--val_every", "2",
              "--val_batches", "2", "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(r["final_loss"])
    assert np.isfinite(r["val_loss"])


@pytest.mark.slow
def test_moe_with_ring_sp_trains(tmp_path):
    """MoE composed with ring sequence parallelism (per-block routing)
    trains end-to-end through the CLI."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    r = main(["--world_size", "8", "--sp", "2", "--moe_experts", "2",
              "--moe_every", "2", "--seq_len", "32", "--d_model", "32",
              "--n_layers", "2", "--n_heads", "4", "--d_ff", "32",
              "--vocab_size", "32", "--batch_size", "2", "--num_steps", "4",
              "--corpus_tokens", "20000",
              "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(r["final_loss"])


@pytest.mark.slow
def test_moe_ep_with_tp_matches_ep_only(tmp_path):
    """ep × tp: expert parallelism (manual all_to_all dispatch over ep)
    composed with GSPMD tensor parallelism on the 3-D (gossip, ep, tp)
    mesh — same tokens, same routing ⇒ same losses as the ep-only run,
    and the expert/projection kernels really shard over tp."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from stochastic_gradient_push_tpu.run.gossip_lm import main
    from stochastic_gradient_push_tpu.train.lm import (
        EP_AXIS, TP_AXIS, ep_tp_sharding_tree, make_dp_ep_tp_mesh)

    common = ["--moe_experts", "4", "--moe_every", "1", "--seq_len", "32",
              "--d_model", "32", "--n_layers", "2", "--n_heads", "4",
              "--d_ff", "64", "--vocab_size", "64", "--batch_size", "4",
              "--num_steps", "4", "--corpus_tokens", "20000",
              "--print_freq", "2"]
    r_tp = main(["--world_size", "8", "--ep", "2", "--tp", "2",
                 "--checkpoint_dir", str(tmp_path / "tp")] + common)
    r_ep = main(["--world_size", "4", "--ep", "2",
                 "--checkpoint_dir", str(tmp_path / "ep")] + common)
    assert np.isfinite(r_tp["final_loss"])
    np.testing.assert_allclose(r_tp["final_loss"], r_ep["final_loss"],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(r_tp["avg_loss"], r_ep["avg_loss"],
                               rtol=2e-5, atol=2e-5)

    # the sharding tree really puts tp on expert FFN dims and ep on the
    # expert dim (a replicated layout would make the parity vacuous)
    import jax
    import jax.numpy as jnp

    mesh = make_dp_ep_tp_mesh(2, 2, 2)
    probe = {"block_0": {"moe": {"experts_up": jnp.zeros((2, 4, 8, 16)),
                                 "experts_down": jnp.zeros((2, 4, 16, 8)),
                                 "router": {"kernel": jnp.zeros((2, 8, 4))}}}}
    shard = ep_tp_sharding_tree(probe, mesh)
    assert shard["block_0"]["moe"]["experts_up"].spec == \
        P("gossip", EP_AXIS, None, TP_AXIS)
    assert shard["block_0"]["moe"]["experts_down"].spec == \
        P("gossip", EP_AXIS, TP_AXIS, None)
    assert shard["block_0"]["moe"]["router"]["kernel"].spec == \
        P("gossip", None, None)


@pytest.mark.slow
def test_moe_pp_trains(tmp_path):
    """MoE × pipeline through the CLI: replicated expert blocks routed per
    microbatch inside the tick schedule (moe_every=1)."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    r = main(["--world_size", "8", "--pp", "2", "--n_micro", "2",
              "--moe_experts", "4", "--moe_every", "1",
              "--seq_len", "32", "--d_model", "32", "--n_layers", "2",
              "--n_heads", "4", "--d_ff", "32", "--vocab_size", "32",
              "--batch_size", "4", "--num_steps", "4",
              "--corpus_tokens", "40000", "--print_freq", "2",
              "--val_frac", "0.1", "--val_every", "2", "--val_batches",
              "2", "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(r["final_loss"])
    # the pipelined eval path (stage-gated head) produced a real value
    assert np.isfinite(r["val_loss"])


@pytest.mark.slow
def test_moe_pp_ep_trains(tmp_path):
    """pp × ep through the CLI: expert-sharded dispatch (all_to_all over
    ep) inside the pipeline tick schedule, with held-out validation."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    r = main(["--world_size", "8", "--pp", "2", "--ep", "2",
              "--n_micro", "2", "--moe_experts", "4", "--moe_every", "1",
              "--seq_len", "32", "--d_model", "32", "--n_layers", "2",
              "--n_heads", "4", "--d_ff", "32", "--vocab_size", "32",
              "--batch_size", "4", "--num_steps", "4",
              "--corpus_tokens", "40000", "--print_freq", "2",
              "--val_frac", "0.1", "--val_every", "2", "--val_batches",
              "2", "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(r["final_loss"])
    assert np.isfinite(r["val_loss"])


@pytest.mark.slow
def test_moe_pp_sp_trains(tmp_path):
    """MoE × pp × sp through the CLI: per-block expert routing inside the
    ring-attention pipeline ticks."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    r = main(["--world_size", "8", "--pp", "2", "--sp", "2",
              "--n_micro", "2", "--moe_experts", "4", "--moe_every", "1",
              "--seq_len", "32", "--d_model", "32", "--n_layers", "2",
              "--n_heads", "4", "--d_ff", "32", "--vocab_size", "32",
              "--batch_size", "4", "--num_steps", "3",
              "--corpus_tokens", "20000", "--print_freq", "3",
              "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(r["final_loss"])


@pytest.mark.slow
def test_moe_pp_ep_sp_4d_trains(tmp_path):
    """The 4-D pipeline mesh through the CLI: gossip × pipe × ep × seq
    with validation through the same composed forward."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    r = main(["--world_size", "8", "--pp", "2", "--ep", "2", "--sp", "2",
              "--n_micro", "2", "--moe_experts", "4", "--moe_every", "1",
              "--seq_len", "32", "--d_model", "32", "--n_layers", "2",
              "--n_heads", "4", "--d_ff", "32", "--vocab_size", "64",
              "--batch_size", "4", "--num_steps", "3",
              "--corpus_tokens", "40000", "--print_freq", "3",
              "--val_frac", "0.1", "--val_every", "3",
              "--val_batches", "2", "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(r["final_loss"])
    assert np.isfinite(r["val_loss"])


def test_moe_ep_with_ring_sp_trains(tmp_path):
    """ep x sp: expert parallelism (all_to_all over ep) composed with
    ring sequence parallelism on the 3-D (gossip, ep, seq) mesh."""
    import numpy as np

    from stochastic_gradient_push_tpu.run.gossip_lm import main

    r = main(["--world_size", "8", "--ep", "2", "--sp", "2",
              "--moe_experts", "4", "--moe_every", "2",
              "--seq_len", "32", "--d_model", "32", "--n_layers", "2",
              "--n_heads", "4", "--d_ff", "32", "--vocab_size", "32",
              "--batch_size", "2", "--num_steps", "6",
              "--corpus_tokens", "40000", "--print_freq", "2",
              "--val_frac", "0.1", "--val_every", "2", "--val_batches",
              "2", "--checkpoint_dir", str(tmp_path)])
    assert np.isfinite(r["final_loss"])
    # the expert-dispatched eval path (ep × sp) produced a real value
    assert np.isfinite(r["val_loss"])
    # divergence guard: stay at or below the uniform-prediction loss
    # (log 32 ≈ 3.47 + small MoE aux term) after 6 steps
    assert r["final_loss"] < 3.6
