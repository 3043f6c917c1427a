"""Quantized gossip wire format + per-rank error feedback (ISSUE 10).

Covers the codec layer (parallel/wire.py) against numpy oracles, the
error-feedback telescoping identity, int8+EF vs f32 consensus parity on
the world-8 CPU mesh, ps-weight-lane exactness under faults plus
compression, reshard residual zeroing, encoded-payload pricing pinned
against hand counts, planner wire-fraction pricing, and the CLI flag
surface of both run harnesses.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.parallel import (
    GOSSIP_AXIS,
    gossip_round,
    make_gossip_mesh,
    mix_push_sum,
)
from stochastic_gradient_push_tpu.parallel import wire
from stochastic_gradient_push_tpu.telemetry import (
    CommModel,
    encoded_payload_bytes,
    tree_payload_bytes,
)
from stochastic_gradient_push_tpu.topology import (
    HierarchicalGraph,
    NPeerDynamicDirectedExponentialGraph,
    RingGraph,
    build_schedule,
)

WORLD = 8


@pytest.fixture(scope="module")
def mesh():
    return make_gossip_mesh(WORLD)


# -- codec oracles ---------------------------------------------------------


def _int8_oracle(x: np.ndarray, block: int) -> np.ndarray:
    """Independent numpy reference for Int8Codec's roundtrip."""
    n = x.size
    nb = -(-n // block)
    flat = np.zeros(nb * block, np.float32)
    flat[:n] = x.reshape(-1).astype(np.float32)
    blocks = flat.reshape(nb, block)
    scale = np.abs(blocks).max(axis=1) / 127.0
    safe = np.where(scale > 0, scale, 1.0)
    q = np.clip(np.round(blocks / safe[:, None]), -127, 127)
    return (q * scale[:, None]).reshape(-1)[:n].reshape(x.shape).astype(
        x.dtype)


@pytest.mark.parametrize("shape", [(7,), (64,), (130,), (3, 5, 11)])
def test_int8_roundtrip_matches_numpy_oracle(shape):
    rng = np.random.default_rng(0)
    x = (rng.normal(size=shape) * rng.uniform(0.01, 10)).astype(
        np.float32)
    codec = wire.Int8Codec(64)
    got = np.asarray(jax.jit(
        lambda a: codec.decode(codec.encode(a), a))(x))
    np.testing.assert_array_equal(got, _int8_oracle(x, 64))


def test_int8_handles_zero_blocks_and_q_of_zero():
    codec = wire.Int8Codec(4)
    x = np.zeros(10, np.float32)
    out = np.asarray(codec.decode(codec.encode(jnp.asarray(x)), x))
    np.testing.assert_array_equal(out, x)  # Q(0) == 0: drop semantics
    q, scale = codec.encode(jnp.asarray(x))
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32


def test_bf16_codec_matches_plain_cast_and_f32_is_identity():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(33,)).astype(np.float32)
    got = np.asarray(wire.BF16.decode(wire.BF16.encode(jnp.asarray(x)),
                                      x))
    np.testing.assert_array_equal(
        got, np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                        .astype(jnp.float32)))
    f32 = np.asarray(wire.F32.decode(wire.F32.encode(jnp.asarray(x)), x))
    np.testing.assert_array_equal(f32, x)


def test_codec_registry_and_pricing():
    assert wire.get_codec(None) is None
    assert wire.get_codec("f32") is wire.F32
    assert wire.get_codec("bf16") is wire.BF16
    int8 = wire.get_codec("int8", 32)
    assert isinstance(int8, wire.Int8Codec) and int8.block == 32
    with pytest.raises(ValueError, match="unknown wire_dtype"):
        wire.get_codec("fp4")
    with pytest.raises(ValueError, match="wire_block"):
        wire.Int8Codec(0)
    # element_bytes hand counts
    assert wire.F32.element_bytes(100) == 400
    assert wire.BF16.element_bytes(100) == 200
    assert wire.Int8Codec(64).element_bytes(100) == 100 + 4 * 2
    # asymptotic fractions drive the planner pricing
    assert wire.F32.wire_fraction() == 1.0
    assert wire.BF16.wire_fraction() == 0.5
    assert wire.Int8Codec(64).wire_fraction() == pytest.approx(
        (1 + 4 / 64) / 4)
    # deprecated alias maps exactly onto the bf16 codec
    assert wire.from_comm_dtype(jnp.bfloat16) is wire.BF16
    assert wire.from_comm_dtype(None) is None


def test_ef_telescoping_identity_single_sender():
    """The error-feedback invariant in isolation: over T rounds,
    sum(delivered) == sum(intended) - final_residual exactly (the
    initial residual is zero) — quantization error never accumulates
    into a bias, it only rides as bounded pending correction."""
    codec = wire.Int8Codec(16)
    rng = np.random.default_rng(2)
    msgs = rng.normal(size=(20, 48)).astype(np.float32)

    def body(r, m):
        v = m + r
        d = codec.decode(codec.encode(v), v)
        return v - d, d

    r = jnp.zeros(48, jnp.float32)
    delivered = np.zeros(48, np.float64)
    step = jax.jit(body)
    for m in msgs:
        r, d = step(r, jnp.asarray(m))
        delivered += np.asarray(d, np.float64)
    want = msgs.astype(np.float64).sum(0) - np.asarray(r, np.float64)
    np.testing.assert_allclose(delivered, want, atol=5e-5)


# -- compiled mesh behavior ------------------------------------------------


def _stacked_init(alg, dim):
    return jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a),
                                  (WORLD,) + np.shape(a)).copy(),
        alg.init(jnp.zeros((dim,), jnp.float32)))


def test_int8_ef_mean_telescopes_on_mesh(mesh):
    """Pure averaging under int8+EF: delivered mass plus pending
    residuals preserves the exact mean; the raw mean drifts by at most
    the residual mass."""
    sched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))
    codec = wire.Int8Codec(64)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(WORLD, 130)).astype(np.float32)
    w = np.ones((WORLD, 1), np.float32)
    r = np.zeros_like(x)
    mean = x.mean(0)

    def step(phase, xs, ws, rs):
        return mix_push_sum(xs, ws, phase, sched, GOSSIP_AXIS,
                            codec=codec, ef_residual=rs)

    f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(GOSSIP_AXIS), P(GOSSIP_AXIS), P(GOSSIP_AXIS)),
        out_specs=(P(GOSSIP_AXIS),) * 3))
    for phase in range(40):
        x, w, r = map(np.asarray,
                      jax.block_until_ready(f(jnp.int32(phase), x, w, r)))
    assert np.abs((x.sum(0) + r.sum(0)) / WORLD - mean).max() < 1e-5
    assert np.abs((x / w).mean(0) - mean).max() < 5e-3
    # and the wire really quantizes: consensus is approximate, not exact
    assert np.abs(r).max() > 0


def test_int8_ef_consensus_parity_with_f32(mesh):
    """Acceptance: an SGD consensus run at int8+EF reaches consensus
    error within 2x of the exact f32 wire after the same step budget,
    and lands at the same optimum."""
    sched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))
    rng = np.random.default_rng(4)
    targets = rng.normal(size=(WORLD, 48)).astype(np.float32)
    p0 = rng.normal(size=(WORLD, 48)).astype(np.float32)
    lr = 0.05

    def run(codec, ef):
        alg = sgp(sched, GOSSIP_AXIS, wire=codec, error_feedback=ef)

        def step(p, g, t):
            p, g = alg.pre_step(p, g)
            z = alg.eval_params(p, g)
            grad = jax.grad(lambda q: 0.5 * jnp.sum((q - t) ** 2))(z)
            return alg.post_step(p - lr * grad, g)

        f = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(GOSSIP_AXIS),) * 3,
            out_specs=(P(GOSSIP_AXIS),) * 2))
        p, g = p0.copy(), _stacked_init(alg, 48)
        for _ in range(150):
            p, g = jax.block_until_ready(f(p, g, targets))
        z = np.asarray(p) / np.asarray(g.ps_weight).reshape(WORLD, 1)
        return (float(np.abs(z - z.mean(0)).max()),
                float(np.abs(z.mean(0) - targets.mean(0)).max()))

    f32_spread, f32_err = run(None, False)
    i8_spread, i8_err = run(wire.Int8Codec(64), True)
    assert i8_spread <= 2.0 * max(f32_spread, 1e-4), \
        (i8_spread, f32_spread)
    assert i8_err <= 2.0 * max(f32_err, 1e-3), (i8_err, f32_err)


def test_ps_weight_lane_exact_under_faults_and_compression(mesh):
    """The push-sum weight trajectory under faults is bit-identical with
    and without wire compression: the scalar lane never touches the
    codec, so mass accounting is exactly the faulted-f32 one."""
    from stochastic_gradient_push_tpu.resilience import parse_fault_spec

    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))

    def run(codec, ef):
        masks = parse_fault_spec("drop:0->1@0:64;seed:7").build_masks(
            sched)
        alg = sgp(sched, GOSSIP_AXIS, faults=masks, wire=codec,
                  error_feedback=ef)

        def step(p, g):
            return alg.post_step(p, g)

        f = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(GOSSIP_AXIS),) * 2,
            out_specs=(P(GOSSIP_AXIS),) * 2))
        rng = np.random.default_rng(5)
        p = rng.normal(size=(WORLD, 64)).astype(np.float32)
        g = _stacked_init(alg, 64)
        ws = []
        for _ in range(10):
            p, g = jax.block_until_ready(f(p, g))
            ws.append(np.asarray(g.ps_weight).copy())
        return np.stack(ws)

    w_exact = run(None, False)
    w_int8 = run(wire.Int8Codec(64), True)
    np.testing.assert_array_equal(w_exact, w_int8)
    assert np.abs(np.asarray(w_int8[-1]).mean() - 1.0) < 1e-5


def test_thinned_gossip_carries_residual_through_idle_steps(mesh):
    """gossip_every=2: non-firing steps pass the residual through
    unchanged; firing steps update it."""
    sched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))
    alg = sgp(sched, GOSSIP_AXIS, gossip_every=2,
              wire=wire.Int8Codec(64), error_feedback=True)

    def step(p, g):
        return alg.post_step(p, g)

    f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(GOSSIP_AXIS),) * 2,
        out_specs=(P(GOSSIP_AXIS),) * 2))
    rng = np.random.default_rng(6)
    p = rng.normal(size=(WORLD, 32)).astype(np.float32)
    g = _stacked_init(alg, 32)
    # tick 0 fires: residual becomes nonzero
    p, g = jax.block_until_ready(f(p, g))
    r_fire = np.asarray(g.ef_residual).copy()
    assert np.abs(r_fire).max() > 0
    # tick 1 does not fire: residual identical
    p, g = jax.block_until_ready(f(p, g))
    np.testing.assert_array_equal(np.asarray(g.ef_residual), r_fire)
    # tick 2 fires again: residual moves
    p, g = jax.block_until_ready(f(p, g))
    assert np.abs(np.asarray(g.ef_residual) - r_fire).max() > 0


def test_hierarchical_delegate_lane_compression(mesh):
    """A hierarchical round with an int8 codec: the wire codec rides the
    delegate (inter) lane while the intra-slice psum stays exact — the
    round still mean-preserves to within the residual bound."""
    g = HierarchicalGraph(WORLD, slice_size=4)
    sched = build_schedule(g)
    codec = wire.Int8Codec(64)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(WORLD, 96)).astype(np.float32)
    w = np.ones((WORLD, 1), np.float32)
    r = np.zeros_like(x)
    mean = x.mean(0)

    def step(phase, xs, ws, rs):
        (p, ww), rr = gossip_round(
            (xs, ws), phase, sched, GOSSIP_AXIS, codec=codec,
            ef_residual=(rs, jnp.zeros_like(ws)))
        return p, ww, rr[0]

    f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(GOSSIP_AXIS), P(GOSSIP_AXIS), P(GOSSIP_AXIS)),
        out_specs=(P(GOSSIP_AXIS),) * 3))
    for phase in range(12):
        x, w, r = map(np.asarray,
                      jax.block_until_ready(f(jnp.int32(phase), x, w, r)))
    z = x / w
    assert np.abs(z.mean(0) - mean).max() < 5e-3
    assert np.abs(z - z.mean(0)).max() < 5e-2  # two-level mixing works


def test_ef_requires_lossy_codec():
    sched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))
    with pytest.raises(ValueError, match="lossy wire codec"):
        sgp(sched, GOSSIP_AXIS, error_feedback=True)
    with pytest.raises(ValueError, match="lossy wire codec"):
        sgp(sched, GOSSIP_AXIS, wire=wire.F32, error_feedback=True)
    # EF composes with overlap now: the residual telescopes against the
    # round being SENT at launch (tests/test_overlap.py pins the
    # telescoping identity on the compiled mesh)
    alg = sgp(sched, GOSSIP_AXIS, overlap=True, wire=wire.Int8Codec(),
              error_feedback=True)
    assert alg.overlap and alg.error_feedback
    with pytest.raises(ValueError, match="not both"):
        sgp(sched, GOSSIP_AXIS, wire=wire.BF16,
            comm_dtype=jnp.bfloat16)
    # push-pull carries no residual state: EF must be rejected up front
    # (a silently-None residual would change the carried pytree
    # structure mid-run)
    from stochastic_gradient_push_tpu.algorithms import PushSumGossip
    with pytest.raises(ValueError, match="track_weight"):
        PushSumGossip(sched, GOSSIP_AXIS, track_weight=False,
                      wire=wire.Int8Codec(), error_feedback=True)


# -- pricing ---------------------------------------------------------------


def test_encoded_payload_bytes_hand_counts():
    params = {"w": np.zeros((WORLD, 1000), np.float32),
              "b": np.zeros((WORLD, 24), np.float32),
              "s": np.zeros((WORLD,), np.float32)}  # scalar per rank
    # f32 / no codec: plain storage bytes
    assert encoded_payload_bytes(params, WORLD) == (1000 + 24 + 1) * 4
    assert encoded_payload_bytes(params, WORLD, wire.F32) \
        == (1000 + 24 + 1) * 4
    # bf16 halves payload lanes; the scalar leaf stays at 4 B (the
    # collective's size>1 guard keeps it off the codec)
    assert encoded_payload_bytes(params, WORLD, wire.BF16) \
        == (1000 + 24) * 2 + 4
    # int8: 1 B/element + one f32 scale per 64-block, scalar exempt
    hand = (1000 + 4 * 16) + (24 + 4 * 1) + 4
    assert encoded_payload_bytes(params, WORLD, wire.Int8Codec(64)) \
        == hand
    # >= 3.5x reduction on the payload lanes (the acceptance ratio)
    full = tree_payload_bytes(params, WORLD)
    assert full / encoded_payload_bytes(params, WORLD,
                                        wire.Int8Codec(64)) >= 3.5


def test_comm_model_prices_encoded_wire_and_stamps_codec():
    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
    codec = wire.Int8Codec(64)
    params = {"w": np.zeros((WORLD, 1000), np.float32)}
    enc = encoded_payload_bytes(params, WORLD, codec)
    exact = tree_payload_bytes(params, WORLD)
    model = CommModel.from_schedule(sched, enc, exact_bytes=exact,
                                    global_avg_every=4, codec=codec,
                                    error_feedback=True)
    totals = model.totals(8)
    # wire = encoded payload + the exact 4B ps-weight lane per message
    assert totals["gossip_wire"] == 8 * (enc + 4)
    # exact lanes (scheduled averages) price the FULL precision payload
    from stochastic_gradient_push_tpu.telemetry import allreduce_bytes
    assert totals["global_avg"] == 2 * allreduce_bytes(exact, WORLD)
    d = model.to_dict()
    assert d["wire_dtype"] == "int8" and d["wire_block"] == 64
    assert d["error_feedback"] is True
    assert d["payload_bytes"] == enc and d["exact_bytes"] == exact


def test_hierarchical_comm_model_compresses_delegate_lane_only():
    g = HierarchicalGraph(WORLD, slice_size=4)
    sched = build_schedule(g)
    params = {"w": np.zeros((WORLD, 4096), np.float32)}
    codec = wire.Int8Codec(64)
    enc = encoded_payload_bytes(params, WORLD, codec)
    exact = tree_payload_bytes(params, WORLD)
    m_enc = CommModel.from_schedule(sched, enc, exact_bytes=exact,
                                    codec=codec)
    m_exact = CommModel.from_schedule(sched, exact, exact_bytes=exact)
    t_enc, t_exact = m_enc.totals(4), m_exact.totals(4)
    # DCN (delegate) lane shrinks by ~the codec ratio...
    assert t_enc["gossip_dcn"] < t_exact["gossip_dcn"] / 3
    # ...while the intra-slice exact average keeps the ICI lane's
    # ring-allreduce term at full precision (strictly above the pure
    # codec ratio)
    assert t_enc["gossip_ici"] > t_exact["gossip_ici"] / 3


def test_planner_prices_wire_fraction():
    from stochastic_gradient_push_tpu.planner import (
        check_topology, plan_for, PlanConstraints)
    from stochastic_gradient_push_tpu.planner.scorer import (
        evaluate_candidate)

    frac = wire.Int8Codec(64).wire_fraction()
    base = evaluate_candidate(RingGraph, 8, 1)
    comp = evaluate_candidate(RingGraph, 8, 1, wire_fraction=frac)
    assert comp.comm_cost == pytest.approx(base.comm_cost * frac)
    assert comp.priced_cost == pytest.approx(base.priced_cost * frac)
    # hierarchical: only the delegate lane compresses — the intra-slice
    # exact average is priced at full precision even on the uniform
    # fabric (where it is priced as-written, not as a fused psum), so
    # the candidate's cost shrinks by LESS than the pure codec ratio
    hb = evaluate_candidate(HierarchicalGraph, 8, 1)
    hc = evaluate_candidate(HierarchicalGraph, 8, 1, wire_fraction=frac)
    assert hc.priced_cost > hb.priced_cost * frac * 1.5
    assert hc.priced_cost < hb.priced_cost
    # the plan stamps the codec config it was priced on
    wire_cfg = {"dtype": "int8", "block": 64, "error_feedback": True}
    plan = plan_for(8, ppi=1, constraints=PlanConstraints(wire=wire_cfg))
    assert plan.wire == wire_cfg
    assert plan.to_dict()["wire"] == wire_cfg
    forced = check_topology(8, RingGraph, ppi=1, wire=wire_cfg)
    assert forced.wire == wire_cfg
    # an f32/absent wire keeps rankings and costs exactly as before
    assert plan_for(8, ppi=1).wire is None


# -- reshard ---------------------------------------------------------------


def test_reshard_zeros_ef_residual_and_preserves_mean():
    from stochastic_gradient_push_tpu.supervise.reshard import (
        consensus_mean, reshard_state)

    rng = np.random.default_rng(8)
    state = {
        "params": {"w": rng.normal(size=(4, 6)).astype(np.float32)},
        "gossip": {
            "phase": np.full((4,), 3, np.int32),
            "ps_weight": np.full((4,), 1.0, np.float32),
            "in_flight": None,
            "ef_residual": {
                "w": rng.normal(size=(4, 6)).astype(np.float32) * 1e-3},
        },
        "step": np.full((4,), 17, np.int32),
    }
    before = consensus_mean(state)
    out = reshard_state(state, 4, 2)
    after = consensus_mean(out)
    for k in before:
        np.testing.assert_allclose(after[k], before[k], atol=1e-7)
    # residuals are dropped (zeroed) at the new world — pending
    # correction is bounded, stale, and schedule-bound
    assert out["gossip"]["ef_residual"]["w"].shape == (2, 6)
    assert np.all(out["gossip"]["ef_residual"]["w"] == 0)


# -- monitor ---------------------------------------------------------------


def test_monitor_reports_and_flags_ef_residual():
    from stochastic_gradient_push_tpu.resilience.monitor import (
        EF_HEALTH_KEY, HealthMonitor)

    base = {"consensus_residual": 0.0, "ps_w_min": 1.0, "ps_w_max": 1.0,
            "ps_mass_err": 0.0, "nonfinite_params": 0.0,
            "nonfinite_grads": 0.0}
    mon = HealthMonitor(health_every=1)
    rep = mon.observe(0, {**base, EF_HEALTH_KEY: 1e-4})
    assert not rep.unhealthy
    assert rep.payload[EF_HEALTH_KEY] == pytest.approx(1e-4)
    rep = mon.observe(1, {**base, EF_HEALTH_KEY: 0.5})
    assert "ef-residual-blowup" in rep.reasons
    rep = mon.observe(2, {**base, EF_HEALTH_KEY: float("nan")})
    assert "ef-residual-blowup" in rep.reasons
    # runs without EF never emit (or diagnose) the key
    rep = mon.observe(3, base)
    assert EF_HEALTH_KEY not in rep.payload and not rep.unhealthy


# -- CLI surface -----------------------------------------------------------


def test_sgd_cli_wire_flags_thread_into_config():
    from stochastic_gradient_push_tpu.run.gossip_sgd import parse_config

    cfg, args = parse_config(
        ["--dataset", "synthetic", "--wire_dtype", "int8",
         "--wire_block", "32", "--error_feedback", "True"])
    assert cfg.wire_dtype == "int8" and cfg.wire_block == 32
    assert cfg.error_feedback is True


def test_sgd_cli_rejects_wire_knobs_outside_push_sum():
    from stochastic_gradient_push_tpu.run.gossip_sgd import parse_config

    for flags in (["--all_reduce", "True", "--graph_type", "-1"],
                  ["--push_sum", "False"]):
        with pytest.raises(SystemExit, match="push-sum knobs"):
            parse_config(["--dataset", "synthetic",
                          "--wire_dtype", "int8"] + flags)
    with pytest.raises(SystemExit, match="lossy --wire_dtype"):
        parse_config(["--dataset", "synthetic",
                      "--error_feedback", "True"])
    # overlap + lossy wire + EF is a supported composition now
    cfg, _ = parse_config(["--dataset", "synthetic", "--overlap", "True",
                           "--wire_dtype", "int8",
                           "--error_feedback", "True"])
    assert cfg.overlap and cfg.error_feedback and cfg.wire_dtype == "int8"


def test_lm_cli_rejects_wire_knobs_outside_push_sum(tmp_path):
    from stochastic_gradient_push_tpu.run.gossip_lm import main

    common = ["--world_size", str(WORLD), "--num_steps", "1",
              "--d_model", "16", "--n_layers", "1", "--n_heads", "2",
              "--d_ff", "32", "--seq_len", "16", "--batch_size", "2",
              "--checkpoint_dir", str(tmp_path),
              "--wire_dtype", "int8"]
    for mode in (["--all_reduce", "True"], ["--bilat", "True"],
                 ["--push_sum", "False"]):
        with pytest.raises(SystemExit, match="push-sum knobs"):
            main(common + mode)


def test_trainer_config_wire_codec_resolution():
    from stochastic_gradient_push_tpu.train.loop import (Trainer,
                                                         TrainerConfig)

    cfg = TrainerConfig(wire_dtype="int8", wire_block=32,
                        error_feedback=True)
    codec = Trainer._wire_codec(
        type("T", (), {"cfg": cfg})())  # resolve without a mesh
    assert isinstance(codec, wire.Int8Codec) and codec.block == 32
    cfg2 = TrainerConfig(wire_dtype="bf16")
    assert Trainer._wire_codec(
        type("T", (), {"cfg": cfg2})()) is wire.BF16
    # an unknown name never runs uncompressed in silence
    with pytest.raises(ValueError, match="unknown wire_dtype"):
        Trainer._wire_codec(type("T", (), {
            "cfg": TrainerConfig(wire_dtype="fp8")})())


def test_sgd_cli_int8_ef_end_to_end(tmp_path):
    """Acceptance e2e: a world-8 CPU run with --wire_dtype int8
    --error_feedback reports comm bytes equal to an independently built
    CommModel over the ENCODED payload — and the health stream carries
    the residual signal."""
    from stochastic_gradient_push_tpu.models import TinyCNN
    from stochastic_gradient_push_tpu.run.gossip_sgd import main

    run_dir = str(tmp_path / "run")
    steps = 4
    main(["--dataset", "synthetic", "--model", "tiny_cnn",
          "--num_classes", "10", "--image_size", "16",
          "--batch_size", "4", "--world_size", str(WORLD),
          "--num_epochs", "1",
          "--num_iterations_per_training_epoch", str(steps),
          "--num_itr_ignore", "0", "--topology", "ring",
          "--wire_dtype", "int8", "--error_feedback", "True",
          "--health_every", "2", "--trace_dir", run_dir,
          "--checkpoint_dir", run_dir])

    events = []
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        for line in f:
            events.append(json.loads(line))
    # plan stamped with the wire config
    plan = next(e for e in events if e["kind"] == "plan")["data"]
    assert plan["wire"] == {"dtype": "int8", "block": 64,
                            "error_feedback": True}
    # health events carry the residual signal, below the blowup floor
    health = [e["data"] for e in events if e["kind"] == "health"]
    assert health and all("ef_residual_rms" in h for h in health)
    assert all(0 <= h["ef_residual_rms"] < 0.1 for h in health)
    # comm totals == independent model over the ENCODED payload
    params = TinyCNN(num_classes=10).init(
        jax.random.PRNGKey(0), jnp.zeros((4, 16, 16, 3)))["params"]
    codec = wire.Int8Codec(64)
    enc = encoded_payload_bytes(params, 1, codec)
    exact = tree_payload_bytes(params, 1)
    model = CommModel.from_schedule(
        build_schedule(RingGraph(WORLD, peers_per_itr=1)), enc,
        exact_bytes=exact, codec=codec, error_feedback=True)
    final_comm = [e for e in events if e["kind"] == "comm"][-1]["data"]
    assert final_comm["bytes"] == model.totals(steps)
    assert final_comm["model"]["wire_dtype"] == "int8"
    # >= 3.5x payload reduction vs the exact wire, as reported
    assert exact / final_comm["model"]["payload_bytes"] >= 3.5


def test_bench_wire_sweep_artifact_schema(tmp_path, monkeypatch):
    """The --gossip-vs-ar wire sweep: artifact entries carry measured ms
    next to modeled encoded bytes, with the int8 lane >= 3.5x below the
    f32 lane and every modeled figure equal to an independent model."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_wire_under_test", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    out_path = str(tmp_path / "gva.json")
    for k, v in (("BENCH_GVA_STEPS", "2"), ("BENCH_GVA_WARMUP", "1"),
                 ("BENCH_GVA_BATCH", "2"), ("BENCH_GVA_GA", "8"),
                 ("BENCH_GVA_OUT", out_path),
                 ("BENCH_GVA_WIRE", "f32,int8")):
        monkeypatch.setenv(k, v)
    out = bench.run_gossip_vs_ar()
    sweep = out["wire_sweep"]
    assert [e["wire_dtype"] for e in sweep] == ["f32", "int8"]
    f32e, i8e = sweep
    assert f32e["step_ms"] > 0 and i8e["step_ms"] > 0
    assert i8e["error_feedback"] is True and i8e["wire_block"] == 64
    ratio = (f32e["modeled_bytes_per_rank"]["gossip_wire"]
             / i8e["modeled_bytes_per_rank"]["gossip_wire"])
    assert ratio >= 3.5
    # artifact on disk carries the same sweep
    doc = json.load(open(out_path))
    assert doc["bench"]["wire_sweep"] == sweep
    # modeled figures equal an independently built CommModel
    from stochastic_gradient_push_tpu.models import TinyCNN
    params = TinyCNN(num_classes=10).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 16, 16, 3)))["params"]
    codec = wire.Int8Codec(64)
    model = CommModel.from_schedule(
        build_schedule(RingGraph(WORLD, peers_per_itr=1)),
        encoded_payload_bytes(params, 1, codec),
        exact_bytes=tree_payload_bytes(params, 1),
        global_avg_every=8, codec=codec, error_feedback=True)
    want = model.totals(2, start=1)
    assert i8e["modeled_bytes_per_rank"]["gossip_wire"] \
        == want["gossip_wire"]
