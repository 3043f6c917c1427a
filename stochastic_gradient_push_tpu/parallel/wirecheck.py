"""``scripts/wirecheck.py`` driver — wire-codec CI selftest.

The acceptance loop for the quantized gossip wire format
(parallel/wire.py + the codec path in parallel/collectives.py), on a
world-8 virtual CPU mesh:

1. **chaos round** — int8 + error feedback UNDER a dropped edge
   (``drop:0->1``): the network-wide parameter mean (including the
   pending residuals — the telescoping identity) is preserved to
   float32 tolerance, the raw mean moves by no more than one
   quantization step, the push-sum weight lane stays exact (mass error
   at float noise — the lane never touches the codec), and the health
   monitor emits the ``ef_residual_rms`` signal in its structured
   ``gossip health:`` line;
2. **parity** — a small SGD consensus problem run twice, exact f32 wire
   vs int8+EF: after the same step budget the compressed run's
   consensus error is within 2x of exact (the ISSUE-10 acceptance
   bound) and its de-biased mean lands at the same optimum;
3. **pricing** — the modeled encoded bytes
   (telemetry.encoded_payload_bytes through CommModel) match an
   independent hand count, and the int8 payload is >= 3.5x below f32;
4. **kernel lane** — the SAME int8+EF chaos round re-run through the
   fused Pallas gossip kernel (ops/gossip_kernel.py, interpret mode)
   must reproduce the XLA path: telescoped mean preserved to the same
   bound, params within f32 tolerance, and the push-sum weight
   trajectory BIT-IDENTICAL round by round (the scalar lane never
   enters the kernel, so any divergence is a transport bug).

Everything runs on CPU in seconds; the wrapper script forces the
virtual 8-device platform before jax loads.
"""

from __future__ import annotations

import argparse
import sys

WORLD = 8
CHAOS_SPEC = "drop:0->1@0:64;seed:7"
CHAOS_ROUNDS = 12
PARITY_STEPS = 120


def _selftest() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ..algorithms import sgp
    from ..resilience import parse_fault_spec
    from ..resilience.monitor import (EF_HEALTH_KEY, HealthMonitor,
                                      health_signals)
    from ..telemetry import CommModel, encoded_payload_bytes
    from ..topology import (NPeerDynamicDirectedExponentialGraph,
                            RingGraph, build_schedule)
    from . import wire
    from .mesh import GOSSIP_AXIS, make_gossip_mesh

    failures: list[str] = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    if jax.device_count() < WORLD:
        print(f"wire selftest FAILED: needs {WORLD} devices, have "
              f"{jax.device_count()} (run via scripts/wirecheck.py, "
              "which forces the virtual CPU platform)", file=sys.stderr)
        return 1

    mesh = make_gossip_mesh(WORLD)
    codec = wire.Int8Codec(64)

    # -- 1. chaos round: int8 + EF + a dropped edge ------------------------
    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
    masks = parse_fault_spec(CHAOS_SPEC).build_masks(sched)
    rng = np.random.default_rng(0)
    x0 = rng.normal(size=(WORLD, 128)).astype(np.float32)
    x0_mean = x0.mean(0)

    def run_chaos(kernel):
        """The chaos loop on one transport lane; returns the final
        (params, gstate, last sig, last report, ps-weight trajectory)."""
        alg = sgp(sched, GOSSIP_AXIS, faults=masks, wire=codec,
                  error_feedback=True, gossip_kernel=kernel)

        def gossip_step(params, gstate):
            params, gstate = alg.post_step(params, gstate)
            sig = health_signals(params, None, gstate.ps_weight,
                                 GOSSIP_AXIS,
                                 ef_residual=gstate.ef_residual)
            return params, gstate, jax.tree.map(lambda a: a[None], sig)

        # the interpreted kernel lane cannot run under the vma check
        # (train/step.py::shard_train_step says why)
        step = jax.jit(jax.shard_map(
            gossip_step, mesh=mesh, in_specs=(P(GOSSIP_AXIS),) * 2,
            out_specs=(P(GOSSIP_AXIS),) * 3, check_vma=kernel is None))

        params = x0.copy()
        gstate = jax.tree.map(
            lambda a: np.broadcast_to(np.asarray(a),
                                      (WORLD,) + np.shape(a)).copy(),
            alg.init(jnp.zeros((128,), jnp.float32)))
        monitor = HealthMonitor(health_every=1, residual_floor=1e9,
                                log=None)
        report = None
        ps_traj = []
        for t in range(CHAOS_ROUNDS):
            params, gstate, sig = jax.block_until_ready(
                step(params, gstate))
            ps_traj.append(np.asarray(gstate.ps_weight).copy())
            sig = {k: float(np.asarray(v)[0]) for k, v in sig.items()}
            report = monitor.observe(t, sig)
        return (np.asarray(params), gstate, sig, report,
                np.stack(ps_traj))

    params, gstate, sig, report, ps_traj = run_chaos(None)

    res = np.asarray(gstate.ef_residual)
    # telescoping identity: delivered mass + pending residuals == exact
    drift_tel = np.abs((params.sum(0) + res.sum(0)) / WORLD
                       - x0_mean).max()
    check(drift_tel < 1e-5,
          f"telescoped mean drifted {drift_tel:.2e} under int8+EF with "
          "a dropped edge (residual accounting broken)")
    # raw mean moves by at most the pending residual mass
    drift_raw = np.abs(params.mean(0) - x0_mean).max()
    check(drift_raw < 5e-3,
          f"raw network mean drifted {drift_raw:.2e} — beyond one "
          "quantization step of pending residual")
    check(sig["ps_mass_err"] < 1e-4,
          f"push-sum mass error {sig['ps_mass_err']:.2e}: the exact "
          "f32 weight lane leaked under compression")
    check(EF_HEALTH_KEY in (report.payload if report else {}),
          "health line is missing the ef_residual_rms signal")
    ef_rms = sig.get(EF_HEALTH_KEY, float("nan"))
    check(0.0 < ef_rms < 0.1,
          f"ef_residual_rms {ef_rms} outside the healthy band "
          "(bounded residual ~ one quantization step)")

    # -- 2. parity: int8+EF vs exact f32 on an SGD consensus problem -------
    psched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))
    targets = rng.normal(size=(WORLD, 64)).astype(np.float32)
    lr = 0.05

    def run(wire_codec, ef):
        a = sgp(psched, GOSSIP_AXIS, wire=wire_codec, error_feedback=ef)

        def sgd_step(p, g, target):
            p, g = a.pre_step(p, g)
            z = a.eval_params(p, g)
            grad = jax.grad(
                lambda q: 0.5 * jnp.sum((q - target) ** 2))(z)
            return a.post_step(p - lr * grad, g)

        f = jax.jit(jax.shard_map(
            sgd_step, mesh=mesh, in_specs=(P(GOSSIP_AXIS),) * 3,
            out_specs=(P(GOSSIP_AXIS),) * 2))
        p = rng.normal(size=(WORLD, 64)).astype(np.float32)
        g = jax.tree.map(
            lambda x: np.broadcast_to(np.asarray(x),
                                      (WORLD,) + np.shape(x)).copy(),
            a.init(jnp.zeros((64,), jnp.float32)))
        for _ in range(PARITY_STEPS):
            p, g = jax.block_until_ready(f(p, g, targets))
        z = np.asarray(p) / np.asarray(g.ps_weight).reshape(WORLD, 1)
        spread = float(np.abs(z - z.mean(0)).max())
        err = float(np.abs(z.mean(0) - targets.mean(0)).max())
        return spread, err

    f32_spread, f32_err = run(None, False)
    i8_spread, i8_err = run(codec, True)
    # acceptance: consensus error within 2x of exact after the same
    # step budget (floors guard the comparison against float noise)
    check(i8_spread <= 2.0 * max(f32_spread, 1e-4),
          f"int8+EF consensus spread {i8_spread:.2e} > 2x f32 "
          f"{f32_spread:.2e}")
    check(i8_err <= 2.0 * max(f32_err, 1e-3),
          f"int8+EF optimum error {i8_err:.2e} > 2x f32 {f32_err:.2e}")

    # -- 3. pricing: modeled == hand count, >= 3.5x reduction --------------
    tmpl = {"w": np.zeros((WORLD, 1000), np.float32),
            "b": np.zeros((WORLD, 24), np.float32)}
    hand = (1000 + 4 * -(-1000 // 64)) + (24 + 4 * -(-24 // 64))
    enc = encoded_payload_bytes(tmpl, WORLD, codec)
    check(enc == hand,
          f"encoded_payload_bytes {enc} != hand count {hand}")
    exact = 4 * 1024
    check(exact / enc >= 3.5,
          f"int8 payload reduction {exact / enc:.2f}x < 3.5x")
    model = CommModel.from_schedule(psched, enc, exact_bytes=exact,
                                    codec=codec, error_feedback=True)
    totals = model.totals(4)
    check(totals["gossip_wire"] == 4 * (enc + 4),
          f"modeled wire bytes {totals['gossip_wire']} != "
          f"{4 * (enc + 4)} (payload + ps-weight lane, 4 rounds)")
    check(model.to_dict()["wire_dtype"] == "int8"
          and model.to_dict()["error_feedback"],
          "CommModel snapshot does not stamp the wire codec")
    check(model.to_dict().get("gossip_kernel") == "xla",
          "CommModel snapshot does not stamp the transport lane")

    # -- 4. kernel lane: the same chaos round through the fused kernel -----
    from ..ops.gossip_kernel import KernelLane

    k_params, k_gstate, _, _, k_ps_traj = run_chaos(
        KernelLane(interpret=True))
    check(np.array_equal(ps_traj, k_ps_traj),
          "kernel-lane ps-weight trajectory diverged from the XLA path "
          f"(max |d| {np.abs(ps_traj - k_ps_traj).max():.2e}); the "
          "scalar lane must be bit-identical — it never enters the "
          "kernel")
    k_res = np.asarray(k_gstate.ef_residual)
    k_drift = np.abs((k_params.sum(0) + k_res.sum(0)) / WORLD
                     - x0_mean).max()
    check(k_drift < 1e-5,
          f"kernel-lane telescoped mean drifted {k_drift:.2e} under "
          "int8+EF with a dropped edge (in-kernel decode broke the "
          "residual accounting)")
    d_params = np.abs(k_params - params).max()
    check(d_params < 1e-5,
          f"kernel-lane params diverged {d_params:.2e} from the XLA "
          "path after the chaos round (beyond f32 tolerance)")

    if failures:
        for f in failures:
            print(f"wire selftest FAILED: {f}", file=sys.stderr)
        return 1
    print(f"wire selftest: OK (world {WORLD}: int8+EF chaos round mean "
          f"drift {drift_tel:.2e} telescoped / {drift_raw:.2e} raw, "
          f"ef_rms {ef_rms:.2e} in band; parity spread {i8_spread:.2e} "
          f"vs f32 {f32_spread:.2e}; payload {exact}->{enc} B = "
          f"{exact / enc:.2f}x; kernel lane: ps-weight bit-identical, "
          f"params |d| {d_params:.1e}, telescoped drift {k_drift:.2e})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="wirecheck",
        description="Quantized gossip wire format: CI selftest")
    ap.add_argument("--selftest", action="store_true",
                    help="run the CI wire self-check and exit")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    ap.error("choose --selftest")
    return 2
