"""Quantify the staleness trade: compiled overlap gossip vs the
reference's host-async thread/process model.

The reference gets gossip asynchrony from wall-clock overlap — OSGP
polls a non-blocking collective for up to ``synch_freq`` steps
(distributed.py:349-352, 578), and AD-PSGD runs bilateral averaging in a
separate OS process (ad_psgd.py:120-133) — so its *effective staleness*
is hardware-dependent: roughly ``ceil(T_comm / T_step)`` steps, jittered
by the scheduler.  This framework compiles gossip into the step instead:
OSGP's staleness is an EXACT knob (a FIFO of in-flight shares), and
AD-PSGD is a synchronous perfect matching (staleness 0).  The round-3
verdict asked for data on what that reformulation changes; this study
produces it on the canonical decentralized quadratic (per-rank targets,
constant LR — the setting of the D-PSGD/SGP convergence theorems, and of
tests/test_algorithms.py):

1. **OSGP staleness sweep (real implementation)** — the compiled
   PushSumGossip at staleness δ ∈ {sync, 1, 2, 4, 8} on the 8-rank
   mesh: steady-state replica spread and distance of the consensus mean
   from the optimum.  δ is exact here; the reference's δ is a random
   variable with mean T_comm/T_step.
2. **AD-PSGD partner-staleness simulation (reference semantics)** — a
   numpy replica of bilateral averaging where the partner's parameters
   are δ steps old, δ ~ min(Geometric(p), 8) with mean matched to a
   comm/compute ratio; sweeping the ratio maps the reference's
   hardware-dependent behavior onto measurable spread/optimality
   numbers, with δ≡0 cross-checked against the compiled BilateralGossip.

Wall-clock anchor (BASELINE.md, round-2 on-chip sweep): gossip adds
≤0.7 ms to a 49.1 ms ResNet-50 step on TPU ICI → T_comm/T_step ≈ 0.014,
i.e. the reference's own model predicts δ ≈ 1 there, the regime where
the measured penalty below is negligible.  The large-δ columns model
slow interconnects (the reference's 10 Gbps Ethernet experiments).

Artifacts: docs/STALENESS_STUDY.md + docs/staleness_study.png.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=. python examples/staleness_study.py
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.algorithms import adpsgd, sgp
from stochastic_gradient_push_tpu.parallel import (
    GOSSIP_AXIS, make_gossip_mesh)
from stochastic_gradient_push_tpu.topology import (
    DynamicBipartiteExponentialGraph,
    NPeerDynamicDirectedExponentialGraph,
    build_pairing_schedule,
    build_schedule,
)

WORLD, DIM, STEPS, LR, TAIL = 8, 16, 500, 0.05, 100

rng = np.random.default_rng(9)
TARGETS = rng.normal(size=(WORLD, DIM)).astype(np.float32)
X0 = rng.normal(size=(WORLD, DIM)).astype(np.float32)
OPT = TARGETS.mean(axis=0)


def quad_grad(x, target):
    return x - target


def run_compiled(alg, steps=STEPS):
    """The real four-slot algorithm step on the 8-device mesh."""
    mesh = make_gossip_mesh(WORLD)

    def step(params, gstate, target):
        params, gstate = alg.pre_step(params, gstate)
        z = alg.eval_params(params, gstate)
        grads = jax.grad(lambda p: 0.5 * jnp.sum((p - target) ** 2))(z)
        grads = alg.reduce_grads(grads)
        params = params - LR * grads
        return alg.post_step(params, gstate)

    f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(GOSSIP_AXIS), P(GOSSIP_AXIS), P(GOSSIP_AXIS)),
        out_specs=(P(GOSSIP_AXIS), P(GOSSIP_AXIS))))
    params = X0.copy()
    gstate = jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a),
                                  (WORLD,) + np.shape(a)).copy(),
        alg.init(jnp.zeros((DIM,), jnp.float32)))
    # drained VALIDATION view (alg.val_params): measuring on the raw
    # between-step params would inflate every spread/gap by the
    # not-yet-applied in-flight shares — the exact eval-time artifact
    # that once made OSGP look +3.4 % ppl worse in CONVERGENCE_PARITY.md
    fval = jax.jit(jax.shard_map(
        alg.val_params, mesh=mesh,
        in_specs=(P(GOSSIP_AXIS), P(GOSSIP_AXIS)),
        out_specs=P(GOSSIP_AXIS)))
    spreads, gaps = [], []
    for _ in range(steps):
        params, gstate = f(params, gstate, TARGETS)
        jax.block_until_ready(params)  # serialize CPU collective dispatch
        z = np.asarray(fval(params, gstate))
        spreads.append(float(np.abs(z - z.mean(0, keepdims=True)).max()))
        gaps.append(float(np.abs(z.mean(0) - OPT).max()))
    return spreads, gaps


def run_bilat_sim(mean_delay: float, steps=STEPS, seed=3):
    """Numpy replica of the reference's AD-PSGD process model: each step
    every rank takes a local SGD step, then averages with its matched
    partner's parameters as they were ``δ`` steps ago,
    δ ~ min(Geometric(p), 8) with mean ≈ mean_delay (δ≡0 reproduces the
    synchronous matching of the compiled BilateralGossip)."""
    g = np.random.default_rng(seed)
    pairing = build_pairing_schedule(
        DynamicBipartiteExponentialGraph(WORLD))
    x = X0.copy()
    hist = []          # end-of-step states of PREVIOUS steps
    spreads, gaps = [], []
    n_phases = pairing.shape[0]
    for t in range(steps):
        x = x - LR * quad_grad(x, TARGETS)
        partners = pairing[t % n_phases]
        if mean_delay > 0:
            # geometric support starts at 1; mean 1/p
            delays = np.minimum(g.geometric(min(1.0, 1.0 / mean_delay),
                                            size=WORLD), 8)
        else:
            delays = np.zeros(WORLD, np.int64)
        # δ=0 mixes the partner's CURRENT post-update params — exactly
        # the compiled BilateralGossip's synchronous matching; δ≥1 takes
        # the partner's end-of-step state from δ steps back
        stale = np.stack([
            x[partners[i]] if d == 0 or not hist
            else hist[max(0, len(hist) - int(d))][partners[i]]
            for i, d in enumerate(delays)])
        x = 0.5 * (x + stale)
        hist.append(x.copy())
        if len(hist) > 16:
            hist.pop(0)
        spreads.append(float(np.abs(x - x.mean(0, keepdims=True)).max()))
        gaps.append(float(np.abs(x.mean(0) - OPT).max()))
    return spreads, gaps


def tail_mean(v):
    return float(np.mean(v[-TAIL:]))


ASYNC_NN_SECTION = """
## AD-PSGD: EXECUTABLE wall-clock asynchrony (round 5, real NN)

`--bilat_async` (train/async_bilat.py) now runs the reference's process
model for real: the compiled step carries no collective, a host thread
continuously computes bilateral displacements from the live params, and
the loop adopts them whenever they're ready — δ set by actual host/device
timing, measured per adoption.  TinyCNN, 8-rank mesh, 4 epochs
(/tmp recipe in tests/test_async_bilat.py + this table's driver):

| Config | mean replica spread | adoptions | measured δ (mean/max) |
|--------|--------------------:|----------:|----------------------:|
| local SGD (no averaging) | 2.46e-3 | — | — |
| sync matchings (compiled AD-PSGD) | 1.71e-4 | — | δ≡0 by construction |
| async, unpaced | 3.10e-4 | 31/32 rounds | 1.0 / 1 |
| async, ≥0.1 s/round | 1.28e-3 | 16 | 1.19 / 2 |
| async, ≥0.4 s/round | 2.43e-3 | 2 | 1.0 / 1 |

Unpaced host averaging holds replicas within ~1.8x of the synchronous
matching's consensus — at a measured staleness of one step, exactly the
δ ≈ 1 regime the wall-clock anchor below predicts for fast interconnects.
Throttling the averaging thread (emulating a slow averaging path) walks
consensus monotonically back toward local SGD, the NN-scale confirmation
of the quadratic sim's dose-response above.

"""


def main():
    schedule = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))

    osgp_rows = []
    curves = {}
    configs = [("SGP (sync, δ=0)", sgp(schedule, GOSSIP_AXIS))]
    for d in (1, 2, 4, 8):
        configs.append((f"OSGP δ={d}",
                        sgp(schedule, GOSSIP_AXIS, overlap=True,
                            staleness=d)))
    for name, alg in configs:
        spreads, gaps = run_compiled(alg)
        osgp_rows.append((name, tail_mean(spreads), tail_mean(gaps)))
        curves[name] = spreads
        print(f"{name}: spread {tail_mean(spreads):.4f} "
              f"opt-gap {tail_mean(gaps):.4f}", flush=True)

    # compiled synchronous AD-PSGD — the product path the sim must match
    sp, gp = run_compiled(adpsgd(
        build_pairing_schedule(DynamicBipartiteExponentialGraph(WORLD)),
        GOSSIP_AXIS))
    bilat_rows = [("AD-PSGD compiled (sync matchings)",
                   tail_mean(sp), tail_mean(gp))]
    for mean_delay in (0, 1, 2, 4):
        spreads, gaps = run_bilat_sim(mean_delay)
        label = ("AD-PSGD sim δ≡0" if mean_delay == 0 else
                 f"AD-PSGD sim E[δ]≈{mean_delay}")
        bilat_rows.append((label, tail_mean(spreads), tail_mean(gaps)))
        print(f"{label}: spread {tail_mean(spreads):.4f} "
              f"opt-gap {tail_mean(gaps):.4f}", flush=True)

    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    palette = ["#2a78d6", "#eb6834", "#1baf7a", "#eda100", "#e87ba4"]
    fig, ax = plt.subplots(figsize=(7.5, 4.5), dpi=150)
    for (name, curve), color in zip(curves.items(), palette):
        ax.plot(curve, color=color, linewidth=1.6, label=name)
    ax.set_yscale("log")
    ax.set_xlabel("step")
    ax.set_ylabel("replica spread (max |zᵢ − z̄|, log)")
    ax.set_title("Spread under exact staleness: compiled push-sum, "
                 "8-rank mesh, constant LR")
    ax.grid(True, color="#eeeeee", linewidth=0.8)
    ax.spines[["top", "right"]].set_visible(False)
    ax.legend(frameon=False, fontsize=8)
    fig.tight_layout()
    fig.savefig("docs/staleness_study.png")

    with open("docs/STALENESS_STUDY.md", "w") as f:
        f.write(
            "# Staleness, measured\n\n"
            "What the synchronous/compiled reformulation of the "
            "reference's host-async gossip actually changes, on the "
            "canonical decentralized quadratic (per-rank targets, "
            f"{WORLD} ranks, constant LR {LR}, steady-state = mean of "
            f"the last {TAIL} of {STEPS} steps; "
            "examples/staleness_study.py — re-run to regenerate).\n\n"
            "## OSGP: exact staleness knob (real implementation)\n\n"
            "The reference's overlap staleness is a hardware random "
            "variable (non-blocking poll, distributed.py:349-352); here "
            "it is an exact FIFO depth.  Cost of each extra step of "
            "staleness:\n\n"
            "| Config | steady-state spread | opt gap |\n"
            "|--------|--------------------:|--------:|\n")
        for name, s, gap in osgp_rows:
            f.write(f"| {name} | {s:.4f} | {gap:.4f} |\n")
        f.write(
            "\nδ=1 is *exactly* free: the incoming share is computed "
            "from same-step peers and merely applied one step-boundary "
            "later, so the drained validation view coincides with sync "
            "SGP (`test_osgp_val_params_drains_to_sync`).  Spreads are "
            "measured on `val_params` — the drained eval view matching "
            "the reference's `model.eval()` gossip drain "
            "(distributed.py:322-327).  An earlier revision measured "
            "the undrained between-step parameters and overstated "
            "every δ's cost 2-3× (δ=1 read 0.2162, δ=8 read 0.9075): "
            "that inflation was the in-flight share validation would "
            "have applied, not a property of staleness.\n"
            "\n![spread curves](staleness_study.png)\n\n"
            "## AD-PSGD: synchronous matchings vs the process model\n\n"
            "The compiled formulation is the δ≡0 row; the sim rows "
            "replay the reference's separate-process semantics "
            "(ad_psgd.py:120-133) with partner parameters "
            "δ ~ min(Geom, 8) steps stale:\n\n"
            "| Config | steady-state spread | opt gap |\n"
            "|--------|--------------------:|--------:|\n")
        for name, s, gap in bilat_rows:
            f.write(f"| {name} | {s:.4f} | {gap:.4f} |\n")
        # recorded by the async_bilat NN driver (round 5), not this
        # script — kept here so regeneration preserves the section
        f.write(ASYNC_NN_SECTION)
        f.write(
            "\n## Reading the numbers\n\n"
            "- Spread grows with staleness (stale mixing is a weaker "
            "contraction), while the consensus mean stays near the "
            "optimum — matching the bounded-staleness theory the "
            "reference's paper leans on.\n"
            "- The sim's δ≡0 row lands on the compiled AD-PSGD's "
            "numbers, validating that the synchronous matching IS the "
            "zero-staleness limit of the reference's process model.\n"
            "- Wall-clock anchor: on TPU ICI the measured gossip cost "
            "is ≤0.7 ms against a 49.1 ms step (BASELINE.md round-2 "
            "sweep), so the reference's own timing model predicts "
            "δ ≈ 1 there — the regime where the table shows the "
            "penalty is small.  Large δ models slow interconnects; if "
            "that regime matters, OSGP's exact-δ FIFO reproduces it "
            "deterministically inside the compiled step.\n")
    print(json.dumps({"osgp": osgp_rows, "bilat": bilat_rows}), flush=True)


if __name__ == "__main__":
    main()
