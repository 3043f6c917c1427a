"""Four CPU-side comparison modes; no chip measurement lives here.

Speed on the chip is measured by the benchmark (``BENCHMARK.json``,
``python benchmark/run.py --workload <cell>``; PERF.md).  ``python
bench.py`` with no mode says so and exits 2.

First mode — ``python bench.py --gossip-vs-ar`` (ROADMAP's
``--global_avg_every`` wall-clock item): times gossip + periodic exact
averaging against AllReduce-every-step on a world-8 virtual CPU mesh,
instrumented through the telemetry span tracer, and writes a BENCH-style
JSON artifact (default artifacts/bench_gossip_vs_ar.json; knobs
BENCH_GVA_WORLD/BATCH/STEPS/WARMUP/GA/OUT).  ``--topology NAME``
(or BENCH_GVA_TOPOLOGY) selects the gossip graph — ``hierarchical``
times the two-level multi-slice schedule against the AR baseline, and
the artifact stamps the modeled per-link-class (ICI vs DCN) bytes next
to the measured milliseconds so the planner's DCN weighting can be
calibrated against real step time.  ``--wire_dtype int8`` (or
BENCH_GVA_WIRE="f32,int8" plus BENCH_GVA_WIRE_BLOCK / BENCH_GVA_EF)
adds a wire-codec sweep: the same gossip step timed per codec with the
modeled ENCODED bytes (int8 scale overhead included) alongside — the
calibration artifact for the planner's wire-fraction pricing.
BENCH_GVA_KERNEL (auto|pallas|xla, also honored by --overlap-vs-sync)
selects the gossip transport lane and both artifacts stamp the resolved
``kernel``; BENCH_GVA_BUCKETS sets the split transport's per-bucket
pipelining depth (stamped as ``gossip_buckets``).  Lane and bucketing
move identical modeled bytes by construction, so only measured ms may
differ.  On the CPU test backend the kernel runs through the Pallas
interpreter, so its step time there is a correctness artifact, not a
measurement.

Second mode — ``python bench.py --synth-vs-registry``: model-only
artifact for the planner's schedule *synthesizer* (planner/
synthesize.py).  Runs the seeded beam search at world 12 and 48 on the
16:1 DCN-dominant fabric plus a uniform-fabric control, and stamps the
winning schedule's spectral gap and modeled priced bytes per consensus
e-fold next to the best registry candidate's, with per-round ICI/DCN
byte lanes for a reference payload (default ResNet-50 f32).  No
measurement: the priced cost model IS the artifact, and fitting it to
real step time is the on-chip calibration item in ROADMAP.  With
``--selftest``, gates that synthesis beats the registry on both DCN
cases (CI; knobs BENCH_SYNTH_BUDGET/PAYLOAD/OUT).  Each modeled row
also carries a ``simulated`` block (sim/ exact engine on the priced
fabric), and the world-48 case stamps the Spearman rank correlation
between modeled priced cost and simulated seconds per consensus e-fold
across the full candidate grid — gated at >= 0.8.

Third mode — ``python bench.py --sim-scale``: consensus-vs-simulated-
wall-clock curves at pod worlds (256/1024/4096 x ring/exponential/
npeer-exponential) on the 16:1 DCN fabric, from the sim/ package's
exact engine.  Artifact: artifacts/bench_sim_scale.json (knobs
BENCH_SIM_TOPOLOGIES/WORLDS/STEPS/OUT).  With ``--selftest``, gates
curve coverage and the exponential-beats-ring wall-clock ordering.

Fourth mode — ``python bench.py --overlap-vs-sync``: step time of the
overlap phase schedule against synchronous gossip on the world-8 CPU
mesh (``run_overlap_vs_sync`` lists the knobs); ``--selftest`` gates
parity.
"""

import json
import os
import subprocess
import sys
import time


def _resolve_bench_kernel():
    """(KernelLane | None, "pallas" | "xla", buckets) from
    BENCH_GVA_KERNEL / BENCH_GVA_BUCKETS — the gossip transport lane
    (and its per-bucket pipelining depth) for both --gossip-vs-ar and
    --overlap-vs-sync.  An explicit ``pallas`` off-TPU runs through the
    Pallas interpreter (correctness lane, honest-but-slow ms); ``auto``
    is the resolver rule (pallas on TPU, xla elsewhere).  The default
    matches production's conservative ``xla`` until the kernel's
    live-TPU capture lands."""
    import jax

    from stochastic_gradient_push_tpu.ops.gossip_kernel import (
        resolve_gossip_kernel)

    flag = os.environ.get("BENCH_GVA_KERNEL", "xla")
    interpret = flag == "pallas" and jax.default_backend() != "tpu"
    lane = resolve_gossip_kernel(flag, interpret=interpret)
    buckets = max(1, int(os.environ.get("BENCH_GVA_BUCKETS", "1")))
    return lane, ("pallas" if lane is not None else "xla"), buckets


def run_gossip_vs_ar() -> dict:
    """Gossip + periodic exact averaging vs AllReduce-every-step.

    Closes part of the ROADMAP ``--global_avg_every`` wall-clock item:
    the same train step is timed under (a) push-sum gossip on a ring
    with an exact global average every ``BENCH_GVA_GA`` steps and (b)
    exact AllReduce every step, at world ``device_count`` on the current
    backend.  Timing runs through the telemetry span tracer (the spans
    ARE the measurement and land in the artifact's trace), and the
    analytic per-rank comm bytes from telemetry.comm sit next to the
    measured milliseconds, so the modeled comm saving can be compared to
    the observed wall-clock saving in one place.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from stochastic_gradient_push_tpu.algorithms import all_reduce, sgp
    from stochastic_gradient_push_tpu.data import synthetic_classification
    from stochastic_gradient_push_tpu.models import TinyCNN
    from stochastic_gradient_push_tpu.parallel import (
        GOSSIP_AXIS, get_codec, make_gossip_mesh)
    from stochastic_gradient_push_tpu.telemetry import (
        CommModel, SpanTracer, encoded_payload_bytes, tree_payload_bytes)
    from stochastic_gradient_push_tpu.topology import (
        TOPOLOGY_NAMES, build_schedule)
    from stochastic_gradient_push_tpu.train import (
        LRSchedule, build_train_step, init_train_state, replicate_state,
        sgd, shard_train_step)

    world = jax.device_count()
    batch = int(os.environ.get("BENCH_GVA_BATCH", "4"))
    steps = max(1, int(os.environ.get("BENCH_GVA_STEPS", "20")))
    warmup = max(1, int(os.environ.get("BENCH_GVA_WARMUP", "3")))
    ga = max(1, int(os.environ.get("BENCH_GVA_GA", "8")))
    topology = os.environ.get("BENCH_GVA_TOPOLOGY", "ring")
    kernel_lane, kernel_name, buckets = _resolve_bench_kernel()
    # an interpreted kernel lane cannot run under the vma check
    # (train/step.py::shard_train_step says why)
    check_vma = kernel_lane is None or not kernel_lane.interpret
    image, classes = 16, 10

    mesh = make_gossip_mesh(world)
    model = TinyCNN(num_classes=classes)
    tx = sgd(momentum=0.9, weight_decay=1e-4)
    lr_sched = LRSchedule(ref_lr=0.1, batch_size=batch, world_size=world)
    if topology not in TOPOLOGY_NAMES:
        raise SystemExit(f"unknown --topology {topology!r}; one of "
                         f"{sorted(TOPOLOGY_NAMES)}")
    schedule = build_schedule(
        TOPOLOGY_NAMES[topology](world, peers_per_itr=1))
    tracer = SpanTracer(rank=0)
    serialize = jax.default_backend() == "cpu"

    images, labels = synthetic_classification(
        world * batch, num_classes=classes, image_size=image, seed=0)
    x = images.reshape(world, batch, image, image, 3)
    y = labels.reshape(world, batch)

    payload = None
    params_tmpl = None

    def timed_ms(label, alg):
        nonlocal payload, params_tmpl
        step = build_train_step(model, alg, tx, lr_sched,
                                itr_per_epoch=100, num_classes=classes)
        fn = shard_train_step(step, mesh, check_vma=check_vma)
        st = replicate_state(
            init_train_state(model, jax.random.PRNGKey(0),
                             jnp.zeros((batch, image, image, 3)), tx,
                             alg),
            world)
        if payload is None:
            payload = tree_payload_bytes(st.params, world)
            params_tmpl = jax.tree.map(
                lambda a: np.zeros(np.shape(a), a.dtype), st.params)
        m = None
        for _ in range(warmup):
            st, m = fn(st, x, y)
            if serialize:
                jax.block_until_ready(st)
        jax.block_until_ready(st)
        with tracer.span(label, "bench", {"steps": steps}):
            for _ in range(steps):
                st, m = fn(st, x, y)
                if serialize:
                    jax.block_until_ready(st)
            jax.block_until_ready(st)
        loss = float(np.min(np.asarray(jax.device_get(m["loss"]))))
        if not np.isfinite(loss):
            raise RuntimeError(f"non-finite loss {loss} in {label}")
        return tracer.durations(label)[-1] / steps * 1e3

    sgp_ms = timed_ms("sgp_ga_steps",
                      sgp(schedule, GOSSIP_AXIS, global_avg_every=ga,
                          gossip_kernel=kernel_lane,
                          gossip_buckets=buckets))
    ar_ms = timed_ms("allreduce_steps", all_reduce(GOSSIP_AXIS))

    # model the TIMED ticks: the algorithm's step counter has already
    # advanced `warmup` ticks when the span opens, and global-average
    # firings depend on the absolute tick
    sgp_bytes = CommModel.from_schedule(
        schedule, payload, global_avg_every=ga,
        gossip_kernel=kernel_name,
        gossip_buckets=buckets).totals(steps, start=warmup)
    ar_bytes = CommModel.for_allreduce(world, payload).totals(steps)

    # wire-dtype sweep: the same gossip step at each codec, measured ms
    # next to the MODELED encoded bytes (scale overhead included) so the
    # planner's wire pricing can be calibrated against step time.
    # BENCH_GVA_WIRE lists the codecs; BENCH_GVA_EF=0 disables error
    # feedback on the lossy lanes; BENCH_GVA_WIRE_BLOCK sets the int8
    # block.
    wire_list = [w.strip() for w in os.environ.get(
        "BENCH_GVA_WIRE", "f32").split(",") if w.strip()]
    wire_block = int(os.environ.get("BENCH_GVA_WIRE_BLOCK", "64"))
    wire_ef = os.environ.get("BENCH_GVA_EF", "1") == "1"
    wire_sweep = []
    for wd in wire_list:
        codec = get_codec(wd, wire_block)
        lossy = codec is not None and codec.lossy
        ef = wire_ef and lossy
        if wd == "f32":
            ms = sgp_ms  # the headline lane IS the f32 sweep point
        else:
            ms = timed_ms(
                f"sgp_ga_steps_{wd}",
                sgp(schedule, GOSSIP_AXIS, global_avg_every=ga,
                    wire=codec, error_feedback=ef,
                    gossip_kernel=kernel_lane,
                    gossip_buckets=buckets))
        enc = encoded_payload_bytes(params_tmpl, world, codec)
        modeled = CommModel.from_schedule(
            schedule, enc, exact_bytes=payload, global_avg_every=ga,
            codec=codec, error_feedback=ef, gossip_kernel=kernel_name,
            gossip_buckets=buckets).totals(steps, start=warmup)
        wire_sweep.append({
            "wire_dtype": wd,
            **({"wire_block": wire_block} if wd == "int8" else {}),
            "error_feedback": ef,
            "step_ms": round(ms, 3),
            "payload_bytes": enc,
            "modeled_bytes_per_rank": {
                "gossip_wire": modeled["gossip_wire"],
                "gossip_ici": modeled["gossip_ici"],
                "gossip_dcn": modeled["gossip_dcn"],
                "global_avg": modeled["global_avg"],
            },
        })

    out = {
        "metric": "sgp_ga_vs_allreduce_step_ms",
        "value": round(sgp_ms, 3),
        "unit": "ms/step",
        "ar_step_ms": round(ar_ms, 3),
        "speedup_vs_ar": round(ar_ms / sgp_ms, 3) if sgp_ms else None,
        "global_avg_every": ga,
        "topology": topology,
        # the gossip transport lane that moved the bytes (modeled bytes
        # are lane-independent by construction; only measured ms moves)
        "kernel": kernel_name,
        "gossip_buckets": buckets,
        "world": world,
        "batch": batch,
        "steps": steps,
        "platform": jax.default_backend(),
        "payload_bytes": payload,
        "modeled_bytes_per_rank": {
            "sgp_ga": sgp_bytes["gossip_wire"] + sgp_bytes["global_avg"],
            # the wire split by link class (hierarchical runs put their
            # intra-slice exact average on ICI, delegate gossip on DCN;
            # flat single-slice schedules are all-ICI)
            "gossip_ici": sgp_bytes["gossip_ici"],
            "gossip_dcn": sgp_bytes["gossip_dcn"],
            "allreduce": ar_bytes["allreduce"],
        },
        "wire_sweep": wire_sweep,
    }
    out_path = os.environ.get(
        "BENCH_GVA_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "artifacts", "bench_gossip_vs_ar.json"))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"bench": out, "trace": tracer.to_chrome()}, f)
    out["artifact"] = out_path
    return out


def run_overlap_vs_sync() -> dict:
    """Double-buffered overlap (OSGP phase schedule) vs synchronous SGP.

    The same full train step — TinyCNN forward/backward, SGD, push-sum
    gossip — timed through the telemetry span tracer in two modes: sync
    (the ppermute on the step's critical path, at the bottom) and
    overlap (pre_step launches the ppermute at the TOP of the step, so
    XLA schedules the collective behind the conv compute; post_step
    consumes the share launched staleness−1 steps earlier).  The
    workload is compute-padded (batch/image knobs below) so the
    collective has compute to hide behind.  The artifact carries the
    analytic per-rank comm bytes for BOTH modes — identical by
    construction (overlap re-times the same wire, it never re-prices
    it) — next to the measured milliseconds, plus a consensus-parity
    diagnostic: both modes from one init over one batch stream must
    land on nearby de-biased means (they follow different but equally
    valid SGP trajectories).

    Knobs: BENCH_OVS_WORLD/BATCH/IMAGE/STEPS/WARMUP/REPS/STALENESS/OUT,
    BENCH_OVS_TOL (selftest step-time tolerance).  Repetitions
    alternate mode order and keep the per-mode MINIMUM — the honest
    floor under CPU scheduling noise.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from stochastic_gradient_push_tpu.algorithms import sgp
    from stochastic_gradient_push_tpu.data import synthetic_classification
    from stochastic_gradient_push_tpu.models import TinyCNN
    from stochastic_gradient_push_tpu.parallel import (
        GOSSIP_AXIS, make_gossip_mesh)
    from stochastic_gradient_push_tpu.telemetry import (
        CommModel, SpanTracer, tree_payload_bytes)
    from stochastic_gradient_push_tpu.topology import (
        NPeerDynamicDirectedExponentialGraph, build_schedule)
    from stochastic_gradient_push_tpu.train import (
        LRSchedule, build_train_step, init_train_state, replicate_state,
        sgd, shard_train_step)

    world = jax.device_count()
    batch = int(os.environ.get("BENCH_OVS_BATCH", "8"))
    image = int(os.environ.get("BENCH_OVS_IMAGE", "24"))
    steps = max(1, int(os.environ.get("BENCH_OVS_STEPS", "25")))
    warmup = max(1, int(os.environ.get("BENCH_OVS_WARMUP", "4")))
    reps = max(1, int(os.environ.get("BENCH_OVS_REPS", "3")))
    staleness = max(1, int(os.environ.get("BENCH_OVS_STALENESS", "2")))
    # since the start/wait split, overlap rounds ride the requested lane
    # first-class (gossip_edge_start at the top of the step, the wait at
    # the bottom), so both timed modes run the SAME transport — the
    # comparison stays lane-pure without forcing anything
    kernel_lane, kernel_name, buckets = _resolve_bench_kernel()
    # an interpreted kernel lane cannot run under the vma check
    # (train/step.py::shard_train_step says why)
    check_vma = kernel_lane is None or not kernel_lane.interpret
    classes = 10

    mesh = make_gossip_mesh(world)
    model = TinyCNN(num_classes=classes)
    tx = sgd(momentum=0.9, weight_decay=1e-4)
    lr_sched = LRSchedule(ref_lr=0.05, batch_size=batch, world_size=world)
    schedule = build_schedule(
        NPeerDynamicDirectedExponentialGraph(world, peers_per_itr=1))
    tracer = SpanTracer(rank=0)
    serialize = jax.default_backend() == "cpu"

    images, labels = synthetic_classification(
        world * batch, num_classes=classes, image_size=image, seed=0)
    x = images.reshape(world, batch, image, image, 3)
    y = labels.reshape(world, batch)

    def build(mode_alg):
        step = build_train_step(model, mode_alg, tx, lr_sched,
                                itr_per_epoch=100, num_classes=classes)
        fn = shard_train_step(step, mesh, check_vma=check_vma)
        st = replicate_state(
            init_train_state(model, jax.random.PRNGKey(0),
                             jnp.zeros((batch, image, image, 3)), tx,
                             mode_alg),
            world)
        return fn, st

    modes = {
        "sync": sgp(schedule, GOSSIP_AXIS, gossip_kernel=kernel_lane,
                    gossip_buckets=buckets),
        "overlap": sgp(schedule, GOSSIP_AXIS, overlap=True,
                       staleness=staleness, gossip_kernel=kernel_lane,
                       gossip_buckets=buckets),
    }
    built = {name: build(alg) for name, alg in modes.items()}
    final_state = {}

    def timed_once(name, rep):
        fn, st = built[name]
        m = None
        for _ in range(warmup if rep == 0 else 1):
            st, m = fn(st, x, y)
            if serialize:
                jax.block_until_ready(st)
        jax.block_until_ready(st)
        with tracer.span(f"{name}_steps_r{rep}", "bench",
                         {"steps": steps}):
            for _ in range(steps):
                st, m = fn(st, x, y)
                if serialize:
                    jax.block_until_ready(st)
            jax.block_until_ready(st)
        built[name] = (fn, st)
        final_state[name] = st
        loss = float(np.min(np.asarray(jax.device_get(m["loss"]))))
        if not np.isfinite(loss):
            raise RuntimeError(f"non-finite loss {loss} in {name}")
        return tracer.durations(f"{name}_steps_r{rep}")[-1] / steps * 1e3

    times = {"sync": [], "overlap": []}
    for rep in range(reps):
        # alternate order so clock drift / cache warmth cancels
        order = (("sync", "overlap") if rep % 2 == 0
                 else ("overlap", "sync"))
        for name in order:
            times[name].append(timed_once(name, rep))
    sync_ms = min(times["sync"])
    overlap_ms = min(times["overlap"])

    # consensus parity: both modes ran the same init/batches; their
    # de-biased network means must be close (different but equally valid
    # SGP trajectories — the overlap one is one round stale)
    def debiased_mean(name):
        st = final_state[name]
        alg = modes[name]
        z = jax.vmap(alg.val_params)(st.params, st.gossip)
        flat = np.concatenate([np.asarray(l).reshape(world, -1)
                               for l in jax.tree.leaves(z)], axis=1)
        return flat.mean(axis=0), np.abs(flat).max()

    mean_s, scale = debiased_mean("sync")
    mean_o, _ = debiased_mean("overlap")
    parity = float(np.abs(mean_o - mean_s).max() / max(scale, 1e-12))

    payload = tree_payload_bytes(built["sync"][1].params, world)
    sync_bytes = CommModel.from_schedule(
        schedule, payload, gossip_kernel=kernel_name,
        gossip_buckets=buckets).totals(steps, start=warmup)
    # the split start/wait transport means overlap runs the SAME lane
    # as sync — the comm model stamps the one lane both modes rode
    over_bytes = CommModel.from_schedule(
        schedule, payload, overlap=True, staleness=staleness,
        gossip_kernel=kernel_name,
        gossip_buckets=buckets).totals(steps, start=warmup)

    out = {
        "metric": "overlap_vs_sync_step_ms",
        "value": round(overlap_ms, 3),
        "unit": "ms/step",
        "sync_step_ms": round(sync_ms, 3),
        "speedup_vs_sync": round(sync_ms / overlap_ms, 3)
        if overlap_ms else None,
        "staleness": staleness,
        # the gossip transport lane BOTH timed modes ran.  Since the
        # start/wait split, overlap rides the requested lane first-class
        # (the fence between launch and compute is gone), so the speedup
        # compares like against like by construction.  Bytes are
        # lane-independent either way; only measured ms may move
        "kernel": kernel_name,
        # per-bucket pipelining depth of the split transport: >1 breaks
        # the round into byte-balanced leaf buckets whose start/wait
        # pairs interleave (bytes identical, only timing may move)
        "gossip_buckets": buckets,
        "world": world,
        "batch": batch,
        "image": image,
        "steps": steps,
        "reps": reps,
        "rep_ms": {k: [round(v, 3) for v in vs]
                   for k, vs in times.items()},
        "platform": jax.default_backend(),
        "consensus_parity_rel": round(parity, 6),
        "payload_bytes": payload,
        # identical by construction: overlap hides the wire, it never
        # changes it (the selftest asserts this equality)
        "modeled_bytes_per_rank": {
            "sync": sync_bytes["gossip_wire"],
            "overlap": over_bytes["gossip_wire"],
        },
    }
    if out["platform"] == "cpu":
        # the win this mode exists to measure needs ASYNC collectives:
        # on TPU the top-of-step collective-permute-start runs behind
        # the conv compute and -done lands at the bottom for free.  The
        # CPU test runtime executes collectives blocking at their
        # schedule point, so the top-issued rendezvous can even cost a
        # few percent on an oversubscribed host — an artifact of the
        # backend, not of the schedule (the spans record it honestly;
        # the selftest gates on a tolerance band, byte equality, and
        # consensus parity instead of a CPU pseudo-win)
        out["note"] = ("cpu backend: collectives are blocking, so the "
                       "overlap win is not observable here; the "
                       "overlap-vs-sync TPU capture is the headline "
                       "measurement.  The same caveat covers the kernel "
                       "lane: BENCH_r04/r05 headline values are cached "
                       "on-chip captures, and the pallas lane's "
                       "measured-ms win needs a live-TPU capture (until "
                       "it lands, pallas is opt-in everywhere — the "
                       "production default is xla; since the start/wait "
                       "split, overlap rounds ride whichever lane is "
                       "requested) — on cpu the kernel runs through the "
                       "Pallas interpreter (correctness, not speed)")
    out_path = os.environ.get(
        "BENCH_OVS_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "artifacts", "bench_overlap_vs_sync.json"))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"bench": out, "trace": tracer.to_chrome()}, f)
    out["artifact"] = out_path
    return out


def overlap_vs_sync_main(selftest: bool) -> int:
    """Parent for --overlap-vs-sync: re-exec as a child on a world-8
    virtual CPU mesh; with --selftest, gate the child's artifact:
    overlap step time within tolerance of (CI) or below (the win on
    hardware with async collectives) the sync step, consensus parity,
    and modeled comm bytes IDENTICAL between the modes."""
    env = _child_env(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            + env.get("BENCH_OVS_WORLD", "8")).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--overlap-vs-sync-child"],
        env=env, capture_output=True, text=True,
        timeout=float(os.environ.get("BENCH_TIMEOUT", "600")))
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        return proc.returncode
    result = _parse_last_json(proc.stdout)
    if result is None:
        print("overlap-vs-sync: child produced no JSON", file=sys.stderr)
        return 1
    if not selftest:
        return 0
    # CPU executes collectives blocking at their schedule point, so the
    # top-of-step rendezvous costs tens of percent instead of being
    # hidden, with huge variance on oversubscribed hosts — the wide CPU
    # band only catches pathological regressions (a 2x step) without
    # flaking CI on scheduler noise; byte equality and consensus parity
    # below are the strict CPU gates.  On an async backend (real TPU)
    # overlap must be <= sync outright: tol collapses to 0.
    default_tol = "1.0" if result.get("platform") == "cpu" else "0.0"
    tol = float(os.environ.get("BENCH_OVS_TOL", default_tol))
    failures = []
    if result["value"] > result["sync_step_ms"] * (1.0 + tol):
        failures.append(
            f"overlap step {result['value']} ms exceeds sync "
            f"{result['sync_step_ms']} ms by more than {tol:.0%} "
            "(the collective is not being hidden)")
    modeled = result["modeled_bytes_per_rank"]
    if modeled["sync"] != modeled["overlap"]:
        failures.append(
            f"modeled comm bytes differ between modes ({modeled}); "
            "overlap must re-time the wire, never re-price it")
    if result.get("kernel") not in ("pallas", "xla"):
        failures.append(
            f"artifact kernel lane {result.get('kernel')!r} missing or "
            "unknown; the transport lane must be stamped (pallas|xla)")
    if not isinstance(result.get("gossip_buckets"), int) \
            or result["gossip_buckets"] < 1:
        failures.append(
            f"artifact gossip_buckets {result.get('gossip_buckets')!r} "
            "missing or invalid; the pipelining depth must be stamped")
    if result["consensus_parity_rel"] > 0.05:
        failures.append(
            f"consensus parity {result['consensus_parity_rel']} "
            "outside tolerance: the overlap trajectory diverged")
    if failures:
        for msg in failures:
            print(f"overlap-vs-sync selftest: FAIL — {msg}",
                  file=sys.stderr)
        return 1
    print(f"overlap-vs-sync selftest: OK (overlap "
          f"{result['value']} ms vs sync {result['sync_step_ms']} ms, "
          f"speedup {result['speedup_vs_sync']}x, parity "
          f"{result['consensus_parity_rel']}, bytes equal, "
          f"kernel {result['kernel']}, "
          f"buckets {result['gossip_buckets']})", flush=True)
    return 0


def _spearman(xs, ys) -> float:
    """Spearman rank correlation with average ranks for ties."""
    def ranks(v):
        order = sorted(range(len(v)), key=lambda i: v[i])
        r = [0.0] * len(v)
        i = 0
        while i < len(order):
            j = i
            while j + 1 < len(order) \
                    and v[order[j + 1]] == v[order[i]]:
                j += 1
            for k in range(i, j + 1):
                r[order[k]] = (i + j) / 2.0 + 1.0
            i = j + 1
        return r
    rx, ry = ranks(list(xs)), ranks(list(ys))
    mx, my = sum(rx) / len(rx), sum(ry) / len(ry)
    num = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    den = (sum((a - mx) ** 2 for a in rx)
           * sum((b - my) ** 2 for b in ry)) ** 0.5
    return num / den if den else 0.0


def _sim_seconds_per_efold(schedule, fabric, steps: int = 64,
                           seed: int = 1) -> dict:
    """Simulated wall-clock per consensus e-fold: the sim/ engine runs
    the exact schedule while the fabric model accumulates priced
    seconds; the quotient is the empirical counterpart of the planner's
    modeled ``priced_cost``."""
    import math

    from stochastic_gradient_push_tpu.sim import (consensus_curve,
                                                  time_to_error)
    curve = consensus_curve(schedule, steps, interconnect=fabric,
                            seed=seed)
    # clamp at the f64 noise floor: exact-averaging cycles bottom out
    # around 1e-16 and would otherwise divide by ~0 e-folds
    first = max(curve["error"][0], 1e-13)
    last = max(curve["error"][-1], 1e-13)
    efolds = math.log(first / last)
    return {"sim_s_per_efold": (curve["time_s"][-1] / efolds
                                if efolds > 1e-9 else None),
            "sim_cycle_time_s": curve["cycle_time_s"],
            "sim_final_error": curve["error"][-1],
            "sim_time_to_1e-6_s": time_to_error(curve, 1e-6),
            "sim_rounds": steps}


def synth_vs_registry_main(selftest: bool) -> int:
    """--synth-vs-registry: stamp the synthesized schedule's modeled
    priced bytes and gap next to the best registry candidate's (see the
    module docstring).  Pure host math — no mesh, no child process."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import functools

    from stochastic_gradient_push_tpu.planner import (
        InterconnectModel,
        SynthesisConfig,
        evaluate_candidate,
        plan_synthesized,
        score_candidates,
    )
    from stochastic_gradient_push_tpu.telemetry import CommModel
    from stochastic_gradient_push_tpu.topology import (
        SynthesizedGraph,
        build_schedule,
        spec_fingerprint,
    )

    budget = int(os.environ.get("BENCH_SYNTH_BUDGET", "800"))
    # reference payload: ResNet-50 f32 (~25.6M params × 4 B)
    payload = int(os.environ.get("BENCH_SYNTH_PAYLOAD",
                                 str(25_600_000 * 4)))
    cfg = SynthesisConfig(budget=budget)

    def round_bytes(schedule, fabric):
        m = CommModel.from_schedule(schedule, payload,
                                    interconnect=fabric)
        phases = max(1, m.num_phases)
        return {"wire": sum(m.wire_bytes_per_phase) // phases,
                "ici": sum(m.ici_bytes_per_phase) // phases,
                "dcn": sum(m.dcn_bytes_per_phase) // phases}

    cases = []
    for world, s, dcn in ((12, 4, 16.0), (48, 8, 16.0),
                          (12, None, None)):
        fabric = (InterconnectModel(slice_size=s, dcn_cost=dcn)
                  if s else None)
        regs = score_candidates(world, interconnect=fabric)
        best_reg = regs[0]
        reg_sched = build_schedule(
            best_reg.graph_class(world, peers_per_itr=best_reg.ppi))
        plan = plan_synthesized(world, interconnect=fabric, config=cfg)
        row = {"world": world,
               "fabric": fabric.to_dict() if fabric else None,
               "plan_topology": plan.topology,
               "beats_registry": plan.topology == "synth",
               "registry_best": {
                   **best_reg.to_dict(),
                   "modeled_bytes_per_round": round_bytes(reg_sched,
                                                          fabric),
                   "simulated": _sim_seconds_per_efold(reg_sched,
                                                       fabric)}}
        if plan.topology == "synth":
            spec = plan.synth["spec"]
            ssched = build_schedule(SynthesizedGraph(world, spec=spec))
            scand = evaluate_candidate(
                functools.partial(SynthesizedGraph, spec=spec), world, 1,
                interconnect=fabric)
            row["synthesized"] = {
                **scand.to_dict(),
                "phases": [ph["kind"] for ph in spec["phases"]],
                "fingerprint": spec_fingerprint(spec),
                "evals": plan.synth["evals"],
                "modeled_bytes_per_round": round_bytes(ssched, fabric),
                "simulated": _sim_seconds_per_efold(ssched, fabric)}
        if world == 48 and fabric is not None:
            # does the modeled per-round priced cost rank schedules the
            # way simulated per-round wall-clock does?  This isolates
            # the PRICING lane (bytes x fabric -> seconds; CommModel +
            # cycle_cost vs the sim FabricModel are independent
            # implementations over the same InterconnectModel); the
            # RATE lane (gap -> rounds/e-fold) is verified separately
            # by engine bit-exactness + SGPV, and its end-to-end
            # residue is stamped per candidate as sim_s_per_efold for
            # the on-chip calibration item
            per_round_m, per_round_s = [], []
            per_efold_m, per_efold_s = [], []
            cand_rows = []
            for c in regs:
                sched_c = build_schedule(
                    c.graph_class(world, peers_per_itr=c.ppi))
                sim = _sim_seconds_per_efold(sched_c, fabric)
                mrow = c.priced_cost / max(c.rounds_per_efold, 1e-12)
                srow = (sim["sim_cycle_time_s"]
                        / max(sched_c.num_phases, 1))
                per_round_m.append(mrow)
                per_round_s.append(srow)
                if sim["sim_s_per_efold"] is not None:
                    per_efold_m.append(c.priced_cost)
                    per_efold_s.append(sim["sim_s_per_efold"])
                cand_rows.append({"topology": c.topology, "ppi": c.ppi,
                                  "priced_cost": c.priced_cost,
                                  "priced_per_round": mrow,
                                  "sim_s_per_round": srow, **sim})
            row["candidate_correlation"] = {
                "spearman": _spearman(per_round_m, per_round_s),
                "spearman_per_efold": _spearman(per_efold_m,
                                                per_efold_s),
                "count": len(cand_rows), "candidates": cand_rows}
        cases.append(row)

    out = {"benchmark": "synth_vs_registry", "budget": budget,
           "payload_bytes": payload, "seed": cfg.seed, "cases": cases}
    out_path = os.environ.get(
        "BENCH_SYNTH_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "artifacts", "bench_synth_vs_registry.json"))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    out["artifact"] = out_path
    print(json.dumps(out), flush=True)
    if not selftest:
        return 0
    failures = []
    for row in out["cases"]:
        dcn_case = bool(row["fabric"])
        if dcn_case and not row["beats_registry"]:
            failures.append(
                f"world {row['world']} on the DCN-dominant fabric: "
                "synthesis did not beat the registry")
        corr = row.get("candidate_correlation")
        if corr is not None and not corr["spearman"] >= 0.8:
            failures.append(
                f"world {row['world']}: modeled priced cost vs "
                f"simulated wall-clock Spearman {corr['spearman']:.3f} "
                f"< 0.8 over {corr['count']} candidates")
        if row["beats_registry"] and not (
                row["synthesized"]["priced_cost"]
                < row["registry_best"]["priced_cost"]):
            failures.append(
                f"world {row['world']}: synthesized priced cost is not "
                "below the registry best it claims to beat")
    if failures:
        for msg in failures:
            print(f"synth-vs-registry selftest: FAIL — {msg}",
                  file=sys.stderr)
        return 1
    beats = [f"world {r['world']}"
             + ("" if not r["fabric"] else " (dcn)")
             + (": synth "
                f"{r['synthesized']['priced_cost']}"
                if r["beats_registry"] else ": registry kept")
             + f" vs registry {r['registry_best']['priced_cost']}"
             for r in out["cases"]]
    print("synth-vs-registry selftest: OK (" + "; ".join(beats) + ")",
          flush=True)
    return 0


def sim_scale_main(selftest: bool) -> int:
    """--sim-scale: consensus-vs-simulated-wall-clock curves at pod
    worlds (256/1024/4096) for the core topology registry on the 16:1
    DCN fabric — the scale regime no CI mesh can execute, produced by
    the sim/ exact engine + priced fabric.  Artifact:
    artifacts/bench_sim_scale.json."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from stochastic_gradient_push_tpu.planner import InterconnectModel
    from stochastic_gradient_push_tpu.sim import sweep_curves
    from stochastic_gradient_push_tpu.topology import (TOPOLOGY_NAMES,
                                                       build_schedule)

    topos = os.environ.get(
        "BENCH_SIM_TOPOLOGIES",
        "ring,exponential,npeer-exponential").split(",")
    worlds = [int(w) for w in os.environ.get(
        "BENCH_SIM_WORLDS", "256,1024,4096").split(",")]
    steps = int(os.environ.get("BENCH_SIM_STEPS", "96"))
    t0 = time.time()
    rows = sweep_curves(
        {name: (lambda w, _cls=TOPOLOGY_NAMES[name]:
                build_schedule(_cls(w, peers_per_itr=1)))
         for name in topos},
        worlds, steps,
        interconnect_for=lambda w: InterconnectModel(slice_size=32,
                                                     dcn_cost=16.0),
        eps=1e-6)
    out = {"benchmark": "sim_scale", "steps": steps,
           "fabric": {"slice_size": 32, "dcn_cost": 16.0},
           "elapsed_s": round(time.time() - t0, 3), "curves": rows}
    out_path = os.environ.get(
        "BENCH_SIM_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "artifacts", "bench_sim_scale.json"))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    for r in rows:
        tte = r["time_to_eps"]
        print(f"sim-scale: {r['topology']}-{r['world']}: final error "
              f"{r['final_error']:.3e}, time-to-1e-6 "
              f"{'unreached' if tte is None else f'{tte:.3e}s'}")
    print(f"sim-scale: wrote {out_path} ({out['elapsed_s']}s)",
          flush=True)
    if not selftest:
        return 0
    failures = []
    seen = {(r["topology"], r["world"]) for r in rows}
    want = {(t, w) for t in topos for w in worlds}
    if seen != want:
        failures.append(f"missing curves: {sorted(want - seen)}")
    for w in worlds:
        exp = next(r for r in rows
                   if r["topology"] == "exponential" and r["world"] == w)
        ring = next(r for r in rows
                    if r["topology"] == "ring" and r["world"] == w)
        if exp["time_to_eps"] is None:
            failures.append(f"exponential-{w} never reached 1e-6")
        elif ring["time_to_eps"] is not None \
                and exp["time_to_eps"] >= ring["time_to_eps"]:
            failures.append(f"ring-{w} beat exponential-{w} to 1e-6")
    if failures:
        for msg in failures:
            print(f"sim-scale selftest: FAIL — {msg}", file=sys.stderr)
        return 1
    print("sim-scale selftest: OK", flush=True)
    return 0


def _gva_flag_arg(argv: list[str], flag: str) -> str | None:
    """``FLAG NAME`` / ``FLAG=NAME`` from a raw argv (no argparse in the
    parent — it must stay transparent to child flags).  Raises
    SystemExit on a dangling flag."""
    for i, arg in enumerate(argv):
        if arg == flag:
            if i + 1 >= len(argv):
                print(f"{flag} needs a value", file=sys.stderr)
                raise SystemExit(2)
            return argv[i + 1]
        if arg.startswith(flag + "="):
            return arg.split("=", 1)[1]
    return None


def _gva_topology_arg(argv: list[str]) -> str | None:
    return _gva_flag_arg(argv, "--topology")


def gossip_vs_ar_main() -> int:
    """Parent for --gossip-vs-ar: re-exec as a child on a world-8
    virtual CPU mesh (the device-count flag must be set before jax
    loads, hence the subprocess).  ``--topology NAME`` rides into the
    child as BENCH_GVA_TOPOLOGY (hierarchical-vs-flat timing)."""
    env = _child_env(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    topology = _gva_topology_arg(sys.argv)
    if topology is not None:
        env["BENCH_GVA_TOPOLOGY"] = topology
    wire = _gva_flag_arg(sys.argv, "--wire_dtype")
    if wire is not None:
        # sweep the requested codec against the f32 baseline so the
        # artifact always carries the payload-reduction ratio
        env["BENCH_GVA_WIRE"] = ("f32" if wire == "f32"
                                 else f"f32,{wire}")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            + env.get("BENCH_GVA_WORLD", "8")).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--gossip-vs-ar-child"],
        env=env, timeout=float(os.environ.get("BENCH_TIMEOUT", "600")))
    return proc.returncode


def _parse_last_json(text: str) -> dict | None:
    for line in reversed((text or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _child_env(base: dict) -> dict:
    env = dict(base)
    env["PYTHONUNBUFFERED"] = "1"  # child prints must survive a kill
    return env


def main() -> int:
    print("bench.py has no default mode.  CPU comparison modes: "
          "--gossip-vs-ar, --overlap-vs-sync, --synth-vs-registry, "
          "--sim-scale.  Chip measurements: python benchmark/run.py "
          "--workload <cell> (BENCHMARK.json, PERF.md)", file=sys.stderr)
    return 2


if __name__ == "__main__":
    if "--gossip-vs-ar-child" in sys.argv:
        print(json.dumps(run_gossip_vs_ar()), flush=True)
    elif "--gossip-vs-ar" in sys.argv:
        sys.exit(gossip_vs_ar_main())
    elif "--overlap-vs-sync-child" in sys.argv:
        print(json.dumps(run_overlap_vs_sync()), flush=True)
    elif "--overlap-vs-sync" in sys.argv:
        sys.exit(overlap_vs_sync_main("--selftest" in sys.argv))
    elif "--synth-vs-registry" in sys.argv:
        sys.exit(synth_vs_registry_main("--selftest" in sys.argv))
    elif "--sim-scale" in sys.argv:
        sys.exit(sim_scale_main("--selftest" in sys.argv))
    else:
        sys.exit(main())
