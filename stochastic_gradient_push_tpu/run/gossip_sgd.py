"""Gossip SGD CLI — decentralized data-parallel training on a TPU mesh.

Flag-compatible port of the reference's experiment harness
(gossip_sgd.py:72-159): same names, same string-encoded booleans, same
integer-coded graph/mixing registries, same flat-list schedule encodings.
Flags that only managed host-side distribution (master address/port, NCCL
backend, NIC type, dataloader workers, cuda streams) are accepted but
ignored, so existing launch scripts keep working.

New flags for the TPU world: ``--world_size`` (mesh size; default all
devices), ``--nprocs_per_node`` (hierarchical mesh), ``--model``,
``--dataset synthetic|imagefolder``, ``--image_size``.

Run (virtual 8-device CPU mesh):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python -m stochastic_gradient_push_tpu.run.gossip_sgd \\
      --dataset synthetic --world_size 8 --num_epochs 1 \\
      --num_iterations_per_training_epoch 5 --checkpoint_dir /tmp/ckpt/
"""

from __future__ import annotations

import argparse
import os

from ..topology import GRAPH_TOPOLOGIES, MIXING_STRATEGIES, TOPOLOGY_NAMES

__all__ = ["build_parser", "parse_config", "main"]


def _str_bool(v: str) -> bool:
    return str(v) == "True"


def add_wire_flags(p: argparse.ArgumentParser) -> None:
    """Gossip wire-format flags, shared by both run CLIs (gossip_sgd and
    gossip_lm): codec selection, int8 block size, error feedback."""
    p.add_argument("--wire_dtype", default=None,
                   choices=[None, "f32", "bf16", "int8"],
                   help="gossip wire codec (parallel/wire.py): f32 = "
                        "exact (default), bf16 halves the payload, int8 "
                        "is symmetric per-block quantization with f32 "
                        "scales riding alongside (~3.8x smaller at the "
                        "default block).  The push-sum weight lane "
                        "always ships exact f32")
    p.add_argument("--wire_block", default=64, type=int,
                   help="int8 codec block size: elements sharing one f32 "
                        "scale (wire overhead 4/wire_block bytes per "
                        "element)")
    p.add_argument("--error_feedback", default="False", type=str,
                   help="carry per-rank error-feedback residual "
                        "accumulators: round t's quantization error is "
                        "re-injected into round t+1's send, so wire "
                        "compression perturbs the network mean by a "
                        "bounded amount instead of a bias (needs a "
                        "lossy --wire_dtype; sync push-sum mode)")


def resolve_wire_flags(args) -> None:
    """Normalize the wire flags in place: coerce --error_feedback to
    bool, and fail fast on inconsistent combinations."""
    ef = _str_bool(args.error_feedback)
    if args.wire_block < 1:
        raise SystemExit("--wire_block must be >= 1")
    if ef and args.wire_dtype not in ("bf16", "int8"):
        raise SystemExit(
            "--error_feedback needs a lossy --wire_dtype (bf16/int8): "
            "an exact wire has no quantization error to feed back")
    # error feedback composes with overlap: the residual telescopes
    # against the round being SENT at launch time (staleness-aware
    # carry), so no overlap rejection here anymore
    args.error_feedback = ef


def add_kernel_flag(p: argparse.ArgumentParser) -> None:
    """The gossip transport-kernel flag, shared by both run CLIs."""
    from ..ops.gossip_kernel import GOSSIP_KERNELS

    p.add_argument("--gossip_kernel", default="xla",
                   choices=list(GOSSIP_KERNELS),
                   help="gossip transport lane (ops/gossip_kernel.py): "
                        "'pallas' fuses the edge exchange into one "
                        "remote-DMA kernel (async copy + in-VMEM wire "
                        "decode + mixing axpy; TPU only), 'auto' picks "
                        "pallas on TPU and xla elsewhere.  Default "
                        "'xla' (ppermute + decode, always available): "
                        "the kernel pair runs on the four-chip host "
                        "and matches the xla lane bit for bit per "
                        "round (chip_smoke.py --chips 4) but its speed "
                        "is unmeasured — opt in with pallas/auto.  "
                        "Numerics "
                        "are lane-independent (CI bit-compares them); "
                        "the push-sum weight lane ships exact f32 "
                        "either way, and overlap rounds ride the "
                        "kernel first-class (split start/wait "
                        "transport)")
    p.add_argument("--gossip_buckets", default=1, type=int,
                   help="kernel-lane transport pipelining: partition "
                        "the payload into this many contiguous "
                        "byte-bounded buckets, one start/wait kernel "
                        "program per bucket, so later buckets' remote "
                        "DMAs overlap earlier buckets' decode.  "
                        "Ignored on the xla lane; never changes bytes "
                        "or numerics (parity-pinned).  Default 1 (one "
                        "program for the whole payload)")


def resolve_kernel_flag(args) -> None:
    """Validate --gossip_kernel at parse time (shared by both CLIs):
    'pallas' on a backend that cannot lower the Mosaic kernel fails
    HERE with the resolver's typed error instead of at first step."""
    from ..ops.gossip_kernel import KernelBackendError, \
        resolve_gossip_kernel

    try:
        resolve_gossip_kernel(args.gossip_kernel)
    except KernelBackendError as e:
        raise SystemExit(f"--gossip_kernel pallas: {e}")
    if getattr(args, "gossip_buckets", 1) < 1:
        raise SystemExit("--gossip_buckets must be >= 1, got "
                         f"{args.gossip_buckets}")


def add_synth_flags(p: argparse.ArgumentParser) -> None:
    """Schedule-synthesizer budget knobs, shared by both run CLIs: only
    meaningful with ``--topology synth`` (planner/synthesize.py)."""
    p.add_argument("--synth_seed", default=None, type=int,
                   help="schedule-synthesizer seed, default 0 (feeds "
                        "the random-permutation moves; the search is "
                        "otherwise deterministic, so seed+knobs "
                        "reproduce the schedule exactly)")
    p.add_argument("--synth_budget", default=None, type=int,
                   help="max candidate-schedule evaluations in the "
                        "synthesizer's beam search (default 1200)")
    p.add_argument("--synth_beam", default=None, type=int,
                   help="beam width: contracting phase-sequence "
                        "prefixes kept per search depth (default 6)")
    p.add_argument("--synth_phases", default=None, type=int,
                   help="longest synthesized cycle considered, in "
                        "phases (default 6)")


def synth_plan_config(args) -> dict | None:
    """The synthesizer knob dict for the planner (None when --topology
    is not 'synth'); rejects stray --synth_* knobs on other topologies
    instead of silently ignoring them."""
    knobs_set = any(v is not None for v in (
        args.synth_seed, args.synth_budget, args.synth_beam,
        args.synth_phases))
    if args.topology != "synth":
        if knobs_set:
            raise SystemExit(
                "--synth_seed/--synth_budget/--synth_beam/"
                "--synth_phases tune the schedule synthesizer; they "
                "need --topology synth")
        return None
    return {"seed": args.synth_seed, "budget": args.synth_budget,
            "beam_width": args.synth_beam,
            "max_phases": args.synth_phases}


def add_fleet_flags(p: argparse.ArgumentParser) -> None:
    """Fleet-supervision flags, shared by both run CLIs: mark this
    process as one host of a coordinated pod (scripts/fleet.py — a
    per-host supervisor plus a pod coordinator own the restart
    boundary)."""
    p.add_argument("--fleet", default="False", type=str,
                   help="this run is one host of a coordinated fleet "
                        "(supervise/coordinator.py): the pod "
                        "coordinator owns cross-world resharding, so "
                        "the per-host auto-reshard on resume is "
                        "disabled (a racing per-host reshard is "
                        "exactly the relaunch storm fleet supervision "
                        "exists to prevent); host identity is stamped "
                        "into run_meta.  Requires --trace_dir (the "
                        "per-host supervisor acts on the typed event "
                        "stream)")
    p.add_argument("--host_id", default=None, type=int,
                   help="this process's host index within the fleet "
                        "(default: the jax process index); only "
                        "meaningful with --fleet True")


def resolve_fleet_flags(args) -> bool:
    """Normalize the fleet flags in place (shared by both CLIs): coerce
    --fleet to bool and fail fast on inconsistent combinations."""
    fleet = _str_bool(args.fleet)
    if args.host_id is not None and not fleet:
        raise SystemExit("--host_id identifies this host under fleet "
                         "supervision; it needs --fleet True")
    if fleet and not args.trace_dir:
        raise SystemExit("--fleet True needs --trace_dir (the per-host "
                         "supervisor tails the typed event stream)")
    args.fleet = fleet
    return fleet


def add_profile_flags(p: argparse.ArgumentParser) -> None:
    """Device-profiling flags, shared by both run CLIs: a step-indexed
    ``jax.profiler`` capture window inside the REAL run
    (utils/profiling.ProfileWindow — one shot, guarded)."""
    p.add_argument("--profile_dir", default=None, type=str,
                   help="capture a jax.profiler device trace of global "
                        "steps [--profile_start_step, +--profile_steps) "
                        "into this directory (TensorBoard XPlane "
                        "format); the dump path is stamped into "
                        "run_meta.  A profiler start that hangs or "
                        "fails is logged as an error and the run "
                        "continues untraced (utils/profiling.py)")
    p.add_argument("--profile_start_step", default=None, type=int,
                   help="first global step of the capture window "
                        "(default 2: past the compile and the "
                        "donation-driven second compile)")
    p.add_argument("--profile_steps", default=None, type=int,
                   help="steps captured in the window (default 3; a "
                        "bounded window — a full-run device trace is "
                        "unloadable for real jobs)")


def resolve_profile_flags(args) -> None:
    """Validate and default the profiling flags in place (shared by
    both CLIs): window knobs without a destination are a mistake."""
    knobs_set = (args.profile_start_step is not None
                 or args.profile_steps is not None)
    if knobs_set and not args.profile_dir:
        raise SystemExit("--profile_start_step/--profile_steps shape "
                         "the capture window; they need --profile_dir")
    if args.profile_start_step is None:
        args.profile_start_step = 2
    if args.profile_steps is None:
        args.profile_steps = 3
    if args.profile_start_step < 0:
        raise SystemExit("--profile_start_step must be >= 0")
    if args.profile_steps < 1:
        raise SystemExit("--profile_steps must be >= 1")


def add_staleness_flag(p: argparse.ArgumentParser) -> None:
    """The overlap staleness bound, shared by both run CLIs (gossip_sgd
    and gossip_lm): the in-flight FIFO depth of the double-buffered
    phase schedule."""
    p.add_argument("--staleness", default=0, type=int,
                   help="overlap-mode staleness bound: the in-flight "
                        "FIFO depth — a share launched at the top of "
                        "step t is consumed at the bottom of step "
                        "t+staleness-1 (staleness 1 hides the ppermute "
                        "behind the same step's compute; higher values "
                        "also tolerate cross-step comm latency, "
                        "reference semantics staleness = synch_freq+1, "
                        "distributed.py:127-129).  0 = derive from "
                        "--synch_freq")


def resolve_staleness_flag(args, overlap: bool) -> None:
    """Validate --staleness in place (shared by both CLIs): non-negative,
    consistent with any --synch_freq alias, and overlap-only."""
    staleness = getattr(args, "staleness", 0)
    synch_freq = getattr(args, "synch_freq", 0)
    if staleness < 0:
        raise SystemExit("--staleness must be >= 0 (0 = derive from "
                         "--synch_freq)")
    if staleness and synch_freq and staleness != synch_freq + 1:
        raise SystemExit(
            f"--staleness {staleness} conflicts with --synch_freq "
            f"{synch_freq} (staleness = synch_freq + 1); set one of "
            "the two")
    if staleness > 1 and not overlap:
        raise SystemExit("--staleness is an overlap-mode knob")


def reject_push_sum_wire_knobs(args) -> None:
    """One rejection for every non-push-sum branch (all_reduce, bilat,
    D-PSGD) of BOTH CLIs: communication thinning and the wire codec tune
    the push-sum gossip wire, which those modes don't have.  Call after
    :func:`resolve_wire_flags`."""
    wire_set = (args.wire_dtype not in (None, "f32")
                or _str_bool(str(args.error_feedback)))
    if args.gossip_every != 1 or wire_set:
        raise SystemExit(
            "gossip_every/wire_dtype/error_feedback are push-sum knobs")


def wire_plan_config(args) -> dict | None:
    """The wire stamp the planner prices on and the plan records
    ({"dtype", "block", "error_feedback"}; None = exact f32 wire)."""
    if args.wire_dtype in (None, "f32"):
        return None
    cfg = {"dtype": args.wire_dtype}
    if args.wire_dtype == "int8":
        cfg["block"] = args.wire_block
    cfg["error_feedback"] = bool(_str_bool(str(args.error_feedback)))
    return cfg


def add_shared_flags(p: argparse.ArgumentParser) -> None:
    """Every flag both run CLIs declare alike (type, default, choices):
    algorithm selection, the planner's inputs, resilience, the optimizer
    and run-shape basics, the cluster rendezvous, and the flag groups
    above.  A flag whose default differs between the CLIs (--lr,
    --batch_size, --weight_decay, --tag) stays in its own parser."""
    # algorithm (reference flag surface, gossip_sgd.py:72-159)
    p.add_argument("--all_reduce", default="False", type=str)
    p.add_argument("--push_sum", default="True", type=str)
    p.add_argument("--overlap", default="False", type=str)
    add_staleness_flag(p)
    p.add_argument("--graph_type", default=5, type=int,
                   choices=list(GRAPH_TOPOLOGIES))
    p.add_argument("--gossip_every", default=1, type=int,
                   help="gossip on every k-th step only (communication "
                        "thinning; sync push-sum mode)")
    add_wire_flags(p)
    add_kernel_flag(p)
    # launch-time topology policy (planner/)
    p.add_argument("--topology", default=None,
                   choices=["auto"] + sorted(TOPOLOGY_NAMES),
                   help="named topology selection: 'auto' lets the "
                        "planner pick (and tune) the gossip graph for "
                        "the gossip world; 'synth' searches a hybrid "
                        "psum/ppermute schedule against the priced "
                        "fabric (falling back to the registry when not "
                        "beaten); a name forces it (overriding "
                        "--graph_type) with a below-floor warning when "
                        "its spectral gap is too small")
    add_synth_flags(p)
    p.add_argument("--gap_floor", default=0.01, type=float,
                   help="minimum acceptable rotation-cycle spectral gap; "
                        "below it the planner auto-switches (or warns "
                        "when the topology is user-forced)")
    p.add_argument("--global_avg_every", default=None, type=int,
                   help="exact global average (one allreduce) every k "
                        "steps; unset = the planner decides (it enables "
                        "periodic averaging when no gossip graph clears "
                        "the gap floor), 0 = explicitly off even below "
                        "the floor, k = force every-k averaging")
    p.add_argument("--slice_size", default=None, type=int,
                   help="gossip ranks per ICI slice (contiguous blocks) "
                        "on a multi-slice pod: the planner prices "
                        "intra-slice edges at torus-hop ICI cost and "
                        "cross-slice edges at the DCN weight, and a "
                        "planned/forced 'hierarchical' topology adopts "
                        "this slice decomposition; unset = uniform fabric")
    p.add_argument("--dcn_cost", default=None, type=float,
                   help="relative per-byte cost of one inter-slice (DCN) "
                        "message (ICI hop = 1.0; default 16 when any "
                        "fabric flag is set); calibrate with bench.py "
                        "--gossip-vs-ar on real slices")
    p.add_argument("--ici_cost", default=None, type=float,
                   help="relative per-byte cost of one intra-slice ICI "
                        "torus hop (default 1.0)")
    p.add_argument("--mixing_alpha", default=None, type=str,
                   help="SelfWeightedMixing self-mass: 'auto' co-"
                        "optimizes alpha against the chosen topology "
                        "(planner scalar search); a float in (0,1) "
                        "forces it (with a warning when co-optimization "
                        "would recover >10%% of the gap); unset = "
                        "uniform mixing")
    # resilience
    p.add_argument("--inject_faults", default=None, type=str,
                   help="deterministic fault injection at the gossip "
                        "boundary (resilience/faults.py grammar, e.g. "
                        "'drop:0->1@10:40;straggler:3@20:30;seed:7'); "
                        "mass-conserving drop semantics, push-sum "
                        "synchronous mode only")
    p.add_argument("--residual_floor", default=0.01, type=float,
                   help="consensus-residual level above which recovery "
                        "fires an immediate exact global average "
                        "(requires --health_every > 0)")
    p.add_argument("--heartbeat_timeout", default=300, type=int,
                   help="seconds a blocking step or metrics fetch may "
                        "take before the watchdog logs a stall (a dead "
                        "peer host shows up as a hung collective; 0 "
                        "disables; ≙ the gossip flag timeout, "
                        "distributed.py:36)")
    # optimizer and run shape
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--nesterov", default="False", type=str)
    p.add_argument("--warmup", default="False", type=str)
    p.add_argument("--seed", default=47, type=int)
    p.add_argument("--resume", default="False", type=str)
    p.add_argument("--print_freq", default=10, type=int)
    p.add_argument("--checkpoint_dir", type=str, default="./checkpoints")
    p.add_argument("--trace_dir", default=None, type=str,
                   help="run telemetry directory (telemetry/): writes "
                        "trace.json (Chrome-trace host spans: data "
                        "fetch, compiled step, checkpoint, eval, "
                        "recovery averages) and events.jsonl (typed "
                        "plan/health/recovery/comm events, one "
                        "versioned schema); analyze with "
                        "scripts/obsreport.py.  Unset = telemetry off "
                        "(zero overhead)")
    add_profile_flags(p)
    add_fleet_flags(p)
    # multi-host rendezvous
    p.add_argument("--multihost", default="auto",
                   choices=["auto", "True", "False"],
                   help="join a multi-host cluster via "
                        "jax.distributed.initialize; 'auto' joins when "
                        "SLURM/coordinator env vars are present or on a "
                        "TPU pod slice "
                        "(≙ dist.init_process_group, gossip_sgd.py:671-673)")
    p.add_argument("--coordinator_address", default=None, type=str,
                   help="host:port of process 0 (multi-host rendezvous)")
    p.add_argument("--num_processes", default=None, type=int)
    p.add_argument("--process_id", default=None, type=int)


def resolve_shared_flags(args) -> None:
    """Normalize and check, in place, what :func:`add_shared_flags`
    declares (plus --health_every/--metrics_every, which both parsers
    have) — one set of error texts for both CLIs, before any device
    work.  ``--bilat`` is the LM CLI's; the AD-PSGD image CLI selects
    its mode after parsing."""
    all_reduce = _str_bool(args.all_reduce)
    not_push_sum = (all_reduce or _str_bool(getattr(args, "bilat", False))
                    or not _str_bool(args.push_sum))
    resolve_wire_flags(args)
    resolve_kernel_flag(args)
    resolve_staleness_flag(args, _str_bool(args.overlap))
    if not_push_sum:
        reject_push_sum_wire_knobs(args)
    args.mixing_alpha = _parse_mixing_alpha(args.mixing_alpha)
    if args.mixing_alpha is not None and (
            all_reduce or not _str_bool(args.push_sum)):
        raise SystemExit("--mixing_alpha needs push-sum gossip: AllReduce "
                         "doesn't mix, and D-PSGD requires a regular "
                         "(doubly-stochastic) schedule")
    if args.inject_faults:
        if not_push_sum:
            raise SystemExit("--inject_faults needs push-sum gossip: only "
                             "push-sum's mass accounting keeps the mean "
                             "exact under dropped edges")
        # overlap composes with faults (masks are keyed on the LAUNCH
        # tick); fail bad specs at parse time, not at first compiled step
        from ..resilience import parse_fault_spec

        parse_fault_spec(args.inject_faults)
    if args.health_every < 0:
        raise SystemExit("--health_every must be >= 0")
    if args.metrics_every < 0:
        raise SystemExit("--metrics_every must be >= 0")
    if args.metrics_every and not args.trace_dir:
        raise SystemExit("--metrics_every needs --trace_dir (telemetry "
                         "events have nowhere to go without it)")
    resolve_fleet_flags(args)
    resolve_profile_flags(args)


def plan_gossip(args, gossip_world: int, *, mode: str, ppi: int,
                graph_class, overlap: bool, log, registry=None):
    """The launch-time topology policy (planner/) for either CLI:
    ``(plan, interconnect)``, both None when ``mode`` (one of
    ``algorithms.GOSSIP_MODES``) or a single-rank world has no gossip
    schedule to plan — planner flags are then an error, not ignored.

    Auto mode picks (and tunes) the graph; forced mode measures the
    user's choice and warns loudly when its gap is below the floor.  The
    chosen plan is logged as one JSON line (via the telemetry registry
    when one exists).  Pure numpy: runs before any mesh/device work.
    """
    fabric_flags = (args.slice_size is not None
                    or args.dcn_cost is not None
                    or args.ici_cost is not None)
    synth = synth_plan_config(args)   # rejects stray --synth_* knobs
    if mode not in ("sgp", "dpsgd") or gossip_world < 2:
        if args.topology in ("auto", "synth") \
                or args.mixing_alpha is not None or fabric_flags \
                or synth is not None:
            raise SystemExit("--topology auto/synth / --mixing_alpha / "
                             "fabric flags (--slice_size/--dcn_cost/"
                             "--ici_cost) plan gossip schedules; they do "
                             "not apply to all_reduce/bilateral modes or "
                             "a single-rank world")
        return None, None
    from ..planner import make_interconnect, resolve_topology

    interconnect = make_interconnect(args.slice_size, args.dcn_cost,
                                     args.ici_cost)
    plan = resolve_topology(
        gossip_world, ppi=ppi, topology=args.topology,
        graph_class=graph_class, floor=args.gap_floor, algorithm=mode,
        self_weighted=(True if args.mixing_alpha == "auto"
                       else (args.mixing_alpha or False)),
        global_avg_every=args.global_avg_every,  # None = policy decides
        interconnect=interconnect,
        overlap=overlap, faults=bool(args.inject_faults),
        wire=wire_plan_config(args), synth=synth,
        log=log, registry=registry)
    return plan, interconnect


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Gossip SGD on TPU")
    add_shared_flags(p)
    # reference flag surface (gossip_sgd.py:72-159)
    p.add_argument("--batch_size", default=32, type=int,
                   help="per-agent batch size")
    p.add_argument("--lr", default=0.1, type=float,
                   help="reference lr for a 256-sample global batch")
    p.add_argument("--num_dataloader_workers", default=8, type=int,
                   help="decode worker threads for the imagefolder "
                        "streaming loader (synthetic data ignores this)")
    p.add_argument("--prefetch", default="False", type=str,
                   help="overlap host->device batch transfer with the "
                        "previous step (data/prefetch.py; single-process "
                        "non-scanned runs)")
    p.add_argument("--data_backend", default="auto",
                   choices=["auto", "native", "pil"],
                   help="imagefolder decode path: the native C++ pipeline "
                        "(libjpeg + GIL-free thread pool), pure-PIL, or "
                        "auto (native when it builds)")
    p.add_argument("--stem_s2d", default="False",
                   help="space-to-depth ResNet stem (MLPerf TPU trick): "
                        "equivalent 4x4/1 conv over 2x2-packed input in "
                        "place of the 7x7/2 stem; better MXU tiling")
    p.add_argument("--data_output", default="f32",
                   choices=["f32", "uint8"],
                   help="loader output: host-normalized float32, or raw "
                        "uint8 pixels normalized on device (4x smaller "
                        "host-to-device transfer)")
    p.add_argument("--num_epochs", default=90, type=int)
    p.add_argument("--num_iterations_per_training_epoch", default=None,
                   type=int, help="early exit for testing")
    p.add_argument("--weight_decay", default=1e-4, type=float)
    p.add_argument("--health_every", default=0, type=int,
                   help="emit a structured 'gossip health:' line every k "
                        "steps (ps-weight drift, push-sum mass error, "
                        "NaN guards, consensus residual, step-time "
                        "p50/p99); excursions log immediately and arm "
                        "the recovery policy; 0 disables")
    p.add_argument("--mixing_strategy", default=0, type=int,
                   choices=list(MIXING_STRATEGIES))
    p.add_argument("--schedule", nargs="+", default=[30, 0.1, 60, 0.1, 80, 0.1],
                   type=float, help="lr schedule as epoch value pairs")
    p.add_argument("--peers_per_itr_schedule", nargs="+", type=int,
                   default=None)
    p.add_argument("--synch_freq", default=0, type=int,
                   help="overlap-mode staleness bound: in-flight gossip is "
                        "consumed synch_freq+1 steps after launch "
                        "(reference semantics: up to N non-blocking polls, "
                        "distributed.py:127-129)")
    p.add_argument("--cosine_lr", default="False", type=str,
                   help="cosine LR decay instead of the step schedule")
    p.add_argument("--label_smoothing", default=0.0, type=float)
    p.add_argument("--grad_accum", default=1, type=int,
                   help="microbatches accumulated per optimizer step")
    p.add_argument("--backend", default="xla",
                   choices=["xla", "nccl", "gloo", "mpi"],
                   help="accepted for compatibility; comm is XLA/ICI")
    p.add_argument("--tag", default="", type=str)
    p.add_argument("--verbose", default="True", type=str)
    p.add_argument("--train_fast", default="False", type=str)
    p.add_argument("--checkpoint_all", default="True", type=str)
    p.add_argument("--overwrite_checkpoints", default="True", type=str)
    p.add_argument("--master_port", default="40100", type=str,
                   help="accepted for compatibility; unused")
    p.add_argument("--network_interface_type", default="infiniband",
                   choices=["infiniband", "ethernet"],
                   help="accepted for compatibility; unused")
    p.add_argument("--num_itr_ignore", type=int, default=10)
    p.add_argument("--dataset_dir", type=str, default=None)
    p.add_argument("--no_cuda_streams", action="store_true",
                   help="accepted for compatibility; unused")
    # TPU-native additions
    p.add_argument("--world_size", default=None, type=int,
                   help="gossip ranks (default: all devices)")
    p.add_argument("--nprocs_per_node", default=1, type=int,
                   help="local mesh axis for hierarchical gossip")
    p.add_argument("--model", default="resnet50", type=str)
    p.add_argument("--dataset", default="imagefolder",
                   choices=["imagefolder", "synthetic"])
    p.add_argument("--image_size", default=224, type=int)
    p.add_argument("--num_classes", default=1000, type=int)
    p.add_argument("--synthetic_samples", default=None, type=int)
    p.add_argument("--requeue_command", default=None, type=str,
                   help="command run by rank 0 on preemption requeue")
    p.add_argument("--precision", default="fp32",
                   choices=["fp32", "bf16"],
                   help="compute dtype (params and BN stats stay fp32)")
    p.add_argument("--scan_steps", default=1, type=int,
                   help="fuse this many iterations into one compiled "
                        "program (dispatch amortization on TPU)")
    p.add_argument("--per_rank_csv", default="False", type=str,
                   help="emit one CSV per gossip rank (reference parity) "
                        "instead of a single rank-averaged file")
    p.add_argument("--ckpt_backend", default="msgpack",
                   choices=["msgpack", "orbax"],
                   help="checkpoint serialization backend")
    p.add_argument("--metrics_every", default=0, type=int,
                   help="emit a step_stats + comm telemetry event "
                        "every k steps (0 = only the final comm "
                        "snapshot); requires --trace_dir")
    return p


def _parse_pair_schedule(flat, value_type=float) -> dict:
    """epoch/value flat list → dict (gossip_sgd.py:624-649)."""
    if len(flat) % 2:
        raise SystemExit(
            f"schedule {flat} must be epoch/value pairs (even length)")
    out = {}
    it = iter(flat)
    for epoch in it:
        out[int(epoch)] = value_type(next(it))
    return out


def parse_config(argv=None):
    from ..train.loop import TrainerConfig

    args = build_parser().parse_args(argv)
    lr_schedule = _parse_pair_schedule(args.schedule, float)
    ppi_flat = args.peers_per_itr_schedule or [0, 1]
    ppi_schedule = _parse_pair_schedule(ppi_flat, int)
    if 0 not in ppi_schedule:
        raise SystemExit("peers_per_itr_schedule must include epoch 0")
    all_reduce = _str_bool(args.all_reduce)
    resolve_shared_flags(args)
    if all_reduce and args.graph_type != -1:
        raise SystemExit("--all_reduce True requires --graph_type -1")
    if all_reduce and args.topology is not None:
        raise SystemExit("--topology selects a gossip graph; it does not "
                         "apply to --all_reduce True")
    if not all_reduce and args.topology is None \
            and GRAPH_TOPOLOGIES[args.graph_type] is None:
        raise SystemExit("gossip training requires a graph_type >= 0 "
                         "(or --topology)")
    # a forced name overrides the integer registry; 'auto' is resolved in
    # main() once the world size is known (planner.resolve_topology)
    graph_class = GRAPH_TOPOLOGIES[args.graph_type]
    if args.topology not in (None, "auto"):
        graph_class = TOPOLOGY_NAMES[args.topology]

    cfg = TrainerConfig(
        all_reduce=all_reduce,
        push_sum=_str_bool(args.push_sum),
        overlap=_str_bool(args.overlap),
        synch_freq=args.synch_freq,
        staleness=args.staleness,
        bilat=getattr(args, "bilat", False),
        graph_class=graph_class,
        mixing_class=MIXING_STRATEGIES[args.mixing_strategy],
        ppi_schedule=ppi_schedule,
        lr=args.lr,
        momentum=args.momentum,
        weight_decay=args.weight_decay,
        nesterov=_str_bool(args.nesterov),
        lr_schedule=lr_schedule,
        warmup=_str_bool(args.warmup),
        batch_size=args.batch_size,
        num_epochs=args.num_epochs,
        num_iterations_per_training_epoch=(
            args.num_iterations_per_training_epoch),
        seed=args.seed,
        num_itr_ignore=args.num_itr_ignore,
        print_freq=args.print_freq,
        train_fast=_str_bool(args.train_fast),
        verbose=_str_bool(args.verbose),
        checkpoint_dir=args.checkpoint_dir,
        tag=args.tag,
        resume=_str_bool(args.resume),
        checkpoint_all=_str_bool(args.checkpoint_all),
        overwrite_checkpoints=_str_bool(args.overwrite_checkpoints),
        num_classes=args.num_classes,
        scan_steps=args.scan_steps,
        num_dataloader_workers=args.num_dataloader_workers,
        prefetch=_str_bool(args.prefetch),
        gossip_every=args.gossip_every,
        cosine_lr=_str_bool(args.cosine_lr),
        label_smoothing=args.label_smoothing,
        grad_accum=args.grad_accum,
        wire_dtype=args.wire_dtype,
        wire_block=args.wire_block,
        error_feedback=bool(args.error_feedback),
        gossip_kernel=args.gossip_kernel,
        gossip_buckets=args.gossip_buckets,
        per_rank_csv=_str_bool(args.per_rank_csv),
        heartbeat_timeout=args.heartbeat_timeout,
        global_avg_every=args.global_avg_every or 0,
        inject_faults=args.inject_faults,
        health_every=args.health_every,
        residual_floor=args.residual_floor,
        trace_dir=args.trace_dir,
        metrics_every=args.metrics_every,
        profile_dir=args.profile_dir,
        profile_start_step=args.profile_start_step,
        profile_steps=args.profile_steps,
        fleet=bool(args.fleet),
        host_id=args.host_id,
    )
    return cfg, args


def _parse_mixing_alpha(v):
    """--mixing_alpha: None, 'auto' (co-optimize), or a float in (0,1)."""
    if v is None:
        return None
    if v == "auto":
        return "auto"
    try:
        alpha = float(v)
    except ValueError:
        raise SystemExit(f"--mixing_alpha must be 'auto' or a float in "
                         f"(0, 1), got {v!r}")
    if not 0.0 < alpha < 1.0:
        raise SystemExit(f"--mixing_alpha {alpha} outside (0, 1)")
    return alpha


def _resolve_plan(cfg, args, gossip_world: int, log, registry=None):
    """Apply the launch-time topology policy (:func:`plan_gossip`) to
    ``cfg``: the planned graph, mixing and periodic averaging replace the
    flags' own, and the plan is stamped into ``cfg.plan`` (and from there
    into checkpoint metadata)."""
    from ..algorithms import gossip_mode
    from ..train.lr import ppi_at_epoch

    # plan for the epoch-0 peers_per_itr (a ppi schedule can change it
    # later; the stamped plan records which value was planned for)
    plan, _ = plan_gossip(
        args, gossip_world,
        mode=gossip_mode(all_reduce=cfg.all_reduce, push_sum=cfg.push_sum,
                         bilat=cfg.bilat, bilat_async=cfg.bilat_async),
        ppi=ppi_at_epoch(cfg.ppi_schedule, 0), graph_class=cfg.graph_class,
        overlap=cfg.overlap, log=log, registry=registry)
    if plan is None:
        return
    cfg.graph_class = plan.graph_class
    if plan.alpha is not None:
        from ..topology import SelfWeightedMixing

        cfg.mixing_class = lambda a=plan.alpha: SelfWeightedMixing(a)
    cfg.global_avg_every = plan.global_avg_every
    cfg.plan = plan.to_dict()


def main(argv=None, config_transform=None, extra_args=None):
    from ..utils.compile_cache import place_compile_cache

    place_compile_cache()     # and arms the set-up ledger
    from ..telemetry import make_run_telemetry, setup_phase

    with setup_phase("parse"):
        cfg, args = parse_config(argv)
        if extra_args:
            for k, v in extra_args.items():
                setattr(args, k, v)
        if config_transform is not None:
            cfg = config_transform(cfg, args)

    import jax

    # multi-host rendezvous BEFORE any other jax use (≙ the reference's
    # dist.init_process_group placement, gossip_sgd.py:671-673)
    want_mh = getattr(args, "multihost", "auto")
    if want_mh == "True" or (want_mh == "auto" and _multihost_env()):
        from ..parallel.discovery import initialize_multihost

        with setup_phase("mesh"):
            initialize_multihost(args.coordinator_address,
                                 args.num_processes, args.process_id)

    from ..data import (DistributedSampler, ShardedLoader,
                        StreamingImageFolder, synthetic_classification)
    from ..models import RESNETS, TinyCNN
    from ..parallel import make_gossip_mesh, make_hierarchical_mesh
    from ..train.loop import Trainer
    from ..utils import make_logger
    from ..utils.checkpoint import ClusterManager

    log = make_logger("main", cfg.verbose)
    # the devices come up here (the first question put to the backend)
    with setup_phase("mesh"):
        world = args.world_size or jax.device_count()
        proc_index = jax.process_index()

    # run telemetry BEFORE planning, so the planner's `plan` event and
    # the train loop share one events.jsonl (the null bundle when no
    # --trace_dir)
    telemetry = make_run_telemetry(cfg.trace_dir, rank=proc_index, log=log,
                                   metrics_every=cfg.metrics_every)

    # launch-time topology policy BEFORE any mesh/device work: planning is
    # pure numpy, and a below-floor warning must reach the user even when
    # the launch subsequently fails.  Gossip ranks live on the node axis
    # of a hierarchical mesh, so that's the world the mixing analysis sees
    gossip_world = (world // args.nprocs_per_node
                    if args.nprocs_per_node > 1 else world)
    with setup_phase("plan"):
        _resolve_plan(cfg, args, gossip_world, log,
                      registry=telemetry.registry)

    with setup_phase("mesh"):
        if args.nprocs_per_node > 1:
            cfg.nprocs_per_node = args.nprocs_per_node
            mesh = make_hierarchical_mesh(args.nprocs_per_node, world)
        else:
            mesh = make_gossip_mesh(world)
    log.info(f"mesh: {mesh}; devices: {world}")

    proc_count = jax.process_count()
    if proc_count > 1:
        if not cfg.checkpoint_all:
            # every process holds *different* ranks; funnelling them into
            # one rank-0 file would interleave writers and corrupt it
            raise SystemExit(
                "--checkpoint_all False is single-process only: on a pod "
                "each process must write its own checkpoint file")
        from ..parallel.multihost import owned_batch_rows

        # loaders feed one row per local DEVICE (mesh-flat order); the
        # Trainer separately derives its gossip-rank ownership (node ranks
        # on a hierarchical mesh)
        local_ranks = owned_batch_rows(mesh)
        log.info(f"process {proc_index}/{proc_count}: feeding batch rows "
                 f"{local_ranks}")
    else:
        local_ranks = None

    import jax.numpy as jnp

    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    with setup_phase("model"):
        if args.model in RESNETS:
            model = RESNETS[args.model](
                num_classes=cfg.num_classes, dtype=dtype,
                stem_s2d=_str_bool(args.stem_s2d))
        elif args.model == "tiny_cnn":
            model = TinyCNN(num_classes=cfg.num_classes, dtype=dtype)
        else:
            raise SystemExit(f"unknown model {args.model}")

    with setup_phase("data"):
        if args.dataset == "synthetic":
            n = args.synthetic_samples or world * cfg.batch_size * 8
            n_val = max(world * cfg.batch_size, n // 8)
            # one draw, then split: train and val share class structure
            all_images, all_labels = synthetic_classification(
                n + n_val, num_classes=cfg.num_classes,
                image_size=args.image_size, seed=cfg.seed)
            images, labels = all_images[:n], all_labels[:n]
            val_images, val_labels = all_images[n:], all_labels[n:]
            sampler = DistributedSampler(len(images), world)
            loader = ShardedLoader(images, labels, cfg.batch_size, sampler,
                                   ranks=local_ranks)
        else:
            if not args.dataset_dir:
                raise SystemExit("--dataset_dir required for imagefolder")
            # both splits stream with background decode; val never needs the
            # whole split resident in host memory
            workers = args.num_dataloader_workers or 8
            loader = StreamingImageFolder(
                args.dataset_dir, "train", world, cfg.batch_size,
                image_size=args.image_size, train=True,
                num_workers=workers, seed=cfg.seed, ranks=local_ranks,
                backend=args.data_backend, output=args.data_output)
            sampler = loader  # owns set_epoch for both sampling and augment
            val_loader = StreamingImageFolder(
                args.dataset_dir, "val", world, cfg.batch_size,
                image_size=args.image_size, train=False, num_workers=workers,
                ranks=local_ranks, backend=args.data_backend,
                output=args.data_output)

        if args.dataset == "synthetic":
            val_sampler = DistributedSampler(len(val_images), world)
            val_loader = ShardedLoader(val_images, val_labels, cfg.batch_size,
                                       val_sampler, ranks=local_ranks)

    ckpt = _make_ckpt_manager(args, cfg, world, proc_index)
    cluster = ClusterManager(ckpt, rank=proc_index,
                             requeue_command=args.requeue_command or
                             _default_requeue())

    channels = images.shape[-1] if args.dataset == "synthetic" else 3
    trainer = Trainer(cfg, model, mesh,
                      sample_input_shape=(
                          cfg.batch_size, args.image_size, args.image_size,
                          channels),
                      cluster_manager=cluster, telemetry=telemetry)
    state = trainer.init_state()
    state, result = trainer.fit(state, loader, sampler, val_loader)
    if hasattr(ckpt, "wait"):
        ckpt.wait()  # async backends: land in-flight saves before exit
    log.info(f"done: {result['best_prec1']:.3f} best top-1, "
             f"elapsed {result['elapsed_time']:.1f}s")
    return result


def _make_ckpt_manager(args, cfg, world: int, proc_index: int):
    """Select the checkpoint backend (--ckpt_backend): the self-contained
    msgpack manager, or orbax (async saves + retention GC) for big jobs."""
    if getattr(args, "ckpt_backend", "msgpack") == "orbax":
        from ..utils.orbax_ckpt import OrbaxCheckpointManager

        return OrbaxCheckpointManager(
            cfg.checkpoint_dir, tag=cfg.tag, rank=proc_index,
            world_size=world, all_workers=cfg.checkpoint_all)
    from ..utils.checkpoint import CheckpointManager

    return CheckpointManager(cfg.checkpoint_dir, tag=cfg.tag,
                             rank=proc_index, world_size=world,
                             all_workers=cfg.checkpoint_all)


def _multihost_env() -> bool:
    """Join a cluster when launched by SLURM with >1 task, when an
    explicit coordinator is configured (gossip_sgd.py:599-605), or on a
    Cloud TPU pod slice (>1 worker hostname in the VM metadata env)."""
    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        return True
    if "," in os.environ.get("TPU_WORKER_HOSTNAMES", ""):
        return True
    try:
        if int(os.environ.get("SLURM_NTASKS", "1")) > 1:
            return True
        # OpenMPI launcher (reference --backend mpi, gossip_sgd.py:600-602)
        return int(os.environ.get(
            "OMPI_COMM_WORLD_SIZE",
            os.environ.get("OMPI_UNIVERSE_SIZE", "1"))) > 1
    except ValueError:
        return False


def _default_requeue() -> str | None:
    if os.environ.get("SGP_SUPERVISED") == "1":
        # the run supervisor (supervise/) owns the relaunch decision —
        # requeueing from inside the child would race it
        return None
    job_id = os.environ.get("SLURM_JOB_ID")
    return f"scontrol requeue {job_id}" if job_id else None


if __name__ == "__main__":
    main()
