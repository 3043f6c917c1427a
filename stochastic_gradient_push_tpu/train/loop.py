"""The experiment loop: epochs, meters, CSV logging, validation, resume.

Port of the reference harness's control flow (gossip_sgd.py:163-471) minus
everything that was only there to manage host-side distribution (process
groups, barriers, NIC pinning).  The CSV schema is byte-compatible with the
reference (header at gossip_sgd.py:262-274, rows at :408-418, :318-327) so
the reference's plotting layer parses these logs unchanged.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import typing as tp

import jax
import numpy as np

from jax.sharding import PartitionSpec as P

from ..algorithms import GossipAlgorithm, gossip_algorithm, gossip_mode
from ..parallel.mesh import GOSSIP_AXIS, LOCAL_AXIS, NODE_AXIS
from ..parallel.multihost import (
    global_state_from_local,
    host_local_slice,
    make_global_batch,
    owned_ranks,
    to_host,
)
from ..telemetry.setup_ledger import first_step, setup_phase
from ..topology import build_pairing_schedule
from ..utils import Meter, make_logger
from ..utils.checkpoint import REQUEUE_EXIT_CODE, ClusterManager
from ..utils.profiling import ProfileWindow, StepWatchdog
from .lr import CosineLRSchedule, LRSchedule, ppi_at_epoch
from .state import init_train_state, sgd
from .step import (
    build_eval_step,
    build_train_step,
    replica_spread,
    replicate_state,
    shard_eval_step,
    shard_scanned_train_step,
    shard_train_step,
)

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    """Experiment configuration (≙ the reference CLI surface,
    gossip_sgd.py:72-159)."""

    # algorithm selection
    all_reduce: bool = False
    push_sum: bool = True
    overlap: bool = False
    # bounded staleness for overlap mode: in-flight gossip is consumed
    # synch_freq+1 steps after launch (≙ synch_freq, distributed.py:127-129)
    synch_freq: int = 0
    # first-class spelling of the overlap staleness bound: the FIFO depth
    # (a share launched at step t is consumed at step t+staleness−1;
    # staleness 1 = same-step consume, the ppermute hidden behind this
    # step's compute).  0 = derive from synch_freq (synch_freq + 1)
    staleness: int = 0
    # gossip on every k-th step (communication thinning; composes with
    # overlap — non-firing steps launch nothing)
    gossip_every: int = 1
    # exact global average (one allreduce) every k-th step, 0 = off —
    # the periodic-global-averaging recovery the planner emits for
    # topologies whose spectral gap is below the floor (planner/policy.py)
    global_avg_every: int = 0
    # launch-time topology plan (planner.Plan.to_dict()); logged at
    # startup and stamped into checkpoint metadata for reproducibility
    plan: dict | None = None
    # gossip wire codec (parallel/wire.py): None/"f32" = exact leaf
    # dtype, "bf16" halves the wire, "int8" is symmetric per-block
    # quantization at wire_block elements per f32 scale (~3.8x smaller)
    wire_dtype: str | None = None
    wire_block: int = 64
    # per-rank error-feedback residual accumulators: re-inject round t's
    # quantization error into round t+1's send so compression noise is a
    # bounded perturbation, not a bias (requires a lossy wire_dtype)
    error_feedback: bool = False
    # gossip transport lane (ops/gossip_kernel.py): "pallas" fuses each
    # edge exchange into one remote-DMA kernel (in-VMEM wire decode +
    # mixing axpy; TPU only — a typed KernelBackendError elsewhere),
    # "auto" picks pallas on TPU.  Default "xla" (ppermute+decode): the
    # kernel pair runs on four chips and matches the xla lane bit for
    # bit per round (chip_smoke.py --chips 4), but its speed is not
    # measured — the default is decided by ROADMAP S4.  Overlap rounds ride
    # the kernel lane first-class: the split start/wait transport
    # launches the remote DMA at the top of the step and lands it at
    # the bottom, so compute actually hides the wire
    gossip_kernel: str = "xla"
    # kernel-lane transport pipelining: partition the payload into this
    # many contiguous byte-bounded buckets, one start/wait kernel
    # program per bucket (own collective_id slot), so later buckets'
    # DMAs overlap earlier buckets' decode.  1 = one program for the
    # whole payload; never changes bytes or math (parity-pinned)
    gossip_buckets: int = 1
    bilat: bool = False                       # AD-PSGD family
    # AD-PSGD with REAL wall-clock asynchrony: the compiled step carries
    # no collective; a host thread averages bilaterally off the hot path
    # and the loop adopts stale displacements (train/async_bilat.py,
    # ≙ the reference's separate averaging process, ad_psgd.py:120-133).
    # Single-process meshes only.  Implies/overrides ``bilat``.
    bilat_async: bool = False
    # minimum seconds between host averaging rounds (0 = unpaced, like
    # the reference); raising it widens the measured staleness
    bilat_async_interval: float = 0.0
    graph_class: tp.Any = None                # GraphTopology subclass
    mixing_class: tp.Any = None               # MixingStrategy subclass
    ppi_schedule: dict[int, int] = dataclasses.field(
        default_factory=lambda: {0: 1})

    # optimization
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    nesterov: bool = False
    lr_schedule: dict[int, float] = dataclasses.field(
        default_factory=lambda: {30: 0.1, 60: 0.1, 80: 0.1})
    warmup: bool = False
    cosine_lr: bool = False                   # cosine decay instead of steps
    label_smoothing: float = 0.0
    grad_accum: int = 1

    # run shape
    batch_size: int = 32                      # per-rank
    num_epochs: int = 90
    num_iterations_per_training_epoch: int | None = None
    seed: int = 47
    num_itr_ignore: int = 10
    print_freq: int = 10
    train_fast: bool = False
    verbose: bool = True

    # io
    checkpoint_dir: str = "./checkpoints"
    # telemetry (telemetry/): when set, the run writes <trace_dir>/
    # trace.json (Chrome-trace host spans: data fetch, compiled step,
    # checkpoint, eval, recovery averages) and <trace_dir>/events.jsonl
    # (typed plan/health/recovery/comm/step_stats events under one
    # versioned schema); None disables the subsystem entirely — the
    # loop then runs the zero-overhead null telemetry (no extra clock
    # reads, allocations, or device syncs; pinned by test)
    trace_dir: str | None = None
    # emit a step_stats + comm event every k steps (0 = only the final
    # comm snapshot at exit); requires trace_dir
    metrics_every: int = 0
    # step-indexed jax.profiler capture (utils/profiling.ProfileWindow):
    # when set, global steps [profile_start_step, profile_start_step +
    # profile_steps) are captured as a TensorBoard XPlane dump under
    # profile_dir.  One-shot and guarded: a profiler start that hangs or
    # fails is logged as an error and abandons the window instead of
    # stalling the run.  The dump path is
    # stamped into run_meta so obsreport/fleetmon can point at it
    profile_dir: str | None = None
    profile_start_step: int = 2
    profile_steps: int = 3
    tag: str = ""
    resume: bool = False
    checkpoint_all: bool = True
    overwrite_checkpoints: bool = True
    # fleet supervision (supervise/coordinator.py): this process is one
    # host of a coordinated pod.  The pod coordinator owns the restart
    # boundary — it assigns each survivor its out_rank/out_rows shard of
    # the cross-world reshard — so the per-host auto-reshard on resume
    # is DISABLED (concurrent per-host reshards with default out_rank 0
    # would race each other: the relaunch storm fleet mode prevents)
    fleet: bool = False
    host_id: int | None = None

    num_classes: int = 1000
    # hierarchical gossip: exact psum averaging inside a node, gossip
    # between nodes (≙ nprocs_per_node, distributed.py:62-78)
    nprocs_per_node: int = 1
    # fuse this many iterations into one compiled program (lax.scan);
    # per-iteration metrics are still logged from the stacked outputs
    scan_steps: int = 1
    # decode workers for streaming loaders (reported in the CSV preamble)
    num_dataloader_workers: int = 0
    # overlap host->device batch transfer with the previous step's compute
    # (data/prefetch.py).  Single-process, non-scanned path only —
    # elsewhere it logs once and stays off.  Measured on chip before any
    # default change (docs/MFU_ANALYSIS.md round-5 prefetch probe).
    prefetch: bool = False
    prefetch_depth: int = 2
    # heartbeat: log loudly when a blocking step exceeds this many seconds
    # (a stalled multi-host collective; ≙ distributed.py:36); 0 disables
    heartbeat_timeout: int = 300
    # emit one CSV per gossip rank with that rank's metrics (the
    # reference's per-process files); off = one rank-averaged out_r0 file
    per_rank_csv: bool = False

    # -- resilience (resilience/) -----------------------------------------
    # deterministic fault injection at the gossip mixing boundary
    # (resilience/faults.py spec grammar, e.g. "drop:0->1@10:40");
    # push-sum sync mode only, mass-conserving drop semantics
    inject_faults: str | None = None
    # consensus health telemetry cadence: compute in-step health signals
    # and emit a structured `gossip health:` line every k steps (plus
    # immediately on any excursion); 0 disables monitoring entirely
    health_every: int = 0
    # consensus-residual level (RMS over the de-biased probe slice) above
    # which the recovery policy fires an immediate exact global average
    residual_floor: float = 0.01


class Trainer:
    """Drives training of ``model`` over ``mesh`` with the configured
    decentralized algorithm."""

    def __init__(self, config: TrainerConfig, model, mesh,
                 sample_input_shape: tuple[int, ...],
                 cluster_manager: ClusterManager | None = None,
                 telemetry=None):
        self.cfg = config
        self.model = model
        self.mesh = mesh
        self.world_size = mesh.devices.size      # data/LR world (all devices)
        if config.nprocs_per_node > 1:
            if mesh.shape.get(LOCAL_AXIS) != config.nprocs_per_node:
                raise ValueError(
                    f"nprocs_per_node={config.nprocs_per_node} requires a "
                    f"hierarchical mesh with a '{LOCAL_AXIS}' axis of that "
                    f"size; got {mesh}")
            self.gossip_axis = NODE_AXIS
            self.local_axis = LOCAL_AXIS
            self.gossip_world = mesh.shape[NODE_AXIS]
        else:
            self.gossip_axis = GOSSIP_AXIS
            self.local_axis = None
            self.gossip_world = self.world_size
        # multi-host: this process feeds/owns only the gossip ranks whose
        # devices it holds (one process per host on a pod slice,
        # ≙ the reference's one-process-per-GPU layout, gossip_sgd.py:586-690)
        self.proc_count = jax.process_count()
        self.proc_index = jax.process_index()
        if self.proc_count > 1:
            # works for the flat gossip mesh AND the hierarchical
            # (node, local) mesh: ranks are indices along the gossip axis
            # (node ranks when hierarchical), and owned_ranks verifies no
            # rank straddles hosts
            self.local_ranks = owned_ranks(mesh, self.gossip_axis)
        else:
            self.local_ranks = list(range(self.gossip_world))
        self.log = make_logger(f"trainer p{self.proc_index}"
                               if self.proc_count > 1 else "trainer",
                               config.verbose)
        self.cluster = cluster_manager
        self.sample_input_shape = sample_input_shape

        # run telemetry (telemetry/): the CLI passes its already-created
        # bundle (so the planner's `plan` event and the loop share one
        # events.jsonl); library users get one built from the config.
        # Without a trace_dir this is the shared zero-overhead null.
        if telemetry is None:
            from ..telemetry import make_run_telemetry

            telemetry = make_run_telemetry(
                config.trace_dir, rank=self.proc_index, log=self.log,
                metrics_every=config.metrics_every)
        self.telemetry = telemetry

        self.tx = sgd(momentum=config.momentum,
                      weight_decay=config.weight_decay,
                      nesterov=config.nesterov)
        # depends on the config and the world only: a constructed Trainer
        # can compile its step (_train_fn) without fit() having run
        schedule = dict(ref_lr=config.lr, batch_size=config.batch_size,
                        world_size=self.world_size, warmup=config.warmup)
        self.lr_schedule_obj = (
            CosineLRSchedule(total_epochs=config.num_epochs, **schedule)
            if config.cosine_lr else
            LRSchedule(decay_schedule=config.lr_schedule, **schedule))
        self._step_cache: dict[tuple, tp.Callable] = {}
        # (step key, shapes) call counts: the first call compiles, and the
        # second can recompile again because donation turns the host-numpy
        # state of call 1 into device-sharded arrays from call 2 on
        self._warm_counts: dict = {}
        self._eval_fn = None
        self._eval_alg = None
        # heartbeat around the blocking step (≙ the reference's 300s gossip
        # flag timeout, distributed.py:36,349-352): a dead peer host shows
        # up as a hung collective, and silence is the worst failure mode
        self.watchdog = (StepWatchdog(timeout=config.heartbeat_timeout,
                                      rank=self.proc_index,
                                      registry=self.telemetry.registry)
                         if config.heartbeat_timeout > 0 else None)
        # device profiling window around the configured global steps
        # (no-op when profile_dir is unset — zero hot-path cost)
        self.profile = ProfileWindow(config.profile_dir,
                                     start_step=config.profile_start_step,
                                     num_steps=config.profile_steps)
        self._async_bilat = None  # built per-fit when cfg.bilat_async
        self._logged_faults = False
        self._warned_prefetch = False

        # runtime consensus health (resilience/): monitor sees, policy
        # decides, the compiled recovery fn (cached per algorithm) acts
        self.monitor = None
        self.recovery_policy = None
        self._recovery_cache: dict = {}
        if config.health_every > 0:
            from ..resilience import HealthMonitor, RecoveryPolicy

            self.monitor = HealthMonitor(
                health_every=config.health_every,
                residual_floor=config.residual_floor, log=self.log,
                registry=self.telemetry.registry)
            if self._mode() in ("sgp", "dpsgd"):
                # overlap runs recover too: the reactive average folds
                # the in-flight FIFO into Σx/Σw and drains it, so
                # nothing is double-counted (resilience/recovery.py)
                from ..topology import topology_name

                try:
                    topo = topology_name(config.graph_class)
                except KeyError:
                    topo = None
                self.recovery_policy = RecoveryPolicy(
                    world=self.gossip_world,
                    ppi=ppi_at_epoch(config.ppi_schedule, 0),
                    algorithm=self._mode(), topology=topo,
                    residual_floor=config.residual_floor,
                    cooldown_steps=config.health_every, log=self.log,
                    registry=self.telemetry.registry,
                    interconnect=self._plan_interconnect(),
                    faults=bool(config.inject_faults),
                    wire=self.wire_config(),
                    synth=(config.plan.get("synth")
                           if config.plan else None))

        # per-rank files: each process writes its local ranks; the single
        # aggregate file is process 0's job
        self._csv_ranks = (tuple(self.local_ranks) if config.per_rank_csv
                           else ((0,) if self.proc_index == 0 else ()))
        self._fname = lambda r: os.path.join(
            config.checkpoint_dir,
            f"{config.tag}out_r{r}_n{self.world_size}.csv")

    # -- algorithm / step construction ------------------------------------

    def _plan_interconnect(self):
        """Rebuild the fabric cost model stamped into the plan (None on a
        uniform fabric) — comm-lane classification and recovery re-plans
        must price on the same fabric the planner did."""
        if self.cfg.plan and self.cfg.plan.get("interconnect"):
            from ..planner import InterconnectModel

            return InterconnectModel.from_dict(self.cfg.plan["interconnect"])
        return None

    def _wire_codec(self):
        """The wire codec the config names (None = unset); an unknown
        ``wire_dtype`` is a ``ValueError``, never an uncompressed run."""
        from ..parallel.wire import get_codec

        return get_codec(self.cfg.wire_dtype, self.cfg.wire_block)

    def wire_config(self) -> dict | None:
        """JSON-safe wire stamp ({"dtype", "block", "error_feedback"}),
        None when the run gossips exact f32 — what the planner prices on
        and the plan/checkpoint meta record."""
        codec = self._wire_codec()
        if codec is None or not codec.lossy:
            return None
        return {**codec.to_dict(),
                "error_feedback": bool(self.cfg.error_feedback)}

    def _resolve_staleness(self) -> int:
        """The overlap FIFO depth from the first-class ``staleness`` knob
        or the reference-compat ``synch_freq`` alias (staleness =
        synch_freq + 1); conflicting values fail fast."""
        cfg = self.cfg
        if cfg.staleness and cfg.synch_freq \
                and cfg.staleness != cfg.synch_freq + 1:
            raise ValueError(
                f"staleness={cfg.staleness} conflicts with "
                f"synch_freq={cfg.synch_freq} (staleness = synch_freq "
                "+ 1); set one of the two")
        staleness = cfg.staleness or (cfg.synch_freq + 1)
        if staleness < 1:
            raise ValueError("staleness must be >= 1")
        if not cfg.overlap:
            if staleness > 1:
                # the reference likewise only reads synch_freq under
                # overlap (distributed.py:578); accept-and-ignore keeps
                # launch scripts flag-compatible
                self.log.warning(
                    "staleness/synch_freq is ignored without overlap "
                    "mode")
            return 1
        return staleness

    def _mode(self) -> str:
        cfg = self.cfg
        return gossip_mode(all_reduce=cfg.all_reduce, push_sum=cfg.push_sum,
                           bilat=cfg.bilat, bilat_async=cfg.bilat_async)

    def make_algorithm(self, ppi: int) -> GossipAlgorithm:
        cfg = self.cfg
        mode = self._mode()
        log = None
        if cfg.inject_faults and not self._logged_faults:
            # make_algorithm runs once per compiled variant; one fault
            # banner per run is enough
            log, self._logged_faults = self.log, True
        return gossip_algorithm(
            mode, self.gossip_axis, world=self.gossip_world,
            graph_class=cfg.graph_class, peers_per_itr=ppi,
            mixing=cfg.mixing_class() if cfg.mixing_class else None,
            overlap=cfg.overlap,
            staleness=(self._resolve_staleness()
                       if mode in ("sgp", "dpsgd") else 1),
            gossip_every=cfg.gossip_every, wire_dtype=cfg.wire_dtype,
            wire_block=cfg.wire_block, error_feedback=cfg.error_feedback,
            global_avg_every=cfg.global_avg_every,
            inject_faults=cfg.inject_faults,
            gossip_kernel=cfg.gossip_kernel,
            gossip_buckets=cfg.gossip_buckets, log=log)

    def _train_fn(self, ppi: int, itr_per_epoch: int, scan: int = 1):
        """Compiled step for a peers-per-itr value; each distinct
        (ppi, scan) is its own compiled variant (SURVEY.md §7 hard part #2
        — the reference mutates the gossiper in place,
        gossip_sgd.py:497-505)."""
        key = (ppi, itr_per_epoch, scan)
        if key not in self._step_cache:
            # the schedule and the algorithm of this variant
            with setup_phase("plan"):
                alg = self.make_algorithm(ppi)
            step = build_train_step(
                self.model, alg, self.tx, self.lr_schedule_obj,
                itr_per_epoch=itr_per_epoch, num_classes=self.cfg.num_classes,
                local_axis=self.local_axis,
                label_smoothing=self.cfg.label_smoothing,
                grad_accum=self.cfg.grad_accum,
                health_axis=(self.gossip_axis if self.monitor is not None
                             else None))
            lane = getattr(alg, "gossip_kernel", None)
            check_vma = lane is None or not lane.interpret
            if scan > 1:
                fn = shard_scanned_train_step(
                    step, self.mesh, scan, self.gossip_axis,
                    self.local_axis, check_vma=check_vma)
            else:
                fn = shard_train_step(
                    step, self.mesh, self.gossip_axis, self.local_axis,
                    check_vma=check_vma)
            self._step_cache[key] = (alg, fn)
        return self._step_cache[key]

    # -- telemetry ---------------------------------------------------------

    def _setup_telemetry(self, state, itr_per_epoch: int) -> None:
        """Attach the comm accountant for the active configuration and
        emit the run_meta event.  Pure host work, done once per fit."""
        from ..telemetry import (CommModel, encoded_payload_bytes,
                                 tree_payload_bytes)

        cfg = self.cfg
        exact = tree_payload_bytes(state.params, self.gossip_world)
        alg_name = self._mode()
        if alg_name == "all_reduce":
            model = CommModel.for_allreduce(self.gossip_world, exact)
        elif alg_name in ("adpsgd", "bilat_async"):
            model = CommModel.for_bilat(self.gossip_world, exact)
        else:
            # the epoch-0 compiled variant's own algorithm object: its
            # schedule/faults are exactly what the wire will run (the
            # cache entry is reused by the epoch loop, so this costs no
            # extra construction)
            alg = self._train_fn(ppi_at_epoch(cfg.ppi_schedule, 0),
                                 itr_per_epoch)[0]
            # price the ENCODED payload — dtype size plus int8 scale
            # overhead, scalar leaves exempt — exactly what the codec
            # puts on the ppermute (pinned against hand-counts)
            codec = self._wire_codec()
            wire = encoded_payload_bytes(state.params, self.gossip_world,
                                         codec)
            # the fabric model the planner priced on classifies the
            # wire's ICI/DCN lanes too (one source of truth)
            interconnect = self._plan_interconnect()
            model = CommModel.from_schedule(
                alg.schedule, wire, exact_bytes=exact,
                gossip_every=alg.gossip_every,
                global_avg_every=alg.global_avg_every,
                faults=alg.faults, ps_weight=cfg.push_sum,
                interconnect=interconnect, codec=codec,
                error_feedback=cfg.error_feedback,
                overlap=getattr(alg, "overlap", False),
                staleness=getattr(alg, "staleness", 1),
                gossip_kernel=getattr(alg, "transport_kernel_name",
                                      "xla"),
                gossip_buckets=getattr(alg, "gossip_buckets", 1))
        self.telemetry.attach_comm(model)
        meta = {
            "world": self.gossip_world, "algorithm": alg_name,
            "gossip_every": cfg.gossip_every,
            "global_avg_every": cfg.global_avg_every,
            "batch_size": cfg.batch_size,
            "itr_per_epoch": itr_per_epoch,
            "num_epochs": cfg.num_epochs,
            "scan_steps": cfg.scan_steps,
            "comm_model": model.to_dict()}
        if self.profile.enabled:
            # where this run's XPlane dump lands (tooling that reads the
            # run directory can link the profiler capture from run_meta)
            meta["profile_dir"] = self.profile.profile_dir
            meta["profile_window"] = [
                self.profile.start_step,
                self.profile.start_step + self.profile.num_steps]
        if cfg.fleet:
            # fleet supervision: the coordinator's obsreport timeline
            # maps event streams to hosts through this stamp
            meta["fleet"] = True
            meta["host_id"] = (cfg.host_id if cfg.host_id is not None
                               else self.proc_index)
        self.telemetry.registry.emit("run_meta", meta)

    # -- csv logging -------------------------------------------------------

    def _init_csv(self) -> None:
        os.makedirs(self.cfg.checkpoint_dir, exist_ok=True)
        for r in self._csv_ranks:
            if os.path.exists(self._fname(r)):
                continue
            with open(self._fname(r), "w") as f:
                print("BEGIN-TRAINING\n"
                      f"World-Size,{self.world_size}\n"
                      f"Num-DLWorkers,{self.cfg.num_dataloader_workers}\n"
                      f"Batch-Size,{self.cfg.batch_size}\n"
                      "Epoch,itr,BT(s),avg:BT(s),std:BT(s),"
                      "NT(s),avg:NT(s),std:NT(s),"
                      "DT(s),avg:DT(s),std:DT(s),"
                      "Loss,avg:Loss,Prec@1,avg:Prec@1,Prec@5,avg:Prec@5,val",
                      file=f)

    def _log_row(self, epoch, itr, meters, stat_meters) -> None:
        """One training row per CSV; stat_meters[r] carries rank r's
        (losses, top1, top5) Meters (timing is shared: one process
        drives every rank)."""
        bt, nt, dt = meters
        for r in self._csv_ranks:
            losses, top1, top5 = stat_meters[r]
            with open(self._fname(r), "a") as f:
                print(f"{epoch},{itr},{bt},{nt},{dt},"
                      f"{losses.val:.4f},{losses.avg:.4f},"
                      f"{top1.val:.3f},{top1.avg:.3f},"
                      f"{top5.val:.3f},{top5.avg:.3f},-1", file=f)

    def _log_val_row(self, epoch, meters, vals) -> None:
        """vals[r] is rank r's validation top-1 (all equal when only
        the rank-averaged file is written)."""
        bt, nt, dt = meters
        for r in self._csv_ranks:
            with open(self._fname(r), "a") as f:
                print(f"{epoch},-1,{bt},{nt},{dt},-1,-1,-1,-1,-1,-1,"
                      f"{vals[r]}", file=f)

    # -- main entry points -------------------------------------------------

    def init_state(self):
        with setup_phase("state_init"):
            import jax.numpy as jnp
            alg = self.make_algorithm(ppi_at_epoch(self.cfg.ppi_schedule, 0))
            state = init_train_state(
                self.model, jax.random.PRNGKey(self.cfg.seed),
                jnp.zeros(self.sample_input_shape), self.tx, alg)
            if self.proc_count == 1:
                return replicate_state(state, self.gossip_world)
            # every rank starts identical (same seed, gossip_sgd.py:172-175);
            # each process materializes only its local rows and assembles the
            # global sharded state from them
            local = jax.tree.map(
                lambda a: np.broadcast_to(
                    np.asarray(a)[None],
                    (len(self.local_ranks),) + np.shape(a)).copy(),
                state)
            return global_state_from_local(self.mesh, self.gossip_axis, local)

    def fit(self, state, train_loader, sampler,
            val_loader=None) -> tuple[tp.Any, dict]:
        cfg = self.cfg
        if len(train_loader) < 1:
            raise ValueError(
                "train loader yields zero batches: batch_size × world_size "
                "exceeds the dataset size")
        # the compiled schedule derives the epoch from state.step, so the
        # per-epoch iteration count must reflect any early-exit cap or the
        # LR trajectory desynchronizes from the host epoch
        itr_per_epoch = len(train_loader)
        cap = cfg.num_iterations_per_training_epoch
        if cap not in (None, -1):
            itr_per_epoch = min(itr_per_epoch, cap)
        self._init_csv()

        batch_meter = Meter(ptag="Time")
        nn_meter = Meter(ptag="Forward/Backward")
        data_meter = Meter(ptag="Data")
        meters = (batch_meter, nn_meter, data_meter)

        start_epoch, start_itr, best_prec1 = 0, 0, 0.0
        elapsed = 0.0

        want_resume = cfg.resume and self.cluster is not None
        have_ckpt = want_resume and self.cluster.ckpt.exists()
        if want_resume and not have_ckpt:
            # a resized relaunch: another world's checkpoint set may be
            # sitting right there — reshard it instead of cold-starting
            have_ckpt = self._try_cross_world_resume()
        if want_resume and self.proc_count > 1:
            # decide COLLECTIVELY: a per-process exists() gate would hang
            # the cluster when one process's checkpoint is missing/torn
            # (the survivors enter the restore collectives alone)
            from jax.experimental import multihost_utils

            all_have = int(np.min(np.asarray(
                multihost_utils.process_allgather(
                    np.asarray([int(have_ckpt)])))))
            if have_ckpt and not all_have:
                self.log.info("checkpoint present here but missing on a "
                              "peer; starting from epoch 0")
            have_ckpt = bool(all_have)
        if have_ckpt:
            with setup_phase("resume"):
                state, meta = self._restore(state)
            start_epoch = meta.get("epoch", 0)
            start_itr = meta.get("itr", 0)
            if self.proc_count > 1:
                # per-process checkpoints can tear under preemption; every
                # process must agree on the loop counts or the compiled
                # collectives deadlock
                from ..parallel.multihost import consensus_resume_point
                start_epoch, start_itr = consensus_resume_point(
                    start_epoch, start_itr, log=self.log)
            best_prec1 = meta.get("best_prec1", 0.0)
            elapsed = meta.get("elapsed_time", 0.0)
            for m, k in zip(meters, ("batch_meter", "nn_meter",
                                     "data_meter")):
                if k in meta:
                    m.__dict__.update(meta[k])
            self.log.info(f"resumed from epoch {start_epoch} itr {start_itr}")

        begin_time = time.time() - elapsed
        if cfg.bilat_async:
            if self.proc_count > 1:
                raise ValueError(
                    "bilat_async averages on one host thread and is "
                    "single-process only (see train/async_bilat.py)")
            if cfg.graph_class is None:
                raise ValueError("bilat_async needs a graph_class for "
                                 "the matching schedule")
            from .async_bilat import AsyncBilateralAverager

            graph = cfg.graph_class(self.gossip_world, peers_per_itr=1)
            self._async_bilat = AsyncBilateralAverager(
                build_pairing_schedule(graph),
                min_interval_s=cfg.bilat_async_interval).start()
        if self.telemetry.enabled:
            self._setup_telemetry(state, itr_per_epoch)
        try:
            state, best_prec1, final_prec1 = self._fit_epochs(
                state, train_loader, sampler, val_loader, itr_per_epoch,
                meters, start_epoch, start_itr, best_prec1, begin_time)

            if cfg.train_fast and val_loader is not None:
                alg = self._train_fn(
                    ppi_at_epoch(cfg.ppi_schedule, cfg.num_epochs - 1)
                    if not cfg.all_reduce else 1, itr_per_epoch)[0]
                final_prec1 = self.validate(state, alg, val_loader)
                self.log.info(f"Test accuracy: {final_prec1}")
        finally:
            if self._async_bilat is not None:
                self._async_bilat.stop()
                self.log.info("async bilateral staleness: "
                              f"{self._async_bilat.staleness_summary()}")
            # a run that ended inside the capture window still dumps
            # what it got (and never leaves the profiler accumulating)
            self.profile.close()
            # write trace.json + the final comm snapshot whatever path
            # exits fit (idempotent; a crashed run still leaves artifacts)
            self.telemetry.finish()

        result = {"best_prec1": float(best_prec1),
                  "final_prec1": float(final_prec1),
                  "elapsed_time": time.time() - begin_time,
                  "batch_meter": meters[0]}
        if self._async_bilat is not None:
            result["async_bilat"] = self._async_bilat.staleness_summary()
        return state, result

    def _fit_epochs(self, state, train_loader, sampler, val_loader,
                    itr_per_epoch, meters, start_epoch, start_itr,
                    best_prec1, begin_time):
        cfg = self.cfg
        batch_meter, nn_meter, data_meter = meters
        final_prec1 = 0.0
        for epoch in range(start_epoch, cfg.num_epochs):
            sampler.set_epoch(epoch + cfg.seed * 90)  # gossip_sgd.py:289
            ppi = (ppi_at_epoch(cfg.ppi_schedule, epoch)
                   if not cfg.all_reduce else 1)
            alg, _ = self._train_fn(ppi, itr_per_epoch)

            state = self._train_epoch(
                state, ppi, itr_per_epoch, train_loader, epoch, start_itr,
                meters, best_prec1, begin_time)
            start_itr = 0

            if not cfg.train_fast:
                if self.proc_count == 1:
                    spread = replica_spread(state, alg)
                    self.log.info(
                        f"epoch {epoch}: replica spread "
                        f"max {spread['max_spread']:.2e} "
                        f"mean {spread['mean_spread']:.2e}")
                prec1 = (self.validate(state, alg, val_loader)
                         if val_loader is not None else -1.0)
                final_prec1 = prec1
                vals = (self._last_val_per_rank if cfg.per_rank_csv
                        and val_loader is not None
                        else {r: prec1 for r in self._csv_ranks})
                self._log_val_row(epoch, meters, vals)
                is_best = prec1 > best_prec1
                best_prec1 = max(best_prec1, prec1)
                if self.cluster is not None:
                    # flush overlap in-flight shares before the save
                    # barrier: the checkpoint (and the continuing run)
                    # carry nothing in flight, so reshard/resume treat
                    # it like a sync checkpoint
                    state = self._drain_in_flight(state)
                    meta = self._ckpt_meta(epoch + 1, 0, best_prec1,
                                           begin_time, meters)
                    epoch_id = (None if cfg.overwrite_checkpoints else epoch)
                    if epoch != cfg.num_epochs - 1 \
                            and self.cluster.any_rank_signalled():
                        # a signal that arrived during validation: this
                        # save will requeue-exit, so the typed exit
                        # record must be flushed first
                        self._emit_exit_event(
                            "preempt-requeue", epoch + 1, 0,
                            (epoch + 1) * itr_per_epoch)
                    with self.telemetry.span("checkpoint_save",
                                             "checkpoint",
                                             {"epoch": epoch}
                                             if self.telemetry.enabled
                                             else None), \
                            self.profile.span("checkpoint_save"):
                        self.cluster.save_checkpoint(
                            self._save_state(state), meta,
                            epoch_id=epoch_id, is_best=is_best,
                            requeue_on_signal=(epoch != cfg.num_epochs
                                               - 1))

        return state, best_prec1, final_prec1

    def _restore(self, state):
        """Checkpoint restore; multi-host either restores the global
        sharded arrays directly (global-state backends, e.g. orbax) or
        reassembles them from this process's own rank-row file (msgpack)."""
        if self.proc_count == 1 or getattr(
                self.cluster.ckpt, "saves_global_state", False):
            return self.cluster.ckpt.restore(state)
        local_tmpl = host_local_slice(state)
        local_state, meta = self.cluster.ckpt.restore(local_tmpl)
        return (global_state_from_local(self.mesh, self.gossip_axis,
                                        local_state), meta)

    def _try_cross_world_resume(self) -> bool:
        """No checkpoint for the current world: discover another world's
        set and reshard it into place (exact-average consensus collapse,
        supervise/reshard.py) so a resized relaunch resumes instead of
        silently cold-starting.  Torn sets are rejected by the reshard
        (assembled rank rows must sum to the source world), and on a pod
        the existing all-gather barrier in fit() still vetoes a resume
        any process could not complete."""
        if self.cfg.fleet:
            # the pod coordinator already resharded (and assigned this
            # host its shard) before relaunching; a per-host reshard
            # here would race the other survivors' writes
            self.log.info("fleet mode: cross-world auto-reshard left "
                          "to the pod coordinator")
            return False
        ckpt = self.cluster.ckpt
        if not hasattr(ckpt, "discover_worlds"):
            return False  # backend without flat per-rank files (orbax)
        if self.local_axis is not None:
            # hierarchical meshes stack gossip rows per NODE while the
            # filename world counts devices; the row algebra would lie
            return False
        if not ckpt.discover_worlds():
            return False
        from ..supervise.reshard import maybe_cross_world_reshard

        report = maybe_cross_world_reshard(
            ckpt.directory, ckpt.tag, self.world_size,
            out_rank=self.proc_index, out_rows=len(self.local_ranks),
            log=self.log)
        return report is not None and ckpt.exists()

    def _ckpt_meta(self, epoch: int, itr: int, best_prec1, begin_time,
                   meters) -> dict:
        """Checkpoint metadata for a resume point at (epoch, itr)."""
        batch_meter, nn_meter, data_meter = meters
        meta = {
            "epoch": epoch, "itr": itr,
            "best_prec1": float(best_prec1),
            "elapsed_time": time.time() - begin_time,
            "batch_meter": batch_meter.state_dict(),
            "nn_meter": nn_meter.state_dict(),
            "data_meter": data_meter.state_dict(),
        }
        if self.cfg.plan:
            # reproducibility: the launch-time topology plan (gap,
            # mixing, averaging period, rationale) rides with the state
            # it shaped
            meta["plan"] = self.cfg.plan
        if self.monitor is not None and self.monitor.last_payload:
            # the run's consensus health at save time rides with the
            # state it describes
            meta["health"] = self.monitor.last_payload
        return meta

    def _drain_in_flight(self, state):
        """Flush overlap in-flight shares into params before a save
        (algorithms.drain_state — the shared fold): each pending share
        is consumed early (purely per-rank adds, no collective), so the
        checkpoint carries nothing in flight and reshards/reloads like
        a sync checkpoint.  The LIVE state adopts the drained view too,
        so a resumed run and the continuing run follow the same
        trajectory (consuming early is mass-conserving: the mean is
        untouched, staleness momentarily shrinks)."""
        from ..algorithms import drain_state

        return drain_state(state)

    def _save_state(self, state):
        """What the checkpoint backend receives: global-state backends
        (orbax on a pod) take the live sharded arrays — every process
        writes its own shards of one logical checkpoint; host-local
        backends (msgpack) take this process's rank rows."""
        if self.proc_count > 1 and not getattr(
                self.cluster.ckpt, "saves_global_state", False):
            return host_local_slice(state)
        return state

    def _emit_exit_event(self, reason: str, epoch: int, itr: int,
                         step: int) -> None:
        """Final ``run_meta`` event with the exit reason — the typed
        record the supervisor (and obsreport) key the requeue on."""
        if not self.telemetry.enabled:
            return
        self.telemetry.registry.emit("run_meta", {
            "exit_reason": reason,
            "signal": (self.cluster.last_signal
                       if self.cluster is not None else None),
            "epoch": epoch, "itr": itr,
            "exit_code": REQUEUE_EXIT_CODE,
        }, step=step, severity="warning")

    def _preempt_exit(self, state, epoch, itr, itr_per_epoch, meters,
                      best_prec1, begin_time):
        """A preemption signal arrived (SIGUSR1/SIGTERM on any rank):
        the in-flight chunk is done, so checkpoint at (epoch, itr), emit
        the final run_meta event, and exit with the requeue status the
        supervisor keys on.  ``save_checkpoint(requeue_on_signal=True)``
        raises ``SystemExit(REQUEUE_EXIT_CODE)`` after the save lands —
        the exit code doubles as the checkpoint barrier."""
        self.log.warning(
            "preemption signal (%s): checkpointing at epoch %d itr %d "
            "and exiting %d (requeue me)",
            self.cluster.last_signal or "peer flag", epoch, itr,
            REQUEUE_EXIT_CODE)
        self._emit_exit_event("preempt-requeue", epoch, itr,
                              epoch * itr_per_epoch + itr)
        state = self._drain_in_flight(state)  # nothing in flight on disk
        meta = self._ckpt_meta(epoch, itr, best_prec1, begin_time, meters)
        with self.telemetry.span("checkpoint_save", "checkpoint"), \
                self.profile.span("checkpoint_save"):
            self.cluster.save_checkpoint(self._save_state(state), meta,
                                         requeue_on_signal=True)
        # only reachable if the flag vanished between check and save
        raise SystemExit(REQUEUE_EXIT_CODE)

    def _batch_spec(self, scanned: bool) -> P:
        """The train step's batch partition spec (must mirror
        shard_train_step / shard_scanned_train_step)."""
        axes = (self.gossip_axis if self.local_axis is None
                else (self.gossip_axis, self.local_axis))
        return P(None, axes) if scanned else P(axes)

    def _train_epoch(self, state, ppi, itr_per_epoch, loader, epoch,
                     start_itr, meters, best_prec1=0.0, begin_time=None):
        cfg = self.cfg
        batch_meter, nn_meter, data_meter = meters
        stat_meters = {r: (Meter(ptag="Loss"), Meter(ptag="Prec@1"),
                           Meter(ptag="Prec@5"))
                       for r in self._csv_ranks}
        num_itr_ignore = cfg.num_itr_ignore
        cap = cfg.num_iterations_per_training_epoch
        cap = None if cap in (None, -1) else cap

        if start_itr:
            loader.fast_forward(start_itr)
        if cfg.prefetch:
            if self.proc_count == 1 and cfg.scan_steps == 1:
                from ..data.prefetch import DevicePrefetcher

                loader = DevicePrefetcher(
                    loader, self.mesh, self._batch_spec(scanned=False),
                    depth=cfg.prefetch_depth)
            elif not self._warned_prefetch:
                self.log.warning(
                    "prefetch supports single-process non-scanned runs "
                    "only; continuing without it")
                self._warned_prefetch = True

        def record(i, metric_slices, chunk, elapsed_nn, elapsed_batch,
                   elapsed_data, timed):
            """Update meters/CSV from ``chunk`` iterations' metrics.
            Chunks never straddle the warm-up boundary, so either every
            iteration here is ignored or none is; a chunk that triggered a
            fresh XLA compile is never timed either."""
            nonlocal num_itr_ignore
            for j in range(chunk):
                if num_itr_ignore == 0:
                    if timed:
                        nn_meter.update(elapsed_nn / chunk)
                        batch_meter.update(elapsed_batch / chunk)
                        data_meter.update(elapsed_data / chunk)
                else:
                    num_itr_ignore -= 1
                n = metric_slices["n"]
                for r in self._csv_ranks:
                    losses, top1, top5 = stat_meters[r]
                    pick = (lambda a: a[r, j]) if cfg.per_rank_csv \
                        else (lambda a: a[:, j].mean())
                    losses.update(float(pick(metric_slices["loss"])), n)
                    top1.update(float(pick(metric_slices["top1"])), n)
                    top5.update(float(pick(metric_slices["top5"])), n)
                itr = i + j
                if itr % cfg.print_freq == 0:
                    self._log_row(epoch, itr, meters, stat_meters)
                    if cfg.verbose and metric_slices.get("grad_norm") \
                            is not None:
                        # grad-norm observability rides the stdout log —
                        # the CSV schema stays byte-compatible with the
                        # reference; step functions not built by
                        # build_train_step may omit the key entirely
                        gn = float(metric_slices["grad_norm"][:, j].mean())
                        self.log.info(
                            f"epoch {epoch} itr {itr}: "
                            f"grad_norm {gn:.4f}")

        it = iter(loader)
        i = start_itr - 1
        batch_time = time.time()
        while True:
            remaining = None if cap is None else cap - (i + 1)
            if remaining is not None and remaining <= 0:
                break
            # chunk sizing: single steps through the warm-up window (so
            # compile time stays out of the timed iterations) and for any
            # tail shorter than scan_steps (so no remainder-sized program
            # is ever compiled) — otherwise exactly scan_steps
            target = cfg.scan_steps
            if num_itr_ignore > 0 or target <= 1:
                target = 1
            if remaining is not None and remaining < target:
                # cap tail: single steps, never a remainder-sized program
                target = 1
            pending = []
            with self.profile.span("data_fetch"):
                for _ in range(target):
                    try:
                        pending.append(next(it))
                    except StopIteration:
                        break
            if not pending:
                break
            if 1 < len(pending) < target:
                # loader tail (only reachable after StopIteration): push the
                # extras back and continue with single steps
                leftovers = pending[1:]
                pending = pending[:1]
                it = iter(leftovers)
            chunk = len(pending)

            alg, train_fn = self._train_fn(
                ppi, itr_per_epoch, chunk if chunk > 1 else 1)
            with self.profile.span("data_fetch"):
                if chunk > 1:
                    x = np.stack([b[0] for b in pending])
                    y = np.stack([b[1] for b in pending])
                else:
                    x, y = pending[0]
                if self.proc_count > 1:
                    # loader rows cover only this process's ranks;
                    # assemble the global array (per-process feeding on a
                    # pod)
                    spec = self._batch_spec(scanned=chunk > 1)
                    x = make_global_batch(self.mesh, spec, x)
                    y = make_global_batch(self.mesh, spec, y)
            elapsed_data = time.time() - batch_time  # includes host stacking
            nn_time = time.time()
            warm_key = (ppi, itr_per_epoch, chunk, np.shape(x))
            timed = self._warm_counts.get(warm_key, 0) >= 2
            self._warm_counts[warm_key] = \
                self._warm_counts.get(warm_key, 0) + 1
            # arm the heartbeat only on warm steps: the first calls of a
            # variant carry XLA compilation, which can legitimately exceed
            # any sane step timeout
            guard = (self.watchdog.step()
                     if self.watchdog is not None and timed
                     else contextlib.nullcontext())
            # the loop's phases by name in a --profile_dir capture
            # (telemetry/names.py); shared no-ops when none is active
            prof = self.profile
            gstep = epoch * itr_per_epoch + i + 1
            if prof.enabled:
                # capture window keyed on the GLOBAL step (resume-safe);
                # a scanned chunk starts/stops around the whole program —
                # the profiler cannot cut inside one compiled scan
                prof.maybe_start(gstep)
            # the process's first step is set-up's last phase and ends
            # with set-up's report; a shared no-op from then on
            with first_step(self.log, self.telemetry, gstep), \
                    prof.step(gstep):
                with guard:
                    with prof.span("dispatch"):
                        state, metrics = train_fn(state, x, y)
                    with prof.span("fence"):
                        jax.block_until_ready(state)
                if self._async_bilat is not None:
                    # wall-clock-async AD-PSGD: expose the fresh params to
                    # the host averaging thread and adopt whatever (stale)
                    # displacement it has ready — the thread worked while
                    # the device computed this step
                    with prof.span("async_bilat"):
                        last = gstep + chunk - 1
                        self._async_bilat.publish(last, state.params)
                        new_params, adopted = \
                            self._async_bilat.maybe_adopt(last,
                                                          state.params)
                        if adopted:
                            state = state.replace(params=new_params)
                with prof.span("metrics_fetch"):
                    if self.proc_count > 1:
                        # metrics come back sharded across hosts;
                        # all-gather the tiny per-rank vectors so every
                        # process logs full rows
                        metrics = to_host(metrics, self.mesh)
                    # metrics: [world] for a single step, [world, chunk]
                    # when scanned — normalize to [world, chunk]
                    to_arr = lambda m: np.asarray(m).reshape(
                        self.gossip_world, chunk)
                    slices = {
                        "n": (pending[0][0].shape[0]
                              * pending[0][0].shape[1]),
                        "loss": to_arr(metrics["loss"]),
                        "top1": to_arr(metrics["top1"]),
                        "top5": to_arr(metrics["top5"]),
                        "grad_norm": (to_arr(metrics["grad_norm"])
                                      if "grad_norm" in metrics else None),
                    }
            if prof.enabled:
                prof.maybe_stop(gstep + chunk - 1)
            elapsed_nn = time.time() - nn_time
            elapsed_batch = time.time() - batch_time
            record(i + 1, slices, chunk, elapsed_nn, elapsed_batch,
                   elapsed_data, timed)
            tel = self.telemetry
            if tel.enabled:
                # spans reuse the loop's OWN timestamps (no extra clock
                # reads or syncs in the hot path); comm accounting is
                # host integer math against the analytic model
                tel.trace_complete("data_fetch", "data", batch_time,
                                   elapsed_data)
                span_args = {"steps": chunk, "timed": timed}
                if tel.comm is not None:
                    m = tel.comm.model
                    span_args["gossip"] = sum(
                        m.gossip_fires(gstep + j) for j in range(chunk))
                    span_args["global_avg"] = sum(
                        m.global_avg_fires(gstep + j)
                        for j in range(chunk))
                    for j in range(chunk):
                        tel.comm.on_step(gstep + j)
                tel.trace_complete("train_step", "step", nn_time,
                                   elapsed_nn, span_args)
                ke = tel.metrics_every
                if ke and any((gstep + j) % ke == 0
                              for j in range(chunk)):
                    last = gstep + chunk - 1
                    tel.registry.emit("step_stats", {
                        "epoch": epoch,
                        "loss": round(float(slices["loss"].mean()), 6),
                        "step_time_s": round(elapsed_batch / chunk, 6),
                        "data_time_s": round(elapsed_data / chunk, 6),
                        "nn_time_s": round(elapsed_nn / chunk, 6),
                        "timed": timed}, step=last)
                    tel.emit_comm(step=last)
            if self.monitor is not None:
                if timed:
                    # per-iteration samples feed the p50/p99 straggler view
                    for _ in range(chunk):
                        self.monitor.record_step_time(elapsed_batch / chunk)
                with prof.span("health"):
                    state = self._observe_health(state, alg, metrics,
                                                 gstep, chunk)
            i += chunk
            if self.cluster is not None \
                    and self.cluster.any_rank_signalled():
                # the in-flight chunk just finished: checkpoint NOW and
                # exit with the requeue status instead of training to
                # the epoch boundary under a preemption deadline
                self._preempt_exit(state, epoch, i + 1, itr_per_epoch,
                                   meters, best_prec1,
                                   begin_time if begin_time is not None
                                   else time.time())
            batch_time = time.time()

        self._log_row(epoch, i, meters, stat_meters)
        return state

    # -- resilience --------------------------------------------------------

    def _recovery_fn(self, alg):
        """Compiled immediate-global-average for ``alg``, cached per
        algorithm instance (the cache pins the algorithm so a dead id
        cannot alias a new object — same idiom as averaging._FN_CACHE)."""
        key = id(alg)
        if key not in self._recovery_cache:
            from ..resilience import make_recovery_fn

            self._recovery_cache[key] = (
                make_recovery_fn(alg, self.mesh, self.gossip_axis), alg)
        return self._recovery_cache[key][0]

    def _observe_health(self, state, alg, metrics, gstep, chunk):
        """Digest one chunk's health signals; fire recovery when the
        policy says so.  Scanned chunks are observed per inner iteration
        but recovered AFTER the chunk (a compiled scan cannot be
        interrupted mid-flight) — the cooldown keeps one excursion from
        firing once per inner step."""
        from ..resilience.monitor import EF_HEALTH_KEY, HEALTH_KEYS

        if any(k not in metrics for k in HEALTH_KEYS):
            return state  # step function built without health signals
        keys = HEALTH_KEYS + ((EF_HEALTH_KEY,)
                              if EF_HEALTH_KEY in metrics else ())
        arrs = {k: np.asarray(metrics[k]).reshape(self.gossip_world, chunk)
                for k in keys}
        for j in range(chunk):
            # each signal is a collective over the gossip axis — every
            # rank carries the same value; read shard 0
            sig = {k: float(arrs[k][0, j]) for k in keys}
            report = self.monitor.observe(gstep + j, sig)
            if report.unhealthy and self.recovery_policy is not None:
                event = self.recovery_policy.assess(report)
                if event.action == "global-average" \
                        and hasattr(alg, "global_average"):
                    with self.telemetry.span("recovery_global_average",
                                             "recovery"), \
                            self.profile.span("recovery_global_average"):
                        if getattr(alg, "overlap", False):
                            # fold + drain the in-flight FIFO: pending
                            # shares are counted exactly once in Σx/Σw
                            new_p, new_w, new_fl = self._recovery_fn(
                                alg)(state.params,
                                     state.gossip.ps_weight,
                                     state.gossip.in_flight)
                            gossip = state.gossip.replace(
                                ps_weight=new_w, in_flight=new_fl)
                        else:
                            new_p, new_w = self._recovery_fn(alg)(
                                state.params, state.gossip.ps_weight)
                            gossip = state.gossip.replace(ps_weight=new_w)
                        state = state.replace(params=new_p, gossip=gossip)
                    if self.telemetry.comm is not None:
                        self.telemetry.comm.on_recovery()
        return state

    def validate(self, state, algorithm, val_loader) -> float:
        """Every rank evaluates the full val set independently
        (gossip_sgd.py:440-471); returns mean top-1 across ranks."""
        # cache keyed on the algorithm: eval_params differs between
        # algorithm instances (e.g. a ppi_schedule rebuilds the algorithm),
        # so a stale compiled eval must not be reused across them
        if self._eval_fn is None or self._eval_alg is not algorithm:
            eval_step = build_eval_step(self.model, algorithm,
                                        self.cfg.num_classes)
            self._eval_fn = shard_eval_step(
                eval_step, self.mesh, self.gossip_axis, self.local_axis)
            self._eval_alg = algorithm
        losses = Meter(ptag="Loss")
        top1 = Meter(ptag="Prec@1")
        top5 = Meter(ptag="Prec@5")
        rank_top1 = np.zeros(self.gossip_world)
        n_batches, n_samples = 0, 0
        with self.telemetry.span("validate", "eval"), \
                self.profile.span("validate"):
            for x, y in val_loader:
                if self.proc_count > 1:
                    spec = self._batch_spec(scanned=False)
                    x = make_global_batch(self.mesh, spec, x)
                    y = make_global_batch(self.mesh, spec, y)
                m = self._eval_fn(state, x, y)
                if self.proc_count > 1:
                    m = to_host(m, self.mesh)
                n = x.shape[0] * x.shape[1]
                losses.update(float(np.mean(m["loss"])), n)
                top1.update(float(np.mean(m["top1"])), n)
                top5.update(float(np.mean(m["top5"])), n)
                # sample-weighted like the aggregate Meter, so per-rank
                # and averaged val columns agree under variable batch
                # sizes
                rank_top1 += np.asarray(m["top1"]).reshape(
                    self.gossip_world) * n
                n_samples += n
                n_batches += 1
        if n_batches == 0:
            self.log.warning(
                "validation loader yielded no batches (dataset smaller "
                "than one world batch?) — reporting -1")
            self._last_val_per_rank = [-1.0] * self.gossip_world
            return -1.0
        self._last_val_per_rank = (rank_top1 / n_samples).tolist()
        self.log.info(
            f" * Prec@1 {top1.avg:.3f} Prec@5 {top5.avg:.3f}")
        return top1.avg
