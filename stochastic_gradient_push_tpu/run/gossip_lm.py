"""Gossip LM CLI — decentralized transformer training with optional
ring-attention sequence parallelism.

The reference's transformer experiments lived in an external fairseq fork
(its repo ships only the log parser, visualization/plotting.py:137-192);
here the transformer path is a first-class CLI.  The mesh composes up to
three axes — ``(gossip, seq, tp)``: gossip data parallelism over
``world_size // (sp·tp)`` replicas, ``--sp``-way exact ring attention, and
``--tp``-way Megatron tensor parallelism (GSPMD auto axis).

Example (virtual 8-device CPU mesh, 4 replicas × 2 sequence shards):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python -m stochastic_gradient_push_tpu.run.gossip_lm \\
      --world_size 8 --sp 2 --seq_len 64 --d_model 64 --n_layers 2 \\
      --num_steps 100 --checkpoint_dir /tmp/lm/

``main`` is parse → validate → telemetry → :func:`build_training` → resume
→ data → :func:`train_loop`.  ``build_training`` is the one place the flags
become a job (mesh, model, algorithm, step, state); a caller that wants
the job without the run — a benchmark, a serving selftest — asks it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
import typing

from ..topology import GRAPH_TOPOLOGIES, TOPOLOGY_NAMES
# the resolve_* / *_plan_config names are read from this module by callers
# that assemble a job flag by flag (benchmark/builders/)
from .gossip_sgd import (_str_bool as sb, add_shared_flags,  # noqa: F401
                         plan_gossip, reject_push_sum_wire_knobs,
                         resolve_kernel_flag, resolve_shared_flags,
                         resolve_staleness_flag, resolve_wire_flags,
                         synth_plan_config, wire_plan_config)

__all__ = ["main", "build_parser", "parse_args", "Training",
           "build_training", "train_loop"]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Gossip LM on TPU")
    # algorithm, planner, resilience, run basics (same as gossip_sgd)
    add_shared_flags(p)
    p.add_argument("--bilat", default="False", type=str,
                   help="AD-PSGD: bilateral perfect-matching averaging "
                        "(synchronous formulation; see algorithms.py)")
    p.add_argument("--health_every", default=0, type=int,
                   help="emit a structured 'gossip health:' line every k "
                        "steps; excursions arm the recovery policy "
                        "(immediate exact global average); flat dp/sp "
                        "meshes only; 0 disables")
    p.add_argument("--peers_per_itr", default=1, type=int)
    # optimization
    p.add_argument("--lr", default=0.5, type=float)
    p.add_argument("--weight_decay", default=0.0, type=float)
    p.add_argument("--warmup_steps", default=None, type=int,
                   help="linear warmup horizon (default: num_steps // 10)")
    # model
    p.add_argument("--vocab_size", default=256, type=int)
    p.add_argument("--d_model", default=256, type=int)
    p.add_argument("--n_layers", default=4, type=int)
    p.add_argument("--n_heads", default=8, type=int)
    p.add_argument("--d_ff", default=1024, type=int)
    p.add_argument("--seq_len", default=256, type=int)
    p.add_argument("--model_json", default=None, type=str,
                   help="a model described by its source's config.json "
                        "keys (models/transformer.py::config_from_source: "
                        "Mamba-2 and grouped-query attention layers in a "
                        "pattern, RMSNorm, gated MLP, tied head); "
                        "replaces --vocab_size, --d_model, --n_layers, "
                        "--n_heads and --d_ff; flat data-parallel mesh only")
    p.add_argument("--attn", default=None,
                   choices=[None, "full", "blockwise", "flash", "ring",
                            "ring_flash"],
                   help="default: ring when --sp > 1 else flash on TPU, "
                        "full elsewhere")
    p.add_argument("--attn_block", default=0, type=int,
                   help="flash/blockwise/ring_flash block size override "
                        "(0 = the measured auto rule, "
                        "ops.flash_attention.default_block)")
    p.add_argument("--attn_block_k", default=0, type=int,
                   help="flash only: asymmetric K/V-side block "
                        "(0 = symmetric with --attn_block)")
    p.add_argument("--precision", default="fp32", choices=["fp32", "bf16"])
    p.add_argument("--remat", default="False", type=str)
    p.add_argument("--grad_accum", default=1, type=int,
                   help="microbatches accumulated per optimizer step "
                        "(1/N peak activation memory; exact — the LM "
                        "has no BatchNorm). Flat dp/sp/tp/ep meshes "
                        "only; --pp has n_micro instead")
    # parallelism / run shape
    p.add_argument("--world_size", default=None, type=int)
    p.add_argument("--sp", default=1, type=int,
                   help="sequence-parallel shards per replica")
    p.add_argument("--tp", default=1, type=int,
                   help="tensor-parallel shards per replica (Megatron "
                        "kernel sharding via GSPMD; composes with --sp "
                        "on a 3-D gossip x seq x tp mesh)")
    p.add_argument("--ep", default=1, type=int,
                   help="expert-parallel shards (requires --moe_experts; "
                        "each ep shard also carries its own tokens)")
    p.add_argument("--pp", default=1, type=int,
                   help="pipeline stages per replica (GPipe microbatch "
                        "schedule on a (gossip, pipe) mesh; dense "
                        "non-ring models only)")
    p.add_argument("--n_micro", default=4, type=int,
                   help="microbatches per step when --pp > 1 "
                        "(must divide batch_size; bubble fraction is "
                        "(pp-1)/(n_micro+pp-1))")
    p.add_argument("--moe_experts", default=0, type=int,
                   help="total switch-MoE experts (0 = dense FFN)")
    p.add_argument("--moe_every", default=2, type=int)
    p.add_argument("--batch_size", default=8, type=int,
                   help="sequences per replica per step")
    p.add_argument("--num_steps", default=1000, type=int)
    p.add_argument("--corpus_tokens", default=500_000, type=int)
    p.add_argument("--corpus_file", default=None,
                   help="real corpus: .npy/.npz pre-tokenized int array, "
                        "or any file read as raw bytes (byte-level LM, "
                        "vocab_size >= 256); default: synthetic Markov")
    p.add_argument("--tag", default="lm_", type=str)
    p.add_argument("--ckpt_every", default=0, type=int,
                   help="checkpoint every N steps (0 = only at the end)")
    p.add_argument("--ckpt_backend", default="msgpack",
                   choices=["msgpack", "orbax"],
                   help="checkpoint backend (same as gossip_sgd): "
                        "self-contained msgpack, or orbax (async saves, "
                        "retention GC; on pods one shared jax.Array-"
                        "native checkpoint).  ep/tp/pp multihost meshes "
                        "force orbax regardless — their state shards on "
                        "non-leading dims")
    p.add_argument("--val_frac", default=0.0, type=float,
                   help="hold out this fraction of the corpus tail for "
                        "validation (0 = off); val_loss/val_ppl columns "
                        "join the CSV")
    p.add_argument("--val_every", default=0, type=int,
                   help="validate every N steps (0 = only at the end); "
                        "must be a multiple of --print_freq since val "
                        "rows ride the CSV print cadence")
    p.add_argument("--val_batches", default=8, type=int,
                   help="validation batches per evaluation")
    p.add_argument("--metrics_every", default=0, type=int,
                   help="emit a step_stats + comm telemetry event every "
                        "k steps (rides the --print_freq metrics fetch "
                        "cadence; 0 = only the final comm snapshot); "
                        "requires --trace_dir")
    return p


def _flash_ok(seq_len: int) -> bool:
    # the pallas kernel needs the (clamped) 128 block to divide seq_len
    return seq_len % min(128, seq_len) == 0


def resolve_attention(flag: str | None, seq_len: int, sp: int,
                      backend: str, log) -> str:
    """The ``--attn`` auto rule: ring under sequence parallelism, the
    flash kernel on TPU when ``seq_len`` tiles, full attention elsewhere.
    Shape is the only thing that routes an auto-selected flash to
    blockwise; a kernel the chip's compiler rejects fails the run."""
    if flag is None:
        if sp > 1:
            return "ring"
        if backend != "tpu":
            return "full"
        if not _flash_ok(seq_len):
            log.info(f"seq_len {seq_len} not divisible by the flash "
                     "kernel block; falling back to blockwise attention")
            return "blockwise"
        return "flash"
    if flag == "flash" and not _flash_ok(seq_len):
        raise SystemExit(
            f"--attn flash needs seq_len divisible by "
            f"{min(128, seq_len)} (got {seq_len}); use "
            "--attn blockwise or a padded seq_len")
    return flag


def resolve_model_json(args) -> None:
    """``--model_json``: load the source's keys into ``args.model_source``
    and set the five size flags they replace, so that the data, the
    checks and the log see the model's own sizes."""
    args.model_source = None
    if not args.model_json:
        return
    if (args.sp, args.tp, args.ep, args.pp) != (1, 1, 1, 1) \
            or args.moe_experts:
        raise SystemExit("--model_json builds a layer pattern, which "
                         "composes with the flat data-parallel mesh only "
                         "(not --sp/--tp/--ep/--pp > 1 or --moe_experts)")
    from ..models.transformer import source_family

    with open(args.model_json) as f:
        src = args.model_source = json.load(f)
    try:
        width_key = source_family(src)[1]
    except ValueError as e:
        raise SystemExit(f"--model_json {args.model_json}: {e}")
    for flag, key in (("vocab_size", "vocab_size"),
                      ("d_model", "hidden_size"),
                      ("n_layers", "num_hidden_layers"),
                      ("n_heads", "num_attention_heads"),
                      ("d_ff", width_key)):
        if key not in src:
            raise SystemExit(f"--model_json {args.model_json}: no {key!r}")
        setattr(args, flag, src[key])


def model_from_args(args, attn: str, seq_axis=None, ep_axis=None):
    """The model the flags describe — the one place ``TransformerConfig``
    is built from ``args`` (``resolve_model_json`` ran before).  ``attn``
    is ``resolve_attention``'s answer; ``--pp > 1`` gives one pipeline
    stage's slice of the stack."""
    import jax.numpy as jnp

    from ..models.transformer import (TransformerConfig, TransformerLM,
                                      config_from_source)


    runtime = dict(
        max_len=args.seq_len,
        dtype=jnp.bfloat16 if args.precision == "bf16" else jnp.float32,
        attn_impl=attn, seq_axis=seq_axis,
        attn_block_size=args.attn_block or None,
        attn_block_k=args.attn_block_k or None,
        remat=sb(args.remat))
    if args.model_source is not None:
        try:
            return TransformerLM(config_from_source(args.model_source,
                                                    **runtime))
        except (KeyError, ValueError) as e:
            raise SystemExit(f"--model_json {args.model_json}: {e!r}")
    cfg = TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads, d_ff=args.d_ff,
        moe_experts=args.moe_experts, moe_every=args.moe_every,
        ep_axis=ep_axis, **runtime)
    if args.pp > 1:
        from ..models import PipelineStageLM
        return PipelineStageLM(cfg, n_local_layers=args.n_layers // args.pp)
    return TransformerLM(cfg)


def validate_args(args) -> None:
    """Resolve and check the parsed flags in place — everything that
    needs no device: the model file, the mesh factors, the shared gossip
    flags (``gossip_sgd.resolve_shared_flags``: one set of error texts)
    and the LM loop's cadences."""
    resolve_model_json(args)
    sp, tp_, ep, pp = args.sp, args.tp, args.ep, args.pp
    if sp < 1 or tp_ < 1 or ep < 1 or pp < 1:
        raise SystemExit("--sp, --tp, --ep and --pp must be >= 1")
    if pp > 1:
        # pipeline composes with gossip DP and — since round 3 — with
        # ring-attention sequence parallelism (the tick's ppermute moves
        # activations over pipe while ring attention rotates KV over seq:
        # different manual axes, both uniform in the tick body), with
        # MoE (every layer an expert block, routed per microbatch inside
        # the ticks — per-block when seq-sharded), with expert
        # parallelism (the MoE all_to_all dispatches token slots over ep
        # inside each tick), and with the full 4-D pp × ep × sp mesh.
        # Only tp stays fenced (ARCHITECTURE.md matrix).
        if tp_ > 1:
            raise SystemExit("--pp composes with gossip DP, --sp, "
                             "--moe_experts and --ep only (not --tp)")
        if ep > 1 and not args.moe_experts:
            raise SystemExit("--pp with --ep requires --moe_experts > 0")
        if args.moe_experts and args.moe_every != 1:
            raise SystemExit("--pp with --moe_experts requires "
                             "--moe_every 1 (the stage stack is one "
                             "uniform scan)")
        if args.n_micro < 1:
            raise SystemExit(f"--n_micro must be >= 1 (got {args.n_micro})")
        if args.n_layers % pp:
            raise SystemExit(f"n_layers {args.n_layers} not divisible "
                             f"by pp {pp}")
        if args.batch_size % args.n_micro:
            raise SystemExit(f"batch_size {args.batch_size} not divisible "
                             f"by n_micro {args.n_micro}")
    # --moe_experts with --sp > 1 (no ep): per-block routing — every
    # sequence shard routes its own block's tokens with per-block capacity;
    # expert weights are replicated over seq.  Routing is per-token, so
    # with enough capacity this matches global routing exactly
    # (tests/test_moe.py::test_moe_ring_per_block_routing_parity).
    if ep > 1 and not args.moe_experts:
        raise SystemExit("--ep requires --moe_experts > 0")
    if args.moe_experts and args.moe_experts % ep:
        raise SystemExit(
            f"moe_experts {args.moe_experts} not divisible by ep {ep}")
    if args.seq_len % sp:
        raise SystemExit(f"seq_len {args.seq_len} not divisible by sp {sp}")
    resolve_shared_flags(args)
    if args.topology is not None and (sb(args.all_reduce)
                                      or sb(args.bilat)):
        raise SystemExit("--topology selects a push-sum/D-PSGD gossip "
                         "graph; it does not apply to all_reduce/bilat "
                         "modes")
    if args.health_every:
        if ep > 1 or tp_ > 1 or pp > 1:
            # ep shards hold different expert slices (health signals
            # would vary over ep and break metrics replication); tp's
            # auto axis and pp's staged step are likewise health-opaque
            raise SystemExit("--health_every composes with the flat dp "
                             "and dp×sp meshes only (not ep/tp/pp)")
        if args.health_every % args.print_freq:
            raise SystemExit(
                f"--health_every {args.health_every} must be a multiple "
                f"of --print_freq {args.print_freq} (health signals ride "
                "the metrics fetch cadence)")
    if args.grad_accum > 1 and pp > 1:
        raise SystemExit("--grad_accum composes with the flat meshes; "
                         "pipeline runs control microbatching with "
                         "--n_micro")
    if args.grad_accum > 1 and args.batch_size % args.grad_accum:
        raise SystemExit(
            f"--batch_size {args.batch_size} not divisible by "
            f"--grad_accum {args.grad_accum}")
    if args.val_frac > 0 and args.val_every \
            and args.val_every % args.print_freq:
        raise SystemExit(
            f"--val_every {args.val_every} must be a multiple of "
            f"--print_freq {args.print_freq} (validation rows ride the "
            "CSV print cadence)")


def parse_args(argv=None) -> argparse.Namespace:
    """``argv`` → the validated namespace :func:`build_training` takes."""
    args = build_parser().parse_args(argv)
    validate_args(args)
    return args


class Training(typing.NamedTuple):
    """What the flags describe, assembled (:func:`build_training`)."""

    mesh: typing.Any
    dp: int
    sp: int
    tp: int
    ep: int
    pp: int
    attn: str                   # resolve_attention's answer
    model: typing.Any
    mode: str                   # one of algorithms.GOSSIP_MODES
    algorithm: typing.Any           # GossipAlgorithm
    plan: typing.Any                # planner.Plan, None when nothing to plan
    interconnect: typing.Any        # the fabric model the plan priced on
    lr_schedule: typing.Any
    itr_per_epoch: int
    step: typing.Callable           # the per-rank step, before sharding
    train_fn: typing.Callable       # (state, tokens, targets) -> state, metrics
    state: typing.Any
    eval_fn: typing.Callable | None   # only with --val_frac > 0

    @property
    def world(self) -> int:
        return self.dp * self.sp * self.tp * self.ep * self.pp

    @property
    def ring(self) -> bool:
        """Sequence-sharded (ring-family) attention: batches carry a
        ``seq`` dim."""
        return self.attn in ("ring", "ring_flash")


def _make_mesh(dp: int, sp: int, tp_: int, ep: int, pp: int):
    """The mesh for the parallelism factors: every composition the CLI
    accepts (ARCHITECTURE.md matrix)."""
    from ..train import lm, pp as pipe

    if pp > 1:
        if sp > 1 and ep > 1:
            return pipe.make_dp_pp_ep_sp_mesh(dp, pp, ep, sp)
        if sp > 1:
            return pipe.make_dp_pp_sp_mesh(dp, pp, sp)
        if ep > 1:
            return pipe.make_dp_pp_ep_mesh(dp, pp, ep)
        return pipe.make_dp_pp_mesh(dp, pp)
    if ep > 1 and sp > 1 and tp_ > 1:
        return lm.make_dp_ep_sp_tp_mesh(dp, ep, sp, tp_)
    if ep > 1 and sp > 1:
        return lm.make_dp_ep_sp_mesh(dp, ep, sp)
    if ep > 1 and tp_ > 1:
        return lm.make_dp_ep_tp_mesh(dp, ep, tp_)
    if ep > 1:
        return lm.make_dp_ep_mesh(dp, ep)
    if sp > 1 and tp_ > 1:
        return lm.make_dp_sp_tp_mesh(dp, sp, tp_)
    if tp_ > 1:
        return lm.make_dp_tp_mesh(dp, tp_)
    return lm.make_dp_sp_mesh(dp, sp)


def build_training(args, log, registry=None) -> Training:
    """From the validated flags (:func:`parse_args`) to the job they
    describe, on every mesh the CLI accepts: plan, mesh, model,
    algorithm, LR schedule, step, sharded train function, state and
    (with ``--val_frac``) the eval function.  No I/O beyond log lines and
    the plan event (``registry``): checkpoints, corpus, CSV, watchdog and
    profiler are the caller's."""
    import jax
    import numpy as np

    from ..algorithms import gossip_algorithm, gossip_mode
    from ..parallel import GOSSIP_AXIS
    from ..telemetry import setup_phase
    from ..train import LRSchedule, sgd
    from ..train.lm import (EP_AXIS, SEQ_AXIS, build_lm_train_step,
                            ep_state_specs, init_lm_state,
                            init_lm_state_ep, shard_lm_train_step)
    from ..train.lr import WARMUP_EPOCHS

    world = args.world_size or jax.device_count()
    sp, tp_, ep, pp = args.sp, args.tp, args.ep, args.pp
    if world % (sp * tp_ * ep * pp):
        raise SystemExit(
            f"world_size {world} not divisible by sp*tp*ep*pp "
            f"{sp * tp_ * ep * pp}")
    dp = world // (sp * tp_ * ep * pp)

    # launch-time topology policy BEFORE any mesh/device work (planning is
    # pure numpy, and a below-floor warning must reach the user even when
    # the launch subsequently fails): the gossip world for the LM is the
    # data-parallel replica count, not raw devices
    mode = gossip_mode(all_reduce=sb(args.all_reduce),
                       push_sum=sb(args.push_sum), bilat=sb(args.bilat))
    with setup_phase("plan"):
        plan, interconnect = plan_gossip(
            args, dp, mode=mode, ppi=args.peers_per_itr,
            graph_class=GRAPH_TOPOLOGIES[args.graph_type],
            overlap=sb(args.overlap), log=log, registry=registry)
    with setup_phase("mesh"):
        mesh = _make_mesh(dp, sp, tp_, ep, pp)
    if jax.process_count() > 1:
        # per-process feeding works on every mesh; checkpoints need a
        # layout that can hold arbitrary shardings.  dp/dp×sp states
        # slice cleanly into per-process rank-row msgpack files; ep/tp/pp
        # states shard on non-leading dims (or via GSPMD), so those
        # meshes use the orbax global-state backend instead (one shared
        # root, each process writes its own shards).
        log.info(f"process {jax.process_index()}/{jax.process_count()}: "
                 f"multihost LM over {mesh}")

    attn = resolve_attention(args.attn, args.seq_len, sp,
                             jax.default_backend(), log)
    ring_family = attn in ("ring", "ring_flash")
    if sp > 1 and not ring_family:
        raise SystemExit("--sp > 1 requires ring attention")
    if attn == "ring_flash":
        shard = args.seq_len // max(1, sp)
        if not _flash_ok(shard):
            raise SystemExit(
                f"--attn ring_flash needs the per-shard length "
                f"(seq_len/sp = {shard}) divisible by "
                f"{min(128, shard)}; pad seq_len or use --attn ring")
    if tp_ > 1 and sp == 1 and ring_family:
        raise SystemExit(
            "--tp with ring attention requires --sp > 1 (3-D mesh)")
    if ep > 1 and ring_family and sp == 1:
        raise SystemExit(
            "--ep with ring attention needs --sp > 1 (the 3-D "
            "gossip × ep × seq mesh)")
    if pp > 1 and ring_family and sp == 1:
        raise SystemExit("--pp with ring attention needs --sp > 1 "
                         "(the 3-D gossip × pipe × seq mesh)")
    seq_axis = SEQ_AXIS if ring_family else None
    ep_axis = EP_AXIS if ep > 1 else None
    with setup_phase("model"):
        model = model_from_args(args, attn, seq_axis=seq_axis,
                                ep_axis=ep_axis)

    if plan is not None:
        graph_class = plan.graph_class
    elif args.topology:  # forced name on a dp==1 mesh (plan skipped)
        graph_class = TOPOLOGY_NAMES[args.topology]
    else:
        graph_class = GRAPH_TOPOLOGIES[args.graph_type]
    alg = gossip_algorithm(
        mode, GOSSIP_AXIS, world=dp, graph_class=graph_class,
        peers_per_itr=args.peers_per_itr,
        mixing=plan.mixing_strategy() if plan is not None else None,
        overlap=sb(args.overlap), staleness=max(1, args.staleness),
        gossip_every=args.gossip_every, wire_dtype=args.wire_dtype,
        wire_block=args.wire_block,
        error_feedback=bool(args.error_feedback),
        global_avg_every=(plan.global_avg_every if plan is not None
                          else (args.global_avg_every or 0)),
        inject_faults=args.inject_faults,
        gossip_kernel=args.gossip_kernel,
        gossip_buckets=args.gossip_buckets, log=log)

    tx = sgd(momentum=args.momentum, weight_decay=args.weight_decay,
             nesterov=sb(args.nesterov))
    # LR linear scaling counts data-parallel replicas (dp), not raw devices:
    # sequence shards don't enlarge the global batch.  The warmup horizon is
    # step-based (LRSchedule spans WARMUP_EPOCHS "epochs" of the synthetic
    # itr_per_epoch below).
    warmup_steps = args.warmup_steps or max(args.num_steps // 10, 1)
    itr_per_epoch = max(warmup_steps // WARMUP_EPOCHS, 1)
    # LR scaling counts every shard that contributes tokens to the global
    # batch: gossip replicas and ep shards do, seq/tp shards don't
    lrs = LRSchedule(ref_lr=args.lr, batch_size=args.batch_size,
                     world_size=dp * ep, decay_schedule={},
                     warmup=sb(args.warmup))
    # the step is assembled and the state is made: the init program is
    # built and run in here (the ledger's rows say which and how long)
    with setup_phase("state_init"):
        if pp > 1:
            from ..train.pp import (build_pp_eval_step, build_pp_train_step,
                                    init_pp_state, pp_state_specs,
                                    shard_pp_eval_step, shard_pp_train_step)

            step = build_pp_train_step(model, alg, tx, lrs,
                                       itr_per_epoch=itr_per_epoch)
            state = init_pp_state(model, mesh, alg, tx, dp=dp, pp=pp,
                                  n_micro=args.n_micro,
                                  micro_batch=args.batch_size // args.n_micro,
                                  seq_len=args.seq_len, seed=args.seed, sp=sp,
                                  ep=ep)
            specs = pp_state_specs(state, ep_axis=ep_axis)
            train_fn = shard_pp_train_step(step, mesh, specs,
                                           seq_axis=seq_axis, ep_axis=ep_axis)
        else:
            step = build_lm_train_step(
                model, alg, tx, lrs, itr_per_epoch=itr_per_epoch,
                seq_axis=seq_axis, ep_axis=ep_axis,
                grad_accum=args.grad_accum,
                health_axis=GOSSIP_AXIS if args.health_every > 0 else None)
            if ep > 1:
                state = init_lm_state_ep(model, mesh, alg, tx, dp=dp, ep=ep,
                                         batch_size=args.batch_size,
                                         seq_len=args.seq_len, seed=args.seed,
                                         sp=sp)
                train_fn = shard_lm_train_step(
                    step, mesh, seq_axis=seq_axis,
                    state_specs=ep_state_specs(state), ep_axis=EP_AXIS,
                    tp=tp_ > 1)
            elif tp_ > 1 and not ring_family:
                from ..train.lm import init_lm_state_tp

                state = init_lm_state_tp(model, mesh, alg, tx, dp=dp,
                                         batch_size=args.batch_size,
                                         seq_len=args.seq_len, seed=args.seed)
                train_fn = shard_lm_train_step(step, mesh, seq_axis=None,
                                               tp=True)
            else:
                state = init_lm_state(
                    model, mesh, alg, tx, dp=dp, sp=sp,
                    batch_size=args.batch_size,
                    block_len=(args.seq_len // sp if ring_family
                               else args.seq_len),
                    seed=args.seed, seq_axis=seq_axis)
                train_fn = shard_lm_train_step(
                    step, mesh, seq_axis=seq_axis, tp=tp_ > 1)

    eval_fn = None
    if args.val_frac > 0 and pp > 1:
        eval_fn = shard_pp_eval_step(
            build_pp_eval_step(model, alg), mesh, specs,
            seq_axis=seq_axis, ep_axis=ep_axis)
    elif args.val_frac > 0:
        from ..train.lm import build_lm_eval_step, shard_lm_eval_step

        eval_fn = shard_lm_eval_step(
            build_lm_eval_step(model, alg, seq_axis=seq_axis,
                               ep_axis=ep_axis),
            mesh, seq_axis=seq_axis, tp=tp_ > 1,
            state_specs=ep_state_specs(state) if ep > 1 else None,
            ep_axis=ep_axis)

    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree.leaves(
                       jax.tree.map(lambda a: a[0], state.params)))
    log.info(f"mesh {mesh}; {n_params/1e6:.2f}M params; attn={attn}")
    return Training(mesh=mesh, dp=dp, sp=sp, tp=tp_, ep=ep, pp=pp,
                    attn=attn, model=model, mode=mode, algorithm=alg,
                    plan=plan, interconnect=interconnect, lr_schedule=lrs,
                    itr_per_epoch=itr_per_epoch, step=step,
                    train_fn=train_fn, state=state, eval_fn=eval_fn)


def _attach_accounting(args, t: Training, rt) -> None:
    """Comm-volume accounting and the ``run_meta`` event (telemetry/);
    a no-op on the null bundle."""
    import jax

    if not rt.enabled:
        return
    dp, sp, tp_, ep, pp = t.dp, t.sp, t.tp, t.ep, t.pp
    alg, state = t.algorithm, t.state
    # comm-volume accounting (telemetry/): flat dp / dp×sp meshes only —
    # ep/tp/pp shard params on non-leading dims, so the per-rank payload
    # arithmetic would be wrong there (same fence as --health_every)
    if pp == 1 and ep == 1 and tp_ == 1:
        from ..parallel.wire import get_codec
        from ..telemetry import (CommModel, encoded_payload_bytes,
                                 tree_payload_bytes)

        exact = tree_payload_bytes(state.params, dp)
        if t.mode == "all_reduce":
            comm_model = CommModel.for_allreduce(dp, exact)
        elif t.mode == "adpsgd":
            comm_model = CommModel.for_bilat(dp, exact)
        else:
            # price the ENCODED payload (codec dtype + int8 scale lane;
            # scalar leaves exempt) — what the wire actually ships
            codec = get_codec(args.wire_dtype, args.wire_block)
            wire = encoded_payload_bytes(state.params, dp, codec)
            comm_model = CommModel.from_schedule(
                alg.schedule, wire, exact_bytes=exact,
                gossip_every=alg.gossip_every,
                global_avg_every=alg.global_avg_every,
                faults=alg.faults, ps_weight=sb(args.push_sum),
                interconnect=t.interconnect, codec=codec,
                error_feedback=bool(args.error_feedback),
                overlap=getattr(alg, "overlap", False),
                staleness=getattr(alg, "staleness", 1),
                gossip_kernel=getattr(alg, "transport_kernel_name",
                                      "xla"),
                gossip_buckets=getattr(alg, "gossip_buckets", 1))
        rt.attach_comm(comm_model)
    run_meta = {
        "world": t.world, "dp": dp, "sp": sp, "tp": tp_, "ep": ep,
        "pp": pp, "algorithm": t.mode,
        "gossip_every": args.gossip_every,
        "batch_size": args.batch_size,
        "num_steps": args.num_steps,
        "comm_model": (rt.comm.model.to_dict()
                       if rt.comm is not None else None)}
    if args.profile_dir:
        # where the XPlane dump lands + the captured step window,
        # discoverable from the run directory (obsreport/fleetmon)
        run_meta["profile_dir"] = args.profile_dir
        run_meta["profile_window"] = [
            args.profile_start_step,
            args.profile_start_step + args.profile_steps]
    if args.fleet:
        run_meta["fleet"] = True
        run_meta["host_id"] = (args.host_id
                               if args.host_id is not None
                               else jax.process_index())
    rt.registry.emit("run_meta", run_meta)


def _checkpointing(args, t: Training):
    """``(ckpt, cluster, use_orbax)``: the checkpoint manager and the
    preemption handler around it.  State and step counter go in one
    atomic payload (same managers as the image harness); on a pod each
    process saves/restores its own rank rows (per-process files)."""
    import jax

    from ..utils.checkpoint import CheckpointManager, ClusterManager

    proc_count, proc_index = jax.process_count(), jax.process_index()
    # ep/tp/pp multihost states shard on non-leading dims — the rank-row
    # msgpack slicing cannot represent them, but orbax's global-state mode
    # holds any sharding (every process writes its own shards of ONE
    # logical checkpoint).  --ckpt_backend orbax selects the same backend
    # voluntarily (async saves + retention GC single-process)
    use_orbax = (args.ckpt_backend == "orbax"
                 or (proc_count > 1 and (t.ep > 1 or t.tp > 1 or t.pp > 1)))
    if use_orbax:
        from ..utils.orbax_ckpt import OrbaxCheckpointManager

        ckpt = OrbaxCheckpointManager(args.checkpoint_dir, tag=args.tag,
                                      rank=proc_index, world_size=t.world)
    else:
        ckpt = CheckpointManager(args.checkpoint_dir, tag=args.tag,
                                 rank=proc_index, world_size=t.world,
                                 all_workers=proc_count > 1)
    # preemption handling (≙ the image harness): SIGUSR1/SIGTERM raise a
    # flag; the step loop below finishes the in-flight step, checkpoints,
    # emits the final run_meta event, and exits with the requeue status
    # the supervisor (supervise/) keys on.  No requeue command: the LM
    # harness leaves relaunching to the supervisor/launch layer
    cluster = ClusterManager(ckpt, rank=proc_index, requeue_command=None)
    return ckpt, cluster, use_orbax


def _resume(args, t: Training, ckpt, use_orbax: bool, log):
    """``(state, start_step)`` under ``--resume``: restored leaves are
    device_put back into the live state's shardings, and a pod resumes
    from the minimum step any process holds."""
    import jax
    import numpy as np

    from ..parallel import GOSSIP_AXIS
    from ..parallel.multihost import (consensus_resume_point,
                                      global_state_from_local,
                                      host_local_slice)

    state, mesh = t.state, t.mesh
    proc_count = jax.process_count()
    if not sb(args.resume):
        return state, 0
    if not use_orbax and not ckpt.exists() \
            and t.pp == t.ep == t.tp == 1 and t.sp == 1 \
            and proc_count == 1 and not args.fleet:
        # a resized relaunch: another world's checkpoint set may exist —
        # reshard it (exact-average consensus collapse) instead of
        # silently cold-starting.  Flat dp meshes only: sharded-dim
        # states (sp/tp/ep/pp) don't stack rank rows on dim 0.  Fleet
        # runs skip this: the pod coordinator already resharded and
        # assigned per-host shards — a local reshard would race them
        from ..supervise.reshard import maybe_cross_world_reshard

        maybe_cross_world_reshard(args.checkpoint_dir, args.tag, t.world,
                                  log=log)
    shardings = jax.tree.map(lambda a: a.sharding, state)
    start_step = 0
    if proc_count > 1:
        # decide to resume COLLECTIVELY: gating the restore (and its
        # allgather) on a per-process exists() would hang the cluster when
        # one process's checkpoint is missing/torn — resume only when
        # every process holds a file, else all start from step 0
        from jax.experimental import multihost_utils

        all_have = int(np.min(np.asarray(multihost_utils.process_allgather(
            np.asarray([int(ckpt.exists())])))))
        if all_have:
            if use_orbax:
                # one shared logical checkpoint: the live sharded state is
                # the restore template, every process reads its own shards
                state, meta = ckpt.restore(state)
            else:
                local_tmpl = host_local_slice(state)
                local_state, meta = ckpt.restore(local_tmpl)
                state = global_state_from_local(mesh, GOSSIP_AXIS,
                                                local_state)
            _, start_step = consensus_resume_point(
                0, int(meta.get("step", 0)), log=log)
            log.info(f"resumed from step {start_step}")
        elif ckpt.exists():
            log.info("checkpoint present here but missing on a peer; "
                     "starting from step 0")
    elif ckpt.exists():
        # the live state is only a structure template; restored host
        # values are device_put back into its shardings
        host_state, meta = ckpt.restore(state)
        state = jax.tree.map(jax.device_put, host_state, shardings)
        start_step = int(meta.get("step", 0))
        log.info(f"resumed from step {start_step}")
    return state, start_step


def _load_corpus(args, t: Training, log):
    """``(corpus, val_corpus)``: the token stream the flags name, the
    tail held out under ``--val_frac``."""
    from ..data.lm import synthetic_lm_corpus

    if args.corpus_file:
        from ..data.lm import load_corpus

        corpus = load_corpus(args.corpus_file, args.vocab_size)
        log.info(f"corpus: {args.corpus_file} ({len(corpus):,} tokens)")
    else:
        corpus = synthetic_lm_corpus(args.corpus_tokens,
                                     vocab_size=args.vocab_size,
                                     seed=args.seed)
    val_corpus = None
    if args.val_frac > 0:
        # hold out the corpus tail; at least one full validation batch
        min_val = (args.seq_len + 1) * t.dp * t.ep * args.batch_size
        n_val = max(int(len(corpus) * args.val_frac), min_val)
        if n_val >= len(corpus) // 2:
            raise SystemExit("--val_frac leaves too little training data")
        corpus, val_corpus = corpus[:-n_val], corpus[-n_val:]
    return corpus, val_corpus


def _open_csv(args, world: int, start_step: int, log) -> str:
    """Start (or, resuming, continue) this process's metrics CSV and
    return its path."""
    import jax

    proc_count, proc_index = jax.process_count(), jax.process_index()
    os.makedirs(args.checkpoint_dir, exist_ok=True)
    out_fname = os.path.join(
        args.checkpoint_dir,
        f"{args.tag}out_n{world}.csv" if proc_count == 1
        else f"{args.tag}out_p{proc_index}_n{world}.csv")
    csv_header = ("step,loss,ppl,lr,tokens_per_sec,grad_norm"
                  + (",moe_dropped" if args.moe_experts > 0 else "")
                  + (",val_loss,val_ppl" if args.val_frac > 0 else ""))
    if start_step and os.path.isfile(out_fname):
        # appending to a pre-existing CSV: the schema has grown over time
        # (grad_norm column), so a resume of an old run could silently
        # misalign rows against the stale header — rewrite it in place
        with open(out_fname) as f:
            old_lines = f.read().splitlines()
        if old_lines and old_lines[0] != csv_header:
            log.warning(
                "existing CSV header %r != current schema %r; remapping "
                "old rows to the new schema (missing columns left empty)",
                old_lines[0], csv_header)
            old_cols = old_lines[0].split(",")
            new_cols = csv_header.split(",")
            # write-then-rename: a crash mid-rewrite must not destroy
            # the run's accumulated loss history
            tmp = out_fname + ".tmp"
            with open(tmp, "w") as f:
                print(csv_header, file=f)
                for row in old_lines[1:]:
                    # re-seat each value under its original column name so
                    # e.g. val_loss never lands in a newly inserted
                    # grad_norm slot
                    vals = dict(zip(old_cols, row.split(",")))
                    print(",".join(vals.get(c, "") for c in new_cols),
                          file=f)
            os.replace(tmp, out_fname)
    else:
        with open(out_fname, "w") as f:
            print(csv_header, file=f)
    return out_fname


def _health_monitoring(args, t: Training, rt, log):
    """``(monitor, policy, recovery)`` for ``--health_every`` (all None
    when off): signals ride the metrics pytree every step and are
    observed at the print cadence (the only points the LM loop fetches
    metrics — dispatch stays asynchronous)."""
    monitor = policy = recovery = None
    if args.health_every > 0:
        from ..resilience import (HealthMonitor, RecoveryPolicy,
                                  make_recovery_fn)

        monitor = HealthMonitor(health_every=args.health_every,
                                residual_floor=args.residual_floor,
                                log=log, registry=rt.registry)
        # overlap runs recover too: the compiled recovery average folds
        # the in-flight FIFO into Σx/Σw and drains it (recovery.py)
        if t.dp > 1 and hasattr(t.algorithm, "global_average"):
            plan = t.plan
            policy = RecoveryPolicy(
                world=t.dp, ppi=args.peers_per_itr, algorithm=t.mode,
                topology=plan.topology if plan is not None else None,
                residual_floor=args.residual_floor,
                cooldown_steps=args.health_every, log=log,
                registry=rt.registry, interconnect=t.interconnect,
                faults=bool(args.inject_faults),
                wire=wire_plan_config(args),
                synth=plan.synth if plan is not None else None)
            recovery = make_recovery_fn(t.algorithm, t.mesh)
    return monitor, policy, recovery


def _globalizer(t: Training):
    """Host batch → what ``train_fn`` takes: the array itself in one
    process, a global array over the mesh's batch dims on a pod."""
    import jax

    if jax.process_count() == 1:
        return lambda arr: arr
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel import GOSSIP_AXIS
    from ..train.lm import EP_AXIS, SEQ_AXIS

    lead = (GOSSIP_AXIS,) + ((EP_AXIS,) if t.ep > 1 else ()) \
        + ((SEQ_AXIS,) if t.ring else ())
    bsharding = NamedSharding(t.mesh, P(*lead))

    def globalize(arr):
        # every process materializes the same (seed-deterministic
        # synthetic) global batch and contributes only the shards its
        # devices address; a real corpus would shard the stream
        return jax.make_array_from_callback(
            arr.shape, bsharding, lambda idx: arr[idx])

    return globalize


def train_loop(args, t: Training, state, start_step: int, corpus,
               val_corpus, *, rt, log, ckpt, cluster, use_orbax: bool
               ) -> dict:
    """Run steps ``start_step .. --num_steps`` of the job ``t`` from
    ``state``: data, dispatch, the print-cadence metrics fetch (CSV row,
    health, recovery, validation), checkpoints and preemption — then the
    final checkpoint and the result line."""
    import jax
    import numpy as np

    from ..data.lm import lm_batches
    from ..parallel.multihost import host_local_slice, to_host
    from ..telemetry.setup_ledger import first_step
    from ..utils import Meter
    from ..utils.checkpoint import REQUEUE_EXIT_CODE
    from ..utils.profiling import ProfileWindow, StepWatchdog

    mesh, dp, sp, ep, pp = t.mesh, t.dp, t.sp, t.ep, t.pp
    alg, plan, ring = t.algorithm, t.plan, t.ring
    train_fn, eval_fn = t.train_fn, t.eval_fn
    proc_count, proc_index = jax.process_count(), jax.process_index()
    moe_on, val_on = args.moe_experts > 0, args.val_frac > 0

    # step-indexed jax.profiler capture (shared with the image harness;
    # utils/profiling.py: a profiler start that hangs or fails is logged
    # as an error and the run continues untraced)
    pw = ProfileWindow(args.profile_dir,
                       start_step=args.profile_start_step,
                       num_steps=args.profile_steps)

    def save_ckpt(st, step):
        """Checkpoint ``st`` (draining overlap in-flight shares into
        params first — algorithms.drain_state, the shared fold — so
        the checkpoint and the continuing run carry nothing in flight)
        and return the state the run should continue from."""
        from ..algorithms import drain_state

        st = drain_state(st)
        meta = {"step": step}
        if plan is not None:
            # reproducibility: the launch-time topology plan rides with
            # the state it shaped
            meta["plan"] = plan.to_dict()
        if monitor is not None and monitor.last_payload:
            # the run's consensus health at save time rides with the
            # state it describes (resilience/monitor.py)
            meta["health"] = monitor.last_payload
        with rt.span("checkpoint_save", "checkpoint"), \
                pw.span("checkpoint_save"):
            if use_orbax:
                # orbax steps are keyed by id: pass the step explicitly
                # (the live sharded state on pods, host conversion
                # single-process)
                ckpt.save(st, meta, epoch_id=step)
            else:
                ckpt.save(host_local_slice(st) if proc_count > 1 else st,
                          meta)
        return st

    out_fname = _open_csv(args, t.world, start_step, log)

    # heartbeat around the blocking metrics fetch (≙ the reference's 300s
    # gossip-flag timeout): a dead peer host shows up as a hung collective
    # at the next host readback, and silence is the worst failure mode.
    # Armed only from the second print point on — the first fetch drains
    # the queued compile, which can legitimately exceed any sane timeout.
    watchdog = (StepWatchdog(timeout=args.heartbeat_timeout,
                             rank=proc_index, registry=rt.registry)
                if args.heartbeat_timeout > 0 else None)
    prints_done = 0

    monitor, policy, recovery = _health_monitoring(args, t, rt, log)
    # (fetch time, steps_done, val_time) at the previous metrics
    # fetch — step-time samples are per-WINDOW deltas, so a straggler
    # phase moves p99 instead of dissolving into the lifetime mean
    health_window_start = None

    loss_meter = Meter(ptag="Loss")
    steps_done = start_step
    # resume fast-forward: restart the data stream where the saved run
    # left off instead of replaying consumed batches (≙ the sampler
    # fast-forward of the image harness, gossip_sgd.py:356-364)
    n_seqs = (len(corpus) - 1) // args.seq_len
    batches_per_epoch = max(1, n_seqs // (dp * ep * args.batch_size))
    epoch = start_step // batches_per_epoch
    skip_batches = start_step % batches_per_epoch
    last_saved = start_step - 1
    t0 = time.time()
    tokens_per_step = dp * ep * args.batch_size * args.seq_len
    # XLA CPU in-process collectives require serialized dispatch; on TPU we
    # fetch metrics only at print points so dispatch stays asynchronous
    serialize = jax.default_backend() == "cpu"
    metrics = None
    globalize = _globalizer(t)

    def host_metrics(m):
        # sharded metrics are not host-addressable on a pod: all-gather
        return (to_host(m, mesh) if proc_count > 1
                else jax.tree.map(np.asarray, m))

    val_time = 0.0  # excluded from the throughput window (see below)

    def shape_batch(arr):
        """lm_batches yields ``[dp·ep, sp, b, block]``; rearrange for the
        active mesh (shared by the train loop and validation so the two
        paths can never disagree).  One compositional shape — leading
        sharded dims ``[dp, ep?, sp?]`` (the batch_layout order), then
        the microbatch split for pipeline runs — covers every mesh."""
        block = args.seq_len // sp
        lead = (dp,) + ((ep,) if ep > 1 else ()) + ((sp,) if ring else ())
        if pp > 1:
            tail = (args.n_micro, args.batch_size // args.n_micro, block)
        else:
            tail = (args.batch_size, block)
        return arr.reshape(lead + tail)

    def run_validation(st):
        """Mean held-out loss over --val_batches batches (≙ validate,
        gossip_sgd.py:440-471).

        Wall time spent here — including the eval_fn compile on the first
        call — is accumulated into ``val_time`` and subtracted from the
        elapsed time used for tokens_per_sec, so validation cadence
        doesn't deflate the reported training throughput."""
        nonlocal val_time
        t_val = time.time()
        vals = []
        with rt.span("validate", "eval"), pw.span("validate"):
            for vt, vy in lm_batches(val_corpus, dp * ep, sp,
                                     args.batch_size, args.seq_len,
                                     seed=1):
                m = eval_fn(st, globalize(shape_batch(vt)),
                            globalize(shape_batch(vy)))
                if serialize:
                    jax.block_until_ready(m)
                vals.append(float(np.mean(host_metrics(m)["loss"])))
                if len(vals) >= args.val_batches:
                    break
        vl = float(np.mean(vals))
        val_time += time.time() - t_val
        return vl, float(np.exp(vl))

    last_val = None
    last_stats_emit = start_step

    def fetched(batches):
        """``batches`` with each draw marked as the loop's data fetch."""
        it = iter(batches)
        while True:
            with pw.span("data_fetch"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            yield batch

    try:
        while steps_done < args.num_steps:
            for tokens, targets in fetched(lm_batches(
                    corpus, dp * ep, sp, args.batch_size, args.seq_len,
                    seed=args.seed + epoch)):
                if skip_batches:
                    skip_batches -= 1
                    continue
                if pw.enabled:
                    pw.maybe_start(steps_done + 1)
                # the loop's phases by name in a --profile_dir capture
                # (telemetry/names.py); shared no-ops when none is active.
                # The process's first step is set-up's last phase and
                # ends with set-up's report; a shared no-op from then on
                with first_step(log, rt, steps_done + 1), \
                        pw.step(steps_done + 1):
                    with pw.span("data_fetch"):
                        x = globalize(shape_batch(tokens))
                        y = globalize(shape_batch(targets))
                    with pw.span("dispatch"):
                        state, metrics = train_fn(state, x, y)
                    if serialize or pw.active:
                        # the capture must cover the dispatched step even
                        # when the loop itself runs unserialized
                        with pw.span("fence"):
                            jax.block_until_ready(state)
                steps_done += 1
                if rt.comm is not None:
                    # step tick is 0-based (matches the algorithm's phase
                    # counter); host integer math, dispatch stays async
                    rt.comm.on_step(steps_done - 1)
                if pw.active:
                    pw.maybe_stop(steps_done)
                if steps_done % args.print_freq == 0 \
                        or steps_done >= args.num_steps:
                    guard = (watchdog.step()
                             if watchdog is not None and prints_done >= 1
                             else contextlib.nullcontext())
                    with guard, rt.span("metrics_fetch", "step",
                                        {"step": steps_done}
                                        if rt.enabled else None), \
                            pw.span("metrics_fetch"):
                        mh = host_metrics(metrics)
                    prints_done += 1
                    if monitor is not None:
                        from ..resilience.monitor import (EF_HEALTH_KEY,
                                                          HEALTH_KEYS)

                        # one sample per fetch window: the window's own
                        # average step time (validation time excluded), NOT
                        # the cumulative run average.  The first window is
                        # skipped — it carries the XLA compile.
                        now = time.time()
                        if health_window_start is not None:
                            t_prev, s_prev, v_prev = health_window_start
                            steps_in_window = steps_done - s_prev
                            if steps_in_window > 0:
                                elapsed = (now - t_prev) - (val_time - v_prev)
                                monitor.record_step_time(
                                    max(0.0, elapsed) / steps_in_window)
                        health_window_start = (now, steps_done, val_time)
                        sig = {k: float(np.asarray(mh[k]).ravel()[0])
                               for k in HEALTH_KEYS
                               + ((EF_HEALTH_KEY,)
                                  if EF_HEALTH_KEY in mh else ())}
                        report = monitor.observe(steps_done, sig)
                        if report.unhealthy and policy is not None:
                            event = policy.assess(report)
                            if event.action == "global-average":
                                with rt.span("recovery_global_average",
                                             "recovery"), \
                                        pw.span("recovery_global_average"):
                                    if getattr(alg, "overlap", False):
                                        new_p, new_w, new_fl = recovery(
                                            state.params,
                                            state.gossip.ps_weight,
                                            state.gossip.in_flight)
                                        new_g = state.gossip.replace(
                                            ps_weight=new_w,
                                            in_flight=new_fl)
                                    else:
                                        new_p, new_w = recovery(
                                            state.params,
                                            state.gossip.ps_weight)
                                        new_g = state.gossip.replace(
                                            ps_weight=new_w)
                                    state = state.replace(
                                        params=new_p, gossip=new_g)
                                if rt.comm is not None:
                                    rt.comm.on_recovery()
                    loss = float(np.mean(mh["loss"]))
                    loss_meter.update(loss)
                    tps = (tokens_per_step * (steps_done - start_step)
                           / (time.time() - t0 - val_time))
                    row = (f"{steps_done},{loss:.4f},"
                           f"{float(np.mean(mh['ppl'])):.2f},"
                           f"{float(np.mean(mh['lr'])):.5f},"
                           f"{tps:.0f},"
                           f"{float(np.mean(mh['grad_norm'])):.4f}")
                    if moe_on:
                        row += (",%.4f" % float(np.mean(mh['moe_dropped'])))
                    if "moe_expert_rows" in mh:
                        # the top-k layer drops nothing: what it reports is
                        # the load, summed over its layers, mean over ranks
                        rows = np.mean(mh["moe_expert_rows"], axis=0)
                        log.info(
                            f"moe: rows a held expert min {rows.min():.0f} "
                            f"mean {rows.mean():.0f} max {rows.max():.0f}; "
                            "pairs not held "
                            f"{float(np.mean(mh['moe_pairs_not_held'])):.0f}")
                    if rt.enabled and rt.metrics_every and \
                            steps_done - last_stats_emit >= rt.metrics_every:
                        # step_stats ride the print-cadence metrics fetch —
                        # the only host sync points of this loop
                        rt.registry.emit("step_stats", {
                            "loss": round(loss, 6),
                            "tokens_per_sec": round(tps, 1),
                            "grad_norm": round(
                                float(np.mean(mh["grad_norm"])), 6)},
                            step=steps_done)
                        rt.emit_comm(step=steps_done)
                        last_stats_emit = steps_done
                    if val_on:
                        val_due = ((args.val_every and steps_done
                                    % args.val_every == 0)
                                   or steps_done >= args.num_steps)
                        if val_due:
                            vl, vppl = run_validation(state)
                            last_val = vl
                            row += f",{vl:.4f},{vppl:.2f}"
                        else:
                            row += ",,"
                    with open(out_fname, "a") as f:
                        print(row, file=f)
                if args.ckpt_every and steps_done % args.ckpt_every == 0:
                    state = save_ckpt(state, steps_done)
                    last_saved = steps_done
                if cluster.any_rank_signalled():
                    # preemption: the in-flight step is done — save,
                    # record the exit reason, exit with the requeue code
                    log.warning(
                        "preemption signal (%s): checkpointing at step "
                        "%d and exiting %d (requeue me)",
                        cluster.last_signal or "peer flag", steps_done,
                        REQUEUE_EXIT_CODE)
                    state = save_ckpt(state, steps_done)
                    last_saved = steps_done
                    if use_orbax:
                        ckpt.wait()
                        ckpt.close()
                    if rt.enabled:
                        rt.registry.emit("run_meta", {
                            "exit_reason": "preempt-requeue",
                            "signal": cluster.last_signal,
                            "exit_code": REQUEUE_EXIT_CODE},
                            step=steps_done, severity="warning")
                    raise SystemExit(REQUEUE_EXIT_CODE)
                if steps_done >= args.num_steps:
                    break
            epoch += 1
        if last_saved != steps_done:
            state = save_ckpt(state, steps_done)
        if use_orbax:
            ckpt.wait()  # async saves must land before exit
            ckpt.close()
    finally:
        # a run that ended inside the capture window still dumps what it
        # got (close() is a no-op when no capture is active)
        pw.close()
        # trace.json + the final comm snapshot must survive a
        # crashed or interrupted run (same contract as the
        # Trainer's fit() finally); finish() is idempotent
        rt.finish(step=steps_done)

    result = {"attn": t.attn,
              "final_loss": loss_meter.val, "avg_loss": loss_meter.avg,
              "tokens_per_sec": tokens_per_step
              * (steps_done - start_step)
              / (time.time() - t0 - val_time)}
    if last_val is not None:
        result["val_loss"] = last_val
    log.info(json.dumps(result))
    return result


def main(argv=None):
    from ..utils.compile_cache import place_compile_cache

    place_compile_cache()     # and arms the set-up ledger
    from ..telemetry import make_run_telemetry, setup_phase

    with setup_phase("parse"):
        args = parse_args(argv)

    import jax

    from ..utils import make_logger
    from .gossip_sgd import _multihost_env

    # the devices come up here (the first question put to the backend)
    with setup_phase("mesh"):
        want_mh = args.multihost
        if want_mh == "True" or (want_mh == "auto" and _multihost_env()):
            from ..parallel.discovery import initialize_multihost

            initialize_multihost(args.coordinator_address,
                                 args.num_processes, args.process_id)
        proc_count, proc_index = jax.process_count(), jax.process_index()
    log = make_logger(f"lm p{proc_index}" if proc_count > 1 else "lm", True)

    # run telemetry BEFORE planning so the plan event and the loop share
    # one events.jsonl (the zero-overhead null bundle without --trace_dir)
    rt = make_run_telemetry(args.trace_dir, rank=proc_index, log=log,
                            metrics_every=args.metrics_every)
    t = build_training(args, log, rt.registry)
    _attach_accounting(args, t, rt)

    ckpt, cluster, use_orbax = _checkpointing(args, t)
    with setup_phase("resume"):
        state, start_step = _resume(args, t, ckpt, use_orbax, log)
    if start_step >= args.num_steps:
        log.info(f"nothing to do: resumed at step {start_step} >= "
                 f"num_steps {args.num_steps}")
        rt.finish(step=start_step)
        return {"final_loss": None, "avg_loss": None,
                "tokens_per_sec": 0.0, "already_complete": True}
    with setup_phase("data"):
        corpus, val_corpus = _load_corpus(args, t, log)
    return train_loop(args, t, state, start_step, corpus, val_corpus,
                      rt=rt, log=log, ckpt=ckpt, cluster=cluster,
                      use_orbax=use_orbax)


if __name__ == "__main__":
    main()
