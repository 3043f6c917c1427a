"""Builder ``lm_trainer``: the calls ``run/gossip_lm.py::main`` makes from
its parser to its train function, for the data-parallel mesh (``--sp``,
``--tp``, ``--ep``, ``--pp`` all 1): ``resolve_attention``, the plan,
``build_schedule``, ``sgp`` / ``dpsgd`` / ``all_reduce``, ``LRSchedule``,
``init_lm_state``, ``build_lm_train_step``, ``shard_lm_train_step``.
``main`` keeps this assembly inside one function, so it is repeated here
call for call (PERF.md §7 asks the program for a ``build_training``).
Every flag the configuration, the traffic and the cell do not set is the
parser's default, read now.
"""

from __future__ import annotations

import math

from benchmark import required_ops
from benchmark.job import Job
from benchmark.traffic.generate import fold_seed, make_batches

# the published config.json's key -> the program's flag
_CONFIG_FLAGS = {"n_embd": "--d_model", "n_layer": "--n_layers",
                 "n_head": "--n_heads", "n_inner": "--d_ff",
                 "vocab_size": "--vocab_size", "precision": "--precision"}


def argv_of(cell, seed: int) -> list[str]:
    config, traffic = cell.config, cell.traffic
    if config["vocab_size"] != traffic["vocab"]:
        raise ValueError(
            f"{cell.name}: configuration vocab_size={config['vocab_size']} "
            f"but traffic {cell.traffic_name} has vocab={traffic['vocab']}")
    argv = []
    for key, flag in _CONFIG_FLAGS.items():
        argv += [flag, str(config[key])]
    argv += ["--seq_len", str(traffic["seq_len"]),
             "--batch_size", str(traffic["batch_per_rank"]),
             "--world_size", str(traffic["ranks"]),
             "--seed", str(fold_seed(seed))]
    return argv + cell.flags


def build(cell, seed: int) -> Job:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stochastic_gradient_push_tpu.algorithms import (
        all_reduce, dpsgd, sgp)
    from stochastic_gradient_push_tpu.models.transformer import (
        TransformerConfig, TransformerLM)
    from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS
    from stochastic_gradient_push_tpu.parallel.wire import get_codec
    from stochastic_gradient_push_tpu.run import gossip_lm, gossip_sgd
    from stochastic_gradient_push_tpu.topology import (
        GRAPH_TOPOLOGIES, TOPOLOGY_NAMES, build_schedule)
    from stochastic_gradient_push_tpu.train import LRSchedule, sgd
    from stochastic_gradient_push_tpu.train.lm import (
        build_lm_train_step, init_lm_state, make_dp_sp_mesh,
        shard_lm_train_step)
    from stochastic_gradient_push_tpu.train.lr import WARMUP_EPOCHS
    from stochastic_gradient_push_tpu.utils import make_logger

    sb = gossip_sgd._str_bool
    args = gossip_lm.build_parser().parse_args(argv_of(cell, seed))
    if (args.sp, args.tp, args.ep, args.pp) != (1, 1, 1, 1) \
            or sb(args.bilat) or args.inject_faults or args.moe_experts:
        raise ValueError("builder lm_trainer drives the dense model on the "
                         "data-parallel mesh only")
    log = make_logger("bench", True)
    dp = args.world_size
    gossip_lm.resolve_wire_flags(args)
    gossip_lm.resolve_kernel_flag(args)
    gossip_lm.resolve_staleness_flag(args, sb(args.overlap))
    args.mixing_alpha = gossip_sgd._parse_mixing_alpha(args.mixing_alpha)

    plan = None
    if not sb(args.all_reduce) and dp > 1:
        from stochastic_gradient_push_tpu.planner import (
            make_interconnect, resolve_topology)

        plan = resolve_topology(
            dp, ppi=args.peers_per_itr, topology=args.topology,
            graph_class=GRAPH_TOPOLOGIES[args.graph_type],
            floor=args.gap_floor,
            algorithm="sgp" if sb(args.push_sum) else "dpsgd",
            self_weighted=(True if args.mixing_alpha == "auto"
                           else (args.mixing_alpha or False)),
            global_avg_every=args.global_avg_every,
            interconnect=make_interconnect(
                args.slice_size, args.dcn_cost, args.ici_cost),
            overlap=sb(args.overlap), faults=False,
            wire=gossip_lm.wire_plan_config(args),
            synth=gossip_lm.synth_plan_config(args), log=log)
    mesh = make_dp_sp_mesh(dp, 1)
    attn = gossip_lm.resolve_attention(
        args.attn, args.seq_len, 1, jax.default_backend(), log)

    cfg = TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads, d_ff=args.d_ff,
        max_len=args.seq_len,
        dtype=jnp.bfloat16 if args.precision == "bf16" else jnp.float32,
        attn_impl=attn, seq_axis=None,
        attn_block_size=args.attn_block or None,
        attn_block_k=args.attn_block_k or None,
        remat=sb(args.remat), moe_experts=0, moe_every=args.moe_every,
        ep_axis=None)
    model = TransformerLM(cfg)

    if sb(args.all_reduce):
        gossip_lm.reject_push_sum_wire_knobs(args)
        algorithm = all_reduce(GOSSIP_AXIS)
    else:
        if plan is not None:
            graph_cls = plan.graph_class
        elif args.topology:
            graph_cls = TOPOLOGY_NAMES[args.topology]
        else:
            graph_cls = GRAPH_TOPOLOGIES[args.graph_type]
        schedule = build_schedule(
            graph_cls(dp, peers_per_itr=args.peers_per_itr),
            plan.mixing_strategy() if plan is not None else None)
        common = dict(
            overlap=sb(args.overlap), staleness=max(1, args.staleness),
            global_avg_every=(plan.global_avg_every if plan is not None
                              else (args.global_avg_every or 0)),
            faults=None, gossip_kernel=args.gossip_kernel,
            gossip_buckets=args.gossip_buckets)
        if sb(args.push_sum):
            algorithm = sgp(
                schedule, GOSSIP_AXIS, gossip_every=args.gossip_every,
                wire=get_codec(args.wire_dtype, args.wire_block),
                error_feedback=bool(args.error_feedback), **common)
        else:
            gossip_lm.reject_push_sum_wire_knobs(args)
            algorithm = dpsgd(schedule, GOSSIP_AXIS, **common)

    tx = sgd(momentum=args.momentum, weight_decay=args.weight_decay,
             nesterov=sb(args.nesterov))
    warmup_steps = args.warmup_steps or max(args.num_steps // 10, 1)
    itr_per_epoch = max(warmup_steps // WARMUP_EPOCHS, 1)
    lrs = LRSchedule(ref_lr=args.lr, batch_size=args.batch_size,
                     world_size=dp, decay_schedule={},
                     warmup=sb(args.warmup))
    step = build_lm_train_step(
        model, algorithm, tx, lrs, itr_per_epoch=itr_per_epoch,
        seq_axis=None, ep_axis=None, grad_accum=args.grad_accum,
        health_axis=GOSSIP_AXIS if args.health_every > 0 else None)
    rows = NamedSharding(mesh, P(GOSSIP_AXIS))
    # the seed is an argument of the program, not a constant in it (every
    # new seed would compile anew)
    state = jax.jit(lambda seed: init_lm_state(
        model, mesh, algorithm, tx, dp=dp, sp=1,
        batch_size=args.batch_size, block_len=args.seq_len,
        seed=seed, seq_axis=None), out_shardings=rows)(jnp.int32(args.seed))
    train_fn = shard_lm_train_step(step, mesh, seq_axis=None, tp=False)

    batches = make_batches(cell.traffic, seed, (rows, rows))
    shape = dict(n_layers=args.n_layers, d_model=args.d_model,
                 d_ff=args.d_ff, vocab=args.vocab_size,
                 seq_len=args.seq_len)
    def reference_check(state, control=None):
        """Two seeded sequences (all there are, where the batch holds
        fewer) through the program's model (its compute dtype, its
        attention) and through the plain float32 reference, on rank 0's
        de-biased weights; one program.  With ``control`` (an operand
        rounding, ``compare.rounded_to``) the reference computed in that
        lower precision stands in the program's place."""
        from benchmark.reference import compare, lm as plain

        @jax.jit
        def both(params, gossip, tokens):
            one = lambda t: jax.tree.map(lambda a: a[0], t)
            z = algorithm.eval_params(one(params), one(gossip))
            tokens = tokens[0, :2]
            with jax.default_matmul_precision("highest"):
                theirs = plain.lm_logits(z, tokens, args.n_heads)
                if control is not None:
                    return plain.lm_logits(z, tokens, args.n_heads,
                                           operand=control), theirs
            return model.apply({"params": z}, tokens), theirs

        ours, theirs = both(state.params, state.gossip, batches[0][0])
        targets = batches[0][1][0, :2]
        return compare.compare(ours, theirs, plain.lm_loss, targets,
                               cell.config["reference"])

    return Job(
        reference_check=(reference_check if "reference" in cell.config
                         else None),
        step=train_fn, state=state, batches=batches, algorithm=algorithm,
        mesh=mesh, world=dp,
        items_per_rank_step=args.batch_size * args.seq_len, item="tokens",
        initial_loss=math.log(args.vocab_size),
        flops_per_rank_step=required_ops.lm_train_flops(
            args.batch_size, **shape),
        shapes={"batch": args.batch_size, "heads": args.n_heads,
                "seq_len": args.seq_len,
                "head_dim": args.d_model // args.n_heads,
                "n_layers": args.n_layers,
                "itemsize": 2 if args.precision == "bf16" else 4},
        resolved={"attn": attn, "graph": graph_cls.__name__
                  if not sb(args.all_reduce) else None,
                  "gossip_kernel": args.gossip_kernel})
