"""Builder ``hybrid_lm_trainer``: a model described by its source's
``config.json`` keys (the configuration's file itself is what
``--model_json`` reads), trained the way ``run/gossip_lm.py::main`` trains
it on the data-parallel mesh: ``resolve_model_json``,
``resolve_attention``, ``model_from_args``, the plan, ``build_schedule``,
``sgp`` / ``dpsgd`` / ``all_reduce``, ``LRSchedule``, ``init_lm_state``,
``build_lm_train_step``, ``shard_lm_train_step``.  The model comes from
the entry point's own ``model_from_args``; the rest of ``main``'s assembly
is repeated call for call as ``lm_trainer`` repeats it (PERF.md §7 asks
the program for a ``build_training``).  Every flag the configuration, the
traffic and the cell do not set is the parser's default, read now.
"""

from __future__ import annotations

import math
import os

from benchmark import required_ops_hybrid, spec
from benchmark.job import Job
from benchmark.traffic.generate import fold_seed, make_batches

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config_file(cell) -> str:
    """The cell's configuration file, which ``--model_json`` reads."""
    bench = spec.load_benchmark(ROOT)
    config = next(w["config"] for w in bench["workloads"]
                  if w["name"] == cell.name)
    return os.path.join(ROOT, next(c["file"] for c in bench["configs"]
                                   if c["name"] == config))


def argv_of(cell, seed: int) -> list[str]:
    config, traffic = cell.config, cell.traffic
    if config["vocab_size"] != traffic["vocab"]:
        raise ValueError(
            f"{cell.name}: configuration vocab_size={config['vocab_size']} "
            f"but traffic {cell.traffic_name} has vocab={traffic['vocab']}")
    return ["--model_json", _config_file(cell),
            "--precision", config["precision"],
            "--seq_len", str(traffic["seq_len"]),
            "--batch_size", str(traffic["batch_per_rank"]),
            "--world_size", str(traffic["ranks"]),
            "--seed", str(fold_seed(seed))] + cell.flags


def build(cell, seed: int) -> Job:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stochastic_gradient_push_tpu.algorithms import (
        all_reduce, dpsgd, sgp)
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
    gossip_lm.resolve_model_json(args)
    if sb(args.bilat) or args.inject_faults:
        raise ValueError("builder hybrid_lm_trainer drives SGP, D-PSGD "
                         "and AllReduce on the data-parallel mesh only")
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
    model = gossip_lm.model_from_args(args, attn)

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
    source = args.model_source

    def reference_check(state, control=None):
        """Two seeded sequences (all there are, where the batch holds
        fewer) through the program's model (its compute dtype, its
        attention, its chunked scan) and through the plain float32
        reference with the recurrence step by step, on rank 0's de-biased
        weights; one program.  With ``control`` (an operand rounding,
        ``compare.rounded_to``) the reference computed in that lower
        precision stands in the program's place."""
        from benchmark.reference import compare, granite_hybrid as plain

        @jax.jit
        def both(params, gossip, tokens):
            one = lambda t: jax.tree.map(lambda a: a[0], t)
            z = algorithm.eval_params(one(params), one(gossip))
            tokens = tokens[0, :2]
            with jax.default_matmul_precision("highest"):
                theirs = plain.lm_logits(z, tokens, source)
                if control is not None:
                    return plain.lm_logits(z, tokens, source,
                                           operand=control), theirs
            return model.apply({"params": z}, tokens), theirs

        ours, theirs = both(state.params, state.gossip, batches[0][0])
        targets = batches[0][1][0, :2]
        return compare.compare(ours, theirs, plain.lm_loss, targets,
                               cell.config["reference"])

    kinds = list(source["layer_types"])
    itemsize = 2 if args.precision == "bf16" else 4
    return Job(
        reference_check=(reference_check if "reference" in cell.config
                         else None),
        step=train_fn, state=state, batches=batches, algorithm=algorithm,
        mesh=mesh, world=dp,
        items_per_rank_step=args.batch_size * args.seq_len, item="tokens",
        initial_loss=math.log(args.vocab_size),
        flops_per_rank_step=required_ops_hybrid.hybrid_train_flops(
            args.batch_size, source, args.seq_len),
        shapes={"batch": args.batch_size, "heads": args.n_heads,
                "seq_len": args.seq_len,
                "head_dim": args.d_model // args.n_heads,
                "n_layers": kinds.count("attention"), "itemsize": itemsize,
                "ssd": {"batch": args.batch_size, "seq_len": args.seq_len,
                        "heads": source["mamba_n_heads"],
                        "head_dim": source["mamba_d_head"],
                        "state": source["mamba_d_state"],
                        "groups": source["mamba_n_groups"],
                        "chunk": source["mamba_chunk_size"],
                        "layers": kinds.count("mamba"),
                        "itemsize": itemsize}},
        resolved={"attn": attn, "graph": graph_cls.__name__
                  if not sb(args.all_reduce) else None,
                  "gossip_kernel": args.gossip_kernel})
