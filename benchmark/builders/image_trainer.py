"""Builder ``image_trainer``: what ``run/gossip_sgd.py::main`` does between
``parse_config`` and ``trainer.fit`` — plan, mesh, model, ``Trainer``,
``init_state``, the trainer's own ``_train_fn`` — without loaders,
checkpoints or a cluster manager.  Every flag the configuration, the
traffic and the cell do not set is the parser's default, read now.
"""

from __future__ import annotations

import dataclasses
import math

from benchmark import required_ops
from benchmark.job import Job
from benchmark.traffic.generate import fold_seed, make_batches

# configuration key -> the program's flag
_CONFIG_FLAGS = {"model": "--model", "image_size": "--image_size",
                 "num_classes": "--num_classes", "precision": "--precision"}
_DEPTH = {"resnet50": 50, "resnet101": 101, "resnet152": 152}


def argv_of(cell, seed: int) -> list[str]:
    config, traffic = cell.config, cell.traffic
    for key, theirs in (("image_size", "image_size"),
                        ("num_classes", "classes")):
        if config[key] != traffic[theirs]:
            raise ValueError(
                f"{cell.name}: configuration {key}={config[key]} but "
                f"traffic {cell.traffic_name} has {theirs}={traffic[theirs]}")
    argv = []
    for key, flag in _CONFIG_FLAGS.items():
        argv += [flag, str(config[key])]
    argv += ["--batch_size", str(traffic["batch_per_rank"]),
             "--world_size", str(traffic["ranks"]),
             "--seed", str(fold_seed(seed))]
    return argv + cell.flags


def build(cell, seed: int) -> Job:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stochastic_gradient_push_tpu.models import RESNETS, TinyCNN
    from stochastic_gradient_push_tpu.parallel import make_gossip_mesh
    from stochastic_gradient_push_tpu.run import gossip_sgd
    from stochastic_gradient_push_tpu.train.loop import Trainer
    from stochastic_gradient_push_tpu.train.lr import (
        CosineLRSchedule, LRSchedule, ppi_at_epoch)
    from stochastic_gradient_push_tpu.utils import make_logger

    cfg, args = gossip_sgd.parse_config(argv_of(cell, seed))
    if args.nprocs_per_node != 1:
        raise ValueError("builder image_trainer drives the flat gossip mesh")
    world = args.world_size
    log = make_logger("bench", cfg.verbose)
    gossip_sgd._resolve_plan(cfg, args, world, log)
    mesh = make_gossip_mesh(world)

    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    if args.model in RESNETS:
        model = RESNETS[args.model](
            num_classes=cfg.num_classes, dtype=dtype,
            stem_s2d=gossip_sgd._str_bool(args.stem_s2d))
    elif args.model == "tiny_cnn":
        model = TinyCNN(num_classes=cfg.num_classes, dtype=dtype)
    else:
        raise ValueError(f"unknown model {args.model}")

    channels = int(cell.traffic["channels"])
    trainer = Trainer(cfg, model, mesh, sample_input_shape=(
        cfg.batch_size, args.image_size, args.image_size, channels))
    # the schedule Trainer.fit builds before its first epoch
    # (train/loop.py:657-667); fit itself cannot be run for a time budget
    schedule = dict(ref_lr=cfg.lr, batch_size=cfg.batch_size,
                    world_size=trainer.world_size, warmup=cfg.warmup)
    trainer.lr_schedule_obj = (
        CosineLRSchedule(total_epochs=cfg.num_epochs, **schedule)
        if cfg.cosine_lr else
        LRSchedule(decay_schedule=cfg.lr_schedule, **schedule))

    rows = NamedSharding(mesh, P(trainer.gossip_axis))

    def init_state(seed):
        # Trainer.init_state reads cfg.seed: handed a traced one, so that
        # the seed is an argument of the program and not a constant in it
        # (every new seed would compile anew)
        trainer.cfg = dataclasses.replace(cfg, seed=seed)
        try:
            return trainer.init_state()
        finally:
            trainer.cfg = cfg

    # weights made on the device, from the seed, in one program
    state = jax.jit(init_state, out_shardings=rows)(jnp.int32(cfg.seed))
    algorithm, step = trainer._train_fn(
        ppi_at_epoch(cfg.ppi_schedule, 0), int(cell.file["itr_per_epoch"]))
    batches = make_batches(cell.traffic, seed, (rows, rows))

    if args.model in _DEPTH:
        flops = required_ops.resnet_train_flops(
            cfg.batch_size, depth=_DEPTH[args.model],
            image_size=args.image_size, num_classes=cfg.num_classes,
            channels=channels)
    else:
        flops = None    # no required-operations function: no mfu_pct
    def reference_check(state, control=None):
        """Eight seeded images through the program's model in training
        mode (its compute dtype, batch statistics) and through the plain
        float32 reference, on rank 0's de-biased weights; one program.
        With ``control`` (an operand rounding, ``compare.rounded_to``) the
        reference computed in that lower precision stands in the
        program's place."""
        from benchmark.reference import compare, resnet as plain

        stages = tuple(RESNETS[args.model].keywords["stage_sizes"])

        @jax.jit
        def both(params, gossip, batch_stats, images):
            one = lambda t: jax.tree.map(lambda a: a[0], t)
            z = algorithm.eval_params(one(params), one(gossip))
            images = images[0, :8]
            with jax.default_matmul_precision("highest"):
                theirs = plain.resnet_logits(z, images, stages)
                if control is not None:
                    return plain.resnet_logits(z, images, stages,
                                               control), theirs
            ours, _ = model.apply(
                {"params": z, "batch_stats": one(batch_stats)}, images,
                train=True, mutable=["batch_stats"])
            return ours, theirs

        ours, theirs = both(state.params, state.gossip, state.batch_stats,
                            batches[0][0])
        labels = batches[0][1][0, :8]
        return compare.compare(ours, theirs, plain.classification_loss,
                               labels, cell.config["reference"])

    return Job(
        reference_check=(reference_check if "reference" in cell.config
                         else None),
        step=step, state=state, batches=batches, algorithm=algorithm,
        mesh=mesh, world=world, items_per_rank_step=cfg.batch_size,
        item="img", initial_loss=math.log(cfg.num_classes),
        flops_per_rank_step=flops, shapes={},
        resolved={"graph": getattr(cfg.graph_class, "__name__", None),
                  "gossip_kernel": cfg.gossip_kernel})
