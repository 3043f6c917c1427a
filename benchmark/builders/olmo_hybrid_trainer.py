"""Builder ``olmo_hybrid_trainer``: an Olmo hybrid LM (gated delta-rule and
full attention layers) described by its source's ``config.json`` keys (the
configuration's file itself is what ``--model_json`` reads), trained by the
entry point's own assembly: ``gossip_lm.build_training(
gossip_lm.parse_args(argv), log)`` gives mesh, model, algorithm, step and
state.  Every flag the configuration, the traffic and the cell do not set
is the parser's default, read now.

The comparison that decides ``correct``: the program's forward pass (its
compute dtype, its flash kernels, its chunked rule) beside the plain
float32 reference with the delta rule token by token, on rank 0's
de-biased weights and the batch's one sequence.  Beside the two errors it
reports the program's own counter ``beta_above_one`` (the share of (token,
head) pairs a ``linear_attention`` layer writes with ``beta > 1``), one
number a layer.
"""

from __future__ import annotations

import math
import os

from benchmark import required_ops_olmo_hybrid, spec
from benchmark.job import Job
from benchmark.traffic.generate import make_batches

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# the flags a cell's files give (``--model_json`` the configuration's own
# file, sizes from the traffic, the seed folded): the hybrid builder's
argv_of = spec.load_plugin(ROOT, "builders", "hybrid_lm_trainer").argv_of


def build(cell, seed: int) -> Job:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS
    from stochastic_gradient_push_tpu.run import gossip_lm
    from stochastic_gradient_push_tpu.utils import make_logger

    args = gossip_lm.parse_args(argv_of(cell, seed))
    t = gossip_lm.build_training(args, make_logger("bench", True))
    if t.world != t.dp:
        raise ValueError("builder olmo_hybrid_trainer drives the flat "
                         "data-parallel mesh only")
    model, algorithm, source = t.model, t.algorithm, args.model_source
    rows = NamedSharding(t.mesh, P(GOSSIP_AXIS))
    batches = make_batches(cell.traffic, seed, (rows, rows))
    linear = [f"block_{i}" for i, kind in enumerate(source["layer_types"])
              if kind == "linear_attention"]

    def reference_check(state, control=None):
        """Two seeded sequences (all there are: the batch holds one)
        through the program's model and through the plain float32
        reference, one program.  With ``control`` (an operand rounding,
        ``compare.rounded_to``) the reference computed in that lower
        precision stands in the program's place."""
        from benchmark.reference import compare, olmo_hybrid as plain

        @jax.jit
        def both(params, gossip, tokens):
            one = lambda tree: jax.tree.map(lambda a: a[0], tree)
            z = algorithm.eval_params(one(params), one(gossip))
            tokens = tokens[0, :2]
            ours, sown = model.apply({"params": z}, tokens,
                                     mutable=["delta_metrics"])
            beta_above_one = jnp.stack([
                sown["delta_metrics"][b]["delta"]["beta_above_one"][0]
                for b in linear])
            with jax.default_matmul_precision("highest"):
                theirs = plain.lm_logits(z, tokens, source)
                if control is not None:
                    ours = plain.lm_logits(z, tokens, source,
                                           operand=control)
            return ours, theirs, beta_above_one

        ours, theirs, beta_above_one = both(state.params, state.gossip,
                                            batches[0][0])
        out = compare.compare(ours, theirs, plain.lm_loss,
                              batches[0][1][0, :2], cell.config["reference"])
        out["beta_above_one"] = [float(b) for b in beta_above_one]
        return out

    cfg = model.cfg
    itemsize = 2 if args.precision == "bf16" else 4
    return Job(
        reference_check=(reference_check if "reference" in cell.config
                         else None),
        step=t.train_fn, state=t.state, batches=batches,
        algorithm=algorithm, mesh=t.mesh, world=t.dp,
        items_per_rank_step=args.batch_size * args.seq_len, item="tokens",
        initial_loss=math.log(args.vocab_size),
        flops_per_rank_step=required_ops_olmo_hybrid.train_flops(
            args.batch_size, source, args.seq_len),
        # the flash kernels' (the full layers') and the rule's shapes
        shapes={"batch": args.batch_size, "heads": cfg.n_heads,
                "seq_len": args.seq_len,
                "head_dim": cfg.d_model // cfg.n_heads,
                "n_layers": len(source["layer_types"]) - len(linear),
                "itemsize": itemsize,
                "delta_rule": {
                    "batch": args.batch_size, "seq_len": args.seq_len,
                    "heads": cfg.delta.n_heads,
                    "d_key": cfg.delta.key_head_dim,
                    "d_value": cfg.delta.value_head_dim,
                    "layers": len(linear), "itemsize": itemsize}},
        resolved={"attn": t.attn, "mode": t.mode,
                  "gossip_kernel": args.gossip_kernel})
