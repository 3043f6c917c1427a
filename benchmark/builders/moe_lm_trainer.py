"""Builder ``moe_lm_trainer``: a sparse-expert LM described by its
source's ``config.json`` keys (the configuration's file itself is what
``--model_json`` reads), trained by the entry point's own assembly:
``gossip_lm.build_training(gossip_lm.parse_args(argv), log)`` gives mesh,
model, algorithm, step and state.  Every flag the configuration, the
traffic and the cell do not set is the parser's default, read now.  The
state is made by the one compiled initialisation whatever the seed
(``init_lm_state`` takes its key as an argument).

The comparison that decides ``correct`` is this file's: a top-k choice
flips on rounding where two scores nearly tie, so the plain reference is
*given the program's selection* for the logits and the loss, and its own
free choice, layer by layer on that same stream, has to agree with the
program's but for a small share of (token, layer) pairs, each a near-tie.
"""

from __future__ import annotations

import math
import os

import numpy as np

from benchmark import required_ops_moe, spec
from benchmark.job import Job
from benchmark.traffic.generate import make_batches

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# the flags a cell's files give (``--model_json`` the configuration's own
# file, sizes from the traffic, the seed folded): the hybrid builder's
argv_of = spec.load_plugin(ROOT, "builders", "hybrid_lm_trainer").argv_of


def selection_numbers(routing, selection) -> dict:
    """The program's ``selection`` (one ``[B, T, k]`` an expert layer)
    beside the reference's own choice on the same stream (``routing``,
    ``reference/lfm2_moe.py``): the share of (token, layer) pairs whose
    sets differ, and the widest margin by which a differing choice missed
    the reference's own ``s + b`` threshold."""
    import jax.numpy as jnp

    from benchmark.reference import lfm2_moe as plain

    gaps = jnp.stack([plain.selection_gap(r["chooser"], s)
                      for r, s in zip(routing, selection)])
    return {"selection_mismatch": float((gaps > 0).mean()),
            "selection_gap": float(gaps.max())}


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
        raise ValueError("builder moe_lm_trainer drives the flat "
                         "data-parallel mesh only")
    model, algorithm, source = t.model, t.algorithm, args.model_source
    rows = NamedSharding(t.mesh, P(GOSSIP_AXIS))
    batches = make_batches(cell.traffic, seed, (rows, rows))
    expert_layers = [f"block_{i}" for i in range(
        source["num_dense_layers"], source["num_hidden_layers"])]

    def compared(control):
        """One program: two seeded sequences (all there are) through the
        program's model (its compute dtype, its attention, its sorted
        grouped experts) and through the plain float32 reference given the
        program's selection, on rank 0's de-biased weights.  With
        ``control`` (an operand rounding, ``compare.rounded_to``) the
        reference computed in that lower precision, choosing freely,
        stands in the program's place.  Last of what it returns: the rows
        each held expert received in the program, ``[layers, held]``."""
        from benchmark.reference import lfm2_moe as plain

        @jax.jit
        def both(params, gossip, tokens):
            one = lambda tree: jax.tree.map(lambda a: a[0], tree)
            z = algorithm.eval_params(one(params), one(gossip))
            tokens = tokens[0, :2]
            if control is None:
                ours, sown = model.apply(
                    {"params": z}, tokens,
                    mutable=["moe_metrics", "moe_selection"])
                selection = [sown["moe_selection"][b]["moe"]["experts"][0]
                             for b in expert_layers]
                expert_rows = jnp.stack([
                    sown["moe_metrics"][b]["moe"]["expert_rows"][0]
                    for b in expert_layers])
            with jax.default_matmul_precision("highest"):
                if control is not None:
                    ours, routed = plain.lm_logits(z, tokens, source,
                                                   operand=control)
                    selection = [r["selection"] for r in routed]
                    expert_rows = None
                theirs, routing = plain.lm_logits(z, tokens, source,
                                                  selection=selection)
            return ours, theirs, selection, routing, expert_rows

        return lambda state: both(state.params, state.gossip, batches[0][0])

    program_beside_reference = compared(None)

    def reference_check(state, control=None):
        from benchmark.reference import compare, lfm2_moe as plain

        ours, theirs, selection, routing, _ = (
            program_beside_reference if control is None
            else compared(control))(state)
        limits = cell.config["reference"]
        out = compare.compare(ours, theirs, plain.lm_loss,
                              batches[0][1][0, :2], limits)
        out.update(selection_numbers(routing, selection),
                   selection_mismatch_tolerance=limits[
                       "selection_mismatch_tolerance"],
                   selection_gap_tolerance=limits["selection_gap_tolerance"])
        out["ok"] = bool(
            out["ok"]
            and out["selection_mismatch"] <= out[
                "selection_mismatch_tolerance"]
            and out["selection_gap"] <= out["selection_gap_tolerance"])
        return out

    moe_shapes = {
        "config": source,
        "itemsize": 2 if args.precision == "bf16" else 4,
        # the program's own counters on a resident batch, at the state
        # handed in: [layers, held experts]
        "expert_rows": lambda state: np.asarray(
            program_beside_reference(state)[-1])}

    return Job(
        reference_check=(reference_check if "reference" in cell.config
                         else None),
        step=t.train_fn, state=t.state, batches=batches,
        algorithm=algorithm, mesh=t.mesh, world=t.dp,
        items_per_rank_step=args.batch_size * args.seq_len, item="tokens",
        initial_loss=math.log(args.vocab_size),
        flops_per_rank_step=required_ops_moe.train_flops(
            args.batch_size, source, args.seq_len),
        shapes={"moe": moe_shapes},
        resolved={"attn": t.attn, "mode": t.mode,
                  "gossip_kernel": args.gossip_kernel})
