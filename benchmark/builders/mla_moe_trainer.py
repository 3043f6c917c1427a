"""Builder ``mla_moe_trainer``: a decoder of the ``joyai_llm_flash`` family
(latent attention, top-k experts with a shared one, one
multi-token-prediction module) described by its source's ``config.json``
keys — the configuration's file itself is what ``--model_json`` reads —
and trained by the entry point's own assembly:
``gossip_lm.build_training(gossip_lm.parse_args(argv), log)``.  Every
flag the configuration, the traffic and the cell do not set is the
parser's default, read now.

Set-up then sets the router's selection bias, once, by DeepSeek-V3's sign
rule (:func:`balance_expert_biases`) on the seed's first resident batch,
one expert layer at a time, in one program: so every seed starts the
window with the held experts at their share of the pairs, as a trained
router gives them.  The train step does not update the bias (the paper
does, every step): it stays as set, the router's weights train, and by
the window's end the held experts' share of the pairs has drifted from
1/16 and differs from seed to seed, so the experts' per-layer metrics
read the drifted rows.

The comparison that decides ``correct`` is ``moe_lm_trainer``'s: the plain
reference is *given the program's selection* for the logits and the
losses (the trunk's and the module's), and its own free choice, layer by
layer on that same stream, has to agree with the program's but for a
small share of (token, layer) pairs, each a near-tie.
"""

from __future__ import annotations

import math
import os

import numpy as np

from benchmark import required_ops_mla, spec
from benchmark.job import Job
from benchmark.traffic.generate import make_batches

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the flags a cell's files give: the hybrid builder's
argv_of = spec.load_plugin(ROOT, "builders", "hybrid_lm_trainer").argv_of
# the reference's free choice beside the program's: the lfm2 builder's
selection_numbers = spec.load_plugin(
    ROOT, "builders", "moe_lm_trainer").selection_numbers

# every routed expert's load within this share of the mean after set-up
BALANCE_BAND = 0.05


def expert_layers(cfg) -> list[str]:
    """The model's expert layers in the order the stream meets them: the
    trunk's, then the multi-token-prediction module's."""
    return [f"block_{i}" for i in range(cfg.n_layers)
            if cfg.ffn_type(i) == "experts"] \
        + (["mtp_block"] if cfg.mtp_layers else [])


# the sign rule's step: GAMMA at first, shrinking by DECAY a step, so
# that a load far from the mean moves fast and one near it settles; at
# most MAX_STEPS steps (a skew of 0 to 8.6 times the mean load over 256
# experts and 8192 tokens settles within 5 % in under 500)
GAMMA, DECAY, MAX_STEPS = 0.01, 0.995, 4000


def balance_bias(scores, bias, per_token: int, *, band: float):
    """The selection bias of one layer by DeepSeek-V3's sign rule
    (arXiv:2412.19437 §2.1.2), ``b_e <- b_e + gamma_i * sign(mean load -
    load_e)`` with ``gamma_i = GAMMA * DECAY ** i``, on fixed ``scores``
    ``[T, E]``: repeated until every expert's load — the pairs whose top
    ``per_token`` of ``scores + b`` name it — lies within ``band`` of the
    mean, ``T * per_token / E``, or ``MAX_STEPS`` have run.  One
    ``while_loop``.  Returns ``(bias, loads [E], steps)``."""
    import jax.numpy as jnp
    from jax import lax

    t, e = scores.shape
    mean = t * per_token / e
    scores = lax.stop_gradient(scores.astype(jnp.float32))

    def loads(b):
        _, selection = lax.top_k(scores + b, per_token)
        return jnp.bincount(selection.reshape(-1), length=e)

    def outside(counts):
        return jnp.abs(counts - mean).max() > band * mean

    def cond(carry):
        i, _, counts = carry
        return (i < MAX_STEPS) & outside(counts)

    def body(carry):
        i, b, counts = carry
        step = GAMMA * DECAY ** i.astype(jnp.float32)
        b = b + step * jnp.sign(mean - counts)
        return i + 1, b, loads(b)

    bias = bias.astype(jnp.float32)
    steps, bias, counts = lax.while_loop(
        cond, body, (jnp.int32(0), bias, loads(bias)))
    return bias, counts, steps


def balance_expert_biases(scores_of, params, layers, per_token: int, *,
                          band: float):
    """Every listed expert layer's selection bias set by
    :func:`balance_bias`, one layer at a time in the order given, each on
    the scores the layers before it (balanced already) hand it.

    ``scores_of(params)`` runs the model and returns its sown router
    scores, a tree in which ``layer + ("scores",)`` leads to a one-tuple
    ``([T, E],)``; ``layers`` are paths of the expert modules in
    ``params`` (each holds ``expert_bias``).  Call it under one ``jit``:
    the layers' forward passes and loops are one program, and XLA keeps of
    each pass only what leads to the layer's scores.  Returns ``(params,
    loads [layers, E], steps [layers])``."""
    import jax.numpy as jnp

    def get(tree, path):
        for key in path:
            tree = tree[key]
        return tree

    def put(tree, path, value):
        if not path:
            return value
        return {**tree, path[0]: put(tree[path[0]], path[1:], value)}

    all_loads, all_steps = [], []
    for layer in layers:
        scores = get(scores_of(params), tuple(layer) + ("scores",))[0]
        module = get(params, layer)
        bias, counts, steps = balance_bias(
            scores.reshape(-1, scores.shape[-1]), module["expert_bias"],
            per_token, band=band)
        params = put(params, tuple(layer), {**module, "expert_bias": bias})
        all_loads.append(counts)
        all_steps.append(steps)
    return params, jnp.stack(all_loads), jnp.stack(all_steps)


def balance(model, state, tokens):
    """``state`` with every expert layer's selection bias set by the sign
    rule on ``tokens`` ``[1, B, T]`` (rank 0's), and the loads ``[layers,
    E]`` and steps it ended at; one program."""
    import jax

    layers = [(name, "moe") for name in expert_layers(model.cfg)]

    @jax.jit
    def run(params, tokens):
        one = jax.tree.map(lambda a: a[0], params)
        scores_of = lambda p: model.apply(
            {"params": p}, tokens[0], mutable=["moe_scores"])[1]["moe_scores"]
        one, loads, steps = balance_expert_biases(
            scores_of, one, layers, model.cfg.experts.per_token,
            band=BALANCE_BAND)
        return jax.tree.map(lambda a: a[None], one), loads, steps

    params, loads, steps = run(state.params, tokens)
    params = jax.tree.map(lambda new, old: jax.device_put(new, old.sharding),
                          params, state.params)
    return state.replace(params=params), np.asarray(loads), np.asarray(steps)


def build(cell, seed: int) -> Job:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS
    from stochastic_gradient_push_tpu.run import gossip_lm
    from stochastic_gradient_push_tpu.utils import make_logger

    log = make_logger("bench", True)
    args = gossip_lm.parse_args(argv_of(cell, seed))
    t = gossip_lm.build_training(args, log)
    if t.world != t.dp:
        raise ValueError("builder mla_moe_trainer drives the flat "
                         "data-parallel mesh only")
    model, algorithm, source = t.model, t.algorithm, args.model_source
    rows = NamedSharding(t.mesh, P(GOSSIP_AXIS))
    batches = make_batches(cell.traffic, seed, (rows, rows))
    state, loads, steps = balance(model, t.state, batches[0][0])
    # each layer's loads over their mean, T * k / E, fewest and most
    spread = loads / loads.mean(axis=1, keepdims=True)
    spread = [float(spread.min()), float(spread.max())]
    log.info(f"selection bias set: steps a layer {steps.tolist()}, loads over "
             f"their mean {spread[0]:.3f} .. {spread[1]:.3f}")
    from stochastic_gradient_push_tpu.train.lm import MTP_LOSS_WEIGHT

    layers = expert_layers(model.cfg)
    weight = MTP_LOSS_WEIGHT if model.cfg.mtp_layers else 0.0

    def compared(control):
        """One program: two seeded sequences (all there are) through the
        program's model and through the plain float32 reference given the
        program's selection, on rank 0's de-biased weights; with
        ``control`` (``compare.rounded_to``) the reference in that lower
        precision, choosing freely, stands in the program's place.
        Returns the trunk's and the module's logits of both sides, the
        selection, the reference's routing and the rows each held expert
        received in the program, ``[layers, held]``."""
        from benchmark.reference import joyai_flash as plain

        @jax.jit
        def both(params, gossip, tokens):
            one = lambda tree: jax.tree.map(lambda a: a[0], tree)
            z = algorithm.eval_params(one(params), one(gossip))
            tokens = tokens[0, :2]
            expert_rows = None
            if control is None:
                logits, sown = model.apply(
                    {"params": z}, tokens,
                    mutable=["moe_metrics", "moe_selection", "mtp"])
                ours = (logits, sown["mtp"]["logits"][0])
                selection = [sown["moe_selection"][b]["moe"]["experts"][0]
                             for b in layers]
                expert_rows = jnp.stack([
                    sown["moe_metrics"][b]["moe"]["expert_rows"][0]
                    for b in layers])
            with jax.default_matmul_precision("highest"):
                if control is not None:
                    *ours, routed = plain.lm_logits(z, tokens, source,
                                                    operand=control)
                    selection = [r["selection"] for r in routed]
                *theirs, routing = plain.lm_logits(z, tokens, source,
                                                   selection=selection)
            return ours, theirs, selection, routing, expert_rows

        return lambda state: both(state.params, state.gossip, batches[0][0])

    program_beside_reference = compared(None)
    last = {}

    def reference_check(state, control=None):
        from benchmark.reference import compare, joyai_flash as plain

        ours, theirs, selection, routing, expert_rows = (
            program_beside_reference if control is None
            else compared(control))(state)
        limits = cell.config["reference"]
        targets = batches[0][1][0, :2]
        out = compare.compare(ours[0], theirs[0], plain.lm_loss, targets,
                              limits)
        ahead = compare.compare(ours[1], theirs[1], plain.mtp_loss, targets,
                                limits)
        out.update({f"mtp_{k}": ahead[k]
                    for k in ("logit_error", "loss_error")},
                   **selection_numbers(routing, selection),
                   selection_mismatch_tolerance=limits[
                       "selection_mismatch_tolerance"],
                   selection_gap_tolerance=limits["selection_gap_tolerance"],
                   mtp_loss_weight=weight)
        if expert_rows is not None:
            last.update(state=state, rows=np.asarray(expert_rows))
            pairs = len(layers) * targets.size \
                * model.cfg.experts.per_token
            out["held_share"] = float(last["rows"].sum() / pairs)
        out["ok"] = bool(
            out["ok"] and ahead["ok"]
            and out["selection_mismatch"] <= out[
                "selection_mismatch_tolerance"]
            and out["selection_gap"] <= out["selection_gap_tolerance"])
        return out

    def counted_rows(state):
        """The program's own counters on resident batch 0 at ``state``,
        ``[layers, held experts]``: the comparison's, where it ran on this
        very state."""
        if last.get("state") is not state:
            reference_check(state)
        return last["rows"]

    itemsize = 2 if args.precision == "bf16" else 4
    return Job(
        reference_check=(reference_check if "reference" in cell.config
                         else None),
        step=t.train_fn, state=state, batches=batches,
        algorithm=algorithm, mesh=t.mesh, world=t.dp,
        items_per_rank_step=args.batch_size * args.seq_len, item="tokens",
        initial_loss=(1.0 + weight) * math.log(args.vocab_size),
        flops_per_rank_step=required_ops_mla.train_flops(
            args.batch_size, source, args.seq_len),
        shapes={"moe": {"config": required_ops_mla.moe_config(source),
                        "itemsize": itemsize, "expert_rows": counted_rows},
                "mla": {"batch": args.batch_size,
                        "heads": source["num_attention_heads"],
                        "seq_len": args.seq_len,
                        "d_qk": required_ops_mla.d_qk(source),
                        "d_v": source["v_head_dim"],
                        "layers": required_ops_mla.latent_layers(source),
                        "itemsize": itemsize}},
        resolved={"attn": t.attn, "mode": t.mode,
                  "gossip_kernel": args.gossip_kernel,
                  "bias_steps": steps.tolist(),
                  "bias_load_over_mean": spread})
