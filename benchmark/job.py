"""What a builder hands the harness: the program's own step, its state,
resident batches, and the facts the checks and the readers need."""

from __future__ import annotations

import dataclasses
import typing as tp


@dataclasses.dataclass
class Job:
    step: tp.Callable        # the program's jitted (state, x, y) -> (state, metrics)
    state: tp.Any            # world-stacked TrainState, on the cell's chips
    batches: list[tuple]     # resident (inputs, targets), leading dim = ranks
    algorithm: tp.Any        # the GossipAlgorithm the step was built with
    mesh: tp.Any
    world: int
    items_per_rank_step: int  # images or tokens one rank takes a step
    item: str                 # "img" or "tokens"
    initial_loss: float       # ln(classes) or ln(vocab): a random model's loss
    # required operations (benchmark/required_ops.py); None: no count
    flops_per_rank_step: float | None
    shapes: dict              # sizes the readers use (flash: B, H, T, D, layers)
    resolved: dict            # what the program's own rules chose (attn, graph)
    # the program's forward pass beside the plain reference's on the
    # current state (benchmark/reference/); None where the configuration
    # file gives no ``reference`` tolerance
    reference_check: tp.Callable[[tp.Any], dict] | None = None
