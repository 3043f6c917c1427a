"""The comparison that joins ``correct``: the program's forward pass and
loss beside the plain reference's, on the same weights and inputs."""

import jax
import jax.numpy as jnp


# the nearest precision below the one a configuration states: the step that
# would tempt a later PR, and what the control computes the reference in
NEXT_LOWER = {"fp32": "bfloat16", "bf16": "float8_e4m3fn"}


def rounded_to(precision: str):
    """The control of a configuration that computes in ``precision``: a
    function that rounds an operand of a product to the next lower
    precision and hands it back in float32."""
    lower = jnp.dtype(NEXT_LOWER[precision])
    return lambda a: a.astype(lower).astype(jnp.float32)


def compare(program_logits, reference_logits, loss_fn, targets,
            tolerance: dict) -> dict:
    """``logit_error`` is the largest difference over the reference's
    largest logit; ``loss_error`` the difference of the two losses
    (``loss_fn(logits, targets)``) in nats.  ``tolerance`` (the
    configuration file's ``reference`` entry) bounds both and says why.
    The four numbers come out of one program: logits of 8192 tokens over
    a 50257-wide vocabulary are 1.6 GB a side, and each operation on them
    made one by one would hold as much again."""
    @jax.jit
    def numbers(ours, theirs, targets):
        return (jnp.abs(theirs).max(), jnp.abs(ours - theirs).max(),
                loss_fn(ours, targets), loss_fn(theirs, targets))

    scale, gap, our_loss, their_loss = (
        float(v) for v in numbers(program_logits, reference_logits, targets))
    logit_error = gap / scale
    loss_error = abs(our_loss - their_loss)
    return {"logit_error": logit_error, "loss_error": loss_error,
            "logit_tolerance": tolerance["logit_tolerance"],
            "loss_tolerance": tolerance["loss_tolerance"],
            "ok": bool(logit_error <= tolerance["logit_tolerance"]
                       and loss_error <= tolerance["loss_tolerance"])}
