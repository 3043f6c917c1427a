"""The comparison that joins ``correct``: the program's forward pass and
loss beside the plain reference's, on the same weights and inputs."""

import jax.numpy as jnp


def compare(program_logits, reference_logits, loss_of, tolerance: dict) -> dict:
    """``logit_error`` is the largest difference over the reference's
    largest logit; ``loss_error`` the difference of the two losses in
    nats.  ``tolerance`` (the configuration file's ``reference`` entry)
    bounds both and says why."""
    scale = float(jnp.abs(reference_logits).max())
    logit_error = float(
        jnp.abs(program_logits - reference_logits).max()) / scale
    loss_error = abs(float(loss_of(program_logits))
                     - float(loss_of(reference_logits)))
    return {"logit_error": logit_error, "loss_error": loss_error,
            "logit_tolerance": tolerance["logit_tolerance"],
            "loss_tolerance": tolerance["loss_tolerance"],
            "ok": bool(logit_error <= tolerance["logit_tolerance"]
                       and loss_error <= tolerance["loss_tolerance"])}
