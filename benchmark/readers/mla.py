"""Latent attention's share of its roofline: the core's required
operations and bytes at q·k and v widths apart
(``benchmark/required_ops_mla.py``) against the device time of the flash
kernels the trace shows (``params.time_metric``).  Nothing where the
builder gives no ``mla`` shapes, as on a program without latent
attention."""

from benchmark import required_ops_mla


def mla_flash_roofline_pct(reading):
    """Least time the chip could take for every latent layer's core of
    one step, forward and backward, over the time the kernels took
    (recomputation included there, not here)."""
    measured_ms = reading.values.get(reading.params["time_metric"])
    s = reading.job.shapes.get("mla")
    if not measured_ms or not s:
        return None
    least = required_ops_mla.core_least_seconds(s, reading.peak)
    return 100.0 * least * 1e3 / measured_ms
