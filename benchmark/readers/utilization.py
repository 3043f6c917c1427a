"""Shares of the chip's published peaks, from required operations
(benchmark/required_ops.py) and measured times."""

from benchmark import required_ops


def mfu_pct(reading):
    """Operations one rank's step requires over the window's step time
    over the chip's bf16 peak.  One rank runs on one chip."""
    flops = reading.job.flops_per_rank_step
    if flops is None:
        return None
    return 100.0 * flops / (reading.step_ms * 1e-3) \
        / reading.peak["bf16_flops_per_s"]


def flash_roofline_pct(reading):
    """Least time the chip could take for the attention kernels' required
    operations and bytes of one step (forward and backward, every layer)
    over the time ``params.time_metric`` measured for them."""
    measured_ms = reading.values.get(reading.params["time_metric"])
    s = reading.job.shapes
    if not measured_ms or "head_dim" not in s:
        return None
    shape = dict(batch=s["batch"], heads=s["heads"], seq_len=s["seq_len"],
                 head_dim=s["head_dim"])
    flops = required_ops.flash_flops(**shape)
    nbytes = required_ops.flash_bytes(itemsize=s["itemsize"], **shape)
    least = sum(
        required_ops.roofline_seconds(flops[p], nbytes[p],
                                      reading.peak)["seconds"]
        for p in ("forward", "backward")) * s["n_layers"]
    return 100.0 * least * 1e3 / measured_ms
