"""The top-k expert layer's readers: its grouped products' share of
their roofline (required operations and bytes from
``benchmark/required_ops_moe.py`` against the device time under the
program's ``lm.moe.experts`` scope) and the load the router gave the held
experts.  Both read the same work whatever implements the products, and
both take the rows from the program's own counters (``moe_metrics``): the
builder's ``shapes["moe"]["expert_rows"]`` fetches them, ``[layers, held
experts]``, for a resident batch on the state the window left."""

from benchmark import required_ops_moe


def _counted_rows(reading):
    s = reading.job.shapes.get("moe")
    return None if not s else s["expert_rows"](reading.job.state)


def moe_experts_roofline_pct(reading):
    """Least time the chip could take for the held rows' three products
    of one step — forward and backward, every expert layer, the rows each
    layer's counters read — over the time ``params.time_metric`` measured
    for them (recomputation included there, not here).  Nothing where the
    builder gives no ``moe`` shapes or the time was not read."""
    measured_ms = reading.values.get(reading.params["time_metric"])
    rows = _counted_rows(reading) if measured_ms else None
    if rows is None:
        return None
    s = reading.job.shapes["moe"]
    least = required_ops_moe.experts_least_seconds(
        rows.sum(axis=1).tolist(), s["config"], reading.peak, s["itemsize"])
    return 100.0 * least * 1e3 / measured_ms


def moe_load_max_over_mean(reading):
    """The fullest held expert's rows over the mean of the held experts'
    rows, over every expert layer.  Nothing where the builder gives no
    ``moe`` shapes or no pair landed on a held expert."""
    rows = _counted_rows(reading)
    if rows is None or not rows.size or not rows.mean():
        return None
    return float(rows.max() / rows.mean())
