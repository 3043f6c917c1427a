"""The gated delta rule's share of its roofline: required operations and
bytes (``benchmark/required_ops_olmo_hybrid.py``) against the device time
the trace shows under the program's ``lm.delta_rule`` scope.  It reads the
same work whatever implements the rule: products in XLA today, a kernel
later."""

from benchmark import required_ops, required_ops_olmo_hybrid


def delta_rule_roofline_pct(reading):
    """Least time the chip could take for the rule of one step — forward
    and backward, every ``linear_attention`` layer — over the time
    ``params.time_metric`` measured for it (recomputation included there,
    not here).  Nothing where the builder gives no ``delta_rule`` shapes
    or the time was not read."""
    measured_ms = reading.values.get(reading.params["time_metric"])
    s = reading.job.shapes.get("delta_rule")
    if not measured_ms or not s:
        return None
    shape = {k: s[k] for k in ("batch", "seq_len", "heads", "d_key",
                               "d_value")}
    flops = required_ops_olmo_hybrid.delta_rule_flops(**shape)
    nbytes = required_ops_olmo_hybrid.delta_rule_bytes(
        itemsize=s["itemsize"], **shape)
    least = sum(
        required_ops.roofline_seconds(flops[p], nbytes[p],
                                      reading.peak)["seconds"]
        for p in ("forward", "backward")) * s["layers"]
    return 100.0 * least * 1e3 / measured_ms
