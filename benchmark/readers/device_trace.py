"""Readers of the device trace (benchmark/trace_reduce.py does the
arithmetic).  Each returns milliseconds per traced step, mean over the
chips, or nothing when the run took no trace or the trace shows no
chip."""

from benchmark import trace_reduce as tr


def _per_step_ms(reading, seconds_on_chip):
    if reading.trace is None or not reading.traced_steps:
        return None
    seconds = tr.mean_over_devices(reading.trace, seconds_on_chip)
    if seconds is None:
        return None
    return seconds * 1e3 / reading.traced_steps


def collective_ms(reading):
    """Time in which a collective was in flight on a chip."""
    return _per_step_ms(reading, lambda ev, flying: tr.total(tr.clip(
        tr.collective_intervals(ev, flying), *reading.window)))


def exposed_collective_ms(reading):
    """The part of it during which nothing else ran on that chip."""
    return _per_step_ms(reading, lambda ev, flying: tr.total(tr.clip(
        tr.exposed_collective_intervals(ev, flying), *reading.window)))


def kernel_ms(reading):
    """Device time of the operations matching ``params.pattern``."""
    return _per_step_ms(reading, lambda ev, _: tr.matching_seconds(
        ev, reading.params["pattern"], reading.window))


def device_idle_pct(reading):
    """1 - busy over the traced stretch, mean over the chips."""
    if reading.busy_s is None:
        return None
    lo, hi = reading.window
    return 100.0 * (1.0 - reading.busy_s / (hi - lo))
