"""Readers of the benchmark's own host clocks."""


def dispatch_ms(reading):
    """Call of the step until it returns, mean per step of the window."""
    return reading.host_ms["dispatch"]
