"""Shared utilities: metering, logging, profiling."""

from .flatten import global_norm
from .logging import make_logger, reset_logger
from .meter import Meter, PercentileMeter
from .profiling import HEARTBEAT_TIMEOUT, StepWatchdog, trace

__all__ = [
    "Meter",
    "PercentileMeter",
    "make_logger",
    "reset_logger",
    "global_norm",
    "StepWatchdog",
    "trace",
    "HEARTBEAT_TIMEOUT",
]
