"""Decentralized data-parallel training algorithms."""

from .api import GossipAlgorithm, GossipState
from .algorithms import (
    GOSSIP_MODES,
    AllReduce,
    BilateralGossip,
    PushPullGossip,
    PushSumGossip,
    adpsgd,
    all_reduce,
    dpsgd,
    drain_in_flight,
    drain_state,
    gossip_algorithm,
    gossip_mode,
    osgp,
    sgp,
)

__all__ = [
    "GossipAlgorithm",
    "GossipState",
    "AllReduce",
    "PushSumGossip",
    "PushPullGossip",
    "BilateralGossip",
    "all_reduce",
    "sgp",
    "osgp",
    "dpsgd",
    "adpsgd",
    "GOSSIP_MODES",
    "gossip_mode",
    "gossip_algorithm",
    "drain_in_flight",
    "drain_state",
]
