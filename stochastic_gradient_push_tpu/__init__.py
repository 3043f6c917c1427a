"""stochastic_gradient_push_tpu — TPU-native decentralized data-parallel training.

A ground-up JAX/XLA re-design of the capabilities of
facebookresearch/stochastic_gradient_push: AllReduce SGD, Stochastic Gradient
Push (SGP), Overlap SGP (OSGP), D-PSGD, and AD-PSGD over time-varying gossip
topologies.  Gossip graphs compile to static ``lax.ppermute`` schedules over
the ICI mesh; averaging runs inside the jitted train step — no host gossip
threads, no process groups, no pinned-memory staging.
"""

__version__ = "0.1.0"

from .topology import (  # noqa: F401
    GRAPH_TOPOLOGIES,
    MIXING_STRATEGIES,
    DynamicBipartiteExponentialGraph,
    DynamicBipartiteLinearGraph,
    DynamicDirectedExponentialGraph,
    DynamicDirectedLinearGraph,
    GossipSchedule,
    GraphTopology,
    MixingStrategy,
    NPeerDynamicDirectedExponentialGraph,
    SelfWeightedMixing,
    RingGraph,
    UniformMixing,
    build_pairing_schedule,
    build_schedule,
)
