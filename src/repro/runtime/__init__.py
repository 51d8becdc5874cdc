"""Cluster runtime: failure detection, elastic re-meshing, stragglers,
the serving stuck-tick watchdog, injectable clocks, and the serving
engine's tracer."""

from repro.runtime.clock import ManualClock, SystemClock  # noqa: F401
from repro.runtime.fault_tolerance import (  # noqa: F401
    ClusterMonitor, ElasticMeshManager, EngineWatchdog, StragglerPolicy,
    StuckTickError,
)
from repro.runtime.trace import Tracer  # noqa: F401
