"""Runtime: fault tolerance, stragglers, elastic scaling, and the
program's span recorder (``repro.runtime.spans``)."""

from .fault import FaultTolerantLoop, PreemptionGuard, StragglerMonitor

__all__ = ["FaultTolerantLoop", "PreemptionGuard", "StragglerMonitor"]
