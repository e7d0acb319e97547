"""Runtime: fault tolerance, straggler mitigation, elastic restart logic,
host spans on the profiler's timeline."""
from .fault import FaultTolerantLoop, Heartbeat, StragglerMonitor
from .spans import span

__all__ = ["FaultTolerantLoop", "Heartbeat", "StragglerMonitor", "span"]
