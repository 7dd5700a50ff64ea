from .fault import FaultInjection, StragglerMonitor, TrainSupervisor
from .elastic import elastic_restore, divisor_meshes
from .telemetry import (Counter, Gauge, Histogram, MetricsRegistry,
                        MetricsSnapshotter, NullTracer, Tracer,
                        make_tracer, metric_attr, percentile)

__all__ = ["FaultInjection", "StragglerMonitor", "TrainSupervisor",
           "elastic_restore", "divisor_meshes",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "MetricsSnapshotter", "NullTracer", "Tracer",
           "make_tracer", "metric_attr", "percentile"]
