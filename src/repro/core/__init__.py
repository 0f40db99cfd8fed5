# The paper's primary contribution: communication-layer profiling
# infrastructure — region annotation (Caliper analog), hierarchical
# GraphFrames (Hatchet analog), comparison-based profiling (method 1),
# chrome-trace timelines + automated analyses (method 2), and the TPU
# adaptation: HLO collective parsing, trip-count-correct cost attribution,
# roofline terms and modeled device timelines.
from . import (analyses, comparison, compat, counters, graphframe, hlo,
               hlo_cost, regions, timeline)
from .collector import Collector, global_collector, reset_global_collector
from .counters import (CounterLane, CounterRegistry, CounterStat,
                       counter_stats, global_registry, lane_events,
                       merge_lane_stats, reduce_lanes,
                       reset_global_registry)
from .comparison import (ComparisonResult, ProfileReport, ReportRow,
                         compare, compare_frames, profile_runs)
from .events import Event
from .graphframe import GraphFrame
from .regions import annotate, configure, profiled
from .roofline import HW, Roofline

__all__ = [
    "analyses", "comparison", "compat", "counters", "graphframe", "hlo",
    "hlo_cost", "regions", "timeline", "Collector", "global_collector",
    "reset_global_collector", "CounterLane", "CounterRegistry", "CounterStat",
    "counter_stats", "global_registry", "lane_events", "merge_lane_stats",
    "reduce_lanes", "reset_global_registry",
    "ComparisonResult", "ProfileReport", "ReportRow", "compare",
    "compare_frames", "profile_runs", "Event",
    "GraphFrame", "annotate", "configure", "profiled",
    "HW", "Roofline",
]
