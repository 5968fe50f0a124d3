"""The paper's contribution: J-DOB scheduling for multiuser co-inference."""
from .telemetry import (NULL_TRACER, Histogram, MetricsRegistry, NullTracer,
                        Telemetry, Tracer, aggregate_counter_fields,
                        tenant_tid, validate_events, validate_trace_file)
from .task_model import TaskProfile, mobilenet_v2_profile, profile_from_arch
from .channel import (CHANNEL_KINDS, ChannelModel, SharedUplink,
                      StaticChannel, TraceChannel, UploadSession, UploadSpan,
                      make_channel, markov_fading_gains)
from .cost_models import (DeviceFleet, EdgeProfile, make_edge_profile,
                          make_tpu_v5e_edge_profile, make_fleet)
from .jdob import (BatchedPlanner, ExecutableCache, PendingPlans,
                   PlannerStats, Schedule, jdob_schedule, jdob_energy_grid,
                   jdob_plan_batched, make_f_sweep, shared_executable_cache)
from .reference import jdob_reference
from .baselines import (STRATEGIES, local_computing, ip_ssa,
                        jdob_no_edge_dvfs, jdob_binary, jdob_plus)
from .planner_service import PlanAheadPool, PlannerService, planner_spec
from .bruteforce import brute_force
from .grouping import (GroupedSchedule, IncrementalOgState,
                       bruteforce_grouping, optimal_grouping,
                       optimal_grouping_reference, single_group)
from .cohort import cohort_bounds, cohort_grouping
from .timeline import (OCCUPANCY_MODES, GpuTimeline, Reservation,
                       TimelineCursor, rescale_edge_dvfs, respeed_edge_dvfs)
from .online import (FlushEvent, GpuFreeEvent, OnlineArrival, OnlineResult,
                     OnlineScheduler, UploadEvent, all_local_energy,
                     oracle_bound, poisson_arrivals, simulate_online,
                     simulate_online_reference)
from .tenancy import (ADMISSION_POLICIES, Booking, GpuLedger,
                      MultiTenantResult, MultiTenantScheduler, ReplanRecord,
                      Tenant, TenantResult, min_offload_completion,
                      naive_fifo, single_tenant_oracle)

__all__ = [
    "TaskProfile", "mobilenet_v2_profile", "profile_from_arch",
    "CHANNEL_KINDS", "ChannelModel", "SharedUplink", "StaticChannel",
    "TraceChannel", "UploadSession", "UploadSpan", "make_channel",
    "markov_fading_gains",
    "DeviceFleet", "EdgeProfile", "make_edge_profile",
    "make_tpu_v5e_edge_profile", "make_fleet",
    "BatchedPlanner", "ExecutableCache", "PendingPlans", "PlannerStats",
    "Schedule",
    "jdob_schedule", "jdob_energy_grid", "jdob_plan_batched", "make_f_sweep",
    "shared_executable_cache",
    "jdob_reference", "STRATEGIES", "local_computing", "ip_ssa",
    "jdob_no_edge_dvfs", "jdob_binary", "jdob_plus",
    "PlanAheadPool", "PlannerService", "planner_spec",
    "brute_force",
    "GroupedSchedule", "IncrementalOgState", "bruteforce_grouping",
    "optimal_grouping", "optimal_grouping_reference", "single_group",
    "cohort_bounds", "cohort_grouping",
    "OCCUPANCY_MODES", "GpuTimeline", "Reservation", "TimelineCursor",
    "rescale_edge_dvfs", "respeed_edge_dvfs",
    "FlushEvent", "GpuFreeEvent", "OnlineArrival", "OnlineResult",
    "OnlineScheduler", "UploadEvent", "simulate_online",
    "simulate_online_reference",
    "oracle_bound", "all_local_energy", "poisson_arrivals",
    "ADMISSION_POLICIES", "Booking", "GpuLedger", "MultiTenantResult",
    "MultiTenantScheduler", "ReplanRecord", "Tenant", "TenantResult",
    "min_offload_completion", "naive_fifo", "single_tenant_oracle",
    "NULL_TRACER", "Histogram", "MetricsRegistry", "NullTracer", "Telemetry",
    "Tracer", "aggregate_counter_fields", "tenant_tid", "validate_events",
    "validate_trace_file",
]
