"""DFabric core: N-tier fabric topology, CommSchedule IR, cost model,
collectives (the schedule executor), planner, NIC-pool and memory-pool
arbiters."""
from repro.core.topology import (
    FabricSpec, HardwareSpec, Tier, TwoTierTopology, as_fabric,
    fabric_from_mesh_sizes, production_topology, three_tier_fabric)
from repro.core.nicpool import LaneGrant, LaneRequest, NicPool, waterfill
from repro.core.mempool import (
    MemDevice, MemGrant, MemPool, MemPoolSpec, MemRequest, mem_waterfill)
from repro.core.schedule import (
    AllGather, AllToAll, CommSchedule, Psum, ReduceScatter, SlowChunk,
    SyncConfig, all_to_all_from_axes, build_all_to_all, build_schedule,
    schedule_from_axes)
from repro.core.cost_model import (
    CostModel, CollectiveEstimate, LegCharge, NTierEstimate,
    ScheduleEstimate, TierCharge)
from repro.core.collectives import (
    dfabric_all_gather, dfabric_all_reduce, dfabric_all_to_all,
    dfabric_reduce_scatter, lower_all_reduce, lower_all_to_all,
    lower_reduce_scatter, ring_all_reduce)
from repro.core.planner import Planner, SyncPlan, Section

__all__ = [
    "FabricSpec", "HardwareSpec", "Tier", "TwoTierTopology", "as_fabric",
    "fabric_from_mesh_sizes", "production_topology", "three_tier_fabric",
    "LaneGrant", "LaneRequest", "NicPool", "waterfill",
    "MemDevice", "MemGrant", "MemPool", "MemPoolSpec", "MemRequest",
    "mem_waterfill",
    "AllGather", "AllToAll", "CommSchedule", "Psum", "ReduceScatter",
    "SlowChunk", "SyncConfig", "all_to_all_from_axes", "build_all_to_all",
    "build_schedule", "schedule_from_axes",
    "CostModel", "CollectiveEstimate", "LegCharge", "NTierEstimate",
    "ScheduleEstimate", "TierCharge",
    "dfabric_all_gather", "dfabric_all_reduce", "dfabric_all_to_all",
    "dfabric_reduce_scatter", "lower_all_reduce", "lower_all_to_all",
    "lower_reduce_scatter", "ring_all_reduce",
    "Planner", "SyncPlan", "Section",
]
