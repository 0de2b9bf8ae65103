"""Event-stream serving runtime.

Stateful SNN sessions, slot-multiplexed micro-batching over per-tier
jitted chunk steps with double-buffered event staging, asynchronous
source ingestion, occupancy-driven adaptive pipeline depth, per-stream
gated OSSL adaptation on a frozen shared base, live DSST topology
evolution, and per-stream/tier/fleet energy telemetry. See
``docs/ARCHITECTURE.md`` / ``docs/SERVING.md`` and the modules'
docstrings for the architecture.
"""
from .adapt import AdaptConfig, delta_norms, make_chunk_fn, merge_lane_into_base
from .autopilot import AutopilotConfig, DepthAutopilot
from .checkpointing import restore_fleet, save_fleet
from .ingest import IngestConfig, IngestWorker
from .scheduler import StreamScheduler, TierConfig
from .session import (SessionStatus, StreamSession, WindowPrediction,
                      fresh_lane_state, read_lane, write_lane)
from .staging import InFlight, LaneRecord, StagedChunk, StagingPipeline
from .stream_source import (AERStreamSource, ArrivalConfig, ReplaySource,
                            TaskStreamSource, aer_decode, aer_encode)
from .telemetry import FleetTelemetry, StreamCounters
from .topology_service import (TopologyEpochEvent, TopologyService,
                               TopologyServiceConfig)

__all__ = [
    "AdaptConfig", "AERStreamSource", "ArrivalConfig", "AutopilotConfig",
    "DepthAutopilot", "FleetTelemetry", "InFlight", "IngestConfig",
    "IngestWorker", "LaneRecord", "ReplaySource", "SessionStatus",
    "StagedChunk", "StagingPipeline", "StreamCounters", "StreamScheduler",
    "StreamSession", "TaskStreamSource", "TierConfig", "TopologyEpochEvent",
    "TopologyService", "TopologyServiceConfig", "WindowPrediction",
    "aer_decode", "aer_encode", "delta_norms", "fresh_lane_state",
    "make_chunk_fn", "merge_lane_into_base", "read_lane",
    "restore_fleet", "save_fleet", "write_lane",
]
