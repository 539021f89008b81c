"""MemEC core: the paper's primary contribution in library form.

Layers:
* gf256 / codes — GF(2^8) arithmetic + RS/RDP/XOR erasure codes with
  delta-based parity updates (paper §2);
* engine — the unified batched coding data plane: one `CodingEngine`
  interface (encode_batch / decode_batch / apply_delta_batch) with
  pluggable numpy / jax / pallas backends, shared by servers, the
  cluster's batched request paths, and batched recovery.  Backend
  selection: the `engine=` constructor knob (configs/memec.py) or the
  `MEMEC_ENGINE` env var;
* chunk / index / stripe — the all-encoding data model: 4KB chunk packing,
  cuckoo-hash object & chunk indexes, write-balanced stripe lists (§3, §4.3);
* server / proxy / coordinator / store — the cluster: decentralized
  normal-mode requests (single-key and batched multi_get/multi_set/
  multi_update), coordinated degraded mode, server states, backups,
  one-shot batched recovery, migration (§4, §5);
* shard — the scale-out layer: `ShardedCluster` hash-partitions the key
  space across S independent shard stores (own stripe lists, coordinator,
  `CodingEngine` — mixed backends allowed), plans multi-key requests
  across shards with pipelined scatter/gather, and scopes failure
  recovery per shard.  `make_cluster(shards=... )` / `$MEMEC_SHARDS`;
  S=1 returns the plain `MemECCluster`;
* ring / rebalance — the elastic placement subsystem: pluggable
  `Placement` routing (FNV-mod or a deterministic consistent-hash ring
  with vnodes + weights, `placement=` / `$MEMEC_PLACEMENT`), live stripe
  migration (`Rebalancer`: chunk-wise moves through the engine/netsim
  paths, redirect-style forwarding keeps every key readable
  mid-migration), and skew-aware rebalancing via
  `ShardedCluster.add_shard/remove_shard/rebalance`;
* trace / telemetry — the observability layer: opt-in per-request span
  tracing (`trace=` / `$MEMEC_TRACE`) with critical-path attribution,
  Chrome trace-event export (Perfetto-loadable), deterministic trace
  capture/replay (`TraceCapture` → `arrival="trace:..."`), and the
  versioned telemetry snapshot every consumer reads;
* baselines — all-replication + hybrid-encoding comparison stores (§3.1);
* analysis — the redundancy formulas of §3.3 (Figure 2).
"""
from .analysis import (AnalysisParams, redundancy_all_encoding,
                       redundancy_all_replication, redundancy_hybrid_encoding)
from .baselines import AllReplicationCluster, HybridEncodingCluster
from .chunk import CHUNK_SIZE, ChunkBuilder, ChunkId, ObjectRef
from .codes import Code, NoCode, RDPCode, RSCode, XORCode, make_code
from .coordinator import Coordinator, ServerState
from .engine import (CodingEngine, DecodePlan, EngineFuture, JaxEngine,
                     NumpyEngine, PallasEngine, make_engine, resolve_async)
from .engine import engine_specs
from .index import CuckooIndex
from .netsim import (ArrivalProcess, CostModel, EventRuntime, LatencyRecorder,
                     Leg, NetSim, resolve_arrival)
from .proxy import Proxy
from .rebalance import MigrationPlan, Rebalancer
from .ring import (ModPlacement, Placement, RingPlacement, make_placement)
from .server import Server
from .shard import (ShardedCluster, ShardedNet, make_cluster, resolve_shards,
                    shard_for_key)
from .store import MemECCluster, PartialFailure
from .stripe import StripeList, StripeMapper, generate_stripe_lists
from .trace import (Span, TraceCapture, Tracer, critical_paths,
                    export_chrome, resolve_trace, validate_chrome)
from . import telemetry
from . import trace

__all__ = [
    "AnalysisParams", "redundancy_all_encoding", "redundancy_all_replication",
    "redundancy_hybrid_encoding", "AllReplicationCluster",
    "HybridEncodingCluster", "CHUNK_SIZE", "ChunkBuilder", "ChunkId",
    "ObjectRef", "Code", "NoCode", "RDPCode", "RSCode", "XORCode",
    "make_code", "CodingEngine", "EngineFuture", "JaxEngine", "NumpyEngine",
    "PallasEngine", "make_engine", "resolve_async", "engine_specs",
    "Coordinator", "ServerState", "CostModel", "ArrivalProcess",
    "EventRuntime", "LatencyRecorder", "resolve_arrival",
    "Leg", "NetSim", "Proxy", "Server", "MemECCluster", "PartialFailure",
    "ShardedCluster", "ShardedNet", "make_cluster", "resolve_shards",
    "shard_for_key", "StripeList", "StripeMapper", "generate_stripe_lists",
    "Placement", "ModPlacement", "RingPlacement", "make_placement",
    "Rebalancer", "MigrationPlan", "telemetry", "trace", "Span", "Tracer",
    "TraceCapture", "critical_paths", "export_chrome", "resolve_trace",
    "validate_chrome",
]
