"""Serving substrate: slot-based KV cache + continuous-batching engines
(transformer decode, the fusion-aware vertex-function decode,
whole-structure scoring, and the cross-request union-frontier engine),
hardened by the robustness layer (lifecycle guards, poison quarantine,
degradation ladder)."""

from repro.serve.kv_cache import CacheSlots
from repro.serve.engine import (Request, ServeEngine, StructureRequest,
                                StructureServeEngine, VertexRequest,
                                VertexServeEngine)
from repro.serve.continuous import (AdmissionPolicy, ContinuousBatchEngine,
                                    ContinuousRequest, HybridChainEngine)
from repro.serve.robustness import (CircuitBreaker, RequestLifecycle,
                                    TERMINAL, quarantine_bisect)

__all__ = ["CacheSlots", "Request", "ServeEngine", "StructureRequest",
           "StructureServeEngine", "VertexRequest", "VertexServeEngine",
           "AdmissionPolicy", "ContinuousBatchEngine", "ContinuousRequest",
           "HybridChainEngine",
           "CircuitBreaker", "RequestLifecycle", "TERMINAL",
           "quarantine_bisect"]
