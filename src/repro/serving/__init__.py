from repro.serving.engine import (  # noqa: F401
    EngineConfig,
    Request,
    ServingEngine,
    SlotPacket,
    request_breakdowns,
)
from repro.serving.cluster import (  # noqa: F401
    ClusterConfig,
    ClusterEngine,
)
from repro.serving.scheduler import (  # noqa: F401
    SLO,
    BlockingScheduler,
    ChunkedScheduler,
    PrefillState,
    Scheduler,
    SLOScheduler,
    SpeculativeScheduler,
    make_scheduler,
    slo_sort_key,
)
from repro.serving.kv_cache import (  # noqa: F401
    BlockAllocator,
    ContiguousCache,
    KVCacheManager,
    PagedCache,
    contiguous_kv_bytes,
    kv_bytes_per_token,
    make_kv_cache,
    paged_resident_kv_bytes,
)
from repro.serving.telemetry import (  # noqa: F401
    NULL_TELEMETRY,
    MetricsRegistry,
    SpanTracer,
    Telemetry,
    merge_snapshots,
    validate_trace_events,
)
from repro.serving.workload import (  # noqa: F401
    TenantSpec,
    Trace,
    TraceRequest,
    autoscale_decision,
    make_named_trace,
    make_trace,
    replay,
)
