"""Schedule-compilation pipeline: compose → fingerprint → cache (memory
+ disk) → bucket → pack → async prefetch (see ``pipeline.py`` for the
architecture note)."""

from repro.pipeline.buckets import (BucketPolicy, PadDims, ShapeCensus,
                                    TIGHT, tight_dims)
from repro.pipeline.cache import ScheduleCache
from repro.pipeline.composer import (BatchComposer, ComposedBatch,
                                     CompositionStats,
                                     ShardedCompositionStats, ShardedStep,
                                     fifo_stats)
from repro.pipeline.fingerprint import (batch_fingerprint, graph_fingerprint,
                                        graph_schedule_key)
from repro.pipeline.persist import (SCHEMA_VERSION, SchedulePersist,
                                    persist_dir_default)
from repro.pipeline.pipeline import (PackedBatch, SchedulePipeline,
                                     ShardedPipeline)
from repro.pipeline.prefetch import AsyncPacker
from repro.pipeline.splice import extract_solo, splice_schedules

__all__ = [
    "AsyncPacker", "BatchComposer", "BucketPolicy", "ComposedBatch",
    "CompositionStats", "PackedBatch", "PadDims", "SCHEMA_VERSION",
    "ScheduleCache", "SchedulePersist", "SchedulePipeline",
    "ShardedCompositionStats", "ShardedPipeline", "ShardedStep",
    "ShapeCensus", "TIGHT", "batch_fingerprint", "extract_solo",
    "fifo_stats", "graph_fingerprint", "graph_schedule_key",
    "persist_dir_default", "splice_schedules", "tight_dims",
]
