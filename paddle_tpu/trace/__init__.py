"""paddle_tpu.trace — end-to-end distributed tracing + flight recorder.

The monitor (paddle_tpu.monitor) answers "what is the average"; trace
answers "why was THIS one slow" and "what happened right before the
hang". Three hot paths are instrumented end to end:

  serve     serve.http -> serve.request -> queue/pad/dispatch/readback
            child spans per request; the batcher's fan-in dispatch is a
            serve.batch span LINKED to every coalesced request's context
            (one slow request stays attributable after batching).
  training  <kind>.step spans whose phase children (feed_wait,
            feed_encode, state_gather, cache_lookup, compile |
            cache_load, dispatch, write_back, fetch_readback) tile the
            step, replayed from monitor.StepRecord's laps at step_end
            under FLAGS_trace alone; one chain of datapipe.* spans per
            chunk (read, handoff, idle, decode, ring_put | map,
            slot_wait, ticket_wait, lock_wait, upstream_wait, stack,
            transfer, next), each recorded where the work happens — a
            decode worker's own stamps ride its ack.
  compiles  a miss's `compile` phase (`cache_load` for a step loaded from
            the persistent store) is tiled by `compile.verify | digest |
            l2_load | trace | lower | backend | export | self` children
            carrying the cache fingerprint, replayed from the build's
            always-on record (cache/builds.py).

Spans land in an in-memory flight recorder (recorder.py): per-thread
fixed-size rings, dumped (spans.jsonl + chrome trace.json +
manifest.json) when the resilience watchdog fires, the NaN guard trips,
a serve SLO violation / ServerOverloaded occurs, or on demand via
`python -m paddle_tpu trace dump`.

Off contract (FLAGS_trace=0, the default): one flag check per
instrumentation site, no allocation — same deal as FLAGS_monitor.
See docs/observability.md.
"""

from .costs import op_costs
from .export import CHROME_PID, FORMAT, chrome_events, load_dump, write_dump
from .recorder import (append, dump, last_dump, maybe_dump, reset,
                       snapshot)
from .span import (SpanContext, attach, current, enabled, new_context,
                   record, span)

__all__ = [
    # span API
    "SpanContext", "enabled", "current", "new_context", "attach", "span",
    "record",
    # flight recorder
    "append", "snapshot", "reset", "dump", "maybe_dump", "last_dump",
    # dump formats
    "FORMAT", "CHROME_PID", "chrome_events", "write_dump", "load_dump",
    # analytic per-op estimates (the planners' weights)
    "op_costs",
]
