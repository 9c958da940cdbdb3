"""tpusim.obs — run telemetry, profiling, and bench regression gating.

The observability plane the replay engines report through (ISSUE 3):

  counters   exact in-scan event counters riding the engines' lax.scan
             carries — bit-reproducible, checkpoint/fault-transparent
  decisions  per-event decision provenance (ISSUE 4): winner, per-policy
             score contributions, top-K runner-ups, tie-break ranks —
             engine-invariant, JSONL-persisted, behind `tpusim
             explain`/`diff`
  series     in-scan cluster time-series plane (ISSUE 5): fixed-stride
             utilization/frag/score-distribution samples emitted by the
             scan — engine-invariant, checkpoint/fault-continuous,
             rendered by `tpusim report` and the analysis plotter
  server     live monitoring endpoint (ISSUE 5): /metrics, /healthz,
             /progress over stdlib-threaded HTTP — in-process via
             `apply --listen`, standalone via `tpusim serve DIR`
  spans      phase timers with a dispatch(compile)/block(execute) wall
             split; Recorder/RunTelemetry accumulate them per run
  heartbeat  jax.debug.callback progress ticks from inside long scans
             (+ the listener hook /progress feeds from)
  emitters   JSONL run records, Prometheus textfiles, Chrome traces
             (incl. frag/alloc + series counter tracks)
  bench      the shared cold+warm-minimum timing protocol + JSON writer
             the bench scripts build on
  gate       `python -m tpusim.obs.gate` — smoke profile diffed against
             the committed BENCH_r*.json baselines

Layering: obs imports nothing from sim/ (engines and the driver import
obs, never the reverse), so it can sit under every engine's scan body.
"""

from tpusim.obs.counters import (  # noqa: F401
    COUNTER_FIELDS,
    INVARIANT_FIELDS,
    NUM_COUNTERS,
    counter_delta,
    counters_from_telemetry,
    counters_to_dict,
    zero_counters,
)
from tpusim.obs.decisions import (  # noqa: F401
    DECISION_SCHEMA,
    DECISION_TOPK,
    DecisionLog,
    DecisionRecord,
)
from tpusim.obs.series import (  # noqa: F401
    FRAG_CATEGORY_NAMES,
    SERIES_SCHEMA,
    UTIL_BUCKETS,
    SeriesLog,
    SeriesSample,
)
from tpusim.obs.spans import (  # noqa: F401
    SCHEMA,
    Recorder,
    RunTelemetry,
    Span,
    SweepRecord,
    compile_counts,
    note_compile_cache,
    sweep_log,
)
