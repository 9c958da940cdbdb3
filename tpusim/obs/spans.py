"""Phase spans + the run recorder — the timing half of obs.

A Span is one named phase of an experiment (trace_load, typical_pods,
init_tables, scan, fetch, metrics_postpass, report, ...) with a
dispatch/block wall split: under JAX's async dispatch, the host returns
from a jitted call once tracing + compilation + enqueue are done and the
device work completes later, so

    dispatch_s  host wall until the call returned — on a COLD call this
                is dominated by trace + XLA compile; on a warm call it is
                the executable-cache lookup + argument transfer
    block_s     wall spent waiting for the device result (the execute
                half). Only attributed when the recorder is enabled
                (profiling mode blocks on the phase result); an
                un-profiled run never adds sync points, so its spans
                carry dispatch walls only.

That is the compile-vs-execute split the JSONL record reports: the first
scan span of a config shows compile in dispatch_s, every later one shows
~0 dispatch + pure execute in block_s.

The Recorder accumulates spans, host counters (degrades, cache hits,
disruption totals), and the engines' in-scan counter vectors
(obs.counters) across every replay a Simulator runs — fault runs note
one scan per segment and the vectors sum. RunTelemetry is the snapshot
the driver attaches to SimulateResult; its to_record() splits the JSONL
payload into a `deterministic` block (bit-identical across same-seed
runs and across kill/resume — the acceptance contract tests pin) and a
`timing` block (machine-dependent walls).

A sweep call wraps its body in Recorder.sweep: its spans carry the
sweep's id and stay flat, and one SweepRecord (wall, spans, the compile
counts over the call) goes to a bounded process-wide log that outlives
the Simulator (sweep_log()).

A span can carry marks: named instants its handle stamps inside it
(`h.mark("copied")`), seconds since the span's start. Three spans of a
sweep have them (fetch: ready, copied; lane_ranks: stacked;
frag_postpass: gathered), and the SweepRecord derives from spans and
marks what the host did before, under and after the device's work
(host_lead_s, covered_s, device_block_s, device_wait_s, host_tail_s).
A sweep with the per-event report on has one span more, `event_metrics`,
between frag_postpass and fetch: the report program's dispatch and, in a
blocked wave, its device time, apart from the lane post-pass.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from tpusim.obs.counters import (
    NUM_COUNTERS,
    counters_to_dict,
)

SCHEMA = "tpusim-obs-v1"


@dataclass
class Span:
    name: str
    start_s: float  # relative to the recorder epoch
    dispatch_s: float  # host wall until dispatch returned (compile on cold)
    block_s: float  # wall waiting on the device result (execute); 0 = unknown
    meta: Dict[str, object] = field(default_factory=dict)
    sweep: Optional[int] = None  # id of the enclosing Recorder.sweep, if any
    # instants stamped inside the span (_SpanHandle.mark): name -> seconds
    # since the span's start, in the order they were stamped
    marks: Dict[str, float] = field(default_factory=dict)

    @property
    def total_s(self) -> float:
        return self.dispatch_s + self.block_s

    def to_dict(self) -> dict:
        d = {
            "name": self.name,
            "start_s": round(self.start_s, 6),
            "dispatch_s": round(self.dispatch_s, 6),
            "block_s": round(self.block_s, 6),
            "total_s": round(self.total_s, 6),
        }
        if self.meta:
            d["meta"] = self.meta
        if self.sweep is not None:
            d["sweep"] = self.sweep
        if self.marks:
            d["marks"] = {k: round(v, 6) for k, v in self.marks.items()}
        return d


# ---------------------------------------------------------------------------
# The compile counter: programs that reached the backend, and those among
# them the persistent compilation cache served, counted where jax reports
# them. One listener pair a process, installed on first use.
# ---------------------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_compile_totals = [0, 0]  # [programs requested, cache loads], process-wide
_compile_lock = threading.Lock()  # any thread may compile
_compile_listening = False


def _on_compile_duration(event, _secs, **_kw):
    if event == COMPILE_EVENT:
        with _compile_lock:
            _compile_totals[0] += 1


def _on_compile_event(event, **_kw):
    if event == CACHE_HIT_EVENT:
        with _compile_lock:
            _compile_totals[1] += 1


def compile_counts() -> Tuple[int, int]:
    """(programs requested, cache loads) of this process since the first
    call, which installs the listeners. Every program that reaches the
    backend cost a trace and a lowering; a cache load among them was
    served by the persistent compilation cache, the rest were compiled.
    Callers take differences."""
    global _compile_listening
    with _compile_lock:
        if not _compile_listening:
            import jax.monitoring as mon

            mon.register_event_duration_secs_listener(_on_compile_duration)
            mon.register_event_listener(_on_compile_event)
            _compile_listening = True
        return _compile_totals[0], _compile_totals[1]


# ---------------------------------------------------------------------------
# Sweep records: one per Recorder.sweep, kept process-wide so that a reader
# that no longer holds the Simulator (the benchmark's layer metrics) finds
# the phases of the last waves.
# ---------------------------------------------------------------------------

SWEEP_LOG_SIZE = 1024
_sweep_log: deque = deque(maxlen=SWEEP_LOG_SIZE)
_sweep_ids = itertools.count()


DERIVED_FIELDS = ("host_lead_s", "covered_s", "device_block_s",
                  "device_wait_s", "host_tail_s")


def _rounded(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(seconds, 6)


@dataclass
class SweepRecord:
    """One schedule_pods_sweep call: its spans are flat and back to back
    (Recorder.spans holds the same objects, no root span among them), so
    the call's own start and wall live here."""

    id: int
    start_s: float  # absolute time.perf_counter() at entry
    blocked: bool  # spans blocked on their results (Recorder.enabled)
    epoch: float = 0.0  # the Recorder's, which the spans' start_s count from
    lanes: int = 0
    events: int = 0
    engine: str = ""
    wall_s: float = 0.0
    spans: List[Span] = field(default_factory=list)
    programs_requested: int = 0  # reached the backend: traced and lowered
    cache_loads: int = 0  # of those, served by the persistent cache
    # write sites of the sweep's program lowered through
    # sim/lane_write.py's batching rule (0: the program was not vmapped)
    lane_writes: int = 0
    # sites of that program, reads too, that the rule lowered in the dense
    # form (a short node axis: the flat step body); 0 on the blocked body
    dense_accesses: int = 0
    # events whose dirty columns one dense pass over a lane-batched table
    # writes: the flat body's group (table_engine.FLAT_GROUP_EVENTS), 1
    # where a column is written every event, 0 with no dense table write
    table_pass_events: int = 0
    # 1 where the sweep's program leaves the commit's add into
    # NodeState.aff_cnt out of its event loop and makes the leaf once a
    # chunk from the events' own record (table_engine.chunk_affinity,
    # scope tpusim.affinity): the flat table body where no scoring kernel
    # reads the leaf and no fault step rewrites it; 0 where the add runs
    # every event (the blocked body, fault plans, a GpuClustering program,
    # the sequential engine)
    affinity_deferred: int = 0
    # policies of the sweep's program whose kernel reads NodeState.aff_cnt
    # (policies.affinity_readers, off the kernels' own `reads_affinity`):
    # 0 for every built-in but GpuClustering. A program with a reader keeps
    # the commit's add in its event loop (scope tpusim.commit.affinity), so
    # a record with readers and affinity_deferred 1 is a wrong program
    affinity_readers: int = 0
    # 1 where that loop holds the leaf nodes minor, i32[classes, N] a lane
    # (transposed once a chunk on the way in and out: the flat table body
    # with a reader and no fault step), 0 where the add goes into [N, 9]
    # rows or has left the loop. A static property of the program
    affinity_nodes_minor: int = 0
    # 1 where the sweep read the score tables an earlier sweep of the
    # Simulator left on the device (its init_tables span says
    # cache="resident"), 0 where it built or loaded them
    tables_reused: int = 0
    # distinct traces the specs span prepared: 1 where the lanes share a
    # trace, fewer than the lanes where some hand over one trace object
    traces: int = 0
    # typical-pod sets, and score-table sets, the sweep carried: 1 where
    # every lane is scored against the Simulator's, F with lanes of F
    # workload families (its init_tables span's cache counts them)
    typical_sets: int = 1
    # distinct rows among the `weights` the caller gave: 1 where every
    # lane is scored under one weight vector (a seed sweep), more where
    # the lanes differ in it (a weight grid, a tuner's generation)
    weight_rows: int = 0
    # policies of the sweep's program whose normalizer runs in its scan
    # (`normalize` minmax / pwr: feasible extrema, scale and weighted
    # total every event), read off the policies; 0 for raw-score families
    normalized_policies: int = 0
    # bytes of the packed buffer the fetch span moved to the host
    # (sim/fetch.device_fetch: every lane's result in one packed buffer)
    fetch_bytes: int = 0
    # transfers that buffer left the device in: 1 where it is no larger
    # than one piece (fetch.PIECE_BYTES), else its size over the piece
    # size, rounded up, every copy started before the first is taken
    fetch_pieces: int = 0
    # 1 where the pieces landed in a host block an earlier fetch had
    # touched (no array cut from it was alive any more), 0 where the
    # fetch had to allocate one or made one transfer; over a window its
    # mean is the landing blocks' hit share
    landing_reused: int = 0
    # Sub hypotheticals ONE column computation of the sweep's program
    # evaluates a lane (table_engine.sub_requests): the type set's distinct
    # (gpu_milli, gpu_num) requests where every scoring kernel takes its
    # whole-branch types by request, the whole group's size where one goes
    # type by type; 0 on the sequential engine (no columns)
    sub_requests: int = 0
    # deletions among the record's real events, summed over its lanes:
    # counted on the host from the streams the specs span built (a stream
    # by timestamp, SimulatorConfig.use_timestamps, holds a deletion a pod
    # that has a deletion time), so an unblocked wave needs no sync for it;
    # 0 where every lane replays creations in list order
    delete_events: int = 0
    # creations the sweep's lanes rejected, summed over the lanes: counted
    # on the host from the fetched `ever_failed` flags as the lanes are cut
    # (SweepLane.failed), so an unblocked wave needs no sync for it; what
    # says a cluster filled up
    rejected_creates: int = 0
    # bytes of fetch_bytes that are per-event report series (the nine
    # EventMetrics leaves over the padded event axis, SimulatorConfig.
    # report_per_event); 0 where the sweep reports no series
    series_bytes: int = 0

    @property
    def compiled(self) -> int:
        return self.programs_requested - self.cache_loads

    # What the host did before, under and after the device's work, from the
    # spans and their marks; None where the call left no such span or mark
    # (a record no schedule_pods_sweep filled). Blocked or not,
    #   host_lead_s + covered_s + device_block_s + device_wait_s
    #   + host_tail_s
    # is the time from the call's start to the end of its last span.

    def _span(self, name: str) -> Optional[Span]:
        return next((s for s in self.spans if s.name == name), None)

    def _scan_dispatched_s(self) -> Optional[float]:
        scan = self._span("scan")
        return None if scan is None else scan.start_s + scan.dispatch_s

    def _fetch_ready_s(self) -> Optional[float]:
        fetch = self._span("fetch")
        if fetch is None or "ready" not in fetch.marks:
            return None
        return fetch.start_s + fetch.marks["ready"]

    @property
    def host_lead_s(self) -> Optional[float]:
        """From the call's start to the scan's dispatch: specs, keys,
        ranks, the tables' hand-over, the sweep wrapper's dispatch and the
        gaps between. The device has no scan to run yet, so nothing here
        can hide behind it."""
        at = self._scan_dispatched_s()
        return None if at is None else at - (self.start_s - self.epoch)

    @property
    def device_block_s(self) -> Optional[float]:
        """What the host waited on the device inside the scan, the
        post-pass and (with the per-event report on) the event_metrics
        spans: their block halves in a blocked wave, microseconds in one
        that did not block."""
        scan, post = self._span("scan"), self._span("frag_postpass")
        if scan is None or post is None:
            return None
        report = self._span("event_metrics")
        return scan.block_s + post.block_s + (
            0.0 if report is None else report.block_s)

    @property
    def covered_s(self) -> Optional[float]:
        """Host work between the scan's dispatch and the fetch's start,
        less device_block_s: the post-pass's gather, trace, lowering and
        cache load, and the report program's dispatch. Hidden iff the wave
        did not block and the scan outlasts it."""
        at, fetch = self._scan_dispatched_s(), self._span("fetch")
        block = self.device_block_s
        if at is None or fetch is None or block is None:
            return None
        return fetch.start_s - at - block

    @property
    def device_wait_s(self) -> Optional[float]:
        """The fetch's start to its `ready` mark: the device finishing
        what it still owed (the pack alone in a blocked wave; the scan's
        tail, the post-pass and the pack in one that did not block)."""
        fetch = self._span("fetch")
        return None if fetch is None else fetch.marks.get("ready")

    @property
    def host_tail_s(self) -> Optional[float]:
        """The fetch's `ready` mark to the end of the slice_lanes span:
        copy, unpack and per-lane slicing, after the device's last
        program, so nothing hides it."""
        ready, last = self._fetch_ready_s(), self._span("slice_lanes")
        if ready is None or last is None:
            return None
        return last.start_s + last.total_s - ready

    def to_dict(self) -> dict:
        return {
            "id": self.id,
            "start_s": round(self.start_s, 6),
            "wall_s": round(self.wall_s, 6),
            "engine": self.engine,
            "lanes": self.lanes,
            "events": self.events,
            "blocked": self.blocked,
            "programs_requested": self.programs_requested,
            "cache_loads": self.cache_loads,
            "compiled": self.compiled,
            "lane_writes": self.lane_writes,
            "dense_accesses": self.dense_accesses,
            "table_pass_events": self.table_pass_events,
            "affinity_deferred": self.affinity_deferred,
            "affinity_readers": self.affinity_readers,
            "affinity_nodes_minor": self.affinity_nodes_minor,
            "tables_reused": self.tables_reused,
            "traces": self.traces,
            "typical_sets": self.typical_sets,
            "weight_rows": self.weight_rows,
            "normalized_policies": self.normalized_policies,
            "fetch_bytes": self.fetch_bytes,
            "fetch_pieces": self.fetch_pieces,
            "landing_reused": self.landing_reused,
            "sub_requests": self.sub_requests,
            "delete_events": self.delete_events,
            "rejected_creates": self.rejected_creates,
            "series_bytes": self.series_bytes,
            **{n: _rounded(getattr(self, n)) for n in DERIVED_FIELDS},
            "spans": [s.to_dict() for s in self.spans],
        }


def sweep_log() -> List[SweepRecord]:
    """The process's last SWEEP_LOG_SIZE sweep records, oldest first."""
    return list(_sweep_log)


class _SpanHandle:
    """Yielded by Recorder.span(); call .dispatched() the moment the
    device call returns to split compile/dispatch from execute/block,
    .mark(name) at any other instant worth keeping, .note(k=v) for what
    the span learns about itself as it runs."""

    __slots__ = ("_name", "_t0", "_t_dispatch", "_sub", "marks", "meta")

    def __init__(self, name: str, t0: float, meta: dict):
        self._name = name
        self._t0 = t0
        self._t_dispatch = None
        self._sub = None  # the open sub-phase annotation, if any
        self.marks: Dict[str, float] = {}
        self.meta = meta

    def dispatched(self):
        if self._t_dispatch is None:
            self._t_dispatch = time.perf_counter()

    def mark(self, name: str, then: str = ""):
        """Stamp the instant `name` (seconds since the span's start, into
        Span.marks). `then` names the sub-phase that starts here: a
        `tpusim/<span>/<then>` annotation nested in the span's own, until
        the next mark or the span's end."""
        self.marks[name] = time.perf_counter() - self._t0
        self._close_sub()
        if then:
            from jax.profiler import TraceAnnotation

            self._sub = TraceAnnotation(f"tpusim/{self._name}/{then}")
            self._sub.__enter__()

    def note(self, **meta):
        """Add to the span's meta."""
        self.meta.update(meta)

    def _close_sub(self):
        if self._sub is not None:
            self._sub.__exit__(None, None, None)
            self._sub = None


class Recorder:
    """Per-Simulator telemetry accumulator. Always cheap to keep on (a
    span is two perf_counter calls); `enabled` additionally makes the
    driver block on phase results for the compile/execute attribution
    and is what --profile turns on."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.reset()

    def reset(self):
        self.epoch = time.perf_counter()
        self.spans: List[Span] = []
        self.sweeps: List[SweepRecord] = []
        self._sweep: Optional[int] = None  # id of the sweep in progress
        self._compile_base = compile_counts()
        self.counts: Dict[str, int] = {}
        self.scan_counters = np.zeros(NUM_COUNTERS, np.int64)
        self._pending_scans: List[tuple] = []  # (device ctr array, pad_skips)
        self.scan_events = 0
        self.engines: List[str] = []
        self.disruption: Dict[str, int] = {}
        self.table_cache = "off"  # off | miss | hit
        # fused-Pallas table residency the last pallas dispatch ran
        # under (ENGINES.md Round 19): off | vmem | hbm — set by the
        # driver's residency select; lands in the run record's
        # deterministic block beside table_cache
        self.pallas_residency = "off"
        # persistent-compilation-cache note: set by note_compile_cache
        # after the run; None = never assessed
        self.compile_cache: Optional[dict] = None

    @contextmanager
    def span(self, name: str, **meta):
        """One phase. It is also a `tpusim/<name>` annotation in the host
        plane of a jax.profiler trace, on the clock the device plane
        uses; with no profiler session that is a flag check."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(f"tpusim/{name}"):
            t0 = time.perf_counter()
            h = _SpanHandle(name, t0, meta)
            try:
                yield h
            finally:
                t1 = time.perf_counter()
                h._close_sub()
                td = h._t_dispatch if h._t_dispatch is not None else t1
                self.spans.append(Span(
                    name=name,
                    start_s=t0 - self.epoch,
                    dispatch_s=td - t0,
                    block_s=t1 - td,
                    meta=meta,
                    sweep=self._sweep,
                    marks=h.marks,
                ))

    def settle(self, handle: _SpanHandle, *results):
        """Close a span's dispatch half; in profiling mode wait for the
        device results, so that the rest of the span is their time."""
        handle.dispatched()
        if self.enabled:
            import jax

            jax.block_until_ready(results)

    @contextmanager
    def sweep(self, lanes: int):
        """Wrap one whole sweep call. Every span opened inside carries the
        sweep's id; the spans stay flat in `spans` (no root span: a reader
        that walks them as back-to-back phases would count an enclosing
        one twice). Yields the SweepRecord so the body can name its engine
        and events; at exit the record gets the wall, the spans and the
        compile counts over the call, and goes to `sweeps` and to the
        process-wide log (sweep_log())."""
        rec = SweepRecord(
            id=next(_sweep_ids), start_s=time.perf_counter(),
            blocked=self.enabled, epoch=self.epoch, lanes=int(lanes),
        )
        first_span = len(self.spans)
        requested0, loads0 = compile_counts()
        self._sweep = rec.id
        try:
            yield rec
        finally:
            self._sweep = None
        # a call that raised leaves its spans and no record
        rec.wall_s = time.perf_counter() - rec.start_s
        rec.spans = self.spans[first_span:]
        requested1, loads1 = compile_counts()
        rec.programs_requested = requested1 - requested0
        rec.cache_loads = loads1 - loads0
        self.sweeps.append(rec)
        _sweep_log.append(rec)

    def count(self, name: str, n: int = 1):
        self.counts[name] = self.counts.get(name, 0) + n

    def note_scan(self, engine: str, counters=None, pad_skips: int = 0,
                  events: int = 0):
        """Record one replay dispatch: which engine ran, how many true
        (un-padded) events, and its in-scan counter vector. The device
        array is stashed un-materialized — np.asarray would force a sync
        mid-pipeline — and folded in lazily at snapshot()."""
        self.engines.append(engine)
        self.scan_events += int(events)
        if counters is not None:
            self._pending_scans.append((counters, int(pad_skips)))

    def note_disruption(self, dm):
        """Fold a DisruptionMetrics into machine-readable counters (the
        [Disruption] log block's obs twin)."""
        self.disruption = {
            "node_failures": int(dm.node_failures),
            "node_recoveries": int(dm.node_recoveries),
            "evicted_pods": int(dm.evicted_pods),
            "rescheduled_pods": int(dm.rescheduled_pods),
            "retries_enqueued": int(dm.retries_enqueued),
            "unscheduled_after_retries": int(dm.unscheduled_after_retries),
        }

    def _drain_pending(self):
        for ctr, pad in self._pending_scans:
            vals = np.asarray(ctr).astype(np.int64).copy()
            vals[4] = max(int(vals[4]) - pad, 0)  # drop bucket-padding skips
            self.scan_counters += vals
        self._pending_scans = []

    def snapshot(self, meta: Optional[dict] = None) -> "RunTelemetry":
        self._drain_pending()
        return RunTelemetry(
            spans=list(self.spans),
            counters=counters_to_dict(self.scan_counters),
            counts=dict(self.counts),
            disruption=dict(self.disruption),
            engines=list(self.engines),
            events=self.scan_events,
            table_cache=self.table_cache,
            pallas_residency=self.pallas_residency,
            meta=dict(meta or {}),
            compile_cache=(
                dict(self.compile_cache) if self.compile_cache else None
            ),
            sweeps=list(self.sweeps),
        )


def note_compile_cache(recorder: Recorder, enabled: bool,
                       cache_dir: str = "") -> dict:
    """Stamp the run's persistent-compilation-cache outcome onto the
    recorder: the programs that reached the backend since the recorder's
    epoch (`requests`: each cost a trace and a lowering), those among
    them the persistent cache served (`cache_loads`) and the rest
    (`compiled`), as compile_counts() saw them, beside the first scan
    span's dispatch wall. Lands in the run record's `timing` block
    (machine-dependent, never the deterministic block)."""
    first = next((s for s in recorder.spans if s.name == "scan"), None)
    requests_now, loads_now = compile_counts()
    requests = requests_now - recorder._compile_base[0]
    loads = loads_now - recorder._compile_base[1]
    info = {
        "enabled": bool(enabled),
        "dir": cache_dir,
        "first_scan_dispatch_s": (
            round(first.dispatch_s, 6) if first is not None else None
        ),
        "requests": requests,
        "cache_loads": loads,
        "compiled": requests - loads,
    }
    recorder.compile_cache = info
    return info


@dataclass
class RunTelemetry:
    """One run's telemetry: the object SimulateResult.telemetry carries
    and the JSONL emitter serializes."""

    spans: List[Span]
    counters: Dict[str, int]  # in-scan counters (obs.counters vocabulary)
    counts: Dict[str, int]  # host-side counters (degrades, cache, retries)
    disruption: Dict[str, int]
    engines: List[str]
    events: int
    table_cache: str
    meta: Dict[str, object]
    # fused-Pallas residency tier of this run's pallas dispatches
    # (off | vmem | hbm) — deterministic, like table_cache
    pallas_residency: str = "off"
    # persistent-compilation-cache note (note_compile_cache): enabled /
    # dir / first-scan dispatch wall / requests, cache_loads, compiled.
    # None when never assessed; machine-dependent, so it reports under
    # `timing`.
    compile_cache: Optional[dict] = None
    # the sweep records of the run (Recorder.sweep), under `timing` too
    sweeps: List[SweepRecord] = field(default_factory=list)

    def to_record(self) -> dict:
        """The JSONL run record. `deterministic` is bit-identical across
        same-seed runs and kill/resume (integer counters + config only);
        `timing` carries the machine-dependent walls."""
        return {
            "schema": SCHEMA,
            "deterministic": {
                "events": self.events,
                "counters": self.counters,
                "degrades": {
                    k: v for k, v in sorted(self.counts.items())
                    if k.startswith("degrade_")
                },
                "counts": {
                    k: v for k, v in sorted(self.counts.items())
                    if not k.startswith("degrade_")
                },
                "disruption": self.disruption,
                "engines": self.engines,
                "table_cache": self.table_cache,
                "pallas_residency": self.pallas_residency,
                "meta": self.meta,
            },
            "timing": {
                "spans": [s.to_dict() for s in self.spans],
                "wall_s": round(
                    max((s.start_s + s.total_s for s in self.spans),
                        default=0.0),
                    6,
                ),
                **(
                    {"compile_cache": self.compile_cache}
                    if self.compile_cache is not None else {}
                ),
                **(
                    {"sweeps": [r.to_dict() for r in self.sweeps]}
                    if self.sweeps else {}
                ),
            },
        }
