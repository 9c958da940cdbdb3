"""Host-side experiment driver — the Simulate() orchestration
(ref: pkg/simulator/core.go:86-268 + the Simulator struct's Interface
surface, core.go:43-74).

The driver owns everything that happens once per experiment (trace prep,
typical pods, tuning, config); the per-event hot loop runs entirely on
device via tpusim.sim.engine.make_replay.
"""

from __future__ import annotations

import functools
import math
import os
import sys
import time
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.constants import MILLI
from tpusim.io.trace import (
    NodeRow,
    PodRow,
    build_events,
    nodes_to_state,
    pods_to_specs,
    tiebreak_rank,
)
from tpusim.policies import affinity_readers, make_policy
from tpusim.sim.engine import EV_DELETE, make_replay
from tpusim.sim.fetch import device_fetch
from tpusim.sim.reports import (
    LogSink,
    cluster_analysis_block,
    report_failed_pods,
)
from tpusim.sim.typical import (
    TypicalPodsConfig,
    get_skyline_pods,
    get_typical_pods,
    pad_typical_pods,
)
from tpusim.sim.workload import sort_cluster_pods, tune_pods
from tpusim.types import CAPACITY_LEAVES, NodeState, TypicalPods


@dataclass
class SimulatorConfig:
    """Experiment knobs (ref: CustomConfig, pkg/api/v1alpha1/types.go:57-109,
    + scheduler-config plugin selection, §5.6)."""

    policies: Sequence[Tuple[str, int]] = (("FGDScore", 1000),)
    gpu_sel_method: str = "best"  # best | worst | random | <policy name>
    dim_ext_method: str = "share"
    norm_method: str = "max"
    shuffle_pod: bool = False
    tuning_ratio: float = 0.0
    tuning_seed: int = 233
    inflation_ratio: float = 1.0
    inflation_seed: int = 233
    typical_pods: TypicalPodsConfig = field(default_factory=TypicalPodsConfig)
    deschedule_ratio: float = 0.0
    deschedule_policy: str = ""
    seed: int = 42  # node tie-break permutation + jax PRNG
    report_per_event: bool = True
    use_timestamps: bool = False
    # replay engine: auto (fastest supported), or force one of
    # sequential | table | pallas (ENGINES.md). `auto` picks the fused
    # Pallas engine on TPU backends for supported configs, else the
    # incremental table engine, else the sequential oracle. Degenerate
    # workloads (zero distinct pod types / fewer events than types) always
    # run the sequential path — the table init would cost more than it
    # saves; a forced table/pallas engine still applies whenever at least
    # one pod type exists. A sweep (schedule_pods_sweep; a seed group,
    # run_batch, is one) honors `sequential`; `pallas` has no batched form
    # and sweeps run the (bit-identical) table engine instead.
    engine: str = "auto"
    # table-engine select layout (tpusim.sim.table_engine.resolve_block_size):
    # 0 = auto (blocked incremental reductions over ~sqrt(N/K)-node blocks
    # at large N, flat elsewhere — openb-scale traces stay flat), > 0 =
    # force that block size, < 0 = force the flat O(N) select. Placements
    # are bit-identical either way; this is purely a throughput knob for
    # the 100k-node scale lane.
    block_size: int = 0
    # Flat-path select layout A/B (ENGINES.md Round 18): True replaces
    # the flat table engine's event switch with the shard engine's
    # unconditional-select form (score rows never cross a branch
    # boundary; small results merge by kind). Bit-identical either way;
    # MEASURED slower on the CPU backend at N=100k (the switch's
    # in-branch row reads lower as plain gathers there), so the default
    # keeps the switch — the knob exists for accelerator backends and
    # A/B measurement (bench_scale --unswitched).
    unswitched_select: bool = False
    # Fused-Pallas table residency (ENGINES.md Round 19): where the
    # [K, N] score/sdev/feas tables live across the kernel's grid steps.
    # "vmem" is the original all-resident layout (fastest, zero DMA,
    # ceiling N <= 4096 at K = 151); "hbm" keeps the tables (and the
    # mutable node state) HBM-resident and crosses only the event's
    # active working set into VMEM by per-event double-buffered async
    # DMA, with selectHost running over VMEM-resident block summaries —
    # ceiling HBM-bounded (>= 256k at K = 151). "auto" (default) picks
    # the first tier whose footprint fits the budget
    # (pallas_engine.select_residency); only when NEITHER fits does the
    # dispatch degrade to the blocked table engine — the [Degrade] path,
    # narrowed from "any table set over ~14 MiB" to genuinely
    # VMEM-impossible shapes. Placements are bit-identical across all
    # three (the interpreter-mode oracle tests pin it); this is purely a
    # capacity/throughput knob.
    table_residency: str = "auto"
    # HTTP scheduler extenders (tpusim.sim.extender.ExtenderConfig tuple).
    # When set, every replay runs the host-loop extender engine — the only
    # execution mode that can splice per-cycle HTTP round-trips between
    # Score and selectHost (ref: simulator.go:196 WithExtenders)
    extenders: tuple = ()
    # Exact checkpoint/resume of the event scan (ENGINES.md
    # "Checkpoint/resume"): > 0 cuts every table/shard-engine replay into
    # checkpoint_every-event segments and persists the full engine carry
    # (state + score/feas/sdev tables + blocked summaries + the
    # PendingCommit pipeline register + the PRNG key) plus the telemetry
    # accumulated so far to a content-addressed file after each segment. A
    # killed run re-invoked with identical inputs resumes at the last
    # completed segment and finishes bit-identically to an uninterrupted
    # scan. 0 disables (the default: one unsegmented scan).
    checkpoint_every: int = 0
    # Where checkpoint files live; resolution order: this field if
    # non-empty, else $TPUSIM_CHECKPOINT_DIR, else
    # <repo>/.tpusim_checkpoints. Only consulted when checkpoint_every > 0.
    checkpoint_dir: str = ""
    # Checkpoint retention (ISSUE 16, `--checkpoint-keep`): 0 keeps the
    # PR 2 resume-only discipline — each save prunes its predecessors and
    # run completion prunes everything (checkpoints exist only to survive
    # a kill). -1 retains EVERY mid-trace checkpoint: the warm-state fork
    # mode, where the svc fork index maps a what-if job to the nearest
    # checkpoint at-or-before its divergence point — pruning would delete
    # exactly what the index needs. N > 0 bounds disk instead: the newest
    # N checkpoints survive, older fork points degrade to full replay.
    checkpoint_keep: int = 0
    # ---- observability (tpusim.obs; ENGINES.md "Round 8") ----
    # profile=True switches the always-on span recorder into profiling
    # mode: the driver blocks on each phase result so spans carry the
    # compile(dispatch)/execute(block) wall split, and derives counters
    # from telemetry for engines whose scan does not count (pallas,
    # extender). Placements and metrics are unaffected either way; the
    # extra sync points cost < 2% on `make bench-scale-smoke` (measured,
    # ENGINES.md Round 8).
    profile: bool = False
    # > 0 fires an obs.heartbeat progress line (events/s, ETA) from
    # INSIDE the table engine's compiled scan every N processed events —
    # long-scan liveness for the 100k-node lane. Baked into the engine
    # jaxpr (part of its cache key); 0 = off. Table engine only (the
    # shard/pallas loops carry no host callback).
    heartbeat_every: int = 0
    # Content-keyed init_tables cache (ROADMAP open item): a directory
    # here (or $TPUSIM_TABLE_CACHE_DIR when empty) lets repeat runs skip
    # the ~27 s N=100k K-node-sweep table build by reloading the tables
    # under the checkpoint content-addressing discipline
    # (io.storage.save_tables; digest = engine-source salt + config +
    # state/types/typical). Bit-identical by construction; obs records
    # the hit/miss. Empty + unset env = disabled. Single-device table
    # engine only (the shard engine builds its tables sharded).
    table_cache_dir: str = ""
    # Decision-provenance flight recorder (ISSUE 4; tpusim.obs.decisions):
    # True makes every replay additionally emit a per-event
    # DecisionRecord stream — winner + per-policy raw/normalized score
    # contributions, top-K runner-ups with tie-break ranks, feasible
    # count, winning block — surfaced as ReplayResult.decisions →
    # SimulateResult.decisions (a DecisionLog) and persisted by `tpusim
    # apply --decisions-out`. Bit-reproducible and engine-invariant
    # (decisions.INVARIANT_FIELDS) across the sequential/flat/blocked/
    # shard engines, and transparent to checkpoint kill/resume and fault
    # segmentation. Unsupported by the fused Pallas kernel (auto falls
    # back to the table engine; a forced engine: pallas raises) and by
    # extender configs / the sweep path.
    record_decisions: bool = False
    # In-scan cluster time-series plane (ISSUE 5; tpusim.obs.series):
    # > 0 makes every replay emit one bounded-shape SeriesSample each
    # `series_every` processed events FROM INSIDE the scan — node-
    # utilization histogram, per-FGD-category frag, feasible-node count,
    # per-policy normalized score extrema, DOWN-node count — surfaced as
    # ReplayResult.series → SimulateResult.series (a SeriesLog) and
    # persisted in the JSONL run record / Chrome counter tracks /
    # `tpusim apply --listen` live endpoint. Bit-identical across the
    # sequential/flat/blocked/shard engines and continuous across
    # checkpoint kill/resume and fault segmentation (the stride clock is
    # the carry's event counter). A static build flag (the sampling cond
    # bakes into the jaxpr): 0 = off, scan bodies compile identical to
    # pre-series builds. Unsupported by the fused Pallas kernel (auto
    # falls back to the table engine; a forced engine: pallas raises)
    # and by extender configs / the sweep path.
    series_every: int = 0
    # Fault-replay execution mode (ISSUE 10): "auto" runs fault
    # schedules INSIDE the compiled scan (tpusim.sim.fault_lane — fault
    # events + an in-carry retry queue as merged stream operands, the
    # chaos-sweep lane) whenever the config allows, falling back to the
    # PR 2 segmented host loop for configs only it can serve (per-event
    # reporting, extenders, decisions/series recording, checkpointing,
    # pallas, heartbeat). "scan" forces the in-scan lane (raises on
    # unsupported configs); "segments" forces the host loop. Both paths
    # are bit-identical for deterministic configs (the acceptance pin);
    # per-event-random configs (RandomScore / gpu_sel random) draw a
    # different — still seeded and reproducible — PRNG chain on the scan
    # lane, because the segmented path's per-segment key fold-in was an
    # artifact of the segmentation.
    fault_mode: str = "auto"
    # Device-mesh width: 0 = single device; N > 1 shards the node axis
    # over an N-device jax.sharding.Mesh and replays on the
    # explicit-collective shard_map engine (tpusim.parallel.shard_engine;
    # MULTICHIP.md). Placements stay bit-identical to the single-device
    # table engine, so merged analysis CSVs are unchanged. Requires N
    # visible devices and a deterministic config (no RandomScore /
    # gpuSelMethod random / extenders).
    mesh: int = 0


@dataclass
class UnscheduledPod:
    """ref: pkg/type/simulate_result.go:10-13."""

    pod: PodRow
    reason: str = "unschedulable"


@dataclass
class SimulateResult:
    """ref: pkg/type/simulate_result.go:5-18 + replay telemetry."""

    unscheduled_pods: List[UnscheduledPod]
    placed_node: np.ndarray  # i32[P] final node per pod (-1 = none)
    dev_mask: np.ndarray  # bool[P, 8]
    state: NodeState
    pods: List[PodRow]
    node_names: List[str]
    wall_seconds: float
    events: int
    # i64[P] position of each pod's creation event in scheduling order
    # (-1 = never created); feeds the assume-time annotation, whose purpose
    # is recovering scheduling order from a snapshot
    creation_rank: np.ndarray = None
    # tpusim.obs.RunTelemetry snapshot for this run: phase spans
    # (compile/execute split), exact in-scan counters, degrade/fault
    # counts, table-cache outcome. Always populated (the recorder is
    # always on); walls are only phase-attributed under cfg.profile.
    telemetry: object = None
    # tpusim.obs.decisions.DecisionLog for this run (records + the event
    # stream they describe), host-side. None unless
    # SimulatorConfig.record_decisions; fault runs concatenate their
    # segment streams, schedule_additional appends.
    decisions: object = None
    # tpusim.obs.series.SeriesLog for this run (filtered samples on the
    # run-global event clock, host-side). None unless
    # SimulatorConfig.series_every > 0; fault runs concatenate their
    # segment logs (pos rebased, retry_depth filled per segment),
    # schedule_additional appends.
    series: object = None


_BELLMAN_SRC_DIGEST = None
_ENGINE_SRC_DIGEST = None


def _engine_source_digest() -> bytes:
    """sha256 over every source file that determines a replay trajectory —
    the checkpoint content key's version salt (the Bellman-cache pattern):
    changing any engine/policy/op code invalidates all prior checkpoints
    instead of resuming into divergence."""
    global _ENGINE_SRC_DIGEST
    if _ENGINE_SRC_DIGEST is None:
        import glob
        import hashlib

        h = hashlib.sha256()
        base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = [
            os.path.join(base, rel)
            for rel in (
                "sim/engine.py", "sim/step.py", "sim/table_engine.py",
                "parallel/shard_engine.py", "io/storage.py", "constants.py",
                "types.py",
                # the counter vocabulary shapes the carry's ctr leaf (and
                # thus the checkpoint layout); changing it must invalidate
                # old checkpoints and cached tables rather than resume into
                # a layout mismatch
                "obs/counters.py",
                # the decision vocabulary shapes the checkpointed decision
                # stream (ISSUE 4) — same invalidation discipline
                "obs/decisions.py",
                # the series vocabulary shapes the checkpointed sample
                # stream (ISSUE 5) — same invalidation discipline
                "obs/series.py",
                # the fault vocabulary shapes the fault-lane trajectory
                # and the FaultCarry layout (ISSUE 10) — same discipline
                "sim/fault_lane.py",
                # the learned-policy feature kernels are score plugins
                # like everything under policies/ (ISSUE 14): editing a
                # feature must invalidate checkpoints and cached tables
                # built from the old vocabulary
                "learn/policy.py",
            )
        ]
        files += glob.glob(os.path.join(base, "policies", "*.py"))
        files += glob.glob(os.path.join(base, "ops", "*.py"))
        for path in sorted(files):
            if os.path.isfile(path):
                with open(path, "rb") as f:
                    h.update(f.read())
        _ENGINE_SRC_DIGEST = h.digest()
    return _ENGINE_SRC_DIGEST


def validate_events(ev_kind, ev_pod, num_pods: int) -> None:
    """Trace validation at run_events entry: a malformed event stream must
    fail loudly HERE, not produce silent wrong answers downstream — under
    jit, an out-of-range pod index turns the Bind scatter into a dropped
    write (XLA scatter semantics) and an unknown kind is clipped into
    EV_SKIP, both of which replay 'successfully' with quietly wrong
    placements and metrics."""
    from tpusim.sim.engine import EV_CREATE, EV_SKIP

    kinds = np.asarray(ev_kind)
    pods = np.asarray(ev_pod)
    if kinds.ndim != 1 or pods.shape != kinds.shape:
        raise ValueError(
            f"event stream shape mismatch: ev_kind {kinds.shape} vs "
            f"ev_pod {pods.shape} (want matching 1-D arrays)"
        )
    bad = (kinds < EV_CREATE) | (kinds > EV_SKIP)
    if bad.any():
        i = int(np.flatnonzero(bad)[0])
        raise ValueError(
            f"event {i}: unknown kind {int(kinds[i])} (expected EV_CREATE=0"
            " | EV_DELETE=1 | EV_SKIP=2; NodeFail/NodeRecover/Evict fault"
            " events are host-level — route them through"
            " Simulator.schedule_pods_with_faults, not run_events)"
        )
    oob = (pods < 0) | (pods >= num_pods)
    if oob.any():
        i = int(np.flatnonzero(oob)[0])
        raise ValueError(
            f"event {i}: pod index {int(pods[i])} out of range for "
            f"{num_pods} pods — a bad trace would otherwise become a "
            "silent no-op scatter under jit"
        )


def _bellman_source_digest() -> bytes:
    """sha256 of the native Bellman evaluator source + the Python fallback
    — the cache-key version salt (computed once per process)."""
    global _BELLMAN_SRC_DIGEST
    if _BELLMAN_SRC_DIGEST is None:
        import hashlib

        h = hashlib.sha256()
        base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for rel in ("native/bellman.cpp", "native/__init__.py",
                    "ops/frag.py"):
            path = os.path.join(base, rel)
            if os.path.isfile(path):
                with open(path, "rb") as f:
                    h.update(f.read())
        _BELLMAN_SRC_DIGEST = h.digest()
    return _BELLMAN_SRC_DIGEST


class Simulator:
    """Drives one cluster + workload through the compiled replay.

    Method surface mirrors simulator.Interface (core.go:43-74); the fake
    API server / informer machinery has no equivalent — cluster state is
    the NodeState array itself.
    """

    def __init__(self, nodes: Sequence[NodeRow], cfg: SimulatorConfig = None):
        self.cfg = cfg or SimulatorConfig()
        self.nodes = list(nodes)
        self.node_names = [n.name for n in self.nodes]
        self.node_index = {n.name: i for i, n in enumerate(self.nodes)}
        self.init_state = nodes_to_state(self.nodes)
        self.rank = jnp.asarray(tiebreak_rank(len(self.nodes), self.cfg.seed))
        self.log = LogSink(stream=None)
        # the observability plane (tpusim.obs): spans + counters are
        # always recorded (two perf_counter calls per phase); profile=True
        # additionally blocks per phase for the compile/execute split
        from tpusim.obs import Recorder

        self.obs = Recorder(enabled=self.cfg.profile)
        self._bellman_eval = None
        self._bellman_pending_replay = None
        self.workload_pods: List[PodRow] = []
        self.typical: Optional[TypicalPods] = None
        self.node_total_milli_cpu = int(sum(n.cpu_milli for n in self.nodes))
        self.node_total_milli_gpu = int(sum(n.gpu * MILLI for n in self.nodes))
        self.total_gpus = int(sum(n.gpu for n in self.nodes))
        self._policy_fns = [
            (
                make_policy(
                    name,
                    dim_ext_method=self.cfg.dim_ext_method,
                    norm_method=self.cfg.norm_method,
                ),
                weight,
            )
            for name, weight in self.cfg.policies
        ]
        # the sequential oracle replay; run_events() below picks between it
        # and the incremental table engine per call. Engines always run
        # metric-free: the per-event report series is reconstructed from
        # replay telemetry by the shared post-pass (tpusim.sim.metrics) —
        # identical across engines by construction
        if self.cfg.record_decisions and self.cfg.extenders:
            raise ValueError(
                "record_decisions cannot combine with extenders (the "
                "host-loop extender engine splices HTTP scores the "
                "flight recorder does not capture)"
            )
        if self.cfg.series_every and self.cfg.extenders:
            raise ValueError(
                "series_every cannot combine with extenders (the "
                "host-loop extender engine has no in-scan sampling "
                "plane)"
            )
        if self.cfg.series_every < 0:
            raise ValueError(
                f"series_every must be >= 0 (got {self.cfg.series_every})"
            )
        self.replay_fn = make_replay(
            self._policy_fns,
            gpu_sel=self.cfg.gpu_sel_method,
            report=False,
            decisions=self.cfg.record_decisions,
            series_every=self.cfg.series_every,
        )
        # which engine the last run_events call dispatched to
        # (pallas | table | sequential) — bench/log labeling
        self._last_engine = None
        # the jitted vmapped wrapper the last sweep dispatched: its
        # _cache_size() is the executables census (svc worker, tuner, gate)
        self._last_sweep_fn = None
        # the score tables the last sweep built, alive on the device with
        # their proof (_sweep_tables): one entry, replaced on a miss
        self._resident_tables: Optional["_ResidentTables"] = None
        # run-level event offset the next heartbeat arm reports from
        # (the fault loop sets it per segment; plain runs leave it 0)
        self._hb_base = 0
        # run/job id the heartbeat ticks of this sim's scans carry
        # (ISSUE 7): the replay service sets it per job batch so the
        # shared /progress listener can keep per-job streams apart;
        # empty = the anonymous single-run behavior
        self._hb_job = ""
        # direct-CSV-path stashes (experiments/analysis.py analyze_sim):
        # per-event structured report data (one entry per reporting replay,
        # main schedule + inflation/deschedule stages, in log order) + the
        # accumulated cluster-analysis summary key/values across stages
        self.event_reports = []
        self.analysis_summary = {}
        self.failed_pod_lists = []
        from tpusim.sim.table_engine import make_table_replay

        # incremental score-table engine (tpusim.sim.table_engine): exact
        # same placements/state, ~4x faster. Since round 5 it also replays
        # per-event-random configs (RandomScore / gpuSelMethod random)
        # bit-identically — it follows the oracle's key-split discipline
        # and recomputes the draw per event instead of reading a table row
        self._table_fn = make_table_replay(
            self._policy_fns,
            gpu_sel=self.cfg.gpu_sel_method,
            report=False,
            block_size=self.cfg.block_size,
            heartbeat_every=self.cfg.heartbeat_every,
            decisions=self.cfg.record_decisions,
            series_every=self.cfg.series_every,
            unswitched=self.cfg.unswitched_select,
        )
        # fused whole-replay Pallas engine (tpusim.sim.pallas_engine): one
        # kernel for the entire event loop, ~4x the table engine on chip;
        # needs a column kernel per enabled policy. On CPU backends it runs
        # in interpreter mode — only sensible when forced (engine: pallas).
        if self.cfg.engine not in ("auto", "sequential", "table", "pallas"):
            raise ValueError(
                f"unknown engine {self.cfg.engine!r}: expected auto | "
                "sequential | table | pallas"
            )
        if self.cfg.table_residency not in ("auto", "vmem", "hbm"):
            raise ValueError(
                f"unknown table_residency {self.cfg.table_residency!r}: "
                "expected auto | vmem | hbm (the fused-Pallas table "
                "placement, ENGINES.md Round 19)"
            )
        from tpusim.sim import pallas_engine

        # report configs are no longer a pallas blocker: the engine replays
        # metric-free and the shared post-pass reconstructs the series
        self._pallas_ok = pallas_engine.supports(
            self._policy_fns, self.cfg.gpu_sel_method
        )
        if self.cfg.engine == "pallas" and not self._pallas_ok:
            raise ValueError(
                "engine: pallas requires a registered Pallas column kernel "
                "for every enabled policy and a non-random gpuSelMethod "
                "(see tpusim.sim.pallas_engine.supports)"
            )
        self._pallas_fn = None
        # HBM-residency twin (ENGINES.md Round 19), built lazily on the
        # first dispatch the residency select routes to it
        self._pallas_hbm_fn = None
        self._extender_fn = None  # built lazily on first extender replay
        self._shard_fn = None
        if self.cfg.mesh:
            # node-axis sharding over an N-device mesh: the shard_map
            # engine with hand-written collectives (flat per-event cost;
            # MULTICHIP.md). Built eagerly so misconfigurations (too few
            # devices, randomized configs) fail at construction.
            from tpusim.parallel import make_mesh
            from tpusim.parallel.shard_engine import make_shardmap_table_replay

            if self.cfg.extenders:
                raise ValueError("mesh and extenders cannot combine")
            if self.cfg.engine != "auto":
                # the mesh path IS an engine choice (the sharded table
                # engine); silently overriding a forced engine would
                # attribute shard_map numbers to whatever was requested
                raise ValueError(
                    f"mesh={self.cfg.mesh} selects the shard_map engine; "
                    f"it cannot combine with engine={self.cfg.engine!r} "
                    "(leave engine: auto)"
                )
            if self.cfg.mesh > len(jax.devices()):
                raise ValueError(
                    f"mesh={self.cfg.mesh} needs {self.cfg.mesh} devices; "
                    f"{len(jax.devices())} visible (virtual CPU meshes: set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "JAX_PLATFORMS=cpu)"
                )
            self._mesh = make_mesh(self.cfg.mesh)
            self._shard_fn = make_shardmap_table_replay(
                self._policy_fns, self._mesh,
                gpu_sel=self.cfg.gpu_sel_method,
                block_size=self.cfg.block_size,
                decisions=self.cfg.record_decisions,
                series_every=self.cfg.series_every,
            )
        if self.cfg.record_decisions and self.cfg.engine == "pallas":
            raise ValueError(
                "engine: pallas cannot record decisions (the fused kernel "
                "emits no per-event provenance); use the table, "
                "sequential, or shard engine"
            )
        if self.cfg.series_every and self.cfg.engine == "pallas":
            raise ValueError(
                "engine: pallas cannot emit the in-scan series (the fused "
                "kernel has no per-event sampling plane); use the table, "
                "sequential, or shard engine"
            )
        if self._pallas_ok and self.cfg.engine in ("auto", "pallas"):
            # Mosaic lowers on TPU backends only; anywhere else (cpu, gpu)
            # a forced `engine: pallas` runs the interpreter — correct but
            # slow, the CPU test lane's harness. `auto` never picks it off
            # TPU (run_events gates on the same predicate).
            self._pallas_interpret = jax.default_backend() != "tpu"
            self._pallas_fn = pallas_engine.make_pallas_replay(
                self._policy_fns,
                gpu_sel=self.cfg.gpu_sel_method,
                interpret=self._pallas_interpret,
            )

    def _attach_metrics(self, out, state, specs, ev_kind, ev_pod,
                        n_events=None):
        """Reconstruct the per-event report series from the replay's
        telemetry (the shared post-pass) when reporting is on, record the
        scan in obs (engine + in-scan counters, padding-corrected), and
        log the engine the dispatch used. `n_events` = true (pre-padding)
        event count for the log line."""
        true_e = int(ev_kind.shape[0]) if n_events is None else int(n_events)
        if self.cfg.heartbeat_every:
            # final 100% heartbeat tick (obs.heartbeat.complete): short
            # runs beat the 1/s rate limit and would otherwise finish
            # silently. The block is a no-op cost-wise — every consumer
            # of this result syncs on it right after anyway.
            from tpusim.obs import heartbeat as obs_heartbeat

            jax.block_until_ready(out.event_node)
            obs_heartbeat.complete(true_e)
        ctr = out.counters
        if ctr is None and self.obs.enabled:
            # engines whose loop does not count (fused pallas, extender):
            # derive the invariant prefix from the per-event telemetry —
            # exact for everything but `rebuilds` (which those engines
            # never pay). Profiling mode only: the readback syncs.
            from tpusim.obs.counters import counters_from_telemetry

            ctr = counters_from_telemetry(
                np.asarray(ev_kind), np.asarray(out.event_node)
            )
        self.obs.note_scan(
            self._last_engine, counters=ctr,
            pad_skips=int(out.event_node.shape[0]) - true_e, events=true_e,
        )
        if self.cfg.report_per_event:
            from tpusim.sim.metrics import compute_event_metrics

            with self.obs.span("metrics_postpass", events=true_e) as h:
                out = out._replace(
                    metrics=compute_event_metrics(
                        state, specs, ev_kind, ev_pod, out.event_node,
                        out.event_dev, self.typical,
                    )
                )
                h.dispatched()
                if self.obs.enabled:
                    jax.block_until_ready(out.metrics)
        # name the engine in the log: the fused engine's documented f32
        # divergence channel means TPU-vs-CPU result diffs must be
        # diagnosable from simon.log alone (the analysis parser ignores
        # unknown line families, so the CSV lanes are unaffected)
        if n_events is None:
            n_events = int(ev_kind.shape[0])
        self.log.info(
            f"[Engine] replay of {n_events} events ran on: {self._last_engine}"
        )
        return out

    def _dispatch_span(self, thunk, **meta):
        """Run one engine dispatch under an obs "scan" span. The
        dispatch/block split is the compile/execute split: the host
        returns from the jitted call once tracing+compile+enqueue are
        done, so dispatch_s on a cold call is dominated by compilation;
        profiling mode then blocks so block_s is the device execution.
        Un-profiled runs never add the sync point — async pipelining is
        untouched."""
        with self.obs.span("scan", **meta) as h:
            out = thunk()
            h.dispatched()
            if self.obs.enabled and out is not None:
                jax.block_until_ready(
                    [l for l in jax.tree.leaves(out)
                     if isinstance(l, jax.Array)]
                )
        return out

    def run_events(
        self, state, specs, ev_kind, ev_pod, key, bucket: int = 512,
        types=None, pod_rows=None, fork=None
    ):
        """Run the compiled replay on prepared arrays, auto-selecting the
        fastest engine that supports the configuration. Small batches
        (descheduler victims, inflation clones) stay on the sequential
        engine: the table init alone costs K full node-sweeps, which only
        amortizes when there are more events than distinct pod types.

        Pod/event axes are padded to `bucket` multiples (inert zero pods +
        EV_SKIP events) so that different seeds/traces of a sweep hit the
        same compiled executable instead of re-jitting per experiment;
        outputs are sliced back to true sizes. Callers replaying the same
        pod specs repeatedly (chunked streams) may pass a prebuilt
        `types = build_pod_types(specs)` to skip the host-side dedup."""
        from tpusim.sim.table_engine import build_pod_types, pad_pod_types

        # fail loudly on malformed traces BEFORE anything is dispatched —
        # under jit a bad pod index or kind degrades into silent no-op
        # scatters (see validate_events)
        validate_events(ev_kind, ev_pod, int(specs.cpu.shape[0]))

        if self.cfg.extenders:
            # extenders splice HTTP round-trips into every cycle — only
            # the host-loop engine can honor them; no padding needed
            if pod_rows is None:
                raise ValueError(
                    "extender-configured replays need the PodRow list "
                    "(run_events(..., pod_rows=...)) to build the "
                    "ExtenderArgs payloads"
                )
            if self._extender_fn is None:
                from tpusim.sim.extender import make_extender_replay

                self._extender_fn = make_extender_replay(
                    self._policy_fns, self.cfg.gpu_sel_method,
                    self.cfg.extenders,
                )
            self._last_engine = "extender"
            out = self._dispatch_span(
                lambda: self._extender_fn(
                    state, specs, ev_kind, ev_pod, self.typical, key,
                    self.rank, pod_rows, self.nodes,
                ),
                engine="extender", events=int(ev_kind.shape[0]),
            )
            return self._attach_metrics(out, state, specs, ev_kind, ev_pod)

        p, e = int(specs.cpu.shape[0]), int(ev_kind.shape[0])
        p2, e2 = _bucket_sizes(p, e, bucket)
        if fork is not None:
            # warm-state what-if (ISSUE 16): `fork = (base_ev_kind,
            # base_ev_pod, fork_event)` — this stream shares the base
            # run's prefix up to fork_event; _run_chunked resumes from
            # the base's nearest checkpoint at-or-before it. Only the
            # chunked table/shard paths can honor a fork; anything else
            # would silently full-replay, so fail loudly instead.
            if not (0 < self.cfg.checkpoint_every < e):
                raise ValueError(
                    "forked replay needs the chunked path: set "
                    "checkpoint_every in (0, num_events) "
                    f"(got {self.cfg.checkpoint_every} for {e} events)"
                )
            if self.cfg.engine not in ("table", "auto") and not self.cfg.mesh:
                raise ValueError(
                    f"forked replay needs the table or shard engine, "
                    f"not {self.cfg.engine!r}"
                )
            bk, bp, fev = fork
            if not 0 <= int(fev) <= int(np.asarray(bk).shape[0]):
                raise ValueError(
                    f"fork_event {fev} outside the base stream "
                    f"(0..{int(np.asarray(bk).shape[0])})"
                )
            # the base streams must carry the identical padding
            # discipline — the fork lookup's digest math is byte-exact
            _, be2 = _bucket_sizes(p, int(np.asarray(bk).shape[0]), bucket)
            bk, bp = _pad_events(jnp.asarray(bk), jnp.asarray(bp), be2,
                                 xp=jnp)
            fork = (bk, bp, int(fev))
        if self.cfg.heartbeat_every:
            # arm the host side of the in-scan progress ticks for this
            # dispatch (ETA needs the event total; the engine only ships
            # its processed count). The total is the PADDED stream e2 —
            # that is what the scan processes and what the carry counter
            # counts, so progress can never read > 100%
            from tpusim.obs import heartbeat as obs_heartbeat

            # base = events of the RUN already replayed by earlier
            # segments (the fault loop sets it; 0 otherwise), so chunked
            # and fault-segmented ticks report run-level progress/ETA
            obs_heartbeat.configure(
                self._hb_base + e2, "replay", base=self._hb_base,
                job=self._hb_job, worker=getattr(self, "_hb_worker", ""),
            )
        # dedup types from the UNPADDED specs (no spurious zero type); the
        # type_id axis is padded alongside the pod axis (padded events only
        # ever reference pod 0)
        if self.cfg.engine == "sequential" and not self.cfg.mesh:
            types = None
        elif types is None:
            types = build_pod_types(specs)
        specs, tid = _pad_specs(
            specs, p2, types.type_id if types is not None else None, xp=jnp
        )
        if types is not None and tid is not None:
            types = types._replace(type_id=tid)
        ev_kind, ev_pod = _pad_events(ev_kind, ev_pod, e2, xp=jnp)

        if self._shard_fn is not None:
            # mesh path: pad the node axis to the mesh width, shard state
            # + tie-break rank, replay with explicit collectives, then
            # slice the node axis back (pad rows are never chosen and
            # metric-inert)
            from tpusim.parallel import pad_nodes, shard_state

            n0 = state.num_nodes
            state_p, rank_p = pad_nodes(state, self.rank, self.cfg.mesh)
            state_p = shard_state(state_p, self._mesh)
            self._last_engine = f"shard_map (mesh={self.cfg.mesh})"
            # guard on the TRUE event count e, not the padded stream: a
            # tiny replay padded to a 512 bucket must not pay the digest/
            # checkpoint machinery it can never benefit from
            if 0 < self.cfg.checkpoint_every < e:
                # chunked scan with gather-to-host snapshots between
                # segments (exact resume; ENGINES.md "Checkpoint/resume").
                # Streams that fit in one segment skip the machinery — no
                # checkpoint could ever be written, so the digest/eval_shape
                # overhead would buy nothing
                out = self._dispatch_span(
                    lambda: self._run_chunked(
                        self._shard_fn, state_p, specs, types, ev_kind,
                        ev_pod, key, rank_p, fork=fork,
                    ),
                    engine=self._last_engine, events=e,
                )
            else:
                out = self._dispatch_span(
                    lambda: self._shard_fn(
                        state_p, specs, types, ev_kind, ev_pod,
                        self.typical, key, rank_p,
                    ),
                    engine=self._last_engine, events=e,
                )
            # the post-pass runs on the UNPADDED state: pad rows are never
            # chosen (every valid event_node < n0), and the f32 initial
            # totals then bracket exactly like a single-device run — so
            # the analysis CSVs come out byte-identical, not merely close
            out = self._attach_metrics(out, state, specs, ev_kind, ev_pod, e)
            out = out._replace(
                state=jax.tree.map(lambda a: a[:n0], out.state)
            )
            return _slice_result(out, p, e)

        out = None
        if types is not None:
            k = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
            big = k > 0 and e >= 2 * k
            if (big or (self.cfg.engine in ("table", "pallas") and k > 0)
                    or (fork is not None and k > 0)):
                if p2 != p or e2 != e:  # bucketed run: stabilize K too
                    types = pad_pod_types(types)
                # the fused Pallas engine wins whenever it applies; its
                # Mosaic path needs a real accelerator (auto never picks
                # the CPU interpreter — that is only for a forced
                # `engine: pallas` under the test lane). Decision-recording
                # runs never take it (the fused kernel emits no per-event
                # provenance; a forced engine: pallas raised at init)
                use_pallas = (
                    self._pallas_fn is not None
                    and fork is None  # fused kernel has no carry surface
                    and not self.cfg.record_decisions
                    and not self.cfg.series_every
                    and (
                        self.cfg.engine == "pallas"
                        or (self.cfg.engine == "auto" and big
                            and jax.default_backend() == "tpu")
                    )
                )
                if use_pallas:
                    # size-based routing only: a replay whose tables fit
                    # neither residency tier runs on the blocked table
                    # engine with a [Degrade] line; a kernel that fails
                    # to compile, dies or returns corrupt telemetry raises
                    out = self._run_pallas_degradable(
                        state, specs, types, ev_kind, ev_pod, key
                    )
                if out is None:
                    self._last_engine = "table"
                    # single-segment streams (true count e, not the padded
                    # stream) skip the checkpoint machinery entirely. The
                    # content-keyed init_tables reuse (obs records the
                    # hit/miss; None when disabled) resolves LAZILY on the
                    # chunked path: a run that resumes from a checkpoint
                    # restores its carry — tables included — and must not
                    # pay a table build/load it would immediately discard
                    if 0 < self.cfg.checkpoint_every < e:
                        out = self._dispatch_span(
                            lambda: self._run_chunked(
                                self._table_fn, state, specs, types,
                                ev_kind, ev_pod, key, self.rank,
                                tables_thunk=lambda: self._cached_tables(
                                    state, types, key
                                ),
                                fork=fork,
                            ),
                            engine="table", events=e,
                        )
                    else:
                        out = self._dispatch_span(
                            lambda: self._table_fn(
                                state, specs, types, ev_kind, ev_pod,
                                self.typical, key, self.rank,
                                tables=self._cached_tables(
                                    state, types, key
                                ),
                            ),
                            engine="table", events=e,
                        )
        if out is None:
            if fork is not None:
                raise ValueError(
                    "forked replay fell through to the sequential engine "
                    "(no pod types / carry surface) — run the base and "
                    "fork on the table or shard engine"
                )
            self._last_engine = "sequential"
            out = self._dispatch_span(
                lambda: self.replay_fn(
                    state, specs, ev_kind, ev_pod, self.typical, key,
                    self.rank,
                ),
                engine="sequential", events=e,
            )
        # post-pass metrics stay on device: the caller's device_fetch
        # moves everything in one transfer
        out = self._attach_metrics(out, state, specs, ev_kind, ev_pod, e)
        return _slice_result(out, p, e)

    # ---- fused-kernel dispatch: residency select, size-based routing ----

    def _run_pallas_degradable(self, state, specs, types, ev_kind, ev_pod,
                               key):
        """Run the fused Pallas engine on the residency tier its shape
        fits. Returns its ReplayResult, or None after a [Degrade] log
        line when NO tier fits and the replay must run on the (blocked)
        table engine instead — the only rerouting left, decided from the
        input size before anything is dispatched.

        Residency is two-tier (ENGINES.md Round 19): tier 1 is the
        all-VMEM-resident kernel (pallas_engine.fits_vmem), tier 2 the
        HBM-resident-table kernel whose VMEM working set is O(K·B + row
        scratch) (fits_hbm). cfg.table_residency forces a tier or lets
        select_residency pick.

        Nothing after the dispatch is caught: a Mosaic lowering or compile
        error, a kernel that dies mid-scan and out-of-range telemetry (the
        observable shadow of NaN/inf in the f32 score path) all raise. A
        replay that quietly reran on another engine would hide exactly the
        failure the chip run exists to show."""
        from tpusim.sim import pallas_engine

        n = state.num_nodes
        k = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
        num_pol = len(self._policy_fns)
        p = int(specs.cpu.shape[0])
        e = int(ev_kind.shape[0])
        n_norm = pallas_engine.num_normalized(self._policy_fns)
        res = self.cfg.table_residency
        if res == "auto":
            res = pallas_engine.select_residency(n, k, num_pol, p, e, n_norm)
        elif res == "vmem" and not pallas_engine.fits_vmem(
                n, k, num_pol, p, e):
            res = None
        elif res == "hbm" and not pallas_engine.fits_hbm(
                n, k, num_pol, p, e, n_norm):
            res = None
        if res is None:
            # the [Degrade] channel also lands in an obs counter so a
            # degraded run is machine-detectable from the JSONL record,
            # not just greppable from stdout prose
            self.obs.count("degrade_vmem")
            self.log.info(
                f"[Degrade] fused pallas kernel would overflow VMEM at "
                f"N={n}, K={k} under table_residency="
                f"{self.cfg.table_residency!r} (neither the VMEM- nor "
                "the HBM-residency tier fits the budget): falling back "
                "to the blocked table engine"
            )
            return None
        if res == "hbm" and self._pallas_hbm_fn is None:
            self._pallas_hbm_fn = pallas_engine.make_pallas_replay(
                self._policy_fns, gpu_sel=self.cfg.gpu_sel_method,
                interpret=self._pallas_interpret,
                residency="hbm",
            )
        fn = self._pallas_fn if res == "vmem" else self._pallas_hbm_fn
        self._last_engine = "pallas" if res == "vmem" else "pallas (hbm)"
        out = self._dispatch_span(
            lambda: fn(
                state, specs, types, ev_kind, ev_pod, self.typical,
                key, self.rank,
            ),
            engine=self._last_engine, events=e,
        )
        dma_stats = None
        if res == "hbm":
            # the kernel's exact in-kernel DMA counters (semaphore
            # waits, DMA starts, extrema-drift summary rebuilds) —
            # surfaced in the obs run record below
            out, dma_stats = out
        bad = self._pallas_result_suspect(out, n)
        if bad:
            raise RuntimeError(
                f"fused pallas replay ({self._last_engine}) returned "
                f"corrupt telemetry: {bad} (NaN/inf in the f32 score "
                "tables?)"
            )
        self.obs.pallas_residency = res
        self.obs.count(f"pallas_residency_{res}")
        if dma_stats is not None:
            waits, starts, rebuilds = (int(v) for v in np.asarray(dma_stats))
            self.obs.count("pallas_dma_waits", waits)
            self.obs.count("pallas_dma_starts", starts)
            self.obs.count("pallas_hbm_rebuilds", rebuilds)
        return out

    def _pallas_result_suspect(self, out, num_nodes: int):
        """Cheap host-side sanity screen over a fused-kernel result: every
        placement/telemetry index must lie in [-1, N). NaN/inf poisoning
        the kernel's f32 score path surfaces as wild argmax indices, which
        this catches without exporting the tables themselves. Returns a
        description or None. Costs one [E]+[P] i32 readback — noise next
        to the replay itself."""
        ev_node = np.asarray(out.event_node)
        placed = np.asarray(out.placed_node)
        if ev_node.size and ((ev_node < -1) | (ev_node >= num_nodes)).any():
            return "event_node out of range"
        if placed.size and ((placed < -1) | (placed >= num_nodes)).any():
            return "placed_node out of range"
        return None

    # ---- exact checkpoint/resume of the chunked event scan ----

    def _checkpoint_dir(self) -> str:
        d = self.cfg.checkpoint_dir or os.environ.get(
            "TPUSIM_CHECKPOINT_DIR", ""
        )
        if not d:
            d = os.path.join(
                os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))), ".tpusim_checkpoints")
        return d

    # ---- content-keyed init_tables cache (ROADMAP open item) ----

    def _table_cache_dir(self) -> str:
        return self.cfg.table_cache_dir or os.environ.get(
            "TPUSIM_TABLE_CACHE_DIR", ""
        )

    def _tables_digest(self, state, types) -> str:
        """Content key of one table build: the engine-source salt + the
        scoring config + every input init_tables reads (initial state,
        the DISTINCT pod type set, typical pods). Deliberately NOT the
        event stream, PRNG key, tie-break rank, the per-policy WEIGHTS,
        or the per-pod `type_id` map — the build never consumes them
        (tables hold raw per-policy scores per distinct type; weights
        joined the run inputs when they became a traced operand, ISSUE 6,
        and type_id — which fingerprints the TUNED workload, i.e. the
        tune factor — moved to the run key with the trace-operand lift,
        ISSUE 7: the run digest's specs/events already embed it). So
        every seed/weight-vector/tune-factor over the same cluster +
        type set shares one entry — a whole what-if batch reuses one
        table build."""
        from tpusim.io.storage import checkpoint_digest

        cfg = self.cfg

        def chunks():
            yield _engine_source_digest()
            yield repr((
                tuple(name for name, _ in cfg.policies),
                cfg.gpu_sel_method, cfg.dim_ext_method,
                cfg.norm_method,
            )).encode()
            for leaf in (
                jax.tree.leaves(state)
                + jax.tree.leaves(types.share) + jax.tree.leaves(types.whole)
                + jax.tree.leaves(self.typical)
            ):
                yield np.asarray(leaf).tobytes()

        return checkpoint_digest(chunks())

    def _cached_tables(self, state, types, key):
        """(score_tbl, sdev_tbl, feas_tbl) for the single-device table
        engine from the content-keyed disk cache, building + persisting
        on miss — or None when caching is disabled (the engine then
        builds the tables inside init_carry exactly as before). A hit
        skips the K-node-sweep build (0.62 s at N=100k, K=71) for a digest
        that copies the state to the host, a file load and a transfer of
        the tables; results are bit-identical either way because every
        downstream aggregate is a pure function of the tables. obs records
        the outcome. A sweep comes here through _sweep_tables, after the
        tables its last sweep left on the device."""
        cache_dir = self._table_cache_dir()
        if not cache_dir:
            return None
        from tpusim.io import storage

        names = ("score_tbl", "sdev_tbl", "feas_tbl")
        digest = self._tables_digest(state, types)
        found = storage.find_tables(cache_dir, digest)
        if found is not None:
            try:
                with self.obs.span("init_tables", cache="hit") as h:
                    arrays = storage.load_tables(found)
                    tables = tuple(jnp.asarray(arrays[k]) for k in names)
                    h.dispatched()
                self.obs.table_cache = "hit"
                self.obs.count("table_cache_hit")
                self.log.info(
                    f"[TableCache] reused init tables from "
                    f"{os.path.basename(found)}"
                )
                return tables
            except Exception as err:
                # torn/stale file: content addressing makes a rebuild
                # always safe; drop the unusable entry
                self.log.info(
                    f"[TableCache] dropping unusable entry "
                    f"{os.path.basename(found)} ({err}); rebuilding"
                )
                try:
                    os.unlink(found)
                except OSError:
                    pass
        with self.obs.span("init_tables", cache="miss") as h:
            tables = self._table_fn.build_tables(
                state, types, self.typical, key
            )
            h.dispatched()
            host = [np.asarray(t) for t in tables]  # also blocks the build
        self.obs.table_cache = "miss"
        self.obs.count("table_cache_miss")
        path = storage.save_tables(
            cache_dir, digest, dict(zip(names, host))
        )
        self.log.info(
            f"[TableCache] saved init tables to {os.path.basename(path)}"
        )
        return tables

    def _sweep_tables(self, engine, state, types, typical, key):
        """((score_tbl, sdev_tbl, feas_tbl), reused) for one sweep of the
        table engine `engine`, under its one init_tables span: the tables
        the last sweep of this Simulator left on the device when they are
        PROVEN right for this one (cache="resident", reused 1), else the
        disk cache where one is configured (_cached_tables: "hit" |
        "miss"), else the engine's build ("sweep-shared"). Whatever a miss
        obtains replaces the entry, so an unchanged cluster and type set
        build once a Simulator and not once a wave (0.62 s of a 2.43 s
        wave at 100,000 nodes, K = 71: PERF.md section 6, PR 31).

        `typical` is what the sweep scores against: the Simulator's set
        ([T] leaves) or F sets stacked ([F, T], one a family of lanes:
        schedule_pods_sweep's lane_typical). F sets build F table sets
        from the one state and type set, stacked [F, ...] like them, and
        the entry proves and keeps them together: the proof compares the
        stacked rows by content, so one changed family misses and builds
        all F, and reused is 1 only when every set's proof holds. The
        span's `cache` then counts the sets ("resident F of F" | "built F
        of F"); the disk tier keys the Simulator's own set and is not
        asked for stacked ones.

        The proof runs ahead of the span, whose `cache=` it decides: a
        hit's span holds the hand-over alone. The sweep wrapper broadcasts
        the tables and donates neither them nor the state (_sweep_engine),
        so a wave leaves both intact. A miss is always safe; what cannot
        be proven misses."""
        obs = self.obs
        sets = int(typical.cpu.shape[0]) if typical.cpu.ndim == 2 else 0
        proof = _tables_proof(engine, state, types, typical)
        held = self._resident_tables
        if _same_build(held, proof):
            cache = f"resident {sets} of {sets}" if sets else "resident"
            with obs.span("init_tables", cache=cache):
                obs.count("table_resident_hit")
                return held.tables, 1
        # one entry a Simulator: the old set goes before the new one is
        # built, so the two never share the device
        self._resident_tables = held = None
        if sets:
            with obs.span("init_tables", cache=f"built {sets} of {sets}") as h:
                built = [
                    engine.build_tables(
                        state, types, jax.tree.map(lambda a: a[f], typical),
                        key)
                    for f in range(sets)
                ]
                tables = tuple(jnp.stack(tbl) for tbl in zip(*built))
                obs.settle(h, tables)
        else:
            tables = self._cached_tables(state, types, key)
            if tables is None:
                with obs.span("init_tables", cache="sweep-shared") as h:
                    tables = engine.build_tables(state, types, typical, key)
                    obs.settle(h, tables)
        if proof is not None:
            self._resident_tables = proof._replace(tables=tuple(tables))
        return tables, 0

    def drop_resident_tables(self):
        """Free the score tables the last sweep left on the device (64 MB
        at 100,000 nodes and K = 71); the next sweep builds, as a first
        one does. For an owner of several Simulators that bounds what
        they pin together (svc.worker)."""
        self._resident_tables = None

    def _run_digest(self, state, specs, ev_kind, ev_pod, key, rank) -> str:
        """Content key of one replay run: the engine-source version salt +
        every input that determines the trajectory (initial state, pod
        specs, typical pods, event stream, PRNG key, tie-break rank, and
        — since the weight vector became a traced operand, ISSUE 6 — the
        per-policy weights, hashed as a RUN INPUT leaf rather than part
        of the static config vocabulary) + the scheduling config.
        checkpoint_every deliberately does NOT participate — chunk
        boundaries are an arbitrary partition, so a resume may use a
        different segment length. A weight change still invalidates
        (different operand bytes ⇒ different digest): the blocked
        summaries inside a checkpointed carry embed the weights, so
        resuming one under different weights would silently diverge."""
        from tpusim.io.storage import checkpoint_digest

        cfg = self.cfg

        def chunks():
            yield _engine_source_digest()
            # record_decisions/series_every participate: a recording run's
            # checkpoints carry the accumulated decision/sample streams,
            # which a non-recording run's do not — the layouts must never
            # mix (and the sample stream's stride is series_every itself)
            yield repr((
                tuple(name for name, _ in cfg.policies),
                cfg.gpu_sel_method, cfg.dim_ext_method,
                cfg.norm_method, cfg.block_size, cfg.mesh,
                cfg.record_decisions, cfg.series_every,
            )).encode()
            for leaf in (
                jax.tree.leaves(state) + jax.tree.leaves(specs)
                + jax.tree.leaves(self.typical)
                + [ev_kind, ev_pod, key, rank,
                   np.asarray([w for _, w in cfg.policies], np.int32)]
            ):
                yield np.asarray(leaf).tobytes()

        return checkpoint_digest(chunks())

    def _run_chunked(self, fn, state, specs, types, ev_kind, ev_pod, key,
                     rank, tables_thunk=None, fork=None):
        """Chunked replay with exact checkpoint/resume: cut the event scan
        into checkpoint_every-event segments via the engine's carry surface
        (fn.init_carry / run_chunk / finish), snapshot the full carry to
        host after each segment (for the shard engine this IS the
        gather-to-host snapshot — np.asarray collects the shards), persist
        it content-addressed (tpusim.io.storage), and on entry resume from
        the newest matching checkpoint. Chaining segments is bit-identical
        to one unsegmented scan (see table_engine.FlatTableCarry), so a
        killed-and-resumed run reproduces the uninterrupted run's
        placements, telemetry, metrics, and final tables exactly.

        `fork = (base_ev_kind, base_ev_pod, fork_event)` is the
        warm-state what-if mode (ISSUE 16): this run's stream shares the
        base run's prefix up to `fork_event`, so when no checkpoint of
        THIS run exists, resume instead from the base run's nearest
        checkpoint at-or-before the divergence point (the base streams
        must already carry this run's padding — the digest math demands
        byte-equal inputs) and replay only the divergent tail. A carry
        restored at cursor c <= fork_event has consumed only shared
        events, so the continuation is bit-identical to the from-event-0
        replay of the forked stream. Missing/torn fork sources degrade
        loudly to a full replay — correct, just cold."""
        from tpusim.io import storage as ckpt
        from tpusim.obs import heartbeat as obs_heartbeat
        from tpusim.obs.decisions import DecisionRecord
        from tpusim.obs.series import SeriesSample
        from tpusim.sim.engine import ReplayResult

        e = int(ev_kind.shape[0])
        every = max(1, int(self.cfg.checkpoint_every))
        cache_dir = self._checkpoint_dir()
        digest = self._run_digest(state, specs, ev_kind, ev_pod, key, rank)
        # expose the run's content identity: the svc fork index persists
        # it so what-if jobs can find this run's checkpoints later
        self.last_run_digest = digest
        self.last_checkpoint_dir = cache_dir
        self._fork_stats = None
        template = jax.eval_shape(
            fn.init_carry, state, specs, types, self.typical, key, rank
        )
        tleaves, tdef = jax.tree.flatten(template)
        record_dec = self.cfg.record_decisions
        dec_fields = DecisionRecord._fields
        record_ser = bool(self.cfg.series_every)
        ser_fields = SeriesSample._fields

        carry = None
        cursor = 0
        node_parts: list = []
        dev_parts: list = []
        dec_parts: list = []  # DecisionRecord-of-np per segment (ISSUE 4)
        ser_parts: list = []  # SeriesSample-of-np per segment (ISSUE 5)
        def _validate(arrays):
            """Layout check against the carry template — a vocabulary or
            shape drift reads as corrupt and the resume walks back."""
            leaves = [arrays[f"c{i:03d}"] for i in range(len(tleaves))]
            if any(
                a.shape != t.shape or a.dtype != t.dtype
                for a, t in zip(leaves, tleaves)
            ):
                raise ValueError("carry layout mismatch")
            arrays["event_node"], arrays["event_dev"]  # must exist
            if record_dec:
                for f in dec_fields:
                    arrays[f"dec_{f}"]
            if record_ser:
                for f in ser_fields:
                    arrays[f"ser_{f}"]

        def _on_skip(path, err):
            # torn/truncated/stale file (ISSUE 10 satellite): skip it
            # with a [Degrade] warning and fall back to the newest VALID
            # checkpoint instead of crashing (or silently restarting).
            # The unusable file is deleted so it cannot shadow future
            # saves below its cursor.
            self.obs.count("degrade_checkpoint")
            self.log.info(
                f"[Degrade] skipping unusable checkpoint "
                f"{os.path.basename(path)} ({err}); trying the newest "
                "valid predecessor"
            )

        found = ckpt.load_valid_checkpoint(
            cache_dir, digest, validate=_validate, on_skip=_on_skip
        )
        if fork is not None:
            base_kind, base_pod, fork_event = fork
            fork_event = int(fork_event)
            # the base run's content identity: same inputs except its
            # OWN event stream (identical prefix, different tail)
            base_digest = self._run_digest(
                state, specs, base_kind, base_pod, key, rank
            )
            self._fork_stats = {
                "base_digest": base_digest, "fork_event": fork_event,
                "source_cursor": 0, "degrade": False,
            }
            if found is None:
                # nearest base checkpoint at-or-before the divergence
                # point: its carry consumed only the SHARED prefix, so
                # continuing it with the forked stream is exact
                found = ckpt.load_valid_checkpoint(
                    cache_dir, base_digest, validate=_validate,
                    on_skip=_on_skip, max_cursor=fork_event,
                    delete_invalid=False,
                )
                if found is None:
                    self.obs.count("degrade_fork")
                    self._fork_stats["degrade"] = True
                    self.log.info(
                        f"[Degrade] no usable fork source at-or-before "
                        f"event {fork_event} for base "
                        f"{base_digest[:12]}…; full replay from event 0"
                    )
        if found is not None:
            cursor, arrays, path = found
            if self._fork_stats is not None:
                self._fork_stats["source_cursor"] = cursor
            leaves = [arrays[f"c{i:03d}"] for i in range(len(tleaves))]
            carry = jax.tree.unflatten(
                tdef, [jnp.asarray(a) for a in leaves]
            )
            node_parts = [arrays["event_node"]]
            dev_parts = [arrays["event_dev"]]
            if record_dec:
                # the decision stream accumulated so far rides the
                # checkpoint beside event_node/event_dev, so a resumed
                # run's stream is continuous
                dec_parts = [DecisionRecord(
                    *(arrays[f"dec_{f}"] for f in dec_fields)
                )]
            if record_ser:
                # likewise the per-event sample stream (ISSUE 5): the
                # stride clock itself is the carry's ctr leaf, so the
                # resumed scan keeps sampling on the same grid
                ser_parts = [SeriesSample(
                    *(arrays[f"ser_{f}"] for f in ser_fields)
                )]
            if self.cfg.heartbeat_every:
                # the resumed carry's event counter already includes
                # `cursor` events this process never executed — keep
                # the tick line / /progress ev-per-s honest
                obs_heartbeat.note_resume(cursor)
            self.log.info(
                f"[Checkpoint] resumed replay at event {cursor}/{e} "
                f"from {os.path.basename(path)}"
            )
        if carry is None:
            # only now resolve the table cache (table engine only): a
            # resumed run never reaches here and must not pay the build
            tables = tables_thunk() if tables_thunk is not None else None
            if tables is not None:
                carry = fn.init_carry(
                    state, specs, types, self.typical, key, rank, tables
                )
            else:
                carry = fn.init_carry(
                    state, specs, types, self.typical, key, rank
                )

        # chunk advances go through the DONATING entry (ISSUE 11): the
        # input carry's buffers are reused by the next segment instead of
        # reallocating the O(N*K) tables every chunk. Safe by
        # construction: the checkpoint snapshot below (np.asarray) copies
        # the carry to host BEFORE the next donating dispatch consumes
        # it, and nothing else holds a reference — the loop variable is
        # rebound. Bit-identity is untouched (same jaxpr, only buffer
        # aliasing moves).
        run_chunk = getattr(fn, "run_chunk_donated", None) or fn.run_chunk
        while cursor < e:
            end = min(cursor + every, e)
            carry, ys = run_chunk(
                carry, specs, types, ev_kind[cursor:end],
                ev_pod[cursor:end], self.typical, rank,
            )
            nseg, dseg = ys[0], ys[1]
            rest = list(ys[2:])
            if record_dec:
                dec_parts.append(jax.tree.map(np.asarray, rest.pop(0)))
            if record_ser:
                ser_parts.append(jax.tree.map(np.asarray, rest.pop(0)))
            node_parts.append(np.asarray(nseg))
            dev_parts.append(np.asarray(dseg))
            cursor = end
            if cursor < e:
                # gather-to-host snapshot + atomic content-addressed save;
                # the final segment skips it (the run completes right after)
                host = jax.tree.map(np.asarray, carry)
                arrays = {
                    f"c{i:03d}": a
                    for i, a in enumerate(jax.tree.leaves(host))
                }
                arrays["event_node"] = np.concatenate(node_parts)
                arrays["event_dev"] = np.concatenate(dev_parts)
                if record_dec:
                    for f in dec_fields:
                        arrays[f"dec_{f}"] = np.concatenate(
                            [np.asarray(getattr(p, f)) for p in dec_parts]
                        )
                if record_ser:
                    for f in ser_fields:
                        arrays[f"ser_{f}"] = np.concatenate(
                            [np.asarray(getattr(p, f)) for p in ser_parts]
                        )
                ckpt.save_checkpoint(cache_dir, digest, cursor, arrays)
                ckpt.prune_checkpoints(
                    cache_dir, digest, cursor, keep=self.cfg.checkpoint_keep
                )

        state_f, placed, masks, failed = fn.finish(carry)
        # run completed: retention-gated (checkpoint_keep != 0 preserves
        # the mid-trace ladder the svc fork index references)
        ckpt.prune_checkpoints(
            cache_dir, digest, e + 1, keep=self.cfg.checkpoint_keep
        )
        nodes = (
            np.concatenate(node_parts) if node_parts
            else np.zeros(0, np.int32)
        )
        devs = (
            np.concatenate(dev_parts) if dev_parts
            else np.zeros((0, 8), bool)
        )
        decs = None
        if record_dec and dec_parts:
            decs = DecisionRecord(*(
                np.concatenate([np.asarray(getattr(p, f)) for p in dec_parts])
                for f in dec_fields
            ))
        sers = None
        if record_ser and ser_parts:
            # the concatenation of segment sample streams IS the
            # unsegmented scan's stream (per-event ys, sentinels included)
            sers = SeriesSample(*(
                np.concatenate([np.asarray(getattr(p, f)) for p in ser_parts])
                for f in ser_fields
            ))
        # the carry's counter leaf accumulated across every segment AND
        # any resumed-from checkpoint — telemetry continuity through
        # kill/resume comes for free from the carry being the checkpoint
        return ReplayResult(
            state_f, placed, masks, failed, None,
            jnp.asarray(nodes), jnp.asarray(devs), carry.ctr, decs, sers,
        )

    # ---- workload prep (core.go:103-142) ----

    def set_workload_pods(self, pods: Sequence[PodRow]):
        self.workload_pods = list(pods)

    def set_typical_pods(self):
        with self.obs.span("typical_pods", pods=len(self.workload_pods)):
            self._set_typical_pods_impl()

    def _set_typical_pods_impl(self):
        self.typical, self._typical_info = get_typical_pods(
            self.workload_pods, self.cfg.typical_pods
        )
        # pad the typical axis to a bucket with zero-frequency rows: every
        # frag/score kernel weights contributions by freq, so zero rows are
        # exact no-ops, and a stable T means sweeps across trace variants
        # (whose distribution sizes differ) reuse one compiled replay
        self.typical = pad_typical_pods(self.typical)
        # host copy for the native Bellman evaluator, one transfer

        self._typical_host = device_fetch(self.typical)
        # The Bellman evaluator (and its memo) is scoped to ONE experiment
        # run, like the reference's fragMemo (simulator.go:58): memoized
        # values embed the cum_prob cutoff context of their first
        # computation, so sharing across experiments would make report
        # values depend on sweep order.
        self._bellman_eval = None
        self._bellman_pending_replay = None
        self.log.info(f"Num of Total Pods: {len(self.workload_pods)}")
        self.log.info(f"Num of Total Pod Sepc: {len(self._typical_info)}")

    def adopt_typical_pods(self, other: "Simulator"):
        """set_typical_pods, copying the (immutable) distribution from a
        same-workload sibling instead of recomputing + re-uploading it —
        a seed group, where all S sims share the workload the distribution
        derives from (run_batch validates that). Emits the same log lines;
        the Bellman evaluator stays per-experiment (its memo embeds
        evaluation-order context)."""
        self.typical = other.typical
        self._typical_info = other._typical_info
        self._typical_host = other._typical_host
        self._bellman_eval = None
        self._bellman_pending_replay = None
        self.log.info(f"Num of Total Pods: {len(self.workload_pods)}")
        self.log.info(f"Num of Total Pod Sepc: {len(self._typical_info)}")

    def set_skyline_pods(self):
        self.skyline = get_skyline_pods(self.workload_pods)

    def get_custom_config(self) -> SimulatorConfig:
        """ref: GetCustomConfig (core.go:69)."""
        return self.cfg

    def record_pod_total_resource(self, pods: Sequence[PodRow] = None):
        """Total workload CPU/GPU milli (ref: RecordPodTotalResource,
        core.go:132; consumed by tuning/inflation ratios)."""
        from tpusim.sim.workload import total_pod_cpu_milli, total_pod_gpu_milli

        pods = self.workload_pods if pods is None else pods
        self.pod_total_milli_cpu = total_pod_cpu_milli(pods)
        self.pod_total_milli_gpu = total_pod_gpu_milli(pods)
        return self.pod_total_milli_cpu, self.pod_total_milli_gpu

    def record_node_total_resource(self):
        """Total cluster CPU/GPU milli (ref: RecordNodeTotalResource,
        core.go:133). Computed at construction; exposed for parity."""
        return self.node_total_milli_cpu, self.node_total_milli_gpu

    def get_cluster_node_status(self):
        """[(NodeRow, [PodRow placed on it])] (ref: GetClusterNodeStatus,
        core.go:56 → simontype.NodeStatus)."""
        res = self.last_result
        by_node = [[] for _ in self.nodes]
        for i, n in enumerate(res.placed_node):
            if n >= 0:
                by_node[int(n)].append(res.pods[i])
        return list(zip(self.nodes, by_node))

    def prepare_pods(
        self, tuning_ratio: float = None, tuning_seed: int = None
    ) -> List[PodRow]:
        """SortClusterPods + tuning (core.go:131-142). The tune knobs
        default to the config's; per-call overrides feed the multi-trace
        sweep (ISSUE 7) — a lane prepared with (ratio, seed) here is
        byte-identical to a standalone run configured with them, because
        the rng discipline is the same: one generator seeded by
        tuning_seed drives the shuffle and then the clone draws."""
        ratio = (
            self.cfg.tuning_ratio if tuning_ratio is None
            else float(tuning_ratio)
        )
        seed = (
            self.cfg.tuning_seed if tuning_seed is None else int(tuning_seed)
        )
        rng = np.random.default_rng(seed)
        pods = sort_cluster_pods(
            list(self.workload_pods), self.cfg.shuffle_pod, rng
        )
        if ratio > 0:
            pods = tune_pods(
                pods, self.node_total_milli_gpu, ratio, rng
            )
        return pods

    # ---- the run (core.go:148 RunCluster → SchedulePods) ----

    def _replay_pods(self, state, pods: Sequence[PodRow], key, use_timestamps: bool):
        """Run the compiled replay for `pods` on `state`. Returns
        (replay output, events, unscheduled list). Pods carrying the
        simon/pod-unscheduled annotation are skipped by the event loop and
        reported as failed (simulator.go:391-399). The full replay output
        moves to host in ONE transfer (fetch.device_fetch) instead of one
        readback per leaf."""

        specs = pods_to_specs(pods, self.node_index)
        ev_kind, ev_pod = build_events(pods, use_timestamps)
        out = self.run_events(
            state, specs, jnp.asarray(ev_kind), jnp.asarray(ev_pod), key,
            pod_rows=pods,
        )
        with self.obs.span("fetch", events=len(ev_kind)):
            out = device_fetch(out)
        return self._finish_replay(out, pods, ev_kind, ev_pod, state)

    def _finish_replay(self, out, pods, ev_kind, ev_pod, state):
        """Host-side tail of a replay: per-event report lines, unscheduled
        list, creation ranks. `out` must already be on host."""
        if out.decisions is not None:
            # pair the decision stream with the events it describes — the
            # DecisionLog the emitter/explain/diff surface consumes
            from tpusim.obs.decisions import DecisionLog

            out = out._replace(decisions=DecisionLog(
                jax.tree.map(np.asarray, out.decisions),
                np.asarray(ev_kind), np.asarray(ev_pod),
            ))
        if out.series is not None:
            # filter the stacked per-event samples down to the real
            # stride points (the host-side SeriesLog); standalone replays
            # start the event clock at 0 with an empty retry queue
            from tpusim.obs.series import log_from_stacked

            out = out._replace(series=log_from_stacked(out.series))
        self._emit_event_reports(out, pods, ev_kind, ev_pod, state)
        skipped = np.array([p.unscheduled for p in pods], bool)
        failed_mask = np.asarray(out.ever_failed) | skipped
        unscheduled = [
            UnscheduledPod(
                pods[i],
                reason="pod-unscheduled annotation" if skipped[i] else "unschedulable",
            )
            for i in np.flatnonzero(failed_mask)
        ]
        from tpusim.sim.engine import EV_CREATE

        rank = np.full(len(pods), -1, np.int64)
        creates = np.asarray(ev_pod)[np.asarray(ev_kind) == EV_CREATE]
        rank[creates] = np.arange(len(creates))
        return out, len(ev_kind), unscheduled, rank

    def schedule_pods(self, pods: Sequence[PodRow]) -> SimulateResult:
        if self.typical is None:
            self.set_typical_pods()
        t0 = time.perf_counter()
        result, events, unscheduled, rank = self._replay_pods(
            self.init_state,
            pods,
            jax.random.PRNGKey(self.cfg.seed),
            self.cfg.use_timestamps,
        )
        return self._record_result(
            result, pods, events, unscheduled, rank,
            time.perf_counter() - t0,
        )

    def schedule_pods_fork(self, pods: Sequence[PodRow], fork_event: int,
                           tail_kind, tail_pod) -> SimulateResult:
        """Warm-state what-if replay (ISSUE 16): run the event stream
        `base[:fork_event] + tail` over the SAME prepared pods, resuming
        from the base run's nearest checkpoint at-or-before fork_event
        instead of event 0 — bit-identical to schedule_pods over the
        spliced stream, but the device only executes the divergent tail
        (plus at most one chunk of shared prefix to reach the fork
        point). The base run must have executed on this Simulator's
        config with checkpoint_every > 0 and checkpoint_keep != 0 so its
        mid-trace carry ladder survives; a missing/torn source degrades
        loudly to a full replay (`self.last_fork["degrade"]`). The tail
        reuses the base's pod specs/weights/seed by construction — the
        checkpointed carry embeds the weight vector via its blocked
        summaries, which is exactly why a weight-changing fork can never
        match a base checkpoint (different run digest) and must be
        rejected upstream, not silently degraded here."""
        if self.typical is None:
            self.set_typical_pods()
        t0 = time.perf_counter()
        base_kind, base_pod = build_events(pods, self.cfg.use_timestamps)
        fev = int(fork_event)
        if not 0 <= fev <= len(base_kind):
            raise ValueError(
                f"fork_event {fev} outside the base stream "
                f"(0..{len(base_kind)})"
            )
        tail_kind = np.asarray(tail_kind, base_kind.dtype)
        tail_pod = np.asarray(tail_pod, base_pod.dtype)
        ev_kind = np.concatenate([base_kind[:fev], tail_kind])
        ev_pod = np.concatenate([base_pod[:fev], tail_pod])
        specs = pods_to_specs(pods, self.node_index)
        out = self.run_events(
            self.init_state, specs, jnp.asarray(ev_kind),
            jnp.asarray(ev_pod), jax.random.PRNGKey(self.cfg.seed),
            pod_rows=pods, fork=(base_kind, base_pod, fev),
        )
        with self.obs.span("fetch", events=len(ev_kind)):
            out = device_fetch(out)
        stats = dict(getattr(self, "_fork_stats", None) or {})
        if stats:
            # REAL events this process fed (pad skips excluded): the
            # tail-only latency-win counter the svc result doc reports
            stats["events_executed"] = max(
                0, len(ev_kind) - int(stats.get("source_cursor", 0))
            )
            stats["events_total"] = int(len(ev_kind))
        self.last_fork = stats
        result, events, unscheduled, rank = self._finish_replay(
            out, pods, ev_kind, ev_pod, self.init_state
        )
        return self._record_result(
            result, pods, events, unscheduled, rank,
            time.perf_counter() - t0,
        )

    def _telemetry_meta(self) -> dict:
        """Deterministic run description for the telemetry record (must be
        identical across same-seed runs — no walls, no paths)."""
        cfg = self.cfg
        return {
            "policies": [[n, w] for n, w in cfg.policies],
            "gpu_sel": cfg.gpu_sel_method,
            "norm": cfg.norm_method,
            "dim_ext": cfg.dim_ext_method,
            "seed": cfg.seed,
            "engine_cfg": cfg.engine,
            "block_size": cfg.block_size,
            "mesh": cfg.mesh,
            "nodes": len(self.nodes),
        }

    def run_telemetry(self):
        """Current RunTelemetry snapshot (spans, counters, degrade/fault
        counts) — also attached to every SimulateResult."""
        return self.obs.snapshot(meta=self._telemetry_meta())

    def event_counter_series(self) -> dict:
        """Per-event counter-track series for the Chrome-trace emitter
        (obs.emitters counter tracks): the cluster frag gpu-milli (total
        AND decomposed by the 7 FGD failure categories — the
        `frag_amounts` columns the postpass already computed), used
        gpu-milli, and used cpu-milli, one value per reported event,
        concatenated across this run's reporting replays. Category
        columns share the in-scan series plane's vocabulary
        (obs.series.FRAG_CATEGORY_NAMES). Empty when per-event reporting
        is off — the trace then simply carries no counter tracks."""
        from tpusim.obs.series import FRAG_CATEGORY_NAMES

        frag: list = []
        used: list = []
        used_cpu: list = []
        cats: list = [[] for _ in FRAG_CATEGORY_NAMES]
        for rep in self.event_reports:
            s = rep.get("series", {})
            if "_frag_milli_f" in s:  # numeric twin of origin_milli
                frag.extend(
                    np.asarray(s["_frag_milli_f"], np.float64).tolist()
                )
            amounts = rep.get("frag_amounts")
            if amounts is not None:
                a = np.asarray(amounts, np.float64)
                for j in range(min(a.shape[1], len(cats))):
                    cats[j].extend(a[:, j].tolist())
            used.extend(
                np.asarray(rep["used_gpu_milli"]).astype(np.int64).tolist()
            )
            used_cpu.extend(
                np.asarray(rep["used_cpu_milli"]).astype(np.int64).tolist()
            )
        out = {}
        if frag:
            out["frag_gpu_milli"] = frag
        if used:
            out["used_gpu_milli"] = used
        if used_cpu:
            out["used_cpu_milli"] = used_cpu
        for name, vals in zip(FRAG_CATEGORY_NAMES, cats):
            if vals:
                out[f"frag_{name}_milli"] = vals
        return out

    def _record_result(self, result, pods, events, unscheduled, rank, wall):
        # exact in-scan counters + creation-failure mask of the newest
        # run: the svc serving path summarizes results in the SweepLane
        # vocabulary (counters included) without re-deriving them
        self.last_counters = (
            np.asarray(result.counters)
            if getattr(result, "counters", None) is not None else None
        )
        self.last_ever_failed = np.asarray(result.ever_failed)
        self.last_result = SimulateResult(
            unscheduled_pods=unscheduled,
            placed_node=np.asarray(result.placed_node),
            dev_mask=np.asarray(result.dev_mask),
            state=jax.tree.map(np.asarray, result.state),
            pods=list(pods),
            node_names=self.node_names,
            wall_seconds=wall,
            events=events,
            creation_rank=rank,
            telemetry=self.run_telemetry(),
            decisions=getattr(result, "decisions", None),
            series=getattr(result, "series", None),
        )
        return self.last_result

    def schedule_additional(self, pods: Sequence[PodRow]) -> List[UnscheduledPod]:
        """Continue scheduling `pods` on the CURRENT cluster state, appending
        them to the run's bookkeeping. This is the engine behind ScheduleApp
        (core.go:255-261) and the new-workload swap (core.go:195-209) — both
        schedule extra pods on top of the already-placed cluster."""
        if self.typical is None:
            self.set_typical_pods()
        res = self.last_result
        out, events, failed, rank = self._replay_pods(
            jax.tree.map(jnp.asarray, res.state),
            pods,
            jax.random.PRNGKey(self.cfg.seed + len(res.pods)),
            use_timestamps=False,
        )
        res.state = jax.tree.map(np.asarray, out.state)
        res.pods = list(res.pods) + list(pods)
        res.placed_node = np.concatenate(
            [res.placed_node, np.asarray(out.placed_node)]
        )
        res.dev_mask = np.concatenate([res.dev_mask, np.asarray(out.dev_mask)])
        res.unscheduled_pods = list(res.unscheduled_pods) + failed
        prior_events = res.events
        res.events += events
        if out.series is not None:
            from tpusim.obs.series import concat_series

            # the appended replay's sample clock starts at 0; rebase onto
            # the run's global event clock before appending
            res.series = concat_series([
                res.series,
                out.series._replace(
                    pos=np.asarray(out.series.pos) + prior_events
                ),
            ])
        if out.decisions is not None:
            from tpusim.obs.decisions import concat_logs

            # the appended replay's events index ITS pod list; shift to
            # the run's concatenated indexing before appending the log
            shifted = out.decisions._replace(
                ev_pod=np.asarray(out.decisions.ev_pod)
                + (len(res.pods) - len(pods))
            )
            res.decisions = concat_logs([res.decisions, shifted])
        base = int(res.creation_rank.max(initial=-1)) + 1
        res.creation_rank = np.concatenate(
            [res.creation_rank, np.where(rank >= 0, rank + base, -1)]
        )
        return failed

    def schedule_app(
        self, name: str, pods: Sequence[PodRow], use_greed: bool = False
    ) -> List[UnscheduledPod]:
        """ScheduleApp (simulator.go:224-237): sort the app's pods through
        the affinity → toleration queues (greed first when --use-greed),
        then schedule them on the current state."""
        from tpusim.sim.queues import app_queue

        ordered = app_queue(pods, self.nodes, use_greed)
        self.log.info(f"Scheduling app {name}: {len(ordered)} pods")
        return self.schedule_additional(ordered)

    def _reset_run_state(self):
        """A reused Simulator must not double-count a previous run's series:
        the direct-CSV stashes accumulate per schedule/report call, and the
        log-reparse lane reads whatever log the caller kept — reset both
        lanes' inputs so they stay byte-identical for any call pattern
        (ADVICE r4). An attached log stream is NOT rewound — the apply path
        wires sys.stdout there, possibly shell-redirected into a file we
        must not clobber; callers re-dumping sim.log after the last run
        (the run.py flow) always get the consistent single-run log."""
        self.event_reports = []
        self.analysis_summary = {}
        self.failed_pod_lists = []
        self.log.lines = []
        self.obs.reset()

    def run(self) -> SimulateResult:
        """Full experiment (core.go:86-268 minus deschedule/inflation, which
        the CLI layers on)."""
        self._reset_run_state()
        self.set_typical_pods()
        self.set_skyline_pods()
        pods = self.prepare_pods()
        self.log.info(f"Number of original workload pods: {len(self.workload_pods)}")
        res = self.schedule_pods(pods)
        # failed-pods detail block (core.go:156 ReportFailedPods)
        self.report_failed([u.pod for u in res.unscheduled_pods])
        self.cluster_analysis("InitSchedule")
        return res

    def run_sweep(self, weights, seeds=None, bucket: int = 512, tunes=None,
                  faults=None):
        """run()'s workload prep + ONE vmapped config-axis sweep replay
        (ISSUE 6): evaluate B (weight-vector, seed) what-if configs of
        this Simulator's policy family in a single compiled scan. See
        schedule_pods_sweep for the contract; returns [SweepLane].

        `tunes` (ISSUE 7, the trace-operand lift): an optional length-B
        list of per-lane tuning ratios. When given, each lane's workload
        is prepared exactly like a standalone run with that
        tuning_ratio (same tuning_seed → same shuffle + clone draws) and
        the tuned traces ride the sweep as DATA (specs/events/type_id
        operands, padded to common buckets), so jobs differing only in
        tune factor pack onto the same compiled scan instead of forcing
        a new jaxpr.

        `faults` (ISSUE 10, the chaos sweep; with `tunes`, ISSUE 12): B
        fault schedules as per-lane operands, each compiled against its
        lane's own trace."""
        self._reset_run_state()
        self.set_typical_pods()
        self.log.info(
            f"Number of original workload pods: {len(self.workload_pods)}"
        )
        if tunes is None:
            return schedule_pods_sweep(
                self, self.prepare_pods(), weights, seeds, bucket,
                fault_specs=faults,
            )
        return schedule_pods_sweep(
            self, None, weights, seeds, bucket, fault_specs=faults,
            lane_pods=[self.prepare_pods(tuning_ratio=t) for t in tunes],
        )

    def run_with_faults(self, fault_cfg=None, faults=None) -> SimulateResult:
        """run() under fault injection: same experiment orchestration, the
        main schedule replaced by schedule_pods_with_faults (the CLI's
        --fault-* flags land here)."""
        self._reset_run_state()
        self.set_typical_pods()
        self.set_skyline_pods()
        pods = self.prepare_pods()
        self.log.info(
            f"Number of original workload pods: {len(self.workload_pods)}"
        )
        res = self.schedule_pods_with_faults(
            pods, faults=faults, fault_cfg=fault_cfg
        )
        self.report_failed([u.pod for u in res.unscheduled_pods])
        self.cluster_analysis("InitSchedule")
        return res

    def report_failed(self, pods) -> None:
        """Failed-pods detail block + the direct-CSV path's stash (every
        block the log carries contributes to the fail-spec grouping, like
        the parser's in_fail_block accumulation)."""
        report_failed_pods(self.log, pods)
        self.failed_pod_lists.append(list(pods))

    def finish(self):
        """Emit the unscheduled-count line (apply.go:228). It is the
        analysis parser's stop marker, so it must come after the LAST
        Cluster Analysis block of the experiment — call once, at the end."""
        self.log.info(
            f"there are {len(self.last_result.unscheduled_pods)} unscheduled pods"
        )

    # ---- snapshot export (export.go) ----

    def export_pod_snapshot_yaml(self, path: str):
        from tpusim.io.export import export_pod_snapshot_yaml

        r = self.last_result
        export_pod_snapshot_yaml(
            r.pods, r.placed_node, r.dev_mask, self.node_names, path,
            creation_rank=r.creation_rank,
        )

    def export_pod_snapshot_csv(self, path: str):
        from tpusim.io.export import export_pod_snapshot_csv

        r = self.last_result
        export_pod_snapshot_csv(r.pods, r.placed_node, r.dev_mask, self.nodes, path)

    def export_node_snapshot_csv(self, path: str):
        from tpusim.io.export import export_node_snapshot_csv

        r = self.last_result
        num_pods = np.zeros(len(self.nodes), np.int64)
        placed = r.placed_node[r.placed_node >= 0]
        np.add.at(num_pods, placed, 1)
        export_node_snapshot_csv(r.state, self.nodes, num_pods, path)

    # ---- workload inflation (simulator.go:1015-1132) ----

    def run_workload_inflation_evaluation(self, tag: str):
        """Clone extra pods onto the current cluster state, schedule them,
        run ClusterAnalysis under `tag`, then drop them (the committed state
        is untouched — we simply never persist the inflated one)."""
        from tpusim.sim.workload import inflation_pods, total_pod_cpu_milli, total_pod_gpu_milli

        rng = np.random.default_rng(self.cfg.inflation_seed)
        extra = inflation_pods(
            self.workload_pods,
            self.cfg.inflation_ratio,
            rng,
            self.node_total_milli_cpu,
            self.node_total_milli_gpu,
            total_pod_cpu_milli(self.workload_pods),
            total_pod_gpu_milli(self.workload_pods),
        )
        if not extra:
            return None
        self.log.info(f"(Inflation) Num of Total Pods: {len(extra)}")
        state = jax.tree.map(jnp.asarray, self.last_result.state)
        # same reporting replay as the main workload (the reference's
        # inflation path reuses SchedulePods + ReportFailedPods,
        # simulator.go:1023-1024)
        out, _, unscheduled, _ = self._replay_pods(
            state, extra, jax.random.PRNGKey(self.cfg.inflation_seed),
            use_timestamps=False,
        )
        self.report_failed([u.pod for u in unscheduled])
        failed = len(unscheduled)
        self.log.info(f"[ReportFailedPods] {failed} unscheduled inflation pods")
        saved = self.last_result.state
        self.last_result.state = jax.tree.map(np.asarray, out.state)
        analysis = self.cluster_analysis(tag)
        self.last_result.state = saved  # inflation pods all deleted
        return analysis

    # ---- descheduling (deschedule.go) ----

    def deschedule_cluster(self) -> List[UnscheduledPod]:
        """Evict pods per the configured policy, report PostEviction, then
        reschedule the victims (ref: DescheduleCluster, deschedule.go:20-47,
        + the core.go:213-218 orchestration: the caller follows up with
        ClusterAnalysis(PostDeschedule))."""
        from tpusim.sim.deschedule import evict, select_victims

        res = self.last_result
        specs = pods_to_specs(res.pods)
        state = jax.tree.map(jnp.asarray, res.state)
        victims = select_victims(
            state,
            specs,
            res.placed_node,
            res.dev_mask,
            self.typical,
            self.cfg.deschedule_policy,
            self.cfg.deschedule_ratio,
            self.node_names,
        )
        self.log.info(
            f"maximum number of pods that can be descheduled: "
            f"{math.ceil(self.cfg.deschedule_ratio * int((res.placed_node >= 0).sum()))}, "
            f"deschedule policy: {self.cfg.deschedule_policy}"
        )
        state = evict(state, specs, res.placed_node, res.dev_mask, victims)
        res.state = jax.tree.map(np.asarray, state)
        res.placed_node = res.placed_node.copy()
        res.dev_mask = res.dev_mask.copy()
        res.placed_node[victims] = -1
        res.dev_mask[victims] = False
        self.cluster_analysis("PostEviction")
        self.log.info(f"[DescheduleCluster] Num of Descheduled Pods: {len(victims)}")

        # reschedule the victims, in eviction order (deschedule.go:89-91)
        if not victims:
            return []
        v = np.asarray(victims, np.int32)
        vspecs = jax.tree.map(lambda a: a[jnp.asarray(v)], specs)
        ev_kind = np.zeros(len(victims), np.int32)  # EV_CREATE stream
        ev_pod = np.arange(len(victims), dtype=np.int32)

        out = device_fetch(
            self.run_events(
                state, vspecs, jnp.asarray(ev_kind), jnp.asarray(ev_pod),
                jax.random.PRNGKey(self.cfg.seed + 1),
                pod_rows=[res.pods[int(i)] for i in v],
            )
        )
        # the victim reschedule goes through the reporting loop in the
        # reference too (deschedule.go:91 → SchedulePods)
        self._emit_event_reports(
            out, [res.pods[int(i)] for i in v], ev_kind, ev_pod, state
        )
        if out.decisions is not None:
            from tpusim.obs.decisions import DecisionLog, concat_logs

            # the victim replay's events index vspecs; remap to the run's
            # global pod indices so the appended log names the right pods
            res.decisions = concat_logs([
                res.decisions,
                DecisionLog(
                    jax.tree.map(np.asarray, out.decisions),
                    np.asarray(ev_kind), v[np.asarray(ev_pod)],
                ),
            ])
        if out.series is not None:
            from tpusim.obs.series import concat_series, log_from_stacked

            # victim reschedules append their samples past the run's
            # event clock (deschedule events are host-level, not trace
            # events, so res.events itself is unchanged)
            res.series = concat_series([
                res.series,
                log_from_stacked(out.series, base_pos=res.events),
            ])
        placed_v = np.asarray(out.placed_node)
        mask_v = np.asarray(out.dev_mask)
        res.placed_node[v] = placed_v
        res.dev_mask[v] = mask_v
        res.state = jax.tree.map(np.asarray, out.state)
        if res.creation_rank is not None:  # victims re-enter last, in order
            base = int(res.creation_rank.max(initial=-1)) + 1
            res.creation_rank = res.creation_rank.copy()
            res.creation_rank[v] = base + np.arange(len(v))
        failed = [
            UnscheduledPod(res.pods[v[i]]) for i in np.flatnonzero(placed_v < 0)
        ]
        res.unscheduled_pods = list(res.unscheduled_pods) + failed
        self.log.info(f"[DescheduleCluster] Num of Failed Pods: {len(failed)}")
        return failed

    # ---- fault injection (tpusim.sim.faults / fault_lane) ----

    def _fault_scan_blockers(self) -> list:
        """Reasons this config cannot run the in-scan fault lane (each
        one is a capability only the segmented host loop provides)."""
        cfg = self.cfg
        out = []
        if cfg.report_per_event:
            out.append("per-event reporting (the report postpass does not "
                       "model fault transitions)")
        if cfg.extenders:
            out.append("extenders")
        if cfg.record_decisions:
            out.append("decision recording")
        if cfg.series_every:
            out.append("the in-scan series plane")
        if cfg.checkpoint_every:
            out.append("checkpointing (composes with the segmented path)")
        if cfg.engine == "pallas":
            out.append("the fused pallas engine")
        if cfg.heartbeat_every:
            out.append("the in-scan heartbeat")
        return out

    def _fault_randomized(self) -> bool:
        """Per-event-random configs (RandomScore / gpu_sel random): the
        scan lane replays them seeded-and-reproducibly, but its one-key-
        chain-per-merged-stream discipline necessarily differs from the
        segmented path's per-segment fold-in — so fault_mode='auto'
        keeps them on the segmented path (same-seed results stay what
        PR 2 produced) and only an explicit fault_mode='scan' opts into
        the lane's chain."""
        return (
            any(fn.policy_name == "RandomScore"
                for fn, _ in self._policy_fns)
            or self.cfg.gpu_sel_method == "random"
        )

    def schedule_pods_with_faults(
        self, pods: Sequence[PodRow], faults=None, fault_cfg=None
    ) -> SimulateResult:
        """schedule_pods under a fault schedule. Since ISSUE 10 the
        default execution is the IN-SCAN fault lane
        (tpusim.sim.fault_lane): the schedule merges into the event
        stream as fixed-shape operands and the retry queue rides the
        scan carry, so the whole disruption trajectory is ONE compiled
        scan — and, crucially, a vmappable one (Simulator.run_sweep's
        `faults=` axis). Configs the lane cannot serve (see
        _fault_scan_blockers) fall back to the PR 2 segmented host loop,
        which remains bit-identical for deterministic configs;
        SimulatorConfig.fault_mode forces either path."""
        mode = getattr(self.cfg, "fault_mode", "auto")
        if mode not in ("auto", "scan", "segments"):
            raise ValueError(
                f"unknown fault_mode {mode!r}: expected auto | scan | "
                "segments"
            )
        blockers = self._fault_scan_blockers()
        if mode == "scan" and blockers:
            raise ValueError(
                f"fault_mode='scan' cannot serve this config: {blockers[0]}"
            )
        if mode == "auto" and not blockers and self._fault_randomized():
            # soft preference, not a capability gap: the lane CAN replay
            # randomized configs (fault_mode='scan' opts in), but auto
            # must not silently change PR 2's same-seed results
            blockers = [
                "per-event randomness draws a different (still seeded) "
                "PRNG chain on the scan lane; fault_mode='scan' opts in"
            ]
        if mode == "segments" or blockers:
            if blockers and mode == "auto":
                self.log.info(
                    f"[Fault] segmented replay ({blockers[0]})"
                )
            return self._schedule_pods_with_faults_segmented(
                pods, faults, fault_cfg
            )
        return self._schedule_pods_faults_scan(pods, faults, fault_cfg)

    def _schedule_pods_with_faults_segmented(
        self, pods: Sequence[PodRow], faults=None, fault_cfg=None
    ) -> SimulateResult:
        """The PR 2 host loop: NodeFail / NodeRecover /
        Evict events fire between compiled replay segments, evicted pods
        re-enter through a capped-exponential-backoff retry queue
        (tpusim.sim.queues.RetryQueue), and pods out of retries become
        terminal UnscheduledPods (reason "max-retries-exceeded").

        `faults`: an explicit FaultEvent list (the trace-column mode), or
        None to generate an MTBF-style schedule from `fault_cfg`
        (tpusim.sim.faults.generate_fault_schedule — seeded, so the whole
        disruption outcome is bit-reproducible; tests/test_faults.py pins
        that). Segments run through run_events unchanged, so fault replays
        inherit engine selection AND checkpoint/resume.

        Creation-ordered traces only (use_timestamps=False, the experiment
        pipeline's mode): a trace-deletion of a pod created in an earlier
        segment would need cross-segment placement memory the engine call
        surface does not carry — deletions under faults are modeled as
        Evict events instead. Disruption totals land in
        `self.last_disruption` and the `[Disruption]` log block."""
        from tpusim.sim.engine import (
            EV_CREATE,
            EV_EVICT,
            EV_NODE_FAIL,
            EV_NODE_RECOVER,
        )
        from tpusim.sim.deschedule import evict as evict_pods
        from tpusim.sim.faults import (
            FaultConfig,
            fail_node,
            generate_fault_schedule,
            pick_eviction_victim,
            recover_node,
            validate_fault_schedule,
        )
        from tpusim.sim.metrics import DisruptionMetrics
        from tpusim.sim.queues import RetryQueue
        from tpusim.sim.reports import disruption_report_block
        from tpusim.sim.table_engine import build_pod_types

        if self.cfg.use_timestamps:
            raise ValueError(
                "schedule_pods_with_faults replays creation-ordered traces "
                "(use_timestamps=False); model deletions as Evict fault "
                "events instead"
            )
        if self.typical is None:
            self.set_typical_pods()
        fcfg = fault_cfg or FaultConfig()
        pods = list(pods)
        ev_kind, ev_pod = build_events(pods, False)
        num_events = len(ev_kind)
        if faults is None:
            faults = generate_fault_schedule(
                len(self.nodes), num_events, fcfg
            )
        faults = sorted(faults, key=lambda f: f.pos)  # stable: ties keep order
        validate_fault_schedule(faults, len(self.nodes), len(pods))
        t0 = time.perf_counter()

        num_pods = len(pods)
        specs = pods_to_specs(pods, self.node_index)
        types = build_pod_types(specs)
        state = jax.tree.map(jnp.asarray, self.init_state)
        gpu_cnt = np.asarray(self.init_state.gpu_cnt)
        ndev = int(self.init_state.gpu_left.shape[1])
        placed = np.full(num_pods, -1, np.int32)
        masks = np.zeros((num_pods, ndev), bool)
        ever_failed = np.zeros(num_pods, bool)
        creation_rank = np.full(num_pods, -1, np.int64)
        base_key = jax.random.PRNGKey(self.cfg.seed)
        rq = RetryQueue(
            fcfg.backoff_base, fcfg.backoff_cap, fcfg.max_retries
        )
        dm = DisruptionMetrics()
        dec_logs: list = []  # per-segment DecisionLogs (ISSUE 4)
        ser_logs: list = []  # per-segment SeriesLogs (ISSUE 5)
        attempts: dict = {}  # pod -> consecutive failed retries so far
        evicted_at: dict = {}  # pod -> eviction position (latency clock)
        down_at: dict = {}  # node -> failure position
        state_box = {"state": state, "rank": 0, "events": 0, "segs": 0}

        def frag_total(st):
            from tpusim.ops.frag import cluster_frag_report, frag_sum_except_q3

            return float(frag_sum_except_q3(
                cluster_frag_report(st, self.typical)[0]
            ))

        def run_segment(seg_kind, seg_pod):
            """One compiled segment via the normal run_events dispatch;
            merges its placements into the host bookkeeping."""
            seg_kind = np.asarray(seg_kind)
            seg_pod = np.asarray(seg_pod)
            seg_key = jax.random.fold_in(base_key, state_box["segs"])
            state_box["segs"] += 1
            pre_state = state_box["state"]
            # run-level heartbeat window: this segment's ticks report
            # `events-so-far + segment progress` out of the run total
            self._hb_base = state_box["events"]
            out = device_fetch(self.run_events(
                pre_state, specs, jnp.asarray(seg_kind),
                jnp.asarray(seg_pod), seg_key, types=types, pod_rows=pods,
            ))
            self._emit_event_reports(out, pods, seg_kind, seg_pod, pre_state)
            if out.series is not None:
                from tpusim.obs.series import log_from_stacked

                # every segment is a fresh scan, so it OPENS with a sample
                # of the post-fault cluster at stride position 0; rebase
                # onto the run's global event clock and stamp the current
                # retry-queue depth (host state the scan cannot see)
                ser_logs.append(log_from_stacked(
                    out.series, base_pos=state_box["events"],
                    retry_depth=len(rq),
                ))
            if out.decisions is not None:
                # the fault replay's provenance is the concatenation of
                # its segments' streams, in replay order — continuous
                # across the segmentation like the counters
                from tpusim.obs.decisions import DecisionLog

                dec_logs.append(DecisionLog(
                    jax.tree.map(np.asarray, out.decisions),
                    seg_kind, seg_pod,
                ))
            state_box["state"] = jax.tree.map(jnp.asarray, out.state)
            created = seg_pod[seg_kind == EV_CREATE]
            placed[created] = np.asarray(out.placed_node)[created]
            masks[created] = np.asarray(out.dev_mask)[created]
            ever_failed[created] |= np.asarray(out.ever_failed)[created]
            creation_rank[created] = (
                state_box["rank"] + np.arange(created.size)
            )
            state_box["rank"] += int(created.size)
            state_box["events"] += int(seg_kind.size)

        def evict_bookkeep(pod_i: int, pos: int):
            placed[pod_i] = -1
            masks[pod_i] = False
            evicted_at[pod_i] = pos
            dm.evicted_pods += 1
            att = attempts.get(pod_i, 0) + 1
            attempts[pod_i] = att
            # rq.dead is THE terminal list; totals are read off it after
            # the loop instead of being double-counted here
            if rq.push(pod_i, pos, att) is None:
                ever_failed[pod_i] = True
            else:
                dm.retries_enqueued += 1

        def apply_fault(f, pos: int):
            if f.kind == EV_NODE_FAIL:
                if f.node in down_at:
                    return  # already down
                victims = np.flatnonzero(placed == f.node)
                state_box["state"] = fail_node(state_box["state"], f.node)
                down_at[f.node] = pos
                dm.node_failures += 1
                self.log.info(
                    f"[Fault] node {self.node_names[f.node]} failed at "
                    f"event {pos}: {victims.size} pods evicted"
                )
                for v in victims.tolist():
                    evict_bookkeep(int(v), pos)
            elif f.kind == EV_NODE_RECOVER:
                if f.node not in down_at:
                    return  # never failed / already recovered
                before = frag_total(state_box["state"])
                state_box["state"] = recover_node(state_box["state"], f.node)
                after = frag_total(state_box["state"])
                dm.post_recovery_frag_delta.append(after - before)
                dm.node_recoveries += 1
                dm.failed_node_gpu_events += int(gpu_cnt[f.node]) * (
                    pos - down_at.pop(f.node)
                )
                self.log.info(
                    f"[Fault] node {self.node_names[f.node]} recovered at "
                    f"event {pos} (frag delta {after - before:+.1f})"
                )
            else:  # EV_EVICT
                v = pick_eviction_victim(placed, pos, fcfg.seed, f.pod)
                if v is None:
                    return  # nothing placed to evict
                state_box["state"] = evict_pods(
                    state_box["state"], specs, jnp.asarray(placed),
                    jnp.asarray(masks), [v],
                )
                self.log.info(
                    f"[Fault] pod {pods[v].name} evicted from node "
                    f"{self.node_names[int(placed[v])]} at event {pos}"
                )
                evict_bookkeep(int(v), pos)

        fi = 0
        cursor = 0
        while True:
            candidates = [num_events] if cursor < num_events else []
            if fi < len(faults):
                candidates.append(min(faults[fi].pos, num_events))
            nr = rq.next_ready()
            if nr is not None:
                candidates.append(min(nr, num_events))
            if not candidates:
                break
            stop = min(candidates)
            if stop > cursor:
                run_segment(ev_kind[cursor:stop], ev_pod[cursor:stop])
                cursor = stop
            pos = stop
            # faults fire first so a retry due at the same position sees
            # the post-fault cluster (never re-lands on the dying node)
            while fi < len(faults) and min(faults[fi].pos, num_events) <= pos:
                apply_fault(faults[fi], pos)
                fi += 1
            # once the trace and fault stream are drained, flush the queue
            # regardless of backoff — there is nothing left to wait for
            thresh = (
                pos if (cursor < num_events or fi < len(faults))
                else float("inf")
            )
            due = rq.pop_due(thresh)
            if due:
                retry_idx = np.array([p for p, _ in due], np.int32)
                run_segment(
                    np.zeros(retry_idx.size, np.int32), retry_idx
                )
                for pod_i, _att in due:
                    if placed[pod_i] >= 0:
                        dm.rescheduled_pods += 1
                        dm.reschedule_latency_events.append(
                            pos - evicted_at.pop(pod_i)
                        )
                        # the budget is max_retries CONSECUTIVE failures
                        # (FaultConfig doc): a successful reschedule resets
                        # it, so a long-lived pod evicted many separate
                        # times is not eventually killed by accumulation
                        attempts.pop(pod_i, None)
                    else:
                        att = attempts[pod_i] + 1
                        attempts[pod_i] = att
                        if rq.push(pod_i, pos, att) is not None:
                            dm.retries_enqueued += 1

        # capacity still dark at trace end counts to the end-of-trace clock
        for node_i, t_fail in down_at.items():
            dm.failed_node_gpu_events += int(gpu_cnt[node_i]) * max(
                num_events - t_fail, 0
            )
        # the retry queue's dead list is the single source of truth for
        # out-of-retries pods
        dead_pods = {p for p, _ in rq.dead}
        dm.unscheduled_after_retries = len(rq.dead)

        self.analysis_summary.update(disruption_report_block(self.log, dm))
        self.last_disruption = dm
        # the [Disruption] block's machine-readable twin: fault totals in
        # the JSONL record instead of stdout-only prose
        self.obs.note_disruption(dm)

        skipped = np.array([p.unscheduled for p in pods], bool)
        unscheduled = []
        for i in range(num_pods):
            if skipped[i]:
                unscheduled.append(UnscheduledPod(
                    pods[i], reason="pod-unscheduled annotation"
                ))
            elif i in dead_pods:
                unscheduled.append(UnscheduledPod(
                    pods[i], reason="max-retries-exceeded"
                ))
            elif placed[i] < 0 and bool(ever_failed[i]):
                unscheduled.append(UnscheduledPod(pods[i]))
        from tpusim.obs.decisions import concat_logs
        from tpusim.obs.series import concat_series

        self._hb_base = 0  # later replays report from a fresh clock
        self.last_result = SimulateResult(
            unscheduled_pods=unscheduled,
            placed_node=placed,
            dev_mask=masks,
            state=jax.tree.map(np.asarray, state_box["state"]),
            pods=pods,
            node_names=self.node_names,
            wall_seconds=time.perf_counter() - t0,
            events=state_box["events"],
            creation_rank=creation_rank,
            telemetry=self.run_telemetry(),
            decisions=concat_logs(dec_logs),
            series=concat_series(ser_logs),
        )
        return self.last_result

    # ---- the in-scan fault lane (ISSUE 10; tpusim.sim.fault_lane) ----

    def _schedule_pods_faults_scan(
        self, pods: Sequence[PodRow], faults=None, fault_cfg=None
    ) -> SimulateResult:
        """schedule_pods_with_faults on the in-scan lane: ONE compiled
        scan over the merged (base + fault + retry-slot) stream, the
        retry queue in the carry, DisruptionMetrics assembled from exact
        in-scan counters + per-event fault telemetry. Bit-identical to
        the segmented path for deterministic configs (the acceptance
        pin, tests/test_fault_lane.py)."""
        from tpusim.sim import fault_lane
        from tpusim.sim.faults import FaultConfig, generate_fault_schedule
        from tpusim.sim.reports import disruption_report_block

        if self.cfg.use_timestamps:
            raise ValueError(
                "schedule_pods_with_faults replays creation-ordered traces "
                "(use_timestamps=False); model deletions as Evict fault "
                "events instead"
            )
        if self.typical is None:
            self.set_typical_pods()
        fcfg = fault_cfg or FaultConfig()
        pods = list(pods)
        ev_kind, ev_pod = build_events(pods, False)
        if faults is None:
            faults = generate_fault_schedule(
                len(self.nodes), len(ev_kind), fcfg
            )
        t0 = time.perf_counter()
        specs = pods_to_specs(pods, self.node_index)
        plan = fault_lane.compile_fault_plan(
            ev_kind, ev_pod, faults, fcfg, len(self.nodes), len(pods)
        )
        out = self._dispatch_fault_scan(specs, plan)
        with self.obs.span("fetch", events=int(plan.kind.shape[0])):
            out = device_fetch(out)
        dm, dead, attempts_run = fault_lane.assemble_disruption(
            plan, out.fault_ys, out.fault_carry,
            np.asarray(self.init_state.gpu_cnt),
            # the shard engine never captures recover frag deltas — drop
            # the series (with the [Degrade] warning above) instead of
            # reporting placeholder zeros as measurements
            frag_delta=self._shard_fn is None,
        )
        e_m = int(plan.kind.shape[0])
        # fault events + inert retry slots counted as skips in-scan; the
        # true event count is base events + actual retry attempts
        self.obs.note_scan(
            self._last_engine, counters=out.counters,
            pad_skips=e_m - plan.num_events - attempts_run,
            events=plan.num_events + attempts_run,
        )
        self.log.info(
            f"[Engine] fault-lane replay of {plan.num_events} events "
            f"(+{attempts_run} retries, merged stream {e_m}) ran on: "
            f"{self._last_engine}"
        )
        self._emit_fault_log_lines(plan, out.fault_ys, pods)
        self.analysis_summary.update(disruption_report_block(self.log, dm))
        self.last_disruption = dm
        self.obs.note_disruption(dm)
        placed = np.asarray(out.placed_node)
        ever_failed = np.asarray(out.ever_failed)
        skipped = np.array([p.unscheduled for p in pods], bool)
        dead = np.asarray(dead)[: len(pods)]
        unscheduled = []
        for i in range(len(pods)):
            if skipped[i]:
                unscheduled.append(UnscheduledPod(
                    pods[i], reason="pod-unscheduled annotation"
                ))
            elif dead[i]:
                unscheduled.append(UnscheduledPod(
                    pods[i], reason="max-retries-exceeded"
                ))
            elif placed[i] < 0 and bool(ever_failed[i]):
                unscheduled.append(UnscheduledPod(pods[i]))
        self.last_result = SimulateResult(
            unscheduled_pods=unscheduled,
            placed_node=placed,
            dev_mask=np.asarray(out.dev_mask),
            state=jax.tree.map(np.asarray, out.state),
            pods=pods,
            node_names=self.node_names,
            wall_seconds=time.perf_counter() - t0,
            events=plan.num_events + attempts_run,
            creation_rank=fault_lane.fault_creation_rank(
                plan, out.fault_ys, len(pods)
            ),
            telemetry=self.run_telemetry(),
        )
        return self.last_result

    def _dispatch_fault_scan(self, specs, plan):
        """Engine dispatch for one fault-lane replay: shard_map under a
        mesh, else the table engine when the workload amortizes its init
        (the run_events heuristic), else the sequential oracle."""
        from tpusim.sim import fault_lane
        from tpusim.sim.engine import make_replay
        from tpusim.sim.table_engine import (
            build_pod_types,
            make_table_replay,
            num_pod_types,
        )

        key = jax.random.PRNGKey(self.cfg.seed)
        e = plan.num_events
        kind_d = jnp.asarray(plan.kind)
        idx_d = jnp.asarray(plan.idx)
        p = int(specs.cpu.shape[0])
        if self._shard_fn is not None:
            from tpusim.parallel import pad_nodes, shard_state
            from tpusim.parallel.shard_engine import (
                make_shardmap_table_replay,
            )

            n0 = self.init_state.num_nodes
            state_p, rank_p = pad_nodes(
                self.init_state, self.rank, self.cfg.mesh
            )
            n_pad = state_p.num_nodes
            state_p = shard_state(state_p, self._mesh)
            ops = fault_lane.FaultOps(
                pos=jnp.asarray(plan.pos), arg=jnp.asarray(plan.arg),
                aux=jnp.asarray(plan.aux), draws=jnp.asarray(plan.draws),
                params=jnp.asarray(plan.params),
                gcnt=jnp.pad(
                    jnp.asarray(self.init_state.gpu_cnt), (0, n_pad - n0)
                ),
            )
            fc0 = fault_lane.init_fault_carry(p, n_pad, plan.capacity)
            if plan.has_recover:
                # the shard engine cannot capture recover frag deltas (a
                # psum of f32 partials is not bit-equal to the
                # single-device cluster sum, ENGINES.md Round 14) — say
                # so loudly instead of reporting silent 0.0 deltas
                # (ISSUE 11 satellite): counter + [Degrade] line, and
                # assemble_disruption below drops the series entirely
                self.obs.count("degrade_mesh_frag")
                self.log.info(
                    "[Degrade] mesh fault replay: recover frag-delta "
                    "capture is unsupported on the shard engine (psum of "
                    "f32 partials != the one-device sum); "
                    "post_recovery_frag_delta will be empty — run "
                    "mesh=0 to capture it"
                )
            fn = make_shardmap_table_replay(
                self._policy_fns, self._mesh,
                gpu_sel=self.cfg.gpu_sel_method,
                block_size=self.cfg.block_size, faults=True,
            )
            self._last_engine = (
                f"shard_map (mesh={self.cfg.mesh}, fault lane)"
            )
            out = self._dispatch_span(
                lambda: fn(
                    state_p, specs, build_pod_types(specs), kind_d, idx_d,
                    self.typical, key, rank_p, fault_ops=ops,
                    fault_carry0=fc0,
                ),
                engine=self._last_engine, events=e,
            )
            return out._replace(
                state=jax.tree.map(lambda a: a[:n0], out.state)
            )

        ops = fault_lane.FaultOps(
            pos=jnp.asarray(plan.pos), arg=jnp.asarray(plan.arg),
            aux=jnp.asarray(plan.aux), draws=jnp.asarray(plan.draws),
            params=jnp.asarray(plan.params),
            gcnt=jnp.asarray(self.init_state.gpu_cnt),
        )
        fc0 = fault_lane.init_fault_carry(
            p, self.init_state.num_nodes, plan.capacity
        )
        types = build_pod_types(specs)
        k = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
        use_table = (
            self.cfg.engine != "sequential"
            and k > 0
            and (self.cfg.engine == "table" or e >= 2 * num_pod_types(specs))
        )
        if use_table:
            fn = make_table_replay(
                self._policy_fns, gpu_sel=self.cfg.gpu_sel_method,
                report=False, block_size=self.cfg.block_size, faults=True,
                fault_frag=plan.has_recover,
                unswitched=self.cfg.unswitched_select,
            )
            self._last_engine = "table (fault lane)"
            out = self._dispatch_span(
                lambda: fn(
                    self.init_state, specs, types, kind_d, idx_d,
                    self.typical, key, self.rank,
                    tables=self._cached_tables(self.init_state, types, key),
                    fault_ops=ops, fault_carry0=fc0,
                ),
                engine=self._last_engine, events=e,
            )
        else:
            fn = make_replay(
                self._policy_fns, gpu_sel=self.cfg.gpu_sel_method,
                report=False, faults=True, fault_frag=plan.has_recover,
            )
            self._last_engine = "sequential (fault lane)"
            out = self._dispatch_span(
                lambda: fn(
                    self.init_state, specs, kind_d, idx_d, self.typical,
                    key, self.rank, fault_ops=ops, fault_carry0=fc0,
                ),
                engine=self._last_engine, events=e,
            )
        return out

    def _emit_fault_log_lines(self, plan, ys, pods):
        """The segmented path's [Fault] narration, reconstructed from the
        plan + per-event fault telemetry (down/up transitions are a pure
        function of the schedule; victims come from the ys)."""
        from tpusim.sim.engine import EV_EVICT, EV_NODE_FAIL, EV_NODE_RECOVER

        nvict = np.asarray(ys.nvict)
        vpod = np.asarray(ys.vpod)
        vnode = np.asarray(ys.vnode)
        fb = np.asarray(ys.fb, np.float64)
        fa = np.asarray(ys.fa, np.float64)
        down: set = set()
        for i, k in enumerate(plan.kind.tolist()):
            pos = int(plan.pos[i])
            a = int(plan.arg[i])
            if k == EV_NODE_FAIL and a not in down:
                down.add(a)
                self.log.info(
                    f"[Fault] node {self.node_names[a]} failed at event "
                    f"{pos}: {int(nvict[i])} pods evicted"
                )
            elif k == EV_NODE_RECOVER and a in down:
                down.discard(a)
                delta = float(fa[i]) - float(fb[i])
                self.log.info(
                    f"[Fault] node {self.node_names[a]} recovered at "
                    f"event {pos} (frag delta {delta:+.1f})"
                )
            elif k == EV_EVICT and int(vpod[i]) >= 0:
                self.log.info(
                    f"[Fault] pod {pods[int(vpod[i])].name} evicted from "
                    f"node {self.node_names[int(vnode[i])]} at event {pos}"
                )

    # ---- reporting (analysis.go) ----

    def _typical_host_rows(self):
        """Typical-pod distribution as host tuples
        [(cpu, gpu_milli, gpu_num, gpu_mask, freq)] — the BellmanEvaluator's
        constructor format."""
        t = getattr(self, "_typical_host", None)
        if t is None:
            t = self._typical_host = device_fetch(self.typical)
        return list(
            zip(
                np.asarray(t.cpu).tolist(),
                np.asarray(t.gpu_milli).tolist(),
                np.asarray(t.gpu_num).tolist(),
                np.asarray(t.gpu_mask).tolist(),
                np.asarray(t.freq).tolist(),
            )
        )

    def _bellman_series(self, start_state, pods, ev_kind, ev_pod, out):
        """Per-event cluster Bellman frag (ref: the `(bellman)` [Report]
        variant, analysis.go:110): reconstruct each event's touched node
        from the replay's (event_node, event_dev) telemetry and update only
        that node's memoized value — mathematically equal to the reference's
        per-event full-cluster sweep because the value function depends on
        node state alone. The whole event stream is evaluated in ONE native
        call (BellmanEvaluator.eval_series) instead of per-event ctypes
        round-trips.

        The series is a deterministic pure function of (typical rows, start
        state, event stream incl. telemetry), so — like XLA's persistent
        compilation cache — a content-keyed disk cache (TPUSIM_BELLMAN_CACHE,
        default <repo>/.bellman_cache, empty disables) lets artifact
        REGENERATION skip the dominant per-experiment host cost. Caching is
        first-call-only per Simulator: later calls (inflation/deschedule
        stages) depend on the warmed memo, whose state embeds evaluation
        order; a first-call cache hit therefore stashes its inputs and
        replays them before any later call evaluates, keeping multi-stage
        values bit-identical to an uncached run."""
        from tpusim.sim.engine import EV_CREATE

        kinds = np.asarray(ev_kind)
        ev_pods = np.asarray(ev_pod)
        pod_cpu = np.fromiter(
            (p.cpu_milli for p in pods), np.int32, count=len(pods)
        )
        pod_gpu = np.fromiter(
            (p.gpu_milli for p in pods), np.int32, count=len(pods)
        )

        start_state = device_fetch(start_state)
        inputs = (
            np.ascontiguousarray(np.asarray(start_state.cpu_left, np.int32)),
            np.ascontiguousarray(np.asarray(start_state.gpu_left, np.int32)),
            np.ascontiguousarray(np.asarray(start_state.gpu_type, np.int32)),
            np.ascontiguousarray(np.asarray(out.event_node, np.int32)),
            np.ascontiguousarray(np.asarray(out.event_dev, np.uint8)),
            np.where(kinds == EV_CREATE, 1, -1).astype(np.int8),
            np.ascontiguousarray(pod_cpu[ev_pods]),
            np.ascontiguousarray(pod_gpu[ev_pods]),
        )

        # "first call" = nothing evaluated OR pending yet: after a cache
        # hit the evaluator is still unbuilt, but later stages must NOT
        # read/write the cache (their values embed the warmed memo's
        # evaluation order — caching them would poison the content keys)
        first_call = (
            self._bellman_eval is None and self._bellman_pending_replay is None
        )
        cache_path = self._bellman_cache_path(inputs) if first_call else None
        if cache_path is not None and os.path.isfile(cache_path):
            self._bellman_pending_replay = inputs
            return np.load(cache_path)

        if self._bellman_eval is None:
            from tpusim.native import BellmanEvaluator

            self._bellman_eval = BellmanEvaluator(self._typical_host_rows())
            pending = getattr(self, "_bellman_pending_replay", None)
            if pending is not None:
                # a later stage after a first-call cache hit: rebuild the
                # memo state the cached call would have produced
                self._bellman_eval.eval_series(*pending)
                self._bellman_pending_replay = None

        series = self._bellman_eval.eval_series(*inputs)
        if cache_path is not None:
            os.makedirs(os.path.dirname(cache_path), exist_ok=True)
            tmp = f"{cache_path}.{os.getpid()}.tmp.npy"
            with open(tmp, "wb") as f:
                np.save(f, series)
            os.replace(tmp, cache_path)
        return series

    def _bellman_cache_path(self, inputs):
        """Content-keyed cache file for a FIRST bellman series of this
        simulator, or None when caching is disabled."""
        import hashlib

        cache_dir = os.environ.get(
            "TPUSIM_BELLMAN_CACHE",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))), ".bellman_cache"),
        )
        if not cache_dir:
            return None
        h = hashlib.sha256()
        # version salt: the evaluator SOURCE participates in the key (like
        # the compiler version in XLA's persistent cache), so changing the
        # native Bellman logic invalidates every cached series
        h.update(_bellman_source_digest())
        for row in self._typical_host_rows():
            h.update(repr(row).encode())
        for a in inputs:
            h.update(a.tobytes())
        return os.path.join(cache_dir, h.hexdigest() + ".npy")

    def _emit_event_reports(self, out, pods, ev_kind, ev_pod, start_state):
        """Per-event log block: `[i] attempt to ...` line (simulator.go:410,
        420; failures echo the deletePod rollback line :354), then the
        frag/alloc/power report lines incl. the bellman variant
        (simulator.go:426-427, analysis.go:109-110). Skip events
        (pod-unscheduled annotation) emit nothing (simulator.go:391-399).
        No-op when per-event reporting is off (the replay carries no
        metrics then). All line families format vectorized over the event
        axis (reports.batch_event_report_msgs) and append in one bulk
        call. The whole block (the Bellman series dominates) runs under
        the obs "report" span."""
        m = out.metrics
        if not self.cfg.report_per_event or m is None:
            return
        with self.obs.span("report", events=int(np.asarray(ev_kind).shape[0])):
            self._emit_event_reports_impl(out, pods, ev_kind, ev_pod,
                                          start_state)

    def _emit_event_reports_impl(self, out, pods, ev_kind, ev_pod,
                                 start_state):
        from tpusim.sim.engine import EV_CREATE, EV_DELETE
        from tpusim.sim.reports import (
            batch_event_report_msgs,
            event_report_series,
        )

        m = out.metrics
        amounts = np.asarray(m.frag_amounts)
        total_gpus = self.total_gpus
        kinds = np.asarray(ev_kind)
        bellman = self._bellman_series(start_state, pods, ev_kind, ev_pod, out)
        names = np.array([p.name for p in pods])
        ev_pods = np.asarray(ev_pod)
        pod_names = names[ev_pods]
        ev_failed = np.asarray(out.ever_failed)[ev_pods]
        series = event_report_series(
            amounts, np.asarray(m.power_cpu), np.asarray(m.power_gpu), bellman
        )
        # stash the structured per-event data for the direct CSV path
        # (experiments/analysis.py analyze_sim — the formatted `series`
        # strings are the SAME objects the log lines embed, so both lanes
        # are byte-identical by construction)
        self.event_reports.append({
            "series": series,
            "frag_amounts": amounts,  # f32[E, 7], FGD category order
            "kinds": kinds,
            "pod_names": pod_names,
            "failed": ev_failed,
            "used_nodes": np.asarray(m.used_nodes),
            "used_gpus": np.asarray(m.used_gpus),
            "used_gpu_milli": np.asarray(m.used_gpu_milli),
            "arrived_gpu_milli": np.asarray(m.arrived_gpu_milli),
            "used_cpu_milli": np.asarray(m.used_cpu_milli),
            "arrived_cpu_milli": np.asarray(m.arrived_cpu_milli),
            "total_gpus": total_gpus,
        })
        self.log.info_many(
            batch_event_report_msgs(
                amounts,
                total_gpus,
                np.asarray(m.used_nodes),
                np.asarray(m.used_gpus),
                np.asarray(m.used_gpu_milli),
                np.asarray(m.arrived_gpu_milli),
                np.asarray(m.used_cpu_milli),
                np.asarray(m.arrived_cpu_milli),
                np.asarray(m.power_cpu),
                np.asarray(m.power_gpu),
                bellman=bellman,
                kinds=kinds,
                ev_create=EV_CREATE,
                ev_delete=EV_DELETE,
                pod_names=pod_names,
                failed=ev_failed,
                series=series,
            )
        )

    def alloc_maps(self, state: NodeState):
        """Cluster requested/allocatable per resource (ref: alloc.go:90-127
        GetNodeAllocMap aggregated)."""
        s = jax.tree.map(np.asarray, state)
        slot = np.arange(s.gpu_left.shape[1])[None, :] < s.gpu_cnt[:, None]
        used_dev = slot & (s.gpu_left < MILLI)
        requested = {
            "MilliCpu": int((s.cpu_cap - s.cpu_left).sum()),
            "Memory": int(np.int64(s.mem_cap - s.mem_left).sum() * 1024 * 1024),
            "Gpu": int(used_dev.sum()),
            "MilliGpu": int((np.where(slot, MILLI - s.gpu_left, 0)).sum()),
        }
        allocatable = {
            "MilliCpu": int(np.int64(s.cpu_cap).sum()),
            "Memory": int(np.int64(s.mem_cap).sum() * 1024 * 1024),
            "Gpu": int(s.gpu_cnt.sum()),
            "MilliGpu": int(s.gpu_cnt.sum()) * MILLI,
        }
        return requested, allocatable

    def cluster_analysis(self, tag: str = "InitSchedule", amounts=None):
        """The end-of-stage 16-line analysis block (analysis.go:145-199).

        `amounts`: the state's seven frag amounts where the caller holds
        them already (a sweep's post-pass fetched every lane's with the
        lanes, SweepLane.frag_amounts); computed here otherwise."""
        from tpusim.ops.frag import cluster_frag_report

        state = (
            self.last_result.state if hasattr(self, "last_result") else self.init_state
        )
        if amounts is not None:
            amounts = np.asarray(amounts)
        else:
            state_j = jax.tree.map(jnp.asarray, state)
            amounts = np.asarray(cluster_frag_report(state_j, self.typical)[0])
        requested, allocatable = self.alloc_maps(state)
        kv = cluster_analysis_block(
            self.log, tag, amounts, requested, allocatable
        )
        # running summary across stages, in emission order (the direct CSV
        # path's stand-in for re-parsing the blocks out of the log)
        self.analysis_summary.update(kv)
        return amounts, requested, allocatable


# ---------------------------------------------------------------------------
# Shared replay-shape plumbing (single runs + sweeps)
# ---------------------------------------------------------------------------


def _bucket_sizes(p: int, e: int, bucket: int) -> Tuple[int, int]:
    """Size-adaptive padding targets: large runs share one bucketed
    executable; small runs (descheduler victims, inflation clones) round to
    the next power of two so padding waste stays <= 2x."""
    b = bucket if max(p, e) >= bucket else max(32, 1 << (max(p, e) - 1).bit_length())
    return -(-p // b) * b, -(-e // b) * b


def _pad_specs(specs, p2: int, type_id=None, xp=jnp):
    """Pad pod specs (and their type ids) to p2 rows with inert zero pods
    (pinned -1, never referenced by any event). xp=jnp pads on device
    (single runs); xp=np keeps host arrays (a sweep stacks several padded
    sets before ONE upload instead of a device round trip per leaf)."""
    from tpusim.types import PodSpec

    p = int(specs.cpu.shape[0])
    if p2 == p:
        return specs, type_id
    pad = p2 - p
    z = xp.zeros(pad, xp.int32)
    out = PodSpec(
        cpu=xp.concatenate([specs.cpu, z]),
        mem=xp.concatenate([specs.mem, z]),
        gpu_milli=xp.concatenate([specs.gpu_milli, z]),
        gpu_num=xp.concatenate([specs.gpu_num, z]),
        gpu_mask=xp.concatenate([specs.gpu_mask, z]),
        pinned=xp.concatenate([specs.pinned, xp.full(pad, -1, xp.int32)]),
    )
    if type_id is not None:
        type_id = xp.concatenate([type_id, z])
    return out, type_id


def _pad_events(ev_kind, ev_pod, e2: int, xp=jnp):
    """Pad event streams to e2 with EV_SKIP events referencing pod 0."""
    from tpusim.sim.engine import EV_SKIP

    e = int(ev_kind.shape[0])
    if e2 == e:
        return ev_kind, ev_pod
    ev_kind = xp.concatenate(
        [ev_kind, xp.full(e2 - e, EV_SKIP, ev_kind.dtype)]
    )
    ev_pod = xp.concatenate([ev_pod, xp.zeros(e2 - e, ev_pod.dtype)])
    return ev_kind, ev_pod


def _slice_result(out, p: int, e: int):
    """Slice a (possibly padded) ReplayResult back to true pod/event sizes."""
    if int(out.placed_node.shape[0]) == p and int(out.event_node.shape[0]) == e:
        return out
    return out._replace(
        placed_node=out.placed_node[:p],
        dev_mask=out.dev_mask[:p],
        ever_failed=out.ever_failed[:p],
        event_node=out.event_node[:e],
        event_dev=out.event_dev[:e],
        metrics=(
            None
            if out.metrics is None
            else jax.tree.map(lambda a: a[:e], out.metrics)
        ),
        decisions=(
            None
            if out.decisions is None
            else jax.tree.map(lambda a: a[:e], out.decisions)
        ),
        series=(
            None
            if out.series is None
            else jax.tree.map(lambda a: a[:e], out.series)
        ),
    )


# ---------------------------------------------------------------------------
# Config-axis sweep: one compiled scan, B what-if configurations (ISSUE 6)
# ---------------------------------------------------------------------------
#
# The reference grids its 1020 policy × weight × seed replays with a
# process per experiment (experiments/README.md step 2, xargs --max-procs).
# Here the replays themselves are the batch: the per-policy WEIGHT VECTOR
# is a traced engine operand (sim.step.resolve_weights), so a [B, num_pol]
# weight matrix plus per-config seeds vmaps over ONE workload and ONE
# compiled replay — the jaxpr is the policy family's, the weights are
# data. The weight-independent score tables are built once and shared
# across every lane (in_axes None), so the marginal what-if costs only
# its share of the vmapped scan, never a table build or a compile.
#
# Three more things ride the same sweep as operands (schedule_pods_sweep):
# the TUNE FACTOR (ISSUE 7: a lane's own tuned trace, per-lane specs,
# type_id and event streams), which is also how a seed group's shuffles
# run (run_batch: a trace and a seed a lane), a FAULT SCHEDULE (ISSUE 10,
# 12: with the fault plane inside the scan, tpusim.sim.fault_lane, a
# schedule is five i32 streams, a draw table and a param vector), and the
# typical pods a lane is scored against. There is one path for all of it:
# one wrapper factory that reads the vmap axes off its operands, one host
# prep, one dispatch, one tail, one sweep record.

_SWEEP_WRAP_CACHE = {}


@dataclass
class SweepLane:
    """One configuration's result out of a config-axis sweep — the
    per-lane slice of the vmapped replay plus the summary scalars the
    CLI table prints. Placements are bit-identical to a standalone run
    with `weights` baked into the config and `seed` as cfg.seed
    (tests/test_sweep.py pins this per engine). Out of a sweep every
    array here, the leaves of `state` and `metrics` too, is a VIEW: of the
    one fetched buffer, or of an array the sweep made once for all its
    lanes (`weights`, `counters`, the bool leaves' casts); the five
    capacity leaves of `state` (types.CAPACITY_LEAVES) are the SAME
    read-only view in every lane. Copy before writing what another lane
    must not see (_slice_sweep_lanes)."""

    weights: np.ndarray  # i32[num_pol] this lane's weight vector
    seed: int
    placed_node: np.ndarray  # i32[P]
    dev_mask: np.ndarray  # bool[P, 8]
    ever_failed: np.ndarray  # bool[P]
    counters: Optional[np.ndarray]  # i32[obs.NUM_COUNTERS], pad-corrected
    metrics: object  # EventMetrics (per-event rows) or None
    state: object  # final NodeState (host arrays)
    events: int
    placed: int  # pods placed at end of trace
    failed: int  # creation attempts rejected
    gpu_alloc_pct: float
    frag_gpu_milli: float
    # pods that ended the trace unplaced AFTER a rejected creation — the
    # schedule_pods_with_faults "unscheduled" semantics (a later retry may
    # place an ever-failed pod; a placed-then-deleted pod is neither).
    # The learned-scoring objective's third term (ISSUE 9): gpu_alloc up,
    # frag down, unscheduled bounded.
    unscheduled: int = 0
    # tpusim.sim.metrics.DisruptionMetrics of this lane's fault schedule
    # (ISSUE 10; None on fault-free sweeps) — bit-identical to the
    # standalone run_with_faults run with the same schedule/seed.
    disruption: object = None
    # the energy model (ops/energy.node_power) over the lane's FINAL node
    # state, summed over its nodes: the reference's `[Power]; cluster;
    # ClusterCPU; ClusterGPU` line is their sum and the two
    power_cpu_w: float = 0.0
    power_gpu_w: float = 0.0
    # the lane's record of its events, as ReplayResult keeps it: the node
    # chosen (a creation) or freed (a deletion) at every real event, -1
    # where nothing moved, and the devices touched. With deletions in the
    # stream placed_node names only who is placed at the END; this is what
    # a reference walks a lane by. Views of the one fetched buffer; None on
    # a lane built from a chunked run's final arrays (lane_from_arrays)
    event_node: Optional[np.ndarray] = None  # i32[E]
    event_dev: Optional[np.ndarray] = None  # bool[E, 8]
    # the lane's frag amounts by category over its final state, f32[7]:
    # what frag_gpu_milli sums, and what cluster_analysis prints
    frag_amounts: Optional[np.ndarray] = None


# The lane axis of a sweep's final states, a leaf: 0 on the four leaves a
# step can write, None on the capacity leaves, which leave the program as
# they entered it, [N] (_sweep_engine). The post-pass's in_axes and the
# slicing read it; device_fetch packs the shapes it is given.
_LANE_STATE_AXES = NodeState(*(
    None if f in CAPACITY_LEAVES else 0 for f in NodeState._fields))


def _lane_axis(operand, rank: int):
    """The vmap axis of a sweep operand, read off the operand itself: 0
    when it was stacked a lane (one axis more than the engine takes), None
    when the lanes share it."""
    return 0 if len(operand.shape) > rank else None


def _sweep_engine(engine, args, keep_streams: bool = False):
    """The jitted vmapped replay of `engine` for the operands `args` (the
    engine's own, in its order; arrays or ShapeDtypeStructs), one lane a
    what-if: THE `jit(vmap(...))` of every sweep, cached by (engine,
    in_axes, donated operands). The underlying weight-operand engine is
    itself shared across weight configs (one jaxpr per job family), and
    consecutive waves of one family resolve to one wrapper, so its
    `_cache_size()` is the family's executable count.

    in_axes are read off the operands. Key, weights and tie-break rank
    always carry the lane axis; cluster state and the distinct type set
    always broadcast. Typical pods and score tables broadcast where the
    lanes share one set; stacked a set ([F, T], [F, ...]: lanes of F
    workload families) `args` ends in one more operand, `lane_set`
    i32[B], and the wrapper hands each lane its family's before the vmap.
    Pod specs
    and `types.type_id` follow the traces: stacked when every lane replays
    its own workload (tuned traces are data, not jaxpr structure: nothing
    in an engine reads type_id except as a per-pod gather key). The event
    streams are stacked with per-lane traces and with fault plans (a plan
    merges its steps into its lane's stream). Fault plans add two
    operands: the per-lane FaultOps (whose gpu-count row is the
    cluster's) and the initial fault carry, which broadcasts.

    What leaves WITHOUT a lane axis, by one rule for every sweep: the
    final states' five capacity leaves (types.CAPACITY_LEAVES: cpu_cap,
    mem_cap, gpu_cnt, gpu_type, cpu_type). No step of any body writes
    them, fault steps included, so after the vmap the wrapper puts the
    start state's own [N] leaves in the output's place and XLA drops
    whatever gave them a lane axis: the vmap's broadcast today (out_axes
    None on the five traces on every body tier-1 runs: none batches them),
    and a select over a whole state under a lane's predicate if a body
    ever makes one, which out_axes None would refuse although it writes
    nothing. The lanes' states keep the lane
    axis on cpu_left, mem_left, gpu_left and aff_cnt (_LANE_STATE_AXES);
    B copies of the cluster's capacities, 20 bytes a node a lane, are
    neither made on the device nor packed nor copied to the host.
    tests/test_sweep_shared.py holds "nothing writes them" by value on
    every body, and the compiled outputs to the shapes.

    Donation, by one rule: the stacked tie-break rank always (the [B, N]
    buffer matches the output state's cpu_left and mem_left, which keep
    the lane axis, so a repeated-wave caller — the
    svc worker's batch loop, a tuning run's generations — reuses it
    instead of reallocating per wave; keys and weights are byte-tiny and
    alias nothing), and a per-lane event stream (its [B, E] i32 buffer
    matches the event_node output leaf) unless `keep_streams`: the
    report_per_event post-pass reads the streams after dispatch. Safe at
    the one dispatch site: schedule_pods_sweep builds both fresh and
    reads neither afterwards. Donation is part of the executable's
    aliasing contract, not of its jaxpr."""
    from tpusim.sim.fault_lane import FaultOps
    from tpusim.sim.table_engine import PodTypes, flat_group_events

    table = isinstance(args[2], PodTypes)
    trace_ax = _lane_axis(args[1].cpu, 1)
    if table:
        # (state, pods, types, ev_kind, ev_pod, tp, key, wts, rank,
        #  tables[, fault_ops, fault_carry0])
        ev_pod_i, rank_i, plain = 4, 8, 10
        a_set = (5, 9)  # typical pods, tables
        ev_ax = _lane_axis(args[3], 1)
        tid_ax = _lane_axis(args[2].type_id, 1)
        set_ax = _lane_axis(args[5].cpu, 1)
        in_axes = (None, trace_ax, PodTypes(None, None, tid_ax), ev_ax,
                   ev_ax, set_ax, 0, 0, 0, set_ax)
    else:
        # (state, pods, ev_kind, ev_pod, tp, key, wts, rank
        #  [, fault_ops, fault_carry0])
        ev_pod_i, rank_i, plain = 3, 7, 8
        a_set = (4,)  # typical pods
        ev_ax = _lane_axis(args[2], 1)
        set_ax = _lane_axis(args[4].cpu, 1)
        in_axes = (None, trace_ax, ev_ax, ev_ax, set_ax, 0, 0, 0)
    # stacked a set, the typical pods (and tables) bring one LAST operand:
    # lane_set i32[B], each lane's set
    faulted = len(args) - (set_ax == 0) > plain
    if faulted:
        in_axes += (FaultOps(0, 0, 0, 0, 0, None), None)
    donate = (rank_i,) + (
        (ev_pod_i,) if ev_ax == 0 and not keep_streams else ())
    # A wide sweep of a short cluster runs the flat step in groups
    # (flat_group_events of the stacked ranks' [lanes, nodes]) where the
    # chip judged it: the table engine with no fault operands, one shared
    # trace (PERF.md section 6, PR 29) or a trace a lane (PR 33: type ids
    # one a lane; the rows are row gathers, the picks out of the pending
    # block lane_write.read_pending's dense form, and the program holds no
    # loop over the lanes). No run has grouped a fault plan's steps: they
    # keep the plain body until a cell or a chip run says otherwise
    # (section 7)
    grouped = table and not faulted

    ck = (engine, in_axes, donate)
    if ck not in _SWEEP_WRAP_CACHE:
        def swept(*operands):
            if set_ax == 0:
                # typical pods and tables stacked a SET ([F, ...], one a
                # family of lanes): each lane takes its own. The pick is
                # F selects over the lane axis, no gather (XLA may run one
                # with an index a lane as a loop over the lanes)
                *operands, lane_set = operands
                for i in a_set:
                    operands[i] = jax.tree.map(
                        functools.partial(_rows_a_lane, lane_set),
                        operands[i])
            group = ({"group": flat_group_events(*operands[rank_i].shape)}
                     if grouped else {})
            out = jax.vmap(
                functools.partial(engine, **group), in_axes=in_axes,
            )(*operands)
            return _share_capacity(out, operands[0])

        # the program keeps the engine's name (traces, compile cache)
        swept.__name__ = getattr(engine, "__name__", swept.__name__)
        _SWEEP_WRAP_CACHE[ck] = jax.jit(swept, donate_argnums=donate)
    return _SWEEP_WRAP_CACHE[ck]


def _share_capacity(out, state):
    """A vmapped replay's result with the capacity leaves of its final
    states as they entered, `state`'s own [N]: no step writes them, so
    every lane's are the start state's, and nothing downstream of the
    program (the post-pass, the pack, the copy, the slicing) handles B
    copies of them."""
    return out._replace(state=out.state._replace(**{
        f: getattr(state, f) for f in CAPACITY_LEAVES}))


def _rows_a_lane(lane_set, sets):
    """`sets[lane_set]` for a handful of stacked sets ([F, ...] -> [B,
    ...]) as F selects over the lane axis: what a sweep does to hand each
    lane its family's typical pods and score tables, inside its program."""
    out = jnp.broadcast_to(sets[0], lane_set.shape + sets.shape[1:])
    mask_shape = lane_set.shape + (1,) * (sets.ndim - 1)
    for f in range(1, sets.shape[0]):
        out = jnp.where((lane_set == f).reshape(mask_shape), sets[f], out)
    return out


# (wrapper, lanes) -> (write sites, dense sites, events a table pass) of
# the program
_SWEEP_LANE_SITES = {}


def _dispatch_counting_lane_sites(fn, lanes: int, *args):
    """fn(*args) for fn = _sweep_engine(engine, args), the number of write
    sites of that program that went through sim/lane_write.py's batching
    rule, the number of its sites (reads too) that the rule lowered in
    the dense form, and the events whose columns one dense pass over a
    table writes (0: the program has no such pass). The rule runs while
    the program is traced, so a call served from the jit cache reports
    what the last trace of the wrapper at that width counted (the lanes
    decide the flat group)."""
    from tpusim.sim import lane_write

    with lane_write.counting() as sites:
        out = fn(*args)
    if sites:
        _SWEEP_LANE_SITES[fn, lanes] = (
            len(sites), len(sites.dense), sites.table_pass_events)
    return (out,) + _SWEEP_LANE_SITES.get((fn, lanes), (0, 0, 0))


@jax.jit
def _lane_watts(state):
    """One lane's (CPU, GPU) watts over its node state: the reports' own
    power_rows, summed over the nodes. Jitted HERE, once a process:
    _lane_postpass is traced anew in every sweep (its caller wraps a new
    function object), and with the energy model's hundred primitives
    traced under two vmaps in every wave the family cell's frag_postpass
    span held the host 0.200 s where it had held it 0.124 s, and its wave
    was 0.84 % longer; as a call of a cached jaxpr they cost the outer
    trace one equation."""
    from tpusim.sim.engine import power_rows

    with jax.named_scope("tpusim.power_postpass"):
        return jnp.stack([rows.sum() for rows in power_rows(state)])


def _lane_postpass(state, tp):
    """One lane's frag amounts by category (the reduction cluster_analysis
    reports) and its (CPU, GPU) watts, each summed over its nodes: the
    sweeps vmap it over their lanes' final states, ONE program before the
    single fetch."""
    from tpusim.ops.frag import cluster_frag_amounts

    with jax.named_scope("tpusim.frag_postpass"):
        amounts = cluster_frag_amounts(state, tp).sum(0)
    return amounts, _lane_watts(state)


@functools.lru_cache(maxsize=None)
def _sweep_metrics_fn(trace_axis, typical_axis=None):
    """compute_event_metrics vmapped over the lanes: ONE cluster, per-lane
    telemetry, of ONE workload (`trace_axis` None) or of the lanes' own
    specs and event streams (0), against ONE typical-pod set or the
    lanes' own (`typical_axis` 0)."""
    from tpusim.sim.metrics import compute_event_metrics

    return jax.jit(
        jax.vmap(
            compute_event_metrics,
            in_axes=(None, trace_axis, trace_axis, trace_axis, 0, 0,
                     typical_axis),
        )
    )


def _reject_unsweepable(cfg) -> None:
    """The execution modes no vmapped config-axis sweep can serve —
    shared by the single-trace and multi-trace (ISSUE 7) paths."""
    if cfg.extenders:
        raise ValueError(
            "schedule_pods_sweep cannot run extender configs (per-cycle "
            "HTTP round-trips do not batch)"
        )
    if cfg.mesh:
        raise ValueError(
            "schedule_pods_sweep cannot run mesh configs (the shard_map "
            "engine owns the device axis)"
        )
    if cfg.record_decisions:
        raise ValueError(
            "schedule_pods_sweep cannot record decisions (the vmapped "
            "replay has no per-config provenance surface)"
        )
    if cfg.series_every:
        raise ValueError(
            "schedule_pods_sweep cannot emit the in-scan series (the "
            "vmapped replay has no per-config sampling surface)"
        )


def _check_sweep_grid(cfg, weights, seeds):
    """Validate the [B, num_pol] weight grid + per-lane seeds; returns
    (w, B, seeds) with defaults resolved."""
    w = np.asarray(weights, np.int32)
    if w.ndim != 2 or w.shape[1] != len(cfg.policies):
        raise ValueError(
            f"weights must be a [B, {len(cfg.policies)}] matrix (one row "
            f"per config, columns in cfg.policies order); got shape "
            f"{w.shape}"
        )
    b = int(w.shape[0])
    if b < 1:
        raise ValueError("weights needs at least one config row")
    if seeds is None:
        seeds = [cfg.seed] * b
    seeds = [int(s) for s in seeds]
    if len(seeds) != b:
        raise ValueError(
            f"seeds has {len(seeds)} entries for {b} weight rows"
        )
    return w, b, seeds


_INT32 = np.iinfo(np.int32)
_LANE_KEYS_FN = jax.jit(jax.vmap(jax.random.PRNGKey))


def _lane_keys(seeds):
    """[B, ...] PRNG keys, one a lane, equal to stacking
    jax.random.PRNGKey(s): ONE transfer and ONE vmapped program, where the
    per-lane form dispatched two small programs a lane (2.2 s of a 9.6 s
    wave at 2,560 lanes; PERF.md section 6, PR 28). Seeds outside int32
    keep the per-lane form, whose wrap-around rule is PRNGKey's own."""
    if all(_INT32.min <= s <= _INT32.max for s in seeds):
        return _LANE_KEYS_FN(np.asarray(seeds, np.int32))
    return jnp.stack([jax.random.PRNGKey(s) for s in seeds])


def _lane_ranks(num_nodes: int, seeds, marks=None):
    """[B, N] tie-break ranks, one permutation a lane, stacked on the host
    and moved in one transfer (fresh every call: the sweep engines donate
    it). `marks`: a span handle to stamp `stacked` on, between the two."""
    stacked = np.stack([tiebreak_rank(num_nodes, s) for s in seeds])
    if marks is not None:
        marks.mark("stacked", then="transfer")
    return jnp.asarray(stacked)


def _slice_sweep_lanes(out, amounts, watts, w, seeds, pods_n, events_n,
                       pad_skips) -> List["SweepLane"]:
    """A fetched (host) vmapped sweep result cut into one SweepLane a lane,
    lane i with its true sizes `pods_n[i]` / `events_n[i]` and its
    `pad_skips[i]` bucket-padding skips. The summary math is ONE pass an
    array over the lane axis, the loop after it builds views and objects
    only: every array a lane holds is a view of the fetched buffer or of a
    per-sweep summary array; the five capacity leaves of `out.state` come
    without a lane axis (_LANE_STATE_AXES) and are ONE view shared by all
    the lanes' states, read-only as the fetched buffer is. Each field
    equals lane_from_arrays' on that lane's own arrays
    (tests/test_sweep_slice.py)."""
    from tpusim.ops.frag import frag_sum_except_q3

    st = out.state
    b = st.gpu_left.shape[0]
    # the allocation ratio without a slot mask: gpu_left is 0 beyond
    # gpu_cnt devices (types.NodeState), so the used milli of the real
    # slots is MILLI a device less all that is left. No [B, N, 8]
    # temporary: three of them leave the cache a lane's own stayed in, and
    # the batched masked form read slower than the loop (PERF.md, PR 39).
    # The devices are the cluster's, one sum for all the lanes
    cnt = int(st.gpu_cnt.sum(dtype=np.int64))
    used = MILLI * cnt - st.gpu_left.reshape(b, -1).sum(1, dtype=np.int64)
    alloc = (100.0 * used.astype(np.float64)) / max(cnt * MILLI, 1)
    # a lane's pods are the first pods_n[i] of the padded pod axis
    live = np.arange(out.placed_node.shape[1]) < np.asarray(pods_n)[:, None]
    on_node = (out.placed_node >= 0) & live
    failed = out.ever_failed & live
    ctr = None
    if out.counters is not None:
        ctr = out.counters.astype(np.int64)
        ctr[:, 4] = np.maximum(ctr[:, 4] - np.asarray(pad_skips), 0)
    weights = np.array(w, np.int32)
    frag = frag_sum_except_q3(amounts).tolist()
    alloc, watts = alloc.tolist(), watts.tolist()
    placed = on_node.sum(1).tolist()
    unscheduled = (failed & ~on_node).sum(1).tolist()
    failed = failed.sum(1).tolist()

    lanes = []
    for i, (p, e) in enumerate(zip(pods_n, events_n)):
        metrics_i = None
        if out.metrics is not None:
            metrics_i = type(out.metrics)(*(a[i, :e] for a in out.metrics))
        lanes.append(SweepLane(
            weights=weights[i],
            seed=seeds[i],
            placed_node=out.placed_node[i, :p],
            dev_mask=out.dev_mask[i, :p],
            ever_failed=out.ever_failed[i, :p],
            counters=None if ctr is None else ctr[i],
            metrics=metrics_i,
            state=NodeState(*(
                leaf if ax is None else leaf[i]
                for leaf, ax in zip(st, _LANE_STATE_AXES))),
            events=e,
            placed=placed[i],
            failed=failed[i],
            gpu_alloc_pct=alloc[i],
            frag_gpu_milli=frag[i],
            unscheduled=unscheduled[i],
            power_cpu_w=watts[i][0],
            power_gpu_w=watts[i][1],
            event_node=out.event_node[i, :e],
            event_dev=out.event_dev[i, :e],
            frag_amounts=amounts[i],
        ))
    return lanes


def lane_from_arrays(state, placed_node, dev_mask, ever_failed, counters,
                     typical, weights, seed, events,
                     pad_skips: int = 0) -> SweepLane:
    """SweepLane from raw final-run arrays — the shared summary math of
    lane_from_run (standalone/forked chunked runs) and the ChunkWave
    serving path (ISSUE 16). One lane's form of what _slice_sweep_lanes
    gives a sweep's lanes, field for field: same counters pad-correction,
    same gpu_alloc (here under the slot mask; the sweep's pass leans on the
    zero pads instead), same frag post-pass, so every result document of a
    family is field-for-field comparable regardless of which execution
    path produced it."""
    from tpusim.ops.frag import frag_sum_except_q3

    pn = np.asarray(placed_node, np.int32)
    failed = np.asarray(ever_failed, bool)
    ctr = None
    if counters is not None:
        ctr = np.asarray(counters).astype(np.int64).copy()
        ctr[4] = max(int(ctr[4]) - int(pad_skips), 0)  # bucket padding
    st = jax.tree.map(np.asarray, state)
    slot = (
        np.arange(st.gpu_left.shape[1])[None, :] < st.gpu_cnt[:, None]
    )
    denom = max(int(st.gpu_cnt.sum()) * MILLI, 1)
    alloc = 100.0 * float(
        np.where(slot, MILLI - st.gpu_left, 0).sum()
    ) / denom
    amounts, watts = (
        np.asarray(a)
        for a in _lane_postpass(jax.tree.map(jnp.asarray, st), typical)
    )
    return SweepLane(
        weights=np.asarray(weights, np.int32).copy(),
        seed=int(seed),
        placed_node=pn,
        dev_mask=np.asarray(dev_mask),
        ever_failed=failed,
        counters=ctr,
        metrics=None,
        state=st,
        events=int(events),
        placed=int((pn >= 0).sum()),
        failed=int(failed.sum()),
        gpu_alloc_pct=alloc,
        frag_gpu_milli=float(frag_sum_except_q3(amounts)),
        unscheduled=int(((pn < 0) & failed).sum()),
        power_cpu_w=float(watts[0]),
        power_gpu_w=float(watts[1]),
        frag_amounts=amounts,
    )


def lane_from_run(sim: "Simulator", weights, seed,
                  pad_skips: int = 0) -> SweepLane:
    """SweepLane view of the Simulator's newest STANDALONE run
    (schedule_pods / schedule_pods_fork) — the svc serving path's result
    vocabulary (learn.objective.lane_terms) applied to base runs and
    warm-state forks, which execute through the chunked replay rather
    than a vmapped sweep."""
    res = sim.last_result
    return lane_from_arrays(
        res.state, res.placed_node, res.dev_mask, sim.last_ever_failed,
        sim.last_counters, sim.typical, weights, seed, int(res.events),
        pad_skips,
    )


class ChunkWave:
    """The continuous-batching chunk surface of the what-if serving
    plane (ISSUE 16): B lanes of one job family stepping through the
    donated `run_chunk` twin TOGETHER, one vmapped dispatch per chunk,
    with per-lane event streams as operands. Because every lane shares
    the family's state/specs/types/typical/weights/rank (forks of one
    base run agree on all of them — the fork index enforces it), the
    vmap axis carries only (carry, ev_kind chunk, ev_pod chunk): a lane
    can be restored from a mid-trace base checkpoint, joined at ANY
    chunk boundary via the scatter entry (replacing a padding lane),
    and finished independently — all through exactly three jitted
    callables whose executable count is the zero-recompile metric.

    Padding discipline mirrors run_events byte-for-byte (_bucket_sizes
    pow2 adaptation included), so `base_digest` here equals the digest
    the standalone base run persisted its checkpoints under — the fork
    index's content contract. Idle/free lanes are fed EV_SKIP chunks:
    the scan body splits the PRNG key BEFORE branching on kind, so a
    skip advances only the key and the skip counter — trailing skip
    count differences between lanes are inert for every extracted
    result (pinned by tests/test_fork.py), and the host-tracked pad
    count corrects the skip counter per lane."""

    def __init__(self, sim: "Simulator", pods, lanes: int, chunk: int,
                 bucket: int = 512):
        from tpusim.io.trace import build_events
        from tpusim.sim.table_engine import build_pod_types, pad_pod_types

        if sim.cfg.mesh or sim.cfg.engine not in ("table", "auto"):
            raise ValueError(
                "chunk waves run on the table engine (engine table/auto, "
                "no mesh)"
            )
        if (sim.cfg.extenders or sim.cfg.record_decisions
                or sim.cfg.series_every):
            raise ValueError(
                "chunk waves have no extender/decision/series surface"
            )
        if sim.typical is None:
            sim.set_typical_pods()
        self.sim = sim
        self.lanes = int(lanes)
        self.chunk = max(1, int(chunk))
        fn = sim._table_fn
        self._fn = fn
        state = sim.init_state
        specs = pods_to_specs(pods, sim.node_index)
        bk, bp = build_events(pods, sim.cfg.use_timestamps)
        bk, bp = jnp.asarray(bk), jnp.asarray(bp)
        validate_events(bk, bp, int(specs.cpu.shape[0]))
        p, e = int(specs.cpu.shape[0]), int(bk.shape[0])
        p2, e2 = _bucket_sizes(p, e, bucket)
        types = build_pod_types(specs)
        k = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
        if k == 0:
            raise ValueError(
                "no distinct pod types — the table carry surface needs "
                "at least one"
            )
        specs, tid = _pad_specs(specs, p2, types.type_id, xp=jnp)
        types = types._replace(type_id=tid)
        if p2 != p or e2 != e:
            types = pad_pod_types(types)
        self.base_kind, self.base_pod = _pad_events(bk, bp, e2, xp=jnp)
        self.p, self.e, self.p2, self.e2 = p, e, p2, e2
        self.specs, self.types = specs, types
        self.state = state
        self.key = jax.random.PRNGKey(sim.cfg.seed)
        self.rank = sim.rank
        self.base_digest = sim._run_digest(
            state, specs, self.base_kind, self.base_pod, self.key,
            sim.rank
        )
        self.checkpoint_dir = sim._checkpoint_dir()
        template = jax.eval_shape(
            fn.init_carry, state, specs, types, sim.typical, self.key,
            sim.rank
        )
        self._tleaves, self._tdef = jax.tree.flatten(template)
        typical, rank = sim.typical, sim.rank

        def _chunk1(carry, evk, evp):
            carry, _ys = fn.run_chunk(
                carry, specs, types, evk, evp, typical, rank
            )
            # strip weak_type from every carry leaf: the scan body
            # leaves one weakly-typed counter, and a weak-vs-strong
            # signature flip between host-built carries (stack/restore,
            # strong) and jit outputs (weak) would re-trace step AND
            # scatter once mid-wave — churn the zero-recompile census
            # must not carry
            return ChunkWave._strong(carry)

        # the three compiled entries of the wave: a lane join, a lane
        # finish, and the B-wide chunk advance — each traces exactly
        # once per family (the donated carry keeps buffers in place)
        self._step = jax.jit(
            jax.vmap(_chunk1, in_axes=(0, 0, 0)), donate_argnums=(0,)
        )
        self._scatter = jax.jit(
            lambda batch, lane, i: jax.tree.map(
                lambda b, l: b.at[i].set(l), batch, lane
            ),
            donate_argnums=(0,),
        )

        def _finish1(batch, i):
            lane = jax.tree.map(lambda x: x[i], batch)
            st, placed, masks, failed = fn.finish(lane)
            return st, placed, masks, failed, lane.ctr

        self._finish = jax.jit(_finish1)

    # ---- lane carries ----

    @staticmethod
    def _strong(tree):
        """Strip weak_type from every leaf (values/dtypes unchanged, so
        checkpoints and digests are unaffected) — the wave's signature
        stability contract: every carry that circulates, whether
        host-built or a jit output, presents the same strong-typed
        avals to step/scatter/finish."""
        return jax.tree.map(lambda x: x.astype(x.dtype), tree)

    def init_lane(self):
        """Fresh event-0 carry — full-replay twins and degraded forks."""
        tables = self.sim._cached_tables(self.state, self.types, self.key)
        return self._strong(self._fn.init_carry(
            self.state, self.specs, self.types, self.sim.typical,
            self.key, self.rank, tables=tables,
        ))

    def restore_lane(self, fork_event: int):
        """(cursor, carry) restored from the base run's nearest persisted
        checkpoint at-or-before the divergence event, or None (the
        degrade path — the caller falls back to init_lane). Never
        deletes a base checkpoint it merely fails to interpret."""
        from tpusim.io import storage as ckpt

        def _validate(arrays):
            leaves = [
                arrays[f"c{i:03d}"] for i in range(len(self._tleaves))
            ]
            if any(
                a.shape != t.shape or a.dtype != t.dtype
                for a, t in zip(leaves, self._tleaves)
            ):
                raise ValueError("carry layout mismatch")

        found = ckpt.load_valid_checkpoint(
            self.checkpoint_dir, self.base_digest, validate=_validate,
            max_cursor=int(fork_event), delete_invalid=False,
        )
        if found is None:
            return None
        cursor, arrays, _path = found
        leaves = [
            jnp.asarray(arrays[f"c{i:03d}"])
            for i in range(len(self._tleaves))
        ]
        return cursor, jax.tree.unflatten(self._tdef, leaves)

    def fork_stream(self, fork_event: int, tail):
        """(ev_kind, ev_pod, real) of the forked run: the shared base
        prefix up to fork_event + the divergent ((kind, pod), ...) tail,
        as host arrays. `real` is the true event count; the wave pads
        each lane's final partial chunk with inert EV_SKIPs."""
        bk = np.asarray(self.base_kind)
        bp = np.asarray(self.base_pod)
        tk = np.asarray([k for k, _ in tail], bk.dtype)
        tpd = np.asarray([pd for _, pd in tail], bp.dtype)
        evk = np.concatenate([bk[: int(fork_event)], tk])
        evp = np.concatenate([bp[: int(fork_event)], tpd])
        return evk, evp, int(evk.shape[0])

    # ---- the wave surface ----

    def stack(self, carries):
        """Lane carries -> the batched wave carry (leading lane axis)."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *carries)

    def step(self, batch_carry, evk, evp):
        """Advance every lane one chunk: evk/evp are [lanes, chunk].
        DONATES batch_carry — the caller rebinds."""
        return self._step(batch_carry, jnp.asarray(evk), jnp.asarray(evp))

    def scatter(self, batch_carry, lane_carry, i: int):
        """Install a joining lane's carry into slot i at a chunk
        boundary (donates batch_carry; i is traced — one executable
        serves every slot)."""
        return self._scatter(batch_carry, lane_carry, jnp.int32(i))

    def finish_lane(self, batch_carry, i: int):
        """(state, placed, masks, failed, counters) of lane i — the
        batch carry survives (not donated) and keeps stepping."""
        return self._finish(batch_carry, jnp.int32(i))

    def executables(self) -> int:
        """Compiled-executable census across the wave's three entries —
        the zero-recompile acceptance metric: stable across join waves,
        lane scatters, and finishes of one family."""
        return (
            self._step._cache_size() + self._scatter._cache_size()
            + self._finish._cache_size()
        )


def resolve_fault_spec(spec, num_nodes: int, num_events: int):
    """One chaos-sweep lane spec -> (FaultConfig, [FaultEvent]): a bare
    FaultConfig generates its seeded MTBF schedule; a (FaultConfig,
    events) tuple carries an explicit schedule with the config supplying
    the retry/backoff knobs."""
    from tpusim.sim.faults import FaultConfig, generate_fault_schedule

    if isinstance(spec, tuple) and len(spec) == 2:
        fcfg, events = spec
        return fcfg, list(events)
    if isinstance(spec, FaultConfig):
        return spec, generate_fault_schedule(num_nodes, num_events, spec)
    raise ValueError(
        "each fault lane must be a FaultConfig (seeded MTBF schedule) or "
        f"a (FaultConfig, [FaultEvent]) tuple, got {type(spec).__name__}"
    )


class _ResidentTables(NamedTuple):
    """The score tables a sweep left on the device and everything their
    build read, which is what proves them right for another sweep
    (Simulator._sweep_tables). Not among it, because the build never
    consumes them: the event stream, the PRNG key (only RandomScore draws
    from it, and its table slot is zeros), the tie-break rank, the
    weights and type_id (Simulator._tables_digest)."""

    # what the builder closes over (_TableEngine.closes_over: the policy
    # kernels, which the policy names, dim_ext_method and norm_method
    # decide, and the selector index, which gpu_sel_method decides)
    closes_over: tuple
    # the initial NodeState's leaves, 9.6 MB at 100,000 nodes. jax arrays
    # are immutable and the entry holds them, so no id is recycled: `is`
    # proves equality without reading a byte
    state_leaves: tuple
    # host copies of types.share, types.whole (K rows of six i32 fields)
    # and the typical pods, a few KB together. Their owners rebuild them
    # (every call its types, Simulator.run_sweep the typical pods), so
    # they are compared by content
    rows: tuple
    tables: tuple = ()


def _tables_proof(engine, state, types, typical):
    """The _ResidentTables of one build's inputs (its tables to come), or
    None where identity proves nothing: a state leaf that is not an
    immutable jax array."""
    state_leaves = tuple(jax.tree.leaves(state))
    if not all(isinstance(leaf, jax.Array) for leaf in state_leaves):
        return None
    rows = tuple(jax.device_get(jax.tree.leaves(
        (types.share, types.whole, typical))))
    return _ResidentTables(engine.closes_over, state_leaves, rows)


def _same_build(held, proof) -> bool:
    """Whether the build `proof` describes is the one the entry `held`
    came from; either may be None (no entry, nothing provable)."""
    return (
        held is not None and proof is not None
        and held.closes_over == proof.closes_over
        and len(held.state_leaves) == len(proof.state_leaves)
        and all(a is b for a, b in zip(held.state_leaves, proof.state_leaves))
        and len(held.rows) == len(proof.rows)
        and all(map(np.array_equal, held.rows, proof.rows))
    )


class _SweepTraces(NamedTuple):
    """What the host prep of a sweep's traces leaves (_sweep_traces)."""

    specs: object  # PodSpec on the device: [P2], or [B, P2] one trace a lane
    types: object  # PodTypes, type_id shaped like a specs leaf; None: sequential
    streams: list  # one (ev_kind, ev_pod) a trace: host i32, true length
    pods: list  # the traces' true pod counts
    p2: int  # the padded pod axis
    e2: int  # the padded event axis


def _distinct(objects):
    """(the distinct objects, first seen first; i32[len] index of each
    object among them), by identity: what several lanes of a sweep hand
    over is prepared once."""
    first = {}
    for obj in objects:
        first.setdefault(id(obj), obj)
    place = {key: i for i, key in enumerate(first)}
    return list(first.values()), np.asarray(
        [place[id(obj)] for obj in objects], np.int32)


def _to_lanes(rows, lane_trace):
    """Host rows of one length, one a DISTINCT trace, as ONE device array:
    the one shared row as it is (`lane_trace` None), else stacked a trace,
    moved once, and picked a lane on the device by the lane-to-trace index
    (i32[B]; no pick where every lane has a trace of its own)."""
    if lane_trace is None:
        return jnp.asarray(np.asarray(rows[0]))
    stacked = jnp.asarray(np.stack(rows))
    if np.array_equal(lane_trace, np.arange(len(rows))):
        return stacked
    return stacked[lane_trace]


def _sweep_traces(sim, traces, lane_trace, stable_k: bool, bucket: int,
                  min_pods: int, min_events: int) -> _SweepTraces:
    """The host prep of a sweep: specs and events of every DISTINCT trace
    (a shared trace is a list of one and `lane_trace` None; lanes that hand
    over one trace object twice index it twice), the padded sizes, the
    type table, the engine choice, then padding and ONE upload a leaf.

    `min_pods` / `min_events` are sticky shape floors: below the 512
    bucket the padding targets are size-adaptive, so a service batch of
    slightly smaller tuned traces would otherwise land on a SMALLER
    padded shape than its predecessor and recompile; the worker passes
    each job family's high-water marks (jaxpr identity includes the padded
    shapes).

    The type table is the dedup over the concatenated specs (np.unique's
    sorted order is canonical, so any set of traces that EQUALS the union,
    e.g. every tuned variant of one base trace, gets the table its
    standalone bucketed run builds); each trace's type_id is its segment.
    K, its length: a shared trace stabilizes K (pad_pod_types) only when
    its pod or event axis was padded, for an exact-size run's shapes are
    its own anyway, and both benchmark cells are such runs (K = 71 and
    61: padding them would change the compiled program). `stable_k`
    (per-lane traces, fault plans) always does: consecutive service
    batches and tuner generations whose traces differ slightly in K must
    hit one executable."""
    from tpusim.sim.table_engine import (
        build_pod_types,
        num_pod_types,
        pad_pod_types,
    )
    from tpusim.types import PodSpec

    cfg = sim.cfg
    specs_l, streams = [], []
    for pods in traces:
        specs = pods_to_specs(pods, sim.node_index, device=False)
        ev_kind, ev_pod = build_events(pods, cfg.use_timestamps)
        validate_events(ev_kind, ev_pod, int(specs.cpu.shape[0]))
        specs_l.append(specs)
        streams.append(
            (np.asarray(ev_kind, np.int32), np.asarray(ev_pod, np.int32)))
    pods_n = [int(s.cpu.shape[0]) for s in specs_l]
    p, e = max(pods_n), max(len(k) for k, _ in streams)
    p2, e2 = _bucket_sizes(
        max(p, int(min_pods)), max(e, int(min_events)), bucket)

    types = build_pod_types(PodSpec(*(
        np.concatenate([np.asarray(getattr(s, f)) for s in specs_l])
        for f in PodSpec._fields
    )))
    k = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
    use_table = (
        cfg.engine != "sequential"
        and k > 0
        and (
            cfg.engine == "table"
            or all(len(kinds) >= 2 * num_pod_types(s)
                   for s, (kinds, _) in zip(specs_l, streams))
        )
    )
    tids = [None] * len(specs_l)
    if use_table:
        offs = np.cumsum([0] + pods_n)
        tid_all = np.asarray(types.type_id)
        tids = [tid_all[a:z] for a, z in zip(offs, offs[1:])]
    padded = [
        _pad_specs(s, p2, tid, xp=np) for s, tid in zip(specs_l, tids)
    ]
    specs_d = PodSpec(*(
        _to_lanes([getattr(s, f) for s, _ in padded], lane_trace)
        for f in PodSpec._fields
    ))
    if not use_table:
        return _SweepTraces(specs_d, None, streams, pods_n, p2, e2)
    types = types._replace(
        type_id=_to_lanes([tid for _, tid in padded], lane_trace))
    if stable_k or p2 != p or e2 != e:
        types = pad_pod_types(types)
    return _SweepTraces(specs_d, types, streams, pods_n, p2, e2)


def _stack_typical(sets):
    """Typical-pod sets of any sizes as ONE TypicalPods with [F, T] leaves,
    T the largest size on its 16-row bucket: the smaller sets end in
    zero-frequency rows, which add nothing to any frag amount or score
    (pad_typical_pods)."""
    from tpusim.types import TypicalPods

    t = -(-max(int(tp.cpu.shape[0]) for tp in sets) // 16) * 16
    return TypicalPods(*map(
        jnp.stack, zip(*(pad_typical_pods(tp, t) for tp in sets))))


def _sweep_fault_plans(sim, fault_specs, streams, pods_n, p2: int,
                       bucket: int):
    """The fault plans of a sweep, one a lane, each compiled against the
    lane's OWN base stream (`streams[i]`, `pods_n[i]`: the lanes of a
    shared trace repeat one), and the operands they become: (plans, merged
    ev_kind [B, E_m], merged ev_pod, (FaultOps, initial FaultCarry),
    fault_frag). A None spec is a fault-free lane riding the faulted build:
    an empty schedule is an exact no-op on the fault lane (no merged steps
    beyond the base stream, the carry never moves).

    The merged streams are padded to a common bucketed length (inert
    EV_SKIP steps), the draw tables to a common row count, and the retry
    queue capacity is the lanes' max; all three, and the frag-delta
    capture (a static build flag, so part of the engine's cache key), are
    sticky a Simulator (`sim._chaos_hw`, like the worker's min_pods /
    min_events): they only ever grow, so consecutive fault waves on one
    sim share one executable (the zero-recompile pin), and a recover-free
    wave after a recovering one reuses the recovering build (the extra ys
    are zeros)."""
    from tpusim.sim import fault_lane
    from tpusim.sim.faults import FaultConfig

    nodes = len(sim.nodes)
    resolved = [
        (FaultConfig(), []) if spec is None
        else resolve_fault_spec(spec, nodes, len(kinds))
        for spec, (kinds, _) in zip(fault_specs, streams)
    ]
    hw_em, hw_rows, hw_cap, hw_rec = getattr(
        sim, "_chaos_hw", (0, 0, 0, False)
    )
    capacity = max(
        max(fault_lane.resolve_capacity(fcfg, n)
            for (fcfg, _), n in zip(resolved, pods_n)),
        hw_cap,
    )
    # identical lanes compile once: a tuning population rolls EVERY lane
    # under one schedule (learn.rollout), the service pads a short batch
    # with its tail job, and each compile walks the merged stream and
    # pre-draws the victim tables. The key is the schedule and the stream
    # it is merged into
    plan_cache: dict = {}
    plans = []
    for (fcfg, events), (kinds, pods) in zip(resolved, streams):
        key = (repr(fcfg), tuple(events), kinds.tobytes(), pods.tobytes())
        if key not in plan_cache:
            plan_cache[key] = fault_lane.compile_fault_plan(
                kinds, pods, events, fcfg, nodes, p2, capacity=capacity,
            )
        plans.append(plan_cache[key])
    (kinds, idxs, poss, args, auxs, draws, params, capacity, has_rec) = (
        fault_lane.pad_fault_plans(
            plans, bucket=bucket, min_stream=hw_em, min_rows=hw_rows,
        )
    )
    has_rec = bool(has_rec or hw_rec)
    sim._chaos_hw = (
        int(kinds.shape[1]), int(draws.shape[1]), capacity, has_rec)
    state = sim.init_state
    ops = fault_lane.FaultOps(
        pos=jnp.asarray(poss), arg=jnp.asarray(args),
        aux=jnp.asarray(auxs), draws=jnp.asarray(draws),
        params=jnp.asarray(params), gcnt=jnp.asarray(state.gpu_cnt),
    )
    fc0 = fault_lane.init_fault_carry(p2, state.num_nodes, capacity)
    return plans, jnp.asarray(kinds), jnp.asarray(idxs), (ops, fc0), has_rec


def _sweep_replay(sim, table: bool, fault_frag: Optional[bool]):
    """The replayer whose weight-operand `.engine` a sweep vmaps: the
    Simulator's own but for two builds. With fault plans (`fault_frag` not
    None) it is the in-scan fault plane's. And the in-scan heartbeat cond
    of the table engine doesn't survive vmap (a batched predicate executes
    both branches, firing the host tick callback every event per lane), so
    a heartbeat config replays on the heartbeat-free build of its family."""
    from tpusim.sim.engine import make_replay
    from tpusim.sim.table_engine import make_table_replay

    cfg = sim.cfg
    build = (
        functools.partial(make_table_replay, block_size=cfg.block_size)
        if table else make_replay
    )
    if fault_frag is not None:
        return build(
            sim._policy_fns, gpu_sel=cfg.gpu_sel_method, report=False,
            faults=True, fault_frag=fault_frag,
        )
    if table and cfg.heartbeat_every:
        sim.log.info(
            "[Sweep] in-scan heartbeat has no batched form; "
            "disabled for the sweep replay"
        )
        return build(
            sim._policy_fns, gpu_sel=cfg.gpu_sel_method, report=False)
    return sim._table_fn if table else sim.replay_fn


def _slice_fault_lanes(out, amounts, watts, w, seeds, pods_n, plans,
                       e_m) -> List[SweepLane]:
    """The lanes of a fetched sweep with fault plans, whose merged streams
    were padded to `e_m` steps: each SweepLane with the DisruptionMetrics
    of its schedule, bit-identical to the standalone run_with_faults run
    (tests/test_fault_lane.py). The telemetry is assembled a lane first
    (the retry attempts that ran decide a lane's padding skips), then the
    lanes are cut as every sweep's are."""
    from tpusim.sim import fault_lane

    assembled = [
        fault_lane.assemble_disruption(
            plan,
            jax.tree.map(lambda a: a[i], out.fault_ys),
            jax.tree.map(lambda a: a[i], out.fault_carry),
            out.state.gpu_cnt,  # the cluster's, as fetched: [N]
        )
        for i, plan in enumerate(plans)
    ]
    events_n = [plan.num_events for plan in plans]
    lanes = _slice_sweep_lanes(
        out, amounts, watts, w, seeds, pods_n, events_n,
        [e_m - e - attempts
         for e, (_, _, attempts) in zip(events_n, assembled)],
    )
    for lane, p, (dm, dead, attempts_run) in zip(lanes, pods_n, assembled):
        lane.disruption = dm
        lane.events += attempts_run
        # the scan's steps are the MERGED stream's here (fault transitions
        # and retry slots among the base events): fault_ys is that record
        lane.event_node = lane.event_dev = None
        # dead pods are terminal max-retries-exceeded: the standalone
        # path's unscheduled accounting includes them
        lane.unscheduled = int(
            ((lane.placed_node < 0) & (lane.ever_failed | dead[:p])).sum()
        )
    return lanes


def schedule_pods_sweep(
    sim: "Simulator", pods, weights, seeds=None, bucket: int = 512, *,
    lane_pods=None, lane_typical=None, fault_specs=None, min_pods: int = 0,
    min_events: int = 0,
) -> List[SweepLane]:
    """Evaluate B what-if configurations in ONE vmapped replay: `weights`
    is a [B, num_pol] i32 matrix (one row per config, columns in
    cfg.policies order), `seeds` an optional length-B list of per-config
    seeds (default: cfg.seed for every lane; a lane's seed drives its PRNG
    key AND its tie-break permutation, exactly like a standalone run's
    cfg.seed). Each lane's placements/counters/metrics are bit-identical
    to a standalone run with that weight vector in the config — same
    kernels, same key splits, vmapped — and the whole batch shares one
    compiled scan and one (weight-independent) set of score tables, which
    stays on the device for the Simulator's next sweep: a call builds it
    only where the cluster's initial state, the distinct type set, the
    typical pods or the scoring kernels are not the last call's
    (Simulator._sweep_tables; SweepRecord.tables_reused). Engine
    selection is run_events' rule, a trace: the table engine unless forced
    sequential or some trace is too small to amortize the table init
    (_sweep_traces); pallas has no batched form; extenders / mesh /
    decision-recording / series configs are rejected.

    What else a lane carries is data, and the one path reads it off its
    operands (_sweep_engine):

    `pods` is the trace every lane replays. `lane_pods` instead (pods
    None) gives lane i its OWN workload (tuned variants of one cluster's
    trace — the tune factor as an operand): specs, type_id and event
    streams padded to common buckets and stacked a lane, while the cluster
    state, the DISTINCT type set (one dedup over the lanes' concatenated
    specs, each lane's type_id its segment), the typical pods and the
    once-built score tables still broadcast. Host prep goes with the
    DISTINCT trace objects: lanes that hand over one list twice have it
    spec'd, padded and moved once, and a lane-to-trace index carries the
    rest (SweepRecord.traces). Every lane shares the Simulator's cluster and
    policy family (the service's batching rule — jaxpr identity);
    `min_pods` / `min_events` are the service's sticky shape floors
    (_sweep_traces).

    `lane_typical` gives lane i the typical pods it is scored and
    frag-reported against (a TypicalPods, e.g. the `.typical` of a
    Simulator built from the lane's own pod list; default: the
    Simulator's for every lane): lanes of DIFFERENT workload families of
    one cluster in one sweep. The distinct objects are the sweep's sets:
    F of them stack to one [F, T] operand (_stack_typical), F table sets
    are built from the one initial state and union type set and kept on
    the device together (Simulator._sweep_tables), each lane's carry
    starts from its family's, and the frag post-pass reads its family's
    rows (SweepRecord.typical_sets). A lane equals the standalone run of a
    Simulator holding ITS typical pods.

    `fault_specs`: a length-B list of fault schedules — FaultConfig /
    (FaultConfig, events) per resolve_fault_spec, or None for a fault-free
    lane. A schedule is five i32 streams, a draw table and a param vector:
    each is compiled against its lane's own base stream, the merged
    streams replace the base event operands, and every SweepLane carries
    its DisruptionMetrics, bit-identical to the standalone run_with_faults
    run with that schedule (given the sweep's unified retry-queue
    capacity: an explicit queue_capacity pins it). So mixed
    fault/tune/weight jobs share one compiled scan
    (_sweep_fault_plans)."""
    cfg, obs = sim.cfg, sim.obs
    _reject_unsweepable(cfg)
    w, b, seeds = _check_sweep_grid(cfg, weights, seeds)
    per_lane, faulted = lane_pods is not None, fault_specs is not None
    if per_lane == (pods is not None):
        raise ValueError(
            "a sweep replays ONE shared trace (pods) or one trace a lane "
            "(lane_pods, pods None)"
        )
    if per_lane and len(lane_pods) != b:
        raise ValueError(
            f"lane_pods has {len(lane_pods)} traces for {b} weight rows "
            "(want one workload per config lane; Simulator.run_sweep "
            "prepares one per tuning ratio)"
        )
    if faulted:
        if len(fault_specs) != b:
            raise ValueError(
                f"fault_specs has {len(fault_specs)} entries for {b} "
                "weight rows (want one fault schedule — or None — per "
                "lane)"
            )
        if cfg.use_timestamps:
            raise ValueError(
                "the chaos sweep replays creation-ordered traces "
                "(use_timestamps=False)"
            )
    if lane_typical is not None and len(lane_typical) != b:
        raise ValueError(
            f"lane_typical has {len(lane_typical)} typical-pod sets for "
            f"{b} weight rows (want one TypicalPods per config lane)"
        )
    if sim.typical is None and lane_typical is None:
        sim.set_typical_pods()  # a span of its own, before the sweep's
    # lane -> distinct trace; None: every lane replays the one shared trace
    traces, lane_trace = _distinct(lane_pods) if per_lane else ([pods], None)
    trace_of = np.zeros(b, np.int32) if lane_trace is None else lane_trace
    state = sim.init_state

    # eight flat, back-to-back spans under one sweep record: specs,
    # lane_keys, lane_ranks (mark: stacked), init_tables, scan,
    # frag_postpass (mark: gathered), fetch (marks: ready, copied),
    # slice_lanes; the record derives the host's lead and tail from them.
    # With the per-event report on, event_metrics is a ninth, before fetch
    with obs.sweep(lanes=b) as sweep:
        sweep.weight_rows = len(np.unique(w, axis=0))
        sweep.normalized_policies = sum(
            fn.normalize in ("minmax", "pwr") for fn, _ in sim._policy_fns)
        sweep.affinity_readers = affinity_readers(sim._policy_fns)
        with obs.span("specs") as h:
            tr = _sweep_traces(
                sim, traces, lane_trace, per_lane or faulted, bucket,
                min_pods, min_events,
            )
            sweep.traces = len(traces)
            # the typical pods the lanes are scored against: the
            # Simulator's one set, or a set a family and each lane's index
            typical, lane_set = sim.typical, ()
            if lane_typical is not None:
                sets, index = _distinct(lane_typical)
                typical = _stack_typical(sets)
                lane_set = (jnp.asarray(index),)
                sweep.typical_sets = len(sets)
            plans, fault_args, fault_frag = None, (), None
            if faulted:
                plans, ev_kind, ev_pod, fault_args, fault_frag = (
                    _sweep_fault_plans(
                        sim, fault_specs,
                        [tr.streams[t] for t in trace_of],
                        [tr.pods[t] for t in trace_of], tr.p2, bucket,
                    )
                )
            else:
                padded = [
                    _pad_events(kinds, idx, tr.e2, xp=np)
                    for kinds, idx in tr.streams
                ]
                ev_kind = _to_lanes(
                    [kinds for kinds, _ in padded], lane_trace)
                ev_pod = _to_lanes([idx for _, idx in padded], lane_trace)
            obs.settle(h, tr.specs, ev_kind, ev_pod, tr.types, fault_args,
                       typical)
        # what a lane replays of its trace, less padding, merged fault
        # steps and retries
        lane_events = [len(tr.streams[t][0]) for t in trace_of]
        sweep.events, true_events = max(lane_events), sum(lane_events)
        deletes = [int((kinds == EV_DELETE).sum()) for kinds, _ in tr.streams]
        sweep.delete_events = sum(deletes[t] for t in trace_of)
        steps = int(ev_kind.shape[-1])  # of the scan, padding included
        with obs.span("lane_keys") as h:
            keys = _lane_keys(seeds)
            obs.settle(h, keys)
        with obs.span("lane_ranks") as h:
            ranks = _lane_ranks(len(sim.nodes), seeds, marks=h)
            weights_d = jnp.asarray(w)
            obs.settle(h, ranks, weights_d)

        use_table = tr.types is not None
        replay_fn = _sweep_replay(sim, use_table, fault_frag)
        args = (ev_kind, ev_pod, typical, keys, weights_d, ranks)
        if use_table:
            # ONE table set for the lanes of a typical-pod set: the tables
            # hold raw per-policy scores (weight-independent) and
            # init_tables reads only the DISTINCT type set (never
            # type_id), so those lanes share them bit-identically, and so
            # does the next sweep of an unchanged cluster and type set:
            # built at most once a call, and not at all where the last
            # call's still hold
            from tpusim.sim.table_engine import sub_requests

            sweep.sub_requests = sub_requests(sim._policy_fns, tr.types)
            sweep.affinity_deferred = int(
                replay_fn.engine.affinity_deferred(len(sim.nodes), tr.types))
            sweep.affinity_nodes_minor = int(
                replay_fn.engine.affinity_nodes_minor(
                    len(sim.nodes), tr.types))
            key0 = jax.random.PRNGKey(seeds[0])
            tables, sweep.tables_reused = sim._sweep_tables(
                replay_fn.engine, state, tr.types, typical, key0)
            engine = replay_fn.engine.replay
            args = (state, tr.specs, tr.types) + args + (tables,)
        else:
            engine = replay_fn.engine
            args = (state, tr.specs) + args
        args += fault_args + lane_set
        # the post-pass re-reads the event streams; a fault sweep has none
        # (its per-event rows would index the merged stream)
        report = cfg.report_per_event and not faulted
        fn = _sweep_engine(engine, args, keep_streams=report)
        # the wrapper dispatched, for the executables census of the
        # service, the tuner and the gate (fn._cache_size())
        sim._last_sweep_fn = fn
        what = (
            ("lane chaos x trace" if per_lane else "lane chaos") if faulted
            else ("trace vmap" if per_lane else "config vmap")
        )
        sim._last_engine = (
            f"{'table' if use_table else 'sequential'} ({b}-{what} sweep)"
        )
        (out, sweep.lane_writes, sweep.dense_accesses,
         sweep.table_pass_events) = sim._dispatch_span(
            lambda: _dispatch_counting_lane_sites(fn, b, *args),
            engine=sim._last_engine, events=true_events,
        )
        sweep.engine = sim._last_engine
        obs.note_scan(sim._last_engine, counters=None, events=true_events)
        sim.log.info(
            f"[Engine] sweep of {b} lanes x <= {sweep.events} events "
            f"(stream {steps}) ran on: {sim._last_engine}"
        )
        with obs.span("frag_postpass") as h:
            # each lane against the typical pods it was scored with: its
            # family's rows of a stacked set (a few KB a lane)
            tp_ax = 0 if lane_set else None
            if lane_set:
                typical = jax.tree.map(lambda a: a[lane_set[0]], typical)
            h.mark("gathered", then="program")
            # per-lane frag and watts of the final states in one vmapped
            # call (the reductions cluster_analysis and the power report
            # make), before the single fetch. The jit wraps a new function
            # object in every call, so dispatch here is a trace, a lowering
            # and a compile or a cache load.
            amounts, watts = jax.jit(
                jax.vmap(_lane_postpass, in_axes=(_LANE_STATE_AXES, tp_ax))
            )(out.state, typical)
            obs.settle(h, amounts, watts)
        if report:
            # the per-event series of every lane, rebuilt from its
            # telemetry: a span of its own, so a blocked wave times the
            # report program apart from the lane post-pass
            with obs.span("event_metrics", events=true_events) as h:
                out = out._replace(
                    metrics=_sweep_metrics_fn(_lane_axis(ev_kind, 1), tp_ax)(
                        state, tr.specs, ev_kind, ev_pod,
                        out.event_node, out.event_dev, typical,
                    )
                )
                obs.settle(h, out.metrics)
        with obs.span("fetch", events=true_events) as h:
            out, amounts, watts = device_fetch((out, amounts, watts), marks=h)
            sweep.fetch_bytes = h.meta.get("bytes", 0)
            sweep.fetch_pieces = h.meta.get("fetch_pieces", 0)
            sweep.landing_reused = h.meta.get("landing_reused", 0)
            # of them, what the lanes share: the capacity leaves, once
            h.note(shared_bytes=sum(
                getattr(out.state, f).nbytes for f in CAPACITY_LEAVES))
            if out.metrics is not None:
                sweep.series_bytes = sum(a.nbytes for a in out.metrics)
                h.note(series_bytes=sweep.series_bytes)

        with obs.span("slice_lanes") as h:
            pods_n = [tr.pods[t] for t in trace_of]
            if faulted:
                lanes = _slice_fault_lanes(
                    out, amounts, watts, w, seeds, pods_n, plans, steps)
            else:
                lanes = _slice_sweep_lanes(
                    out, amounts, watts, w, seeds, pods_n, lane_events,
                    [steps - e for e in lane_events],
                )
            sweep.rejected_creates = sum(lane.failed for lane in lanes)
            h.note(rejected_creates=sweep.rejected_creates)
            return lanes


def run_batch(sims: Sequence["Simulator"]) -> List[SimulateResult]:
    """run() for a seed group, S experiments of one configuration whose
    seeds differ (shuffle order, tuning, tie-break permutation): per-sim
    host prep and reporting around ONE schedule_pods_sweep on the lead, a
    trace and a seed a lane. Every member ends as its own run() would, in
    placements, device masks, final state, unscheduled list, creation
    ranks and log (tests/test_batch.py)."""
    from tpusim.sim.engine import ReplayResult

    def shared(s):
        # what the one compiled replay, its tables and its typical pods are
        # made from; every lane is scored against the lead's typical pods,
        # which is only sound when the seeds share the workload the
        # distribution derives from
        c = s.cfg
        return (
            c.policies, c.gpu_sel_method, c.dim_ext_method, c.norm_method,
            c.report_per_event, c.use_timestamps, c.engine, c.block_size,
            c.typical_pods, s.nodes, s.workload_pods,
        )

    lead = sims[0]
    for s in sims:
        # any member, not the lead alone: the sweep replays on the lead's
        # engine, so a member that records would get None for its stream
        _reject_unsweepable(s.cfg)
        if shared(s) != shared(lead):
            raise ValueError(
                "run_batch requires same-config sims (policies, "
                "gpu/dim/norm methods, report flag, typical-pod knobs, the "
                "node cluster, and the workload may not differ across the "
                "group)"
            )
    pods_list = []
    for sim in sims:
        sim._reset_run_state()
        if sim is lead:
            sim.set_typical_pods()
        else:
            sim.adopt_typical_pods(lead)
        sim.set_skyline_pods()
        pods_list.append(sim.prepare_pods())
        sim.log.info(
            f"Number of original workload pods: {len(sim.workload_pods)}"
        )
    t0 = time.perf_counter()
    # the sweep names itself in its Simulator's log; a member's log is a
    # standalone run's, so the lead lends the call another
    keep, lead.log = lead.log, LogSink()
    try:
        lanes = schedule_pods_sweep(
            lead, None,
            [[w for _, w in lead.cfg.policies]] * len(sims),
            [s.cfg.seed for s in sims], lane_pods=pods_list,
        )
    finally:
        lead.log = keep
    wall = (time.perf_counter() - t0) / len(sims)
    # the logged name is the engine SEMANTICS, a standalone run's line
    engine_name = lead._last_engine.split()[0]
    results = []
    for sim, pods, lane in zip(sims, pods_list, lanes):
        ev_kind, ev_pod = build_events(pods, sim.cfg.use_timestamps)
        sim._last_engine = lead._last_engine
        sim.log.info(
            f"[Engine] replay of {len(ev_kind)} events ran on: {engine_name}"
        )
        # a lane comes cut to its own pods and events, its counters
        # pad-corrected, as VIEWS of the sweep's one buffer with capacity
        # leaves all lanes share: what a Simulator keeps, and later stages
        # write to (deschedule, inflation, schedule_additional), is its own
        out = ReplayResult(
            state=jax.tree.map(np.array, lane.state),
            placed_node=lane.placed_node.copy(),
            dev_mask=lane.dev_mask.copy(),
            ever_failed=lane.ever_failed.copy(),
            metrics=lane.metrics,
            event_node=lane.event_node,
            event_dev=lane.event_dev,
            counters=lane.counters,
        )
        out, events, unscheduled, rank = sim._finish_replay(
            out, pods, ev_kind, ev_pod, sim.init_state
        )
        results.append(
            sim._record_result(out, pods, events, unscheduled, rank, wall)
        )
        sim.report_failed([u.pod for u in unscheduled])
        sim.cluster_analysis("InitSchedule", amounts=lane.frag_amounts)
    return results


def format_chaos_table(lanes: Sequence[SweepLane], policies) -> str:
    """Per-lane disruption frontier of a chaos sweep — the `tpusim apply
    --sweep-faults` output: placements plus the DisruptionMetrics
    headline numbers per fault schedule."""
    names = [n for n, _ in policies]
    head = (
        f"{'lane':>4} {'weights(' + ','.join(names) + ')':<28} "
        f"{'seed':>6} {'placed':>7} {'evicted':>8} {'resched':>8} "
        f"{'dead':>5} {'fails':>6} {'lat_mean':>9} {'gpu_alloc%':>10} "
        f"{'frag_gpu_milli':>15}"
    )
    rows = [head, "-" * len(head)]
    for i, ln in enumerate(lanes):
        dm = ln.disruption
        wstr = ",".join(str(int(x)) for x in ln.weights)
        rows.append(
            f"{i:>4} {wstr:<28} {ln.seed:>6} {ln.placed:>7} "
            f"{dm.evicted_pods:>8} {dm.rescheduled_pods:>8} "
            f"{dm.unscheduled_after_retries:>5} {dm.node_failures:>6} "
            f"{dm.mean_reschedule_latency():>9.2f} "
            f"{ln.gpu_alloc_pct:>10.2f} {ln.frag_gpu_milli:>15.0f}"
        )
    return "\n".join(rows)


def format_sweep_table(lanes: Sequence[SweepLane], policies) -> str:
    """Per-config summary table of a sweep — the `tpusim apply
    --sweep-weights` output: one row per lane with its weight vector,
    seed, placed/failed counts, GPU allocation, frag gpu-milli and the
    watts of its final cluster (the reference's `[Power]; cluster` value:
    power_cpu_w + power_gpu_w)."""
    names = [n for n, _ in policies]
    head = (
        f"{'cfg':>4} {'weights(' + ','.join(names) + ')':<32} "
        f"{'seed':>6} {'placed':>7} {'failed':>7} "
        f"{'gpu_alloc%':>10} {'frag_gpu_milli':>15} {'power_w':>10}"
    )
    rows = [head, "-" * len(head)]
    for i, ln in enumerate(lanes):
        wstr = ",".join(str(int(x)) for x in ln.weights)
        rows.append(
            f"{i:>4} {wstr:<32} {ln.seed:>6} {ln.placed:>7} "
            f"{ln.failed:>7} {ln.gpu_alloc_pct:>10.2f} "
            f"{ln.frag_gpu_milli:>15.0f} "
            f"{ln.power_cpu_w + ln.power_gpu_w:>10.0f}"
        )
    return "\n".join(rows)
