"""Worker: one batch-serving loop per process (ISSUE 7, fleet-grown in
ISSUE 12).

The worker drains the JobQueue batch by batch and dispatches each batch
through the vmapped sweep, one trace a lane (driver.schedule_pods_sweep)
— so a whole batch of what-if jobs costs one compiled scan, and across
batches the one-jaxpr-per-family contract holds: per-family Simulators
are cached (sharing the weight-operand engines, the content-keyed table
cache entry, and the persistent compile cache), batches are padded to a
FIXED lane width (a 3-job batch repeats its tail job into the dead
lanes — vmap's axis size is jaxpr structure), and per-family pod/event
shape high-water marks are sticky (the driver's min_pods/min_events
floors), so consecutive batches differing only in weights/seeds/tune
factors — and, since ISSUE 12, fault schedules: the chaos dispatch
folded into the one path — reuse ONE compiled executable —
`jit._cache_size()` stable, the acceptance criterion.

Every batch runs under the lease protocol (ISSUE 12): run_batch stakes
signed lease files before dispatching, a LeaseKeeper renews them on
heartbeat ticks plus a fallback timer, and completion releases them —
so a `kill -9`'d worker's batch is steal-eligible after one lease. The
same Worker class serves both deployments: the single in-process thread
of PR 7 (claiming from the shared queue directly) and the fleet worker
process (svc.fleet.run_worker, claiming over HTTP with `renew_cb`
pointed at the coordinator).

Results are summarized host-side (placements, counters, gpu_alloc,
frag, a placements digest for cheap bit-identity checks), persisted as
digest-signed JSONL (svc.jobs.write_result), and marked on the queue.
A batch that raises marks its jobs failed and the worker keeps serving
— one poisoned job family must not take the service down.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from tpusim.svc import jobs as svc_jobs
from tpusim.svc import leases as svc_leases
from tpusim.svc.batcher import Job, JobQueue


class LeaseKeeper:
    """Renews a batch's leases while it is in flight (ISSUE 12): a
    fallback timer fires every lease_s/3, and heartbeat ticks from the
    scan poke an immediate renewal (the ISSUE's renew-on-heartbeat —
    the timer covers vmapped sweeps, whose builds strip the in-scan
    heartbeat). Each renewal rewrites the signed lease files AND calls
    `renew_cb(digests)` — the queue update in-process, an HTTP POST on
    a fleet worker. A renewal learning its leases were LOST (stolen
    after a stall) just logs: finishing anyway is harmless — the
    completion dedups."""

    def __init__(self, artifact_dir: str, worker_id: str, lease_s: float,
                 members: Sequence[str], renew_cb=None, out=None,
                 stake_cb=None, release_cb=None):
        self.artifact_dir = artifact_dir
        self.worker_id = worker_id
        self.lease_s = float(lease_s)
        self.members = [str(m) for m in members]
        self.renew_cb = renew_cb
        # remote-mode callbacks (ISSUE 13): a no-shared-fs worker cannot
        # write lease FILES into the coordinator's artifact dir, so
        # stake_cb(members)/release_cb(members) POST /leases instead and
        # the COORDINATOR writes/deletes its own signed files — the
        # on-disk mirror (adoption, reaping) is unchanged. None = the
        # shared-fs local file writes.
        self.stake_cb = stake_cb
        self.release_cb = release_cb
        self.out = out
        self._stop = threading.Event()
        self._poke = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.renewals = 0

    def renew_now(self) -> None:
        # ask the authority FIRST: a digest the coordinator reports lost
        # (stolen after a stall) now belongs to a thief whose lease file
        # this keeper must never overwrite again — nor delete at stop()
        # — so lost members leave the set before any file write
        if self.renew_cb is not None:
            try:
                lost = set(self.renew_cb(self.members))
            except Exception:
                lost = set()  # coordinator unreachable: keep staking;
                # it will steal if we really stall
            if lost:
                self.members = [m for m in self.members if m not in lost]
                if self.out is not None:
                    print(
                        f"[worker {self.worker_id}] lease(s) lost to a "
                        f"steal: "
                        f"{', '.join(str(x)[:12] for x in sorted(lost))}"
                        " — finishing anyway (duplicate completion "
                        "dedups)",
                        file=self.out,
                    )
        if self.stake_cb is not None:
            try:
                self.stake_cb(self.members)
            except Exception:
                pass  # coordinator unreachable mid-renewal: same story
                # as a lost renew_cb — keep computing, it will steal if
                # we really stall, and completion dedups
        else:
            deadline = time.time() + self.lease_s
            for d in self.members:
                svc_leases.write_lease(
                    self.artifact_dir, d, self.worker_id, os.getpid(),
                    deadline, self.members,
                )
        self.renewals += 1

    def on_heartbeat(self, _info) -> None:
        """obs.heartbeat listener: a live scan tick proves the worker is
        healthy — renew without waiting for the timer."""
        self._poke.set()

    def _loop(self) -> None:
        period = max(self.lease_s / 3.0, 0.05)
        last = time.time()
        while not self._stop.is_set():
            if self._poke.wait(period):
                self._poke.clear()
            if self._stop.is_set():
                return
            # heartbeat ticks can arrive many times a second — renewing
            # more often than period/3 is pure churn
            if time.time() - last >= period / 3.0:
                self.renew_now()
                last = time.time()

    def start(self) -> "LeaseKeeper":
        from tpusim.obs import heartbeat

        self.renew_now()  # the initial claim stake
        heartbeat.add_listener(self.on_heartbeat)
        self._thread = threading.Thread(
            target=self._loop, name="tpusim-lease-keeper", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, release: bool = True) -> None:
        from tpusim.obs import heartbeat

        self._stop.set()
        self._poke.set()
        heartbeat.remove_listener(self.on_heartbeat)
        if self._thread is not None:
            self._thread.join(2.0)
            self._thread = None
        if release:
            if self.release_cb is not None:
                try:
                    self.release_cb(self.members)
                except Exception:
                    pass  # the coordinator's reaper cleans expired
                    # files anyway; a lost release is a timeout, not
                    # a leak
            else:
                for d in self.members:
                    svc_leases.delete_lease(self.artifact_dir, d)


@dataclass
class TraceRef:
    """One hosted trace: the cluster + workload every job of this ref
    replays, plus their content digest (part of every job digest). The
    CSV source paths ride along when load_trace built it — the fleet
    register handshake (ISSUE 12) hands them to joining workers, which
    re-load and digest-verify the trace themselves."""

    name: str
    nodes: list
    pods: list
    digest: str
    nodes_csv: str = ""
    pods_csv: str = ""
    max_pods: int = 0
    # per-FILE integrity (ISSUE 13): sha256 + size of the raw CSV bytes,
    # so a no-shared-fs worker can verify a (possibly resumed) download
    # before parsing, and resume partial transfers against a known size
    nodes_sha256: str = ""
    pods_sha256: str = ""
    nodes_bytes: int = 0
    pods_bytes: int = 0


def load_trace(name: str, nodes_csv: str, pods_csv: str,
               max_pods: int = 0) -> TraceRef:
    """Load a hosted trace from node/pod CSVs (`tpusim serve --jobs
    --nodes ... --pods ...`); max_pods > 0 truncates the workload (the
    smoke/prefix knob)."""
    from tpusim.io.storage import file_sha256
    from tpusim.io.trace import load_node_csv, load_pod_csv

    nodes = load_node_csv(nodes_csv)
    pods = load_pod_csv(pods_csv)
    if max_pods > 0:
        pods = pods[:max_pods]
    return TraceRef(
        name=name, nodes=nodes, pods=pods,
        digest=svc_jobs.trace_digest(nodes, pods),
        nodes_csv=os.path.abspath(nodes_csv),
        pods_csv=os.path.abspath(pods_csv),
        max_pods=int(max_pods),
        nodes_sha256=file_sha256(nodes_csv),
        pods_sha256=file_sha256(pods_csv),
        nodes_bytes=os.path.getsize(nodes_csv),
        pods_bytes=os.path.getsize(pods_csv),
    )


def local_caps() -> dict:
    """The capability tags this process declares in the fleet register
    handshake (ISSUE 17): accelerator backend + local device count as
    JAX reports them (a JAX that cannot start its backend raises here —
    a worker that cannot reach its device must not register as a
    one-device CPU worker), approximate host memory, fault-lane support
    (every engine in this tree carries the chaos dispatch, so True unless
    an operator override says otherwise), and max_nodes (0 = no cluster-size ceiling). The coordinator routes
    claims against these tags (JobQueue.eligible)."""
    import jax

    backend = str(jax.default_backend())
    devices = int(jax.local_device_count())
    mem = 0
    try:
        mem = int(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))
    except (ValueError, OSError, AttributeError):
        pass
    return {
        "backend": backend,
        "devices": devices,
        "memory_bytes": mem,
        "fault_lanes": True,
        "max_nodes": 0,
    }


def summarize_lane(lane, job: Job) -> dict:
    """SweepLane -> the persisted/HTTP result document: the shared
    per-lane term vocabulary (learn.objective.lane_terms — ONE code
    path, so a remote tuning client's terms_from_result reads back
    exactly what a local lane yields, the ISSUE 9 bit-identity
    contract) plus the job's identity fields and the full placements
    (i32 node per pod; -1 = unplaced; the terms' sha256 over
    placed_node+dev_mask makes bit-identity against a standalone run
    one string compare)."""
    from tpusim.learn.objective import lane_terms
    from tpusim.obs.counters import COUNTER_FIELDS

    out = lane_terms(lane)
    out.update({
        "job": job.digest,
        "trace": job.spec.trace,
        "policies": [list(p) for p in job.spec.policies],
        "weights": list(job.spec.weights),
        "seed": job.spec.seed,
        "tune": job.spec.tune,
        "placed_node": np.asarray(lane.placed_node, np.int32).tolist(),
    })
    if lane.counters is not None:
        out["counters"] = {
            f: int(c) for f, c in zip(COUNTER_FIELDS, lane.counters)
        }
    if lane.disruption is not None:
        # chaos lanes (ISSUE 10): the full DisruptionMetrics scalar
        # summary rides the result document beside the objective terms
        out["disruption"] = lane.disruption.as_dict()
    return out


class Worker:
    """The single batch-serving thread (see module docstring)."""

    def __init__(self, queue: JobQueue, traces: Dict[str, TraceRef],
                 artifact_dir: str, bucket: int = 512, monitor=None,
                 table_cache_dir: str = "",
                 linger_s: float = 0.05, worker_id: str = "",
                 lease_files: bool = True):
        self.queue = queue
        self.traces = dict(traces)
        self.artifact_dir = artifact_dir
        self.bucket = int(bucket)
        self.monitor = monitor  # MonitorServer (per-job /progress) or None
        self.table_cache_dir = table_cache_dir
        self.linger_s = float(linger_s)  # batching window (JobQueue.next_batch)
        # fleet identity (ISSUE 12): the id the lease files and the
        # /queue per-worker rows carry; in-process workers default to a
        # pid-scoped local id
        self.worker_id = str(worker_id) or f"local-{os.getpid()}"
        # lease files are the cross-process protocol; tests driving
        # run_batch synchronously can switch them off
        self.lease_files = bool(lease_files)
        self._sims: dict = {}  # family_key -> Simulator
        self._shape_hw: dict = {}  # family_key -> (max pods, max events)
        self._sweep_fns: set = set()  # jitted sweep wrappers dispatched
        self._waves: dict = {}  # family_key -> svc.waves.ForkWave
        self.batches_run = 0
        self.last_dispatch_s = 0.0  # wall of the newest run_batch
        self.first_dispatch_s = 0.0  # wall of the FIRST (compile) batch
        # lease renewal sink: digests -> lost list. In-process workers
        # renew the shared queue directly; a fleet worker (svc.fleet)
        # swaps in the coordinator's POST /workers/renew.
        self.renew_cb = lambda ds: self.queue.renew(self.worker_id, ds)[1]
        # remote-mode lease plane (ISSUE 13): svc.fleet.run_worker wires
        # these at POST /leases when the worker shares no filesystem
        # with the coordinator; None keeps the local signed-file writes
        self.lease_stake_cb = None
        self.lease_release_cb = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---- lifecycle ----

    def start(self) -> "Worker":
        self._thread = threading.Thread(
            target=self._loop, name="tpusim-svc-worker", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            # reap orphans first: with several in-process workers on one
            # queue, any live worker's idle pass reclaims expired leases
            self.queue.steal_expired()
            batch = self.queue.claim_batch(
                self.worker_id, timeout=0.2, linger_s=self.linger_s
            )
            if batch:
                self.run_batch(batch)

    # ---- per-family simulator cache ----

    def _sim_for(self, job: Job):
        """The family's shared Simulator: one weight-operand engine, one
        table-cache entry, one typical-pod distribution for every tenant
        of the family."""
        from tpusim.sim.driver import Simulator, SimulatorConfig

        key = job.spec.family_key()
        sim = self._sims.get(key)
        if sim is None:
            trace = self.traces[job.spec.trace]
            cfg = SimulatorConfig(
                policies=job.spec.policies,
                gpu_sel_method=job.spec.gpu_sel,
                norm_method=job.spec.norm,
                dim_ext_method=job.spec.dim_ext,
                engine=job.spec.engine,
                report_per_event=False,
                shuffle_pod=False,
                seed=42,
                table_cache_dir=self.table_cache_dir,
            )
            sim = Simulator(trace.nodes, cfg)
            sim.set_workload_pods(trace.pods)
            sim.set_typical_pods()
            self._sims[key] = sim
        # a Simulator keeps its last sweep's score tables on the device
        # (Simulator._sweep_tables: 64 MB at 100,000 nodes), and _sims
        # grows with the families seen: only the family being served
        # keeps its set, so a worker pins one set, not one a family
        for other in self._sims.values():
            if other is not sim:
                other.drop_resident_tables()
        # tag scans with this worker's id (obs.heartbeat, ISSUE 12): a
        # fleet's /progress streams say WHICH worker is scanning
        sim._hb_worker = self.worker_id
        return sim

    # ---- the batch dispatch ----

    def run_batch(self, batch: List[Job]) -> None:
        """Serve one compatible batch, under the lease protocol
        (ISSUE 12): signed lease files are staked before dispatch,
        renewed while the scan runs (heartbeat ticks + the fallback
        timer), and released on completion — a `kill -9` mid-batch
        leaves expired leases any live worker can steal. Three routes
        (family keys keep them unmixed): base jobs advance their trace
        once through the chunked path and persist the checkpoint ladder
        + fork-index entry; fork/full jobs ride the family's continuous
        ForkWave (late arrivals JOIN it at chunk boundaries, so
        all_jobs can outgrow the claimed batch); everything else is the
        vmapped sweep. Public so smoke/tests can drive it
        synchronously."""
        self.queue.mark_running(batch)
        self._publish(batch, phase="running")
        members = [j.digest for j in batch]
        keeper = None
        if self.lease_files:
            keeper = LeaseKeeper(
                self.artifact_dir, self.worker_id, self.queue.lease_s,
                members, renew_cb=self.renew_cb,
                stake_cb=self.lease_stake_cb,
                release_cb=self.lease_release_cb,
            ).start()
        all_jobs = list(batch)  # grows when joiners enter a fork wave
        t0 = time.perf_counter()
        try:
            if batch[0].spec.base:
                for job in batch:
                    job.dispatched_unix = time.time()
                    self._run_base(job)
            elif batch[0].spec.fork:
                self._run_fork_wave(batch, keeper, all_jobs)
            else:
                now = time.time()
                for job in batch:
                    job.dispatched_unix = now
                lanes = self._dispatch(batch)
                for job, lane in zip(batch, lanes):
                    self._complete(job, lane)
        except Exception as err:  # poisoned family: fail the jobs, live on
            msg = f"{type(err).__name__}: {err}"
            undone = [j for j in all_jobs if j.status != "done"]
            for job in undone:
                self.queue.mark_failed(job, msg)
                # terminal: drop the persisted spec so restart recovery
                # does not re-run the poisoned batch forever
                svc_jobs.delete_job_spec(self.artifact_dir, job.digest)
            if keeper is not None:
                keeper.stop(release=True)
            self._publish(undone, phase="failed", error=msg)
            return
        self.last_dispatch_s = time.perf_counter() - t0
        if self.batches_run == 0:
            self.first_dispatch_s = self.last_dispatch_s
        if keeper is not None:
            keeper.stop(release=True)
        self.batches_run += 1
        self._publish(all_jobs, phase="done")

    def _complete(self, job: Job, lane, fork_meta: Optional[dict] = None,
                  base_meta: Optional[dict] = None) -> None:
        """One job's terminal bookkeeping: summarize, persist the signed
        result, mark done, drop the spec. Fork/base serving telemetry
        rides the result document (`result["fork"]` / `result["base_run"]`
        — what the latency gate and what-if clients read)."""
        result = summarize_lane(lane, job)
        if fork_meta is not None:
            result["fork"] = dict(fork_meta)
        if base_meta is not None:
            result["base_run"] = dict(base_meta)
        svc_jobs.write_result(self.artifact_dir, job.digest, result)
        self.queue.mark_done(job, result)
        # terminal: the signed result is the durable record now
        svc_jobs.delete_job_spec(self.artifact_dir, job.digest)

    def _dispatch(self, batch: List[Job]):
        """ONE dispatch path for fault-free AND fault batches (the
        ISSUE 12 fold): every batch rides schedule_pods_sweep with one
        tuned trace a lane (lane_pods), and
        a fault family simply adds per-lane fault schedules — compiled
        against each lane's OWN tuned stream — as operands. Mixed
        fault/tune/weight jobs of one family therefore share one
        compiled scan (the family key no longer pins a tune factor for
        fault jobs)."""
        from tpusim.sim.driver import schedule_pods_sweep

        sim = self._sim_for(batch[0])
        key = batch[0].spec.family_key()
        # tag the shared heartbeat stream with this batch's lead job so
        # /progress keeps per-job windows apart (obs.heartbeat, ISSUE 7
        # satellite); the vmapped sweep itself strips in-scan heartbeats,
        # but chunked/standalone replays of the same sim honor it
        sim._hb_job = batch[0].id

        pods_list = [
            sim.prepare_pods(
                tuning_ratio=j.spec.tune, tuning_seed=j.spec.tune_seed
            )
            for j in batch
        ]
        weights = [list(j.spec.weights) for j in batch]
        seeds = [j.spec.seed for j in batch]
        faulted = bool(batch[0].spec.fault)
        fault_specs = (
            [j.spec.fault_config() for j in batch] if faulted else None
        )
        # pad to the FIXED lane width by repeating the tail job: vmap's
        # axis size is jaxpr structure, so a short batch must not compile
        # its own executable; dead lanes are sliced off below. The tail's
        # PREPARED pods (and compiled fault plan, via the driver's plan
        # cache) are reused, not recomputed per dead lane.
        n = len(batch)
        while len(weights) < self.queue.lane_width:
            pods_list.append(pods_list[-1])
            weights.append(weights[-1])
            seeds.append(seeds[-1])
            if fault_specs is not None:
                fault_specs.append(fault_specs[-1])

        # sticky per-family shape floors (see module docstring): without
        # them a later batch of slightly smaller tuned traces would land
        # on a smaller padded shape and recompile. The event count is the
        # real build_events length under the family's event ordering
        # (the sweep builds the same streams right after — this extra
        # host-side O(P) pass per lane is noise next to the scan), not a
        # bound: an inflated floor would pad dead EV_SKIPs into every
        # future scan. Fault families additionally keep their merged-
        # stream/draw-table/capacity floors on the Simulator itself
        # (sim._chaos_hw, driver._sweep_fault_plans).
        from tpusim.io.trace import build_events

        p_max = max(len(p) for p in pods_list)
        e_max = max(
            len(build_events(p, sim.cfg.use_timestamps)[0])
            for p in pods_list
        )
        hw_p, hw_e = self._shape_hw.get(key, (0, 0))
        hw_p, hw_e = max(hw_p, p_max), max(hw_e, e_max)
        self._shape_hw[key] = (hw_p, hw_e)

        sim._reset_run_state()
        if sim.typical is None:
            sim.set_typical_pods()
        lanes = schedule_pods_sweep(
            sim, None, np.asarray(weights, np.int32), seeds=seeds,
            bucket=self.bucket, lane_pods=pods_list, min_pods=hw_p,
            min_events=hw_e, fault_specs=fault_specs,
        )[:n]
        # track the jitted sweep wrapper actually dispatched so /queue
        # can report the compiled-executable count (the PR 6
        # jit._cache_size() zero-recompile check, now a live metric):
        # every sweep leaves it on the sim
        self._sweep_fns.add(sim._last_sweep_fn)
        return lanes

    # ---- the warm-state serving plane (ISSUE 16) ----

    def _chunked_sim(self, job: Job):
        """The exact-replay Simulator a base run or fork wave executes
        on. Unlike the sweep cache (weights/seeds are vmap operands
        there), the chunked path bakes THIS job's weights into
        cfg.policies and THIS job's seed into cfg.seed — both feed the
        run digest its checkpoints are content-addressed under, which
        is precisely how a weight-changing fork can never match a base
        checkpoint. Cached per (family, weights, seed); forks of one
        base all share one entry because the fork index pins their
        weights/seed to the base's."""
        from tpusim.sim.driver import Simulator, SimulatorConfig
        from tpusim.svc import forks as svc_forks

        spec = job.spec
        key = (spec.family_key(), tuple(spec.weights), int(spec.seed))
        sim = self._sims.get(key)
        if sim is None:
            trace = self.traces[spec.trace]
            cfg = SimulatorConfig(
                policies=tuple(
                    (name, int(w))
                    for (name, _), w in zip(spec.policies, spec.weights)
                ),
                gpu_sel_method=spec.gpu_sel,
                norm_method=spec.norm,
                dim_ext_method=spec.dim_ext,
                # forced off "auto": only the table engine has the
                # chunked carry surface the checkpoint ladder rides
                engine="table",
                report_per_event=False,
                shuffle_pod=False,
                seed=int(spec.seed),
                table_cache_dir=self.table_cache_dir,
                checkpoint_dir=svc_forks.checkpoint_dir(self.artifact_dir),
                checkpoint_keep=-1,  # base ladders must survive the run
            )
            sim = Simulator(trace.nodes, cfg)
            sim.set_workload_pods(trace.pods)
            sim.set_typical_pods()
            self._sims[key] = sim
        sim._hb_worker = self.worker_id
        sim._hb_job = job.id
        return sim

    def _checkpoint_every(self, events: int) -> int:
        """Base-run chunk length: ~32 rungs across the trace, capped at
        the serving bucket. The fork latency bound is `tail + one
        chunk`, so shorter chunks mean warmer forks AND more wave steps
        for a full replay — the p99 separation the latency gate
        enforces; 32 keeps the per-base checkpoint count (and the base
        run's write overhead) modest."""
        return max(1, min(self.bucket, -(-int(events) // 32)))

    def _run_base(self, job: Job) -> None:
        """Advance one base trace through the chunked table path,
        persisting every mid-trace carry (checkpoint_keep=-1) and the
        fork-index entry that makes the ladder discoverable."""
        from tpusim.io.trace import build_events
        from tpusim.sim.driver import _bucket_sizes, lane_from_run
        from tpusim.svc import forks as svc_forks

        spec = job.spec
        sim = self._chunked_sim(job)
        prep = sim.prepare_pods(
            tuning_ratio=spec.tune, tuning_seed=spec.tune_seed
        )
        e = len(build_events(prep, sim.cfg.use_timestamps)[0])
        sim.cfg.checkpoint_every = self._checkpoint_every(e)
        sim._reset_run_state()
        sim.schedule_pods(prep)
        p = len(prep)
        # the replay padded events up to the bucket geometry: correct
        # the skip counter exactly like the sweep path does
        _, e2 = _bucket_sizes(p, e, 512)
        lane = lane_from_run(
            sim, spec.weights, spec.seed, pad_skips=e2 - e
        )
        svc_forks.write_base_entry(
            self.artifact_dir, job.digest, sim.last_run_digest,
            sim.cfg.checkpoint_every, e, p,
            svc_jobs.spec_to_payload(spec),
        )
        meta = {
            "run_digest": str(sim.last_run_digest),
            "checkpoint_every": int(sim.cfg.checkpoint_every),
            "events": int(e),
            "pods": int(p),
        }
        self._complete(job, lane, base_meta=meta)

    def _fork_wave_for(self, job: Job):
        """The family's ForkWave (one ChunkWave = three jitted entries,
        shared by every fork of the base — the zero-recompile census).
        The chunk length comes from the base's fork-index entry so lane
        restore cursors land exactly on the base ladder's rungs; a
        missing entry (fleet worker without the coordinator's artifact
        dir) falls back to the same derivation the base used — forks
        then degrade per-lane to full replay, loudly."""
        from tpusim.sim.driver import ChunkWave
        from tpusim.svc import forks as svc_forks
        from tpusim.svc.waves import ForkWave

        key = job.spec.family_key()
        fw = self._waves.get(key)
        if fw is None:
            spec = job.spec
            sim = self._chunked_sim(job)
            prep = sim.prepare_pods(
                tuning_ratio=spec.tune, tuning_seed=spec.tune_seed
            )
            entry = svc_forks.load_base_entry(
                self.artifact_dir, spec.fork[0]
            )
            if entry is not None:
                chunk = int(entry["checkpoint_every"])
            else:
                from tpusim.io.trace import build_events

                e = len(build_events(prep, sim.cfg.use_timestamps)[0])
                chunk = self._checkpoint_every(e)
            wave = ChunkWave(
                sim, prep, lanes=self.queue.lane_width, chunk=chunk
            )
            fw = ForkWave(wave, monitor=self.monitor, out=sys.stderr)
            self._waves[key] = fw
        return fw

    def _run_fork_wave(self, batch: List[Job], keeper,
                       all_jobs: List[Job]) -> None:
        """Serve one fork family's batch through its continuous
        ForkWave: claimed jobs fill lanes, and at every chunk boundary
        the wave pulls MORE queued jobs of the family off the queue
        (claim_family) — the late arrival joins the running wave instead
        of waiting behind it. Joiners enter the lease set (and all_jobs,
        so the poisoned-batch path fails them too)."""
        fw = self._fork_wave_for(batch[0])
        fw.wave.sim._hb_job = batch[0].id
        key = batch[0].spec.family_key()

        def claim_more(n: int) -> List[Job]:
            if n <= 0:
                return []
            got = self.queue.claim_family(self.worker_id, key, n)
            if got:
                self.queue.mark_running(got)
                all_jobs.extend(got)
                if keeper is not None:
                    keeper.members.extend(j.digest for j in got)
                    keeper.renew_now()
            return got

        def on_join(job: Job) -> None:
            if not job.dispatched_unix:
                job.dispatched_unix = time.time()
            self._publish([job], phase="running")

        def on_done(job: Job, lane, meta: dict) -> None:
            self._complete(job, lane, fork_meta=meta)

        fw.serve(
            batch, claim_more=claim_more, on_join=on_join,
            on_done=on_done,
        )

    # ---- introspection ----

    def wave_executables(self) -> int:
        """Compiled executables across every ForkWave served (step +
        scatter + finish per family) — stable across fork waves AND
        boundary joins, the serve-latency gate's zero-recompile
        check."""
        return sum(fw.executables() for fw in self._waves.values())

    def wave_stats(self) -> dict:
        """Continuous-batching counters for /queue."""
        return {
            "families": len(self._waves),
            "waves_run": sum(f.waves_run for f in self._waves.values()),
            "joins": sum(f.joins for f in self._waves.values()),
            "degrades": sum(f.degrades for f in self._waves.values()),
            "executables": self.wave_executables(),
        }

    def sweep_executables(self) -> int:
        """Compiled sweep executables across every family served — the
        /queue `sweep_executables` field. Stable across batches differing
        only in weights/seeds/tunes (zero recompiles); grows only when a
        new job family or padded shape genuinely needs a new jaxpr."""
        return sum(fn._cache_size() for fn in self._sweep_fns)

    def _publish(self, batch: Sequence[Job], **fields) -> None:
        if self.monitor is None:
            return
        for job in batch:
            self.monitor.publish_job_progress(
                job.id,
                dict(fields, status=job.status, batch=job.batch,
                     lane=job.lane),
            )
