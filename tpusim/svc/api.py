"""HTTP plane of the replay service: the POST side of `tpusim serve`
(ISSUE 7).

JobService is a MonitorServer extension app (obs.server.add_app), so one
listener carries both planes — the PR 5 observability GETs (/metrics,
/healthz, /progress with per-job windows) and the job plane:

  POST /jobs             submit one job object or {"jobs": [...]};
                         202 on enqueue, 200 when every job was answered
                         from the digest cache, 400 on a malformed spec,
                         429 + Retry-After on a full queue (the
                         kube_client backoff contract)
  GET  /jobs/<id>        lifecycle: queued/batched/running/done/failed +
                         batch/lane placement
  GET  /jobs/<id>/result result document (placements summary, gpu_alloc,
                         frag, counters); 409 while the job is still in
                         flight, 404 for unknown ids
  GET  /queue            depth, capacity, batches formed, dedup hits,
                         compiled sweep-executable count (the PR 6
                         jit._cache_size() zero-recompile check, live)

start_job_server wires the full stack — queue + worker + monitor — and
is what `tpusim serve DIR --jobs` and the smoke/test surfaces drive.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.parse
from typing import Dict, Optional, Tuple

from tpusim.obs import trace as obs_trace
from tpusim.svc import jobs as svc_jobs
from tpusim.svc.auth import check as _auth_check
from tpusim.svc.batcher import JobQueue, QueueFull, QuotaFull
from tpusim.svc.worker import TraceRef, Worker

_JSON = "application/json"


def _json_body(code: int, doc, headers: Optional[dict] = None):
    body = (json.dumps(doc, sort_keys=True) + "\n").encode()
    if headers:
        return code, _JSON, body, headers
    return code, _JSON, body


class JobService:
    """The extension app MonitorServer routes /jobs and /queue to."""

    # MonitorServer hands us the raw query string (the /events filters)
    accepts_query = True

    # bound on the digest -> trace-id map: FIFO like the monitor's
    # per-job progress window — a long-lived service must not grow
    # per-job state forever
    MAX_TRACE_IDS = 1024

    def __init__(self, queue: JobQueue, worker: Optional[Worker],
                 traces: Dict[str, TraceRef], artifact_dir: str,
                 monitor=None, policy_presets: Optional[dict] = None):
        self.queue = queue
        self.worker = worker  # in-process Worker, or None in fleet mode
        self.traces = dict(traces)
        self.artifact_dir = artifact_dir
        self.monitor = monitor
        # named learned-policy presets (ISSUE 14): preset name ->
        # [(policy name, weight)] pairs, expanded at submit time so the
        # queued/persisted/claimed spec is an ordinary policies job —
        # workers and the digest vocabulary never see preset names
        self.policy_presets = dict(policy_presets or {})
        # the fleet coordinator app (svc.fleet.FleetService) when
        # `serve --jobs --workers N` runs; None for the single
        # in-process worker of PR 7
        self.fleet = None
        # bearer token guarding every mutating endpoint (ISSUE 17);
        # empty = auth disabled. FleetService reads it via its `token`
        # property so both planes enforce ONE secret.
        self.token = ""
        # flight recorder (ISSUE 19): per-process span file + chained
        # audit log, armed by start_job_server / the CLI. Both optional:
        # a bare JobService in a unit test records nothing.
        self.spans = None  # obs.trace.SpanRecorder
        self.audit = None  # obs.audit.AuditLog
        # the SLO plane (ISSUE 20), armed by start_job_server: metrics
        # history ring, alert rule engine, and the sampler thread
        # driving both. A bare JobService in a unit test has none.
        self.tsdb = None  # obs.tsdb.TSDB
        self.alerts = None  # obs.alerts.AlertEngine
        self.sampler = None  # obs.tsdb.MetricsSampler
        # job digest -> trace id, fed by the submit header (or minted
        # here) and handed to workers at claim time so every process
        # tags its spans with the id minted at submit
        self.trace_ids: Dict[str, str] = {}
        # submit path serializes digest lookup + enqueue so concurrent
        # duplicate POSTs dedup instead of double-running
        self._submit_lock = threading.Lock()

    def trace_of(self, digest: str) -> str:
        return self.trace_ids.get(digest, "")

    def adopt_history(self, out=None) -> int:
        """Splice the predecessor's persisted tsdb snapshot into this
        process's ring and start (or resume) sampling — the metrics
        half of a takeover (ISSUE 20): a promoted standby serves
        /query with the deposed leader's history behind its own new
        samples instead of starting blind. Also the non-HA restart
        path: a rebooted coordinator adopts its own last snapshot.
        Returns the number of buckets adopted; a torn/edited snapshot
        is refused loudly (and sampling still resumes — fresh history
        beats no history)."""
        if self.tsdb is None:
            return 0
        n = 0
        try:
            n = self.tsdb.adopt(self.artifact_dir)
        except ValueError as err:
            if out is not None:
                print(f"[slo] refusing torn/edited tsdb snapshot: "
                      f"{err}", file=out)
        if self.sampler is not None:
            self.sampler.resume()
        if n and out is not None:
            print(f"[slo] adopted {n} history bucket(s) from the "
                  f"previous coordinator's snapshot", file=out)
        return n

    def publish_job(self, job) -> None:
        """Push a job's lifecycle change into the monitor's per-job
        /progress map (the fleet completion path publishes here on the
        worker's behalf)."""
        if self.monitor is not None:
            self.monitor.publish_job_progress(
                job.id, {"status": job.status, "worker": job.worker or ""}
            )

    # ---- submission (shared by HTTP and in-process callers) ----

    def submit_payload(self, payload: dict, trace_id: str = "") -> dict:
        """Validate + dedup + enqueue one job document. Returns the job
        description (with `cached` marking digest-cache answers); raises
        ValueError (→ 400) or QueueFull (→ 429). `trace_id` is the
        flight-recorder id off the submit header (minted here for
        in-process callers); it tags the admission span and is handed
        to whichever worker later claims the job — it NEVER enters the
        spec or its digest (two submits of one spec must still dedup)."""
        t_admit = time.time()
        payload = svc_jobs.expand_policy_preset(
            payload, self.policy_presets
        )
        if isinstance(payload, dict) and payload.get("fork"):
            payload = self._resolve_fork(payload)
        spec = svc_jobs.validate_job(payload)
        trace = self.traces.get(spec.trace)
        if trace is None:
            raise ValueError(
                f"unknown trace {spec.trace!r} (hosted: "
                f"{', '.join(sorted(self.traces)) or 'none'})"
            )
        digest = svc_jobs.job_digest(spec, trace.digest)
        tid = trace_id or obs_trace.new_trace_id()
        self.trace_ids[digest] = tid
        while len(self.trace_ids) > self.MAX_TRACE_IDS:
            self.trace_ids.pop(next(iter(self.trace_ids)))
        with self._submit_lock:
            cached = svc_jobs.find_result(self.artifact_dir, digest)
            job = self.queue.submit(spec, digest, cached_result=cached)
            if cached is None:
                # persist the accepted spec BEFORE it becomes runnable: a
                # crash mid-batch leaves a recoverable `.job.json` on
                # disk instead of a job stranded in `running` forever
                # (recover_pending_jobs requeues it at the next startup)
                svc_jobs.write_job_spec(self.artifact_dir, digest, payload)
        if self.spans is not None:
            self.spans.emit(
                obs_trace.SPAN_ADMIT, t_admit, time.time(),
                job=digest, trace=tid,
                cached=bool(cached is not None),
            )
        if self.monitor is not None:
            self.monitor.publish_job_progress(
                job.id, {"status": job.status, "phase": "submitted"}
            )
        return job.describe()

    def _resolve_fork(self, payload: dict) -> dict:
        """Expand a fork submission against the fork index (ISSUE 16):
        the client sends only the handle — base job digest, divergence
        event, tail (and mode) — and the base's full spec payload is
        merged in, so a fork is BY CONSTRUCTION the same replay as its
        base up to the divergence event. Any explicitly-supplied field
        must EQUAL the base's: the checkpointed carry embeds the base's
        weights in its blocked summaries, so a weight-changing fork can
        never restore from a base checkpoint — reject it loudly here
        instead of silently replaying cold."""
        from tpusim.svc import forks as svc_forks

        fork = payload.get("fork")
        if not isinstance(fork, dict):
            raise ValueError(
                'fork must be an object: {"base": <base job digest>, '
                '"event": E, "tail": [[kind, pod], ...]}'
            )
        base_digest = str(fork.get("base", ""))
        entry = svc_forks.load_base_entry(self.artifact_dir, base_digest)
        if entry is None:
            raise ValueError(
                f"fork base {base_digest[:12] or '?'}… has no finished "
                'base run on this service — submit {"base": true, ...} '
                "for the trace first and wait for it to finish"
            )
        base_payload = {
            k: v for k, v in entry["spec"].items() if k != "base"
        }
        base_spec = svc_jobs.validate_job(base_payload)
        merged = dict(base_payload)
        merged.update(
            {k: v for k, v in payload.items() if k != "fork"}
        )
        merged["fork"] = fork
        spec = svc_jobs.validate_job(merged)
        for field in ("trace", "policies", "weights", "seed", "gpu_sel",
                      "norm", "dim_ext", "tune", "tune_seed", "engine"):
            if getattr(spec, field) == getattr(base_spec, field):
                continue
            hint = ""
            if field in ("weights", "policies"):
                hint = (
                    " — the base checkpoints' carry embeds the base's "
                    "weight vector (blocked score summaries), so a "
                    "weight-changing what-if can never restore warm; "
                    "run it as its own base job"
                )
            raise ValueError(
                f"fork field {field!r} differs from base "
                f"{base_digest[:12]}… "
                f"({getattr(spec, field)!r} != "
                f"{getattr(base_spec, field)!r}): a warm-state fork "
                f"replays the base bit-identically up to the divergence "
                f"event{hint}"
            )
        return merged

    # ---- the MonitorServer app hook ----

    def handle(self, method: str, path: str, body: bytes, headers=None,
               query: str = ""):
        if path == "/jobs" and method == "POST":
            # auth BEFORE any parsing: a 401 must not leak whether the
            # body would have been a valid spec or a known digest
            if not _auth_check(headers, self.token):
                return _json_body(
                    401, {"error": "missing or invalid bearer token"}
                )
            if self.fleet is not None and self.fleet.role != "leader":
                return self.fleet.standby_503()
            return self._post_jobs(body, obs_trace.header_trace(headers))
        if path == "/queue" and method == "GET":
            return self._get_queue()
        if path == "/events" and method == "GET":
            return self._get_events(query)
        if path.startswith("/jobs/"):
            if method != "GET":
                return _json_body(405, {"error": "method not allowed"})
            rest = path[len("/jobs/"):]
            if rest.endswith("/result"):
                return self._get_result(rest[: -len("/result")])
            return self._get_job(rest)
        return None  # not ours: fall through to the monitor built-ins

    def _post_jobs(self, body: bytes, trace_id: str = ""):
        try:
            payload = json.loads(body.decode() or "null")
        except (json.JSONDecodeError, UnicodeDecodeError) as err:
            return _json_body(400, {"error": f"bad JSON body: {err}"})
        is_batch = isinstance(payload, dict) and "jobs" in payload
        docs = payload["jobs"] if is_batch else [payload]
        if not isinstance(docs, list) or not docs:
            return _json_body(
                400, {"error": 'want a job object or {"jobs": [...]}'}
            )
        accepted = []
        first_429: Optional[QueueFull] = None
        rejected_indices = []
        for i, doc in enumerate(docs):
            try:
                accepted.append(self.submit_payload(doc, trace_id))
            except ValueError as err:
                # reject the lot on the first malformed doc: a half-
                # accepted batch would make retries re-submit (harmless,
                # dedup'd) but hides the error from casual clients
                return _json_body(
                    400, {"error": str(err), "accepted": accepted}
                )
            except QueueFull as err:
                # backpressure: the rejected doc waits, but the REST of
                # the batch still gets its admission attempt — a hot
                # family at its quota must not block a cold family's
                # jobs riding the same POST (the ISSUE 12 quota goal),
                # and even on a full queue a later duplicate can still
                # answer from the digest cache. The 429 body lists the
                # rejected docs' indices so the client retries exactly
                # those; a QuotaFull additionally names the family.
                if first_429 is None:
                    first_429 = err
                rejected_indices.append(i)
        if first_429 is not None:
            body = {"error": str(first_429), "accepted": accepted,
                    "rejected_indices": rejected_indices,
                    "retry_after_s": first_429.retry_after_s}
            if isinstance(first_429, QuotaFull):
                body["family"] = first_429.family
                body["family_quota"] = first_429.quota
            return _json_body(
                429, body,
                headers={"Retry-After": str(first_429.retry_after_s)},
            )
        all_cached = all(d["status"] == "done" for d in accepted)
        doc = {"jobs": accepted} if is_batch else accepted[0]
        return _json_body(200 if all_cached else 202, doc)

    def _get_job(self, job_id: str):
        job = self.queue.get(job_id)
        if job is None:
            return _json_body(404, {"error": f"unknown job {job_id!r}"})
        return _json_body(200, job.describe())

    def _get_result(self, job_id: str):
        job = self.queue.get(job_id)
        if job is None:
            return _json_body(404, {"error": f"unknown job {job_id!r}"})
        if job.status == "failed":
            return _json_body(
                500, {"error": job.error or "job failed", "id": job.id}
            )
        if job.status != "done" or job.result is None:
            return _json_body(
                409,
                {"error": f"job {job.id} is {job.status}; result not "
                 "ready", "status": job.status},
            )
        return _json_body(200, job.result)

    def _get_events(self, query: str = ""):
        """The audit-log query endpoint (ISSUE 19): bounded tail of the
        chained control-plane log, filterable by kind/job/worker. The
        read path link-checks the whole chain, so an edited log answers
        500 with the verifier's complaint, never silently wrong data."""
        from tpusim.obs import audit as obs_audit

        q = urllib.parse.parse_qs(query or "")

        def one(key, default=""):
            vals = q.get(key) or [default]
            return vals[0]

        try:
            # `limit` is the cursor-pagination spelling (ISSUE 20);
            # `n` stays as the original tail parameter — same clamp
            n = min(max(int(one("limit", "") or one("n", "50")), 1), 500)
            after = max(int(one("after", "0")), 0)
        except ValueError:
            return _json_body(
                400, {"error": "n, limit and after must be integers"}
            )
        try:
            events = obs_audit.tail(
                self.artifact_dir, n=n, kind=one("kind"),
                job=one("job"), worker=one("worker"), after=after,
            )
        except ValueError as err:
            return _json_body(
                500, {"error": f"audit chain unreadable: {err}"}
            )
        # next_after: the cursor a delta poller passes back — the
        # highest chain seq this response covers (records are
        # seq-stamped by obs_audit.tail). No events -> echo the cursor.
        next_after = max([r.get("seq", 0) for r in events] + [after])
        return _json_body(
            200, {"events": events, "n": len(events),
                  "next_after": next_after}
        )

    def _get_queue(self):
        """The aggregated /queue document (ISSUE 12): queue + quota
        stats, plus — in fleet mode — the per-worker rows (depth served,
        leases held, steals benefited, executables) and fleet totals;
        in single-worker mode, the in-process worker's numbers."""
        stats = self.queue.stats()
        if self.worker is not None:
            stats["sweep_executables"] = self.worker.sweep_executables()
            stats["batches_run"] = self.worker.batches_run
            stats["waves"] = self.worker.wave_stats()
        if self.fleet is not None:
            stats.update(self.fleet.queue_fields())
        stats["traces"] = sorted(self.traces)
        stats["policy_presets"] = sorted(self.policy_presets)
        return _json_body(200, stats)


def recover_pending_jobs(service: JobService, out=None) -> int:
    """Restart recovery (ISSUE 10 satellite; batched for the standby-
    promotion path, ISSUE 20): requeue every persisted job spec with no
    signed result — a service killed mid-batch answers its stranded
    jobs after restart instead of leaving them `running` forever.

    Two passes instead of the old one-submit_payload-per-spec loop: a
    LOCK-FREE validation pass (preset expansion, fork resolution, spec
    validation, digest recompute, result-cache probe — the expensive
    re-verification), then ONE JobQueue.submit_many under one lock
    acquisition, so a takeover with hundreds of queued jobs re-admits
    in a single pass. Returns the number requeued; malformed or
    no-longer-valid specs (code drift changes the digest, a hosted
    trace vanished) are skipped with a note, never fatal; a full queue
    stops the batch and leaves the rest for the clients' retries."""
    pending = svc_jobs.pending_job_specs(service.artifact_dir)
    if not pending:
        return 0
    t_admit = time.time()
    prepared = []  # (persisted digest, recomputed digest, spec, payload, cached)
    for digest, payload in pending:
        try:
            p = svc_jobs.expand_policy_preset(payload,
                                              service.policy_presets)
            if isinstance(p, dict) and p.get("fork"):
                p = service._resolve_fork(p)
            spec = svc_jobs.validate_job(p)
            trace = service.traces.get(spec.trace)
            if trace is None:
                raise ValueError(
                    f"unknown trace {spec.trace!r} (hosted: "
                    f"{', '.join(sorted(service.traces)) or 'none'})"
                )
            new_digest = svc_jobs.job_digest(spec, trace.digest)
            cached = svc_jobs.find_result(service.artifact_dir,
                                          new_digest)
            prepared.append((digest, new_digest, spec, p, cached))
        except ValueError as err:
            if out is not None:
                print(
                    f"[serve] skipping unrecoverable job "
                    f"{digest[:12]}…: {err}", file=out,
                )
    with service._submit_lock:
        jobs, leftover = service.queue.submit_many(
            [(spec, d, cached) for _, d, spec, _, cached in prepared]
        )
    t_done = time.time()
    for job, (old_digest, new_digest, _, p, cached) in zip(jobs,
                                                           prepared):
        tid = obs_trace.new_trace_id()
        service.trace_ids[new_digest] = tid
        if cached is None and new_digest != old_digest:
            # code drift moved the digest: persist under the NEW name
            # so the next crash recovers the job the queue now runs
            svc_jobs.write_job_spec(service.artifact_dir, new_digest, p)
        if service.spans is not None:
            service.spans.emit(
                obs_trace.SPAN_ADMIT, t_admit, t_done,
                job=new_digest, trace=tid,
                cached=bool(cached is not None),
            )
        if service.monitor is not None:
            service.monitor.publish_job_progress(
                job.id, {"status": job.status, "phase": "recovered"}
            )
    while len(service.trace_ids) > service.MAX_TRACE_IDS:
        service.trace_ids.pop(next(iter(service.trace_ids)))
    n = len(jobs)
    if n and service.audit is not None:
        # one batch record, not n flocked appends: the takeover path
        # must not serialize on the audit lock per queued job
        service.audit.emit(
            "requeue", n=n, reason="recovered-specs",
            jobs=[d[:12] for _, d, _, _, _ in prepared[:16]],
        )
    if leftover and out is not None:
        print(
            f"[serve] recovery stopped at a full queue ({leftover} "
            f"spec(s) left for the clients' retries)", file=out,
        )
    if n and out is not None:
        print(f"[serve] requeued {n} interrupted job(s) from "
              f"{service.artifact_dir}", file=out)
    return n


def start_job_server(
    artifact_dir: str, traces: Dict[str, TraceRef], listen: str = "",
    lane_width: int = 8, queue_size: int = 64, bucket: int = 512,
    table_cache_dir: str = "",
    start_worker: bool = True, recover: bool = True, out=None,
    fleet: bool = False, lease_s: float = 0.0, family_quota: int = 0,
    policy_presets: Optional[dict] = None, token: str = "",
    coord=None, slo_file: str = "", slo_rules=None,
) -> Tuple[object, JobService, Optional[Worker]]:
    """Wire the full service: MonitorServer (+ heartbeat-fed /progress)
    with the JobService app, a bounded JobQueue, and either the single
    in-process Worker thread (PR 7) or — fleet=True (ISSUE 12) — the
    FleetService coordinator app (/workers/register|claim|renew|
    complete) that external worker PROCESSES drain the queue through.
    Returns (server, service, worker); worker is None in fleet mode.
    Caller owns shutdown (srv.begin_drain(); worker.stop(); srv.stop()).
    start_worker=False leaves batch dispatch to the caller
    (deterministic tests); recover=True requeues crash-interrupted jobs
    from the artifact dir before serving — in fleet mode it additionally
    ADOPTS still-live lease files (a coordinator restart under live
    workers must not double-hand-out their batches). `family_quota`
    arms the per-family admission cap; `lease_s` overrides the lease
    duration (svc.leases.DEFAULT_LEASE_S). `token` arms bearer auth on
    every mutating endpoint (ISSUE 17); `coord` (a
    svc.coord.CoordinatorState, fleet mode only) arms HA — epoch-fenced
    mutations, standby 503s, and recovery deferred until this process
    actually holds the leadership lease. `slo_file` (or `slo_rules`, a
    pre-validated list) arms the SLO plane (ISSUE 20): the tsdb
    history ring + sampler thread, the alert rule engine, and the
    /query + /alerts endpoints — a standby's sampler starts PAUSED and
    resumes at promotion via service.adopt_history()."""
    from tpusim.obs.server import MonitorServer

    srv = MonitorServer(listen)
    queue = JobQueue(maxsize=queue_size, lane_width=lane_width,
                     family_quota=family_quota, lease_s=lease_s)
    worker = None
    if not fleet:
        worker = Worker(
            queue, traces, artifact_dir, bucket=bucket, monitor=srv,
            table_cache_dir=table_cache_dir,
        )
    service = JobService(queue, worker, traces, artifact_dir, monitor=srv,
                         policy_presets=policy_presets)
    service.bucket = bucket  # the register handshake hands it to workers
    service.token = str(token or "")
    # flight recorder (ISSUE 19): every coordinator process writes its
    # own span file (HA pairs share the artifact dir, so the name is
    # pid-scoped) and appends control-plane decisions to the chained
    # audit log. Always armed — the log IS the operational record.
    from tpusim.obs.audit import AuditLog
    from tpusim.obs.trace import SpanRecorder

    proc = f"coord-{os.getpid()}"
    service.spans = SpanRecorder(artifact_dir, proc)
    service.audit = AuditLog(artifact_dir, proc)
    if coord is not None:
        coord.audit = service.audit

    # capability routing (ISSUE 17): tell the queue what each family
    # actually NEEDS, judged against the hosted trace — claim_batch only
    # hands fault-family or large-N work to workers declaring support.
    def _family_needs(spec):
        ref = service.traces.get(spec.trace)
        n_nodes = len(ref.nodes) if ref is not None else 0
        return {"fault": bool(spec.fault), "nodes": int(n_nodes),
                "mem_bytes": 0}

    queue.family_needs_fn = _family_needs
    srv.add_app(service)
    if fleet:
        from tpusim.svc.fleet import FleetService

        service.fleet = FleetService(service, lease_s=lease_s, out=out)
        service.fleet.coord = coord
        srv.add_app(service.fleet)
        # fleet /healthz: 503 only when NO worker is live
        srv.health_hook = service.fleet.health

    # the SLO plane (ISSUE 20): live per-kind latency summaries on
    # /metrics, the tsdb history ring + sampler, the alert rule engine,
    # and the /query + /alerts read surface. Always armed — history and
    # alerting ARE the operational record, like the audit chain.
    from tpusim.obs import alerts as obs_alerts
    from tpusim.obs import tsdb as obs_tsdb
    from tpusim.obs.emitters import latency_summary_lines

    srv.metrics_extra_fn = (
        lambda: latency_summary_lines(queue.latency_percentiles())
    )
    service.tsdb = obs_tsdb.TSDB()
    rules = (slo_rules if slo_rules is not None
             else obs_alerts.load_rules(slo_file))
    service.alerts = obs_alerts.AlertEngine(
        service.tsdb, rules, audit=service.audit
    )
    srv.add_app(obs_tsdb.TsdbApp(service.tsdb, service.alerts))
    # page-severity burn flips /healthz readiness detail — composed
    # over the fleet's worker-liveness hook, never replacing it
    srv.health_hook = service.alerts.compose_health(srv.health_hook)
    # a standby must not sample: only the leader writes history (and
    # the snapshot file) — promotion adopts + resumes (adopt_history)
    standby = coord is not None and coord.role != "leader"
    service.sampler = obs_tsdb.MetricsSampler(
        service.tsdb, obs_tsdb.ServiceCollector(service),
        alerts=service.alerts, artifact_dir=artifact_dir,
        paused=standby,
    )
    service.sampler.start()
    srv.on_stop(service.sampler.stop)
    if recover and (coord is None or coord.role == "leader"):
        # before start(): recovered jobs must be queued before the first
        # client request can observe the service. A standby defers —
        # adoption happens at promotion (the CLI's takeover path), when
        # the epoch fence guarantees the old leader can no longer act.
        recover_pending_jobs(service, out=out)
        if service.fleet is not None:
            service.fleet.adopt_leases(out=out)
    if not standby:
        # a booting leader adopts its own last snapshot: metrics
        # history survives a graceful restart, not just a failover
        service.adopt_history(out=out)
    srv.start()
    srv.attach_heartbeat()
    srv.publish_progress(phase="serving-jobs")
    if start_worker and worker is not None:
        worker.start()
    return srv, service, worker
