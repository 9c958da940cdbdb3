"""The worker fleet: many processes draining one JobQueue (ISSUE 12),
grown wide-area in ISSUE 13 — workers need NO shared filesystem.

PR 10 made ONE worker crash-safe (spec persistence, SIGTERM drain); this
module promotes that per-worker lifecycle into a fleet protocol. The
coordinator — `tpusim serve --jobs --workers N` — owns the HTTP plane,
the bounded JobQueue, and the artifact dir; worker PROCESSES (spawned
locally, or joined from ANY host with `tpusim worker --join URL`) pull
batches over the /workers/* POST endpoints plus, for no-shared-fs
("remote" mode) workers, the transfer plane (ISSUE 13):

  GET  /traces/<name>[/nodes.csv|/pods.csv]
                      digest-named trace download: the handshake
                      carries per-file sha256 + the trace content
                      digest; the worker caches by digest, resumes
                      partial transfers (Range), re-downloads on
                      mismatch, and refuses to serve on residual skew
  POST /results/<digest>
                      signed-result upload: the coordinator verifies
                      the payload digest BEFORE the atomic rename — a
                      torn or forged upload is a 400 + [Degrade]
                      warning, never a half-written result file
  POST /leases        the remote workers' lease mirror: the
                      coordinator writes/deletes its own signed lease
                      files (op=stake|release), keeping the on-disk
                      recovery plane identical for both modes

Every worker→coordinator request rides the shared kube_client
capped-exponential-backoff-with-jitter schedule honoring Retry-After
(`_with_backoff`), so a coordinator restart mid-claim is a stall, not a
dead worker. The original shared-filesystem endpoints:

  /workers/register   identity + the hosting handshake: lease duration,
                      lane width, artifact dir, and the hosted traces'
                      CSV paths + content digests (the worker re-loads
                      and digest-verifies them — version/trace skew
                      fails loudly at join time, not as wrong results)
  /workers/claim      the queue pop with OWNERSHIP: a family-sharded
                      FIFO batch stamped with the worker id and a lease
                      deadline; every claim first runs the orphan
                      reaper (JobQueue.steal_expired), so ANY live
                      worker's poll reclaims a dead worker's jobs —
                      no operator action, no dedicated janitor
  /workers/renew      deadline extension while a batch is in flight
                      (the worker ALSO rewrites its signed lease files,
                      svc.leases — the on-disk mirror that survives a
                      coordinator restart)
  /workers/complete   digest-keyed completion: the coordinator loads
                      the signed result the worker wrote into the
                      shared artifact dir; completing an already-done
                      job (the stolen-job race) is a silent dedup

At-least-once + idempotent = exactly-once results: a `kill -9` mid-batch
loses nothing — the specs are on disk (PR 10), the lease expires, a live
worker steals, and the re-run's result is byte-identical because the job
digest pins the whole trajectory and result writes are atomic whole-file
replaces. The shared warm state (the PR 6 persistent compile cache +
content-keyed table cache) means a freshly joined worker's first batch
skips the ~5 s compile.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from tpusim.obs import trace as obs_trace
from tpusim.svc import jobs as svc_jobs
from tpusim.svc import leases as svc_leases
from tpusim.svc.api import _json_body
from tpusim.svc.auth import bearer_headers
from tpusim.svc.auth import check as auth_check
from tpusim.svc.batcher import Job, JobQueue


# ---------------------------------------------------------------------------
# Worker registry
# ---------------------------------------------------------------------------


@dataclass
class WorkerInfo:
    """One registered worker's coordinator-side record."""

    id: str
    pid: int = 0
    host: str = ""
    joined_unix: float = field(default_factory=time.time)
    last_seen_unix: float = field(default_factory=time.time)
    claims: int = 0
    batches: int = 0
    jobs_done: int = 0
    jobs_failed: int = 0
    first_dispatch_s: float = 0.0
    last_dispatch_s: float = 0.0
    sweep_executables: int = 0
    steals_benefited: int = 0  # stolen jobs this worker re-ran
    # the topology view (ISSUE 13): how this worker reaches the
    # artifact plane — "shared-fs" (reads trace CSVs by path, writes
    # results directly) or "remote" (digest-verified download/upload
    # over HTTP, no shared filesystem) — plus its reported transfer
    # counters (downloads/uploads/bytes/resumes/sha retries)
    mode: str = "shared-fs"
    transfers: dict = field(default_factory=dict)
    # the MEASURED capability profile (ISSUE 19), beside the caps the
    # worker merely declared: EWMA of reported batch dispatch walls,
    # the compile-cache probable-hit count (obs.spans.note_compile_cache
    # heuristic, counted worker-side), and the worker's own pushed
    # exposition-format snapshot (merged worker-labeled into /metrics)
    ewma_dispatch_s: float = 0.0
    probable_hits: int = 0
    metrics_text: str = ""
    # capability tags (ISSUE 17): what this worker declared at
    # registration — backend name, device count, approximate memory
    # bytes, fault-lane support, and the biggest trace it will take
    # (max_nodes, 0 = unlimited). claim_batch routes families by these.
    caps: dict = field(default_factory=dict)

    def live(self, now: float, window_s: float) -> bool:
        return (now - self.last_seen_unix) <= window_s

    def profile(self, now: float) -> dict:
        """The measured profile row for /workers: what this worker
        actually does — smoothed dispatch wall, transfer throughput
        since join, compile-cache hit rate — as opposed to what its
        caps tags declared at registration."""
        tr = self.transfers or {}
        moved = (int(tr.get("download_bytes") or 0)
                 + int(tr.get("upload_bytes") or 0))
        return {
            "ewma_dispatch_s": round(self.ewma_dispatch_s, 3),
            "transfer_bps": round(
                moved / max(now - self.joined_unix, 1e-6), 1
            ),
            "compile_hit_rate": (
                round(self.probable_hits / self.batches, 3)
                if self.batches else 0.0
            ),
        }


class WorkerRegistry:
    """The fleet roster. MonitorServer is a ThreadingHTTPServer, so
    register/claim/renew/complete handlers run CONCURRENTLY — the
    roster map and the auto-id counter are lock-guarded; the per-worker
    stat fields are scalar writes only ever made by that worker's own
    requests."""

    def __init__(self, lease_s: float):
        import threading

        self.lease_s = float(lease_s)
        self.workers: Dict[str, WorkerInfo] = {}
        self._auto = 0
        self._lock = threading.Lock()

    @property
    def live_window_s(self) -> float:
        # three missed renewals = presumed dead for the LIVENESS view
        # (lease expiry is judged per job, not per worker)
        return max(3.0 * self.lease_s, 3.0)

    def register(self, worker_id: str, pid: int, host: str,
                 mode: str = "", caps: Optional[dict] = None) -> WorkerInfo:
        with self._lock:
            if not worker_id:
                self._auto += 1
                worker_id = f"w{self._auto:03d}-{pid or 0}"
            info = self.workers.get(worker_id)
            if info is None:
                info = WorkerInfo(id=worker_id, pid=int(pid or 0),
                                  host=str(host or ""))
                self.workers[worker_id] = info
            else:  # re-register after a coordinator restart or reconnect
                info.pid = int(pid or info.pid)
                info.host = str(host or info.host)
                info.last_seen_unix = time.time()
            if mode:
                info.mode = str(mode)
            if isinstance(caps, dict):
                info.caps = dict(caps)
            return info

    def live_caps(self, now: Optional[float] = None) -> List[dict]:
        """The capability tags of every LIVE worker — the starvation
        judge's input (a family no live worker can serve is starved;
        an empty fleet is a different problem)."""
        if now is None:
            now = time.time()
        with self._lock:
            snapshot = list(self.workers.values())
        return [
            w.caps or {} for w in snapshot
            if w.live(now, self.live_window_s)
        ]

    def touch(self, worker_id: str) -> Optional[WorkerInfo]:
        with self._lock:
            info = self.workers.get(worker_id)
        if info is not None:
            info.last_seen_unix = time.time()
        return info

    def live_count(self, now: Optional[float] = None) -> int:
        if now is None:
            now = time.time()
        with self._lock:
            snapshot = list(self.workers.values())
        return sum(
            1 for w in snapshot if w.live(now, self.live_window_s)
        )

    def describe(self, queue: Optional[JobQueue] = None) -> dict:
        now = time.time()
        rows = {}
        with self._lock:
            snapshot = list(self.workers.values())
        for w in snapshot:
            rows[w.id] = {
                "pid": w.pid,
                "host": w.host,
                "mode": w.mode,
                "caps": dict(w.caps),
                "transfers": dict(w.transfers),
                "live": w.live(now, self.live_window_s),
                "last_seen_s": round(now - w.last_seen_unix, 2),
                "claims": w.claims,
                "batches": w.batches,
                "jobs_done": w.jobs_done,
                "jobs_failed": w.jobs_failed,
                "steals_benefited": w.steals_benefited,
                "sweep_executables": w.sweep_executables,
                "first_dispatch_s": round(w.first_dispatch_s, 3),
                "last_dispatch_s": round(w.last_dispatch_s, 3),
                "profile": w.profile(now),
                "leases_held": (
                    len(queue.jobs_of_worker(w.id)) if queue else 0
                ),
            }
        return rows


# ---------------------------------------------------------------------------
# Coordinator-side HTTP app
# ---------------------------------------------------------------------------


class FleetService:
    """The /workers/* extension app (MonitorServer.add_app) the job
    coordinator mounts beside JobService. Holds the registry and the
    steal/adopt logic; the JobQueue it drives is JobService's."""

    def __init__(self, service, lease_s: float = 0.0, out=None):
        self.service = service  # svc.api.JobService
        self.queue: JobQueue = service.queue
        if lease_s > 0:
            self.queue.lease_s = float(lease_s)
        self.registry = WorkerRegistry(self.queue.lease_s)
        self.out = out
        self.total_steals_cleaned = 0
        # the supervisor owning `--workers N` children (svc.supervisor,
        # ISSUE 13), or None when workers join only from outside; /queue
        # and /healthz surface its respawn/breaker state when set
        self.supervisor = None
        # the HA plane (ISSUE 17): a CoordinatorState when leadership
        # leases are armed (the serve CLI / the fencing tests); None
        # keeps every single-coordinator flow unfenced and unchanged
        self.coord = None
        # families already warned about in a [Degrade] line — once per
        # family per process, not once per /queue poll
        self._starve_warned = set()
        # coordinator-side transfer-plane counters (ISSUE 13)
        self.transfers = {
            "trace_requests": 0, "trace_bytes": 0,
            "uploads_ok": 0, "uploads_rejected": 0, "lease_posts": 0,
        }

    # ---- the HA + auth gates (ISSUE 17) ----

    @property
    def epoch(self) -> int:
        return self.coord.epoch if self.coord is not None else 0

    @property
    def role(self) -> str:
        return self.coord.role if self.coord is not None else "leader"

    @property
    def token(self) -> str:
        return getattr(self.service, "token", "") or ""

    # ---- the flight recorder (ISSUE 19): the audit log + span
    # recorder live on JobService (one pair per coordinator process);
    # every control-plane decision below witnesses itself through them

    @property
    def audit(self):
        return getattr(self.service, "audit", None)

    @property
    def spans(self):
        return getattr(self.service, "spans", None)

    def _audit(self, kind: str, job: str = "", worker: str = "",
               **fields):
        log = self.audit
        if log is not None:
            log.emit(kind, job=job, worker=worker, **fields)

    def _unauthorized(self, path: str = ""):
        # one uniform body for missing/malformed/forged tokens, issued
        # BEFORE any digest parsing — a 401 never reveals whether a
        # digest (or worker, or trace) exists. The audit record carries
        # the path only: token material never enters the chain.
        self._audit("auth_401", path=path)
        return _json_body(
            401, {"error": "missing or invalid bearer token"}
        )

    def standby_503(self):
        return _json_body(
            503,
            {"error": "standby coordinator — not the leader",
             "role": self.role, "epoch": self.epoch},
            headers={"Retry-After": "2"},
        )

    def _fence(self, doc: dict):
        """Epoch fencing (ISSUE 17): judge the op's coordinator-epoch
        stamp against ours. Older → 409 `{"stale_epoch": true,
        "register": true}` (the worker re-registers and adopts the new
        epoch). NEWER → the sender holds proof a newer leader exists,
        so WE are the deposed one: demote on the spot and answer 409
        `{"deposed": true}`. Unstamped ops (pre-HA workers, HA off)
        pass untouched."""
        if self.coord is None:
            return None
        op_epoch = doc.get("epoch")
        if op_epoch is None:
            return None
        try:
            op_epoch = int(op_epoch)
        except (TypeError, ValueError):
            return _json_body(400, {"error": "epoch must be an integer"})
        mine = self.epoch
        if op_epoch < mine:
            self._audit("fence_409", worker=str(doc.get("worker") or ""),
                        detail="stale_epoch", op_epoch=op_epoch,
                        epoch=mine)
            return _json_body(409, {
                "error": f"stale coordinator epoch {op_epoch} "
                         f"(current {mine})",
                "stale_epoch": True, "epoch": mine, "register": True,
            })
        if op_epoch > mine:
            self.coord.note_epoch(op_epoch)
            self._audit("fence_409", worker=str(doc.get("worker") or ""),
                        detail="deposed", op_epoch=op_epoch, epoch=mine)
            return _json_body(409, {
                "error": f"op carries epoch {op_epoch} > ours ({mine}) "
                         "— this coordinator was deposed and has "
                         "demoted itself",
                "deposed": True, "epoch": op_epoch,
            })
        return None

    # ---- request routing ----

    def handle(self, method: str, path: str, body: bytes, headers=None):
        mine = (path in ("/traces", "/leases", "/workers")
                or path.startswith(("/traces/", "/results/", "/workers/")))
        if mine and method == "POST":
            # admission first (auth runs before ANY path/digest
            # parsing), then leadership: a standby must not mutate
            # shared state even for a validly-authed worker
            if not auth_check(headers, self.token):
                return self._unauthorized(path)
            if self.role != "leader":
                return self.standby_503()
        # the fleet-aggregated metrics view (ISSUE 19): read-only, so
        # it answers in front of MonitorServer's single-run builtin
        if path == "/metrics" and method == "GET":
            return self._metrics()
        # the transfer plane (ISSUE 13): trace download, result upload,
        # and the remote workers' lease mirror — all digest-guarded
        if path == "/traces" and method == "GET":
            return _json_body(200, {
                "traces": {
                    name: self._trace_meta(t)
                    for name, t in self.service.traces.items()
                }
            })
        if path.startswith("/traces/") and method == "GET":
            return self._get_trace(path, headers)
        if path.startswith("/results/") and method == "POST":
            return self._accept_result(path, body, headers)
        if path == "/leases" and method == "POST":
            return self._leases(body)
        if not path.startswith("/workers"):
            return None
        if path == "/workers" and method == "GET":
            return _json_body(
                200, {"workers": self.registry.describe(self.queue),
                      "live": self.registry.live_count()}
            )
        if method != "POST":
            return _json_body(405, {"error": "method not allowed"})
        try:
            doc = json.loads(body.decode() or "null")
        except (json.JSONDecodeError, UnicodeDecodeError) as err:
            return _json_body(400, {"error": f"bad JSON body: {err}"})
        if not isinstance(doc, dict):
            return _json_body(400, {"error": "want a JSON object"})
        if path == "/workers/register":
            # never fenced: register is HOW a worker adopts the new
            # epoch after a takeover
            return self._register(doc)
        fenced = self._fence(doc)
        if fenced is not None:
            return fenced
        if path == "/workers/claim":
            return self._claim(doc)
        if path == "/workers/renew":
            return self._renew(doc)
        if path == "/workers/complete":
            return self._complete(doc)
        return _json_body(404, {"error": f"unknown fleet path {path}"})

    # ---- the transfer plane (ISSUE 13) ----

    @staticmethod
    def _safe_digest(s: str) -> bool:
        """True when `s` is usable as a file stem inside the artifact
        dir: digests are lowercase sha256 hex, and anything else —
        path separators, dot-dot, empty — must be rejected BEFORE it
        reaches an os.path.join (the /leases and /results endpoints
        take these strings off the wire)."""
        s = str(s)
        return bool(s) and all(c in "0123456789abcdef" for c in s) \
            and len(s) <= 128

    def _trace_meta(self, t) -> dict:
        return {
            "nodes_csv": t.nodes_csv, "pods_csv": t.pods_csv,
            "max_pods": t.max_pods, "digest": t.digest,
            "nodes_sha256": t.nodes_sha256, "pods_sha256": t.pods_sha256,
            "nodes_bytes": t.nodes_bytes, "pods_bytes": t.pods_bytes,
        }

    def _get_trace(self, path: str, headers):
        """GET /traces/<name> (meta JSON) and /traces/<name>/nodes.csv |
        pods.csv (the raw file, Range-resumable) — the download half of
        the no-shared-fs transport: the worker verifies each file
        against the handshake's sha256 and the parsed trace against the
        content digest, so a truncated or skewed transfer can only fail
        loudly, never run the wrong trace."""
        parts = path[len("/traces/"):].split("/")
        trace = self.service.traces.get(parts[0])
        if trace is None:
            return _json_body(
                404, {"error": f"unknown trace {parts[0]!r} (hosted: "
                      f"{', '.join(sorted(self.service.traces))})"}
            )
        if len(parts) == 1:
            return _json_body(200, self._trace_meta(trace))
        which = parts[1] if len(parts) == 2 else ""
        src = {"nodes.csv": trace.nodes_csv,
               "pods.csv": trace.pods_csv}.get(which)
        if not src:
            return _json_body(
                404, {"error": f"unknown trace file {which!r} "
                      "(want nodes.csv or pods.csv)"}
            )
        sha = {"nodes.csv": trace.nodes_sha256,
               "pods.csv": trace.pods_sha256}[which]
        try:
            size = os.path.getsize(src)
            start = 0
            rng = str((headers or {}).get("Range") or "").strip()
            if rng:
                import re as _re

                m = _re.match(r"bytes=(\d+)-$", rng)
                # >= : a Range at exactly EOF (a fully-written .part
                # that died pre-rename) is 416, never an empty 206
                # with an inverted Content-Range
                if m is None or int(m.group(1)) >= size:
                    return (416, "text/plain", b"",
                            {"Content-Range": f"bytes */{size}"})
                start = int(m.group(1))
            # seek + read the suffix only: a resume of the last few
            # bytes must not cost an O(file) read per retry
            with open(src, "rb") as f:
                if start:
                    f.seek(start)
                data = f.read()
        except OSError as err:
            return _json_body(
                500, {"error": f"hosted trace file unreadable: {err}"}
            )
        self.transfers["trace_requests"] += 1
        self.transfers["trace_bytes"] += len(data)
        hdrs = {"X-Content-SHA256": sha, "Accept-Ranges": "bytes"}
        if start > 0:
            hdrs["Content-Range"] = f"bytes {start}-{size - 1}/{size}"
            return (206, "text/csv", data, hdrs)
        return (200, "text/csv", data, hdrs)

    def _accept_result(self, path: str, body: bytes, headers=None):
        """POST /results/<digest> — the upload half: the bytes must
        verify as a signed result for EXACTLY this digest before the
        atomic rename lands them; a torn or forged upload is rejected
        with a [Degrade] warning and the artifact dir keeps no partial
        file (svc.jobs.accept_result_upload)."""
        digest = path[len("/results/"):]
        if not self._safe_digest(digest):
            return _json_body(404, {"error": f"bad result path {path!r}"})
        t_verify = time.time()
        try:
            svc_jobs.accept_result_upload(
                self.service.artifact_dir, digest, body
            )
        except (ValueError, json.JSONDecodeError) as err:
            self.transfers["uploads_rejected"] += 1
            self._audit("degrade", job=digest, reason="rejected-upload",
                        detail=str(err))
            print(
                f"[Degrade] rejected result upload for {digest[:12]}… "
                f"({err}); nothing written — the worker retries or the "
                "lease expires",
                file=self.out if self.out is not None else sys.stderr,
            )
            return _json_body(400, {"error": f"rejected upload: {err}"})
        self.transfers["uploads_ok"] += 1
        if self.spans is not None:
            tid = (obs_trace.header_trace(headers)
                   or self.service.trace_of(digest))
            self.spans.emit(
                obs_trace.SPAN_VERIFY, t_verify, time.time(),
                job=digest, trace=tid, bytes=len(body),
            )
        return _json_body(200, {"stored": digest, "bytes": len(body)})

    def _leases(self, body: bytes):
        """POST /leases — the remote workers' lease mirror: the
        COORDINATOR writes/deletes the signed lease files on their
        behalf (op=stake|release), so the on-disk recovery plane
        (adoption, reaping, skew-judged expiry) is identical for
        shared-fs and remote workers. Lenient about roster membership:
        the lease file itself is the proof that matters."""
        try:
            doc = json.loads(body.decode() or "null")
        except (json.JSONDecodeError, UnicodeDecodeError) as err:
            return _json_body(400, {"error": f"bad JSON body: {err}"})
        if not isinstance(doc, dict):
            return _json_body(400, {"error": "want a JSON object"})
        fenced = self._fence(doc)
        if fenced is not None:
            return fenced
        members = [str(m) for m in doc.get("members") or []]
        if not members:
            return _json_body(400, {"error": "want a members list"})
        op = str(doc.get("op") or "stake")
        if op not in ("stake", "release"):
            return _json_body(
                400, {"error": f"op must be stake|release, got {op!r}"}
            )
        bad = [m for m in members if not self._safe_digest(m)]
        if bad:
            # members become file stems under the artifact dir — a
            # traversal payload ("../../x") must die here, loudly,
            # before any os.path.join sees it
            return _json_body(
                400, {"error": f"member(s) are not job digests: "
                      f"{[b[:40] for b in bad]}"}
            )
        wid = str(doc.get("worker") or "")
        self.transfers["lease_posts"] += 1
        self.registry.touch(wid)
        if op == "release":
            for d in members:
                svc_leases.delete_lease(self.service.artifact_dir, d)
            return _json_body(200, {"released": len(members)})
        deadline = time.time() + self.queue.lease_s
        for d in members:
            svc_leases.write_lease(
                self.service.artifact_dir, d, wid,
                int(doc.get("pid") or 0), deadline, members,
            )
        return _json_body(
            200, {"staked": len(members), "deadline_unix": deadline}
        )

    def _known(self, doc):
        wid = str(doc.get("worker") or "")
        info = self.registry.touch(wid)
        if info is None:
            # a coordinator restart wiped the roster: tell the worker to
            # re-register (409 — the run_worker loop handles it)
            return None, _json_body(
                409, {"error": f"unknown worker {wid!r}", "register": True}
            )
        return info, None

    def _register(self, doc):
        info = self.registry.register(
            str(doc.get("worker") or ""), doc.get("pid") or 0,
            str(doc.get("host") or ""), mode=str(doc.get("mode") or ""),
            caps=doc.get("caps"),
        )
        if self.out is not None:
            print(f"[fleet] worker {info.id} joined (pid {info.pid}"
                  f"{', ' + info.mode if doc.get('mode') else ''})",
                  file=self.out)
        traces = {
            name: self._trace_meta(t)
            for name, t in self.service.traces.items()
        }
        return _json_body(200, {
            "worker": info.id,
            "lease_s": self.queue.lease_s,
            "lane_width": self.queue.lane_width,
            "artifact_dir": os.path.abspath(self.service.artifact_dir),
            "bucket": getattr(self.service, "bucket", 512),
            "traces": traces,
            # the handshake is how a worker learns the coordinator
            # epoch it must stamp every subsequent op with (ISSUE 17)
            "epoch": self.epoch,
        })

    def release_dead(self, pid: int) -> int:
        """Instant reclaim for a worker KNOWN dead (the serve loop
        reaped its child process): release everything it held — no
        need to wait out the lease — and clean its lease files.
        Returns the number of jobs released."""
        with self.registry._lock:
            wid = next(
                (w.id for w in self.registry.workers.values()
                 if w.pid == int(pid)), None,
            )
        if wid is None:
            return 0
        held = self.queue.release_worker(wid)
        for job in held:
            svc_leases.delete_lease(self.service.artifact_dir, job.digest)
            self._audit("requeue", job=job.digest, worker=wid,
                        reason="worker-dead", dead_pid=int(pid))
        if held and self.out is not None:
            print(
                f"[fleet] released {len(held)} job(s) of dead worker "
                f"{wid} (pid {pid}) for immediate re-claim",
                file=self.out,
            )
        return len(held)

    def steal_sweep(self) -> List[Job]:
        """Run the orphan reaper and clean the dead owners' lease files
        (the coordinator's half of stealing; the re-claiming worker's
        fresh lease write is the other half)."""
        stolen = self.queue.steal_expired()
        for job in stolen:
            svc_leases.delete_lease(self.service.artifact_dir, job.digest)
            self._audit("steal", job=job.digest,
                        worker=getattr(job, "last_worker", ""),
                        reason="lease_expired",
                        attempts=getattr(job, "attempts", 0))
            if self.out is not None:
                print(
                    f"[fleet] lease expired on {job.id} "
                    f"({job.digest[:12]}…) — requeued for stealing",
                    file=self.out,
                )
        self.total_steals_cleaned += len(stolen)
        return stolen

    def starved_families(self) -> List[str]:
        """Queued families NO live worker's declared capabilities can
        serve (ISSUE 17) — the `/queue` visibility + one loud
        `[Degrade]` per family. Empty when the fleet is empty: that is
        'no workers', a different (already-visible) problem."""
        caps_list = self.registry.live_caps()
        if not caps_list:
            return []
        starved = self.queue.starved_families(caps_list)
        for fam in starved:
            if fam not in self._starve_warned:
                self._starve_warned.add(fam)
                print(
                    f"[Degrade] queued family {fam} is STARVED: no "
                    "live worker declares the capabilities it needs "
                    "(fault-lane support / max_nodes / memory) — it "
                    "waits until a capable worker joins",
                    file=self.out if self.out is not None else sys.stderr,
                )
        return starved

    def _claim(self, doc):
        info, err = self._known(doc)
        if err is not None:
            return err
        self.steal_sweep()
        info.claims += 1
        batch = self.queue.claim_batch(info.id, timeout=0.0,
                                       linger_s=0.05,
                                       caps=info.caps or None)
        if not batch and self.queue.depth() > 0:
            # this worker found only work it cannot serve — judge the
            # whole fleet so a truly starved family is loud, not a
            # silent forever-queued row
            self.starved_families()
        # stolen-but-already-finished shortcut: a thief's claim of a job
        # whose (presumed dead, actually slow) owner DID write the
        # signed result answers from disk — never re-runs the device
        ready: List[Job] = []
        for job in batch:
            cached = svc_jobs.find_result(
                self.service.artifact_dir, job.digest
            )
            if cached is not None:
                self.queue.mark_done(job, cached)
                svc_jobs.delete_job_spec(
                    self.service.artifact_dir, job.digest
                )
                continue
            if job.stolen:
                info.steals_benefited += 1
            ready.append(job)
        now = time.time()
        deadline = now + self.queue.lease_s
        handed = []
        for j in ready:
            # the trace id rides the claim answer (ISSUE 19): the
            # worker tags its dispatch/upload spans with the SAME id
            # the submit minted — no shared state beyond this field
            tid = self.service.trace_of(j.digest)
            if self.spans is not None:
                # queue_wait closes at hand-out; a re-claim after a
                # steal re-emits it with the attempt count, so the
                # stitched timeline shows both waits
                self.spans.emit(
                    obs_trace.SPAN_QUEUE_WAIT, j.submitted_unix, now,
                    job=j.digest, trace=tid, worker=info.id,
                    stolen=int(j.stolen),
                    attempts=getattr(j, "attempts", 0),
                )
            handed.append({
                "id": j.id, "digest": j.digest,
                "spec": svc_jobs.spec_to_payload(j.spec),
                "stolen": j.stolen,
                "trace": tid,
            })
        return _json_body(200, {
            "jobs": handed,
            "deadline_unix": deadline,
            "lease_s": self.queue.lease_s,
            "epoch": self.epoch,
        })

    def _renew(self, doc):
        info, err = self._known(doc)
        if err is not None:
            return err
        digests = doc.get("digests") or []
        renewed, lost = self.queue.renew(info.id, digests)
        return _json_body(200, {
            "renewed": renewed, "lost": lost,
            "deadline_unix": time.time() + self.queue.lease_s,
        })

    def _complete(self, doc):
        info, err = self._known(doc)
        if err is not None:
            return err
        done = doc.get("done") or []
        failed = doc.get("failed") or {}
        acked = dup = 0
        for digest in done:
            job = self.queue.get_by_digest(digest)
            t_verify = time.time()
            result = svc_jobs.find_result(
                self.service.artifact_dir, digest
            )
            if result is not None and info.mode != "remote" \
                    and self.spans is not None:
                # shared-fs jobs never cross _accept_result, so the
                # signature check above IS their verify hop — witness
                # it (remote uploads were witnessed at upload time)
                self.spans.emit(
                    obs_trace.SPAN_VERIFY, t_verify, time.time(),
                    job=digest, trace=self.service.trace_of(digest),
                )
            if job is None:
                dup += 1  # finished after a restart reset the registry
                continue
            if result is None:
                if job.worker != info.id:
                    dup += 1  # a non-owner's resultless claim is noise
                    continue
                self.queue.mark_failed(
                    job, "completion reported but no valid signed "
                    "result on disk"
                )
                info.jobs_failed += 1
                continue
            before = self.queue.stats_counters["dup_completions"]
            self.queue.mark_done(job, result)
            if self.queue.stats_counters["dup_completions"] > before:
                dup += 1
            else:
                acked += 1
                info.jobs_done += 1
            svc_jobs.delete_job_spec(self.service.artifact_dir, digest)
            self.service.publish_job(job)
        for digest, msg in failed.items():
            job = self.queue.get_by_digest(digest)
            if job is None:
                continue
            # only the CURRENT owner may fail a job: a stalled worker
            # whose batch was stolen reports failures for jobs another
            # worker is validly running (or that were requeued) — those
            # reports are late noise, not verdicts. The done path needs
            # no such guard (results are idempotent; failures are not).
            if job.worker != info.id:
                dup += 1
                continue
            self.queue.mark_failed(job, str(msg))
            info.jobs_failed += 1
            svc_jobs.delete_job_spec(
                self.service.artifact_dir, digest
            )
            self.service.publish_job(job)
        info.batches += 1
        if doc.get("dispatch_s"):
            d = float(doc["dispatch_s"])
            info.last_dispatch_s = d
            if not info.first_dispatch_s:
                info.first_dispatch_s = d
            # the measured profile (ISSUE 19): first sample seeds the
            # EWMA, then 0.7/0.3 smoothing — slow enough to damp one
            # cold compile, fast enough to notice a degraded host
            info.ewma_dispatch_s = (
                d if not info.ewma_dispatch_s
                else 0.7 * info.ewma_dispatch_s + 0.3 * d
            )
        if doc.get("probable_hits") is not None:
            try:
                info.probable_hits = int(doc["probable_hits"])
            except (TypeError, ValueError):
                pass
        pushed = doc.get("metrics_text")
        if isinstance(pushed, str) and pushed:
            from tpusim.obs.emitters import parse_prometheus_text
            try:
                parse_prometheus_text(pushed)
            except ValueError:
                pass  # an unparseable push never poisons the merge
            else:
                info.metrics_text = pushed
        if doc.get("sweep_executables") is not None:
            info.sweep_executables = int(doc["sweep_executables"])
        if isinstance(doc.get("transfers"), dict):
            info.transfers = {
                k: int(v) for k, v in doc["transfers"].items()
            }
        return _json_body(200, {"acked": acked, "dup": dup})

    # ---- the fleet-aggregated /metrics (ISSUE 19) ----

    def _metrics(self):
        """GET /metrics, fleet edition: the coordinator's own snapshot
        (MonitorServer.metrics_text, present once a run record was
        published) + fleet-level gauges + every LIVE worker's pushed
        snapshot re-emitted under a `worker="<id>"` label. Every label
        value rides escape_label_value, `# TYPE` declarations are
        emitted once per name across the whole merge, and the result
        must round-trip parse_prometheus_text — the bench gate scrapes
        and re-parses it. Name spaces keep the merge collision-free:
        the base snapshot owns `tpusim_*` run-record names, the fleet
        gauges own `tpusim_fleet_*`, worker pushes own
        `tpusim_worker_*` (worker_metrics_text)."""
        from tpusim.obs.emitters import (escape_label_value,
                                         parse_prometheus_text)

        lines: List[str] = []
        typed = set()

        def declare(name: str):
            if name not in typed:
                typed.add(name)
                lines.append(f"# TYPE {name} gauge")

        # include_extra: the live per-kind latency summaries (ISSUE 20)
        # ride the merged scrape under the same names the tsdb samples
        monitor = getattr(self.service, "monitor", None)
        base = (monitor.metrics_text(include_extra=True)
                if monitor is not None else "")
        if base:
            for ln in base.rstrip("\n").splitlines():
                if ln.startswith("# TYPE "):
                    parts = ln.split()
                    if len(parts) >= 3:
                        typed.add(parts[2])
                lines.append(ln)
        now = time.time()
        declare("tpusim_fleet_workers_live")
        lines.append(
            f"tpusim_fleet_workers_live {self.registry.live_count(now)}"
        )
        declare("tpusim_fleet_queue_depth")
        lines.append(f"tpusim_fleet_queue_depth {self.queue.depth()}")
        for fam, depth in sorted(self.queue.family_depths().items()):
            declare("tpusim_fleet_family_depth")
            lines.append(
                'tpusim_fleet_family_depth{family="%s"} %d'
                % (escape_label_value(fam), depth)
            )
        with self.registry._lock:
            snapshot = list(self.registry.workers.values())
        for w in sorted(snapshot, key=lambda w: w.id):
            if not w.metrics_text:
                continue
            if not w.live(now, self.registry.live_window_s):
                continue  # a dead worker's last push is history, not state
            try:
                series = parse_prometheus_text(w.metrics_text)
            except ValueError:
                continue  # _complete validates, but never trust stale state
            wl = escape_label_value(w.id)
            for (name, labels) in sorted(series):
                declare(name)
                pairs = [
                    f'{k}="{escape_label_value(v)}"' for k, v in labels
                ] + [f'worker="{wl}"']
                lines.append(
                    f"{name}{{{','.join(pairs)}}} {series[(name, labels)]}"
                )
        text = "\n".join(lines) + "\n"
        return (200, "text/plain; version=0.0.4; charset=utf-8",
                text.encode())

    # ---- restart recovery (the lease-file half) ----

    def adopt_leases(self, out=None) -> int:
        """Coordinator-restart recovery (runs after recover_pending_jobs
        requeued the pending specs): a job whose lease FILE is still
        LIVE — within deadline + skew — belongs to a worker that may
        well still be computing it, so re-attach the claim instead of
        letting the queue hand it out twice; expired files are cleaned
        (their jobs stay queued — already stolen, in effect). Returns
        the number of adopted jobs."""
        adopted = 0
        for digest, lease in svc_leases.scan_leases(
            self.service.artifact_dir
        ):
            job = self.queue.get_by_digest(digest)
            if svc_leases.lease_expired(lease):
                svc_leases.delete_lease(self.service.artifact_dir, digest)
                self.queue.stats_counters["lease_expired"] += 1
                self._audit("lease_expired", job=digest,
                            worker=str(lease.get("worker") or ""),
                            reason="expired-at-adoption")
                continue
            if job is None or job.status != "queued":
                continue
            wid = str(lease.get("worker") or "")
            info = self.registry.register(
                wid, lease.get("pid") or 0, ""
            )
            claimed = self.queue.claim_specific(
                wid, [digest], float(lease["deadline_unix"])
            )
            adopted += len(claimed)
            if claimed and out is not None:
                print(
                    f"[fleet] adopted live lease of {wid} on "
                    f"{digest[:12]}… (deadline in "
                    f"{lease['deadline_unix'] - time.time():.1f}s)",
                    file=out,
                )
            info.last_seen_unix = time.time()
        return adopted

    # ---- the /queue aggregation fields ----

    def queue_fields(self) -> dict:
        from tpusim.svc.auth import describe as auth_describe

        rows = self.registry.describe(self.queue)
        out = {
            "workers": rows,
            "workers_live": self.registry.live_count(),
            "batches_run": sum(r["batches"] for r in rows.values()),
            "sweep_executables": sum(
                r["sweep_executables"] for r in rows.values()
            ),
            "transfer": dict(self.transfers),
            # the HA + auth surfaces (ISSUE 17): role/epoch for the
            # operator, auth armed-or-not (NEVER token material), and
            # the families currently starved for a capable worker
            "role": self.role,
            "epoch": self.epoch,
            "auth": auth_describe(self.token),
            "starved_families": self.starved_families(),
        }
        if self.supervisor is not None:
            # respawns, backoff, breaker state + reason, autoscale
            # counters — /queue "says why" (ISSUE 13)
            out["supervisor"] = self.supervisor.describe()
        return out

    def health(self):
        """MonitorServer.health_hook: the fleet coordinator is healthy
        while ANY worker is live (the ISSUE 12 contract) AND the
        supervisor's crash-loop circuit breaker is closed (ISSUE 13):
        a breaker held open means the fleet cannot self-heal — that is
        a loud 503, not three quiet respawn attempts per second."""
        live = self.registry.live_count()
        ok = live > 0
        extra = {
            "workers_live": live,
            "workers_known": len(self.registry.workers),
            # role + epoch (ISSUE 17): `leader|standby` here; the
            # /healthz handler overrides role to `draining` during a
            # graceful shutdown (MonitorServer owns that flag)
            "role": self.role,
            "epoch": self.epoch,
        }
        if self.role == "standby":
            # a standby with no workers is doing its one job: watching
            # the leadership lease. It is healthy by existing.
            return True, extra
        if self.supervisor is not None:
            sup_ok, sup_fields = self.supervisor.healthy()
            extra.update(sup_fields)
            ok = ok and sup_ok
        return ok, extra


# ---------------------------------------------------------------------------
# The worker process (`tpusim worker --join URL`)
# ---------------------------------------------------------------------------


def _with_backoff(call, max_attempts: int = 8, stop_event=None):
    """The shared kube_client.with_backoff schedule (ISSUE 14 satellite:
    the loop moved INTO kube_client beside retryable_conn_excs /
    is_retryable_status so the fleet, the extender client, and the rest
    client all ride one implementation; this thin alias keeps the fleet's
    internal call sites and test monkeypatch points stable)."""
    from tpusim.io.kube_client import with_backoff

    return with_backoff(call, max_attempts=max_attempts,
                        stop_event=stop_event)


def _trace_headers(token: str, trace: str) -> dict:
    """Auth + trace-propagation headers for one fleet hop (ISSUE 19):
    the trace id rides X-Tpusim-Trace on every worker→coordinator POST
    so both sides tag the same journey without shared state."""
    headers = bearer_headers(token)
    if trace:
        headers[obs_trace.TRACE_HEADER] = str(trace)
    return headers


def _post(url: str, path: str, doc: dict, timeout: float = 30.0,
          max_attempts: int = 8, stop_event=None, token: str = "",
          trace: str = ""):
    from tpusim.svc.client import _request

    full = url.rstrip("/") + path
    data = json.dumps(doc).encode()
    return _with_backoff(
        lambda: _request(full, data, timeout=timeout,
                         headers=_trace_headers(token, trace)),
        max_attempts=max_attempts, stop_event=stop_event,
    )


def _post_bytes(url: str, path: str, data: bytes, timeout: float = 60.0,
                max_attempts: int = 8, token: str = "", trace: str = ""):
    """POST raw bytes (the signed-result upload) on the same backoff
    schedule as _post."""
    from tpusim.svc.client import _request

    full = url.rstrip("/") + path
    return _with_backoff(
        lambda: _request(full, data, timeout=timeout,
                         content_type="application/octet-stream",
                         headers=_trace_headers(token, trace)),
        max_attempts=max_attempts,
    )


class CoordinatorRing:
    """Multi-coordinator failover client (ISSUE 17): an ordered URL
    list (`--join u1,u2`), one live cursor. Every post rides the
    shared `with_backoff` schedule against the CURRENT coordinator;
    when that coordinator stays unreachable past the whole schedule —
    or keeps answering 503 (a standby, or a draining leader) — the
    cursor rotates to the next URL and the call is retried there. With
    a single URL this degrades to exactly the pre-HA behavior (the
    final answer or exception surfaces).

    Carries the bearer token so every mutating call through the ring
    is authenticated; the token itself never appears in any log line.
    """

    def __init__(self, urls, token: str = "", stop_event=None):
        from tpusim.io.kube_client import parse_url_list

        self.urls = parse_url_list(urls)
        self.token = str(token or "")
        self.stop_event = stop_event
        self._idx = 0

    @property
    def url(self) -> str:
        return self.urls[self._idx]

    def rotate(self) -> str:
        self._idx = (self._idx + 1) % len(self.urls)
        return self.url

    def _attempts_per_url(self, max_attempts: int) -> int:
        # with alternatives available, give up on one coordinator
        # sooner — the schedule is shared, the budget is split
        return max_attempts if len(self.urls) == 1 else min(max_attempts, 3)

    def _drive(self, fn, max_attempts: int):
        from tpusim.io.kube_client import retryable_conn_excs
        from tpusim.svc.client import ServiceError

        last_exc = None
        answer = None
        per_url = self._attempts_per_url(max_attempts)
        for i in range(len(self.urls)):
            try:
                answer = fn(self.url, per_url)
            except retryable_conn_excs() as err:
                last_exc = err
                if len(self.urls) > 1:
                    self.rotate()
                continue
            code = answer[0]
            if code == 503 and i < len(self.urls) - 1:
                # a standby (or a draining leader) said "not me" —
                # the next coordinator in the ring may be leading
                self.rotate()
                continue
            return answer
        if answer is not None:
            return answer
        if last_exc is not None:
            raise last_exc
        raise ServiceError(f"no coordinator reachable in {self.urls}")

    def post(self, path: str, doc: dict, timeout: float = 30.0,
             max_attempts: int = 8, stop_event=None, trace: str = ""):
        return self._drive(
            lambda u, ma: _post(
                u, path, doc, timeout=timeout, max_attempts=ma,
                stop_event=stop_event or self.stop_event,
                token=self.token, trace=trace,
            ),
            max_attempts,
        )

    def post_bytes(self, path: str, data: bytes, timeout: float = 60.0,
                   max_attempts: int = 8, trace: str = ""):
        return self._drive(
            lambda u, ma: _post_bytes(
                u, path, data, timeout=timeout, max_attempts=ma,
                token=self.token, trace=trace,
            ),
            max_attempts,
        )


def _get_bytes(url: str, path: str, offset: int = 0,
               timeout: float = 60.0):
    """(code, headers, raw bytes) of one coordinator GET; offset > 0
    sends a Range header (the partial-transfer resume)."""
    import urllib.error
    import urllib.request

    req = urllib.request.Request(url.rstrip("/") + path)
    if offset > 0:
        req.add_header("Range", f"bytes={int(offset)}-")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers or {}), e.read()


def new_transfer_counters() -> dict:
    """The worker-side transfer counters reported on every complete
    POST and surfaced per worker in /workers (ISSUE 13)."""
    return {
        "downloads": 0, "download_bytes": 0, "resumed": 0,
        "sha_retries": 0, "uploads": 0, "upload_bytes": 0,
        "upload_failed": 0,
    }


def worker_metrics_text(served: int, jobs_done: int, jobs_failed: int,
                        dispatch_s: float, probable_hits: int,
                        counters: dict) -> str:
    """The worker's own exposition-format snapshot, pushed on every
    complete POST and re-emitted under a `worker="<id>"` label by the
    coordinator's merged /metrics (ISSUE 19). Unlabeled here on
    purpose: the coordinator owns the worker label, so the
    escape_label_value hygiene lives at exactly one merge point."""
    pairs = [
        ("tpusim_worker_batches", int(served)),
        ("tpusim_worker_jobs_done", int(jobs_done)),
        ("tpusim_worker_jobs_failed", int(jobs_failed)),
        ("tpusim_worker_last_dispatch_seconds", round(dispatch_s, 6)),
        ("tpusim_worker_probable_compile_hits", int(probable_hits)),
        ("tpusim_worker_download_bytes",
         int(counters.get("download_bytes") or 0)),
        ("tpusim_worker_upload_bytes",
         int(counters.get("upload_bytes") or 0)),
    ]
    lines = []
    for name, val in pairs:
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {val}")
    return "\n".join(lines) + "\n"


def _part_path(dest: str) -> str:
    # pid-scoped so two workers sharing one trace cache never append
    # into each other's partial transfer
    return f"{dest}.{os.getpid()}.part"


def _adopt_orphan_part(dest: str) -> None:
    """Claim a DEAD predecessor's partial download so crash-resume
    actually reaches across a respawn: pid-scoped .part names keep live
    writers apart, but a worker that was kill -9'd mid-transfer leaves
    a part its respawned successor (new pid) could neither resume nor
    clean. Adopt the largest part whose pid no longer exists (a dead
    pid cannot write again, so the rename is race-free against its
    owner); unlink the other dead ones."""
    mine = _part_path(dest)
    if os.path.isfile(mine):
        return
    d, base = os.path.split(dest)
    dead = []
    try:
        names = os.listdir(d or ".")
    except OSError:
        return
    for fname in names:
        if not (fname.startswith(base + ".") and fname.endswith(".part")):
            continue
        pid_s = fname[len(base) + 1:-len(".part")]
        if not pid_s.isdigit() or int(pid_s) == os.getpid():
            continue
        try:
            os.kill(int(pid_s), 0)
            continue  # owner still alive: hands off
        except ProcessLookupError:
            pass
        except (PermissionError, OSError):
            continue  # exists (other uid) or unknowable: hands off
        path = os.path.join(d, fname)
        try:
            dead.append((os.path.getsize(path), path))
        except OSError:
            pass
    if not dead:
        return
    dead.sort(reverse=True)
    try:
        os.replace(dead[0][1], mine)
    except OSError:
        return
    for _, path in dead[1:]:
        try:
            os.unlink(path)
        except OSError:
            pass


def fetch_trace_file(url: str, rel: str, dest: str, sha256: str,
                     counters: Optional[dict] = None, out=None,
                     max_attempts: int = 8) -> str:
    """Download one hosted trace file to `dest`, resuming a partial
    transfer (Range from the .part file's size) and verifying the raw
    bytes against the handshake's sha256. A verification miss wipes the
    partial file and re-downloads from byte 0 ONCE; a second miss is a
    loud failure (the coordinator is serving different bytes than it
    advertised — version skew, never something to paper over). The
    completed file lands by atomic rename, so a cached dest is always
    whole."""
    from tpusim.io.storage import file_sha256
    from tpusim.svc.client import ServiceError

    if counters is None:
        counters = new_transfer_counters()
    _adopt_orphan_part(dest)
    part = _part_path(dest)
    for round_ in (1, 2):
        offset = os.path.getsize(part) if os.path.isfile(part) else 0
        if offset > 0 and sha256 and file_sha256(part) == sha256:
            # the predecessor had actually finished the bytes and died
            # between write and rename — nothing left to transfer
            os.replace(part, dest)
            return dest
        if offset > 0:
            counters["resumed"] += 1
        code, headers, data = _with_backoff(
            lambda: _get_bytes(url, rel, offset=offset),
            max_attempts=max_attempts,
        )
        if code == 416:
            # stale oversized .part (the file shrank server-side):
            # restart clean
            try:
                os.unlink(part)
            except OSError:
                pass
            offset = 0
            code, headers, data = _with_backoff(
                lambda: _get_bytes(url, rel, offset=0),
                max_attempts=max_attempts,
            )
        if code not in (200, 206):
            raise ServiceError(f"GET {rel} -> HTTP {code}")
        mode = "ab" if (code == 206 and offset > 0) else "wb"
        with open(part, mode) as f:
            f.write(data)
        counters["downloads"] += 1
        counters["download_bytes"] += len(data)
        got = file_sha256(part)
        want = sha256 or (headers or {}).get("X-Content-SHA256") or ""
        if not want or got == want:
            os.replace(part, dest)
            return dest
        counters["sha_retries"] += 1
        if out is not None:
            print(
                f"[worker] {rel}: sha256 mismatch after download "
                f"(got {got[:12]}…, want {want[:12]}…) — "
                f"{'re-downloading from byte 0' if round_ == 1 else 'giving up'}",
                file=out,
            )
        try:
            os.unlink(part)
        except OSError:
            pass
    raise ServiceError(
        f"downloaded {rel} twice and the sha256 still mismatches the "
        "register handshake (coordinator/worker version or content "
        "skew) — refusing to parse it"
    )


def ensure_local_trace(url: str, name: str, meta: dict, cache_dir: str,
                       counters: Optional[dict] = None, out=None):
    """The remote worker's trace acquisition: a local cache keyed by
    the trace CONTENT digest (`<cache>/traces/<digest>/{nodes,pods}.csv`)
    — a cache hit (file present, sha256 matching the handshake) costs
    zero HTTP; a miss/mismatch re-downloads with resume; and the parsed
    trace must reproduce the coordinator's content digest exactly or
    the worker refuses to serve (the ISSUE 12 skew contract, now over
    the wire). Returns a TraceRef."""
    from tpusim.io.storage import file_sha256
    from tpusim.svc.client import ServiceError
    from tpusim.svc.worker import load_trace

    ddir = os.path.join(cache_dir, "traces", str(meta["digest"]))
    os.makedirs(ddir, exist_ok=True)
    paths = {}
    for which, sha_key in (("nodes.csv", "nodes_sha256"),
                           ("pods.csv", "pods_sha256")):
        dest = os.path.join(ddir, which)
        sha = str(meta.get(sha_key) or "")
        if os.path.isfile(dest) and sha and file_sha256(dest) == sha:
            paths[which] = dest
            continue
        if os.path.isfile(dest):
            # cached bytes no longer match the handshake: force a
            # fresh download (the re-download-on-mismatch contract)
            if counters is not None:
                counters["sha_retries"] += 1
            try:
                os.unlink(dest)
            except OSError:
                pass
        fetch_trace_file(
            url, f"/traces/{name}/{which}", dest, sha,
            counters=counters, out=out,
        )
        paths[which] = dest
    t = load_trace(
        name, paths["nodes.csv"], paths["pods.csv"],
        max_pods=int(meta.get("max_pods") or 0),
    )
    if t.digest != meta["digest"]:
        raise ServiceError(
            f"hosted trace {name!r} content-digest mismatch after a "
            f"verified download: coordinator {meta['digest'][:12]}… vs "
            f"local parse {t.digest[:12]}… (code version skew)"
        )
    return t


def resolve_worker_mode(mode: str, reg: dict) -> str:
    """auto → shared-fs iff the coordinator's artifact dir AND every
    hosted trace CSV are readable from this host (same machine or a
    genuinely shared filesystem — the digest checks still guard
    content skew); anything unreachable means this worker runs in
    remote mode: digest-verified downloads, result uploads, lease
    POSTs. Explicit modes pass through untouched."""
    if mode in ("shared-fs", "remote"):
        return mode
    if mode not in ("", "auto"):
        raise ValueError(
            f"worker mode must be auto | shared-fs | remote, got {mode!r}"
        )
    if not os.path.isdir(reg.get("artifact_dir") or ""):
        return "remote"
    for meta in (reg.get("traces") or {}).values():
        if not (os.path.isfile(meta.get("nodes_csv") or "")
                and os.path.isfile(meta.get("pods_csv") or "")):
            return "remote"
    return "shared-fs"


def run_worker(url: str, worker_id: str = "", poll_s: float = 0.2,
               max_batches: int = 0, table_cache_dir: str = "",
               out=None,
               stop_event=None, mode: str = "auto",
               cache_dir: str = "", token: str = "",
               caps: Optional[dict] = None) -> int:
    """The fleet worker's main loop: register, then claim/run/complete
    until stopped (or `max_batches` served — the test/smoke bound).
    Returns the number of batches served. SIGTERM handling is the
    caller's (the CLI installs a drain flag via `stop_event`); a
    `kill -9` needs no handling — that is what the leases are for.

    `mode` (ISSUE 13): "shared-fs" reads the coordinator's trace CSVs
    by path and writes results straight into the shared artifact dir
    (the ISSUE 12 behavior); "remote" needs NO shared filesystem —
    traces are downloaded into a digest-keyed local cache, results are
    written locally then UPLOADED (the coordinator digest-verifies
    before the atomic rename), and leases are staked/released via POST
    /leases; "auto" (default) probes the handshake's paths and picks.
    Every POST rides the shared capped-backoff-with-jitter schedule
    honoring Retry-After, so a coordinator restart mid-claim is a
    stall, not a dead worker.

    `url` may be a comma-separated coordinator LIST (ISSUE 17): the
    worker rotates through it via CoordinatorRing when the current
    coordinator dies or demotes to standby, re-registering after an
    epoch bump — a coordinator failover is a stall, not lost work.
    `token` authenticates every mutating POST; `caps` are the
    capability tags declared at registration (default:
    svc.worker.local_caps())."""
    from tpusim.io.kube_client import retryable_conn_excs
    from tpusim.svc.client import ServiceError
    from tpusim.svc.worker import Worker, load_trace, local_caps

    host = os.uname().nodename if hasattr(os, "uname") else ""
    if caps is None:
        caps = local_caps()
    ring = CoordinatorRing(url, token=token, stop_event=stop_event)
    try:
        code, _, reg = ring.post("/workers/register", {
            "worker": worker_id, "pid": os.getpid(), "host": host,
            "caps": caps,
        }, stop_event=stop_event)
    except retryable_conn_excs() as err:
        raise ServiceError(
            f"could not reach any coordinator in {ring.urls} "
            f"({type(err).__name__}: {err})"
        )
    if code == 401:
        raise ServiceError(
            "POST /workers/register -> HTTP 401: bearer token missing "
            "or rejected (--token-file / TPUSIM_FLEET_TOKEN)"
        )
    if code != 200:
        raise ServiceError(
            f"POST /workers/register -> HTTP {code}: {reg}"
        )
    wid = reg["worker"]
    lease_s = float(reg["lease_s"])
    epoch = int(reg.get("epoch") or 0)
    counters = new_transfer_counters()
    # the flight-recorder state (ISSUE 19): trace ids arrive on the
    # claim answer, keyed by digest; every subsequent hop for that job
    # rides the id as an X-Tpusim-Trace header. current_trace is the
    # last batch's lead id — the claim/re-register hops' best context.
    trace_ids: Dict[str, str] = {}
    current_trace = ""
    probable_hits = 0
    jobs_done_total = 0
    jobs_failed_total = 0

    def stamp(doc: dict) -> dict:
        # every mirrored lease/complete/claim op carries the
        # coordinator epoch (ISSUE 17) — the fencing stamp
        if epoch:
            doc["epoch"] = epoch
        return doc

    def re_register() -> int:
        # after a takeover the ring may already point at the new
        # leader; registering there adopts ITS epoch for all
        # subsequent stamps
        nonlocal epoch
        code, _, r = ring.post("/workers/register", {
            "worker": wid, "pid": os.getpid(), "host": host,
            "mode": mode, "caps": caps,
        }, trace=current_trace)
        if code == 200:
            new_epoch = int(r.get("epoch") or 0)
            if out is not None and new_epoch != epoch:
                print(
                    f"[worker {wid}] re-registered at {ring.url} "
                    f"(epoch {epoch} -> {new_epoch})", file=out,
                )
            epoch = new_epoch
        return code

    mode = resolve_worker_mode(mode, reg)
    # record the resolved topology in the roster (register is an
    # idempotent update — /workers shows mode per worker)
    re_register()

    traces = {}
    if mode == "remote":
        if not cache_dir:
            import tempfile

            cache_dir = os.path.join(
                tempfile.gettempdir(), "tpusim-worker-cache"
            )
        artifact_dir = os.path.join(cache_dir, "artifacts")
        os.makedirs(artifact_dir, exist_ok=True)
        # remote-mode spans land in the worker's LOCAL artifact cache —
        # `tpusim trace` stitches them only where the dir is shared
        # (the documented limitation; the local fleet shares it)
        recorder = obs_trace.SpanRecorder(artifact_dir, f"worker-{wid}")
        for name, meta in (reg.get("traces") or {}).items():
            with recorder.span(obs_trace.SPAN_TRANSFER,
                               trace_name=name) as sp:
                traces[name] = ensure_local_trace(
                    ring.url, name, meta, cache_dir, counters=counters,
                    out=out,
                )
                sp.meta["download_bytes"] = counters["download_bytes"]
    else:
        artifact_dir = reg["artifact_dir"]
        recorder = obs_trace.SpanRecorder(artifact_dir, f"worker-{wid}")
        for name, meta in (reg.get("traces") or {}).items():
            t = load_trace(
                name, meta["nodes_csv"], meta["pods_csv"],
                max_pods=int(meta.get("max_pods") or 0),
            )
            if t.digest != meta["digest"]:
                # trace skew: this worker would compute results under a
                # DIFFERENT digest vocabulary — refuse to serve
                raise ServiceError(
                    f"hosted trace {name!r} digest mismatch: coordinator "
                    f"{meta['digest'][:12]}… vs local {t.digest[:12]}… "
                    "(differing CSVs or code version)"
                )
            traces[name] = t

    queue = JobQueue(
        maxsize=max(4 * int(reg["lane_width"]), 8),
        lane_width=int(reg["lane_width"]), lease_s=lease_s,
    )
    worker = Worker(
        queue, traces, artifact_dir, bucket=int(reg.get("bucket") or 512),
        table_cache_dir=table_cache_dir,
        worker_id=wid, lease_files=True,
    )

    def renew_remote(digests):
        # one 409 (epoch bump / wiped roster) earns an immediate
        # re-register + retry so in-flight work keeps its lease across
        # a coordinator failover instead of riding out a steal
        digests = list(digests)
        for attempt in (1, 2):
            code, _, doc = ring.post(
                "/workers/renew",
                stamp({"worker": wid, "digests": digests}),
                trace=trace_ids.get(digests[0], "") if digests else "",
            )
            if code == 409 and attempt == 1:
                re_register()
                continue
            if code != 200:
                return []
            return doc.get("lost") or []
        return []

    worker.renew_cb = renew_remote
    if mode == "remote":
        # the lease FILES live on the coordinator's disk (adoption and
        # reaping are unchanged) — a no-shared-fs worker mirrors them
        # over POST /leases; short retry budgets keep the keeper thread
        # from stalling a whole renewal period on a flaky link
        def _stake(members):
            members = list(members)
            return ring.post(
                "/leases",
                stamp({"op": "stake", "worker": wid,
                       "pid": os.getpid(), "members": members}),
                max_attempts=3,
                trace=(trace_ids.get(members[0], "")
                       if members else ""),
            )

        def _release(members):
            members = list(members)
            return ring.post(
                "/leases",
                stamp({"op": "release", "worker": wid,
                       "members": members}),
                max_attempts=3,
                trace=(trace_ids.get(members[0], "")
                       if members else ""),
            )

        worker.lease_stake_cb = _stake
        worker.lease_release_cb = _release

    if out is not None:
        print(
            f"[worker {wid}] joined {ring.url} ({mode}, pid "
            f"{os.getpid()}, {len(traces)} trace(s), lease "
            f"{lease_s:.1f}s)", file=out,
        )

    served = 0
    while stop_event is None or not stop_event.is_set():
        t_claim = time.time()
        try:
            # the IDLE path carries the stop_event: a drain must not
            # wait out the whole backoff schedule against a draining
            # coordinator's 503s (uploads/completions below finish
            # regardless — that is the graceful half)
            code, _, doc = ring.post("/workers/claim",
                                     stamp({"worker": wid}),
                                     stop_event=stop_event,
                                     trace=current_trace)
        except retryable_conn_excs():
            # every coordinator down longer than the whole backoff
            # schedule: recovery requeues everything; keep polling
            time.sleep(max(poll_s, 0.5))
            continue
        if code == 409:
            # roster wiped by a coordinator restart, or our epoch
            # stamp is stale after a takeover — re-register (the ring
            # already points at whichever coordinator answered)
            re_register()
            continue
        if code != 200:
            time.sleep(max(poll_s, 0.5))
            continue
        resp_epoch = int((doc or {}).get("epoch") or 0)
        if resp_epoch and epoch and resp_epoch < epoch:
            # the worker-side fence (ISSUE 17): a resurrected
            # old-epoch leader handed us work — refuse it and move to
            # the coordinator whose epoch matches what we adopted
            if out is not None:
                print(
                    f"[worker {wid}] rejecting claim from {ring.url} "
                    f"(epoch {resp_epoch} < {epoch} — deposed "
                    "leader); rotating", file=out,
                )
            ring.rotate()
            time.sleep(max(poll_s, 0.5))
            continue
        jobs_docs = doc.get("jobs") or []
        if not jobs_docs:
            time.sleep(poll_s)
            continue
        # adopt the claim answer's trace ids (ISSUE 19): each job's
        # remaining hops — dispatch, upload, complete, lease mirror —
        # tag themselves with the id the submit minted
        t_claimed = time.time()
        for jd in jobs_docs:
            d = str(jd.get("digest") or "")
            tid = str(jd.get("trace") or "")
            if d:
                trace_ids[d] = tid
            recorder.emit(obs_trace.SPAN_CLAIM, t_claim, t_claimed,
                          job=d, trace=tid,
                          stolen=int(jd.get("stolen") or 0))
        current_trace = str(jobs_docs[0].get("trace") or "")

        batch, skew_failed = [], {}
        for lane, jd in enumerate(jobs_docs):
            try:
                spec = svc_jobs.validate_job(jd["spec"])
                digest = svc_jobs.job_digest(
                    spec, traces[spec.trace].digest
                )
                if digest != jd["digest"]:
                    raise ValueError(
                        "job digest mismatch (coordinator/worker "
                        "version skew)"
                    )
            except (KeyError, ValueError) as err:
                skew_failed[jd.get("digest", "?")] = str(err)
                continue
            batch.append(Job(
                id=jd["id"], spec=spec, digest=jd["digest"],
                status="batched", batch=served + 1, lane=lane,
                worker=wid,
            ))
        # one dispatch span per job, OPEN across run_batch: a kill -9
        # mid-batch leaves begins with no ends — the stitcher renders
        # them ABANDONED, the visible corpse the steal accounts for
        dispatch_spans = {
            j.digest: recorder.begin(
                obs_trace.SPAN_DISPATCH, job=j.digest,
                trace=trace_ids.get(j.digest, ""), lane=j.lane,
                stolen=int(j.stolen),
            )
            for j in batch
        }
        if batch:
            worker.run_batch(batch)
            served += 1
            # the compile-cache heuristic (obs.spans.note_compile_cache):
            # a batch dispatch wall under 2 s means the persistent
            # cache almost certainly served the executable
            if 0 < worker.last_dispatch_s < 2.0:
                probable_hits += 1
        for j in batch:
            recorder.end(dispatch_spans[j.digest], status=j.status,
                         dispatch_s=worker.last_dispatch_s)
        done = [j.digest for j in batch if j.status == "done"]
        failed = {
            j.digest: j.error for j in batch if j.status == "failed"
        }
        failed.update(skew_failed)
        if mode == "remote" and done:
            # the upload half (ISSUE 13): ship each signed result's
            # BYTES to the coordinator, which digest-verifies before
            # the atomic rename — completion below then finds them on
            # ITS disk. An upload the coordinator rejects (impossible
            # for bytes our own read just verified, short of a forged
            # proxy) demotes the job to failed so the loud complete
            # path reports it.
            still_done = []
            for d in done:
                data = svc_jobs.result_bytes(artifact_dir, d)
                if data is None:
                    failed[d] = "local signed result vanished/torn"
                    continue
                t_upload = time.time()
                try:
                    code, _, up = ring.post_bytes(
                        f"/results/{d}", data,
                        trace=trace_ids.get(d, ""),
                    )
                except retryable_conn_excs():
                    code, up = 0, {"error": "coordinator unreachable"}
                recorder.emit(obs_trace.SPAN_UPLOAD, t_upload,
                              time.time(), job=d,
                              trace=trace_ids.get(d, ""),
                              code=code, bytes=len(data))
                if code == 200:
                    counters["uploads"] += 1
                    counters["upload_bytes"] += len(data)
                    still_done.append(d)
                elif 400 <= code < 500:
                    # a definitive rejection (torn/forged verdict from
                    # the coordinator) is terminal — report it loudly
                    counters["upload_failed"] += 1
                    failed[d] = (
                        f"result upload -> HTTP {code}: "
                        f"{(up or {}).get('error', up)}"
                    )
                else:
                    # transport failure / 5xx after the whole backoff
                    # schedule: the result is correct and sitting in
                    # local scratch — do NOT report the job at all, so
                    # the lease expires and a steal either re-runs it
                    # or (after our later re-upload) answers from disk.
                    # Demoting to failed here would make a transient
                    # partition terminal.
                    counters["upload_failed"] += 1
                    if out is not None:
                        print(
                            f"[worker {wid}] result upload for "
                            f"{d[:12]}… failed transiently (HTTP "
                            f"{code}); leaving the job to lease "
                            "expiry", file=out,
                        )
            done = still_done
        elif done:
            # shared-fs publish half: run_batch already wrote the
            # signed results into the shared artifact dir — witness
            # each publish so the stitched timeline is mode-invariant
            # (upload = the result reaching the shared store; the
            # coordinator's verify span lands at complete time)
            for d in done:
                t_pub = time.time()
                data = svc_jobs.result_bytes(artifact_dir, d)
                recorder.emit(obs_trace.SPAN_UPLOAD, t_pub, time.time(),
                              job=d, trace=trace_ids.get(d, ""),
                              bytes=len(data) if data else 0,
                              shared_fs=1)
        jobs_done_total += len(done)
        jobs_failed_total += len(failed)
        for attempt in (1, 2):
            try:
                code, _, _ack = ring.post("/workers/complete", stamp({
                    "worker": wid, "done": done, "failed": failed,
                    "dispatch_s": worker.last_dispatch_s,
                    "sweep_executables": worker.sweep_executables(),
                    "transfers": counters,
                    # the measured-profile push (ISSUE 19)
                    "probable_hits": probable_hits,
                    "metrics_text": worker_metrics_text(
                        served, jobs_done_total, jobs_failed_total,
                        worker.last_dispatch_s, probable_hits,
                        counters,
                    ),
                }), trace=current_trace)
            except retryable_conn_excs():
                # results + spec deletions are already on disk — a
                # restarted coordinator reconciles from there (its
                # claim shortcut)
                break
            if code == 409 and attempt == 1:
                # epoch bump mid-batch: adopt the new epoch and report
                # the SAME completion once more — mark_done dedups, so
                # across-epoch duplicates are silent, never conflicts
                re_register()
                continue
            break
        if out is not None and batch:
            print(
                f"[worker {wid}] batch {served}: {len(done)} done, "
                f"{len(failed)} failed "
                f"({worker.last_dispatch_s:.2f}s dispatch)", file=out,
            )
        # finished journeys no longer need their trace ids (the map
        # would otherwise grow one entry per job served, forever)
        for d in list(done) + list(failed):
            trace_ids.pop(d, None)
        if max_batches and served >= max_batches:
            break
    worker.stop()
    return served


# ---------------------------------------------------------------------------
# Local fleet spawning (`tpusim serve --jobs --workers N`)
# ---------------------------------------------------------------------------


def worker_command(url: str, table_cache_dir: str = "", mode: str = "",
                   cache_dir: str = "", token_file: str = "") -> List[str]:
    """The `tpusim worker --join` argv for one spawned child — shared
    by spawn_local_workers and the supervisor's spawn_fn (ISSUE 13).
    No --id: the coordinator assigns pid-scoped ids, so a respawned or
    later-joined child can never collide with (and inherit the stats
    of) an earlier worker's roster entry."""
    cmd = [sys.executable, "-m", "tpusim", "worker", "--join", url]
    if table_cache_dir:
        cmd += ["--table-cache-dir", table_cache_dir]
    if mode:
        cmd += ["--mode", mode]
    if cache_dir:
        cmd += ["--cache-dir", cache_dir]
    if token_file:
        # the token travels as a file PATH, never argv material — a
        # `ps` on the worker host shows the path, not the secret
        cmd += ["--token-file", token_file]
    return cmd


def spawn_local_workers(url: str, n: int, table_cache_dir: str = "",
                        out=None, token_file: str = "") -> List[subprocess.Popen]:
    """Spawn N `tpusim worker --join` processes against this
    coordinator. They inherit the environment (JAX_PLATFORMS,
    JAX_COMPILATION_CACHE_DIR etc.) and share the table cache dir and
    the one persistent compile cache (tpusim.compile_cache) — the warm
    state that makes a joiner's first batch skip the compile."""
    procs = []
    for _ in range(int(n)):
        cmd = worker_command(
            url, table_cache_dir=table_cache_dir, token_file=token_file,
        )
        procs.append(subprocess.Popen(cmd))
        if out is not None:
            print(f"[fleet] spawned worker process pid {procs[-1].pid}",
                  file=out)
    return procs


def stop_workers(procs, timeout: float = 10.0, out=None) -> None:
    """Drain the spawned fleet: SIGTERM each child (graceful — the
    CLI's stop flag finishes the in-flight batch), escalate to SIGKILL
    past the timeout (leases make even that safe)."""
    for p in procs:
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
    deadline = time.time() + timeout
    for p in procs:
        remaining = max(deadline - time.time(), 0.1)
        try:
            p.wait(remaining)
        except subprocess.TimeoutExpired:
            if out is not None:
                print(f"[fleet] worker pid {p.pid} ignored SIGTERM — "
                      "killing (leases cover it)", file=out)
            p.kill()
