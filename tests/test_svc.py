"""The queueing what-if replay service (tpusim.svc; ISSUE 7).

Pins the service contracts end-to-end:

  1. job validation + the grid expander (no device work);
  2. the digest vocabulary: deterministic, moves with every spec field
     and the trace content, identical jobs share — and the TABLE digest
     is tune-independent (the operand lift moved the per-pod type map
     from the table key to the run key);
  3. signed result persistence: round-trip, torn-file rejection
     (deleted + recomputed, never served), foreign-header rejection;
  4. batch formation: compatible jobs coalesce FIFO up to the lane
     width, incompatible jobs don't, full queues raise QueueFull and
     the HTTP plane answers 429 + Retry-After;
  5. POST-path bit-identity: every job's placements equal a standalone
     run with that weight vector/seed/tune factor baked into the
     config, duplicates answered from the digest cache;
  6. zero recompiles: two batches differing only in weights+tune share
     ONE compiled sweep executable (jit._cache_size() stable);
  7. per-job /progress (the heartbeat job-tag satellite) and the
     watch_dir TOCTOU fix.

The openb end-to-end acceptance (N concurrent jobs over real HTTP,
<= ceil(N/B) compiled sweeps, marginal cost bound) is slow-marked into
`make resume-smoke` — the tier-1 slice here stays on a tiny synthetic
cluster sharing one compiled family.
"""

import json
import os

import numpy as np
import pytest

from tpusim.io.trace import NodeRow, PodRow
from tpusim.sim.typical import TypicalPodsConfig
from tpusim.svc import jobs as svc_jobs
from tpusim.svc.api import JobService
from tpusim.svc.batcher import JobQueue, QueueFull
from tpusim.svc.worker import TraceRef, Worker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAM = [["FGDScore", 1000], ["BestFitScore", 500]]


def _mk_cluster(rng, n=16):
    return [
        NodeRow(f"n{i:03d}", 32000, 131072, int(g), "V100M16" if g else "")
        for i, g in enumerate(rng.choice([0, 2, 4, 8], n))
    ]


def _mk_pods(rng, n=40):
    out = []
    for i in range(n):
        gpu = int(rng.choice([0, 1, 2]))
        milli = 1000 if gpu > 1 else int(rng.choice([0, 300, 500, 1000]))
        if gpu == 0:
            milli = 0
        out.append(
            PodRow(f"p{i:04d}", int(rng.choice([1000, 2000, 4000])), 2048,
                   gpu, milli)
        )
    return out


@pytest.fixture(scope="module")
def trace():
    rng = np.random.default_rng(3)
    nodes, pods = _mk_cluster(rng), _mk_pods(rng)
    return TraceRef(
        "default", nodes, pods, svc_jobs.trace_digest(nodes, pods)
    )


def _standalone(trace, weights, seed, tune):
    """A standalone baked-config run over the hosted trace — the
    bit-identity oracle for one job."""
    from tpusim.sim.driver import Simulator, SimulatorConfig

    sim = Simulator(trace.nodes, SimulatorConfig(
        policies=tuple((n, int(w)) for (n, _), w in zip(FAM, weights)),
        gpu_sel_method="best", seed=seed, report_per_event=False,
        tuning_ratio=tune, shuffle_pod=False,
    ))
    sim.set_workload_pods(trace.pods)
    return sim.run()


def _service(trace, tmp_path, lane_width=4, queue_size=16):
    """An in-process service stack with a SYNCHRONOUS worker (no thread):
    tests drive batch formation deterministically via drain()."""
    queue = JobQueue(maxsize=queue_size, lane_width=lane_width)
    worker = Worker(queue, {"default": trace}, str(tmp_path))
    service = JobService(queue, worker, {"default": trace}, str(tmp_path))
    return queue, worker, service


def _drain(queue, worker):
    batches = 0
    while True:
        batch = queue.next_batch(timeout=0)
        if not batch:
            return batches
        worker.run_batch(batch)
        batches += 1


def _post(service, doc):
    """Drive the real POST surface (MonitorServer routes here)."""
    return service.handle("POST", "/jobs", json.dumps(doc).encode())


def _body(resp):
    return json.loads(resp[2].decode())


# ---------------------------------------------------------------------------
# 1. validation + grid expansion (no device)
# ---------------------------------------------------------------------------


def test_validate_job():
    spec = svc_jobs.validate_job({})
    assert spec.policies == svc_jobs.DEFAULT_POLICIES
    assert spec.weights == (1000,)  # defaults to the family weights
    assert spec.engine == "auto" and spec.tune == 0.0

    spec = svc_jobs.validate_job({
        "policies": FAM, "weights": [7, 9], "seed": 5, "tune": 1.5,
        "gpu_sel": "FGDScore", "engine": "table",
    })
    assert spec.weights == (7, 9) and spec.tune == 1.5
    assert spec.family_key() == (
        "default", ("FGDScore", "BestFitScore"), "FGDScore", "max",
        "share", "table", False,
    )
    # fault jobs (ISSUE 10) batch separately; the ISSUE 12 lift made
    # the tune factor an operand for them too — no longer in the key
    spec_f = svc_jobs.validate_job({
        "policies": FAM, "tune": 1.5,
        "fault": {"mtbf_events": 5.0, "seed": 7},
    })
    assert spec_f.fault_config().mtbf_events == 5.0
    assert spec_f.family_key()[-1] is True
    spec_nf = svc_jobs.validate_job({"policies": FAM, "tune": 1.5})
    assert spec_f.family_key() != spec_nf.family_key()
    spec_f2 = svc_jobs.validate_job({
        "policies": FAM, "tune": 0.5,
        "fault": {"mtbf_events": 5.0, "seed": 7},
    })
    assert spec_f.family_key() == spec_f2.family_key()
    with pytest.raises(ValueError, match="unknown fault key"):
        svc_jobs.validate_job({"fault": {"mtbf": 5.0}})
    with pytest.raises(ValueError, match="fault needs"):
        svc_jobs.validate_job({"fault": {"seed": 3}})

    with pytest.raises(ValueError, match="unknown job key"):
        svc_jobs.validate_job({"wieghts": [1]})
    with pytest.raises(ValueError, match="unknown policy"):
        svc_jobs.validate_job({"policies": [["NoSuchScore", 1]]})
    with pytest.raises(ValueError, match="one integer per policy"):
        svc_jobs.validate_job({"policies": FAM, "weights": [1]})
    with pytest.raises(ValueError, match="engine must be one of"):
        svc_jobs.validate_job({"engine": "pallas"})
    with pytest.raises(ValueError, match="tune must be >= 0"):
        svc_jobs.validate_job({"tune": -1})
    with pytest.raises(ValueError, match="must be an integer"):
        svc_jobs.validate_job({"seed": "42"})
    # method typos must be 400s, not silently-default replays cached
    # under the typo'd digest (sim.step's gpu_sel dispatch has no
    # else-error — validation is the only fail-loudly point)
    with pytest.raises(ValueError, match="gpu_sel must be"):
        svc_jobs.validate_job({"gpu_sel": "bets"})
    with pytest.raises(ValueError, match="norm must be"):
        svc_jobs.validate_job({"norm": "maxx"})
    with pytest.raises(ValueError, match="dim_ext must be"):
        svc_jobs.validate_job({"dim_ext": "shared"})


def test_jobs_from_grid():
    docs = svc_jobs.jobs_from_grid({
        "weights": [[1000, 1], [2, 2000]], "seeds": [4, 5],
        "tunes": [0.0, 1.3], "policies": FAM, "gpu_sel": "FGDScore",
    })
    assert len(docs) == 2
    assert docs[1] == {
        "weights": [2, 2000], "seed": 5, "tune": 1.3, "policies": FAM,
        "gpu_sel": "FGDScore",
    }
    # bare rows + default family; full job docs pass through
    docs = svc_jobs.jobs_from_grid([[10], [20]])
    assert [d["weights"] for d in docs] == [[10], [20]]
    passthrough = [{"weights": [1], "seed": 9}]
    assert svc_jobs.jobs_from_grid({"jobs": passthrough}) == passthrough
    with pytest.raises(ValueError, match="no weight rows"):
        svc_jobs.jobs_from_grid([])
    with pytest.raises(ValueError, match="seeds has 1"):
        svc_jobs.jobs_from_grid({"weights": [[1], [2]], "seeds": [3]})
    # singular-key typos are loud, never silently-defaulted rows
    with pytest.raises(ValueError, match="unknown grid key.*seed"):
        svc_jobs.jobs_from_grid({"weights": [[1], [2]], "seed": 7})


def test_docs_from_payload_routing():
    """The `tpusim submit` shape router: a single job document carrying
    a FLAT `weights` vector (a JOB_KEYS field) must stay one job, not
    misroute into the grid expander."""
    single = {"policies": FAM, "weights": [1000, 500], "seed": 7}
    assert svc_jobs.docs_from_payload(single) == [single]
    # rows-of-lists -> grid; list-of-docs and {"jobs"} pass through
    assert [d["weights"] for d in
            svc_jobs.docs_from_payload({"weights": [[1], [2]]})] \
        == [[1], [2]]
    assert svc_jobs.docs_from_payload([[10], [20]])[1]["weights"] == [20]
    assert svc_jobs.docs_from_payload([single]) == [single]
    assert svc_jobs.docs_from_payload({"jobs": [single]}) == [single]


# ---------------------------------------------------------------------------
# 2. digest vocabulary
# ---------------------------------------------------------------------------


def test_job_digest_vocabulary():
    base = svc_jobs.validate_job({"policies": FAM, "seed": 42})
    d0 = svc_jobs.job_digest(base, "tracedigest")
    assert d0 == svc_jobs.job_digest(base, "tracedigest")  # deterministic
    for variant in (
        {"policies": FAM, "seed": 43},
        {"policies": FAM, "seed": 42, "weights": [999, 500]},
        {"policies": FAM, "seed": 42, "tune": 0.1},
        {"policies": FAM, "seed": 42, "engine": "table"},
    ):
        assert svc_jobs.job_digest(
            svc_jobs.validate_job(variant), "tracedigest"
        ) != d0, variant
    # the hosted trace's CONTENT participates
    assert svc_jobs.job_digest(base, "othertrace") != d0


def test_tables_digest_tune_independent(trace):
    """The operand lift's digest move: traces differing only in tune
    factor (same distinct type set, different per-pod type_id) share ONE
    table-cache entry — while the run digest still moves."""
    import jax

    from tpusim.io.trace import build_events, pods_to_specs
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.table_engine import build_pod_types

    sim = Simulator(trace.nodes, SimulatorConfig(
        policies=(("FGDScore", 1000),), report_per_event=False,
        shuffle_pod=False,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    ))
    sim.set_workload_pods(trace.pods)
    sim.set_typical_pods()

    def digests(tune):
        pods = sim.prepare_pods(tuning_ratio=tune)
        specs = pods_to_specs(pods, sim.node_index)
        ev_kind, ev_pod = build_events(pods)
        types = build_pod_types(specs)
        tbl = sim._tables_digest(sim.init_state, types)
        run = sim._run_digest(
            sim.init_state, specs, np.asarray(ev_kind),
            np.asarray(ev_pod), np.asarray(jax.random.PRNGKey(42)),
            np.asarray(sim.rank),
        )
        return tbl, run

    tbl_a, run_a = digests(0.0)
    tbl_b, run_b = digests(1.5)
    assert tbl_a == tbl_b  # tune factor left the table key...
    assert run_a != run_b  # ...and lives in the run key (specs/events)


# ---------------------------------------------------------------------------
# 3. signed result persistence
# ---------------------------------------------------------------------------


def test_signed_result_roundtrip(tmp_path):
    art = str(tmp_path)
    result = {"job": "d" * 64, "placed": 12, "weights": [7, 9],
              "gpu_alloc_pct": 33.25}
    path = svc_jobs.write_result(art, "d" * 64, result)
    assert svc_jobs.find_result(art, "d" * 64) == result

    # torn file: fails the payload digest, gets deleted, reads as a miss
    with open(path) as f:
        lines = f.read().splitlines()
    with open(path, "w") as f:
        f.write(lines[0] + "\n")
        f.write(lines[1].replace("12", "13") + "\n")
    assert svc_jobs.find_result(art, "d" * 64) is None
    assert not os.path.exists(path)

    # foreign header (digest-valid but for another job) never matches
    svc_jobs.write_result(art, "e" * 64, dict(result, job="x"))
    path_e = svc_jobs.result_path(art, "e" * 64)
    os.replace(path_e, svc_jobs.result_path(art, "f" * 64))
    assert svc_jobs.find_result(art, "f" * 64) is None


# ---------------------------------------------------------------------------
# 4. batch formation + backpressure (no device)
# ---------------------------------------------------------------------------


def test_batch_formation_and_queue_full():
    q = JobQueue(maxsize=4, lane_width=3)
    fam_a = svc_jobs.validate_job({"policies": FAM})
    fam_b = svc_jobs.validate_job({"policies": FAM, "gpu_sel": "FGDScore"})
    a1 = q.submit(fam_a, "a1")
    b1 = q.submit(fam_b, "b1")
    a2 = q.submit(svc_jobs.validate_job(
        {"policies": FAM, "weights": [1, 2], "tune": 2.0}), "a2")
    a3 = q.submit(svc_jobs.validate_job(
        {"policies": FAM, "seed": 9}), "a3")
    with pytest.raises(QueueFull) as exc:
        q.submit(svc_jobs.validate_job({"policies": FAM, "seed": 10}), "a4")
    assert exc.value.retry_after_s >= 1
    assert q.stats()["rejected"] == 1

    # dedup: a known digest re-submits to the SAME job, no queue slot
    assert q.submit(fam_a, "a1") is a1
    assert q.depth() == 4

    # batch 1: the a-family coalesces FIFO (a1, a2, a3 — b1 skipped,
    # weights/tune differences do NOT split the family), capped at 3
    batch = q.next_batch(timeout=0)
    assert [j.id for j in batch] == [a1.id, a2.id, a3.id]
    assert [j.lane for j in batch] == [0, 1, 2]
    assert all(j.status == "batched" for j in batch)
    # batch 2: the incompatible job rides its own (singleton) batch
    assert [j.id for j in q.next_batch(timeout=0)] == [b1.id]
    assert q.next_batch(timeout=0) == []

    # a failed job releases its digest for re-submission
    q.mark_failed(a1, "boom")
    retry = q.submit(fam_a, "a1")
    assert retry is not a1 and retry.status == "queued"


# ---------------------------------------------------------------------------
# 5./6. POST-path bit-identity, dedup, 429, zero recompiles
# ---------------------------------------------------------------------------


@pytest.mark.slow  # tier-1 trim, ISSUE 16: rides resume-smoke
def test_post_path_lane_vs_standalone(trace, tmp_path):
    """The marquee contract: results served through the POST path are
    bit-identical to standalone baked-config runs — across weight,
    seed, AND tune-factor variants batched onto one sweep — duplicates
    come from the digest cache, and a second batch differing only in
    weights+tune adds no compiled executable."""
    queue, worker, service = _service(trace, tmp_path)
    # two tune-1.3 jobs deliberately share their tuned trace shape (and
    # the tune-0 job the base shape): the tier-1 slice pays one
    # standalone-engine compile per DISTINCT shape, not per job
    docs = [
        {"policies": FAM, "weights": [1000, 500], "seed": 42},
        {"policies": FAM, "weights": [100, 2000], "seed": 43, "tune": 1.3},
        {"policies": FAM, "weights": [1000, 500], "seed": 42},  # duplicate
        {"policies": FAM, "weights": [7, 900], "seed": 44, "tune": 1.3},
    ]
    resp = _post(service, {"jobs": docs})
    assert resp[0] == 202, resp
    accepted = _body(resp)["jobs"]
    assert accepted[0]["id"] == accepted[2]["id"]  # in-queue dedup
    assert queue.stats()["dedup_hits"] == 1
    assert _drain(queue, worker) == 1  # one compatible batch

    # (the duplicate needs no oracle of its own — it IS job 0's record,
    # pinned by the id equality above)
    for doc in (docs[0], docs[1], docs[3]):
        job_id = _body(_post(service, doc))["id"]
        code, _, body = service.handle(
            "GET", f"/jobs/{job_id}/result", b"")[:3]
        assert code == 200
        got = json.loads(body.decode())
        res = _standalone(
            trace, doc["weights"], doc.get("seed", 42), doc.get("tune", 0.0)
        )
        np.testing.assert_array_equal(
            np.asarray(got["placed_node"]), np.asarray(res.placed_node)
        )
        assert got["failed"] == len(res.unscheduled_pods)
        assert got["events"] == res.events
    # those re-submissions were all answered from the digest cache —
    # nothing new to drain, the device was never touched
    assert queue.depth() == 0 and worker.batches_run == 1

    # zero recompiles: a second batch differing only in weights+tune
    # must not grow the jitted sweep wrapper's executable cache (counts
    # are read RELATIVE to the first batch — the wrapper is process-
    # global, so sibling tests may have compiled other shapes into it)
    # the wrapper the first batch dispatched (the service lane runs
    # report_per_event=False: the STREAM-DONATING one, ISSUE 15)
    sim = worker._sims[list(worker._sims)[0]]
    fn = sim._last_sweep_fn
    before = fn._cache_size()
    _post(service, {"policies": FAM, "weights": [555, 111], "tune": 1.1,
                    "seed": 7})
    assert _drain(queue, worker) == 1
    assert sim._last_sweep_fn is fn and fn._cache_size() == before
    assert worker.sweep_executables() == fn._cache_size()

    # GET surfaces: status doc, /queue stats, unknown id
    jid = _body(_post(service, docs[0]))["id"]
    code, _, body = service.handle("GET", f"/jobs/{jid}", b"")[:3]
    assert code == 200 and json.loads(body.decode())["status"] == "done"
    code, _, body = service.handle("GET", "/queue", b"")[:3]
    stats = json.loads(body.decode())
    assert code == 200 and stats["sweep_executables"] == before
    assert stats["batches_run"] == 2
    assert service.handle("GET", "/jobs/nope", b"")[0] == 404
    # a result file landed per distinct job, signed
    digests = {j.digest for j in queue._jobs.values()}
    for d in digests:
        assert svc_jobs.find_result(str(tmp_path), d) is not None


def test_http_429_retry_after(trace, tmp_path):
    queue, worker, service = _service(trace, tmp_path, queue_size=2)
    for i in range(2):
        assert _post(service, {"policies": FAM, "seed": i})[0] == 202
    resp = _post(service, {"policies": FAM, "seed": 99})
    code, ctype, body, headers = resp
    assert code == 429
    assert int(headers["Retry-After"]) >= 1
    doc = json.loads(body.decode())
    assert doc["retry_after_s"] == int(headers["Retry-After"])
    # an in-flight (not yet done) job answers /result with 409
    jid = _body(_post(service, {"policies": FAM, "seed": 0}))["id"]
    assert service.handle("GET", f"/jobs/{jid}/result", b"")[0] == 409
    # malformed docs are 400 with the validation message
    resp = _post(service, {"wieghts": [1]})
    assert resp[0] == 400 and "unknown job key" in _body(resp)["error"]
    assert _post(service, {"trace": "nope"})[0] == 400


# ---------------------------------------------------------------------------
# 7. per-job progress + watch_dir TOCTOU
# ---------------------------------------------------------------------------


def test_heartbeat_job_tag_routes_progress():
    from tpusim.obs import heartbeat
    from tpusim.obs.server import MonitorServer

    srv = MonitorServer(":0")  # never started: write surface only
    srv.attach_heartbeat()
    try:
        seen = []
        listener = seen.append
        heartbeat.add_listener(listener)
        try:
            heartbeat.configure(100, "replay", sink=lambda line: None,
                                job="j00001-abc")
            heartbeat.tick(50)
            heartbeat.complete(100)
        finally:
            heartbeat.remove_listener(listener)
        assert seen and all(i["job"] == "j00001-abc" for i in seen)
        # tagged ticks land under /progress's jobs map, not the flat keys
        assert "events_done" not in srv._progress
        entry = srv._progress["jobs"]["j00001-abc"]
        assert entry["events_total"] == 100
        assert srv._progress["job"] == "j00001-abc"

        # untagged ticks keep the flat single-run behavior
        heartbeat.configure(10, "replay", sink=lambda line: None)
        heartbeat.complete(10)
        assert srv._progress["events_done"] == 10
    finally:
        srv.stop()
        heartbeat.configure(0, sink=None)


def test_progress_jobs_map_bounded():
    from tpusim.obs.server import MonitorServer

    srv = MonitorServer(":0")
    for i in range(srv.MAX_JOB_PROGRESS + 9):
        srv.publish_job_progress(f"j{i:04d}", {"phase": "done"})
    jobs = srv._progress["jobs"]
    assert len(jobs) == srv.MAX_JOB_PROGRESS
    assert "j0000" not in jobs  # oldest aged out FIFO


def test_watch_dir_survives_vanishing_files(tmp_path, monkeypatch):
    from tpusim.obs import server as obs_server

    keep = tmp_path / "keep.jsonl"
    keep.write_text('{"deterministic": {}, "timing": {}}\n')
    gone = tmp_path / "gone.jsonl"
    gone.write_text("{}\n")

    real_getmtime = os.path.getmtime

    def racy_getmtime(path):
        # the TOCTOU race: the file vanishes between listdir and stat
        if os.path.basename(path) == "gone.jsonl":
            os.unlink(path)
            raise FileNotFoundError(path)
        return real_getmtime(path)

    monkeypatch.setattr(
        obs_server.os.path, "getmtime", racy_getmtime
    )
    record, progress = obs_server.watch_dir(str(tmp_path))
    assert record is not None  # the surviving record is still served
    assert progress["record_file"] == "keep.jsonl"


# ---------------------------------------------------------------------------
# openb end-to-end acceptance (slow; `make resume-smoke` / `make svc-smoke`)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_openb_service_acceptance(tmp_path):
    """ISSUE 7 acceptance on the openb prefix, over real HTTP: N jobs
    POSTed concurrently are served from <= ceil(N/B) compiled sweeps
    with zero recompiles after the first batch, every result
    bit-identical to a standalone run with that weight vector/seed/tune
    baked, duplicates answered from the digest cache without touching
    the device, and the marginal per-job wall beating a standalone warm
    replay outright on CPU (<= 1/5 of it off-CPU)."""
    import time

    import jax

    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.svc import start_job_server
    from tpusim.svc.client import _request, submit_and_wait

    nodes = load_node_csv(
        os.path.join(REPO, "data/csv/openb_node_list_gpu_node.csv")
    )
    pods = load_pod_csv(
        os.path.join(REPO, "data/csv/openb_pod_list_default.csv")
    )[:400]
    trace = TraceRef(
        "default", nodes, pods, svc_jobs.trace_digest(nodes, pods)
    )
    n_jobs, lane_width = 6, 4
    srv, service, worker = start_job_server(
        str(tmp_path), {"default": trace}, listen=":0",
        lane_width=lane_width, queue_size=32,
    )
    try:
        fam = [["FGDScore", 1000], ["BestFitScore", 500]]
        docs = [
            {"policies": fam, "weights": [1000 - 37 * i, 100 + 60 * i],
             "seed": 42 + (i % 2), "tune": [0.0, 0.2][i % 2]}
            for i in range(n_jobs)
        ]
        results = submit_and_wait(srv.url, docs, timeout=600)
        _, _, q = _request(srv.url + "/queue")
        # <= ceil(N/B) compiled sweeps; executables read relative (the
        # jitted wrapper is process-global — sibling tests may have
        # compiled other shapes into it before this one ran)
        assert q["batches_run"] <= -(-n_jobs // lane_width)
        execs0 = q["sweep_executables"]

        # bit-identity of every job against its standalone baked run
        for doc, got in zip(docs, results):
            from tpusim.sim.driver import Simulator, SimulatorConfig

            sim = Simulator(nodes, SimulatorConfig(
                policies=(("FGDScore", doc["weights"][0]),
                          ("BestFitScore", doc["weights"][1])),
                gpu_sel_method="best", seed=doc["seed"],
                report_per_event=False, tuning_ratio=doc["tune"],
                shuffle_pod=False,
            ))
            sim.set_workload_pods(pods)
            res = sim.run()
            np.testing.assert_array_equal(
                np.asarray(got["placed_node"]), np.asarray(res.placed_node)
            )
            assert got["failed"] == len(res.unscheduled_pods)

        # duplicates: the whole wave again — zero new batches, the
        # device untouched, results identical
        batches_before = q["batches_run"]
        dup = submit_and_wait(srv.url, docs, timeout=60)
        _, _, q2 = _request(srv.url + "/queue")
        assert q2["batches_run"] == batches_before
        # zero recompiles after the first batch: every batch of the N-job
        # wave and the dup wave ran on the executables of batch 1
        assert q2["sweep_executables"] == execs0, (q, q2)
        assert [d["placements_sha256"] for d in dup] == [
            d["placements_sha256"] for d in results
        ]

        # marginal per-job cost through the POST path: the slope between
        # a full fresh wave and a single fresh job — both warm and both
        # padded to the SAME lane width/shapes by the service, so the
        # slope isolates what one EXTRA job costs once a batch exists —
        # against a warm single-lane replay at the same padded shapes
        # (the worker's sticky floors; this B=1 call compiles its own
        # vmap shape, which is why it comes after the stability checks)
        from tpusim.sim.driver import schedule_pods_sweep
        from tpusim.svc.client import submit_jobs, wait_jobs

        sim = worker._sims[list(worker._sims)[0]]
        hw_p, hw_e = worker._shape_hw[list(worker._shape_hw)[0]]
        trace_pods = sim.prepare_pods()

        def standalone_warm():
            t0 = time.perf_counter()
            schedule_pods_sweep(
                sim, None, np.asarray([[1000, 500]], np.int32),
                seeds=[42], lane_pods=[trace_pods], min_pods=hw_p,
                min_events=hw_e,
            )
            return time.perf_counter() - t0

        standalone_warm()  # compile the B=1 vmap shape
        sw = min(standalone_warm() for _ in range(2))

        def fresh(i):  # every wave needs undedup'd weights
            return {"policies": fam, "weights": [400 + i, 800 - i],
                    "seed": 42}

        def wave_wall(wave):
            t0 = time.perf_counter()
            ids = [a["id"] for a in submit_jobs(srv.url, wave)]
            wait_jobs(srv.url, ids, timeout=600, poll_s=0.02)
            return time.perf_counter() - t0

        wave_wall([fresh(0)])  # warm the HTTP + dispatch path
        wall_b = min(
            wave_wall([fresh(10 * r + j) for j in range(1, lane_width + 1)])
            for r in range(2)
        )
        wall_1 = min(wave_wall([fresh(100 + r)]) for r in range(2))
        marginal = max(wall_b - wall_1, 0.0) / (lane_width - 1)
        bound = 0.2 if jax.default_backend() != "cpu" else 1.0
        assert marginal <= bound * sw, (marginal, wall_b, wall_1, sw)
        # and a whole fresh B-job batch beats B standalone warm replays
        assert wall_b < lane_width * sw, (wall_b, sw)
    finally:
        worker.stop()
        srv.stop()


# ---------------------------------------------------------------------------
# 8. ISSUE 9 satellites: singular grid keys, shared poll backoff,
#    nonzero submit exit on failed jobs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("singular,plural", [
    ("weight", "weights"), ("seed", "seeds"), ("tune", "tunes"),
])
def test_grid_singular_keys_rejected(singular, plural):
    """Every singular form of a per-row vector fails LOUDLY, naming its
    plural — a typo'd grid must never run rows at the defaults."""
    with pytest.raises(ValueError) as err:
        svc_jobs.jobs_from_grid(
            {"weights": [[1], [2]], singular: 7}
            if singular != "weight" else
            {"weights": [[1], [2]], "weight": [3]}
        )
    msg = str(err.value)
    assert f'"{singular}"' in msg and f'"{plural}"' in msg


def test_wait_jobs_uses_shared_backoff(monkeypatch):
    """The poll loop sleeps the kube_client capped-exponential-with-
    jitter schedule (ONE shared utility): idle rounds escalate the
    attempt count, any job reaching terminal resets it."""
    from tpusim.svc import client

    # job j1 turns done on the 2nd poll, j2 on the 5th
    polls = {"n": 0}

    def fake_request(url, data=None, timeout=30.0):
        jid = url.rsplit("/", 1)[-1]
        if jid == "j1":
            status = "done" if polls["n"] >= 1 else "running"
        else:
            status = "done" if polls["n"] >= 4 else "running"
        return 200, {}, {"id": jid, "status": status}

    attempts = []

    def fake_delay(attempt, retry_after=None):
        attempts.append(attempt)
        return 0.0

    slept = []
    monkeypatch.setattr(client, "_request", fake_request)
    monkeypatch.setattr(client, "_retry_delay_s", fake_delay)

    def fake_sleep(s):
        slept.append(s)
        polls["n"] += 1

    monkeypatch.setattr(client.time, "sleep", fake_sleep)
    final = client.wait_jobs("http://x", ["j1", "j2"], timeout=60)
    assert [d["status"] for d in final] == ["done", "done"]
    # round 0: both running -> attempt 1; round 1: j1 done (progress) ->
    # reset to 1; rounds 2..: idle polls escalate 2, 3
    assert attempts == [1, 1, 2, 3]


def test_wait_jobs_poll_cap(monkeypatch):
    """poll_s > 0 caps the shared-backoff delay (the fast-test knob)."""
    from tpusim.svc import client

    calls = {"n": 0}

    def fake_request(url, data=None, timeout=30.0):
        calls["n"] += 1
        status = "done" if calls["n"] >= 3 else "running"
        return 200, {}, {"id": "j1", "status": status}

    slept = []
    monkeypatch.setattr(client, "_request", fake_request)
    monkeypatch.setattr(client.time, "sleep", slept.append)
    client.wait_jobs("http://x", ["j1"], timeout=60, poll_s=0.01)
    assert slept and all(s <= 0.01 for s in slept)


@pytest.mark.slow  # boots a real service + compiles its sweep (~13 s);
# the error-path contract runs under `make resume-smoke` (tier-1 trim,
# ISSUE 11 satellite)
def test_submit_exits_nonzero_on_failed_job(trace, tmp_path, monkeypatch):
    """A server-side job failure surfaces as JobsFailed carrying the
    done jobs' results, and `tpusim submit` exits nonzero while still
    printing the partial table."""
    import threading

    from tpusim.cli import main as cli_main
    from tpusim.svc.api import start_job_server
    from tpusim.svc.client import JobsFailed, submit_and_wait
    from tpusim.svc.worker import Worker

    real_dispatch = Worker._dispatch

    def poisoned(self, batch):
        # split by family: the worst-gpu_sel family is the poisoned one
        if batch[0].spec.gpu_sel == "worst":
            raise RuntimeError("poisoned family")
        return real_dispatch(self, batch)

    monkeypatch.setattr(Worker, "_dispatch", poisoned)
    srv, service, worker = start_job_server(
        str(tmp_path), {"default": trace}, listen=":0", lane_width=2,
        queue_size=8,
    )
    try:
        good = {"policies": FAM, "weights": [1000, 500], "seed": 1}
        bad = {"policies": FAM, "weights": [1000, 500], "seed": 1,
               "gpu_sel": "worst"}
        with pytest.raises(JobsFailed) as err:
            submit_and_wait(srv.url, [good, bad], timeout=120)
        assert len(err.value.failed) == 1
        assert "poisoned family" in err.value.failed[0]["error"]
        assert len(err.value.results) == 1  # the good job's result rode along
        assert err.value.results[0]["placed"] >= 0

        # the CLI surface: nonzero exit, partial table still printed
        jobs_file = tmp_path / "jobs.json"
        jobs_file.write_text(json.dumps([good, bad]))
        rc = cli_main(
            ["submit", str(jobs_file), "--url", srv.url,
             "--timeout", "120"]
        )
        assert rc == 1
    finally:
        worker.stop()
        srv.stop()
