"""`tpusim` command-line interface (ref: cmd/, the cobra `simon` tree).

Subcommands mirror the reference binary, plus the decision-provenance
verbs (ISSUE 4) and the live-telemetry verbs (ISSUE 5):
  apply    run a simulation from a Simon-CR cluster config
           (ref: cmd/apply/apply.go:14-40)
  explain  why a node won one scheduling decision: per-policy score
           table + runner-ups, from a `--decisions-out` JSONL
  diff     first-divergence finder + divergence histogram between two
           decision JSONLs (e.g. FGD vs BestFit over the same trace)
  report   terminal summary of a run record's in-scan series (min /
           median / max + sparkline per series), from a `--profile`
           JSONL of a `--series-every` run
  serve    watch a directory of run records / checkpoints and expose
           /metrics, /healthz, /progress over HTTP; --jobs additionally
           grows the POST side — a queueing what-if replay service
           (ISSUE 7: POST /jobs, GET /jobs/<id>[/result], GET /queue);
           --workers N promotes it to a kill-tolerant worker FLEET
           (ISSUE 12: leased ownership, orphan stealing, aggregated
           /queue, fleet /healthz)
  worker   join a `serve --jobs` coordinator as a fleet worker
           (ISSUE 12): claim leased batches, renew while scanning,
           write signed results into the shared artifact dir
  submit   POST what-if jobs to a `serve --jobs` service, wait, and
           print the per-job results
  tune     learned-scoring lane (ISSUE 9): ES/CMA tuning of the
           per-policy score weights over the vectorized sweep, local
           or against a `serve --jobs` rollout service, with a
           digest-signed resumable tuning log and a held-out
           tuned-vs-default report
  version  print version/commit (ref: cmd/version/version.go)
  gen-doc  emit markdown docs for the CLI tree (ref: cmd/doc/)
  debug    scaffold, intentionally empty (ref: cmd/debug/debug.go)

Log level comes from env LOGLEVEL (debug|info|warn|error), matching
cmd/simon/simon.go:52-72.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

VERSION = "0.1.0"
COMMIT = os.environ.get("TPUSIM_COMMIT", "dev")


def _build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim",
        description="TPU-native Kubernetes GPU-cluster scheduling simulator",
    )
    sub = parser.add_subparsers(dest="command")

    p_apply = sub.add_parser("apply", help="run a simulation")
    p_apply.add_argument(
        "-f", "--simon-config", required=True, help="cluster-config YAML (Simon CR)"
    )
    p_apply.add_argument(
        "-s",
        "--default-scheduler-config",
        default="",
        help="KubeSchedulerConfiguration YAML",
    )
    p_apply.add_argument(
        "--use-greed", action="store_true", help="greedy app-pod queue sort"
    )
    p_apply.add_argument(
        "-i", "--interactive", action="store_true", help="confirm app list"
    )
    p_apply.add_argument(
        "-e",
        "--extended-resources",
        default="gpu",
        help="comma-separated: gpu, open-local",
    )
    p_apply.add_argument(
        "--base-dir",
        default=".",
        help="root for relative paths inside the CR (default: cwd)",
    )
    p_apply.add_argument(
        "--report", action="store_true", help="print placement report tables"
    )
    # exact checkpoint/resume of the main replay (README "Checkpoint/resume")
    p_apply.add_argument(
        "--checkpoint-every", type=int, default=0, metavar="EVENTS",
        help="checkpoint the replay every N events (0 = off); a killed run "
        "re-invoked with identical inputs resumes bit-identically",
    )
    p_apply.add_argument(
        "--checkpoint-dir", default="",
        help="checkpoint directory (default: $TPUSIM_CHECKPOINT_DIR or "
        "<repo>/.tpusim_checkpoints)",
    )
    p_apply.add_argument(
        "--checkpoint-keep", type=int, default=0, metavar="N",
        help="checkpoint retention: 0 prunes behind the run (resume-only,"
        " the default), -1 keeps every segment carry (the warm-state "
        "fork ladder), N>0 keeps the newest N",
    )
    # fault injection (README "Fault injection"); all rates in EVENTS
    p_apply.add_argument(
        "--fault-mtbf", type=float, default=0.0, metavar="EVENTS",
        help="mean events between node failures (0 = no failures)",
    )
    p_apply.add_argument(
        "--fault-mttr", type=float, default=0.0, metavar="EVENTS",
        help="mean events until a failed node recovers (0 = permanent loss)",
    )
    p_apply.add_argument(
        "--fault-evict-every", type=float, default=0.0, metavar="EVENTS",
        help="mean events between single-pod evictions (0 = off)",
    )
    p_apply.add_argument(
        "--fault-seed", type=int, default=0,
        help="fault-schedule PRNG seed (fixed seed -> identical disruption)",
    )
    p_apply.add_argument(
        "--fault-max-retries", type=int, default=3,
        help="retry budget per evicted pod before it becomes terminally "
        "unscheduled",
    )
    # observability (README "Profiling & telemetry"; tpusim.obs)
    p_apply.add_argument(
        "--profile", nargs="?",
        const=os.path.join(".tpusim_obs", "tpusim_profile.jsonl"),
        default="", metavar="PATH",
        help="profile the run and append a JSONL run record (spans with "
        "compile/execute split, exact scan counters, degrade/fault "
        "counts); default path .tpusim_obs/tpusim_profile.jsonl (the "
        "ignored obs scratch dir — smoke artifacts stay out of the tree)",
    )
    p_apply.add_argument(
        "--metrics-out", default="", metavar="PATH",
        help="write a Prometheus textfile-collector snapshot of the run's "
        "telemetry (atomic rewrite; also enables profiling)",
    )
    p_apply.add_argument(
        "--trace-out", default="", metavar="PATH",
        help="write a Chrome-trace (chrome://tracing / Perfetto) timeline "
        "of the run's phase spans (also enables profiling)",
    )
    p_apply.add_argument(
        "--heartbeat-every", type=int, default=0, metavar="EVENTS",
        help="emit an in-scan progress line (events/s, ETA) every N "
        "processed events of long table-engine scans (0 = off)",
    )
    p_apply.add_argument(
        "--decisions-out", default="", metavar="PATH",
        help="record per-event decision provenance (winner, per-policy "
        "score contributions, top-K runner-ups) and write it as JSONL — "
        "the input of `tpusim explain` / `tpusim diff`",
    )
    # live cluster telemetry (README "Live monitoring"; ISSUE 5)
    p_apply.add_argument(
        "--series-every", type=int, default=0, metavar="EVENTS",
        help="sample the in-scan cluster time-series plane (utilization "
        "histogram, per-category frag, feasible count, per-policy score "
        "extrema) every N processed events (0 = off); lands in the "
        "--profile JSONL, the Chrome counter tracks, and `tpusim report`",
    )
    p_apply.add_argument(
        "--listen", default="", metavar="[HOST]:PORT",
        help="serve /metrics, /healthz, /progress over HTTP for the "
        "run's lifetime (the final /metrics scrape is byte-equal to "
        "--metrics-out); bare :PORT binds loopback only",
    )
    # config-axis sweep (ISSUE 6; README "Sweep many configs in one
    # compile")
    p_apply.add_argument(
        "--sweep-weights", default="", metavar="WEIGHTS.json",
        help="replace the main schedule with ONE vmapped what-if sweep "
        "over this [B, num_policies] weight grid (bare list-of-rows or "
        '{"weights": [[...]], "seeds": [...]}) and print the per-config '
        "summary table (gpu_alloc, frag, placed) — B configs, one "
        "compiled scan",
    )
    # chaos sweep (ISSUE 10; README "Chaos sweep")
    p_apply.add_argument(
        "--sweep-faults", default="", metavar="FAULTS.json",
        help="replace the main schedule with ONE vmapped chaos sweep: "
        "same trace, B fault schedules (per-lane FaultConfig documents — "
        "mtbf_events/mttr_events/evict_every_events/seed/backoff knobs; "
        'bare list or {"faults": [...], "weights": [[...]], "seeds": '
        "[...]}) and print the per-lane disruption frontier — B fault "
        "what-ifs, one compiled scan",
    )
    # the learned policy as a drop-in scorer (ISSUE 14)
    p_apply.add_argument(
        "--policy", default="", metavar="SPEC",
        help="override the scheduler-config score plugins: "
        "'LearnedScore:FILE.json' replays a signed learned-policy "
        "artifact (trained via `tpusim imitate` / `tpusim tune "
        "--policy learned`), 'learned'/'learned-bucketed' the "
        "default-parameter families, or a built-in policy name at "
        "weight 1000",
    )

    p_explain = sub.add_parser(
        "explain",
        help="why a node won one scheduling decision (per-policy score "
        "table from a --decisions-out JSONL)",
    )
    p_explain.add_argument("decisions", help="decision JSONL file")
    p_explain.add_argument(
        "-e", "--event", type=int, required=True,
        help="event index to explain",
    )

    p_diff = sub.add_parser(
        "diff",
        help="first-divergence finder + divergence histogram between two "
        "decision JSONLs (two runs/policies over the same trace)",
    )
    p_diff.add_argument("run_a", help="decision JSONL of run A")
    p_diff.add_argument("run_b", help="decision JSONL of run B")
    p_diff.add_argument(
        "--buckets", type=int, default=10,
        help="event-range buckets of the divergence histogram",
    )

    p_report = sub.add_parser(
        "report",
        help="terminal summary of a run record's in-scan series "
        "(min/median/max + sparkline, straight from the JSONL — no "
        "recomputation)",
    )
    p_report.add_argument(
        "run", help="run-record JSONL (a --profile output of a "
        "--series-every run)",
    )

    p_serve = sub.add_parser(
        "serve",
        help="watch a directory of run records / checkpoints and expose "
        "/metrics, /healthz, /progress over HTTP",
    )
    p_serve.add_argument(
        "dir", help="directory to watch (run-record JSONLs and "
        "io.storage checkpoint files)",
    )
    p_serve.add_argument(
        "--listen", default="", metavar="[HOST]:PORT",
        help="bind address (default loopback on port 8642); bare :PORT "
        "binds loopback only",
    )
    p_serve.add_argument(
        "--poll", type=float, default=2.0, metavar="SECONDS",
        help="directory poll interval",
    )
    p_serve.add_argument(
        "--once", action="store_true",
        help="publish a single poll, self-scrape /metrics and /healthz, "
        "print the verdict, and exit (the `make serve-smoke` mode; with "
        "--jobs it additionally self-checks /queue)",
    )
    # the queueing what-if replay service (ISSUE 7; README "Simulation
    # as a service"): POST /jobs onto the one-compile sweep axis
    p_serve.add_argument(
        "--jobs", action="store_true",
        help="grow the POST side: accept what-if replay jobs (policy "
        "weights x seed x tune factor over the hosted trace), batch "
        "compatible jobs onto ONE vmapped compiled scan, dedup "
        "identical jobs by content digest, and persist signed results "
        "into DIR; needs --nodes/--pods",
    )
    p_serve.add_argument(
        "--nodes", default="", metavar="CSV",
        help="node CSV of the hosted trace (--jobs mode)",
    )
    p_serve.add_argument(
        "--pods", default="", metavar="CSV",
        help="pod CSV of the hosted trace (--jobs mode)",
    )
    p_serve.add_argument(
        "--max-pods", type=int, default=0, metavar="N",
        help="truncate the hosted workload to its first N pods (0 = all)",
    )
    # multi-trace hosting (ISSUE 13): families already key by trace
    # name, so batching stays per-(trace, family) with one compiled
    # scan per family
    p_serve.add_argument(
        "--trace", action="append", default=[],
        metavar="NAME=NODES.csv:PODS.csv[:MAX_PODS]",
        help="host an ADDITIONAL named trace (repeatable); jobs select "
        'it via their "trace" key. --nodes/--pods host the trace named '
        "'default'; at least one trace must be given either way",
    )
    p_serve.add_argument(
        "--lane-width", type=int, default=8, metavar="B",
        help="sweep lanes per batch: up to B compatible jobs share one "
        "compiled scan (short batches pad to B so the executable count "
        "stays at one per job family)",
    )
    p_serve.add_argument(
        "--queue-size", type=int, default=64, metavar="N",
        help="bounded job queue depth; a full queue answers POST /jobs "
        "with 429 + Retry-After",
    )
    # the worker fleet (ISSUE 12; README "Worker fleet")
    p_serve.add_argument(
        "--workers", type=int, default=0, metavar="N",
        help="spawn N worker PROCESSES draining the one job queue "
        "under leased ownership (signed lease files, orphan stealing — "
        "a kill -9'd worker's jobs are reclaimed by any live worker); "
        "0 keeps the single in-process worker thread. Remote hosts "
        "join the same fleet with `tpusim worker --join URL`",
    )
    p_serve.add_argument(
        "--max-workers", type=int, default=0, metavar="M",
        help="autoscale ceiling (ISSUE 13; needs --workers N, M >= N): "
        "a queue backlog deeper than the live fleet can chew spawns "
        "extra workers up to M; an idle queue drains back down to N "
        "(graceful SIGTERM). The supervisor also respawns crashed "
        "children under capped backoff, with a crash-loop circuit "
        "breaker that degrades /healthz instead of spinning",
    )
    p_serve.add_argument(
        "--lease-s", type=float, default=0.0, metavar="SECONDS",
        help="job lease duration (default 15): a worker silent this "
        "long past its deadline forfeits its batch to the fleet",
    )
    p_serve.add_argument(
        "--family-quota", type=int, default=0, metavar="N",
        help="per-family admission quota: at most N queued jobs per "
        "job family (a hot trace can't starve the rest); overflow "
        "answers 429 + Retry-After naming the family (0 = no cap)",
    )
    # named learned-policy presets (ISSUE 14): the fleet serves a
    # trained artifact exactly like a built-in policy family
    p_serve.add_argument(
        "--policy-preset", action="append", default=[],
        metavar="NAME=ARTIFACT.json",
        help="register a named learned-policy preset from a signed "
        "artifact (repeatable); submit jobs reference it via "
        '{"policy_preset": "NAME"} and replay byte-identically to the '
        "artifact run locally",
    )
    # coordinator HA (ISSUE 17): leadership is one more signed file in
    # the artifact dir — a standby watches it and takes over, epoch-
    # fenced against the deposed leader
    p_serve.add_argument(
        "--standby", action="store_true",
        help="start as a STANDBY coordinator: watch the artifact dir's "
        "coordinator.lease.json and take over (bump the epoch, adopt "
        "pending jobs and live worker leases) when the leader's lease "
        "goes stale; mutating endpoints answer 503 + Retry-After until "
        "promotion. Implies --fleet",
    )
    p_serve.add_argument(
        "--fleet", action="store_true",
        help="arm the fleet coordinator plane (register/claim/renew/"
        "complete + the HA leadership lease) WITHOUT spawning local "
        "workers — remote hosts join with `tpusim worker --join`; "
        "--workers N implies it",
    )
    p_serve.add_argument(
        "--token-file", default="", metavar="FILE",
        help="bearer token (the file's stripped contents; or env "
        "TPUSIM_FLEET_TOKEN) required on every mutating endpoint — "
        "POST /jobs, claim/renew/complete/leases, result uploads, "
        "register. Constant-time compare; 401 without leaking whether "
        "a digest exists; token material never appears in logs or "
        "/queue",
    )
    p_serve.add_argument(
        "--slo-file", default="", metavar="FILE",
        help="SLO/alert rules JSON (threshold + multi-window burn-rate "
        "over the in-process metrics history; see obs.alerts) — "
        "overrides/extends the built-in defaults; firing transitions "
        "append kind=alert audit records, surface on GET /alerts, and "
        "page-severity burn flips /healthz (default $TPUSIM_SLO_FILE)",
    )
    p_serve.add_argument(
        "--table-cache-dir", default="", metavar="DIR",
        help="content-keyed init-table cache shared by the fleet "
        "(default $TPUSIM_TABLE_CACHE_DIR)",
    )

    # the fleet worker process (ISSUE 12): joins a `serve --jobs`
    # coordinator, pulls leased batches, writes signed results into the
    # shared artifact dir
    p_worker = sub.add_parser(
        "worker",
        help="join a `tpusim serve --jobs` coordinator as a fleet "
        "worker: claim leased batches, run them on this host's device, "
        "write signed results into the shared artifact dir, renew "
        "leases while scanning; SIGTERM drains the in-flight batch",
    )
    p_worker.add_argument(
        "--join", required=True, metavar="URL[,URL...]",
        help="coordinator base URL (the address `serve --jobs` "
        "printed); a comma-separated list names an HA pair/set — the "
        "worker rotates to the next coordinator on connection failure "
        "or standby 503, on the shared backoff schedule (ISSUE 17)",
    )
    p_worker.add_argument(
        "--id", default="", metavar="NAME",
        help="worker id (default: coordinator-assigned)",
    )
    p_worker.add_argument(
        "--poll", type=float, default=0.2, metavar="SECONDS",
        help="idle claim-poll interval",
    )
    p_worker.add_argument(
        "--max-batches", type=int, default=0, metavar="N",
        help="exit after serving N batches (0 = run until stopped)",
    )
    p_worker.add_argument(
        "--table-cache-dir", default="", metavar="DIR",
        help="shared content-keyed table cache",
    )
    # the no-shared-fs transport (ISSUE 13)
    p_worker.add_argument(
        "--mode", choices=("auto", "shared-fs", "remote"),
        default="auto",
        help="artifact-plane topology: shared-fs reads trace CSVs by "
        "path and writes results into the shared artifact dir; remote "
        "needs NO shared filesystem (digest-verified trace downloads "
        "into a local cache, signed-result uploads, lease POSTs); "
        "auto probes the handshake's paths and picks",
    )
    p_worker.add_argument(
        "--cache-dir", default="", metavar="DIR",
        help="remote-mode local cache root (downloaded traces keyed "
        "by content digest + this worker's artifact scratch); default "
        "a per-host tmp dir",
    )
    p_worker.add_argument(
        "--token-file", default="", metavar="FILE",
        help="bearer token for an auth-armed fleet (the file's "
        "stripped contents; or env TPUSIM_FLEET_TOKEN)",
    )

    # the learned-scoring lane (ISSUE 9; README "Tune policy weights"):
    # ES/CMA weight tuning over the vectorized sweep, with the job plane
    # as an optional remote rollout farm
    p_tune = sub.add_parser(
        "tune",
        help="tune the per-policy score weights with ES/CMA over the "
        "vectorized sweep (one compiled scan per generation; --url "
        "offloads rollouts to a `serve --jobs` service) and report "
        "tuned-vs-default on a held-out trace suffix",
    )
    p_tune.add_argument(
        "--nodes", required=True, metavar="CSV",
        help="node CSV of the tuning trace",
    )
    p_tune.add_argument(
        "--pods", required=True, metavar="CSV",
        help="pod CSV of the tuning trace",
    )
    p_tune.add_argument(
        "--max-pods", type=int, default=0, metavar="N",
        help="truncate the workload to its first N pods (0 = all)",
    )
    p_tune.add_argument(
        "--policies", default='[["FGDScore", 1000], ["BestFitScore", 500]]',
        metavar="JSON",
        help="policy family as [[name, default_weight], ...]; the "
        "default weights seed the optimizer AND are the held-out "
        "report's baseline",
    )
    # the learned policy as the tuned family (ISSUE 14): the parameter
    # vector IS the weight vector, so ES/CMA search over it reuses the
    # whole one-compile sweep machinery unchanged
    p_tune.add_argument(
        "--policy", default="", metavar="SPEC",
        help="tune a LEARNED policy instead of --policies: 'learned' "
        "(the linear feature vocabulary, FGD-equivalent init), "
        "'learned-bucketed' (plus the 10 occupancy-bucket table "
        "features), or 'LearnedScore:FILE.json' (resume search from a "
        "signed artifact, e.g. an imitation-trained one); --best-out "
        "then writes a signed policy ARTIFACT, and the weight bounds "
        "default to the symmetric [-4000, 4000] parameter space",
    )
    p_tune.add_argument(
        "--algo", choices=("es", "cma"), default="es",
        help="optimizer: antithetic OpenAI-ES or diagonal CMA-ES",
    )
    p_tune.add_argument("--generations", type=int, default=10)
    p_tune.add_argument("--popsize", type=int, default=8)
    p_tune.add_argument(
        "--sigma", type=float, default=250.0,
        help="initial perturbation scale in weight units",
    )
    p_tune.add_argument(
        "--lr", type=float, default=300.0,
        help="ES step size in weight units (cma adapts its own)",
    )
    p_tune.add_argument(
        "--seed", type=int, default=0,
        help="optimizer draw seed (fixed seed -> byte-identical log)",
    )
    p_tune.add_argument(
        "--eval-seed", type=int, default=42,
        help="replay seed every candidate shares (common random numbers)",
    )
    p_tune.add_argument(
        "--w-min", type=int, default=None,
        help="weight lower bound (default 0; -4000 under --policy "
        "learned — feature signs are meaningful)",
    )
    p_tune.add_argument(
        "--w-max", type=int, default=None,
        help="weight upper bound (default 4000)",
    )
    p_tune.add_argument(
        "--obj-alloc", type=float, default=1.0,
        help="objective weight on gpu_alloc_pct",
    )
    p_tune.add_argument(
        "--obj-frag", type=float, default=1.0,
        help="objective weight on frag percent of cluster GPU",
    )
    p_tune.add_argument(
        "--obj-unsched", type=float, default=1.0,
        help="objective weight on unscheduled percent of pods",
    )
    p_tune.add_argument(
        "--holdout", type=float, default=0.2, metavar="FRAC",
        help="trailing fraction of the pod list held out of tuning and "
        "used for the final tuned-vs-default report (0 disables)",
    )
    p_tune.add_argument(
        "--log", default=os.path.join(".tpusim_obs", "tune_log.jsonl"),
        metavar="PATH",
        help="digest-signed tuning log (JSONL; the --resume input and "
        "the `analysis --plot-tuning` source)",
    )
    p_tune.add_argument(
        "--resume", action="store_true",
        help="continue from the log's last generation (byte-identical "
        "to an uninterrupted run under the same flags)",
    )
    p_tune.add_argument(
        "--url", default="", metavar="URL",
        help="offload rollouts to a `tpusim serve --jobs` service (it "
        "must host the tuning trace prefix); default: local vmapped "
        "sweeps",
    )
    p_tune.add_argument(
        "--engine", choices=("auto", "table", "sequential"),
        default="auto", help="replay engine for the rollouts",
    )
    p_tune.add_argument(
        "--best-out", default="", metavar="PATH",
        help="write the tuned weight vector as a weights-grid JSON "
        "(apply --sweep-weights / submit shape)",
    )
    p_tune.add_argument(
        "--robust-mtbf", type=float, default=0.0, metavar="EVENTS",
        help="per-generation robustness eval: replay the generation "
        "best through seeded fault injection with this MTBF (0 = off; "
        "logged, not fed back into the optimizer)",
    )
    p_tune.add_argument(
        "--robust-mttr", type=float, default=0.0, metavar="EVENTS",
        help="mean events until a failed node recovers in the "
        "robustness eval",
    )
    p_tune.add_argument("--robust-seed", type=int, default=0)
    # chaos-sweep training (ISSUE 10): roll the POPULATION itself through
    # a seeded fault schedule (one compiled faulted scan per generation)
    # so the objective's disruption term trains directly
    p_tune.add_argument(
        "--train-fault-mtbf", type=float, default=0.0, metavar="EVENTS",
        help="train under disruption: every rollout lane replays under "
        "a seeded fault schedule with this MTBF (0 = fault-free "
        "training); local backend only",
    )
    p_tune.add_argument(
        "--train-fault-mttr", type=float, default=0.0, metavar="EVENTS")
    p_tune.add_argument(
        "--train-fault-evict-every", type=float, default=0.0,
        metavar="EVENTS")
    p_tune.add_argument("--train-fault-seed", type=int, default=0)
    p_tune.add_argument(
        "--obj-disrupt", type=float, default=0.0,
        help="objective weight on pods terminally lost to disruption "
        "(percent of trace pods); needs --train-fault-* to be non-zero "
        "to matter",
    )
    p_tune.add_argument(
        "--timeout", type=float, default=600.0, metavar="SECONDS",
        help="per-generation wait budget on the remote backend",
    )

    # the imitation trainer (ISSUE 14; README "Train and serve a learned
    # policy"): decision JSONL -> (feature-row, chosen, runner-up)
    # tuples -> a trained, i32-exported, digest-signed policy artifact
    p_imitate = sub.add_parser(
        "imitate",
        help="train a learned policy to imitate a recorded teacher: "
        "teacher-force the trace through a --decisions-out JSONL, build "
        "(winner, runner-up) feature pairs, fit the linear scorer, "
        "export it into the engines' i32 vocabulary, and report "
        "held-out top-1 agreement",
    )
    p_imitate.add_argument(
        "--nodes", required=True, metavar="CSV",
        help="node CSV of the recorded trace",
    )
    p_imitate.add_argument(
        "--pods", required=True, metavar="CSV",
        help="pod CSV of the recorded trace",
    )
    p_imitate.add_argument(
        "--decisions", required=True, metavar="JSONL",
        help="the teacher run's decision log (`tpusim apply "
        "--decisions-out`) — digest-verified on load",
    )
    p_imitate.add_argument(
        "--max-pods", type=int, default=0, metavar="N",
        help="truncate the workload to its first N pods (must match the "
        "recorded run)",
    )
    p_imitate.add_argument(
        "--features", choices=("linear", "bucketed"), default="linear",
        help="feature vocabulary: the 10 linear node/pod features, or "
        "plus the 10 occupancy-bucket table features",
    )
    p_imitate.add_argument("--steps", type=int, default=500)
    p_imitate.add_argument("--lr", type=float, default=0.15)
    p_imitate.add_argument("--l2", type=float, default=1e-4)
    p_imitate.add_argument("--seed", type=int, default=0)
    p_imitate.add_argument(
        "--holdout", type=float, default=0.2, metavar="FRAC",
        help="trailing fraction of EVENTS held out of training; the "
        "reported agreement is teacher-forced top-1 on this suffix",
    )
    p_imitate.add_argument(
        "--out", default="", metavar="PATH",
        help="write the trained policy as a digest-signed artifact "
        "(the `apply --policy LearnedScore:FILE.json` / `serve "
        "--policy-preset` / `tune --policy LearnedScore:FILE.json` "
        "input)",
    )

    p_submit = sub.add_parser(
        "submit",
        help="POST what-if jobs to a `tpusim serve --jobs` replay "
        "service, wait for completion, and print the per-job results",
    )
    p_submit.add_argument(
        "jobs",
        help="job JSON: one job object, {\"jobs\": [...]}, or an "
        "apply-style weights grid ([[w, ...], ...] or {\"weights\": "
        "[[...]], \"seeds\": [...], \"tunes\": [...], \"policies\": "
        "[[name, w], ...]})",
    )
    p_submit.add_argument(
        "--url", required=True, metavar="URL[,URL...]",
        help="service base URL (the address `serve --jobs` printed, "
        "e.g. http://127.0.0.1:8642); a comma-separated list names an "
        "HA pair/set — the client fails over to the next coordinator "
        "when one dies mid-wait (re-submission dedups by job digest)",
    )
    p_submit.add_argument(
        "--timeout", type=float, default=300.0, metavar="SECONDS",
        help="overall wait budget for results",
    )
    p_submit.add_argument(
        "--token-file", default="", metavar="FILE",
        help="bearer token for an auth-armed service (the file's "
        "stripped contents; or env TPUSIM_FLEET_TOKEN)",
    )

    p_trace = sub.add_parser(
        "trace",
        help="stitch a job's cross-process fleet timeline from the "
        "artifact dir's span files (admission, queue wait, claim, "
        "dispatch, upload, verify — abandoned attempts included)",
    )
    p_trace.add_argument(
        "job", nargs="?", default="",
        help="job digest (or unique prefix); omit for every span",
    )
    p_trace.add_argument(
        "-d", "--dir", default="runs", metavar="DIR",
        help="artifact dir the coordinator served from",
    )
    p_trace.add_argument(
        "--trace-id", default="", metavar="ID",
        help="filter by trace id instead of (or as well as) job digest",
    )
    p_trace.add_argument(
        "--out", default="", metavar="FILE",
        help="also write a Chrome-trace JSON (one track per process; "
        "open in chrome://tracing or Perfetto)",
    )

    p_audit = sub.add_parser(
        "audit",
        help="query or verify the hash-chained control-plane audit "
        "log (takeovers, depositions, steals, lease expiries, "
        "requeues, breaker trips, fence hits, degrades)",
    )
    p_audit.add_argument(
        "-d", "--dir", default="runs", metavar="DIR",
        help="artifact dir holding audit.jsonl",
    )
    p_audit.add_argument(
        "--verify", action="store_true",
        help="walk the whole chain + head sidecar; exit 1 loudly on "
        "any edit, truncation, or torn tail",
    )
    p_audit.add_argument(
        "--tail", type=int, default=20, metavar="N",
        help="show the last N matching records (0 = all)",
    )
    p_audit.add_argument("--kind", default="",
                         help="filter by record kind")
    p_audit.add_argument("--job", default="",
                         help="filter by job digest (prefix ok)")
    p_audit.add_argument("--worker", default="",
                         help="filter by worker id")
    p_audit.add_argument(
        "--url", default="", metavar="URL",
        help="tail a LIVE coordinator over HTTP instead of reading "
        "local files: polls GET /events with the seq cursor "
        "(?after=&limit=) so each poll ships only the delta",
    )
    p_audit.add_argument(
        "--follow", action="store_true",
        help="with --url: keep polling the cursor (Ctrl-C to stop)",
    )

    # the live fleet dashboard (ISSUE 20)
    p_top = sub.add_parser(
        "top",
        help="live terminal dashboard for a serve --jobs coordinator: "
        "queue, workers, firing alerts, and sparkline history "
        "stitched from /queue, /workers, /alerts, /query",
    )
    p_top.add_argument("url", help="coordinator base URL "
                       "(e.g. http://127.0.0.1:8642)")
    p_top.add_argument(
        "--interval", type=float, default=2.0, metavar="S",
        help="redraw interval seconds (default 2)",
    )
    p_top.add_argument(
        "--once", action="store_true",
        help="render one frame and exit (no screen clearing) — the "
        "scriptable/smoke form",
    )
    p_top.add_argument(
        "--width", type=int, default=0, metavar="COLS",
        help="frame width (default: terminal width, floor 60)",
    )

    sub.add_parser("version", help="print version")

    p_doc = sub.add_parser("gen-doc", help="generate markdown CLI docs")
    p_doc.add_argument("-d", "--dir", default="docs", help="output directory")

    sub.add_parser("debug", help="debug scaffold (no-op, ref parity)")
    return parser


def cmd_apply(args) -> int:
    from tpusim.apply import Applier, ApplyOptions
    from tpusim.compile_cache import enable_compile_cache

    enable_compile_cache()
    opts = ApplyOptions(
        simon_config=args.simon_config,
        default_scheduler_config=args.default_scheduler_config,
        use_greed=args.use_greed,
        interactive=args.interactive,
        extended_resources=[
            e.strip() for e in args.extended_resources.split(",") if e.strip()
        ],
        base_dir=args.base_dir,
        report_tables=args.report,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_keep=args.checkpoint_keep,
        fault_mtbf=args.fault_mtbf,
        fault_mttr=args.fault_mttr,
        fault_evict_every=args.fault_evict_every,
        fault_seed=args.fault_seed,
        fault_max_retries=args.fault_max_retries,
        profile_out=args.profile,
        metrics_out=args.metrics_out,
        trace_out=args.trace_out,
        heartbeat_every=args.heartbeat_every,
        decisions_out=args.decisions_out,
        series_every=args.series_every,
        listen=args.listen,
        sweep_weights=args.sweep_weights,
        sweep_faults=args.sweep_faults,
        policy=args.policy,
    )
    Applier(opts).run()
    return 0


def cmd_explain(args) -> int:
    from tpusim.obs import decisions as obs_decisions

    # diff(1)-style exit codes: 0 ok, 2 on unusable input (missing /
    # torn / digest-mismatched file, event out of range) — a one-line
    # error, not a traceback
    try:
        header, rows = obs_decisions.read_decisions(args.decisions)
        print(obs_decisions.format_explain(header, rows, args.event))
    except (OSError, ValueError, json.JSONDecodeError) as err:
        print(f"tpusim explain: {err}", file=sys.stderr)
        return 2
    return 0


def cmd_diff(args) -> int:
    from tpusim.obs import decisions as obs_decisions

    try:
        ha, ra = obs_decisions.read_decisions(args.run_a)
        hb, rb = obs_decisions.read_decisions(args.run_b)
        # run_diff also rejects files from DIFFERENT traces (per-row
        # kind/pod mismatch) — a ValueError, not a bogus divergence
        d = obs_decisions.run_diff(
            ha, ra, hb, rb,
            label_a=os.path.basename(args.run_a),
            label_b=os.path.basename(args.run_b),
            buckets=args.buckets,
        )
    except (OSError, ValueError, json.JSONDecodeError) as err:
        print(f"tpusim diff: {err}", file=sys.stderr)
        return 2
    print(d["text"])
    # like diff(1): exit 0 on identical placements, 1 on divergence
    return 1 if d["first"] else 0


def cmd_report(args) -> int:
    from tpusim.obs.emitters import read_jsonl
    from tpusim.obs.series import format_report

    # same exit discipline as explain/diff: 2 on unusable input, with a
    # one-line error instead of a traceback
    try:
        records = read_jsonl(args.run)
        with_series = [r for r in records if r.get("series")]
        if not with_series:
            raise ValueError(
                f"{args.run}: no record carries a series block (was the "
                "run made with --series-every and --profile?)"
            )
        print(format_report(with_series[-1]["series"]))
    except (OSError, ValueError, json.JSONDecodeError) as err:
        print(f"tpusim report: {err}", file=sys.stderr)
        return 2
    return 0


def cmd_serve(args) -> int:
    from tpusim.obs.server import serve_dir

    try:
        if args.jobs:
            return _serve_jobs(args)
        if args.once:
            # smoke mode: one poll, a real self-scrape over HTTP, exit.
            # Exit 2 when the scrape fails or the /metrics text does not
            # parse — the `make serve-smoke` verdict.
            import urllib.request

            from tpusim.obs.emitters import parse_prometheus_text

            srv = serve_dir(args.dir, listen=args.listen,
                            poll_s=args.poll, once=True, out=sys.stderr)
            try:
                with urllib.request.urlopen(srv.url + "/healthz",
                                            timeout=10) as r:
                    health = json.loads(r.read().decode())
                try:
                    with urllib.request.urlopen(srv.url + "/metrics",
                                                timeout=10) as r:
                        text = r.read().decode()
                except urllib.error.HTTPError as err:
                    # 503 = no run record in the directory yet — the
                    # server is healthy, there is just nothing to scrape
                    print(f"[serve] once: healthz ok={health.get('ok')}, "
                          f"no run record yet (/metrics {err.code})",
                          file=sys.stderr)
                else:
                    n = len(parse_prometheus_text(text))
                    print(f"[serve] once: healthz ok={health.get('ok')}, "
                          f"/metrics parses ({n} series)", file=sys.stderr)
            finally:
                srv.stop()
            return 0
        serve_dir(args.dir, listen=args.listen, poll_s=args.poll,
                  out=sys.stderr)
    except (OSError, ValueError) as err:
        print(f"tpusim serve: {err}", file=sys.stderr)
        return 2
    return 0


def parse_trace_arg(entry: str):
    """One `--trace NAME=NODES.csv:PODS.csv[:MAX_PODS]` entry ->
    (name, nodes_csv, pods_csv, max_pods), failing loudly on anything
    malformed (ISSUE 13 multi-trace hosting)."""
    name, sep, rest = entry.partition("=")
    name = name.strip()
    if not sep or not name:
        raise ValueError(
            f"--trace {entry!r}: want NAME=NODES.csv:PODS.csv[:MAX_PODS]"
        )
    parts = rest.split(":")
    if len(parts) not in (2, 3) or not parts[0] or not parts[1]:
        raise ValueError(
            f"--trace {entry!r}: want NAME=NODES.csv:PODS.csv[:MAX_PODS]"
        )
    max_pods = 0
    if len(parts) == 3:
        try:
            max_pods = int(parts[2])
        except ValueError:
            raise ValueError(
                f"--trace {entry!r}: MAX_PODS must be an integer, got "
                f"{parts[2]!r}"
            )
    return name, parts[0], parts[1], max_pods


def _serve_jobs(args) -> int:
    """`tpusim serve DIR --jobs`: the queueing what-if replay service
    (ISSUE 7) — the monitor plane plus POST /jobs over the hosted
    trace(s); signed results land in DIR, which is also watched/
    republished like plain serve. --workers N runs the self-healing
    supervisor (ISSUE 13): respawn-on-exit with capped backoff, a
    crash-loop circuit breaker, and --max-workers M autoscale."""
    import time
    import urllib.request

    from tpusim.compile_cache import enable_compile_cache
    from tpusim.obs.server import watch_dir
    from tpusim.svc import load_trace, start_job_server
    from tpusim.svc.api import recover_pending_jobs
    from tpusim.svc.auth import describe as auth_describe
    from tpusim.svc.auth import load_token
    from tpusim.svc.coord import CoordinatorState, CoordKeeper

    enable_compile_cache()
    traces = {}
    if args.nodes or args.pods:
        if not (args.nodes and args.pods):
            raise ValueError(
                "serve --jobs hosts a trace: pass BOTH --nodes "
                "NODES.csv and --pods PODS.csv"
            )
        traces["default"] = load_trace(
            "default", args.nodes, args.pods, max_pods=args.max_pods
        )
    for entry in args.trace:
        name, nodes_csv, pods_csv, max_pods = parse_trace_arg(entry)
        if name in traces:
            raise ValueError(f"--trace {name!r} given twice")
        traces[name] = load_trace(name, nodes_csv, pods_csv,
                                  max_pods=max_pods)
    if not traces:
        raise ValueError(
            "serve --jobs hosts at least one trace: pass --nodes/--pods "
            "(the trace named 'default') and/or --trace NAME=..."
        )
    fleet_n = int(getattr(args, "workers", 0) or 0)
    max_n = int(getattr(args, "max_workers", 0) or 0)
    if max_n and not fleet_n:
        raise ValueError("--max-workers needs --workers N")
    standby = bool(getattr(args, "standby", False))
    fleet_mode = fleet_n > 0 or standby or bool(getattr(args, "fleet", False))
    token = load_token(getattr(args, "token_file", ""))
    # the HA leadership lease (ISSUE 17): armed in fleet mode only —
    # the single in-process-worker service of PR 7 has no standby to
    # fence against and stays exactly as it was
    coord = None
    if fleet_mode:
        try:
            host = os.uname().nodename
        except (AttributeError, OSError):
            host = "localhost"
        coord = CoordinatorState(
            args.dir, name=f"{host}-{os.getpid()}", out=sys.stderr
        )
        if not standby:
            if not coord.try_acquire():
                print(
                    "[serve] another coordinator holds a LIVE "
                    "leadership lease (epoch "
                    f"{coord.epoch}) — running as standby; pass "
                    "--standby to silence this",
                    file=sys.stderr,
                )
    # named learned-policy presets (ISSUE 14): NAME=artifact.json ->
    # the [(name, weight)] pairs submit jobs reference by preset name
    presets = {}
    for entry in getattr(args, "policy_preset", []):
        name, sep, path = entry.partition("=")
        name = name.strip()
        if not sep or not name or not path:
            raise ValueError(
                f"--policy-preset {entry!r}: want NAME=ARTIFACT.json"
            )
        if name in presets:
            raise ValueError(f"--policy-preset {name!r} given twice")
        from tpusim.learn.policy import policies_from_artifact

        presets[name] = policies_from_artifact(path)
        print(
            f"[serve] policy preset {name!r} <- {path} "
            f"({len(presets[name])} features)", file=sys.stderr,
        )
    srv, service, worker = start_job_server(
        args.dir, traces, listen=args.listen,
        lane_width=args.lane_width, queue_size=args.queue_size,
        table_cache_dir=args.table_cache_dir,
        fleet=fleet_mode, lease_s=args.lease_s,
        family_quota=args.family_quota,
        policy_presets=presets,
        token=token, coord=coord,
        slo_file=args.slo_file or os.environ.get("TPUSIM_SLO_FILE", ""),
        out=sys.stderr,
    )
    if coord is not None:
        # the lease is re-staked with the bound URL at the next renewal
        coord.url = srv.url
    sup = None
    if fleet_n > 0:
        import subprocess

        from tpusim.svc.fleet import worker_command
        from tpusim.svc.supervisor import Supervisor

        cmd = worker_command(
            srv.url, table_cache_dir=args.table_cache_dir,
            token_file=getattr(args, "token_file", ""),
        )
        sup = Supervisor(
            lambda _n: subprocess.Popen(cmd), fleet_n,
            max_workers=max_n,
            load_fn=service.queue.depth,
            depth_per_worker=args.lane_width,
            on_exit=service.fleet.release_dead,
            out=sys.stderr,
        )
        service.fleet.supervisor = sup
        # respawns/breaker trips append to the coordinator's audit
        # chain (ISSUE 19)
        sup.audit = service.audit
        if coord is not None and coord.role != "leader":
            # a standby's local workers would only spin on its own
            # 503s — spawn them at promotion (resume fills the floor)
            sup.pause()
        sup.start()
    # HA plumbing (ISSUE 17): the leader renews its leadership lease on
    # a CoordKeeper timer; a standby (or a deposed ex-leader) polls
    # try_acquire on the watch cadence and promotes by adopting the
    # artifact dir's pending state — which the epoch fence guarantees
    # the old leader can no longer mutate
    ha = {"keeper": None}

    def _on_deposed():
        if sup is not None:
            sup.pause()

    def _promote():
        old = ha["keeper"]
        if old is not None:
            old.stop()
        recover_pending_jobs(service, out=sys.stderr)
        if service.fleet is not None:
            service.fleet.adopt_leases(out=sys.stderr)
        # the metrics half of the takeover (ISSUE 20): splice the
        # deposed leader's persisted tsdb snapshot under our ring and
        # resume the (standby-paused) sampler — /query history survives
        # the failover instead of starting blind
        service.adopt_history(out=sys.stderr)
        if sup is not None:
            sup.resume()
        ha["keeper"] = CoordKeeper(coord, on_deposed=_on_deposed).start()
        print(
            f"[serve] PROMOTED to leader at epoch {coord.epoch} — "
            "pending jobs requeued, live worker leases adopted, "
            "metrics history spliced",
            file=sys.stderr,
        )

    if coord is not None and coord.role == "leader":
        ha["keeper"] = CoordKeeper(coord, on_deposed=_on_deposed).start()
    # graceful shutdown (ISSUE 10): SIGTERM/SIGINT begin the drain —
    # /healthz flips to 503, POSTs answer 503 + Retry-After, the
    # in-flight batch finishes (worker.stop joins after it), and every
    # queued job's spec is already on disk for the next startup's
    # recovery pass
    import signal

    stop_flag = {"stop": False}

    def _graceful(_signum, _frame):
        stop_flag["stop"] = True
        srv.begin_drain()

    try:
        signal.signal(signal.SIGTERM, _graceful)
        signal.signal(signal.SIGINT, _graceful)
    except ValueError:
        pass  # non-main thread (tests drive _serve_jobs directly)
    mode = (f"supervised fleet of {fleet_n} worker processes"
            + (f" (autoscale to {max_n})" if max_n else "")
            if fleet_n else
            ("fleet coordinator (external workers)" if fleet_mode
             else "single in-process worker"))
    if coord is not None:
        mode += (f"; role {coord.role} epoch {coord.epoch}; "
                 f"auth {auth_describe(token)}")
    hosted = "; ".join(
        f"trace {name!r} = {len(t.nodes)} nodes x {len(t.pods)} pods"
        for name, t in traces.items()
    )
    print(
        f"[serve] job plane at {srv.url} (POST /jobs, GET "
        f"/jobs/<id>[/result], /queue, /workers, /traces, /metrics, "
        f"/healthz, /progress); {mode}; {hosted}; results -> "
        f"{os.path.abspath(args.dir)}", file=sys.stderr,
    )
    try:
        if args.once:
            # smoke mode: a real self-check of both planes over HTTP
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=10) as r:
                health = json.loads(r.read().decode())
            with urllib.request.urlopen(srv.url + "/queue",
                                        timeout=10) as r:
                queue = json.loads(r.read().decode())
            print(
                f"[serve] once: healthz ok={health.get('ok')}, /queue "
                f"depth={queue.get('depth')} capacity="
                f"{queue.get('capacity')} lanes={queue.get('lane_width')}",
                file=sys.stderr,
            )
            return 0
        while not stop_flag["stop"]:
            if (coord is not None and coord.role != "leader"
                    and coord.try_acquire()):
                _promote()
            record, progress = watch_dir(args.dir)
            if record is not None:
                srv.publish_record(record)
            if sup is not None:
                # the supervision pass (ISSUE 13): reap (releasing held
                # jobs immediately via release_dead — a kill -9 from
                # outside still goes the lease-expiry route), respawn
                # under backoff/breaker, autoscale
                sup.poll()
            time.sleep(max(args.poll, 0.2))
        print("[serve] draining: finishing the in-flight batch",
              file=sys.stderr)
    except KeyboardInterrupt:
        srv.begin_drain()
    finally:
        if ha["keeper"] is not None:
            # graceful exit releases the leadership lease so a standby
            # takes over immediately, not one lease + skew later
            ha["keeper"].stop(release=True)
        elif coord is not None:
            coord.release()
        if sup is not None:
            sup.stop()
        if worker is not None:
            worker.stop()  # joins after the current batch — the drain
        srv.stop()
    return 0


def cmd_worker(args) -> int:
    """`tpusim worker --join URL`: the fleet worker process (ISSUE 12).
    SIGTERM/SIGINT drain the in-flight batch before exit; a kill -9 is
    recovered by the lease protocol (the coordinator steals)."""
    import signal
    import threading

    from tpusim.compile_cache import enable_compile_cache
    from tpusim.svc.auth import load_token
    from tpusim.svc.client import ServiceError
    from tpusim.svc.fleet import run_worker

    enable_compile_cache()
    stop_event = threading.Event()

    def _graceful(_signum, _frame):
        stop_event.set()

    try:
        signal.signal(signal.SIGTERM, _graceful)
        signal.signal(signal.SIGINT, _graceful)
    except ValueError:
        pass  # non-main thread (tests drive run_worker directly)
    try:
        served = run_worker(
            args.join, worker_id=args.id, poll_s=args.poll,
            max_batches=args.max_batches,
            table_cache_dir=args.table_cache_dir,
            out=sys.stderr, stop_event=stop_event,
            mode=args.mode, cache_dir=args.cache_dir,
            token=load_token(getattr(args, "token_file", "")),
        )
    except ServiceError as err:
        print(f"tpusim worker: {err}", file=sys.stderr)
        return 1
    except (OSError, ValueError, json.JSONDecodeError) as err:
        print(f"tpusim worker: {err}", file=sys.stderr)
        return 2
    print(f"[worker] drained after {served} batch(es)", file=sys.stderr)
    return 0


def cmd_tune(args) -> int:
    """`tpusim tune`: the learned-scoring lane's CLI (ISSUE 9)."""
    from tpusim.learn import (
        LocalRollout,
        ObjectiveConfig,
        RemoteRollout,
        TuneConfig,
        format_holdout_report,
        holdout_report,
        make_family_sim,
        make_robust_eval,
        run_tune,
    )
    from tpusim.policies import POLICY_NAMES, is_policy_name
    from tpusim.svc.client import ServiceError
    from tpusim.svc.worker import load_trace

    try:
        learned = False
        if args.policy:
            # the --policy spec (ISSUE 14): for a LEARNED family the
            # parameters ARE the weight vector, so the loop below is
            # unchanged — only the bounds default (signs are meaningful)
            # and the --best-out format (a signed policy artifact)
            # differ. parse_policy_spec also accepts a built-in name
            # (weight 1000), which tunes like a --policies run.
            from tpusim.learn.policy import parse_learned_name, parse_policy_spec

            policies = [
                (n, int(w)) for n, w in parse_policy_spec(args.policy)
            ]
            learned = all(
                parse_learned_name(n) is not None for n, _ in policies
            )
        else:
            policies = [
                (str(n), int(w)) for n, w in json.loads(args.policies)
            ]
        for name, _ in policies:
            if not is_policy_name(name):
                raise ValueError(
                    f"unknown policy {name!r} (known: "
                    f"{', '.join(POLICY_NAMES)}, "
                    "LearnedScore[<feature>])"
                )
        w_lo = args.w_min if args.w_min is not None else (
            -4000 if learned else 0
        )
        w_hi = args.w_max if args.w_max is not None else 4000
        if learned:
            # fail BEFORE the (potentially hours-long) search, not at
            # the artifact export: the i32 theta vocabulary is hard-
            # bounded, and a best vector outside it cannot be saved
            from tpusim.learn.policy import THETA_HI, THETA_LO

            if w_lo < THETA_LO or w_hi > THETA_HI:
                raise ValueError(
                    f"--policy learned bounds [{w_lo}, {w_hi}] exceed "
                    f"the i32 theta export range [{THETA_LO}, "
                    f"{THETA_HI}]"
                )
        if not 0.0 <= args.holdout < 1.0:
            raise ValueError(
                f"--holdout must be in [0, 1), got {args.holdout}"
            )
        trace = load_trace(
            "default", args.nodes, args.pods, max_pods=args.max_pods
        )
        n_train = len(trace.pods) - int(len(trace.pods) * args.holdout)
        train, held = trace.pods[:n_train], trace.pods[n_train:]
        if not train:
            raise ValueError("no training pods left after the holdout split")

        cfg = TuneConfig(
            algo=args.algo, generations=args.generations,
            popsize=args.popsize, sigma=args.sigma, lr=args.lr,
            seed=args.seed, eval_seed=args.eval_seed,
            w_lo=w_lo, w_hi=w_hi,
            objective=ObjectiveConfig(
                w_alloc=args.obj_alloc, w_frag=args.obj_frag,
                w_unsched=args.obj_unsched,
                w_disrupt=args.obj_disrupt,
            ),
        )
        train_fault = None
        train_fault_meta = None
        if args.train_fault_mtbf > 0 or args.train_fault_evict_every > 0:
            from tpusim.sim.faults import FaultConfig

            train_fault = FaultConfig(
                mtbf_events=args.train_fault_mtbf,
                mttr_events=args.train_fault_mttr,
                evict_every_events=args.train_fault_evict_every,
                seed=args.train_fault_seed,
            )
            train_fault_meta = {
                "mtbf": float(args.train_fault_mtbf),
                "mttr": float(args.train_fault_mttr),
                "evict_every": float(args.train_fault_evict_every),
                "seed": int(args.train_fault_seed),
            }
        if args.url:
            if train_fault is not None:
                raise ValueError(
                    "--train-fault-* needs the local backend (the remote "
                    "job plane takes per-job `fault` fields instead — "
                    "submit a chaos grid through `tpusim submit`)"
                )
            # the service must host the SAME train prefix this CLI
            # computed (serve --jobs --max-pods), else the tuned vector
            # describes a different workload
            print(
                f"[tune] remote rollouts via {args.url} (service must "
                f"host the {len(train)}-pod train prefix of "
                f"{os.path.basename(args.pods)})", file=sys.stderr,
            )
            backend = RemoteRollout(
                args.url, policies, engine=args.engine,
                timeout=args.timeout, out=sys.stderr,
            )
        else:
            sim = make_family_sim(
                trace.nodes, train, policies, engine=args.engine
            )
            backend = LocalRollout(
                sim, width=args.popsize, fault=train_fault
            )

        robust_eval, robust_meta = None, None
        if args.robust_mtbf > 0:
            from tpusim.sim.faults import FaultConfig

            robust_eval = make_robust_eval(
                trace.nodes, train, policies,
                FaultConfig(
                    mtbf_events=args.robust_mtbf,
                    mttr_events=args.robust_mttr,
                    seed=args.robust_seed,
                ),
            )
            # lands in the log header: the robustness knobs shape the
            # log's bytes, so a resume under different ones must fail
            # loudly instead of writing a mixed log
            robust_meta = {
                "mtbf": float(args.robust_mtbf),
                "mttr": float(args.robust_mttr),
                "seed": int(args.robust_seed),
            }

        result = run_tune(
            backend, policies, cfg, args.log, resume=args.resume,
            robust_eval=robust_eval, robust_meta=robust_meta,
            train_fault_meta=train_fault_meta,
            out=sys.stderr,
        )

        from tpusim.obs.emitters import format_tuning_curve

        print(format_tuning_curve(result.records))
        print(
            f"[tune] best weights "
            f"{','.join(str(w) for w in result.best_weights)} "
            f"(objective {result.best_objective:+.4f}) after "
            f"{len(result.records)} generations -> {result.log_path}"
        )
        if held:
            eval_sim = make_family_sim(
                trace.nodes, held, policies, engine=args.engine
            )
            report = holdout_report(
                eval_sim, policies, result.best_weights,
                objective=cfg.objective, eval_seed=cfg.eval_seed,
            )
            print(format_holdout_report(report, policies))
        if args.best_out:
            if learned:
                # the learned lane exports a signed policy ARTIFACT —
                # the apply --policy / serve --policy-preset input
                from tpusim.learn.dataset import feature_names_of
                from tpusim.learn.policy import save_policy_artifact

                path = save_policy_artifact(
                    args.best_out, result.best_weights,
                    features=feature_names_of(policies),
                    meta={
                        "trained": args.algo,
                        "objective": result.best_objective,
                        "source": "tune",
                    },
                )
                print(f"[tune] wrote learned-policy artifact {path}",
                      file=sys.stderr)
            else:
                from tpusim.apply import save_weights_payload

                path = save_weights_payload(
                    args.best_out, [result.best_weights],
                    policies=policies,
                )
                print(f"[tune] wrote tuned weights payload {path}",
                      file=sys.stderr)
    except ServiceError as err:
        # remote-backend failures (service down, job failed server-side,
        # wait timeout) exit 1 like `tpusim submit` — the run state is
        # safe: the log holds every completed generation and --resume
        # continues from it
        print(f"tpusim tune: {err}", file=sys.stderr)
        return 1
    except (OSError, ValueError, json.JSONDecodeError) as err:
        print(f"tpusim tune: {err}", file=sys.stderr)
        return 2
    return 0


def cmd_imitate(args) -> int:
    """`tpusim imitate`: the supervised-imitation trainer (ISSUE 14) —
    decision JSONL -> teacher-forced feature extraction -> pairwise
    ranking fit -> i32 export -> held-out top-1 agreement (+ optional
    signed artifact)."""
    import numpy as np

    from tpusim.learn import (
        ImitateConfig,
        TeacherReplay,
        imitate_with_mining,
        load_teacher_log,
        save_policy_artifact,
    )
    from tpusim.learn.policy import FEATURE_SETS
    from tpusim.sim.workload import sort_cluster_pods
    from tpusim.svc.worker import load_trace

    try:
        if not 0.0 <= args.holdout < 1.0:
            raise ValueError(
                f"--holdout must be in [0, 1), got {args.holdout}"
            )
        header, rows = load_teacher_log(args.decisions)
        teacher = "+".join(
            n for n, _ in header.get("policies", [])
        ) or "?"
        trace = load_trace(
            "default", args.nodes, args.pods, max_pods=args.max_pods
        )
        # the driver's run() prep: stable (creation_time, name) sort,
        # no shuffle/tuning — a log recorded under other prep options
        # fails the replay's feasible-count cross-check loudly
        pods = sort_cluster_pods(
            list(trace.pods), False, np.random.default_rng(233)
        )
        features = FEATURE_SETS[args.features]
        replay = TeacherReplay(
            trace.nodes, pods, header, rows, features=features
        )
        cut = len(rows) - int(len(rows) * args.holdout)
        print(
            f"[imitate] teacher {teacher}: {len(rows)} events, training "
            f"on [0, {cut}), holdout from event {cut}", file=sys.stderr,
        )
        _, theta, _hist = imitate_with_mining(
            replay,
            ImitateConfig(steps=args.steps, lr=args.lr, l2=args.l2,
                          seed=args.seed),
            end_event=cut, out=sys.stderr,
        )
        rep_train = replay.agreement(theta)
        rep_held = replay.agreement(theta, start_event=cut)
        print(
            f"[imitate] exported theta "
            f"{','.join(str(t) for t in theta)}"
        )
        print(
            f"[imitate] teacher-forced top-1 agreement: "
            f"{rep_train['matches']}/{rep_train['creates']} "
            f"({100 * rep_train['agreement']:.2f}%) overall, "
            f"{rep_held['matches']}/{rep_held['creates']} "
            f"({100 * rep_held['agreement']:.2f}%) on the held-out "
            "suffix"
        )
        if args.out:
            path = save_policy_artifact(
                args.out, theta, features=features,
                meta={
                    "trained": "imitation",
                    "teacher": header.get("policies", []),
                    "agreement_holdout": rep_held["agreement"],
                    "source": "imitate",
                },
            )
            print(f"[imitate] wrote learned-policy artifact {path}",
                  file=sys.stderr)
    except (OSError, ValueError, json.JSONDecodeError) as err:
        print(f"tpusim imitate: {err}", file=sys.stderr)
        return 2
    return 0


def cmd_submit(args) -> int:
    from tpusim.svc.client import (
        JobsFailed,
        ServiceError,
        format_results_table,
        submit_and_wait,
    )
    from tpusim.svc.jobs import docs_from_payload

    # exit discipline: 2 on unusable input or a failed round-trip (one-
    # line error), 1 when the service ran but some JOBS failed — partial
    # results still print, the exit code never reads as success
    try:
        with open(args.jobs) as f:
            payload = json.load(f)
        # shape-routed: grid files expand per row, single job documents
        # (incl. ones carrying a flat `weights` vector) pass through
        docs = docs_from_payload(payload)
        from tpusim.svc.auth import load_token

        results = submit_and_wait(
            args.url, docs, timeout=args.timeout, out=sys.stderr,
            token=load_token(getattr(args, "token_file", "")),
        )
    except JobsFailed as err:
        if err.results:
            print(format_results_table(err.results))
        for d in err.failed:
            print(
                f"[submit] FAILED {d['id']}: {d.get('error', '?')}",
                file=sys.stderr,
            )
        print(f"tpusim submit: {err}", file=sys.stderr)
        return 1
    except (OSError, ValueError, json.JSONDecodeError,
            ServiceError) as err:
        print(f"tpusim submit: {err}", file=sys.stderr)
        return 2
    print(f"[submit] {len(results)} job(s) done via {args.url}",
          file=sys.stderr)
    print(format_results_table(results))
    return 0


def cmd_trace(args) -> int:
    """`tpusim trace <job-digest>` — stitch the per-process span files
    under an artifact dir into one cross-process timeline (ISSUE 19).
    Exit 2 when the dir holds no matching spans (unusable input, the
    CLI discipline), 0 otherwise — file-level problems (torn lines,
    bad signatures) print loudly but don't fail the stitch."""
    from tpusim.obs import trace as obs_trace

    if not os.path.isdir(args.dir):
        print(f"tpusim trace: no such artifact dir {args.dir!r}",
              file=sys.stderr)
        return 2
    spans, problems = obs_trace.stitch(
        args.dir, job=args.job, trace=args.trace_id
    )
    for p in problems:
        print(f"[trace] WARNING: {p}", file=sys.stderr)
    if not spans:
        what = f" for job {args.job!r}" if args.job else ""
        print(f"tpusim trace: no spans{what} under {args.dir}",
              file=sys.stderr)
        return 2
    for line in obs_trace.format_timeline(spans):
        print(line)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(obs_trace.chrome_trace(spans), f)
        print(f"[trace] wrote Chrome trace {args.out} "
              f"({len(spans)} spans)", file=sys.stderr)
    return 0


def _audit_over_http(args) -> int:
    """The --url form of `tpusim audit`: GET /events with cursor
    pagination. One shot prints the newest --tail records; --follow
    keeps walking `after = next_after` so every poll is a delta."""
    import time
    import urllib.error
    import urllib.parse
    import urllib.request

    from tpusim.obs import audit as obs_audit

    base = args.url.rstrip("/")
    filters = {"kind": args.kind, "job": args.job, "worker": args.worker}

    def fetch(after: int, limit: int) -> dict:
        q = {k: v for k, v in filters.items() if v}
        q["limit"] = str(limit)
        if after:
            q["after"] = str(after)
        url = f"{base}/events?{urllib.parse.urlencode(q)}"
        with urllib.request.urlopen(url, timeout=10.0) as resp:
            return json.loads(resp.read().decode())

    try:
        doc = fetch(0, max(args.tail, 1) if args.tail else 500)
    except (urllib.error.URLError, OSError, ValueError) as err:
        print(f"tpusim audit: {base}/events unreachable: {err}",
              file=sys.stderr)
        return 2
    for line in obs_audit.format_records(doc.get("events") or []):
        print(line)
    if not args.follow:
        if not doc.get("events"):
            print("[audit] no matching records", file=sys.stderr)
        return 0
    cursor = int(doc.get("next_after") or 0)
    try:
        while True:
            time.sleep(2.0)
            try:
                doc = fetch(cursor, 500)
            except (urllib.error.URLError, OSError, ValueError) as err:
                print(f"[audit] poll failed ({err}); retrying",
                      file=sys.stderr)
                continue
            for line in obs_audit.format_records(doc.get("events") or []):
                print(line, flush=True)
            cursor = max(cursor, int(doc.get("next_after") or 0))
    except KeyboardInterrupt:
        return 0


def cmd_top(args) -> int:
    """`tpusim top URL` — the live fleet dashboard (ISSUE 20)."""
    from tpusim.obs import top as obs_top

    return obs_top.run(
        args.url, interval=args.interval, once=args.once,
        width=args.width,
    )


def cmd_audit(args) -> int:
    """`tpusim audit [--verify]` — query or verify the hash-chained
    control-plane audit log (ISSUE 19). --verify exits 1 LOUDLY on a
    broken chain (edit, truncation, torn tail, missing head).
    --url tails a LIVE coordinator via the /events seq cursor
    (ISSUE 20): each poll asks only for records past the last seen
    seq, so a long-lived fleet's tail ships deltas, not the chain."""
    from tpusim.obs import audit as obs_audit

    if args.url:
        return _audit_over_http(args)
    path = obs_audit.audit_path(args.dir)
    if not os.path.isfile(path):
        print(f"tpusim audit: no audit log at {path}", file=sys.stderr)
        return 2
    if args.verify:
        try:
            n = obs_audit.verify(path)
        except ValueError as err:
            print(f"tpusim audit: CHAIN BROKEN: {err}", file=sys.stderr)
            return 1
        print(f"[audit] chain intact: {n} record(s), head verified")
        return 0
    try:
        records = obs_audit.tail(
            path, n=args.tail, kind=args.kind, job=args.job,
            worker=args.worker,
        )
    except ValueError as err:
        print(f"tpusim audit: chain unreadable: {err}", file=sys.stderr)
        return 1
    for line in obs_audit.format_records(records):
        print(line)
    if not records:
        print("[audit] no matching records", file=sys.stderr)
    return 0


def cmd_gen_doc(parser: argparse.ArgumentParser, args) -> int:
    os.makedirs(args.dir, exist_ok=True)
    path = os.path.join(args.dir, "tpusim.md")
    with open(path, "w") as f:
        f.write(f"# tpusim\n\n```\n{parser.format_help()}\n```\n")
    print(f"wrote {path}")
    return 0


def main(argv=None) -> int:
    parser = _build_parser()
    args = parser.parse_args(argv)
    if args.command == "apply":
        return cmd_apply(args)
    if args.command == "explain":
        return cmd_explain(args)
    if args.command == "diff":
        return cmd_diff(args)
    if args.command == "report":
        return cmd_report(args)
    if args.command == "serve":
        return cmd_serve(args)
    if args.command == "worker":
        return cmd_worker(args)
    if args.command == "tune":
        return cmd_tune(args)
    if args.command == "imitate":
        return cmd_imitate(args)
    if args.command == "submit":
        return cmd_submit(args)
    if args.command == "trace":
        return cmd_trace(args)
    if args.command == "audit":
        return cmd_audit(args)
    if args.command == "top":
        return cmd_top(args)
    if args.command == "version":
        print(f"tpusim version {VERSION} (commit {COMMIT})")
        return 0
    if args.command == "gen-doc":
        return cmd_gen_doc(parser, args)
    if args.command == "debug":
        return 0  # ref: cmd/debug/debug.go run() is empty
    parser.print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())
