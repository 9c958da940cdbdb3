"""Applier: Simon-CR-driven experiment orchestration.

The reference's pkg/apply/apply.go Run() + pkg/simulator/core.go Simulate()
pipeline, driving the array-state Simulator:

  load CR → load cluster YAML dir (+ apps / Helm charts) → daemonset pods →
  typical pods → sort/tune workload → replay → ClusterAnalysis(InitSchedule)
  → snapshot export → inflation eval → new-workload swap → deschedule +
  reschedule → per-app scheduling → success/failure verdict.

Env caps MaxCPU/MaxMemory/MaxVG (apply.go:550-631 satisfyResourceSetting)
are honored for the final verdict; MaxVG reads the open-local VG totals
from the node storage annotations (see _satisfy_resource_setting).
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np

from tpusim.config.scheduler import SchedulerConfig, load_scheduler_config
from tpusim.config.simon import SimonCR, load_simon_cr
from tpusim.io.k8s_yaml import ClusterResource, load_cluster_from_dir
from tpusim.io.trace import PodRow
from tpusim.sim.driver import SimulateResult, Simulator, SimulatorConfig

COLOR_RED = "\033[31m"
COLOR_GREEN = "\033[32m"
COLOR_RESET = "\033[0m"


@dataclass
class ApplyOptions:
    """CLI surface (ref: cmd/apply/apply.go:26-40)."""

    simon_config: str = ""
    default_scheduler_config: str = ""
    use_greed: bool = False
    interactive: bool = False
    extended_resources: List[str] = field(default_factory=lambda: ["gpu"])
    base_dir: str = "."
    report_tables: bool = False
    # exact checkpoint/resume of the main replay (ISSUE 2; README
    # "Checkpoint/resume"): segment length in events, 0 = off
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
    # retention (ISSUE 16): 0 = prune behind the run (resume-only),
    # -1 = keep every segment carry (the warm-fork ladder), N>0 = newest N
    checkpoint_keep: int = 0
    # fault injection (README "Fault injection"): MTBF-style schedule
    # knobs, all in EVENTS; mtbf 0 = no node failures, evict 0 = no
    # preemptions. Any non-zero rate routes the main schedule through
    # Simulator.run_with_faults.
    fault_mtbf: float = 0.0
    fault_mttr: float = 0.0
    fault_evict_every: float = 0.0
    fault_seed: int = 0
    fault_max_retries: int = 3
    # observability (README "Profiling & telemetry"; tpusim.obs): any
    # non-empty output path switches the run into profiling mode (phase
    # spans get the compile/execute split) and emits the corresponding
    # artifact after the run.
    profile_out: str = ""  # JSONL run record (appended)
    metrics_out: str = ""  # Prometheus textfile (atomic rewrite)
    trace_out: str = ""  # Chrome-trace timeline
    heartbeat_every: int = 0  # in-scan progress ticks (0 = off)
    # decision-provenance flight recorder (ISSUE 4; README "Explain a
    # placement"): a non-empty path turns record_decisions on and writes
    # the run's decision JSONL there — the input of `tpusim explain` /
    # `tpusim diff`.
    decisions_out: str = ""
    # in-scan cluster time-series plane (ISSUE 5; README "Live
    # monitoring"): > 0 samples utilization/frag/score distributions
    # every N processed events from inside the scan
    # (SimulatorConfig.series_every); the series lands in the JSONL run
    # record, the Chrome counter tracks, and `tpusim report`.
    series_every: int = 0
    # live monitoring endpoint: "HOST:PORT" / ":PORT" / "PORT" starts a
    # threaded HTTP server (tpusim.obs.server.MonitorServer) for the
    # run's lifetime — /metrics (Prometheus text; the final publish is
    # byte-equal to --metrics-out), /healthz, /progress (heartbeat-fed
    # phase/ev-per-s/ETA). Empty = off; bare ":PORT" binds loopback.
    listen: str = ""
    # config-axis sweep (ISSUE 6; README "Sweep many configs in one
    # compile"): a weights JSON here replaces the main schedule with ONE
    # vmapped replay over its [B, num_pol] weight grid (+ optional
    # per-config seeds) and prints the per-config summary table. The
    # file is either a bare [[w...], ...] list or
    # {"weights": [[...]], "seeds": [...]}.
    sweep_weights: str = ""
    # chaos sweep (ISSUE 10; README "Chaos sweep"): a faults JSON here
    # replaces the main schedule with ONE vmapped fault-lane replay —
    # same trace, B fault schedules (seed/MTBF/evict cadence/backoff as
    # per-lane operands) — and prints the per-lane disruption frontier.
    # The file is a bare [{...FaultConfig fields...}, ...] list or
    # {"faults": [...], "weights": [[...]], "seeds": [...]}.
    sweep_faults: str = ""
    # score-plugin override (ISSUE 14): 'LearnedScore:FILE.json' replays
    # a signed learned-policy artifact as the (only) scoring family,
    # 'learned'/'learned-bucketed' the default-parameter families, or a
    # built-in name at weight 1000. Empty = the scheduler config's
    # plugins. A gpuSelMethod delegating to a policy the override
    # removed falls back to 'best' (the learned family carries no
    # Reserve-phase device pick of its own).
    policy: str = ""


class Applier:
    def __init__(self, options: ApplyOptions):
        if not options.simon_config:
            raise ValueError("--simon-config is required")
        self.options = options
        self.cr: SimonCR = load_simon_cr(options.simon_config, options.base_dir)
        self.sched_cfg: SchedulerConfig = load_scheduler_config(
            options.default_scheduler_config
        )
        # kubeConfig mode: the reference connects a kube-client and lists
        # the cluster's objects (CreateClusterResourceFromClient,
        # simulator.go:746-830). Here the kubeConfig path accepts BOTH a
        # kubeconfig credential file (live API server, thin HTTP client in
        # tpusim.io.kube_client) and a `kubectl get ... -o yaml` dump
        # (offline fallback); run() routes on the file's shape.

    def _simulator_config(self) -> SimulatorConfig:
        cc = self.cr.custom_config
        policies = self.sched_cfg.policy_tuple()
        gpu_sel = self.sched_cfg.gpu_sel_method
        if self.options.policy:
            # --policy override (ISSUE 14): replace the scheduler
            # config's plugin family wholesale; a policy-delegated
            # gpuSelMethod whose plugin is no longer enabled would
            # silently degrade inside the step, so resolve it to 'best'
            # loudly here
            from tpusim.learn.policy import parse_policy_spec

            policies = tuple(parse_policy_spec(self.options.policy))
            if gpu_sel not in ("best", "worst", "random") and gpu_sel not in {
                n for n, _ in policies
            }:
                print(
                    f"[policy] gpuSelMethod {gpu_sel!r} delegates to a "
                    "plugin the --policy override removed; using 'best'",
                    file=sys.stderr,
                )
                gpu_sel = "best"
        return SimulatorConfig(
            policies=policies,
            gpu_sel_method=gpu_sel,
            dim_ext_method=self.sched_cfg.dim_ext_method,
            norm_method=self.sched_cfg.norm_method,
            shuffle_pod=cc.shuffle_pod,
            tuning_ratio=cc.tuning.ratio,
            tuning_seed=cc.tuning.seed,
            inflation_ratio=cc.inflation.ratio,
            inflation_seed=cc.inflation.seed,
            typical_pods=cc.typical_pods,
            deschedule_ratio=cc.deschedule.ratio,
            deschedule_policy=cc.deschedule.policy,
            use_timestamps=cc.use_timestamps,
            engine=cc.engine,
            mesh=cc.mesh,
            extenders=self.sched_cfg.extenders,
            checkpoint_every=self.options.checkpoint_every,
            checkpoint_dir=self.options.checkpoint_dir,
            checkpoint_keep=self.options.checkpoint_keep,
            profile=bool(
                self.options.profile_out or self.options.metrics_out
                or self.options.trace_out
            ),
            heartbeat_every=self.options.heartbeat_every,
            record_decisions=bool(self.options.decisions_out),
            series_every=self.options.series_every,
        )

    def _fault_config(self):
        """FaultConfig from the --fault-* flags, or None when fault
        injection is off (no failure/eviction rate configured)."""
        o = self.options
        if o.fault_mtbf <= 0 and o.fault_evict_every <= 0:
            return None
        from tpusim.sim.faults import FaultConfig

        return FaultConfig(
            mtbf_events=o.fault_mtbf,
            mttr_events=o.fault_mttr,
            evict_every_events=o.fault_evict_every,
            seed=o.fault_seed,
            max_retries=o.fault_max_retries,
        )

    def _load_apps(self, node_names: Sequence[str]) -> List[tuple]:
        """appList → [(name, pods)] (apply.go:118-141; Helm charts render
        through tpusim.io.chart). App DaemonSets expand over the CLUSTER's
        nodes, which an app-only ClusterResource does not know about."""
        from tpusim.io.chart import chart_objects
        from tpusim.io.k8s_yaml import (
            daemonset_pods,
            load_cluster_from_objects,
            load_objects,
            yaml_files_in_dir,
        )

        apps = []
        for app in self.cr.app_list:
            if app.chart:
                objs = chart_objects(app.name, app.path)
            else:
                objs = load_objects(yaml_files_in_dir(app.path))
            res = load_cluster_from_objects(objs)
            pods = list(res.workload_pods())
            for ds in res.daemonsets:
                pods.extend(daemonset_pods(ds, node_names))
            apps.append((app.name, pods))
        if self.options.interactive and apps:
            apps = _interactive_select(apps)
        return apps

    def run(self, out=sys.stdout) -> SimulateResult:
        if self.cr.kube_config:
            from tpusim.io.k8s_yaml import load_cluster_from_dump
            from tpusim.io.kube_client import (
                is_kubeconfig_file,
                load_cluster_from_client,
            )

            if is_kubeconfig_file(self.cr.kube_config):
                cluster = load_cluster_from_client(self.cr.kube_config)
            else:
                cluster = load_cluster_from_dump(self.cr.kube_config)
            if not cluster.nodes:
                raise ValueError(
                    f"no Node objects from kubeConfig {self.cr.kube_config}"
                )
        else:
            cluster = load_cluster_from_dir(self.cr.custom_cluster)
        if not cluster.nodes:
            raise ValueError(f"no Node manifests under {self.cr.custom_cluster}")
        cc = self.cr.custom_config

        # live monitoring endpoint (--listen): up BEFORE the replay so a
        # scraper sees the run from its first phase; lives for the
        # process (a daemon thread — `tpusim serve` covers post-hoc
        # watching of checkpoint/record directories)
        self.monitor = None
        if self.options.listen:
            from tpusim.obs.server import MonitorServer

            self.monitor = MonitorServer(self.options.listen).start()
            self.monitor.attach_heartbeat()
            self.monitor.publish_progress(phase="loading")
            print(
                f"[obs] monitoring at {self.monitor.url} "
                "(/metrics /healthz /progress)", file=out,
            )

        sim = Simulator(cluster.nodes, self._simulator_config())
        sim.log.stream = out
        self.sim = sim

        # workload = trace pods + per-node daemonset pods (core.go:103-123)
        workload = cluster.workload_pods()
        ds_pods = cluster.daemonset_pods()
        sim.set_workload_pods(workload + ds_pods)
        fault_cfg = self._fault_config()
        if self.options.sweep_faults:
            # chaos sweep replaces the main schedule: one vmapped scan
            # over B fault schedules, the disruption frontier table
            if self.options.sweep_weights:
                raise ValueError(
                    "--sweep-faults and --sweep-weights are separate "
                    "sweep axes; pass per-lane weights inside the faults "
                    "JSON instead"
                )
            if fault_cfg is not None:
                raise ValueError(
                    "--sweep-faults replaces the --fault-* flags (each "
                    "lane carries its own schedule)"
                )
            return self._run_chaos(sim, out)
        if self.options.sweep_weights:
            # config-axis sweep replaces the main schedule: one vmapped
            # replay over the weight grid, a summary table, telemetry —
            # no snapshot/inflation/deschedule stages (they describe one
            # placement run, not B of them)
            if fault_cfg is not None:
                raise ValueError(
                    "--sweep-weights cannot combine with fault injection "
                    "(the vmapped sweep replays a single uninterrupted "
                    "event stream per config)"
                )
            return self._run_sweep(sim, out)
        if self.monitor is not None:
            self.monitor.publish_progress(
                phase="scheduling", nodes=len(cluster.nodes),
                pods=len(workload) + len(ds_pods),
            )
        if fault_cfg is not None:
            sim.run_with_faults(fault_cfg)
        else:
            sim.run()

        # snapshot export at InitSchedule (core.go:160-185)
        self._export_snapshots(sim, "init_schedule")

        # workload inflation eval (core.go:189-192)
        if cc.inflation.ratio > 1:
            sim.run_workload_inflation_evaluation("ScheduleInflation")

        # new-workload swap (core.go:195-209): replace the typical-pod
        # distribution with the new workload's, then schedule it on top
        if cc.new_workload_config:
            nw_dir = cc.new_workload_config
            if not os.path.isabs(nw_dir):
                nw_dir = os.path.join(self.options.base_dir, nw_dir)
            nw = load_cluster_from_dir(nw_dir)
            nw_pods = nw.workload_pods()
            sim.set_workload_pods(nw_pods)
            sim.set_typical_pods()
            sim.schedule_additional(nw_pods)
            sim.cluster_analysis("InitSchedule")

        # deschedule + reschedule (core.go:213-246)
        if cc.deschedule.ratio > 0 and cc.deschedule.policy:
            sim.deschedule_cluster()
            sim.cluster_analysis("PostDeschedule")
            self._export_snapshots(sim, "post_deschedule")
            if cc.inflation.ratio > 1:
                sim.run_workload_inflation_evaluation("DescheduleInflation")

        # per-app scheduling (core.go:255-261)
        for name, pods in self._load_apps(cluster.node_names):
            sim.schedule_app(name, pods, self.options.use_greed)

        result = sim.last_result
        sim.finish()
        self._note_compile_cache(sim)
        self._emit_telemetry(sim, out)
        if self.monitor is not None:
            self.monitor.publish_progress(
                phase="done", events_done=result.events,
                events_total=result.events,
            )
        self._emit_decisions(sim, out)
        self._verdict(result, out)
        if self.options.report_tables:
            from tpusim.sim.report_tables import full_report

            print(
                full_report(
                    result.pods,
                    result.placed_node,
                    result.dev_mask,
                    cluster.nodes,
                    self.options.extended_resources,
                ),
                file=out,
            )
        return result

    def _note_compile_cache(self, sim: Simulator):
        """Record the persistent-compilation-cache outcome on the run's
        telemetry (the `timing.compile_cache` block of the JSONL record;
        counted through jax.monitoring, obs.spans.note_compile_cache). The
        directory is whatever the process runs under — the entry point
        placed it (tpusim.compile_cache), not this run."""
        import jax

        from tpusim.obs import note_compile_cache

        cache_dir = jax.config.jax_compilation_cache_dir or ""
        note_compile_cache(
            sim.obs, enabled=bool(cache_dir), cache_dir=cache_dir
        )

    def _run_sweep(self, sim: Simulator, out):
        """`apply --sweep-weights`: load the weight grid, run the
        config-axis sweep (one compiled scan for all B configs; per-lane
        `tunes` ride the multi-trace sweep, ISSUE 7), print the
        per-config summary table (README "Sweep many configs in one
        compile")."""
        from tpusim.sim.driver import format_sweep_table

        weights, seeds, tunes = load_weights_payload(
            self.options.sweep_weights
        )
        lanes = sim.run_sweep(weights, seeds=seeds, tunes=tunes)
        print(
            f"[Sweep] {len(lanes)} configs x {lanes[0].events} events "
            f"in one compiled scan ({sim._last_engine})",
            file=out,
        )
        print(format_sweep_table(lanes, sim.cfg.policies), file=out)
        self._note_compile_cache(sim)
        self._emit_telemetry(sim, out)
        if self.monitor is not None:
            self.monitor.publish_progress(
                phase="done", events_done=lanes[0].events * len(lanes),
                events_total=lanes[0].events * len(lanes),
            )
        return None

    def _run_chaos(self, sim: Simulator, out):
        """`apply --sweep-faults`: load the per-lane fault documents, run
        the chaos sweep (one compiled vmapped scan for all B disruption
        what-ifs), print the per-lane disruption frontier (README "Chaos
        sweep")."""
        from tpusim.sim.driver import format_chaos_table

        specs, weights, seeds = load_faults_payload(
            self.options.sweep_faults, sim.cfg.policies
        )
        lanes = sim.run_sweep(weights, seeds=seeds, faults=specs)
        print(
            f"[Chaos] {len(lanes)} fault lanes x {lanes[0].events} events "
            f"in one compiled scan ({sim._last_engine})",
            file=out,
        )
        print(format_chaos_table(lanes, sim.cfg.policies), file=out)
        self._note_compile_cache(sim)
        self._emit_telemetry(sim, out)
        if self.monitor is not None:
            self.monitor.publish_progress(
                phase="done", events_done=sum(l.events for l in lanes),
                events_total=sum(l.events for l in lanes),
            )
        return None

    def _series_block(self, sim: Simulator):
        """The run's in-scan series as a JSONL record block, or None when
        series sampling was off (no key then — old records stay
        byte-identical)."""
        res = getattr(sim, "last_result", None)
        if res is None or res.series is None:
            return None
        from tpusim.obs.series import series_to_record

        return series_to_record(
            res.series, sim.cfg.series_every,
            [name for name, _ in sim.cfg.policies],
        )

    def _emit_telemetry(self, sim: Simulator, out):
        """Write the requested obs artifacts (--profile / --metrics-out /
        --trace-out) from the full experiment's telemetry — every stage
        (main schedule, inflation, deschedule, apps) contributed spans
        and counters to the one recorder. The record is built ONCE and
        shared with the live /metrics endpoint, so the final scrape of a
        --listen run is byte-equal to the --metrics-out textfile."""
        o = self.options
        if not (o.profile_out or o.metrics_out or o.trace_out
                or self.monitor is not None):
            return
        from tpusim.obs import emitters

        telemetry = sim.run_telemetry()
        record = emitters.build_record(
            telemetry, series=self._series_block(sim)
        )
        counter_series = None
        if o.trace_out:
            # only the Chrome-trace emitter consumes the counter series;
            # building it walks every per-event report row (O(E)). The
            # in-scan series adds its own counter tracks (per sample, not
            # per event — each track is laid across the wall window
            # independently).
            counter_series = sim.event_counter_series()
            last = getattr(sim, "last_result", None)
            if last is not None and last.series is not None:
                from tpusim.obs.series import series_tracks

                counter_series.update(series_tracks(last.series))
        paths = emitters.emit_record(
            record, telemetry.spans,
            jsonl=o.profile_out, metrics=o.metrics_out, trace=o.trace_out,
            counter_series=counter_series,
        )
        if self.monitor is not None:
            self.monitor.publish_record(record)
        for p in paths:
            print(f"[obs] wrote {p}", file=out)

    def _emit_decisions(self, sim: Simulator, out):
        """Persist the run's decision-provenance stream (--decisions-out)
        — the `tpusim explain` / `tpusim diff` input (ISSUE 4)."""
        path = self.options.decisions_out
        if not path:
            return
        from tpusim.obs import decisions as obs_decisions

        res = sim.last_result
        if res.decisions is None:
            print(
                "[obs] no decision stream recorded (engine without "
                "provenance support?)", file=out,
            )
            return
        written = obs_decisions.write_decisions(
            path, res.decisions,
            policies=list(sim.cfg.policies),
            meta=sim._telemetry_meta(),
            pod_names=[p.name for p in res.pods],
        )
        print(f"[obs] wrote {written}", file=out)

    def _export_snapshots(self, sim: Simulator, tag: str):
        exp = self.cr.custom_config.export
        if exp.pod_snapshot_yaml_file_prefix:
            path = f"{exp.pod_snapshot_yaml_file_prefix}_{tag}.yaml"
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            sim.export_pod_snapshot_yaml(path)
        if exp.node_snapshot_csv_file_prefix:
            path = f"{exp.node_snapshot_csv_file_prefix}_{tag}.csv"
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            sim.export_node_snapshot_csv(path)
            sim.export_pod_snapshot_csv(
                f"{exp.node_snapshot_csv_file_prefix}_{tag}_pod.csv"
            )

    def _verdict(self, result: SimulateResult, out):
        """Success print + env resource caps (apply.go:219-246, 550-631)."""
        if result.unscheduled_pods:
            print(
                f"{COLOR_RED}there are {len(result.unscheduled_pods)} "
                f"unscheduled pods{COLOR_RESET}",
                file=out,
            )
            print(f"{COLOR_RED}Failed!{COLOR_RESET}", file=out)
            return
        ok, reason = self._satisfy_resource_setting(result)
        if not ok:
            print(f"{COLOR_RED}{reason}{COLOR_RESET}", file=out)
            print(f"{COLOR_RED}Failed!{COLOR_RESET}", file=out)
        else:
            print(f"{COLOR_GREEN}Success!{COLOR_RESET}", file=out)

    def _satisfy_resource_setting(self, result: SimulateResult):
        """Env caps MaxCPU / MaxMemory / MaxVG as PERCENT occupancy-rate
        ceilings over cluster totals (ref: satisfyResourceSetting,
        apply.go:550-631: defaults 100, out-of-range values clamp to 100;
        VG totals come from the open-local node storage annotations)."""

        def _cap(env: str) -> int:
            raw = os.environ.get(env, "")
            if not raw:
                return 100
            v = int(raw)  # non-integers are an error in the reference too
            return 100 if v > 100 or v < 0 else v

        max_cpu, max_mem, max_vg = _cap("MaxCPU"), _cap("MaxMemory"), _cap("MaxVG")
        s = result.state
        cpu_rate = int(
            100.0 * (np.asarray(s.cpu_cap) - np.asarray(s.cpu_left)).sum()
            / max(1, np.asarray(s.cpu_cap, np.int64).sum())
        )
        mem_rate = int(
            100.0 * (np.asarray(s.mem_cap) - np.asarray(s.mem_left)).sum()
            / max(1, np.asarray(s.mem_cap, np.int64).sum())
        )
        if cpu_rate > max_cpu:
            return False, (
                f"the average occupancy rate({cpu_rate}%) of cpu goes beyond "
                f"the env setting({max_cpu}%)\n"
            )
        if mem_rate > max_mem:
            return False, (
                f"the average occupancy rate({mem_rate}%) of memory goes "
                f"beyond the env setting({max_mem}%)\n"
            )
        from tpusim.io.storage import cluster_vg_totals, parse_node_storage

        vg_req, vg_cap = cluster_vg_totals(
            parse_node_storage(n.local_storage) for n in self.sim.nodes
        )
        if vg_cap:
            vg_rate = int(100.0 * vg_req / vg_cap)
            if vg_rate > max_vg:
                return False, (
                    f"the average occupancy rate({vg_rate}%) of vg goes "
                    f"beyond the env setting({max_vg}%)\n"
                )
        return True, ""


def load_weights_payload(path: str):
    """Weights-grid JSON -> (weights, seeds, tunes): a bare
    [[w, ...], ...] list of rows, or {"weights": [[...]], "seeds":
    [...], "tunes": [...]} with the optional per-row seed/tune vectors.
    Shared vocabulary of `apply --sweep-weights` and the `tpusim submit`
    grid form (tpusim.svc.jobs.jobs_from_grid expands the same shape
    into job documents)."""
    import json

    with open(path) as f:
        payload = json.load(f)
    if isinstance(payload, dict):
        weights = payload.get("weights")
        seeds = payload.get("seeds")
        tunes = payload.get("tunes")
    else:
        weights, seeds, tunes = payload, None, None
    if not weights:
        raise ValueError(
            f"{path}: no weight rows (want [[w, ...], ...] or "
            '{"weights": [[...]], "seeds": [...], "tunes": [...]})'
        )
    return weights, seeds, tunes


# every key a chaos-lane fault document may carry — FaultConfig's field
# names exactly; unknown keys are rejected loudly (a typo'd "mtbf" must
# not silently run a fault-free lane)
FAULT_PAYLOAD_KEYS = frozenset((
    "mtbf_events", "mttr_events", "evict_every_events", "seed",
    "max_retries", "backoff_base", "backoff_cap", "queue_capacity",
))


def load_faults_payload(path: str, policies):
    """Chaos-sweep JSON -> (fault_specs, weights, seeds) for
    `Simulator.run_sweep(faults=...)`: a bare [{...FaultConfig
    fields...}, ...] list of per-lane fault documents, or
    {"faults": [...], "weights": [[...]], "seeds": [...]} with optional
    per-lane weight rows / seeds (defaults: the scheduler config's
    weights and cfg.seed for every lane)."""
    import json

    from tpusim.sim.faults import FaultConfig

    with open(path) as f:
        payload = json.load(f)
    if isinstance(payload, dict):
        docs = payload.get("faults")
        weights = payload.get("weights")
        seeds = payload.get("seeds")
        unknown = set(payload) - {"faults", "weights", "seeds"}
        if unknown:
            raise ValueError(
                f"{path}: unknown key(s) {sorted(unknown)} (known: "
                "faults, weights, seeds)"
            )
    else:
        docs, weights, seeds = payload, None, None
    if not isinstance(docs, list) or not docs:
        raise ValueError(
            f"{path}: no fault lanes (want [{{...FaultConfig fields...}}, "
            '...] or {"faults": [...], "weights": [[...]], "seeds": [...]})'
        )
    specs = []
    for i, doc in enumerate(docs):
        if not isinstance(doc, dict):
            raise ValueError(f"{path}: fault lane {i} must be an object")
        unknown = set(doc) - FAULT_PAYLOAD_KEYS
        if unknown:
            raise ValueError(
                f"{path}: fault lane {i} has unknown key(s) "
                f"{sorted(unknown)} (known: {sorted(FAULT_PAYLOAD_KEYS)})"
            )
        specs.append(FaultConfig(**doc))
    if weights is None:
        weights = [[w for _, w in policies]] * len(specs)
    if len(weights) != len(specs):
        raise ValueError(
            f"{path}: {len(weights)} weight rows for {len(specs)} fault "
            "lanes"
        )
    return specs, weights, seeds


def save_weights_payload(path: str, weights, seeds=None, tunes=None,
                         policies=None) -> str:
    """Write a weights-grid JSON in the exact shape load_weights_payload /
    `tpusim submit` read back — the shared weights-payload I/O (ISSUE 9):
    `tpusim tune --best-out` persists its tuned vector here so the next
    `apply --sweep-weights` or `submit` run replays it unchanged. Rows
    are coerced to plain ints (the engines' i32 operand space); the
    optional `policies` key names the columns for submit's grid form.
    Atomic (tmp + rename) like every other artifact writer."""
    import json

    doc = {"weights": [[int(w) for w in row] for row in weights]}
    if seeds is not None:
        doc["seeds"] = [int(s) for s in seeds]
    if tunes is not None:
        doc["tunes"] = [float(t) for t in tunes]
    if policies is not None:
        doc["policies"] = [[str(n), int(w)] for n, w in policies]
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, sort_keys=True, separators=(",", ":"))
        f.write("\n")
    os.replace(tmp, path)
    return path


def _interactive_select(apps):
    """Multi-select confirmation (apply.go:172-189, survey lib)."""
    print("Confirm your apps (comma-separated indices, empty = all):")
    for i, (name, pods) in enumerate(apps):
        print(f"  [{i}] {name} ({len(pods)} pods)")
    line = input("> ").strip()
    if not line:
        return apps
    picked = {int(x) for x in line.split(",") if x.strip().isdigit()}
    return [a for i, a in enumerate(apps) if i in picked]
