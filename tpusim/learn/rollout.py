"""Rollout backends of the tuning loop (ISSUE 9): one interface, two
executions.

LocalRollout drives `Simulator.run_sweep` — the whole generation's
population is ONE vmapped compiled scan (ISSUE 6), and because the
weight vectors are traced operands, generation after generation reuses
the same executable: zero recompiles after generation 1. The lane count
is pinned to `width` (short/dedup'd populations repeat their tail row —
the svc worker's padding trick), so the vmap axis never changes size.

RemoteRollout turns a `tpusim serve --jobs` service into the rollout
farm ROADMAP names: each candidate row becomes a job document, submitted
through the backpressure-honoring client (svc.client) and read back from
the digest-signed results. The service's content-digest dedup makes
re-evaluated candidates (CMA revisiting a region, resumed runs) free.

Both backends return the SAME term dicts (learn.objective lane_terms /
terms_from_result), so a tuning log records identical bytes whichever
executed the rollouts — the acceptance contract.

Candidates live in the engines' i32 operand space: `project_weights`
rounds/clips the optimizer's float vectors, `dedup_rows` collapses
integer collisions so a generation never replays the same vector twice.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from tpusim.learn.objective import lane_terms, terms_from_result


def project_weights(xs, lo: int = 0, hi: int = 4000) -> np.ndarray:
    """Float candidates [B, d] -> the engines' i32 operand space:
    round-half-even, clip to [lo, hi]. Weight 0 disables a policy's
    contribution (the extender-config vocabulary allows it for plain
    score plugins), negative weights never reach the engines."""
    if hi <= lo:
        raise ValueError(f"need lo < hi, got [{lo}, {hi}]")
    return np.clip(np.rint(np.asarray(xs, np.float64)), lo, hi).astype(
        np.int32
    )


def dedup_rows(rows: np.ndarray) -> Tuple[List[tuple], List[int]]:
    """Integer candidate rows -> (unique rows in first-seen order,
    per-candidate index into them). Projection collapses nearby float
    candidates onto the same integer vector; rolling the collision out
    twice would waste a lane (or a remote job) to learn nothing."""
    uniq: List[tuple] = []
    index: dict = {}
    where: List[int] = []
    for row in np.asarray(rows, np.int32):
        key = tuple(int(w) for w in row)
        if key not in index:
            index[key] = len(uniq)
            uniq.append(key)
        where.append(index[key])
    return uniq, where


def make_family_sim(nodes, pods, policies, gpu_sel: str = "best",
                    norm: str = "max", dim_ext: str = "share",
                    engine: str = "auto", table_cache_dir: str = ""):
    """A Simulator configured EXACTLY like the service worker's per-family
    sims (svc.worker._sim_for): same knobs, deterministic prep, reporting
    off. Local tuning over a trace and remote tuning against a service
    hosting that trace then replay identical trajectories — the
    local-vs-remote log-identity contract reduces to the sweep-vs-sweep
    bit-identity tests/test_svc.py already pins."""
    from tpusim.sim.driver import Simulator, SimulatorConfig

    cfg = SimulatorConfig(
        policies=tuple((str(n), int(w)) for n, w in policies),
        gpu_sel_method=gpu_sel,
        norm_method=norm,
        dim_ext_method=dim_ext,
        engine=engine,
        report_per_event=False,
        shuffle_pod=False,
        seed=42,
        table_cache_dir=table_cache_dir,
    )
    sim = Simulator(nodes, cfg)
    sim.set_workload_pods(list(pods))
    return sim


class LocalRollout:
    """Vectorized local backend: rollout(rows, seed) -> term dicts via
    one `run_sweep` dispatch of exactly `width` lanes."""

    name = "local"

    def __init__(self, sim, width: int, bucket: int = 512, fault=None):
        """`fault` (ISSUE 10): a FaultConfig makes every generation's
        rollout a CHAOS sweep — the whole population replays under the
        same seeded fault schedule (common random disruption, like the
        shared eval seed), so the objective's w_disrupt term trains on
        in-scan DisruptionMetrics instead of a post-hoc robustness
        report. Still one compiled scan per generation: the schedule is
        a lane operand."""
        self.fault = fault
        self._init_common(sim, width, bucket)

    def _init_common(self, sim, width: int, bucket: int):
        if width < 1:
            raise ValueError(f"width must be >= 1, got {width}")
        if sim.cfg.heartbeat_every:
            # the sweep strips in-scan heartbeats by REBUILDING a
            # heartbeat-free engine per run_sweep call (driver), which
            # would both recompile every generation and make
            # executables() track the wrong wrapper — reject up front
            raise ValueError(
                "LocalRollout needs a heartbeat-free Simulator "
                "(heartbeat_every=0): the vmapped sweep rebuilds a "
                "fresh engine per call under heartbeat_every, paying a "
                "recompile every generation"
            )
        self.sim = sim
        self.width = int(width)
        self.bucket = int(bucket)
        self._fns: set = set()  # jitted sweep wrappers dispatched

    def rollout(self, rows: Sequence[tuple], seed: int) -> List[dict]:
        if not rows:
            return []
        if len(rows) > self.width:
            raise ValueError(
                f"{len(rows)} unique candidates exceed the backend width "
                f"{self.width}"
            )
        # pad to the fixed lane count by repeating the tail row: the vmap
        # axis size is jaxpr structure, so a dedup-shrunk generation must
        # not compile its own executable (the svc worker's discipline)
        padded = list(rows) + [rows[-1]] * (self.width - len(rows))
        w = np.asarray(padded, np.int32)
        faults = [self.fault] * self.width if self.fault else None
        lanes = self.sim.run_sweep(
            w, seeds=[int(seed)] * self.width, bucket=self.bucket,
            faults=faults,
        )[: len(rows)]
        # track the dispatched wrapper so executables() can assert the
        # zero-recompile contract (the svc worker's /queue metric)
        self._fns.add(self.sim._last_sweep_fn)
        return [lane_terms(lane) for lane in lanes]

    def executables(self) -> int:
        """Compiled sweep executables dispatched by this backend — must
        sit at 1 for a whole tuning run (`make tune-smoke` hard-checks
        it via jit._cache_size())."""
        return sum(fn._cache_size() for fn in self._fns)


class RemoteRollout:
    """Service-backed backend: rollout(rows, seed) -> term dicts via the
    `tpusim submit` machinery against a `serve --jobs` endpoint."""

    name = "remote"

    def __init__(self, url: str, policies, trace: str = "default",
                 gpu_sel: str = "best", norm: str = "max",
                 dim_ext: str = "share", engine: str = "auto",
                 timeout: float = 600.0, out=None):
        self.url = url.rstrip("/")
        self.policies = [[str(n), int(w)] for n, w in policies]
        self.trace = trace
        self.gpu_sel = gpu_sel
        self.norm = norm
        self.dim_ext = dim_ext
        self.engine = engine
        self.timeout = float(timeout)
        self.out = out

    def rollout(self, rows: Sequence[tuple], seed: int) -> List[dict]:
        from tpusim.svc.client import submit_and_wait

        if not rows:
            return []
        docs = [
            {
                "trace": self.trace,
                "policies": self.policies,
                "weights": [int(w) for w in row],
                "seed": int(seed),
                "gpu_sel": self.gpu_sel,
                "norm": self.norm,
                "dim_ext": self.dim_ext,
                "engine": self.engine,
            }
            for row in rows
        ]
        results = submit_and_wait(
            self.url, docs, timeout=self.timeout, out=self.out
        )
        return [terms_from_result(r) for r in results]
