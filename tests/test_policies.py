"""Policy-kernel tests. Golden values ported from the reference's
plugin/gpu_packing_score_test.go; other policies pinned by hand-computed
cases following the formulas in SURVEY.md §2.5."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusim.constants import GPU_MODEL_IDS, MILLI
from tpusim.policies import ScoreContext, jit_policy, make_policy
from tpusim.policies.dotprod import make_dotprod
from tpusim.types import NodeState, make_node_state, make_pod, make_typical_pods


def mk_state(gpu_lefts, cpu_left=1000, cpu_cap=96000, mem=262144, gpu_type="1080"):
    """One-node state with explicit per-device gpu_left."""
    n_dev = len(gpu_lefts)
    st = make_node_state(
        cpu_cap=[cpu_cap],
        mem_cap=[mem],
        gpu_cnt=[n_dev],
        gpu_type=[GPU_MODEL_IDS[gpu_type]],
    )
    gl = np.zeros((1, 8), np.int32)
    gl[0, :n_dev] = gpu_lefts
    return st._replace(
        cpu_left=jnp.asarray([cpu_left], jnp.int32), gpu_left=jnp.asarray(gl)
    )


def ctx_for(state, tp=None):
    return ScoreContext(
        tp=tp,
        feasible=jnp.ones(state.num_nodes, bool),
        rng=jax.random.PRNGKey(0),
    )


class TestPackingGolden:
    """Ported from gpu_packing_score_test.go."""

    def score(self, gpu_lefts, milli, num):
        st = mk_state(gpu_lefts)
        pod = make_pod(cpu=100, gpu_milli=milli, gpu_num=num)
        fn = make_policy("GpuPackingScore")
        return int(jit_policy(fn)(st, pod, ctx_for(st)).raw_scores[0])

    def test_case2_dip_into_free(self):
        assert self.score([200, 1000, 1000, 500], 1000, 2) == 48

    def test_case3_free_node_4gpu(self):
        assert self.score([1000, 1000, 1000, 1000], 1000, 2) == 29

    def test_case3_free_node_8gpu(self):
        assert self.score([1000] * 8, 1000, 2) == 25

    def test_case1_shared_only(self):
        assert self.score([200, 1000, 1000, 500], 200, 2) == 93


class TestBestFit:
    def test_formula(self):
        st = mk_state([500, 1000], cpu_left=31000)
        pod = make_pod(cpu=5000, gpu_milli=500, gpu_num=1)
        fn = make_policy("BestFitScore")
        # s = (31000-5000)/128000*0.5 + (1500-500)/8000*0.5 = 0.1015625+0.0625
        # score = floor((1-0.1640625)*100) = 83
        assert int(jit_policy(fn)(st, pod, ctx_for(st)).raw_scores[0]) == 83


class TestClustering:
    def test_quartiles(self):
        st = mk_state([500, 1000], cpu_left=31000)  # total_left=1500
        pack = 25 * (8000 - 1500) // 8000  # 20
        pod = make_pod(cpu=100, gpu_milli=500, gpu_num=1)  # share class 0
        fn = make_policy("GpuClusteringScore")

        # idle node, no affinities → (25, 50]
        assert int(jit_policy(fn)(st, pod, ctx_for(st)).raw_scores[0]) == 25 + pack
        # only same affinity → (75, 100]
        st2 = st._replace(aff_cnt=st.aff_cnt.at[0, 0].set(2))
        assert int(jit_policy(fn)(st2, pod, ctx_for(st2)).raw_scores[0]) == 75 + pack
        # multiple affinities incl pod's → (50, 75]
        st3 = st2._replace(aff_cnt=st2.aff_cnt.at[0, 1].set(1))
        assert int(jit_policy(fn)(st3, pod, ctx_for(st3)).raw_scores[0]) == 50 + pack
        # different affinity only → (0, 25]
        st4 = st._replace(aff_cnt=st.aff_cnt.at[0, 1].set(1))
        assert int(jit_policy(fn)(st4, pod, ctx_for(st4)).raw_scores[0]) == 0 + pack
        # no-gpu pod → 0
        cpu_pod = make_pod(cpu=100)
        assert int(jit_policy(fn)(st2, cpu_pod, ctx_for(st2)).raw_scores[0]) == 0


class TestFGD:
    def tp(self):
        return make_typical_pods(
            [(1000, 500, 1, 0, 0.5), (2000, 1000, 1, 0, 0.5)]
        )

    def test_prefers_frag_reducing_device(self):
        """Placing a 500m pod on the 500m-left device keeps the 1000m device
        usable by the 1-GPU typical pod — strictly better than breaking it."""
        st = mk_state([500, 1000], cpu_left=31000)
        pod = make_pod(cpu=1000, gpu_milli=500, gpu_num=1)
        fn = make_policy("FGDScore")
        res = jit_policy(fn)(st, pod, ctx_for(st, self.tp()))
        assert int(res.share_dev[0]) == 0

    def test_matches_manual_formula(self):
        from tpusim.ops.frag import node_frag_score

        st = mk_state([500, 1000], cpu_left=31000)
        tp = self.tp()
        pod = make_pod(cpu=1000, gpu_milli=500, gpu_num=1)
        fn = make_policy("FGDScore")
        got = int(jit_policy(fn)(st, pod, ctx_for(st, tp)).raw_scores[0])

        cur = float(
            node_frag_score(
                st.cpu_left[0], st.gpu_left[0], st.gpu_type[0], tp
            )
        )
        best = 0
        for d in range(2):
            gl = np.array(st.gpu_left[0])
            if gl[d] < 500:
                continue
            gl[d] -= 500
            new = float(
                node_frag_score(
                    st.cpu_left[0] - 1000, jnp.asarray(gl), st.gpu_type[0], tp
                )
            )
            s = int(np.floor(100.0 / (1.0 + np.exp(-(cur - new) / 1000.0))))
            best = max(best, s)
        assert got == best


class TestDotProduct:
    def test_merge_max_handcomputed(self):
        st = mk_state([500, 1000], cpu_left=31000)
        pod = make_pod(cpu=5000, gpu_milli=500, gpu_num=1)
        fn = make_dotprod("merge", "max")
        # nodeVec/max = [31000/128000, 1500/8000], podVec/max = [5000/128000, 500/8000]
        dot = ((31000 / 128000) * (5000 / 128000) + (1500 / 8000) * (500 / 8000)) / 2
        want = int(100 * (1 - dot))
        assert int(jit_policy(fn)(st, pod, ctx_for(st)).raw_scores[0]) == want

    def test_share_prefers_tight_device(self):
        st = mk_state([500, 1000], cpu_left=31000)
        pod = make_pod(cpu=5000, gpu_milli=400, gpu_num=1)
        fn = make_dotprod("share", "max")
        res = jit_policy(fn)(st, pod, ctx_for(st))
        # device slot 0 (500m shared) has smaller gpu dim → smaller dot →
        # higher score than the idle pool (1000m)
        assert int(res.share_dev[0]) == 0

    def test_infeasible_cpu_scores_zero(self):
        st = mk_state([500, 1000], cpu_left=100)
        pod = make_pod(cpu=5000, gpu_milli=400, gpu_num=1)
        for dim in ("merge", "share", "divide", "extend"):
            fn = make_dotprod(dim, "max")
            assert int(jit_policy(fn)(st, pod, ctx_for(st)).raw_scores[0]) == 0


class TestPWR:
    def test_share_picks_used_device(self):
        """V100M32: placing on an already-busy device adds no GPU power;
        waking an idle device costs full-minus-idle watts."""
        st = mk_state([500, 1000], gpu_type="V100M32")
        pod = make_pod(cpu=0, gpu_milli=400, gpu_num=1)
        fn = make_policy("PWRScore")
        res = jit_policy(fn)(st, pod, ctx_for(st))
        assert int(res.share_dev[0]) == 0
        assert int(res.raw_scores[0]) == 0  # no power delta on the busy device


class TestSimonAndRandom:
    def test_simon_share(self):
        # Simon scores against static ALLOCATABLE capacity, not free
        # resources (simon.go:59-64 reads node.Status.Allocatable, which the
        # fake cluster never decrements)
        st = mk_state([1000, 1000], cpu_left=10000, cpu_cap=10000, mem=100000)
        pod = make_pod(cpu=5000, mem=0, gpu_milli=0, gpu_num=0)
        fn = make_policy("Simon")
        # cpu share = 5000/(10000-5000) = 1.0 → score 100
        assert int(jit_policy(fn)(st, pod, ctx_for(st)).raw_scores[0]) == 100
        st2 = mk_state([1000, 1000], cpu_left=10000, cpu_cap=96000, mem=100000)
        # cpu share = 5000/91000, mem 0, gpu 0 → round(100 x 0.0549) = 5
        assert int(jit_policy(fn)(st2, pod, ctx_for(st2)).raw_scores[0]) == 5

    def test_random_single_winner(self):
        st = make_node_state(
            cpu_cap=[1000] * 4, mem_cap=[1000] * 4, gpu_cnt=[0] * 4,
            gpu_type=[-1] * 4,
        )
        fn = make_policy("RandomScore")
        scores = np.asarray(jit_policy(fn)(st, make_pod(cpu=1), ctx_for(st)).raw_scores)
        assert (scores == 100).sum() == 1 and (scores == 0).sum() == 3


def test_pwr_matches_direct_form():
    """The incremental PWR delta must equal re-running the full power model
    on every hypothetical, across random states incl. zero-milli share pods."""
    import jax

    from tpusim.constants import MAX_GPUS_PER_NODE
    from tpusim.ops.energy import node_power
    from tpusim.ops.resource import sub_pod
    from tpusim.policies.pwr import _pwr_node
    from tpusim.types import PodSpec

    def direct(row, pod):
        def power(cpu_left, gpu_left):
            c, g = node_power(
                cpu_left, row.cpu_cap, gpu_left, row.gpu_cnt, row.gpu_type,
                row.cpu_type,
            )
            return c + g

        old = power(row.cpu_left, row.gpu_left)

        def per_dev(d):
            return power(row.cpu_left - pod.cpu, row.gpu_left.at[d].add(-pod.gpu_milli))

        new_per_dev = jax.vmap(per_dev)(jnp.arange(MAX_GPUS_PER_NODE))
        fits = row.gpu_left >= pod.gpu_milli
        neg = jnp.int32(-(2**31) + 1)
        dev_scores = jnp.where(fits, (old - new_per_dev).astype(jnp.int32), neg)
        best = jnp.argmax(dev_scores)
        share = (jnp.where(fits.any(), dev_scores[best], neg),
                 jnp.where(fits.any(), best, -1))
        c2, _, g2, _, _ = sub_pod(row.cpu_left, row.mem_left, row.gpu_left, pod)
        whole = (old - power(c2, g2)).astype(jnp.int32)
        is_share = pod.is_gpu_share()
        return (jnp.where(is_share, share[0], whole),
                jnp.where(is_share, share[1], -1))

    rng = np.random.default_rng(77)
    from tpusim.types import make_node_state

    for trial in range(60):
        gcnt = int(rng.choice([0, 2, 4, 8]))
        st = make_node_state(
            cpu_cap=[int(rng.choice([32000, 96000]))],
            mem_cap=[262144],
            gpu_cnt=[gcnt],
            gpu_type=[int(rng.integers(0, 4)) if gcnt else -1],
            cpu_type=[int(rng.integers(0, 3))],
        )
        gl = np.zeros((1, 8), np.int32)
        gl[0, :gcnt] = rng.choice([0, 250, 500, 999, 1000], gcnt)
        st = st._replace(
            gpu_left=jnp.asarray(gl),
            cpu_left=jnp.asarray([int(rng.integers(0, 32000))], jnp.int32),
        )
        row = jax.tree.map(lambda a: a[0], st)
        pod = PodSpec(
            cpu=jnp.int32(int(rng.integers(0, 8000))),
            mem=jnp.int32(1024),
            gpu_milli=jnp.int32(int(rng.choice([0, 250, 500, 1000]))),
            gpu_num=jnp.int32(int(rng.choice([0, 1, 2]))),
            gpu_mask=jnp.int32(0),
            pinned=jnp.int32(-1),
        )
        a = jax.jit(_pwr_node)(row, pod)
        b = jax.jit(direct)(row, pod)
        assert int(a[0]) == int(b[0]) and int(a[1]) == int(b[1]), (
            trial, gl, pod, int(a[0]), int(b[0]), int(a[1]), int(b[1])
        )


# ------------------------------------------------------------------ first_max
# ops/resource.first_max (ISSUE 35): the per-type device pick as reductions.
# The kernels had `best = argmax(x); x[best]`, which the chip ran as a
# serialized gather of one of eight for every (lane, type).

from tpusim.policies.pwr import _NEG_INF as _PWR_NEG_INF  # noqa: E402


def _element_at_argmax(x):
    best = jnp.argmax(x).astype(jnp.int32)
    return x[best], best


def _device_score_rows(kind):
    """int32[n, 8] rows of device scores as the two kernels make them: a
    score (FGD: 0..100, PWR: a watt delta <= 0) where the device fits, -1 or
    `_NEG_INF` where it does not."""
    rng = np.random.default_rng(35)
    if kind == "random":
        return rng.integers(-400, 101, (64, 8)).astype(np.int32)
    if kind == "ties":  # few distinct values: every row has a tied maximum
        return rng.choice(np.asarray([-1, 0, 37, 100], np.int32), (64, 8))
    if kind == "one fitting device":
        x = np.full((16, 8), -1, np.int32)
        x[np.arange(16), np.arange(16) % 8] = rng.integers(0, 101, 16)
        return x
    if kind == "none fitting (-1)":
        return np.full((4, 8), -1, np.int32)
    if kind == "none fitting (_NEG_INF)":
        return np.full((4, 8), _PWR_NEG_INF, np.int32)
    if kind == "watt deltas beside _NEG_INF":
        x = rng.choice(np.asarray([-195, -75, 0], np.int32), (64, 8))
        return np.where(rng.random((64, 8)) < 0.5, x, _PWR_NEG_INF).astype(
            np.int32)
    raise ValueError(kind)


@pytest.mark.parametrize("kind", [
    "random", "ties", "one fitting device", "none fitting (-1)",
    "none fitting (_NEG_INF)", "watt deltas beside _NEG_INF"])
def test_first_max_is_the_element_at_its_own_argmax(kind):
    from tpusim.ops.resource import first_max

    x = _device_score_rows(kind)
    value, index = jax.jit(jax.vmap(first_max))(jnp.asarray(x))
    want_value, want_index = jax.vmap(_element_at_argmax)(jnp.asarray(x))
    assert value.dtype == want_value.dtype and index.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(value), np.asarray(want_value))
    np.testing.assert_array_equal(np.asarray(index), np.asarray(want_index))
    # and by numpy: the maximum, at its FIRST index
    np.testing.assert_array_equal(np.asarray(value), x.max(1))
    np.testing.assert_array_equal(np.asarray(index), x.argmax(1))


@pytest.mark.parametrize("dtype", ["int32", "float32"])
def test_first_max_over_lane_and_type(dtype):
    """Under vmap(vmap(...)) over (lane, type), as the table sweep's column
    computation calls it; float32 as DotProd has it: finite slots and -inf,
    no NaN."""
    from tpusim.ops.resource import first_max

    rng = np.random.default_rng(8)
    if dtype == "int32":
        x = rng.choice(np.asarray([-1, 0, 50, 99], np.int32), (6, 15, 8))
    else:
        x = rng.choice(np.asarray([-np.inf, 0.0, 0.25, 0.75], np.float32),
                       (6, 15, 9))
    value, index = jax.jit(jax.vmap(jax.vmap(first_max)))(jnp.asarray(x))
    want = jax.vmap(jax.vmap(_element_at_argmax))(jnp.asarray(x))
    assert value.shape == index.shape == (6, 15) and value.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(value), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(index), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(index), x.argmax(2))


def _share_kernel_rows():
    """48 random node rows (many tied and full devices, CPU-only nodes
    among them) and three share pods."""
    from tpusim.types import PodSpec

    rng = np.random.default_rng(3535)
    n = 48
    gcnt = rng.choice([0, 2, 4, 8], n)
    st = make_node_state(
        cpu_cap=rng.choice([32000, 96000], n).tolist(),
        mem_cap=[262144] * n,
        gpu_cnt=gcnt.tolist(),
        gpu_type=[int(rng.integers(0, 4)) if g else -1 for g in gcnt],
        cpu_type=rng.integers(0, 3, n).tolist(),
    )
    gl = rng.choice([0, 250, 500, 999, 1000], (n, 8)).astype(np.int32)
    gl[np.arange(8)[None, :] >= gcnt[:, None]] = 0
    st = st._replace(
        gpu_left=jnp.asarray(gl),
        cpu_left=jnp.asarray(rng.integers(0, 32000, n).astype(np.int32)))
    pods = PodSpec(
        cpu=jnp.asarray([100, 4000, 8000], jnp.int32),
        mem=jnp.full(3, 1024, jnp.int32),
        gpu_milli=jnp.asarray([250, 500, 1000], jnp.int32),
        gpu_num=jnp.ones(3, jnp.int32),
        gpu_mask=jnp.zeros(3, jnp.int32),
        pinned=jnp.full(3, -1, jnp.int32))
    return st, pods


def _share_kernel(kernel):
    """(module that holds the kernel's `first_max`, run): run() scores every
    (pod, node) of _share_kernel_rows under a fresh jit (a new vmap object a
    call), so a patched `first_max` is traced anew, and returns (score[3, 48], dev[3, 48])."""
    from tpusim.policies import dotprod, fgd, pwr

    st, pods = _share_kernel_rows()
    tp = make_typical_pods([
        (6000, 465, 1, 0, 0.4), (16000, 1000, 1, 0, 0.3),
        (8000, 250, 1, 0, 0.2), (32000, 1000, 2, 0, 0.1)])
    module, node = {
        "fgd share": (fgd, lambda row, pod: fgd._fgd_share_node(
            row.cpu_left, row.gpu_left, row.gpu_type, pod, tp)),
        "pwr": (pwr, pwr._pwr_node),
        "dotprod share": (dotprod, lambda row, pod: dotprod._share_divide_node(
            row, pod, "max", False)),
        "dotprod extend": (dotprod, lambda row, pod: dotprod._extend_node(
            row, pod, "max")),
    }[kernel]

    def run():
        rows = NodeState(*([0] * len(st)))
        score, dev = jax.jit(jax.vmap(
            jax.vmap(node, in_axes=(rows, None)),
            in_axes=(None, 0)))(st, pods)
        return np.asarray(score), np.asarray(dev)

    return module, run


# what the parent of ISSUE 35 (`x[argmax(x)]` in each kernel) gives on
# _share_kernel_rows: (sum of the scores over fitting entries, sum of the
# devices, entries with no device, the first pod's first twelve devices)
PARENT_SHARE_VALUES = {
    "fgd share": (4296.0, 18, 59, [-1, 0, 1, 5, -1, -1, 1, 1, 2, -1, 0, 0]),
    "pwr": (-105.0, -66, 78, [-1, 0, 1, 0, -1, -1, 0, 0, 0, -1, 0, 0]),
    "dotprod share": (
        81.519, 48, 62, [-1, 0, 1, 5, -1, -1, 1, 1, 2, -1, 2, 1]),
    "dotprod extend": (
        81.775, 48, 62, [-1, 0, 1, 5, -1, -1, 1, 1, 2, -1, 2, 1]),
}


@pytest.mark.parametrize("kernel", [
    "fgd share", "pwr", "dotprod share", "dotprod extend"])
def test_the_share_kernels_keep_the_parent_s_values(kernel, monkeypatch):
    """score and device of every kernel that picks through first_max: equal
    to the same kernel with the parent's `x[argmax(x)]` in its place, entry
    by entry, and to what the parent's tree gave."""
    module, run = _share_kernel(kernel)
    score, dev = run()
    monkeypatch.setattr(module, "first_max", _element_at_argmax)
    parent_score, parent_dev = run()
    assert score.dtype == parent_score.dtype
    np.testing.assert_array_equal(score, parent_score)
    np.testing.assert_array_equal(dev, parent_dev)
    assert (dev >= 0).any() and (dev < 0).any()  # both outcomes occur
    placed = dev >= 0
    score_sum, *ints = PARENT_SHARE_VALUES[kernel]
    assert [int(dev.sum()), int((~placed).sum()), dev[0, :12].tolist()] == ints
    assert float(score[placed].astype(np.float64).sum()) == pytest.approx(
        score_sum, abs=1e-3)


# every kind of whole-branch request a type set can hold: (gpu_milli, gpu_num)
WHOLE_REQUESTS = {
    "cpu only": (0, 0),
    "one gpu": (1000, 1),
    "two gpus": (1000, 2),
    "four gpus": (1000, 4),
    "eight gpus": (1000, 8),
    "fractional multi-gpu": (500, 2),
    "more devices than fit": (1000, 6),
    "padded dummy": (0, 0),
}


@pytest.fixture(scope="module")
def whole_split_scores():
    """(kind of each type, what FGD's whole kernel gives a (type, node), what
    its two steps give) over _share_kernel_rows' 48 node rows and three CPU
    requests of every kind of WHOLE_REQUESTS; the dummy as pad_pod_types
    makes it."""
    from tpusim.policies.fgd import fgd_score
    from tpusim.sim.table_engine import _take_request, _whole_requests
    from tpusim.types import PodSpec

    st, _ = _share_kernel_rows()
    tp = make_typical_pods([
        (6000, 465, 1, 0, 0.4), (16000, 1000, 1, 0, 0.3),
        (8000, 250, 1, 0, 0.2), (32000, 1000, 2, 0, 0.1)])
    kinds = [k for k in WHOLE_REQUESTS for _ in range(3)]
    cpus = [2**30 if k == "padded dummy" else c
            for k in WHOLE_REQUESTS for c in (100, 9000, 40000)]
    pods = PodSpec(
        cpu=jnp.asarray(cpus, jnp.int32),
        mem=jnp.asarray([2**30 if k == "padded dummy" else 1024
                         for k in kinds], jnp.int32),
        gpu_milli=jnp.asarray([WHOLE_REQUESTS[k][0] for k in kinds], jnp.int32),
        gpu_num=jnp.asarray([WHOLE_REQUESTS[k][1] for k in kinds], jnp.int32),
        gpu_mask=jnp.zeros(len(kinds), jnp.int32),
        pinned=jnp.full(len(kinds), -1, jnp.int32))
    requests, request_of = _whole_requests(pods)
    # seven distinct requests among the eight kinds (the dummy is CPU-only),
    # on their bucket of eight
    assert requests.shape == (8, 2) and int(request_of.max()) == 6
    ctx = ctx_for(st, tp)
    whole = fgd_score.branches["whole"]
    request, finish = fgd_score.branches["whole_split"]

    @jax.jit
    def both(st, pods, requests, request_of):
        plain = jax.vmap(lambda pod: whole(st, pod, ctx))(pods)
        terms = jax.vmap(lambda q: request(st, q[0], q[1], ctx))(requests)
        split = jax.vmap(lambda pod, r: finish(
            st, pod, _take_request(terms, r), ctx))(pods, request_of)
        return plain, split

    plain, split = both(st, pods, requests, request_of)
    return kinds, st, pods, jax.tree.map(np.asarray, (plain, split))


@pytest.mark.parametrize("kind", list(WHOLE_REQUESTS))
def test_the_whole_split_keeps_the_whole_kernel_s_values(
        kind, whole_split_scores):
    """Sub's hypothetical once a REQUEST and the finish once a type: equal to
    _fgd_whole_node entry by entry, score and dtype, for every kind of
    whole-branch request: fitting or not (Sub's `ok` false: the kernel never
    read it), CPU-only, and the dummy type pad_pod_types appends."""
    from tpusim.ops.resource import sub_devices

    kinds, st, pods, (plain, split) = whole_split_scores
    rows = np.flatnonzero(np.asarray(kinds) == kind)
    assert len(rows) == 3
    assert split.raw_scores.dtype == plain.raw_scores.dtype == np.int32
    np.testing.assert_array_equal(split.raw_scores[rows], plain.raw_scores[rows])
    np.testing.assert_array_equal(split.share_dev[rows], plain.share_dev[rows])
    assert (split.share_dev[rows] == -1).all()
    # the scores are not one number: the rows tell nodes and CPU requests apart
    assert len(np.unique(plain.raw_scores[rows])) > 1
    milli, num = WHOLE_REQUESTS[kind]
    _, _, ok = jax.vmap(sub_devices, in_axes=(0, None, None))(
        st.gpu_left, milli, num)
    if kind == "more devices than fit":
        assert not np.asarray(ok).all()  # Sub fails on some node
    if num == 0:
        assert np.asarray(ok).all()
