"""The readback in pieces (ISSUE 47): tpusim/sim/fetch.py cuts its packed
buffer into pieces, starts every copy before it takes the first, and lands
them in a host block it keeps from call to call. Held here, on the CPU, the
piece size patched small:

  1. in pieces, device_fetch returns what the one-transfer form returns,
     leaf for leaf (value, dtype, shape, writeable), below one piece, at a
     piece boundary and with a ragged last piece, and notes the bytes, the
     pieces and that a buffer of at most one piece touches no landing block;
  2. the rule for landing blocks: arrays an earlier fetch returned never
     change under a later one; a block comes back only when every array cut
     from it is dead; the module keeps at most two free blocks;
  3. the same of a sweep's lanes, through schedule_pods_sweep.
"""

import dataclasses
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_sweep_trace import _sim
from tpusim.obs import Recorder, sweep_log
from tpusim.sim import fetch
from tpusim.sim.driver import schedule_pods_sweep


@pytest.fixture
def piece_bytes(monkeypatch):
    """Set the piece size; the landing blocks are the test's own. Returns
    (set, landing)."""
    landing = fetch._LandingBlocks()
    monkeypatch.setattr(fetch, "_landing", landing)

    def set_piece(size):
        monkeypatch.setattr(fetch, "PIECE_BYTES", size)
        fetch._packer.cache_clear()  # the cut is traced into the packer

    yield set_piece, landing
    fetch._packer.cache_clear()


def _tree(seed=3):
    """Mixed dtypes, bool leaves, None, a Python scalar, a numpy leaf, an
    empty leaf and a scalar one: 1,014 packed bytes."""
    rng = np.random.default_rng(seed)
    f32 = rng.standard_normal((5, 7)).astype(np.float32)
    f32[0, :3] = [np.nan, -0.0, np.inf]
    return {
        "i": jnp.asarray(rng.integers(-2**31, 2**31, (4, 3), np.int64)
                         .astype(np.int32)),
        "b": jnp.asarray(rng.random((6, 5, 8)) < 0.5),
        "f": jnp.asarray(f32),
        "none": None, "host": np.arange(3), "scalar": 7,
        "u8": jnp.asarray(rng.integers(0, 256, (9,), np.uint8)),
        "h": jnp.asarray(rng.standard_normal((11, 3)).astype(np.float16)),
        "odd": jnp.asarray(rng.random((7,)) < 0.5),
        "nested": (jnp.zeros((0,), np.int32), [jnp.int32(-5)]),
        "long": jnp.asarray(rng.integers(-9, 9, (125,), np.int32)),
    }


TREE_BYTES = 4 * 12 + 240 + 4 * 35 + 9 + 2 * 33 + 7 + 0 + 4 + 4 * 125


def _noting(tree):
    """device_fetch(tree) under a span: (the host tree, the span's meta)."""
    rec = Recorder()
    with rec.span("fetch") as h:
        out = fetch.device_fetch(tree, marks=h)
    assert list(rec.spans[0].marks) == ["ready", "copied"]
    return out, rec.spans[0].meta


@pytest.mark.parametrize("piece, pieces", [
    (TREE_BYTES + 1, 1),  # below one piece
    (TREE_BYTES, 1),  # exactly one piece: still one transfer
    (TREE_BYTES - 1, 2),  # a last piece of one byte
    (TREE_BYTES // 6, 6),  # 1,014 = 6 x 169: the last cut is the end
    (256, 4),  # a ragged last piece; `b` (240 bytes) spans two
    (16, 64),  # every leaf but the small ones spans several
], ids=["below", "one-piece", "one-byte-tail", "boundary", "ragged", "many"])
def test_in_pieces_the_fetch_returns_what_one_transfer_returns(
        piece_bytes, piece, pieces):
    set_piece, landing = piece_bytes
    tree = _tree()
    assert TREE_BYTES == 1014
    want = fetch.device_fetch(tree)  # 8 MB a piece: one transfer
    assert landing.free == []
    set_piece(piece)
    if pieces == 1:
        landing.take = None  # touching a landing block would raise
    got, meta = _noting(tree)
    assert meta == {"bytes": TREE_BYTES, "fetch_pieces": pieces,
                    "landing_reused": 0}
    a, ta = jax.tree_util.tree_flatten(got)
    b, tb = jax.tree_util.tree_flatten(want)
    src, ts = jax.tree_util.tree_flatten(tree)
    assert ta == tb == ts
    for x, y, s in zip(a, b, src):
        if not isinstance(s, jax.Array):
            assert x is y is s
            continue
        assert type(x) is type(y) is np.ndarray
        assert x.dtype == y.dtype == s.dtype and x.shape == y.shape == s.shape
        assert x.tobytes() == y.tobytes() == np.asarray(s).tobytes()
        # a bool leaf is a cast (a copy of its own); the rest are read-only
        # views of the fetched bytes
        assert x.flags.writeable == y.flags.writeable == (s.dtype == bool)
    del a, got, x
    # every view is dead: the block came free, and only one was ever made
    assert len(landing.free) == (0 if pieces == 1 else 1)


def _lanes(value, n=300):
    """A tree of one signature whose every byte says which fetch made it."""
    return {"a": jnp.full((n,), value, np.int32),
            "flag": jnp.full((5,), value % 2 == 1),
            "b": jnp.full((n, 2), value, np.int8)}


def _says(out, value):
    return (np.all(out["a"] == value) and np.all(out["b"] == value)
            and np.all(out["flag"] == (value % 2 == 1)))


def test_an_earlier_fetchs_arrays_never_change_under_later_ones(piece_bytes):
    set_piece, landing = piece_bytes
    set_piece(256)
    first, meta = _noting(_lanes(1))
    assert meta["fetch_pieces"] == 8 and meta["landing_reused"] == 0
    snapshot = {k: v.copy() for k, v in first.items()}
    later = []
    for value in (2, 3, 4):  # other contents, while `first` is held
        out, meta = _noting(_lanes(value))
        assert meta["landing_reused"] == 0 and _says(out, value)
        later.append(out)
        assert all(np.array_equal(first[k], snapshot[k]) for k in first)
    assert landing.free == [] and _says(first, 1)
    assert all(_says(out, v) for out, v in zip(later, (2, 3, 4)))
    # dropped: the next fetch lands where an earlier one had
    del first, out
    later.clear()
    assert len(landing.free) == fetch.FREE_BLOCKS == 2  # of four: two let go
    out, meta = _noting(_lanes(5))
    assert meta["landing_reused"] == 1 and _says(out, 5)
    assert len(landing.free) == 1


def test_a_block_is_not_reused_while_one_view_of_it_is_held(piece_bytes):
    set_piece, landing = piece_bytes
    set_piece(256)
    out, _ = _noting(_lanes(6))
    kept = out["b"][17:19]  # a view of a view of one leaf
    assert kept.base is out["a"].base and type(kept.base) is np.ndarray
    del out
    assert landing.free == []
    out, meta = _noting(_lanes(7))
    assert meta["landing_reused"] == 0 and np.all(kept == 6)
    del kept
    assert len(landing.free) == 1
    again, meta = _noting(_lanes(8))
    assert meta["landing_reused"] == 1 and _says(out, 7) and _says(again, 8)
    # a bool leaf's cast holds no block: a fetch of bools alone frees its own
    del out, again
    flags, meta = _noting({"flag": jnp.ones((600,), bool)})
    assert meta["landing_reused"] == 1 and len(landing.free) == 2


def test_free_blocks_are_the_two_largest_and_a_fetch_takes_the_smallest_fit(
        piece_bytes):
    set_piece, landing = piece_bytes
    set_piece(256)
    held = [_noting(_lanes(v, n))[0] for v, n in ((1, 100), (2, 300), (3, 200))]
    sizes = [6 * n + 5 for n in (100, 300, 200)]
    del held
    assert sorted(len(b) for b in landing.free) == sorted(sizes)[1:]
    out, meta = _noting(_lanes(4, 150))  # fits both: takes the 200's block
    assert meta["landing_reused"] == 1
    assert [len(b) for b in landing.free] == [sizes[1]]
    big, meta = _noting(_lanes(5, 400))  # fits neither: a block of its own
    assert meta["landing_reused"] == 0 and _says(out, 4) and _says(big, 5)


def test_fetches_from_several_threads_never_share_a_live_block(piece_bytes):
    set_piece, landing = piece_bytes
    set_piece(256)
    fetch.device_fetch(_lanes(0))  # the packer traced before the threads
    wrong, done = [], []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def worker(k):
        held = None
        for i in range(40):
            value = 10 * k + i % 10
            out = fetch.device_fetch(_lanes(value))
            if not _says(out, value) or (
                    held is not None and not _says(*held)):
                wrong.append((k, i))
            held = (out, value) if i % 3 else None
        done.append(k)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(8)) and wrong == []
    assert len(landing.free) <= fetch.FREE_BLOCKS


def _arrays(lane):
    """Every array a SweepLane holds, by name."""
    out = {}
    for f in dataclasses.fields(lane):
        value = getattr(lane, f.name)
        for i, leaf in enumerate(jax.tree_util.tree_leaves(value)):
            if isinstance(leaf, np.ndarray):
                out[f"{f.name}.{i}"] = leaf
    return out


def test_a_sweeps_lanes_do_not_change_under_the_next_sweep(piece_bytes):
    set_piece, landing = piece_bytes
    set_piece(512)
    sim, trace = _sim(block_size=-1)
    weights = [[1000], [1000], [700]]
    first = schedule_pods_sweep(sim, trace, weights, [11, 12, 13])
    rec = sweep_log()[-1]
    assert rec.fetch_pieces == -(-rec.fetch_bytes // 512) > 1
    assert rec.landing_reused == 0
    fetched = [_arrays(lane) for lane in first]
    assert all({"placed_node.0", "event_node.0", "state.0"} <= set(a)
               for a in fetched)
    snapshot = [{k: v.copy() for k, v in a.items()} for a in fetched]
    # other seeds, other placements, the first call's lanes held
    second = schedule_pods_sweep(sim, trace, weights, [21, 22, 23])
    assert sweep_log()[-1].landing_reused == 0 and landing.free == []
    assert any(not np.array_equal(a.placed_node, b.placed_node)
               for a, b in zip(first, second))
    for lane, was in zip(fetched, snapshot):
        for name, leaf in lane.items():
            assert leaf.tobytes() == was[name].tobytes(), name
    # the first call's lanes dropped: the third sweep lands in their block
    del first, fetched, lane, leaf
    assert len(landing.free) == 1
    third = schedule_pods_sweep(sim, trace, weights, [11, 12, 13])
    rec = sweep_log()[-1]
    assert rec.landing_reused == 1 and landing.free == []
    assert rec.to_dict()["landing_reused"] == 1
    assert rec.to_dict()["fetch_pieces"] == rec.fetch_pieces
    for lane, was in zip(third, snapshot):
        for name, leaf in _arrays(lane).items():
            assert leaf.tobytes() == was[name].tobytes(), name
