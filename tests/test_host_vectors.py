"""The host vectors the instant checkpoint lands the optimizer state in
(`runtime/recovery.py` `HostVectorPool`): a reused vector holds bitwise what
a fresh one would, and no vector is handed out again while a held snapshot,
a stale in-flight stream or a pending recovery stream still reads it."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduce_for_smoke
from repro.launch import spans
from repro.optim import AdamWConfig
from repro.runtime.cluster import (ClusterConfig, FabricConfig, FaultScript,
                                   SimCluster)
from repro.runtime.recovery import HostVectorPool, _flatten_opt

ODD_SHAPES = [(3,), (5, 7), (1,), (11, 13), (2, 3, 5), (17,), (9, 1, 3)]


def _tree(n_leaves: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return {f"l{i}": jnp.asarray(rng.standard_normal(s), jnp.float32)
            for i, s in enumerate(ODD_SHAPES[:n_leaves])}


def _mk(tmp_path, **fabric):
    cfg = dataclasses.replace(reduce_for_smoke(get_arch("qwen3-0.6b")),
                              dtype="float32")
    return SimCluster(cfg, cluster=ClusterConfig(
        dp=2, global_batch=4, seq_len=16, ckpt_dir=tmp_path / "ck",
        full_every=50, hp=AdamWConfig(lr=1e-3, warmup_steps=2,
                                      total_steps=50)),
        fabric=FabricConfig(**fabric))


def _host(tree):
    return [np.array(x) for x in jax.tree.leaves(tree)]


def _d2h_counts(since):
    return [s.counts for s in spans.spans(since) if s.name == "opt.d2h"]


@pytest.mark.parametrize("n_leaves", [1, 3, 5, 7])
def test_a_reused_vector_holds_what_a_fresh_one_does(n_leaves):
    tree = _tree(n_leaves)
    fresh, meta = _flatten_opt(tree, HostVectorPool())
    pool = HostVectorPool()
    stale, reused = pool.take(fresh.size)
    assert not reused
    stale[:] = np.nan                     # whatever the last step left there
    del stale
    vec, meta2 = _flatten_opt(tree, pool)
    assert vec.dtype == np.float32 and vec.size == fresh.size
    np.testing.assert_array_equal(vec.view(np.uint32), fresh.view(np.uint32))
    assert meta2[0] == meta[0] and meta2[1] == meta[1]


def test_the_pool_reuses_only_vectors_nothing_else_refers_to():
    pool = HostVectorPool()
    a, _ = pool.take(10)
    view = a[2:5]
    del a
    b, reused = pool.take(10)
    assert not reused and not np.shares_memory(b, view)
    del view, b
    c, reused = pool.take(10)
    assert reused
    d, reused = pool.take(10)             # c is in use: a fresh one
    assert not reused and not np.shares_memory(c, d)


def test_the_pool_keeps_at_most_one_free_vector_of_a_length():
    pool = HostVectorPool()
    held = [pool.take(8)[0] for _ in range(3)] + [pool.take(4)[0]]
    del held                              # three free of length 8, one of 4
    vec, reused = pool.take(8)
    assert reused
    # the other two free ones of length 8 are let go; the length-4 one stays
    assert sorted(len(v) for v in pool._vecs) == [4, 8]
    assert pool.take(4)[1]


def test_six_steps_reuse_from_the_fourth_and_keep_held_copies(tmp_path):
    clu = _mk(tmp_path)
    t = time.perf_counter()
    pushed = {}                           # (wid, keeper, iteration) -> copy
    for _ in range(6):
        clu.step()
        for w in clu.workers:
            for kind in ("own", "neighbor"):
                snap = getattr(w.engine, kind).latest()
                pushed[w.wid, kind, snap.iteration] = \
                    np.array(snap.state["shard"])
    for w in clu.workers:
        for kind in ("own", "neighbor"):
            keeper = getattr(w.engine, kind)
            assert keeper.iterations == [5, 6]
            for it in keeper.iterations:
                np.testing.assert_array_equal(
                    keeper.get(it).state["shard"], pushed[w.wid, kind, it])
    counts = _d2h_counts(t)
    assert len(counts) == 6
    assert all(c["reused_bytes"] == 0 for c in counts[:3])
    assert all(c["reused_bytes"] == c["bytes"] > 0 for c in counts[3:])
    assert len(clu.host_vectors._vecs) == 3


def test_a_stale_in_flight_stream_keeps_its_vector(tmp_path):
    """On a fabric too slow to land a shard within an iteration, a stream
    superseded while still in flight is never overwritten by a later step:
    every chunk of it still matches its CRC."""
    slow = _mk(tmp_path, link_bw=2e6, quantum=2048)
    slow.step()
    stale = [w.engine.last_instant_ticket for w in slow.workers]
    assert not any(tk.complete for tk in stale)
    for _ in range(5):
        slow.step()
    assert slow.instant_exposed == 6
    assert all(c.verify() for tk in stale for c in tk.chunks)
    bufs = [np.frombuffer(tk.chunks[0].payload, np.uint8) for tk in stale]
    for w in slow.workers:
        for keeper in (w.engine.own, w.engine.neighbor):
            for it in keeper.iterations:
                shard = keeper.get(it).state["shard"]
                assert not any(np.shares_memory(shard, b) for b in bufs)


def test_a_pending_recovery_stream_keeps_its_vector(tmp_path):
    """An interrupted recovery's partial stream is cut from a held copy; no
    vector the pool hands out afterwards shares its memory, and the resumed
    recovery restores the state bitwise."""
    clu = _mk(tmp_path, quantum=2048)
    clu.run(5)
    want = _host(clu.state)
    clu.inject_failure([1])
    r1 = clu.recover(FaultScript(interrupt_after_chunks=3))
    assert r1.kind == "interrupted" and clu._pending_recovery
    (stream, asm), = clu._pending_recovery.values()
    bufs = [np.frombuffer(c.payload, np.uint8) for c in stream.chunks[:1]]
    for _ in range(4):                    # the state landed again and again
        vec, _ = _flatten_opt(clu.state["opt"], clu.host_vectors)
        assert not any(np.shares_memory(vec, b) for b in bufs)
        vec[:] = np.nan
        del vec
    assert all(c.verify() for c in stream.chunks)
    r2 = clu.recover(FaultScript())
    assert r2.kind == "software" and r2.chunks_reused == 3
    for x, y in zip(want, _host(clu.state)):
        np.testing.assert_array_equal(x, y)


def test_recovery_after_reused_steps_restores_bitwise(tmp_path):
    ref = _mk(tmp_path / "a")
    ref.run(8)
    clu = _mk(tmp_path / "b")
    clu.run(5)
    want = _host(clu.state)
    t = time.perf_counter()
    clu.inject_failure([1])
    rep = clu.recover(FaultScript())
    assert rep.recovered_from == "neighbor"
    assert rep.rolled_back_iterations == 0
    assert _d2h_counts(t)[0]["reused_bytes"] > 0
    for x, y in zip(want, _host(clu.state)):
        np.testing.assert_array_equal(x, y)
    clu.run(8 - clu.iteration)
    for x, y in zip(_host(ref.state), _host(clu.state)):
        np.testing.assert_array_equal(x, y)
    assert ref.loss_history == clu.loss_history
