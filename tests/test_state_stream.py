"""StateStream tentpole coverage: chunk format + CRCs, resumable assembly,
CkptEngine paths through the shared transport, scheduler-derived failover
timelines (preemption delays recovery), multi-failure resume-from-partial-
chunks on the cluster, and the emergent FCR hiding condition."""
import dataclasses
import multiprocessing
import os
import time
import zlib
from pathlib import Path

import numpy as np
import pytest

from repro.ckpt import stream as stream_mod
from repro.ckpt.engine import CkptEngine, CkptEngineConfig
from repro.ckpt.stream import (ChunkedStream, StreamAssembler, StreamChunk,
                               StreamTransport, stream_pytree)
from repro.core.lccl import LinkScheduler
from repro.launch import spans
from repro.runtime.recovery import FaultScript


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": rng.normal(size=1000).astype(np.float32),
            "b": {"c": rng.normal(size=(3, 7)),
                  "d": np.int32(5)}}


# --------------------------------------------------------------------------- #
# chunk format
# --------------------------------------------------------------------------- #
def test_pytree_chunk_roundtrip_bitwise():
    tree = _tree()
    cs = ChunkedStream.from_pytree("s", tree, quantum=512)
    assert cs.n_chunks > 3
    assert sum(c.nbytes for c in cs.chunks) == cs.total_bytes
    asm = StreamAssembler.for_stream(cs)
    for c in reversed(cs.chunks):          # out-of-order delivery
        assert asm.offer(c)
    out = asm.to_pytree(tree)
    for k in ("a",):
        np.testing.assert_array_equal(out[k], tree[k])
    np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])
    assert out["b"]["d"] == tree["b"]["d"]


def test_corrupt_chunk_rejected_by_crc():
    cs = ChunkedStream.from_pytree("s", _tree(), quantum=512)
    good = cs.chunks[1]
    flipped = bytes([good.payload[0] ^ 0xFF]) + good.payload[1:]
    bad = StreamChunk(good.stream_id, good.seq, good.n_chunks, good.offset,
                      flipped, good.crc, good.total_bytes)
    asm = StreamAssembler.for_stream(cs)
    assert not asm.offer(bad)
    assert asm.rejected == 1
    assert good.seq in asm.missing()       # still owed after corruption
    assert asm.offer(good)                 # retransmit succeeds


def test_assembler_resumes_from_partial():
    cs = ChunkedStream.from_pytree("s", _tree(), quantum=256)
    asm = StreamAssembler.for_stream(cs)
    for c in cs.chunks[:3]:
        asm.offer(c)
    assert len(asm.missing()) == cs.n_chunks - 3
    # duplicate delivery is idempotent
    assert not asm.offer(cs.chunks[0])
    for seq in asm.missing():
        asm.offer(cs.chunks[seq])
    assert asm.complete


# --------------------------------------------------------------------------- #
# CRCs on the pool of host threads
# --------------------------------------------------------------------------- #
_FANOUT = stream_mod._POOL_MIN_BYTES
_POOLED = len(os.sched_getaffinity(0)) >= 2
# both sides of the fan-out size, and lengths 1 MiB chunks do not divide
_SIZES = [_FANOUT // 2 + 7, _FANOUT - 1, _FANOUT, 2 * _FANOUT + 12345]


def _cut(nbytes):
    """`nbytes` random bytes, their stream in 1 MiB chunks, the span that
    cut it, and the chunks' CRCs taken one after another on this thread."""
    raw = np.random.default_rng(nbytes).integers(0, 256, nbytes, np.uint8)
    t0 = time.perf_counter()
    cs = ChunkedStream.from_pytree("big", {"x": raw})
    cut = [s for s in spans.spans(t0) if s.name == "stream.chunk"]
    q = cs.quantum
    serial = [(i, o, len(raw[o:o + q]), zlib.crc32(raw[o:o + q].tobytes()))
              for i, o in enumerate(range(0, nbytes, q))]
    return raw, cs, cut, serial


def _cut_in_child(nbytes, conn):
    _, cs, cut, serial = _cut(nbytes)
    conn.send(([tuple(c.manifest_entry().values()) for c in cs.chunks],
               serial, cut[0].counts["parallel_bytes"]))
    conn.close()


@pytest.mark.parametrize("nbytes", _SIZES)
@pytest.mark.parametrize("check", ["crcs", "counter", "nack", "fork"])
def test_pooled_crcs_equal_one_thread(check, nbytes):
    pooled = _POOLED and nbytes >= _FANOUT
    if check == "fork":
        _cut(_FANOUT)                      # the parent's pool exists first
        ctx = multiprocessing.get_context("fork")
        recv, send = ctx.Pipe(duplex=False)
        child = ctx.Process(target=_cut_in_child, args=(nbytes, send))
        child.start()
        answered = recv.poll(60)
        if not answered:
            child.kill()
        child.join(60)
        assert answered, "the forked child cut no stream"
        got, serial, parallel = recv.recv()
        assert child.exitcode == 0
        assert got == serial
        assert parallel == (nbytes if pooled else 0)
        return
    raw, cs, cut, serial = _cut(nbytes)
    if check == "crcs":
        assert [(c.seq, c.offset, c.nbytes, c.crc) for c in cs.chunks] \
            == serial
        assert all(c.payload.readonly for c in cs.chunks)
    elif check == "counter":
        assert len(cut) == 1
        assert cut[0].counts == {"bytes": nbytes, "chunks": cs.n_chunks,
                                 "parallel_bytes": nbytes if pooled else 0}
    else:
        good = cs.chunks[1]
        flipped = bytes([good.payload[0] ^ 0xFF]) + good.payload[1:]
        assert good.verify()
        assert not dataclasses.replace(good, payload=flipped).verify()
        tp = StreamTransport(LinkScheduler(1e9, quantum=1 << 20))
        asm = StreamAssembler.for_stream(cs)
        tp.corrupt_once("big", 1)
        ticket = tp.send(cs, 0.0, assembler=asm)
        tp.drain()
        assert ticket.complete and asm.rejected == 1 and tp.nacks_sent == 1
        assert asm.data() == raw.tobytes()


# --------------------------------------------------------------------------- #
# transport: STATE chunks + TRAIN preemption on one scheduler
# --------------------------------------------------------------------------- #
def test_transport_delivers_through_scheduler():
    tp = StreamTransport(LinkScheduler(1e6, quantum=256))
    tree = _tree()
    ticket, asm = stream_pytree(tp, "t", tree, t=0.0, quantum=512)
    tp.drain()
    assert ticket.complete and asm.complete
    np.testing.assert_array_equal(asm.to_pytree(tree)["a"], tree["a"])


@pytest.mark.parametrize("fabric", ["link", "topology"])
def test_withdraw_takes_back_only_unstarted_chunks(fabric):
    """Withdrawal dequeues the chunks that never moved a byte; the chunk on
    the wire and those already delivered stay, and the ticket stays
    incomplete. The withdrawn bytes leave the STATE byte count."""
    from repro.ckpt.stream import TopologyTransport
    from repro.core.lccl import LinkTopology
    if fabric == "link":
        tp = StreamTransport(LinkScheduler(1e6, quantum=256))
        send = {}
    else:
        tp = TopologyTransport(LinkTopology(4, 1e6, quantum=256))
        send = {"src": 0, "dst": 1, "policy": "shortest"}
    tree = _tree()
    cs = ChunkedStream.from_pytree("s", tree, quantum=512)
    asm = StreamAssembler.for_stream(cs)
    ticket = tp.send(cs, 0.0, assembler=asm, **send)
    tp.run(until=0.00155)        # 3 chunks of 512 B at 1 MB/s, then half one
    landed = asm.received
    assert 0 < landed < cs.n_chunks - 1
    gone = tp.withdraw(ticket)
    assert gone == cs.n_chunks - landed - 1   # one chunk is mid-flight
    assert tp.state_bytes_submitted == cs.total_bytes - sum(
        cs.chunks[i].nbytes for i in range(landed + 1, cs.n_chunks))
    assert tp.withdraw(ticket) == 0
    tp.drain()
    assert asm.received == landed + 1 and not ticket.complete


def test_train_traffic_delays_stream_completion():
    def finish(with_train):
        tp = StreamTransport(LinkScheduler(1e6, quantum=256))
        ticket, _ = stream_pytree(tp, "t", _tree(), t=0.0, quantum=512)
        if with_train:
            tp.submit_train(2e6, 0.0005)   # 2 s of TRAIN early on
        tp.drain()
        return ticket.finish_time
    assert finish(True) > finish(False) + 1.5


# --------------------------------------------------------------------------- #
# CkptEngine: instant + full + lazy all ride the shared link
# --------------------------------------------------------------------------- #
def test_engine_paths_stream_chunks(tmp_path):
    tp = StreamTransport(LinkScheduler(1e9, quantum=1 << 20))
    eng = CkptEngine(CkptEngineConfig(out_dir=tmp_path, full_every=2,
                                      quantum=512), worker_id=0, transport=tp)
    shard = {"shard": np.arange(400, dtype=np.float32)}
    eng.on_step(1, shard, shard, t=0.0)
    assert eng.streamed_chunks > 0
    n_after_instant = eng.streamed_chunks
    eng.maybe_full_checkpoint(2, {"w": np.ones(300, np.float32)}, t=0.1)
    assert eng.streamed_chunks > n_after_instant
    n_after_full = eng.streamed_chunks
    eng.lazy_backup(2, {"params": np.ones(100, np.float32)},
                    is_dp_rank0=True, t=0.2)
    assert eng.streamed_chunks > n_after_full
    tp.drain()
    assert tp.chunks_delivered == eng.streamed_chunks
    # full ckpt wrote a per-chunk CRC manifest
    from repro.ckpt.storage import load_manifest
    man = load_manifest(eng._full_path(2))
    assert man is not None and man["n_chunks"] >= 1
    eng.writer.drain()
    eng.close()


def test_newer_instant_stream_supersedes_stale_one(tmp_path):
    """On a link slower than the iteration, each instant checkpoint takes
    back the unsent chunks of the one before it: at most one version per
    worker is queued, and the newest still lands whole."""
    tp = StreamTransport(LinkScheduler(1e6, quantum=256))
    eng = CkptEngine(CkptEngineConfig(out_dir=tmp_path, quantum=512),
                     worker_id=0, transport=tp)
    shard = {"shard": np.arange(2000, dtype=np.float32)}   # 16 chunks
    tickets = []
    for it in range(1, 4):
        eng.on_step(it, shard, shard, t=(it - 1) * 1e-3)
        tickets.append(eng.last_instant_ticket)
        tp.run(until=it * 1e-3)
        queued = {id(p.ticket) for p in tp._pending}
        assert len(queued) <= 2           # the newest + one chunk on the wire
    assert eng.superseded_chunks > 0
    assert not any(tk.complete for tk in tickets[:-1])
    tp.drain()
    assert eng.last_instant_ticket.complete
    assert eng.last_instant_ticket.assembler.complete
    eng.close()


def test_engine_export_import_stream(tmp_path):
    eng = CkptEngine(CkptEngineConfig(out_dir=tmp_path, quantum=128))
    shard = {"shard": np.arange(100, dtype=np.float32)}
    eng.on_step(7, shard, shard)
    stream = eng.export_stream(7, which="neighbor")
    asm = StreamAssembler.for_stream(stream)
    for c in stream.chunks:
        asm.offer(c)
    out = CkptEngine.import_stream(asm, shard)
    np.testing.assert_array_equal(out["shard"], shard["shard"])
    eng.close()


# --------------------------------------------------------------------------- #
# failover timelines are scheduler-derived
# --------------------------------------------------------------------------- #
def test_preempted_state_chunks_delay_recovery():
    """The acceptance-criteria property: TRAIN traffic on the shared link
    preempts recovery STATE chunks and the fftrainer timeline stretches by
    the schedule's answer."""
    from repro.runtime.failover import fftrainer_timeline
    quiet = fftrainer_timeline(16, 10e9)
    busy = fftrainer_timeline(16, 10e9,
                              train_traffic=[(0.0, 50e9), (1.0, 50e9)])
    assert busy["network_and_state"] > quiet["network_and_state"] + 0.5
    assert busy["total"] > quiet["total"] + 0.5
    # without competition the schedule reduces to bytes/bandwidth (+ramp)
    assert quiet["network_and_state"] == pytest.approx(
        max(0.5 + 0.001 * 16, 10e9 / 50e9 + 0.2), rel=1e-3)


def test_baseline_timeline_still_serial():
    from repro.runtime.failover import baseline_timeline
    tl = baseline_timeline(16, 13e9 / 4)
    assert tl["state_recovery"] == pytest.approx(13e9 / 4 / 1e9 + 2.0,
                                                 rel=1e-3)
    assert tl["total"] > 800.0


# --------------------------------------------------------------------------- #
# emergent FCR
# --------------------------------------------------------------------------- #
def test_fcr_emergent_matches_closed_form():
    from repro.core.fcr import fcr, fcr_hidden_emergent, is_free
    rng = np.random.default_rng(3)
    for _ in range(40):
        s = float(rng.integers(128, 1 << 18))
        b = float(rng.integers(1, 64))
        v = float(rng.uniform(1e9, 1e12))
        c = float(rng.uniform(1e12, 1e16))
        if abs(fcr(s, b, v, c) - 1.0) < 1e-3:
            continue                      # numerical knife-edge
        assert fcr_hidden_emergent(s, b, v, c, phi=1e8) == is_free(s, b, v, c)


def test_fcr_hiding_breaks_under_train_contention():
    from repro.core.fcr import fcr_hidden_emergent, is_free
    s, b, c, phi = 4096, 8, 1e15, 1e8
    v = 2.0 * c / (s * b) * 1.1           # marginally free link
    assert is_free(s, b, v, c)
    t_c = 6 * s * b * phi / c
    busy = [(i * t_c, 0.5 * v * t_c) for i in range(3)]
    assert fcr_hidden_emergent(s, b, v, c, phi=phi)
    assert not fcr_hidden_emergent(s, b, v, c, phi=phi, train_traffic=busy)


# --------------------------------------------------------------------------- #
# cluster: multi-failure, resume from partial chunks (real state movement)
# --------------------------------------------------------------------------- #
def _mk_cluster(tmp_path, **fabric_kw):
    import jax  # noqa: F401  (ensures cpu backend initialized)
    from repro.configs import get_arch, reduce_for_smoke
    from repro.optim import AdamWConfig
    from repro.runtime.cluster import (ClusterConfig, FabricConfig,
                                       SimCluster)
    cfg = dataclasses.replace(reduce_for_smoke(get_arch("qwen3-0.6b")),
                              dtype="float32")
    fabric_kw.setdefault("quantum", 2048)
    return SimCluster(
        cfg,
        cluster=ClusterConfig(
            dp=4, global_batch=8, seq_len=16, ckpt_dir=tmp_path / "ck",
            full_every=50,
            hp=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50), seed=0),
        fabric=FabricConfig(**fabric_kw))


def test_multi_failure_resumes_from_partial_chunks(tmp_path):
    import jax
    ref = _mk_cluster(tmp_path / "a")
    ref.run(10)

    clu = _mk_cluster(tmp_path / "b")
    clu.run(5)
    clu.inject_failure([0], hardware=True)
    r1 = clu.recover(FaultScript(hardware=True, interrupt_after_chunks=3))
    assert r1.kind == "interrupted"
    assert r1.chunks_sent == 3 and r1.chunks_total > 3
    assert not clu.workers[0].alive        # still down mid-transfer

    # second concurrent failure (non-adjacent: its backup holder is alive)
    clu.inject_failure([2], hardware=True)
    r2 = clu.recover(FaultScript(hardware=True))
    assert r2.kind == "hardware"
    assert r2.chunks_reused == 3           # partial chunks NOT re-sent
    assert r2.chunks_sent == r2.chunks_total - 3
    assert r2.rolled_back_iterations == 0  # instant ckpt: zero rollback

    clu.run(10 - clu.iteration)
    for x, y in zip(jax.tree.leaves(ref.state), jax.tree.leaves(clu.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_corruption_mid_recovery_heals_via_nack(tmp_path):
    """Bytes flipped on the wire mid-recovery are CRC-rejected and healed by
    per-chunk NACK retransmits — recovery completes with NO rollback and the
    recovered state is bitwise identical to an uninterrupted run."""
    import jax
    ref = _mk_cluster(tmp_path / "a")
    ref.run(8)

    clu = _mk_cluster(tmp_path / "b")
    clu.run(5)
    clu.inject_failure([1], hardware=True)
    rep = clu.recover(FaultScript(hardware=True, corrupt_chunks=3))
    assert rep.kind == "hardware"
    assert rep.rolled_back_iterations == 0     # healed in-stream: no rollback
    assert clu.transport.nacks_sent == 3       # one immediate resend each
    clu.run(8 - clu.iteration)
    for x, y in zip(jax.tree.leaves(ref.state), jax.tree.leaves(clu.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shrink_mid_transfer_keeps_partial_streams(tmp_path):
    """Elastic shrink striking mid-recovery: the removed worker's stream dies
    with it, but the surviving failed worker's partial stream (and its
    received chunks) persists across the rescale and the next recover()
    RESUMES it — no restart, no rollback."""
    import jax
    clu = _mk_cluster(tmp_path)
    clu.run(5)
    at_failure = [np.asarray(x).copy() for x in jax.tree.leaves(clu.state)]

    clu.inject_failure([0, 2], hardware=True)  # non-adjacent: backups survive
    r1 = clu.recover(FaultScript(hardware=True, interrupt_after_chunks=3))
    assert r1.kind == "interrupted" and r1.chunks_sent == 3

    # no spare capacity for worker 2: shrink it away mid-transfer; worker 0
    # keeps its partial recovery stream across the rescale
    assert clu.shrink([2]) == 3
    r2 = clu.recover(FaultScript(hardware=True))
    assert r2.kind == "hardware"
    assert r2.chunks_reused == 3               # partial chunks NOT re-sent
    assert r2.rolled_back_iterations == 0
    # the rebuilt state is bitwise the state at the failure iteration
    for x, y in zip(at_failure, jax.tree.leaves(clu.state)):
        np.testing.assert_array_equal(x, np.asarray(y))
    # training continues at dp=3
    losses = clu.run(3)
    assert all(np.isfinite(l) for l in losses)


def test_instant_ckpt_hidden_on_fast_link(tmp_path):
    """On the ICI-class default link the per-iteration shard drains inside
    the modeled iteration — the FCR condition, emergent from the transport."""
    clu = _mk_cluster(tmp_path)
    clu.run(4)
    assert clu.instant_hidden == 4
    assert clu.instant_exposed == 0
    assert clu.transport.chunks_delivered > 0


def test_instant_backlog_bounded_on_slow_fabric(tmp_path):
    """A fabric too slow to drain the instant checkpoint in an iteration
    exposes every step, but stale versions are superseded instead of
    queueing: the in-flight chunks never exceed one shard per worker plus
    the chunks on the wire, and training and recovery are unchanged."""
    import jax
    slow = _mk_cluster(tmp_path / "slow", link_bw=2e6)
    for _ in range(5):
        slow.step()
        newest = sum(w.engine.last_instant_ticket.assembler.n_chunks
                     for w in slow.workers)
        # one chunk at most on the wire per ring edge
        assert len(slow.transport._pending) <= newest + slow.dp
    assert slow.instant_exposed == 5
    assert sum(w.engine.superseded_chunks for w in slow.workers) > 0
    fast = _mk_cluster(tmp_path / "fast")
    fast.run(5)
    assert slow.loss_history == fast.loss_history
    slow.inject_failure([1])
    rep = slow.recover()
    assert rep.recovered_from == "neighbor"
    assert rep.rolled_back_iterations == 0
    for x, y in zip(jax.tree.leaves(fast.state), jax.tree.leaves(slow.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
