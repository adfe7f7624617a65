"""The host span recorder (`repro.launch.spans`) and the spans the loop, the
instant checkpoint and recovery record: parent links, identifiers, counts,
the ring's bound, the span tree of a smoke step and recovery, the spans in
a `jax.profiler` trace, and the named scopes of the jitted step."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import spans
from repro.launch.spans import CAPACITY, count, span


def _since():
    import time
    return time.perf_counter()


def test_parent_links_ids_and_counts_on_the_innermost_span():
    t = _since()
    with span("outer", iteration=3):
        count("bytes", 5)
        with span("inner", part=1):
            count("bytes", 7)
            count("bytes", 1)
        with span("leaf"):
            pass
    got = {s.name: s for s in spans.spans(t)}
    outer, inner, leaf = got["outer"], got["inner"], got["leaf"]
    assert outer.parent is None
    assert inner.parent == outer.sid and leaf.parent == outer.sid
    assert outer.ids == {"iteration": 3}
    assert inner.ids == {"iteration": 3, "part": 1}
    assert leaf.ids == {"iteration": 3}
    assert outer.counts == {"bytes": 5} and inner.counts == {"bytes": 8}
    assert leaf.counts == {}
    assert outer.t0 <= inner.t0 <= inner.t1 <= leaf.t0 <= leaf.t1 <= outer.t1
    # closed in order: children first
    names = [s.name for s in spans.spans(t)]
    assert names == ["inner", "leaf", "outer"]
    row = spans.summary(t)["outer"]
    assert row["calls"] == 1 and row["bytes"] == 5 and row["seconds"] >= 0


def test_a_count_with_no_open_span_is_dropped_and_threads_do_not_nest():
    t = _since()
    count("bytes", 3)

    def other():
        with span("other"):
            count("rows", 2)
    with span("main", iteration=1):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    got = {s.name: s for s in spans.spans(t)}
    assert got["other"].parent is None and got["other"].ids == {}
    assert got["other"].counts == {"rows": 2}
    assert got["main"].counts == {}
    assert spans.summary(t).keys() == {"main", "other"}


def test_the_record_is_a_bounded_ring_of_numbers():
    t = _since()
    for i in range(CAPACITY + 10):
        with span("ring", iteration=np.int64(i)):
            count("bytes", jnp.int32(4))
    kept = spans.spans()
    assert len(kept) == CAPACITY
    ours = [s for s in kept if s.t0 >= t]
    assert len(ours) == CAPACITY and ours[0].ids["iteration"] == 10
    for s in ours[:3] + ours[-3:]:
        for v in (*s.ids.values(), *s.counts.values()):
            assert type(v) is int          # never an array, even a scalar
        assert isinstance(s.t0, float) and isinstance(s.t1, float)


# --------------------------------------------------------------------------- #
def _smoke_cluster(tmp_path):
    from repro.configs import get_arch, reduce_for_smoke
    from repro.runtime.cluster import ClusterConfig, SimCluster
    return SimCluster(reduce_for_smoke(get_arch("qwen3-0.6b")),
                      cluster=ClusterConfig(dp=2, global_batch=4, seq_len=16,
                                            ckpt_dir=tmp_path))


def _children(recorded, parent):
    return [s for s in recorded if s.parent == parent.sid]


def test_a_step_and_a_recovery_give_the_span_tree(tmp_path):
    from repro.runtime.cluster import FaultScript
    clu = _smoke_cluster(tmp_path)
    n_params = sum(x.size for x in jax.tree.leaves(clu.state["params"]))
    t = _since()
    clu.step()
    clu.inject_failure([1])
    clu.recover(FaultScript())
    recorded = spans.spans(t)
    roots = [s for s in recorded if s.parent is None]
    assert [s.name for s in roots] == ["loop.step", "recover"]
    step, rec = roots
    assert step.ids == {"iteration": 0} and rec.ids == {"recovery": 1}

    kids = [s.name for s in _children(recorded, step)]
    assert kids == ["data.batch", "data.batch", "loop.device_wait",
                    "ckpt.instant", "fabric.run"]
    ckpt = next(s for s in _children(recorded, step)
                if s.name == "ckpt.instant")
    inside = _children(recorded, ckpt)
    assert [s.name for s in inside] == ["opt.d2h"] + [
        "stream.chunk", "stream.send"] * 2
    assert inside[0].counts == {"bytes": 12 * n_params, "reused_bytes": 0}
    assert sum(s.counts["bytes"] for s in inside
               if s.name == "stream.chunk") == 12 * n_params
    assert all(s.ids == {"iteration": 0} for s in inside)
    assert sum(s.counts["rows"] for s in _children(recorded, step)
               if s.name == "data.batch") == 4

    phases = [s.name for s in _children(recorded, rec)]
    assert phases == ["recover.lazy_backup", "recover.stream", "opt.d2h",
                      "recover.stream", "recover.upload"]
    by = {s.name: s for s in _children(recorded, rec)}
    assert by["opt.d2h"].counts == {"bytes": 12 * n_params,
                                    "reused_bytes": 0}
    assert by["recover.upload"].counts == {"bytes": 12 * n_params}
    lazy = [s.name for s in _children(recorded, by["recover.lazy_backup"])]
    assert lazy == ["storage.save", "stream.chunk", "stream.send"]
    first_stream = next(s for s in _children(recorded, rec)
                        if s.name == "recover.stream")
    assert [s.name for s in _children(recorded, first_stream)] == [
        "stream.chunk", "fabric.drain"]
    assert first_stream.counts["bytes"] == 12 * n_params // 2

    by_sid = {s.sid: s for s in recorded}
    for s in recorded:
        if s.parent is not None:
            p = by_sid[s.parent]
            assert p.t0 <= s.t0 <= s.t1 <= p.t1, (s.name, p.name)
    for w in clu.workers:
        w.engine.close()


def test_a_profile_of_a_step_holds_the_span_names_on_a_host_plane(tmp_path):
    from jax.profiler import ProfileData
    clu = _smoke_cluster(tmp_path / "ckpt")
    clu.step()                            # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=opts)
    try:
        clu.step()
    finally:
        jax.profiler.stop_trace()
    names = set()
    for path in glob.glob(os.path.join(tmp_path, "trace", "**",
                                       "*.xplane.pb"), recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                names.update(ev.name for line in plane.lines
                             for ev in line.events)
    assert {"loop.step", "data.batch", "loop.device_wait", "ckpt.instant",
            "opt.d2h", "stream.chunk", "stream.send", "fabric.run"} <= names
    for w in clu.workers:
        w.engine.close()


@pytest.mark.parametrize("scope", ["jvp(forward)", "transpose(jvp(forward))",
                                   "optimizer"])
def test_the_lowered_step_names_its_scopes(scope):
    from repro.configs import get_arch, reduce_for_smoke
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.runtime.cluster import loop_step
    from repro.train.state import init_state
    model = build_model(reduce_for_smoke(get_arch("qwen3-0.6b")))
    state = init_state(model, jax.random.key(0))
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    text = loop_step(model, AdamWConfig()).lower(state, batch) \
        .as_text(debug_info=True)
    # the forward under `jvp`, the backward under `transpose` of it
    assert f'loc("jit(step)/{scope}/' in text
