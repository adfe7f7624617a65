"""A run of a cell, with the chip check skipped and the timed path broken
underneath, comes out not correct. Smoke size on the CPU, with the limits
of that size."""
import dataclasses

import jax
import pytest

from bench_small import judged


@pytest.mark.parametrize("traffic", ["steady", "failover", "nockpt"])
def test_a_step_that_returns_its_state_unchanged_fails(traffic, monkeypatch):
    from repro.runtime import cluster
    monkeypatch.setattr(cluster, "loop_step", lambda model, hp: jax.jit(
        lambda s, b: (s, model.loss(s["params"], b)[0])))
    ok, numbers = judged("qwen3-0.6b", traffic)
    assert not ok and numbers["change_gap"] > 0.99, numbers


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b-d8"])
def test_a_step_over_half_the_batch_fails(config, monkeypatch):
    from repro.runtime import cluster
    build = cluster.build_model

    def half(cfg):
        m = build(cfg)
        return dataclasses.replace(m, loss=lambda p, b: m.loss(
            p, {"tokens": b["tokens"][:b["tokens"].shape[0] // 2]}))
    monkeypatch.setattr(cluster, "build_model", half)
    ok, numbers = judged(config)
    assert not ok, numbers


def test_a_stale_held_copy_fails(monkeypatch):
    from repro.ckpt.engine import CkptEngine
    on_step = CkptEngine.on_step

    def stale(self, it, own, nbr, *, t=0.0):
        return on_step(self, it, own, nbr if it <= 3 else None, t=t)
    monkeypatch.setattr(CkptEngine, "on_step", stale)
    ok, numbers = judged("qwen3-0.6b")
    assert not ok and numbers["held_copy_mismatch"] > 0, numbers


def test_one_element_altered_in_a_held_copy_fails(monkeypatch):
    import numpy as np
    from repro.ckpt.engine import CkptEngine
    on_step = CkptEngine.on_step

    def altered(self, it, own, nbr, *, t=0.0):
        if nbr is not None:
            shard = np.array(nbr["shard"])
            shard.reshape(-1).view(np.uint32)[shard.size // 2] ^= 1
            nbr = {"shard": shard}
        return on_step(self, it, own, nbr, t=t)
    monkeypatch.setattr(CkptEngine, "on_step", altered)
    ok, numbers = judged("qwen3-0.6b")
    assert not ok and numbers["held_copy_mismatch"] > 0, numbers


def test_a_step_rebuilt_by_recovery_with_another_learning_rate_fails(
        monkeypatch):
    from repro.runtime import cluster
    recover = cluster.SimCluster.recover

    def rebuilt(self, *a, **k):
        rep = recover(self, *a, **k)
        self._step = cluster.loop_step(self.model, dataclasses.replace(
            self.hp, lr=2 * self.hp.lr))
        return rep
    monkeypatch.setattr(cluster.SimCluster, "recover", rebuilt)
    ok, numbers = judged("qwen3-0.6b", "failover")
    assert not ok and numbers["change_gap.4"] > 0.1, numbers


def test_a_restore_that_differs_from_the_killed_state_fails(monkeypatch):
    from repro.runtime.cluster import SimCluster
    recover = SimCluster.recover

    def off(self, *a, **k):
        rep = recover(self, *a, **k)
        self.state["opt"]["v"] = jax.tree.map(lambda x: x * 2,
                                              self.state["opt"]["v"])
        return rep
    monkeypatch.setattr(SimCluster, "recover", off)
    ok, numbers = judged("qwen3-0.6b", "failover")
    assert not ok and numbers["restored_state_mismatch"] > 0, numbers


def test_a_loss_altered_where_it_is_produced_fails(monkeypatch):
    from repro.runtime import cluster
    from bench.control import ALTERED
    step = cluster.loop_step

    def altered(model, hp):
        fn = step(model, hp)
        return lambda s, b: (lambda out: (out[0], out[1] * (1 + ALTERED)))(
            fn(s, b))
    monkeypatch.setattr(cluster, "loop_step", altered)
    ok, numbers = judged("qwen3-0.6b")
    assert not ok and numbers["loss_gap"] > ALTERED / 2, numbers
