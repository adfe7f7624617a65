"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
output shapes + no NaNs (deliverable f)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow    # one jit compile per arch, ~2 min total

from repro.configs import ASSIGNED, PAPER_WORKLOADS, get_arch, reduce_for_smoke
from repro.models import build_model

B, S = 2, 16


def _batch(cfg, rng, seq=S):
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, seq + 1)).astype(np.int32))}
    if cfg.num_patch_tokens:
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_patch_tokens, cfg.d_model)),
            jnp.bfloat16)
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED + PAPER_WORKLOADS)
def test_smoke_loss_and_grad(arch, rng):
    cfg = reduce_for_smoke(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg, rng)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True)(params)
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    gnorm = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, f"{arch}: bad grads"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_shapes(arch, rng):
    cfg = reduce_for_smoke(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg, rng)
    logits = model.forward(params, {**batch,
                                    "tokens": batch["tokens"][:, :-1]})
    s_total = S + (cfg.num_patch_tokens or 0)
    assert logits.shape == (B, s_total, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_prefill_decode_matches_forward(arch, rng):
    """Decode continuing a prefill must reproduce the full-forward logits
    (fp32)."""
    cfg = dataclasses.replace(reduce_for_smoke(get_arch(arch)),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = rng.integers(0, cfg.vocab_size, (B, 12)).astype(np.int32)
    npatch = cfg.num_patch_tokens
    batch = {"tokens": jnp.asarray(toks)}
    if npatch:
        batch["patch_embeds"] = jax.random.normal(
            jax.random.key(1), (B, npatch, cfg.d_model), jnp.float32)
    if cfg.encoder_layers:
        batch["frames"] = jax.random.normal(
            jax.random.key(2), (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    full = model.forward(params, batch)
    pre = dict(batch)
    pre["tokens"] = jnp.asarray(toks[:, :-1])
    pre["max_len"] = 12 + npatch + 4
    _, cache = model.prefill(params, pre)
    logits, cache = model.decode_step(params, cache, jnp.asarray(toks[:, -1]))
    ref = np.asarray(full[:, -1], np.float32)
    np.testing.assert_allclose(np.asarray(logits, np.float32), ref,
                               rtol=2e-4, atol=2e-4)
    assert int(cache["index"]) == 12 + npatch


def test_param_counts_match_analytic():
    """Exact param accounting for a dense arch (validates eval_shape path)."""
    from repro.models import param_count
    cfg = get_arch("llama3-8b")
    n = param_count(cfg)
    d, f, l, v = cfg.d_model, cfg.d_ff, cfg.num_layers, cfg.padded_vocab
    hd, h, kh = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    per_layer = (d * h * hd + 2 * d * kh * hd + h * hd * d  # attn
                 + 3 * d * f                                 # swiglu
                 + 2 * d)                                    # norms
    expected = 2 * v * d + l * per_layer + d
    assert n == expected
