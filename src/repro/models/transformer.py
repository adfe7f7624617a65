"""Model assembly for every architecture family.

One functional ``Model`` per ArchConfig with:
  init(key)                      -> params (real arrays; smoke-scale only)
  param_specs()                  -> ShapeDtypeStruct pytree (production-scale safe)
  loss(params, batch)            -> (scalar, metrics)
  prefill(params, batch)         -> (last_logits, cache)
  decode_step(params, cache, tok)-> (logits, cache)
  cache_specs(batch, max_len)    -> ShapeDtypeStruct pytree
  input_specs(shape)             -> batch pytree of ShapeDtypeStruct

Decoder stacks are ``lax.scan`` over stacked layer params so HLO size (and
compile time) is depth-independent; hybrid (Zamba2) applies its weight-shared
attention block inside the scan via ``lax.cond``. Remat policy per config.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig, ShapeConfig
from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.models.layers import (chunked_xent, embed_init, embed_lookup,
                                 mlp_apply, mlp_init, rms_norm,
                                 sinusoidal_positions, unembed)
from repro.models.modes import (in_analysis_mode, scan_layers,
                                unshard_layer_params)
from repro.parallel.constraints import BATCH, constrain

PyTree = Any


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _remat(fn, cfg: ArchConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    raise ValueError(f"unknown remat policy {cfg.remat_policy}")


# =========================================================================== #
# Per-layer parameter initializers
# =========================================================================== #
def _attn_block_init(key, cfg: ArchConfig, dtype, *, d_ff: Optional[int] = None):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn.attn_init(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.is_moe and d_ff is None:
        p["moe"] = moe.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, d_ff or cfg.d_ff, cfg.mlp_type, dtype)
    return p


def _mamba_block_init(key, cfg: ArchConfig, dtype):
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "mamba": mamba2.mamba_init(key, cfg, dtype),
    }


def _encdec_block_init(key, cfg: ArchConfig, dtype, *, cross: bool):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn.attn_init(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype),
    }
    if cross:
        p["ln_cross"] = jnp.zeros((cfg.d_model,), dtype)
        p["cross"] = attn.cross_attn_init(k2, cfg, dtype)
    return p


# =========================================================================== #
# Per-layer forward bodies
# =========================================================================== #
def _attn_block(p, cfg: ArchConfig, x, aux):
    p = unshard_layer_params(p, cfg)         # FSDP: in-body all-gather
    x = constrain(x, BATCH, "model", None)   # Megatron-style SP
    h = rms_norm(x, p["ln1"])
    # constraining the addend BEFORE the residual add turns the TP
    # partial-sum resolution into a reduce-scatter (bytes/16) instead of a
    # full all-reduce — measured 7.3 GB/layer -> see EXPERIMENTS.md §Perf
    a_out = constrain(attn.self_attention(p["attn"], cfg, h),
                      BATCH, "model", None)
    x = x + a_out
    h = rms_norm(x, p["ln2"])
    if "moe" in p:
        out, lb = moe.moe_apply(p["moe"], cfg, h)
        x = x + constrain(out, BATCH, "model", None)
        aux = aux + lb
    else:
        x = x + constrain(mlp_apply(p["mlp"], h, cfg.mlp_type),
                          BATCH, "model", None)
    # exit constraint: keeps the scan carry (and the remat-saved stack of
    # layer inputs) sequence-sharded over "model"
    return constrain(x, BATCH, "model", None), aux


def _mamba_block(p, cfg: ArchConfig, x):
    p = unshard_layer_params(p, cfg)
    x = constrain(x, BATCH, "model", None)   # SP on the residual stream
    h = rms_norm(x, p["ln1"])
    x = x + constrain(mamba2.mamba_apply(p["mamba"], cfg, h),
                      BATCH, "model", None)
    return constrain(x, BATCH, "model", None)


def _shared_attn_block(p, cfg: ArchConfig, x):
    """Zamba2's weight-shared attention+MLP block."""
    x = constrain(x, BATCH, "model", None)
    h = rms_norm(x, p["ln1"])
    x = x + constrain(attn.self_attention(p["attn"], cfg, h),
                      BATCH, "model", None)
    h = rms_norm(x, p["ln2"])
    x = x + constrain(mlp_apply(p["mlp"], h, cfg.mlp_type),
                      BATCH, "model", None)
    return constrain(x, BATCH, "model", None)


# =========================================================================== #
# Model
# =========================================================================== #
@dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable[[jax.Array], PyTree]
    param_specs: Callable[[], PyTree]
    loss: Callable[[PyTree, Dict], Tuple[jax.Array, Dict]]
    forward: Callable[[PyTree, Dict], jax.Array]
    prefill: Callable[[PyTree, Dict], Tuple[jax.Array, PyTree]]
    decode_step: Callable[[PyTree, PyTree, jax.Array], Tuple[jax.Array, PyTree]]
    cache_specs: Callable[[int, int], PyTree]
    input_specs: Callable[[ShapeConfig], Dict]


def build_model(cfg: ArchConfig) -> Model:
    builder = {
        "dense": _build_decoder_lm,
        "moe": _build_decoder_lm,
        "vlm": _build_decoder_lm,
        "ssm": _build_ssm_lm,
        "hybrid": _build_hybrid_lm,
        "encdec": _build_encdec,
    }[cfg.family]
    return builder(cfg)


def _stacked_init(block_init, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(block_init)(keys)


def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy; logits fp32 (B, S, V).

    logsumexp - label-logit form: avoids materializing a second (B, S, V)
    log-softmax buffer (the logits themselves are unavoidable)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - lab)


# --------------------------------------------------------------------------- #
# Dense / MoE / VLM decoder-only LM
# --------------------------------------------------------------------------- #
def _build_decoder_lm(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)
    n_dense = cfg.first_k_dense
    n_layers = cfg.num_layers - n_dense      # the scanned stack
    v = cfg.padded_vocab

    def init(key):
        k_emb, k_blocks, k_head = jax.random.split(key, 3)
        params = {
            "embed": embed_init(k_emb, v, cfg.d_model, dtype),
            "blocks": _stacked_init(
                lambda k: _attn_block_init(k, cfg, dtype), k_blocks, n_layers),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        if n_dense:   # leading dense blocks, each its own (unstacked) tree
            params["dense"] = [
                _attn_block_init(k, cfg, dtype, d_ff=cfg.d_ff) for k in
                jax.random.split(jax.random.fold_in(k_blocks, 1), n_dense)]
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(k_head, v, cfg.d_model, dtype)
        return params

    def param_specs():
        return jax.eval_shape(init, jax.random.key(0))

    def _embed_inputs(params, batch, seq_in):
        """Token (and patch) embeddings -> (B, S_total, D)."""
        x = embed_lookup(params["embed"], seq_in)
        if cfg.num_patch_tokens:
            x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
        return x

    def _backbone(params, x):
        body = _remat(lambda carry, p: _attn_block(p, cfg, *carry), cfg)
        carry = (x, jnp.zeros((), jnp.float32))
        for p in params.get("dense", ()):
            carry = body(carry, p)
        (x, aux), _ = scan_layers(
            lambda c, p: (body(c, p), None), carry,
            params["blocks"], length=n_layers)
        return rms_norm(x, params["final_norm"]), aux

    def _logits(params, x):
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return unembed(head, x)

    def forward(params, batch):
        tokens = batch["tokens"]
        x = _embed_inputs(params, batch, tokens)
        x, _ = _backbone(params, x)
        return _logits(params, x)

    def loss(params, batch):
        tokens = batch["tokens"]                    # (B, S_text+1)
        inp, labels = tokens[:, :-1], tokens[:, 1:]
        x = _embed_inputs(params, batch, inp)
        x, aux = _backbone(params, x)
        npatch = cfg.num_patch_tokens
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        l = chunked_xent(head, x[:, npatch:] if npatch else x, labels)
        total = l + cfg.aux_loss_alpha * aux
        return total, {"xent": l, "aux": aux}

    def _no_cache():
        if cfg.is_mla or n_dense:
            raise NotImplementedError(
                f"{cfg.name}: serving needs a latent KV cache and leading "
                "dense blocks in prefill and decode, which are not built")

    def cache_specs(batch: int, max_len: int):
        _no_cache()
        kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        kv = jax.ShapeDtypeStruct((n_layers, batch, max_len, kh, hd), dtype)
        return {"k": kv, "v": kv, "index": jax.ShapeDtypeStruct((), jnp.int32)}

    def prefill(params, batch):
        _no_cache()
        tokens = batch["tokens"]
        max_len = batch.get("max_len", tokens.shape[1] + (cfg.num_patch_tokens or 0))
        x = _embed_inputs(params, batch, tokens)
        s_total = x.shape[1]

        def body(carry, p):
            x = carry
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            a_out, (k_c, v_c) = attn.self_attention_prefill(
                p["attn"], cfg, h, max_len)
            x = x + a_out
            h = rms_norm(x, p["ln2"])
            if "moe" in p:
                x = x + moe.moe_apply(p["moe"], cfg, h)[0]
            else:
                x = x + mlp_apply(p["mlp"], h, cfg.mlp_type)
            return x, (k_c, v_c)

        x, (k_all, v_all) = scan_layers(_remat(body, cfg), x, params["blocks"],
                                        length=n_layers)
        x = rms_norm(x, params["final_norm"])
        logits = _logits(params, x[:, -1])
        cache = {"k": k_all, "v": v_all,
                 "index": jnp.asarray(s_total, jnp.int32)}
        return logits, cache

    def decode_step(params, cache, token):
        _no_cache()
        x = embed_lookup(params["embed"], token[:, None])  # (B,1,D)
        index = cache["index"]

        def body(x, layer):
            p, k_c, v_c = layer
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            a_out, (k_c, v_c) = attn.self_attention_decode(
                p["attn"], cfg, h, (k_c, v_c), index)
            x = x + a_out
            h = rms_norm(x, p["ln2"])
            if "moe" in p:
                x = x + moe.moe_apply(p["moe"], cfg, h)[0]
            else:
                x = x + mlp_apply(p["mlp"], h, cfg.mlp_type)
            return x, (k_c, v_c)

        x, (k_all, v_all) = scan_layers(
            body, x, (params["blocks"], cache["k"], cache["v"]),
            length=n_layers)
        x = rms_norm(x, params["final_norm"])
        logits = _logits(params, x[:, 0])
        return logits, {"k": k_all, "v": v_all, "index": index + 1}

    def input_specs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        npatch = cfg.num_patch_tokens
        specs: Dict[str, Any] = {}
        if shape.kind == "train":
            specs["tokens"] = jax.ShapeDtypeStruct((b, s - npatch + 1), jnp.int32)
        elif shape.kind == "prefill":
            specs["tokens"] = jax.ShapeDtypeStruct((b, s - npatch), jnp.int32)
        else:  # decode
            specs["token"] = jax.ShapeDtypeStruct((b,), jnp.int32)
            specs["cache"] = cache_specs(b, s)
        if npatch and shape.kind != "decode":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, npatch, cfg.d_model), dtype)
        return specs

    return Model(cfg, init, param_specs, loss, forward, prefill, decode_step,
                 cache_specs, input_specs)


# --------------------------------------------------------------------------- #
# Pure SSM (Mamba2) LM
# --------------------------------------------------------------------------- #
def _build_ssm_lm(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)
    n_layers = cfg.num_layers
    v = cfg.padded_vocab

    def init(key):
        k_emb, k_blocks = jax.random.split(key)
        return {
            "embed": embed_init(k_emb, v, cfg.d_model, dtype),
            "blocks": _stacked_init(
                lambda k: _mamba_block_init(k, cfg, dtype), k_blocks, n_layers),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }

    def param_specs():
        return jax.eval_shape(init, jax.random.key(0))

    def _hidden(params, tokens):
        x = embed_lookup(params["embed"], tokens)
        body = _remat(lambda x, p: _mamba_block(p, cfg, x), cfg)
        x, _ = scan_layers(lambda x, p: (body(x, p), None), x,
                           params["blocks"], length=n_layers)
        return rms_norm(x, params["final_norm"])

    def forward(params, batch):
        return unembed(params["embed"], _hidden(params, batch["tokens"]))

    def loss(params, batch):
        tokens = batch["tokens"]
        x = _hidden(params, tokens[:, :-1])
        l = chunked_xent(params["embed"], x, tokens[:, 1:])
        return l, {"xent": l, "aux": jnp.zeros((), jnp.float32)}

    def cache_specs(batch: int, max_len: int):
        per_layer = mamba2.mamba_state_specs(cfg, batch)
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_layers,) + s.shape, s.dtype), per_layer)
        return {"mamba": stacked, "index": jax.ShapeDtypeStruct((), jnp.int32)}

    def prefill(params, batch):
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens)

        def body(x, p):
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            out, state = mamba2.mamba_prefill(p["mamba"], cfg, h)
            return x + out, state

        x, states = scan_layers(_remat(body, cfg), x, params["blocks"],
                                length=n_layers)
        x = rms_norm(x, params["final_norm"])
        logits = unembed(params["embed"], x[:, -1])
        return logits, {"mamba": states,
                        "index": jnp.asarray(tokens.shape[1], jnp.int32)}

    def decode_step(params, cache, token):
        x = embed_lookup(params["embed"], token[:, None])

        def body(x, layer):
            p, state = layer
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            out, state = mamba2.mamba_decode(p["mamba"], cfg, h, state)
            return x + out, state

        x, states = scan_layers(body, x, (params["blocks"], cache["mamba"]),
                                length=n_layers)
        x = rms_norm(x, params["final_norm"])
        logits = unembed(params["embed"], x[:, 0])
        return logits, {"mamba": states, "index": cache["index"] + 1}

    def input_specs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            return {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32)}
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"token": jax.ShapeDtypeStruct((b,), jnp.int32),
                "cache": cache_specs(b, s)}

    return Model(cfg, init, param_specs, loss, forward, prefill, decode_step,
                 cache_specs, input_specs)


# --------------------------------------------------------------------------- #
# Hybrid (Zamba2): scanned Mamba2 stack + weight-shared attention block
# --------------------------------------------------------------------------- #
def _build_hybrid_lm(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)
    n_layers = cfg.num_layers
    v = cfg.padded_vocab
    kinds = cfg.layer_kinds()
    attn_layers = tuple(i for i, k in enumerate(kinds) if k == "mamba_attn")
    n_attn = len(attn_layers)
    is_attn = jnp.asarray([k == "mamba_attn" for k in kinds], jnp.bool_)

    def init(key):
        k_emb, k_blocks, k_shared = jax.random.split(key, 3)
        return {
            "embed": embed_init(k_emb, v, cfg.d_model, dtype),
            "blocks": _stacked_init(
                lambda k: _mamba_block_init(k, cfg, dtype), k_blocks, n_layers),
            "shared_attn": _attn_block_init(k_shared, cfg, dtype, d_ff=cfg.d_ff),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }

    def param_specs():
        return jax.eval_shape(init, jax.random.key(0))

    def _hidden(params, tokens):
        x = embed_lookup(params["embed"], tokens)
        shared = params["shared_attn"]

        if in_analysis_mode():  # static unroll: exact cost accounting
            for i in range(n_layers):
                p = jax.tree.map(lambda a: a[i], params["blocks"])
                x = _mamba_block(p, cfg, x)
                if kinds[i] == "mamba_attn":
                    x = _shared_attn_block(shared, cfg, x)
            return rms_norm(x, params["final_norm"])

        def body(x, layer):
            p, apply_attn = layer
            x = _mamba_block(p, cfg, x)
            x = jax.lax.cond(apply_attn,
                             lambda x: _shared_attn_block(shared, cfg, x),
                             lambda x: x, x)
            return x

        wrapped = _remat(body, cfg)
        x, _ = jax.lax.scan(lambda x, l: (wrapped(x, l), None), x,
                            (params["blocks"], is_attn))
        return rms_norm(x, params["final_norm"])

    def forward(params, batch):
        return unembed(params["embed"], _hidden(params, batch["tokens"]))

    def loss(params, batch):
        tokens = batch["tokens"]
        x = _hidden(params, tokens[:, :-1])
        l = chunked_xent(params["embed"], x, tokens[:, 1:])
        return l, {"xent": l, "aux": jnp.zeros((), jnp.float32)}

    def cache_specs(batch: int, max_len: int):
        kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        per_layer = mamba2.mamba_state_specs(cfg, batch)
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_layers,) + s.shape, s.dtype), per_layer)
        kv = jax.ShapeDtypeStruct((n_attn, batch, max_len, kh, hd), dtype)
        return {"mamba": stacked, "k": kv, "v": kv,
                "index": jax.ShapeDtypeStruct((), jnp.int32)}

    def _layer_params(params, i):
        return jax.tree.map(lambda a: a[i], params["blocks"])

    def prefill(params, batch):
        tokens = batch["tokens"]
        max_len = batch.get("max_len", tokens.shape[1])
        x = embed_lookup(params["embed"], tokens)
        shared = params["shared_attn"]
        mamba_states, k_list, v_list = [], [], []
        for i in range(n_layers):
            p = _layer_params(params, i)
            h = rms_norm(x, p["ln1"])
            out, st = mamba2.mamba_prefill(p["mamba"], cfg, h)
            x = x + out
            mamba_states.append(st)
            if kinds[i] == "mamba_attn":
                h = rms_norm(x, shared["ln1"])
                a_out, (k_c, v_c) = attn.self_attention_prefill(
                    shared["attn"], cfg, h, max_len)
                x = x + a_out
                h = rms_norm(x, shared["ln2"])
                x = x + mlp_apply(shared["mlp"], h, cfg.mlp_type)
                k_list.append(k_c)
                v_list.append(v_c)
        x = rms_norm(x, params["final_norm"])
        logits = unembed(params["embed"], x[:, -1])
        cache = {
            "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *mamba_states),
            "k": jnp.stack(k_list), "v": jnp.stack(v_list),
            "index": jnp.asarray(tokens.shape[1], jnp.int32),
        }
        return logits, cache

    def decode_step(params, cache, token):
        x = embed_lookup(params["embed"], token[:, None])
        shared = params["shared_attn"]
        index = cache["index"]
        new_states, new_k, new_v = [], [], []
        a_i = 0
        for i in range(n_layers):
            p = _layer_params(params, i)
            st = jax.tree.map(lambda a: a[i], cache["mamba"])
            h = rms_norm(x, p["ln1"])
            out, st = mamba2.mamba_decode(p["mamba"], cfg, h, st)
            x = x + out
            new_states.append(st)
            if kinds[i] == "mamba_attn":
                h = rms_norm(x, shared["ln1"])
                a_out, (k_c, v_c) = attn.self_attention_decode(
                    shared["attn"], cfg, h,
                    (cache["k"][a_i], cache["v"][a_i]), index)
                x = x + a_out
                h = rms_norm(x, shared["ln2"])
                x = x + mlp_apply(shared["mlp"], h, cfg.mlp_type)
                new_k.append(k_c)
                new_v.append(v_c)
                a_i += 1
        x = rms_norm(x, params["final_norm"])
        logits = unembed(params["embed"], x[:, 0])
        cache = {
            "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *new_states),
            "k": jnp.stack(new_k), "v": jnp.stack(new_v),
            "index": index + 1,
        }
        return logits, cache

    def input_specs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            return {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32)}
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"token": jax.ShapeDtypeStruct((b,), jnp.int32),
                "cache": cache_specs(b, s)}

    return Model(cfg, init, param_specs, loss, forward, prefill, decode_step,
                 cache_specs, input_specs)


# --------------------------------------------------------------------------- #
# Encoder-decoder (Whisper): stubbed conv frontend -> frame embeddings
# --------------------------------------------------------------------------- #
def _build_encdec(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)
    n_dec, n_enc = cfg.num_layers, cfg.encoder_layers
    v = cfg.padded_vocab

    def init(key):
        k_emb, k_enc, k_dec = jax.random.split(key, 3)
        return {
            "embed": embed_init(k_emb, v, cfg.d_model, dtype),
            "encoder": _stacked_init(
                lambda k: _encdec_block_init(k, cfg, dtype, cross=False),
                k_enc, n_enc),
            "enc_norm": jnp.zeros((cfg.d_model,), dtype),
            "decoder": _stacked_init(
                lambda k: _encdec_block_init(k, cfg, dtype, cross=True),
                k_dec, n_dec),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }

    def param_specs():
        return jax.eval_shape(init, jax.random.key(0))

    def _encode(params, frames):
        pos = jnp.asarray(sinusoidal_positions(frames.shape[1], cfg.d_model),
                          dtype)
        x = frames.astype(dtype) + pos[None]

        def body(x, p):
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            x = x + attn.self_attention(p["attn"], cfg, h, causal=False, rope=False)
            h = rms_norm(x, p["ln2"])
            return x + mlp_apply(p["mlp"], h, cfg.mlp_type)

        wrapped = _remat(body, cfg)
        x, _ = scan_layers(lambda x, p: (wrapped(x, p), None), x,
                           params["encoder"], length=n_enc)
        return rms_norm(x, params["enc_norm"])

    def _decode_train(params, enc_out, tokens):
        x = embed_lookup(params["embed"], tokens)

        def body(x, p):
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            x = x + attn.self_attention(p["attn"], cfg, h, causal=True)
            h = rms_norm(x, p["ln_cross"])
            kv = attn.cross_kv(p["cross"], cfg, enc_out)
            x = x + attn.cross_attention(p["cross"], cfg, h, kv)
            h = rms_norm(x, p["ln2"])
            return x + mlp_apply(p["mlp"], h, cfg.mlp_type)

        wrapped = _remat(body, cfg)
        x, _ = scan_layers(lambda x, p: (wrapped(x, p), None), x,
                           params["decoder"], length=n_dec)
        return rms_norm(x, params["final_norm"])

    def forward(params, batch):
        enc_out = _encode(params, batch["frames"])
        x = _decode_train(params, enc_out, batch["tokens"])
        return unembed(params["embed"], x)

    def loss(params, batch):
        tokens = batch["tokens"]
        enc_out = _encode(params, batch["frames"])
        x = _decode_train(params, enc_out, tokens[:, :-1])
        l = chunked_xent(params["embed"], x, tokens[:, 1:])
        return l, {"xent": l, "aux": jnp.zeros((), jnp.float32)}

    def cache_specs(batch: int, max_len: int):
        kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        kv = jax.ShapeDtypeStruct((n_dec, batch, max_len, kh, hd), dtype)
        cross = jax.ShapeDtypeStruct((n_dec, batch, cfg.encoder_seq, kh, hd), dtype)
        return {"k": kv, "v": kv, "cross_k": cross, "cross_v": cross,
                "index": jax.ShapeDtypeStruct((), jnp.int32)}

    def prefill(params, batch):
        tokens = batch["tokens"]
        max_len = batch.get("max_len", tokens.shape[1])
        enc_out = _encode(params, batch["frames"])
        x = embed_lookup(params["embed"], tokens)

        def body(x, p):
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            a_out, (k_c, v_c) = attn.self_attention_prefill(
                p["attn"], cfg, h, max_len)
            x = x + a_out
            h = rms_norm(x, p["ln_cross"])
            ckv = attn.cross_kv(p["cross"], cfg, enc_out)
            x = x + attn.cross_attention(p["cross"], cfg, h, ckv)
            h = rms_norm(x, p["ln2"])
            x = x + mlp_apply(p["mlp"], h, cfg.mlp_type)
            return x, (k_c, v_c, ckv[0], ckv[1])

        x, (k_all, v_all, ck, cv) = scan_layers(_remat(body, cfg), x,
                                                params["decoder"], length=n_dec)
        x = rms_norm(x, params["final_norm"])
        logits = unembed(params["embed"], x[:, -1])
        return logits, {"k": k_all, "v": v_all, "cross_k": ck, "cross_v": cv,
                        "index": jnp.asarray(tokens.shape[1], jnp.int32)}

    def decode_step(params, cache, token):
        x = embed_lookup(params["embed"], token[:, None])
        index = cache["index"]

        def body(x, layer):
            p, k_c, v_c, ck, cv = layer
            p = unshard_layer_params(p, cfg)
            h = rms_norm(x, p["ln1"])
            a_out, (k_c, v_c) = attn.self_attention_decode(
                p["attn"], cfg, h, (k_c, v_c), index)
            x = x + a_out
            h = rms_norm(x, p["ln_cross"])
            x = x + attn.cross_attention(p["cross"], cfg, h, (ck, cv))
            h = rms_norm(x, p["ln2"])
            x = x + mlp_apply(p["mlp"], h, cfg.mlp_type)
            return x, (k_c, v_c)

        x, (k_all, v_all) = scan_layers(
            body, x, (params["decoder"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]), length=n_dec)
        x = rms_norm(x, params["final_norm"])
        logits = unembed(params["embed"], x[:, 0])
        return logits, {"k": k_all, "v": v_all, "cross_k": cache["cross_k"],
                        "cross_v": cache["cross_v"], "index": index + 1}

    def input_specs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        frames = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model), dtype)
        if shape.kind == "train":
            return {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32),
                    "frames": frames}
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                    "frames": frames}
        return {"token": jax.ShapeDtypeStruct((b,), jnp.int32),
                "cache": cache_specs(b, s)}

    return Model(cfg, init, param_specs, loss, forward, prefill, decode_step,
                 cache_specs, input_specs)


# --------------------------------------------------------------------------- #
# Parameter accounting (used by roofline MODEL_FLOPS and the checkpoint razor)
# --------------------------------------------------------------------------- #
def param_count(cfg: ArchConfig) -> int:
    specs = build_model(cfg).param_specs()
    return int(sum(np.prod(s.shape) for s in jax.tree.leaves(specs)))


def active_param_count(cfg: ArchConfig) -> int:
    """Params touched per token (MoE: top_k of routed experts + shared; of
    a share of the experts, top_k at the share's expected rate)."""
    total = param_count(cfg)
    if not cfg.is_moe:
        return total
    n_moe = cfg.num_layers - cfg.first_k_dense
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    share = cfg.experts_held / cfg.num_experts if cfg.experts_held else 1.0
    routed_all = n_moe * cfg.held_experts * per_expert
    routed_active = n_moe * cfg.top_k * share * per_expert
    return int(total - routed_all + routed_active)
