"""The plain reference's training: AdamW steps in float32 at the highest
matmul precision, over a configuration's own reference loss, and
the numbers that a run of the program is compared on.

Nothing here imports the program. A configuration's reference module
(`configs/<name>.py`) gives `init_params(arch, key)`, the weights both sides
start from in the program's parameter layout, `loss(params, tokens, arch,
mm)`, and `flops_per_token(arch, seq_len)`.
"""
from __future__ import annotations

import functools
import json
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def exact_mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x, dtype):
    """Quantise to a float8 type with one scale per tensor, and back."""
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(jnp.max(jnp.abs(x)),
                                                      1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8_mm(a, b):
    """The control: a weight matmul `a (..., k) @ b (k, n)` in float8, the
    usual recipe: operands in e4m3 and the incoming gradient in e5m2, each
    scaled per tensor, products accumulated in float32."""
    return fp8_mm_fwd(a, b)[0]


def fp8_mm_fwd(a, b):
    qa, qb = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def fp8_mm_bwd(res, g):
    qa, qb = res
    qg = _fp8(g, jnp.float8_e5m2)
    da = jnp.matmul(qg, qb.T, precision=HIGHEST)
    db = jnp.matmul(qa.reshape(-1, qa.shape[-1]).T,
                    qg.reshape(-1, qg.shape[-1]), precision=HIGHEST)
    return da, db


fp8_mm.defvjp(fp8_mm_fwd, fp8_mm_bwd)


MATMULS: Dict[str, Callable] = {"float32": exact_mm, "fp8": fp8_mm}


def rms_norm(x, scale, eps: float = 1e-6):
    """RMSNorm with the scale stored as an offset from one."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def learning_rate(step: int, hp: Dict[str, float]) -> float:
    """Linear warm-up, then cosine decay to a tenth of the peak."""
    lr, warm, total = hp["lr"], hp["warmup_steps"], hp["total_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))


@dataclass
class Readings:
    """What the first training steps give, on either side: each step's
    loss, each leaf's norm of the first (clipped) gradient as the optimizer
    got it and its sketch, and each leaf's norm of the parameters' change
    after step k, for each k in `changes`."""
    losses: List[float]
    grad_norms: np.ndarray
    grad_sketch: np.ndarray
    changes: Dict[int, np.ndarray]


SKETCH = 16                       # random +-1 projections of each leaf


def sketch(tree) -> jax.Array:
    """(leaves, SKETCH): each leaf's dot products with SKETCH fixed
    pseudo-random sign vectors (a hash of the element's index), so that
    ||sketch(a) - sketch(b)|| / sqrt(SKETCH) estimates ||a - b|| leaf by
    leaf without either side keeping the other's arrays."""
    rows = []
    for x in jax.tree.leaves(tree):
        x = x.astype(jnp.float32).reshape(-1)
        idx = jnp.arange(x.size, dtype=jnp.uint32)
        cols = []
        for i in range(SKETCH):
            h = idx * jnp.uint32(0x9E3779B1) \
                + jnp.uint32((i * 0x85EBCA77) % 2 ** 32)
            h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
            h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
            h = h ^ (h >> 16)
            cols.append(jnp.sum(jnp.where(h >> 31 == 1, x, -x)))
        rows.append(jnp.stack(cols))
    return jnp.stack(rows)


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@functools.lru_cache(maxsize=None)
def _programs(ref, arch_json: str, hp_json: str, rows: int, matmul: str,
              half_batch: bool):
    """The jitted pieces of `train`, built once for each setting so that
    further seeds reuse their compiled programs."""
    arch, hp = json.loads(arch_json), json.loads(hp_json)
    mm = MATMULS[matmul]
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]

    @jax.jit
    def loss_grad(p, tokens):
        if half_batch:
            tokens = tokens[:tokens.shape[0] // 2]
        blocks = tokens.reshape(-1, min(rows, tokens.shape[0]),
                                tokens.shape[1])
        vg = jax.value_and_grad(lambda q, t: ref.loss(q, t, arch, mm))
        if blocks.shape[0] == 1:
            return vg(p, blocks[0])

        def body(acc, blk):
            l, g = vg(p, blk)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None
        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, p))
        (l, g), _ = jax.lax.scan(body, zero, blocks)
        n = blocks.shape[0]
        return l / n, jax.tree.map(lambda x: x / n, g)

    @jax.jit
    def init(k):
        return jax.tree.map(lambda x: x.astype(jnp.float32),
                            ref.init_params(arch, k))

    @jax.jit
    def change(p, k):
        p0 = ref.init_params(arch, k)
        return leaf_norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), p, p0))

    def update(p, m, v, g, t, lr):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, hp["grad_clip"]
                                      / jnp.maximum(gnorm, 1e-9)), g)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                         v, g)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * (
                (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t))
                                        + eps)
                + hp["weight_decay"] * p_), p, m, v)
        return p, m, v, leaf_norms(g), sketch(g)

    return loss_grad, init, change, jax.jit(update, donate_argnums=(0, 1, 2))


def train(ref, arch: Dict[str, Any], hp: Dict[str, float], key,
          batches: Sequence[np.ndarray], *, rows: int,
          changes: Sequence[int] = (3,), matmul: str = "float32",
          half_batch: bool = False) -> Readings:
    """An AdamW step of the reference from `init_params(arch, key)` on each
    of `batches`, `rows` rows at a time, reading the parameters' change
    after each step in `changes`. `matmul="fp8"` is the control;
    `half_batch` takes the loss over the first half of each batch only (a
    planted fault)."""
    loss_grad, init, change, update = _programs(
        ref, json.dumps(arch, sort_keys=True), json.dumps(hp, sort_keys=True),
        rows, matmul, half_batch)
    with jax.default_matmul_precision("highest"):
        p = init(key)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, first, moved = [], None, {}
        for step, tokens in enumerate(batches):
            loss, g = loss_grad(p, jnp.asarray(tokens))
            p, m, v, gn, gs = update(p, m, v, g, jnp.float32(step + 1),
                                     jnp.float32(learning_rate(step, hp)))
            del g
            losses.append(float(loss))
            if first is None:
                first = (np.asarray(gn), np.asarray(gs))
            if step + 1 in changes:
                moved[step + 1] = np.asarray(change(p, key))
    return Readings(losses, first[0], first[1], moved)


def compare(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The numbers a run is judged on: each step's loss relative to the
    reference's, by the worst step (`loss_gap`); the gap of norms of the
    first gradient
    (`grad_gap`) and of the change after three steps (`change_gap`) or after
    step k > 3 (`change_gap.k`); and `grad_dev`, the first gradient's
    distance from the reference's by the worst leaf's sketch, relative to
    that leaf's or the median leaf's sketch. Each gap of norms is taken by the
    worst leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone under Adam and
    are left out of the change."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses)]

    def worst(p, r, keep):
        p, r = p[keep], r[keep]
        den = np.maximum(r, np.median(r))
        return float(np.max(np.abs(p - r) / den))

    moves = ref.grad_norms >= 1e-3 * np.median(ref.grad_norms)
    every = np.ones_like(moves)
    dev = np.linalg.norm(prog.grad_sketch - ref.grad_sketch, axis=1)
    size = np.linalg.norm(ref.grad_sketch, axis=1)
    return {"loss_gap": max(gaps),
            "grad_gap": worst(prog.grad_norms, ref.grad_norms, every),
            "grad_dev": float(np.max(dev / np.maximum(size,
                                                      np.median(size)))),
            **{"change_gap" + ("" if k == 3 else f".{k}"):
               worst(prog.changes[k], ref.changes[k], moves)
               for k in prog.changes}}
