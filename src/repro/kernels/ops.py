"""jit'd public wrappers for the Pallas kernels — the one place that picks
interpret mode: off-TPU the kernel body executes in Python via the Pallas
interpreter (correctness path); on TPU backends it compiles to Mosaic. The
kernel modules take ``interpret`` from their caller, with no default."""
from __future__ import annotations

import functools

import jax

from repro.kernels import decode_attn as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 256):
    return _fa.flash_attention(q, k, v, causal, bq, bk, _default_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, a, b_mat, c_mat, *, chunk: int = 256):
    return _ssd.ssd(x, dt, a, b_mat, c_mat, chunk=chunk,
                    interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("bt",))
def decode_attention(q, k_cache, v_cache, cur_len, *, bt: int = 512):
    return _dec.decode_attention(q, k_cache, v_cache, cur_len, bt=bt,
                                 interpret=_default_interpret())
